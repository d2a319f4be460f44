"""Run one cell of the chip benchmark and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration file and its traffic file are found by name
through ``BENCHMARK.json``; the traffic file names the driver
(``bench/drivers/<driver>.py``), and each per-layer metric is read by
``bench/metrics/<metric>.py``. A new cell or metric is a new file and an
entry, never an edit.

The run needs a TPU and the chips its cell asks for; without them it
exits non-zero and prints no result. Its last line on standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics, or with ``--trace 1`` its per-layer
ones), ``device``, with ``--trace 1`` a ``breakdown``, and last the
numbers compared with the reference beside their limits (``checks``),
which also close standard error.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

T_START = time.perf_counter()
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(rel: str) -> dict:
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def cell(spec: dict, workload: str):
    """(workload entry, configuration, traffic) of a cell, by name."""
    by_name = {w["name"]: w for w in spec["workloads"]}
    if workload not in by_name:
        raise SystemExit(f"unknown workload {workload!r}; cells: "
                         f"{sorted(by_name)}")
    w = by_name[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    return w, read_json(conf["file"]), read_json(
        os.path.join("bench", "traffic", w["traffic"] + ".json"))


def applies(metric: dict, workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = read_json("BENCHMARK.json")
    w, config, traffic = cell(spec, args.workload)
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)

    import jax
    from bench.lib import harness, peaks

    devs = jax.devices()
    print(f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
          f"count={len(devs)}", flush=True)
    if devs[0].platform != "tpu":
        harness.log("bench: no TPU found; the benchmark has no CPU fallback")
        return 2
    if len(devs) < w["chips"]:
        harness.log(f"bench: {args.workload} needs {w['chips']} chips, "
                    f"found {len(devs)}")
        return 2
    pk = peaks.peaks(devs[0].device_kind)

    from repro.launch import compile_cache
    cache_dir = compile_cache.enable()
    harness.log(f"compile cache: {cache_dir}")
    return run_cell(args, w, config, traffic, spec, pk)


def run_cell(args, w, config, traffic, spec, pk) -> int:
    """Everything after the chip check: drive the cell, read its metrics,
    print the result. Tests call this directly on the CPU."""
    from bench.lib import harness
    from bench.lib import trace as trace_lib
    ledger = harness.CompileLedger()
    tracer = trace_lib.Tracer() if args.trace else None
    ctx = harness.Context(config=config, traffic=traffic, seed=args.seed,
                          seconds=args.seconds, t_start=T_START,
                          ledger=ledger, tracer=tracer)
    driver = load_module(os.path.join(BENCH, "drivers",
                                      traffic["driver"] + ".py"),
                         "bench_driver_" + traffic["driver"])
    out = driver.run(ctx)
    if ctx.window_compiles:
        harness.log(f"WARNING: {ctx.window_compiles} compiles inside the "
                    "measured window")
    correct = all(c.ok for c in out.checks)
    device = harness.device_info(w["chips"])
    device["memory_peak_bytes"] = int(ctx.memory_peak_bytes or 0)
    metrics, breakdown = {}, None
    if args.trace:
        tr = trace_lib.load(tracer.dir, w["chips"])
        for line in tr.planes:
            harness.log(f"trace plane: {line}")
        for line in trace_lib.op_texts(tr):
            harness.log(f"trace op: {line}")
        tracer.close()
        device["busy_s"] = trace_lib.busy_s(tr)
        device["window_s"] = tr.window_s
        breakdown = trace_lib.breakdown(tr)
        from bench.lib import flops
        for kernel, calls in out.layer.get("kernels", {}).items():
            harness.log(f"{kernel}: " + "; ".join(
                f"{c['flops'] / c['bytes']:.0f} FLOP/B, {flops.bound(c, pk)}"
                f"-bound" for c in calls))
        reading = harness.Reading(trace=tr, layer=out.layer, peaks=pk,
                                  config=config, traffic=traffic)
        for m in spec["per_layer"]:
            if not applies(m, args.workload):
                continue
            reader = load_module(os.path.join(
                BENCH, "metrics", m["name"] + ".py"),
                "bench_metric_" + m["name"].replace(".", "_"))
            v = reader.read(reading)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in spec["end_to_end"]:
            if not applies(m, args.workload):
                continue
            v = ctx.setup_s if m["name"] == "setup_s" else \
                out.metrics[m["name"]]
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    harness.log(f"attempted {out.attempted}, failed {out.failed}, "
                f"correct {correct}")
    for c in out.checks:
        harness.log(f"check {c.name}: {c.value!r} (limit {c.limit!r})"
                    f"{'' if c.ok else '  FAILED'}")
    print(harness.result_line(correct=correct, outcome=out,
                              metrics=metrics, device=device,
                              breakdown=breakdown), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
