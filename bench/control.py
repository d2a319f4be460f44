"""Readings that set a training cell's correctness limits, on the chip.

    python bench/control.py --workload <cell> --seeds 11,12,13 \
        --control-seeds 11,12,13 [--out <file.jsonl>]

Each cell's driver takes the readings (its ``readings`` function). For
each seed of ``--seeds`` the program's first compared steps are set up
as a timed run sets them up and compared with the plain reference: the
lower reading. For each seed of ``--control-seeds`` two more runs of the
reference take the program's place and are compared with it the same
way: the control, its products computed one precision step below the
configuration's bfloat16 operands (float8 e4m3), and the planted fault
"half of the batch left out, the mean taken over the rest". A step that
returns its state unchanged reads 1 on the change gap by construction
and needs no run. The benchmark's own runs never run this; it prints
one JSON line per reading.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--kinds", default="control,half_batch",
                    help="which of the readings beside the program's to "
                         "take for --control-seeds")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import run as run_lib
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU found", file=sys.stderr)
        return 2
    from repro.launch import compile_cache
    compile_cache.enable()
    spec = run_lib.read_json("BENCHMARK.json")
    _, config, traffic = run_lib.cell(spec, args.workload)
    drv = run_lib.load_module(os.path.join(
        BENCH, "drivers", traffic["driver"] + ".py"),
        "bench_driver_" + traffic["driver"])
    seeds = [int(s) for s in args.seeds.split(",") if s]
    cseeds = [int(s) for s in args.control_seeds.split(",") if s]
    out = open(args.out, "a") if args.out else None

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    for seed in sorted(set(seeds) | set(cseeds)):
        t0 = time.perf_counter()
        rec = drv.readings(config, traffic, seed, program=seed in seeds,
                           control=seed in cseeds,
                           kinds=args.kinds.split(","))
        for kind, nums in rec.items():
            emit({"workload": args.workload, "seed": seed, "kind": kind,
                  **nums})
        print(f"seed {seed}: {time.perf_counter() - t0:.1f} s",
              file=sys.stderr, flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
