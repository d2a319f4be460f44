"""Local QLoRA training steps of Jamba2-Mini's layers through the
``models/`` period stack (``Model.train_step``), jitted once.

Set-up, window and comparison are the trunk cell's
(``bench/drivers/trunk_step.py``, whose helpers this driver imports):
the model from the registry entry the configuration file names, with the
file's values, weights made on the device from the seed
(``Model.init_params``, which NF4-quantizes each layer and expert as it
makes it), the first three steps driven and kept for the comparison, then
the same program and state stepped until ``--seconds`` have passed, one
2,048-token document of the clients in turn a step. Each step's routing
statistics (``moe_load_max``, ``moe_pad_rows``) are logged.

Correctness: the plain reference (``bench/lib/ref_jamba.py``) follows the
first three steps; each step's loss, the first gradient as Adam's first
moment holds it, and the trainables' change over the three steps are
compared (``bench/lib/compare.py``).
"""
from __future__ import annotations

import gc
import time
from functools import partial

import jax
import numpy as np

from bench.drivers import trunk_step
from bench.lib import compare, flops_jamba, gen, harness, ref_jamba
from bench.lib.harness import Check, Outcome, span

COMPARED = trunk_step.COMPARED
STATS = ("moe_load_max", "moe_pad_rows")


def _log_stats(label, ms):
    harness.log(f"{label}: " + "; ".join(
        " ".join(f"{n} {float(m[n]):.6g}" for n in STATS) for m in ms))


def build(config, traffic, seed):
    """The trunk's jitted step and state, after checking that the
    registry's layer pattern is the one the configuration file states."""
    from repro.configs import get_config
    cfg = get_config(config["registry"]).replace(**config["model"])
    want = ref_jamba.sizes(config)["kinds"]
    got = [("attn" if k == "attn" else "mamba",
            "moe" if cfg.expert_layer(j) else "mlp")
           for j, k in enumerate(cfg.layer_kinds()[:cfg.period])]
    if got != want:
        raise ValueError(f"registry pattern {got} != the file's {want}")
    return trunk_step.build(config, traffic, seed)


def first_steps(step, frozen, tr, opt, toks, n=COMPARED):
    """:func:`trunk_step.first_steps`, logging each step's routing."""
    snaps = [jax.tree.map(np.asarray, tr)]
    losses, g1, ms = [], None, []
    for i in range(n):
        with span("bench.step"):
            tr, opt, m = step(frozen, tr, opt, toks, np.int32(i))
        losses.append(float(m["loss"]))
        ms.append(m)
        snaps.append(jax.tree.map(np.asarray, tr))
        if i == 0:
            g1 = jax.tree.map(lambda mu: np.asarray(mu) / (1 - trunk_step.B1),
                              opt.mu)
    _log_stats("compared steps", ms)
    return tr, opt, snaps, g1, np.asarray(losses)


def reference(config, traffic, seed, *, n=COMPARED, mode="f32",
              half=False):
    """The first ``n`` steps by the plain reference; returns (snapshots,
    first clipped gradient, each leaf's largest gradient norm over the
    steps, losses)."""
    with jax.default_matmul_precision("highest"):
        frozen, tr = jax.jit(partial(ref_jamba.init, config=config))(
            gen.key(seed, 20))
        toks = trunk_step.stream(traffic, seed)
        step = ref_jamba.make_step(config, lr=traffic["lr"], mode=mode,
                                   half=half)
        opt = ref_jamba.ref_clip.adam_init(tr)
        snaps = [jax.tree.map(np.asarray, tr)]
        losses, g1, gmax = [], None, {}
        for i in range(n):
            t = trunk_step.batch_of(toks, i, traffic)
            tr, opt, loss, g = step(tr, opt, frozen, t)
            losses.append(float(loss))
            snaps.append(jax.tree.map(np.asarray, tr))
            if i == 0:
                g1 = jax.tree.map(np.asarray, g)
            for k, v in compare.leaf_norms(g).items():
                gmax[k] = max(gmax.get(k, 0.0), v)
    return snaps, g1, gmax, np.asarray(losses)


def readings(config, traffic, seed, *, program, control,
             kinds=("control", "half_batch")):
    """{kind: numbers} for one seed against the float32 reference: the
    program's first steps (``program``), and with ``control`` the
    reference with float8 products (``control``) and with the second
    half of each document's tokens left out (``half_batch``: the batch
    is one sequence) in the program's place."""
    r_snaps, r_g1, r_gmax, r_losses = ref_out = reference(config, traffic,
                                                          seed)
    out = {}
    if program:
        step, frozen, tr, opt, toks = build(config, traffic, seed)
        _, _, snaps, g1, losses = first_steps(step, frozen, tr, opt, toks)
        del step, frozen, tr, opt, toks
        gc.collect()
        out["program"] = trunk_step.numbers(snaps, g1, losses, *ref_out)
    if control:
        for kind, kw in (("control", {"mode": "fp8"}),
                         ("half_batch", {"half": True})):
            if kind in kinds:
                s, g1, _, l = reference(config, traffic, seed, **kw)
                out[kind] = trunk_step.numbers(s, g1, l, r_snaps, r_g1,
                                               r_gmax, r_losses)
    return out


def run(ctx) -> Outcome:
    traffic, config = ctx.traffic, ctx.config
    step, frozen, tr, opt, toks = build(config, traffic, ctx.seed)
    tr, opt, snaps, g1, losses = first_steps(step, frozen, tr, opt, toks)

    in_window, marks = [], []
    t0 = ctx.open_window()
    i, prev = COMPARED, None
    while True:
        with span("bench.step"):
            tr, opt, m = step(frozen, tr, opt, toks, np.int32(i))
        i += 1
        in_window.append(m)
        if prev is not None:
            with span("bench.block"):
                prev.block_until_ready()
            marks.append(time.perf_counter())
        prev = m["loss"]
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    with span("bench.block"):
        jax.block_until_ready((tr, opt))
    window = ctx.close_window()
    gaps = np.diff(marks) if len(marks) > 1 else np.zeros(1)
    harness.log(f"step intervals in the window: min {gaps.min():.4f} s, "
                f"median {np.median(gaps):.4f} s, max {gaps.max():.4f} s")
    _log_stats("window steps", in_window)
    n = i - COMPARED
    bad = sum(not np.isfinite(float(m["loss"])) for m in in_window)
    bad += int(not np.all(np.isfinite(losses)))

    del step, frozen, tr, opt, toks, m, prev, in_window
    gc.collect()
    t_ref = time.perf_counter()
    r_snaps, r_g1, r_gmax, r_losses = reference(config, traffic, ctx.seed)
    harness.log(f"reference: {time.perf_counter() - t_ref:.3f} s; losses "
                f"{losses} (program), {r_losses} (reference)")
    got = trunk_step.numbers(snaps, g1, losses, r_snaps, r_g1, r_gmax,
                             r_losses)
    harness.log(f"worst leaves: grad {got['grad_gap_at']}, change "
                f"{got['change_gap_at']}; left out (reference gradient "
                f"under {compare.EXCLUDE_BELOW} of the median leaf's): "
                f"{got['excluded']}")
    checks = [Check(k, got[k], v) for k, v in traffic["limits"].items()]
    B, S = traffic["batch"], traffic["seq"]
    m = config["model"]
    period = ref_jamba.sizes(config)["kinds"]
    kinds = [period[j % len(period)] for j in range(m["n_layers"])]
    gmm_fwd, gmm_dx = flops_jamba.gmm_calls(m, B, S)
    scan_fwd, scan_bwd = flops_jamba.scan_calls(m, B, S)
    lora_fwd, lora_dx = flops_jamba.lora_calls(
        m, period, B, S, x_bytes=jax.numpy.dtype(m["dtype"]).itemsize)
    return Outcome(
        attempted=n + COMPARED, failed=bad,
        metrics={"train_tokens_per_s": n * B * S / window},
        checks=checks,
        layer={"work": n,
               "flops_per_work": flops_jamba.step(m, kinds, B, S),
               "kernels": {"moe_gmm_fwd": gmm_fwd, "moe_gmm_dx": gmm_dx,
                           "ssm_scan_fwd": scan_fwd,
                           "ssm_scan_bwd": scan_bwd,
                           "lora_matmul": lora_fwd,
                           "quant_matmul_t": lora_dx}})
