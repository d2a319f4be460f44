"""Local QLoRA training steps of the ``models/`` trunk
(``Model.train_step``), jitted once.

Set-up builds the model from the registry entry the configuration file
names, with the file's values, makes the weights on the device from the
seed (``Model.init_params``, which NF4-quantizes the backbone), and
drives the one jitted step through its first three steps; the window
keeps stepping the same program and state until ``--seconds`` have
passed. Each step takes the next batch of a device-resident non-IID
token stream, the clients in turn. ``train_tokens_per_s`` is all tokens
trained in the window over the window.

Correctness: the plain reference (``bench/lib/ref_trunk.py``) follows
the first three steps; each step's loss, the first gradient as Adam's
first moment holds it after one step, and the change of the trainables
over the three steps are compared (``bench/lib/compare.py``).
"""
from __future__ import annotations

import gc
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import compare, flops, gen, harness
from bench.lib import ref_trunk
from bench.lib.harness import Check, Outcome, span

COMPARED = 3
B1 = 0.9          # Adam's first-moment decay, as the program's optimizer


def stream(traffic, seed):
    return gen.token_stream(seed, vocab=traffic["vocab"],
                            n_clients=traffic["n_clients"],
                            docs=traffic["docs_per_client"],
                            length=traffic["seq"] + 1)


def batch_of(toks, i, traffic):
    """Step ``i``'s rows: client ``i mod n``, the next ``batch`` of its
    documents (wrapping around)."""
    C, B = traffic["n_clients"], traffic["batch"]
    per = traffic["docs_per_client"] // B
    c = i % C
    j = (i // C) % per
    return jax.lax.dynamic_slice(
        toks, (c, j * B, 0), (1, B, toks.shape[-1]))[0]


def build(config, traffic, seed):
    from repro.configs import get_config
    from repro.core import optim
    from repro.models import build_model
    cfg = get_config(config["registry"]).replace(**config["model"])
    model = build_model(cfg)
    lr = traffic["lr"]
    with span("bench.setup.weights"):
        params = jax.jit(model.init_params)(gen.key(seed, 20))
        frozen, tr = params["frozen"], params["trainable"]
        opt = jax.jit(optim.adam_init)(tr)
    with span("bench.setup.data"):
        toks = stream(traffic, seed)

    def step(frozen, tr, opt, toks, i):
        t = batch_of(toks, i, traffic)
        b = {"tokens": t[:, :-1], "labels": t[:, 1:],
             "mask": jnp.ones(t[:, 1:].shape, jnp.float32)}
        return model.train_step(frozen, tr, opt, b, lr=lr)

    return jax.jit(step, donate_argnums=(1, 2)), frozen, tr, opt, toks


def first_steps(step, frozen, tr, opt, toks, n=COMPARED):
    """Drive the step through its first ``n`` steps; returns (trainable,
    optimizer state, host snapshots of the trainables before and after,
    the first gradient from the optimizer's state, losses)."""
    snaps = [jax.tree.map(np.asarray, tr)]
    losses, g1 = [], None
    for i in range(n):
        with span("bench.step"):
            tr, opt, m = step(frozen, tr, opt, toks, np.int32(i))
        losses.append(float(m["loss"]))
        snaps.append(jax.tree.map(np.asarray, tr))
        if i == 0:
            g1 = jax.tree.map(lambda mu: np.asarray(mu) / (1 - B1), opt.mu)
    return tr, opt, snaps, g1, np.asarray(losses)


def reference(config, traffic, seed, *, n=COMPARED, mode="f32", rows=None):
    """The first ``n`` steps by the plain reference; returns (snapshots,
    first clipped gradient, each leaf's largest gradient norm over the
    steps, losses)."""
    m = config["model"]
    with jax.default_matmul_precision("highest"):
        frozen, tr = jax.jit(partial(ref_trunk.init, m=m))(
            gen.key(seed, 20))
        toks = stream(traffic, seed)
        step = ref_trunk.make_step(m, lr=traffic["lr"], mode=mode,
                                   rows=rows)
        opt = ref_trunk.ref_clip.adam_init(tr)
        snaps = [jax.tree.map(np.asarray, tr)]
        losses, g1, gmax = [], None, {}
        for i in range(n):
            t = batch_of(toks, i, traffic)
            tr, opt, loss, g = step(tr, opt, frozen, t)
            losses.append(float(loss))
            snaps.append(jax.tree.map(np.asarray, tr))
            if i == 0:
                g1 = jax.tree.map(np.asarray, g)
            for k, v in compare.leaf_norms(g).items():
                gmax[k] = max(gmax.get(k, 0.0), v)
    return snaps, g1, gmax, np.asarray(losses)


def numbers(p_snaps, p_g1, p_losses, r_snaps, r_g1, r_gmax, r_losses):
    keep = compare.kept_leaves(r_gmax)
    g_p, g_r = compare.leaf_norms(p_g1), compare.leaf_norms(r_g1)
    ug, ug_at = compare.norm_gap(g_p, g_r, keep)
    c_p = compare.leaf_norms(compare.diff(p_snaps[-1], p_snaps[0]))
    c_r = compare.leaf_norms(compare.diff(r_snaps[-1], r_snaps[0]))
    cg, cg_at = compare.norm_gap(c_p, c_r, keep)
    return {"loss_gap": compare.loss_gap(p_losses, r_losses),
            "grad_gap": ug, "change_gap": cg,
            "grad_gap_at": ug_at, "change_gap_at": cg_at,
            "grad_leaf_gaps": compare.leaf_gaps(g_p, g_r, keep),
            "change_leaf_gaps": compare.leaf_gaps(c_p, c_r, keep),
            "excluded": sorted(set(r_gmax) - set(keep))}


def readings(config, traffic, seed, *, program, control,
             kinds=("control", "half_batch")):
    """{kind: numbers} for one seed against the float32 reference: the
    program's first steps (``program``), and with ``control`` the
    reference with float8 products (``control``) and with half of each
    batch left out (``half_batch``) in the program's place."""
    r_snaps, r_g1, r_gmax, r_losses = ref_out = reference(config, traffic,
                                                          seed)
    out = {}
    if program:
        step, frozen, tr, opt, toks = build(config, traffic, seed)
        _, _, snaps, g1, losses = first_steps(step, frozen, tr, opt, toks)
        del step, frozen, tr, opt, toks
        gc.collect()
        out["program"] = numbers(snaps, g1, losses, *ref_out)
    if control:
        for kind, kw in (("control", {"mode": "fp8"}),
                         ("half_batch", {"rows": traffic["batch"] // 2})):
            if kind in kinds:
                s, g1, _, l = reference(config, traffic, seed, **kw)
                out[kind] = numbers(s, g1, l, r_snaps, r_g1, r_gmax,
                                    r_losses)
    return out


def kernel_layer(config, traffic):
    m = config["model"]
    x_bytes = jnp.dtype(m["dtype"]).itemsize
    fwd, bwd = flops.trunk_kernel_calls(m, traffic["batch"], traffic["seq"],
                                        x_bytes)
    return fwd, bwd


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
        in_window.append(m["loss"])
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
    n = i - COMPARED
    bad = sum(not np.isfinite(float(l)) for l in in_window)
    bad += int(not np.all(np.isfinite(losses)))

    del step, frozen, tr, opt, toks, m, prev, in_window
    gc.collect()
    t_ref = time.perf_counter()
    r_snaps, r_g1, r_gmax, r_losses = reference(config, traffic, ctx.seed)
    harness.log(f"reference: {time.perf_counter() - t_ref:.3f} s; losses "
                f"{losses} (program), {r_losses} (reference)")
    got = numbers(snaps, g1, losses, r_snaps, r_g1, r_gmax, r_losses)
    harness.log(f"worst leaves: grad {got['grad_gap_at']}, change "
                f"{got['change_gap_at']}; left out (reference gradient "
                f"under {compare.EXCLUDE_BELOW} of the median leaf's): "
                f"{got['excluded']}")
    checks = [Check(k, got[k], v) for k, v in traffic["limits"].items()]
    tokens = traffic["batch"] * traffic["seq"]
    fwd, bwd = kernel_layer(config, traffic)
    return Outcome(
        attempted=n + COMPARED, failed=bad,
        metrics={"train_tokens_per_s": n * tokens / window},
        checks=checks,
        layer={"work": n,
               "flops_per_work": flops.trunk_step(
                   config["model"], traffic["batch"], traffic["seq"]),
               "kernels": {"lora_matmul": fwd, "quant_matmul_t": bwd}})
