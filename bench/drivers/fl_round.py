"""Federated rounds of the CLIP cohort engine (``CohortEngine.run_round``).

Set-up builds one engine over the cell's clients, as
``repro.fl.simulator.run_federated`` does at the configuration's widths,
and drives it through its first two rounds; the window then commits
rounds on the same engine until ``--seconds`` have passed, one round in
flight ahead of the host. ``round_s`` is the window over the rounds
committed in it.

Correctness: the plain reference (``bench/lib/ref_clip.py``) replays the
first two rounds from the seed (two, not three, to keep it near the
window's length), and the program's per-client losses, its first
aggregated update and its change over the two rounds are compared with
it (``bench/lib/compare.py``): as gaps of norms, and the first update
element by element, since Adam's per-element scaling keeps a leaf's
update norm the same at a lower precision.
"""
from __future__ import annotations

import gc
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import compare, flops, gen, harness
from bench.lib import ref_clip as ref
from bench.lib.harness import Check, Outcome, span

COMPARED = 2
MAX_ROUNDS = 100_000
STAGE_ROWS = 128


def _arm(traffic):
    """The program's strategy for the cell's arm, held to what the
    traffic file states of it."""
    from repro.fl.strategies import STRATEGIES
    strat = STRATEGIES[traffic["arm"]["name"]]
    a = traffic["arm"]
    got = {"use_lora": strat.use_lora, "backbone_bits": strat.backbone_bits,
           "comm_bits": strat.comm_bits}
    want = {k: a[k] for k in got}
    if got != want:
        raise ValueError(f"arm {a['name']}: the program runs {got}, the "
                         f"traffic file states {want}")
    return strat


def data(traffic, seed):
    """Labels (host), images (device) and the fixed partition."""
    labels = gen.labels_for(traffic["class_counts"], seed)
    imgs = gen.images(seed, labels, size=traffic["image_size"],
                      n_classes=traffic["n_classes"],
                      n_domains=traffic["n_domains"])
    parts = gen.dirichlet_partition(labels, traffic["n_clients"],
                                    traffic["alpha"],
                                    seed=traffic["partition_seed"])
    return labels, imgs, parts


def build(config, traffic, seed):
    """The system under test: weights, class embeddings, clients and the
    cohort engine, as the simulator builds them. Returns (engine, global
    trainables, round keys)."""
    from repro.core import clip as clip_lib
    from repro.core.quant import dequantize_tree, quantize_tree
    from repro.fl import client as client_lib
    from repro.fl import cohort as cohort_lib
    from repro.fl import runtime as runtime_lib
    strat = _arm(traffic)
    if sum(traffic["class_counts"]) != config["pacs_images"]:
        raise ValueError("the traffic's class counts do not add up to the "
                         "configuration's pacs_images")
    ccfg = clip_lib.CLIPConfig(**config["model"])
    with span("bench.setup.weights"):
        frozen = jax.jit(clip_lib.init_clip, static_argnums=1)(
            gen.key(seed, 10), ccfg)
        if strat.backbone_bits:
            q = config["quant"]

            def qdq(vision):
                return dequantize_tree(quantize_tree(
                    vision, bits=strat.backbone_bits,
                    mode=strat.backbone_mode, block=q["block"],
                    min_size=q["min_size"]))
            frozen = dict(frozen, vision=jax.jit(qdq)(frozen["vision"]))
        class_emb = jax.jit(clip_lib.text_embedding, static_argnums=1)(
            frozen, ccfg, jnp.asarray(gen.class_prompts(
                traffic["n_classes"])))
        global_tr = jax.jit(client_lib.init_trainable,
                            static_argnums=(1, 2))(
            gen.key(seed, 11), ccfg, strat)
    with span("bench.setup.data"):
        labels, imgs, parts = data(traffic, seed)
        imgs = np.asarray(imgs)
        clients = [client_lib.Client(
            cid=i, images=imgs[idx], labels=labels[idx],
            n_classes=traffic["n_classes"], strategy=strat)
            for i, idx in enumerate(parts)]
        del imgs
    with span("bench.setup.stage"):
        engine = cohort_lib.CohortEngine(
            frozen=frozen, ccfg=ccfg, class_emb=class_emb, clients=clients,
            cfg=cohort_lib.CohortConfig(
                strategy=strat, local_steps=traffic["local_steps"],
                batch_size=traffic["batch"], lr=traffic["lr"]),
            runtime=runtime_lib.ProgramRuntime())
    del clients
    return engine, global_tr, gen.round_keys(seed, MAX_ROUNDS)


def first_rounds(engine, global_tr, keys, n=COMPARED):
    """Drive the engine through its first ``n`` rounds (the window's own
    call); returns (global trainables, host snapshots before and after
    each round, per-round per-client losses)."""
    snaps = [jax.tree.map(np.asarray, global_tr)]
    losses = []
    for r in range(n):
        with span("bench.round"):
            global_tr, m = engine.run_round(global_tr, keys[r])
        losses.append(np.asarray(m["loss"]))
        snaps.append(jax.tree.map(np.asarray, global_tr))
    return global_tr, snaps, np.stack(losses)


def reference(config, traffic, seed, *, n=COMPARED, mode="f32",
              rows=None):
    """The first ``n`` rounds by the plain reference; returns (snapshots,
    losses (n, clients), mean per-client first-round gradient norms by
    leaf)."""
    arm = traffic["arm"]
    c = config["model"]
    use_lora = arm["use_lora"]
    with jax.default_matmul_precision("highest"):
        frozen = jax.jit(partial(ref.init_clip, c=c))(gen.key(seed, 10))
        if arm["backbone_bits"]:
            frozen = jax.jit(partial(ref.quantize_backbone,
                                     block=config["quant"]["block"]))(frozen)
        class_emb = jax.jit(partial(ref.class_embedding, c=c))(
            frozen, tokens=jnp.asarray(gen.class_prompts(
                traffic["n_classes"])))
        tr = jax.jit(partial(ref.init_trainable, c=c, use_lora=use_lora,
                             rank=config["lora_rank"]))(gen.key(seed, 11))
        labels, imgs, parts = data(traffic, seed)
        r = ref.rounding(mode)
        if use_lora:
            stage = jax.jit(lambda f, x: ref.embed_patches(f, c, x, r))
        else:
            stage = jax.jit(lambda f, x: ref.encode_tokens(
                f, c, ref.embed_patches(f, c, x, r), r=r))
        pools = []
        for idx in parts:
            pad = np.zeros(-len(idx) % STAGE_ROWS, idx.dtype)
            sel = jnp.asarray(np.concatenate([idx, pad]))
            pools.append(jnp.concatenate([
                stage(frozen, imgs[sel[i:i + STAGE_ROWS]])
                for i in range(0, len(sel), STAGE_ROWS)])[:len(idx)])
        del imgs
        lens = np.asarray([len(p) for p in parts], np.int32)
        w = lens.astype(np.float32) / lens.sum()
        local = ref.make_local_train(c, use_lora=use_lora,
                                     lr=traffic["lr"], mode=mode, rows=rows)
        keys = gen.round_keys(seed, n)
        snaps = [jax.tree.map(np.asarray, tr)]
        losses, grad_norms = [], None
        for r in range(n):
            idx = np.asarray(ref.batch_indices(
                jnp.asarray(keys[r]), jnp.asarray(lens),
                traffic["local_steps"], traffic["batch"]))
            agg = jax.tree.map(jnp.zeros_like, tr)
            row, norms = [], []
            for i, part in enumerate(parts):
                xs = pools[i][jnp.asarray(idx[i])]
                ys = jnp.asarray(labels[part][idx[i]])
                tr_i, ls, gn = local(frozen, class_emb, tr, xs, ys)
                d = ref.uplink(jax.tree.map(lambda a, b: a - b, tr_i, tr),
                               comm_bits=arm["comm_bits"],
                               comm_block=config["uplink"]["block"],
                               min_size=config["uplink"]["min_size"])
                agg = jax.tree.map(lambda a, x: a + w[i] * x, agg, d)
                row.append(float(ls[-1]))
                norms.append(compare.leaf_norms(
                    jax.tree.map(lambda v: np.max(np.asarray(v)), gn)))
            tr = jax.tree.map(lambda a, b: a + b, tr, agg)
            snaps.append(jax.tree.map(np.asarray, tr))
            losses.append(row)
            if grad_norms is None:
                grad_norms = {k: float(np.mean([nm[k] for nm in norms]))
                              for k in norms[0]}
    return snaps, np.asarray(losses), grad_norms


def numbers(prog_snaps, prog_losses, ref_snaps, ref_losses, grad_norms):
    """The numbers a cell may compare: each round's loss (the mean of its
    clients' last-step losses), and the first aggregated update and the
    change over the compared rounds, each by its worst kept leaf and by
    its median kept leaf, as gaps of norms; the first update's
    element-wise error (``update_err``, :func:`compare.error_gaps`) by
    the same two leaves; with the per-leaf gaps and the per-client loss
    gap beside them. The traffic file's ``limits`` name the ones its
    cell compares."""
    keep = compare.kept_leaves(grad_norms)
    g_p = compare.leaf_norms(compare.diff(prog_snaps[1], prog_snaps[0]))
    g_r = compare.leaf_norms(compare.diff(ref_snaps[1], ref_snaps[0]))
    c_p = compare.leaf_norms(compare.diff(prog_snaps[-1], prog_snaps[0]))
    c_r = compare.leaf_norms(compare.diff(ref_snaps[-1], ref_snaps[0]))
    ug, ug_at = compare.norm_gap(g_p, g_r, keep)
    cg, cg_at = compare.norm_gap(c_p, c_r, keep)
    pl, rl = np.asarray(prog_losses), np.asarray(ref_losses)
    ul = compare.leaf_gaps(g_p, g_r, keep)
    cl = compare.leaf_gaps(c_p, c_r, keep)
    ue = compare.error_gaps(prog_snaps[0], prog_snaps[1], ref_snaps[0],
                            ref_snaps[1], keep)
    return {"loss_gap": compare.loss_gap(pl.mean(-1), rl.mean(-1)),
            "update_gap": ug, "change_gap": cg,
            "update_gap_median": float(np.median(list(ul.values()))),
            "change_gap_median": float(np.median(list(cl.values()))),
            "update_err": max(ue.values()),
            "update_err_median": float(np.median(list(ue.values()))),
            "client_loss_gap": compare.loss_gap(pl, rl),
            "update_gap_at": ug_at, "change_gap_at": cg_at,
            "update_leaf_gaps": ul, "change_leaf_gaps": cl,
            "excluded": sorted(set(grad_norms) - set(keep))}


def readings(config, traffic, seed, *, program, control,
             kinds=("control", "half_batch")):
    """{kind: numbers} for one seed against the float32 reference: the
    program's first rounds (``program``), and with ``control`` the
    reference with float8 products (``control``) and with half of each
    batch left out (``half_batch``) in the program's place."""
    ref_out = reference(config, traffic, seed)
    out = {}
    if program:
        eng, g, keys = build(config, traffic, seed)
        _, snaps, losses = first_rounds(eng, g, keys)
        del eng, g
        gc.collect()
        out["program"] = numbers(snaps, losses, *ref_out)
    if control:
        for kind, kw in (("control", {"mode": "fp8"}),
                         ("half_batch", {"rows": traffic["batch"] // 2})):
            if kind in kinds:
                s, l, _ = reference(config, traffic, seed, **kw)
                out[kind] = numbers(s, l, *ref_out)
    return out


def run(ctx) -> Outcome:
    traffic = ctx.traffic
    strat = _arm(traffic)
    engine, global_tr, keys = build(ctx.config, traffic, ctx.seed)
    global_tr, snaps, losses = first_rounds(engine, global_tr, keys)

    in_window, marks = [], []
    t0 = ctx.open_window()
    r, prev = COMPARED, None
    while True:
        with span("bench.round"):
            global_tr, m = engine.run_round(global_tr, keys[r])
        r += 1
        in_window.append(m["loss"])
        if prev is not None:
            with span("bench.block"):
                prev.block_until_ready()
            marks.append(time.perf_counter())
        prev = m["loss"]
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    with span("bench.block"):
        jax.block_until_ready(global_tr)
    window = ctx.close_window()
    gaps = np.diff(marks) if len(marks) > 1 else np.zeros(1)
    harness.log(f"round intervals in the window: min {gaps.min():.4f} s, "
                f"median {np.median(gaps):.4f} s, max {gaps.max():.4f} s")
    n = r - COMPARED
    bad = sum(not np.all(np.isfinite(np.asarray(l))) for l in in_window)
    bad += int(not np.all(np.isfinite(losses)))

    del engine, global_tr, m, prev, in_window
    gc.collect()
    t_ref = time.perf_counter()
    ref_snaps, ref_losses, gnorms = reference(ctx.config, traffic, ctx.seed)
    harness.log(f"reference: {time.perf_counter() - t_ref:.3f} s")
    harness.log(f"losses, program: {np.round(losses, 5).tolist()}")
    harness.log(f"losses, reference: {np.round(ref_losses, 5).tolist()}")
    got = numbers(snaps, losses, ref_snaps, ref_losses, gnorms)
    harness.log(f"worst leaves: update {got['update_gap_at']}, change "
                f"{got['change_gap_at']}; left out (reference gradient "
                f"under {compare.EXCLUDE_BELOW} of the median leaf's): "
                f"{got['excluded']}")
    harness.log("numbers: " + ", ".join(
        f"{k} {v!r}" for k, v in got.items() if isinstance(v, float)))
    checks = [Check(k, got[k], v) for k, v in traffic["limits"].items()]
    per_round = flops.clip_round(ctx.config["model"], traffic,
                                 use_lora=strat.use_lora,
                                 rank=ctx.config["lora_rank"])
    return Outcome(
        attempted=n + COMPARED, failed=bad,
        metrics={"round_s": window / n},
        checks=checks,
        layer={"work": n, "flops_per_work": per_round})
