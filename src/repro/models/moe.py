"""Token-choice top-k MoE with explicit expert parallelism.

Two execution paths with identical routing semantics:

- **local** (no Runtime installed — unit tests, CPU examples): capacity-
  bounded gather/scatter dispatch into an (E, C, d) buffer, batched expert
  einsum, combine.
- **distributed** (under the production mesh): ``shard_map`` over
  ``(pod, data, model)``. Tokens are sharded over (dp × model); each rank
  builds an (E, C_e, d) send buffer, an ``all_to_all`` over the ``model``
  axis delivers token slots to their expert's owner, expert weights are
  2-D sharded (E→model, last-dim→data, FSDP-style) and all-gathered
  per-expert inside a scan, and a reverse ``all_to_all`` returns outputs.
  This is the collective pattern a real expert-parallel deployment uses,
  and the all-to-all / all-gather bytes it emits are what §Roofline reads.

Experts are part of the *frozen, quantizable* backbone (TriplePlay trains
only LoRA/adapter), so no optimizer state or weight gradients exist for
them — the backward pass only transports activation gradients through the
collectives (their transposes are themselves collectives).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.quant import QTensor, dequantize
from repro.models import runtime as rt_lib


# ------------------------------------------------------------------ params
def init_experts(rng, cfg: ModelConfig, dtype, quantize=None):
    """Router and experts. ``quantize`` (a function of one expert's
    ``{wg, wu, wd}``) makes and quantizes the experts one at a time, so
    no bf16 copy of the whole layer's experts ever exists."""
    E, d, ff = cfg.n_experts, cfg.d_model, cfg.d_ff
    ks = jax.random.split(rng, 4)
    s = lambda fan: 1.0 / jnp.sqrt(fan)
    router = jax.random.normal(ks[0], (d, E), jnp.float32) * s(d)
    if quantize is None:
        return {
            "router": router,
            "wg": jax.random.normal(ks[1], (E, d, ff), dtype) * s(d),
            "wu": jax.random.normal(ks[2], (E, d, ff), dtype) * s(d),
            "wd": jax.random.normal(ks[3], (E, ff, d), dtype) * s(ff),
        }

    def one(k):
        kg, ku, kd = jax.random.split(k, 3)
        return quantize({
            "wg": jax.random.normal(kg, (d, ff), dtype) * s(d),
            "wu": jax.random.normal(ku, (d, ff), dtype) * s(d),
            "wd": jax.random.normal(kd, (ff, d), dtype) * s(ff)})

    experts = lax.map(one, jax.random.split(ks[1], E))
    return {"router": router, **experts}


def expert_specs(cfg: ModelConfig, dtype, lead=()):
    E, d, ff = cfg.n_experts, cfg.d_model, cfg.d_ff
    f = lambda *sh: jax.ShapeDtypeStruct((*lead, *sh), dtype)
    return {"router": jax.ShapeDtypeStruct((*lead, d, E), jnp.float32),
            "wg": f(E, d, ff), "wu": f(E, d, ff), "wd": f(E, ff, d)}


def expert_partition_specs(params, tp_axis="model", fsdp_axis="data",
                           lead_scanned=True):
    """PartitionSpec tree for the (possibly quantized) expert params.
    Uniform rule: E dim -> tp axis, last dim -> fsdp axis; router replicated.
    ``lead_scanned``: params carry a leading (L,) stacked-layer dim."""
    def spec(path, leaf):
        name = path[-1] if isinstance(path[-1], str) else str(path[-1])
        nlead = 1 if lead_scanned else 0
        if "router" in str(path):
            return P(*([None] * leaf.ndim))
        dims = [None] * leaf.ndim
        dims[nlead] = tp_axis          # E dim
        dims[-1] = fsdp_axis           # d or ff — uniformly gatherable
        return P(*dims)
    return _tree_map_with_name(spec, params)


def _tree_map_with_name(fn, tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda l: isinstance(l, jax.ShapeDtypeStruct))
    out = [fn(tuple(str(k) for k in path), leaf) for path, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, out)


# ------------------------------------------------------------------ routing
def _route(router_w, x2d, cfg: ModelConfig):
    """x2d: (T, d) -> (gates (T,k), ids (T,k), aux): the top-k of a
    softmax over all experts, the gates renormalized to sum to one where
    ``cfg.router_renorm``."""
    logits = x2d.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = lax.top_k(probs, cfg.experts_per_token)
    if cfg.router_renorm:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # auxiliary load-balance statistics (Switch-style)
    density = jnp.mean(jax.nn.one_hot(ids[:, 0], cfg.n_experts), axis=0)
    p_mean = jnp.mean(probs, axis=0)
    aux = cfg.n_experts * jnp.sum(density * p_mean)
    return gates, ids, aux


def _slot_assignment(ids_flat: jax.Array, E: int, C: int):
    """Capacity-bounded slot for every token-copy.

    Returns (order, sorted_ids, slot, keep): sorting token-copies by expert
    id, ``slot`` is the position within the expert's segment; copies with
    slot >= C are dropped (their gate contribution becomes zero)."""
    order = jnp.argsort(ids_flat, stable=True)
    sorted_ids = ids_flat[order]
    seg_start = jnp.searchsorted(sorted_ids, sorted_ids, side="left")
    slot = jnp.arange(ids_flat.size, dtype=jnp.int32) - seg_start
    keep = slot < C
    return order, sorted_ids, slot, keep


def _expert_mlp(x_e, wg_e, wu_e, wd_e, dtype):
    h = jax.nn.silu(x_e @ wg_e.astype(dtype)) * (x_e @ wu_e.astype(dtype))
    return h @ wd_e.astype(dtype)


def _deq(w, dtype):
    return dequantize(w, dtype) if isinstance(w, QTensor) else w


# ------------------------------------------------------------------ local
def _moe_local(p, x2d, cfg: ModelConfig):
    T, d = x2d.shape
    E, k = cfg.n_experts, cfg.experts_per_token
    C = max(1, math.ceil(T * k * cfg.capacity_factor / E))
    gates, ids, aux = _route(p["router"], x2d, cfg)
    order, sorted_ids, slot, keep = _slot_assignment(ids.reshape(-1), E, C)
    vals = x2d[order // k]
    buf = jnp.zeros((E, C, d), x2d.dtype).at[
        sorted_ids, jnp.where(keep, slot, C)].set(vals, mode="drop")
    wg, wu, wd = (_deq(p[n], x2d.dtype) for n in ("wg", "wu", "wd"))
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg)) * \
        jnp.einsum("ecd,edf->ecf", buf, wu)
    out = jnp.einsum("ecf,efd->ecd", h, wd)
    y_sorted = out.at[sorted_ids, jnp.where(keep, slot, C)].get(
        mode="fill", fill_value=0)
    y_copies = jnp.zeros_like(y_sorted).at[order].set(
        y_sorted * keep[:, None].astype(y_sorted.dtype))
    y = (y_copies.reshape(T, k, d) *
         gates[..., None].astype(y_copies.dtype)).sum(axis=1)
    return y, aux


# ------------------------------------------------------------------ dropless
GROUP_ROWS = 256      # row tile of the grouped matmul (kernels/moe_gmm.py)


def group_by_expert(ids_flat: jax.Array, E: int, tm: int = GROUP_ROWS):
    """Dropless grouping of the ``n`` token copies by expert: sorted
    stably by expert, each expert's group padded to whole ``tm``-row
    tiles. Returns ``(order, dest, tile_expert, n_tiles, counts)``: the
    sorted copies' source positions, each one's row in the grouped
    buffer of the static ``round_up(n + E·(tm − 1), tm)`` rows, the
    expert of each row tile, the tiles in use and each expert's
    copies."""
    n = ids_flat.shape[0]
    order = jnp.argsort(ids_flat, stable=True)
    sorted_ids = ids_flat[order]
    counts = jnp.zeros((E,), jnp.int32).at[ids_flat].add(1)
    padded = -(-counts // tm) * tm
    ends = jnp.cumsum(padded)
    first = jnp.cumsum(counts) - counts            # in sorted order
    dest = (ends - padded)[sorted_ids] + \
        jnp.arange(n, dtype=jnp.int32) - first[sorted_ids]
    rows = -(-(n + E * (tm - 1)) // tm) * tm
    tile_expert = jnp.minimum(jnp.searchsorted(
        ends, jnp.arange(0, rows, tm), side="right"), E - 1)
    return order, dest, tile_expert.astype(jnp.int32), ends[-1] // tm, \
        counts


def moe_dropless(p, x2d, cfg: ModelConfig, tm: int = GROUP_ROWS):
    """Every token copy through its expert, none dropped: route, group
    the copies by expert (:func:`group_by_expert`), run the experts'
    SwiGLU through the grouped NF4 matmul (``kernels.ops.moe_gmm``), and
    combine by a gate-weighted scatter-add of the copies' rows (pad rows
    are never read). x2d: (T, d) -> (y (T, d), aux, stats) with stats
    ``moe_load_max`` (the fullest expert's copies over the mean) and
    ``moe_pad_rows`` (pad rows inside the expert groups). The grouped
    matmul computes every row of the static buffer, so its work does not
    follow the routing."""
    from repro.kernels import ops as kops
    T, d = x2d.shape
    E, k = cfg.n_experts, cfg.experts_per_token
    with jax.named_scope("router"):
        gates, ids, aux = _route(p["router"], x2d, cfg)
    with jax.named_scope("dispatch"):
        order, dest, tile_expert, n_tiles, counts = group_by_expert(
            ids.reshape(-1), E, tm)
        src = order // k
        xs = jnp.zeros((tile_expert.shape[0] * tm, d), x2d.dtype).at[
            dest].set(x2d[src])
    with jax.named_scope("experts"):
        gmm = lambda a, w: kops.moe_gmm(a, w, tile_expert)
        h = jax.nn.silu(gmm(xs, p["wg"])) * gmm(xs, p["wu"])
        ys = gmm(h, p["wd"])
    with jax.named_scope("combine"):
        w = gates.reshape(-1)[order][:, None]
        y = jnp.zeros((T, d), jnp.float32).at[src].add(
            ys[dest].astype(jnp.float32) * w).astype(x2d.dtype)
    stats = {"moe_load_max": counts.max() * (E / (T * k)),
             "moe_pad_rows": n_tiles * tm - T * k}
    stats = {n: lax.stop_gradient(v.astype(jnp.float32))
             for n, v in stats.items()}
    return y, aux, stats


# ------------------------------------------------------------------ dist
def _q8_rows(x):
    """Per-row absmax int8 quantization (for low-precision dispatch)."""
    s = jnp.maximum(jnp.abs(x.astype(jnp.float32)).max(-1, keepdims=True),
                    1e-12) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s), -127,
                 127).astype(jnp.int8)
    return q, s


import functools


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _a2a_q8(x, tp_axis):
    """int8 all_to_all: per-row absmax quantize, exchange payload+scales,
    dequantize. Both directions (activations fwd, cotangents bwd) ride the
    wire in int8 — the DeepSeek-V3 low-precision-dispatch pattern."""
    q, s = _q8_rows(x)
    q = lax.all_to_all(q, tp_axis, split_axis=0, concat_axis=0, tiled=True)
    s = lax.all_to_all(s, tp_axis, split_axis=0, concat_axis=0, tiled=True)
    return (q.astype(jnp.float32) * s).astype(x.dtype)


def _a2a_q8_fwd(x, tp_axis):
    return _a2a_q8(x, tp_axis), None


def _a2a_q8_bwd(tp_axis, _, g):
    # all_to_all (split=concat, tiled) is its own transpose
    return (_a2a_q8(g, tp_axis),)


_a2a_q8.defvjp(_a2a_q8_fwd, _a2a_q8_bwd)


def _a2a_maybe_q8(x, tp_axis, enabled, dtype):
    """all_to_all with optional int8 payload + f32 per-row scales."""
    if not enabled:
        return lax.all_to_all(x, tp_axis, split_axis=0, concat_axis=0,
                              tiled=True)
    return _a2a_q8(x, tp_axis).astype(dtype)


def _moe_dist_body(x_loc, p, cfg: ModelConfig, m: int, tp_axis: str,
                   fsdp_axis: str):
    """Runs per-device inside shard_map. x_loc: (T_ls, d) local token slice.
    p: expert params with local shards (E/m experts × last-dim/fsdp)."""
    T_ls, d = x_loc.shape
    E, k = cfg.n_experts, cfg.experts_per_token
    E_l = E // m
    C = max(1, math.ceil(T_ls * k * cfg.capacity_factor / E))
    dtype = x_loc.dtype

    gates, ids, aux = _route(p["router"], x_loc, cfg)  # router replicated
    order, sorted_ids, slot, keep = _slot_assignment(ids.reshape(-1), E, C)
    vals = x_loc[order // k]
    send = jnp.zeros((E, C, d), dtype).at[
        sorted_ids, jnp.where(keep, slot, C)].set(vals, mode="drop")

    # exchange slots with expert owners: (m, E_l, C, d) transpose-a2a
    q8 = cfg.moe_dispatch_bits == 8
    send = send.reshape(m, E_l, C, d)
    recv = _a2a_maybe_q8(send, tp_axis, q8, dtype)       # (m_src, E_l, C, d)
    toks = recv.transpose(1, 0, 2, 3).reshape(E_l, m * C, d)

    # per-expert FSDP: gather this expert's full weights over `fsdp_axis`
    gather = lambda w: jax.tree.map(
        lambda l: lax.all_gather(l, fsdp_axis, axis=l.ndim - 1,
                                 tiled=True), w)
    if cfg.calibrate:
        # batched expert einsum (no scan) for exact FLOP accounting
        wg_f = _deq(gather(p["wg"]), dtype)
        wu_f = _deq(gather(p["wu"]), dtype)
        wd_f = _deq(gather(p["wd"]), dtype)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", toks, wg_f)) * \
            jnp.einsum("ecd,edf->ecf", toks, wu_f)
        y_experts = jnp.einsum("ecf,efd->ecd", h, wd_f)
    else:
        def body(_, inp):
            x_e, wg_e, wu_e, wd_e = inp
            wg_f = _deq(gather(wg_e), dtype)
            wu_f = _deq(gather(wu_e), dtype)
            wd_f = _deq(gather(wd_e), dtype)
            return None, _expert_mlp(x_e, wg_f, wu_f, wd_f, dtype)

        xs = (toks, p["wg"], p["wu"], p["wd"])
        _, y_experts = lax.scan(body, None, xs)          # (E_l, m*C, d)

    y_back = y_experts.reshape(E_l, m, C, d).transpose(1, 0, 2, 3)
    y_home = _a2a_maybe_q8(y_back, tp_axis, q8, dtype)   # (m, E_l, C, d)
    y_buf = y_home.reshape(E, C, d)
    y_sorted = y_buf.at[sorted_ids, jnp.where(keep, slot, C)].get(
        mode="fill", fill_value=0)
    y_copies = jnp.zeros_like(y_sorted).at[order].set(
        y_sorted * keep[:, None].astype(dtype))
    y = (y_copies.reshape(T_ls, k, d) *
         gates[..., None].astype(dtype)).sum(axis=1)
    return y, aux


def moe_ffn(p, x, cfg: ModelConfig):
    """x: (B, S, d) -> (y (B, S, d), aux load-balance loss).

    Dispatches to the shard_map expert-parallel path when a Runtime is
    installed, else to the local path."""
    B, S, d = x.shape
    rt = rt_lib.get_runtime()
    if rt is None:
        y, aux = _moe_local(p, x.reshape(B * S, d), cfg)
        return y.reshape(B, S, d), aux

    mesh = rt.mesh
    m = rt.tp_size
    dp = rt.dp_axes
    tp, fsdp = rt.tp_axis, "data"
    pspecs = expert_partition_specs(p, tp_axis=tp, fsdp_axis=fsdp,
                                    lead_scanned=False)
    seq_shardable = S > 1 and S % m == 0

    all_axes = tuple(dp) + (tp,)

    if seq_shardable:
        def fn(x_in, p_in):
            x_loc = x_in.reshape(-1, d)
            y, aux = _moe_dist_body(x_loc, p_in, cfg, m, tp, fsdp)
            return y.reshape(x_in.shape), lax.pmean(aux, all_axes)
        return jax.shard_map(
            fn, mesh=mesh,
            in_specs=(P(dp, tp, None), pspecs),
            out_specs=(P(dp, tp, None), P()),
            check_vma=False)(x, p)

    # decode path: S == 1 -> split the batch over the tp axis inside
    def fn(x_in, p_in):
        Bl = x_in.shape[0]
        t = max(1, -(-Bl // m))
        r = lax.axis_index(tp)
        x_pad = jnp.pad(x_in.reshape(Bl, d), ((0, m * t - Bl), (0, 0)))
        x_loc = lax.dynamic_slice_in_dim(x_pad, r * t, t, axis=0)
        y_loc, aux = _moe_dist_body(x_loc, p_in, cfg, m, tp, fsdp)
        y_all = lax.all_gather(y_loc, tp, axis=0, tiled=True)[:Bl]
        return y_all.reshape(x_in.shape), lax.pmean(aux, all_axes)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(dp, None, None), pspecs),
        out_specs=(P(dp, None, None), P()),
        check_vma=False)(x, p)
