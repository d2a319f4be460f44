"""Mamba-1 selective-scan block (falcon-mamba), TPU-adapted.

GPU Mamba fuses the recurrence into one CUDA kernel; the TPU-native shape
of the same math is a *chunked* scan (DESIGN.md §5): ``lax.scan`` over
sequence chunks carrying the (B, d_inner, N) state, with an
``associative_scan`` inside each chunk — the chunk working set is sized for
VMEM and every op is MXU/VPU-friendly. The recurrence
``h_t = exp(Δ_t A) h_{t-1} + Δ_t B_t x_t`` is composed associatively via
(a, b) pairs: (a2, b2)∘(a1, b1) = (a1·a2, a2·b1 + b2).

Distribution: everything in the block is per-channel in d_inner, so under a
Runtime the block runs inside ``shard_map`` with d_inner sharded over the
``model`` axis. The only cross-shard communication is the small psum for
x_proj (Δ/B/C depend on all channels) and the reduce-scatter of the output
projection back to the sequence-sharded residual. Relying on GSPMD to
partition the scan instead replicates the (B,S,d_inner,N) tensors
(measured 342 GiB/device on falcon-mamba train_4k).

``in_proj`` is stored as two matrices (x-branch, z-gate) so the d_inner
shard never straddles the packed halves.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import lora as lora_lib
from repro.models import layers as L
from repro.models import runtime as rt_lib


# ---------------------------------------------------------------- scan util
def _comb(left, right):
    a1, b1 = left
    a2, b2 = right
    return a1 * a2, a2 * b1 + b2


def chunked_linear_scan(a, b, h0, chunk: int):
    """Elementwise linear recurrence h_t = a_t·h_{t-1} + b_t.

    a, b: (B, S, ...); h0: (B, ...). Returns (h_all (B,S,...), h_last).
    Chunked so peak memory is O(B·chunk·state) regardless of S."""
    B, S = a.shape[0], a.shape[1]
    chunk = min(chunk, S)
    Sp = -(-S // chunk) * chunk
    if Sp != S:  # pad with identity transitions (a=1, b=0), slice after
        pw = [(0, 0), (0, Sp - S)] + [(0, 0)] * (a.ndim - 2)
        a = jnp.pad(a, pw, constant_values=1.0)
        b = jnp.pad(b, pw)
    nc = Sp // chunk
    rest = a.shape[2:]
    a_c = jnp.moveaxis(a.reshape(B, nc, chunk, *rest), 1, 0)
    b_c = jnp.moveaxis(b.reshape(B, nc, chunk, *rest), 1, 0)

    def step(h, ab):
        ac, bc = ab
        a_cum, b_scan = lax.associative_scan(_comb, (ac, bc), axis=1)
        h_full = b_scan + a_cum * h[:, None]
        return h_full[:, -1], h_full

    _, h_all = lax.scan(step, h0, (a_c, b_c))
    h_all = jnp.moveaxis(h_all, 0, 1).reshape(B, Sp, *rest)[:, :S]
    return h_all, h_all[:, -1]


def _chunked_ssm_scan(dt, A, Bm, Cm, xc, h0, chunk: int):
    """Selective scan emitting y = (h·C).sum(N) chunk-by-chunk so the
    (B, chunk, di, N) state tensor never materializes beyond one chunk.

    dt, xc: (B,S,di); A: (di,N); Bm, Cm: (B,S,N); h0: (B,di,N) f32.
    Returns (y (B,S,di) f32, h_last). Padded steps have Δ = 0, so a = 1
    and b = 0: the state passes through them and h_last is the last
    real step's."""
    B, S, di = xc.shape
    N = A.shape[-1]
    chunk = min(chunk, S)
    Sp = -(-S // chunk) * chunk
    pad = Sp - S
    if pad:
        z2 = lambda x: jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        dt, xc, Bm, Cm = z2(dt), z2(xc), z2(Bm), z2(Cm)
    nc = Sp // chunk
    mv = lambda x: jnp.moveaxis(
        x.reshape(B, nc, chunk, *x.shape[2:]), 1, 0)
    dt_c, xc_c, B_c, C_c = mv(dt), mv(xc), mv(Bm), mv(Cm)

    def step(h, inp):
        dtc, xcc, bc, cc = inp                    # (B,L,di) / (B,L,N)
        a = jnp.exp(dtc[..., None] * A)           # (B,L,di,N)
        b = (dtc * xcc)[..., None] * bc[:, :, None, :]
        a_cum, b_scan = lax.associative_scan(_comb, (a, b), axis=1)
        h_full = b_scan + a_cum * h[:, None]
        y = jnp.einsum("blen,bln->ble", h_full, cc)
        return h_full[:, -1], y

    h_last, y = lax.scan(step, h0, (dt_c, xc_c, B_c, C_c))
    y = jnp.moveaxis(y, 0, 1).reshape(B, Sp, di)[:, :S]
    return y, h_last


# ---------------------------------------------------------------- params
def init_mamba(rng, cfg: ModelConfig, dtype):
    d, di, N, R, K = (cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.dt_rank,
                      cfg.ssm_conv)
    ks = jax.random.split(rng, 6)
    s = lambda fan: 1.0 / jnp.sqrt(fan)
    p = {
        "in_proj_x": jax.random.normal(ks[0], (d, di), dtype) * s(d),
        "in_proj_z": jax.random.normal(ks[5], (d, di), dtype) * s(d),
        "conv_w": jax.random.normal(ks[1], (K, di), dtype) * s(K),
        "x_proj": jax.random.normal(ks[2], (di, R + 2 * N), dtype) * s(di),
        "dt_proj": jax.random.normal(ks[3], (R, di), dtype) * s(R),
        "dt_bias": jnp.full((di,), -2.0, jnp.float32),
        "a_log": jnp.log(jnp.broadcast_to(
            jnp.arange(1, N + 1, dtype=jnp.float32), (di, N))),
        "d_skip": jnp.ones((di,), jnp.float32),
        "out_proj": jax.random.normal(ks[4], (di, d), dtype) * s(di),
    }
    if cfg.ssm_conv_bias:
        p["conv_b"] = jax.random.normal(jax.random.fold_in(rng, 1), (di,),
                                        jnp.float32) * s(K)
    if cfg.ssm_inner_norms:
        p.update(dt_norm=jnp.zeros((R,), jnp.float32),
                 b_norm=jnp.zeros((N,), jnp.float32),
                 c_norm=jnp.zeros((N,), jnp.float32))
    return p


def mamba_specs(cfg: ModelConfig, dtype, lead=()):
    d, di, N, R, K = (cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.dt_rank,
                      cfg.ssm_conv)
    f = lambda *sh, dt=dtype: jax.ShapeDtypeStruct((*lead, *sh), dt)
    p = {"in_proj_x": f(d, di), "in_proj_z": f(d, di),
         "conv_w": f(K, di),
         "x_proj": f(di, R + 2 * N), "dt_proj": f(R, di),
         "dt_bias": f(di, dt=jnp.float32),
         "a_log": f(di, N, dt=jnp.float32),
         "d_skip": f(di, dt=jnp.float32), "out_proj": f(di, d)}
    if cfg.ssm_conv_bias:
        p["conv_b"] = f(di, dt=jnp.float32)
    if cfg.ssm_inner_norms:
        p.update(dt_norm=f(R, dt=jnp.float32), b_norm=f(N, dt=jnp.float32),
                 c_norm=f(N, dt=jnp.float32))
    return p


def mamba_partition_specs(cfg: ModelConfig, tp_axis="model", lead=()):
    """Per-leaf PartitionSpecs: the d_inner dim -> tp axis. Shared by the
    launch sharding rules and the shard_map in_specs (they must agree)."""
    nl = (None,) * len(lead)
    p = {"in_proj_x": P(*nl, None, tp_axis),
         "in_proj_z": P(*nl, None, tp_axis),
         "conv_w": P(*nl, None, tp_axis),
         "x_proj": P(*nl, tp_axis, None),
         "dt_proj": P(*nl, None, tp_axis),
         "dt_bias": P(*nl, tp_axis),
         "a_log": P(*nl, tp_axis, None),
         "d_skip": P(*nl, tp_axis),
         "out_proj": P(*nl, tp_axis, None)}
    if cfg.ssm_conv_bias:
        p["conv_b"] = P(*nl, tp_axis)
    if cfg.ssm_inner_norms:      # over dt_rank and N: replicated
        p.update(dt_norm=P(*nl, None), b_norm=P(*nl, None),
                 c_norm=P(*nl, None))
    return p


def mamba_cache_init(cfg: ModelConfig, batch: int, dtype):
    di, N, K = cfg.d_inner, cfg.ssm_state, cfg.ssm_conv
    return {"h": jnp.zeros((batch, di, N), jnp.float32),
            "conv": jnp.zeros((batch, K - 1, di), dtype)}


def mamba_cache_specs(cfg: ModelConfig, batch: int, dtype, lead=()):
    di, N, K = cfg.d_inner, cfg.ssm_state, cfg.ssm_conv
    return {"h": jax.ShapeDtypeStruct((*lead, batch, di, N), jnp.float32),
            "conv": jax.ShapeDtypeStruct((*lead, batch, K - 1, di), dtype)}


# ---------------------------------------------------------------- forward
def _causal_conv(conv_w, x1, dtype, bias=None):
    """Depthwise causal conv over S, plus ``bias`` where the config has
    one. x1: (B, S, di)."""
    K = conv_w.shape[0]
    w = conv_w.astype(dtype)[:, None, :]
    x_pad = jnp.pad(x1, ((0, 0), (K - 1, 0), (0, 0)))
    y = lax.conv_general_dilated(
        x_pad, w, window_strides=(1,), padding="VALID",
        dimension_numbers=("NHC", "HIO", "NHC"),
        feature_group_count=x1.shape[-1])
    return y if bias is None else y + bias.astype(dtype)


def _dt_b_c(p, proj, R, N):
    """Split x_proj's output into Δ's rank-R input, B and C, with Jamba's
    RMSNorms on each where the config has them."""
    dt_r, Bm, Cm = jnp.split(proj, [R, R + N], axis=-1)
    if "dt_norm" in p:
        dt_r = L.rms_norm(dt_r, p["dt_norm"])
        Bm = L.rms_norm(Bm, p["b_norm"])
        Cm = L.rms_norm(Cm, p["c_norm"])
    return dt_r, Bm, Cm


def _lora_delta(x, pair, sl, alpha, rank):
    """LoRA delta for a d_inner-sharded target: B is column-sliced."""
    if pair is None:
        return 0.0
    h = jnp.einsum("...k,kr->...r", x.astype(pair["a"].dtype), pair["a"])
    b = pair["b"] if sl is None else lax.dynamic_slice_in_dim(
        pair["b"], sl[0], sl[1], axis=1)
    return (jnp.einsum("...r,rn->...n", h, b) * (alpha / rank)).astype(
        x.dtype)


def _mamba_core(p, x, cfg: ModelConfig, h0, lo, *, shard=None):
    """x: (B, S, d) -> (out_partial, cache). When ``shard=(r, m)`` the
    params are local d_inner shards and the output is a PARTIAL sum
    (caller reduces)."""
    B, S, _ = x.shape
    dtype = x.dtype
    di_l = p["in_proj_x"].shape[-1]
    N, R = cfg.ssm_state, cfg.dt_rank
    alpha, rank = cfg.lora_alpha, cfg.lora_rank
    sl_x = None if shard is None else (shard[0] * di_l, di_l)

    x1 = x @ p["in_proj_x"].astype(dtype) + _lora_delta(
        x, lo.get("in_proj_x"), sl_x, alpha, rank)
    z = x @ p["in_proj_z"].astype(dtype)
    xc = jax.nn.silu(_causal_conv(p["conv_w"], x1, dtype, p.get("conv_b")))

    proj = (xc @ p["x_proj"].astype(dtype)).astype(jnp.float32)
    if shard is not None:
        proj = lax.psum(proj, rt_lib.get_runtime().tp_axis)
    dt_r, Bm, Cm = _dt_b_c(p, proj, R, N)
    dt = jax.nn.softplus(dt_r @ p["dt_proj"].astype(jnp.float32) +
                         p["dt_bias"])
    A = -jnp.exp(p["a_log"])
    zero_start = h0 is None
    if h0 is None:
        h0 = jnp.zeros((B, di_l, N), jnp.float32)
    kern = None
    if zero_start and not cfg.calibrate:
        # TPU: fused Pallas selective scan (kernels/selective_scan.py);
        # returns None on CPU where the chunked associative scan is used
        from repro.kernels import ops as kops
        kern = kops.selective_scan(dt, xc.astype(jnp.float32), Bm, Cm, A)
    if kern is not None:
        y, h_last = kern
    else:
        chunk = S if cfg.calibrate else cfg.scan_chunk
        y, h_last = _chunked_ssm_scan(dt, A, Bm, Cm,
                                      xc.astype(jnp.float32), h0, chunk)
    y = y + p["d_skip"] * xc.astype(jnp.float32)
    y = y.astype(dtype) * jax.nn.silu(z)
    # out_proj contracts the (possibly sharded) d_inner dim -> partial
    out = y @ p["out_proj"].astype(dtype)
    if lo.get("out_proj") is not None:
        a = lo["out_proj"]["a"] if shard is None else \
            lax.dynamic_slice_in_dim(lo["out_proj"]["a"], sl_x[0], di_l, 0)
        h = jnp.einsum("...k,kr->...r", y.astype(a.dtype), a)
        out = out + (jnp.einsum("...r,rn->...n", h, lo["out_proj"]["b"]) *
                     (alpha / rank)).astype(dtype)
    K = cfg.ssm_conv
    tail = x1[:, -(K - 1):, :] if S >= K - 1 else \
        jnp.pad(x1, ((0, 0), (K - 1 - S, 0), (0, 0)))
    return out, {"h": h_last, "conv": tail}


def mamba_block(p, x, cfg: ModelConfig, *, lora=None, h0=None):
    """x: (B, S, d) -> (y (B, S, d), cache). Dispatches to the shard_map
    d_inner-parallel path under a Runtime."""
    from repro.core.quant import QTensor, maybe_dequantize
    lo = lora or {}
    rt = rt_lib.get_runtime()
    B, S, d = x.shape
    # recurrent blocks consume dense weights; QLoRA storage stays int4/NF4
    # in HBM, dequantization is fused into the per-layer compute
    p = jax.tree.map(maybe_dequantize, p,
                     is_leaf=lambda l: isinstance(l, QTensor))
    if rt is None:
        return _mamba_core(p, x, cfg, h0, lo)

    mesh, m, tp, dp = rt.mesh, rt.tp_size, rt.tp_axis, rt.dp_axes
    if cfg.d_inner % m or (B % rt.dp_size):
        return _mamba_core(p, x, cfg, h0, lo)
    pspec = mamba_partition_specs(cfg, tp)
    p = {k: p[k] for k in pspec}          # layer dict may carry norms etc.
    lo = {k: v for k, v in lo.items() if k in ("in_proj_x", "out_proj")}
    lspec = jax.tree.map(lambda _: P(), lo)
    seq_out = tp if (cfg.seq_shard and S % m == 0 and S > 1) else None

    # checkpoint INSIDE the shard_map body: its AD residuals reduce to the
    # block inputs (kept sequence-SHARDED — the all-gather happens inside
    # the checkpointed region and is recomputed in the backward), so the
    # layer scan saves only (B, S/m, d) per layer. Wrapping the shard_map
    # in the scan-body checkpoint instead compiles pathologically slowly
    # (measured 25+ minutes vs 17 s on falcon-mamba train_4k).
    @jax.checkpoint
    def fn(x_l, p_l, lo_l, h0_l):
        r = lax.axis_index(tp)
        if seq_out:
            x_l = lax.all_gather(x_l, tp, axis=1, tiled=True)
        out, cache = _mamba_core(p_l, x_l, cfg, h0_l, lo_l, shard=(r, m))
        if seq_out:
            out = lax.psum_scatter(out, tp, scatter_dimension=1,
                                   tiled=True)
        else:
            out = lax.psum(out, tp)
        return out, cache

    h0_spec = P(dp, tp, None)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(dp, seq_out, None), pspec, lspec,
                  None if h0 is None else h0_spec),
        out_specs=(P(dp, seq_out, None),
                   {"h": P(dp, tp, None), "conv": P(dp, None, tp)}),
        check_vma=False)(x, p, lo, h0)


def mamba_decode(p, x, cache, cfg: ModelConfig, *, lora=None):
    """Single-token step. x: (B, 1, d). Plain (GSPMD) execution — every op
    is small and elementwise, so no explicit mapping is needed."""
    from repro.core.quant import QTensor, maybe_dequantize
    p = jax.tree.map(maybe_dequantize, p,
                     is_leaf=lambda l: isinstance(l, QTensor))
    B = x.shape[0]
    dtype = x.dtype
    lo = lora or {}
    alpha, rank = cfg.lora_alpha, cfg.lora_rank
    x1 = (x[:, 0] @ p["in_proj_x"].astype(dtype) +
          _lora_delta(x[:, 0], lo.get("in_proj_x"), None, alpha, rank))
    z = x[:, 0] @ p["in_proj_z"].astype(dtype)
    window = jnp.concatenate([cache["conv"],
                              x1[:, None, :].astype(cache["conv"].dtype)], 1)
    w = p["conv_w"].astype(dtype)
    xc = jnp.einsum("bkd,kd->bd", window.astype(dtype), w)
    if "conv_b" in p:
        xc = xc + p["conv_b"].astype(dtype)
    xc = jax.nn.silu(xc)
    N, R = cfg.ssm_state, cfg.dt_rank
    proj = (xc @ p["x_proj"].astype(dtype)).astype(jnp.float32)
    dt_r, Bm, Cm = _dt_b_c(p, proj, R, N)
    dt = jax.nn.softplus(dt_r @ p["dt_proj"].astype(jnp.float32) +
                         p["dt_bias"])
    A = -jnp.exp(p["a_log"])
    a = jnp.exp(dt[..., None] * A)
    b = (dt * xc.astype(jnp.float32))[..., None] * Bm[:, None, :]
    h = a * cache["h"] + b
    y = jnp.einsum("ben,bn->be", h, Cm) + p["d_skip"] * xc.astype(
        jnp.float32)
    y = y.astype(dtype) * jax.nn.silu(z)
    out = y @ p["out_proj"].astype(dtype)
    if lo.get("out_proj") is not None:
        out = out + _lora_delta(y, lo["out_proj"], None, alpha, rank)
    return out[:, None, :], {"h": h, "conv": window[:, 1:, :]}
