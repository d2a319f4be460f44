"""Pure-jnp oracles for every Pallas kernel.

These are the *reference semantics* — kernels must match them via
assert_allclose in tests — and they double as the CPU execution path used
by the dry-run (Pallas lowers only on TPU; see DESIGN.md §6).

All three are memory-conscious implementations (the flash reference is
itself blocked) so that 32k-sequence dry-runs never materialize S×S scores.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import quant as qlib

NEG_INF = -1e30


# ------------------------------------------------------------------
# flash attention (causal / sliding-window / bidirectional), GQA-aware
# ------------------------------------------------------------------
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    q_chunk: int = 512, k_chunk: int = 512) -> jax.Array:
    """q: (B, S, H, D); k, v: (B, S, Hkv, D) -> (B, S, H, D).

    Blocked softmax(QK^T)V with running (m, l, acc) statistics; never
    materializes more than a (q_chunk, k_chunk) score tile per head group.
    """
    B, S, H, D = q.shape
    Skv = k.shape[1]
    Hkv = k.shape[2]
    G = H // Hkv
    qc = min(q_chunk, S)
    kc = min(k_chunk, Skv)
    # pad to chunk multiples; padded keys are masked, padded queries sliced
    Sp = -(-S // qc) * qc
    Skvp = -(-Skv // kc) * kc
    if Sp != S:
        q = jnp.pad(q, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
    if Skvp != Skv:
        k = jnp.pad(k, ((0, 0), (0, Skvp - Skv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Skvp - Skv), (0, 0), (0, 0)))
    nq, nk = Sp // qc, Skvp // kc
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))

    qg = q.reshape(B, Sp, Hkv, G, D)

    def q_block(qi):
        qb = lax.dynamic_slice_in_dim(qg, qi * qc, qc, axis=1)
        qb = qb.astype(jnp.float32) * scale
        qpos = qi * qc + jnp.arange(qc)

        def k_step(carry, kj):
            m, l, acc = carry
            kb = lax.dynamic_slice_in_dim(k, kj * kc, kc, axis=1)
            vb = lax.dynamic_slice_in_dim(v, kj * kc, kc, axis=1)
            s = jnp.einsum("bqkgd,bpkd->bkgqp", qb, kb.astype(jnp.float32))
            kpos = kj * kc + jnp.arange(kc)
            mask = jnp.broadcast_to(kpos[None, :] < Skv, (qc, kc))
            if causal:
                mask &= qpos[:, None] >= kpos[None, :]
            if window is not None:
                mask &= (qpos[:, None] - kpos[None, :]) < window
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqp,bpkd->bkgqd", p, vb.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Hkv, G, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, qc), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, qc, D), jnp.float32)
        (m, l, acc), _ = lax.scan(k_step, (m0, l0, a0), jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out  # (B, Hkv, G, qc, D)

    blocks = lax.map(q_block, jnp.arange(nq))           # (nq, B, Hkv, G, qc, D)
    out = jnp.moveaxis(blocks, 0, 3)                    # (B, Hkv, G, nq, qc, D)
    out = out.reshape(B, Hkv, G, Sp, D).transpose(0, 3, 1, 2, 4)
    return out.reshape(B, Sp, H, D)[:, :S].astype(q.dtype)


def decode_attention_partial(q: jax.Array, k_cache: jax.Array,
                             v_cache: jax.Array, slot_pos: jax.Array):
    """Partial (m, l, acc) statistics for flash-decoding over a slice of
    the cache slots. Shapes as in ``decode_attention`` but with any slot
    count; combine partials with log-sum-exp (see kernels/ops.py)."""
    B, _, H, D = q.shape
    Hkv = k_cache.shape[2]
    G = H // Hkv
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    qg = q.reshape(B, Hkv, G, D).astype(jnp.float32) * scale
    s = jnp.einsum("bkgd,bpkd->bkgp", qg, k_cache.astype(jnp.float32))
    s = jnp.where((slot_pos >= 0)[:, None, None, :], s, NEG_INF)
    m = s.max(-1)                                          # (B,Hkv,G)
    p = jnp.exp(s - m[..., None])
    p = jnp.where((slot_pos >= 0)[:, None, None, :], p, 0.0)
    l = p.sum(-1)
    acc = jnp.einsum("bkgp,bpkd->bkgd", p, v_cache.astype(jnp.float32))
    return m, l, acc


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     slot_pos: jax.Array) -> jax.Array:
    """Single-token attention against a (ring-)cache.

    q: (B, 1, H, D); caches: (B, Smax, Hkv, D); slot_pos: (B, Smax) int32
    absolute position stored in each slot, -1 for empty. Keys are stored
    already position-encoded, so only validity masking is needed.
    """
    B, _, H, D = q.shape
    Hkv = k_cache.shape[2]
    G = H // Hkv
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    qg = q.reshape(B, Hkv, G, D).astype(jnp.float32) * scale
    s = jnp.einsum("bkgd,bpkd->bkgp", qg, k_cache.astype(jnp.float32))
    s = jnp.where((slot_pos >= 0)[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgp,bpkd->bkgd", p, v_cache.astype(jnp.float32))
    return out.reshape(B, 1, H, D).astype(q.dtype)


# ------------------------------------------------------------------
# selective scan (Mamba-1 recurrence) — naive sequential oracle
# ------------------------------------------------------------------
def selective_scan(dt, x, Bm, Cm, A, h0=None):
    """dt, x: (B, S, di); Bm, Cm: (B, S, N); A: (di, N).
    Returns (y (B, S, di), h_last (B, di, N)); h0 defaults to zeros."""
    B, S, di = x.shape
    N = A.shape[-1]
    if h0 is None:
        h0 = jnp.zeros((B, di, N), jnp.float32)

    def step(h, t):
        a = jnp.exp(dt[:, t, :, None] * A)                 # (B, di, N)
        h = a * h + (dt[:, t] * x[:, t])[..., None] * Bm[:, t, None, :]
        y = jnp.einsum("ben,bn->be", h, Cm[:, t])
        return h, y

    h_last, ys = lax.scan(step, h0, jnp.arange(S))
    return jnp.moveaxis(ys, 0, 1), h_last


# ------------------------------------------------------------------
# fused dequant-matmul (QLoRA backbone hot path)
# ------------------------------------------------------------------
def quant_matmul(x: jax.Array, qt: qlib.QTensor) -> jax.Array:
    """x: (..., K) @ dequant(qt): (K, N) -> (..., N).

    ``qt`` may cover a K zero-padded to a block multiple (the odd-K
    ``blockwise_quant`` contract); x's contraction dim zero-pads to
    match, which contracts exactly like slicing the pad rows off."""
    w = qlib.dequantize(qt, x.dtype)
    Kq, K = w.shape[-2], x.shape[-1]
    if Kq != K:
        if Kq < K or (Kq - K) >= qt.block:
            raise ValueError(
                f"quantized contraction dim {Kq} incompatible with "
                f"x's {K} (block {qt.block})")
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, Kq - K)])
    if w.ndim > 2:
        # stacked (per-client / per-layer) QTensor: contract pairwise
        # along the shared leading axes — the serve plane's vmapped
        # per-tenant slabs executed un-vmapped
        lead = w.shape[:-2]
        if x.shape[:len(lead)] != lead:
            raise ValueError(
                f"stacked quant_matmul needs matching lead dims: x "
                f"{x.shape} vs dequant(qt) {w.shape}")
        if x.ndim == w.ndim - 1:              # one row per stack entry
            return (x[..., None, :] @ w)[..., 0, :]
        return jnp.matmul(x, w)
    return jnp.einsum("...k,kn->...n", x, w)


# ------------------------------------------------------------------
# fused LoRA matmul (the QLoRA arm's whole linear layer)
# ------------------------------------------------------------------
def lora_matmul(x: jax.Array, w, a: jax.Array, b: jax.Array, *,
                scale: float) -> jax.Array:
    """``y = x @ W(+dequant) + scale·(x@A)@B`` with fp32 accumulation,
    cast back to ``x.dtype`` — the parity oracle and CPU execution path
    of the fused Pallas LoRA kernel (``kernels.lora_matmul``). ``w``
    may be a :class:`~repro.core.quant.QTensor` (odd-K pad contract as
    in :func:`quant_matmul`) or a dense matrix."""
    xf = x.astype(jnp.float32)
    if isinstance(w, qlib.QTensor):
        base = quant_matmul(xf, w)
    else:
        base = jnp.einsum("...k,kn->...n", xf, w.astype(jnp.float32))
    h = jnp.einsum("...k,kr->...r", xf, a.astype(jnp.float32))
    delta = jnp.einsum("...r,rn->...n", h, b.astype(jnp.float32))
    return (base + scale * delta).astype(x.dtype)


# ------------------------------------------------------------------
# blockwise quantization (communication compression / KV quant)
# ------------------------------------------------------------------
def blockwise_quant(x: jax.Array, *, bits: int = 8, block: int = 128,
                    mode: str = "linear") -> qlib.QTensor:
    """Same contract as the Pallas kernel, including odd K: a
    contraction dim not divisible by the block zero-pads up to the next
    block multiple (pad rows never perturb a block's absmax scale), the
    payload covers the padded K, and ``orig_shape`` records the true
    shape — callers slice dequantized rows ``[:K]``."""
    *lead, K, N = x.shape
    blk = min(block, K)
    Kp = -(-K // blk) * blk
    if Kp == K:
        return qlib.quantize(x, bits=bits, block=block, mode=mode)
    pad = [(0, 0)] * len(lead) + [(0, Kp - K), (0, 0)]
    qt = qlib.quantize(jnp.pad(x, pad), bits=bits, block=block,
                       mode=mode)
    return dataclasses.replace(qt, orig_shape=tuple(x.shape))


# ------------------------------------------------------------------
# grouped matmul over the experts of a dropless MoE
# ------------------------------------------------------------------
def _gmm_rows(x, qt: qlib.QTensor, tile_expert, transpose):
    R = x.shape[0]
    tm = R // tile_expert.shape[0]
    row_e = jnp.repeat(tile_expert, tm)
    w = qlib.maybe_dequantize(qt, jnp.float32).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    y = 0.0
    for e in range(w.shape[0]):
        we = w[e].T if transpose else w[e]
        y = y + jnp.where((row_e == e)[:, None], xf @ we, 0.0)
    return y.astype(x.dtype)


def moe_gmm(x, qt: qlib.QTensor, tile_expert):
    """``x (R, K)`` rows grouped by expert in tiles of ``R /
    len(tile_expert)`` rows, tile ``i`` of expert ``tile_expert[i]``:
    each row times its expert's dequantized ``(K, N)`` weight, f32
    accumulation — the oracle and CPU path of ``kernels.moe_gmm``."""
    return _gmm_rows(x, qt, tile_expert, False)


def moe_gmm_t(g, qt: qlib.QTensor, tile_expert):
    """``g (R, N)`` times each row's expert's weight transposed."""
    return _gmm_rows(g, qt, tile_expert, True)
