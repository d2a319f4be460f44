"""Pallas TPU fused LoRA matmul — the QLoRA arm's whole linear layer
in one kernel (§III-C).

Computes ``y = x @ dequant(W_q) + scale * (x @ A) @ B`` without ever
materializing the dequantized weight: the quantized tiles stream
HBM→VMEM and are dequantized in-register exactly as in
``kernels.quant_matmul`` (the shared ``dequant_tile``), while the LoRA
factors ride the same grid — A is blocked along the contraction dim by
the K tiles (an ``(bm, r)`` f32 VMEM scratch accumulates ``x @ A``
alongside the main ``(bm, bn)`` accumulator), and B joins at the final
K tile with one tiny ``(r, bn)`` gemm before the flush. All accumulation
is fp32.

Precision. Every product is the one a float32 gemm at ``HIGHEST`` forms.
A bfloat16 ``x`` (the trunk's activations) is exact in one bf16 term, so
of HIGHEST's six bf16 MXU passes three multiply zeros; the kernel keeps
the x tile in bf16 and splits the dequantized f32 weight tile (and A)
into three bf16 terms (``split_bf16x3``): ``x@w_hi + x@w_mid + x@w_lo``,
three passes of exact bf16 x bf16 products accumulated in f32. Three
terms because a dequantized weight carries a full 24-bit mantissa (an
NF4 code times an f32 absmax): two terms would cut it to 16 bits. Any
other ``x`` dtype takes the float32 HIGHEST route.

``x @ A`` depends only on the row tile and the K tile, so the grid
(``mi, ni, ki``, ``ki`` minormost) accumulates it into the ``(bm, r)``
scratch during the ``ni == 0`` sweep only, and every later column tile
of the row reuses it; ``ni`` and ``ki`` are declared ``arbitrary`` so no
megacore split can reorder them.

``quant_matmul_t`` is the backward-pass companion: ``g @ dequant(W)ᵀ``
through the same streamed tiles (grid minormost over the N blocks, the
output tile indexed by K tile), which is the ``dx``-through-Wᵀ
gemm of the custom VJP in ``kernels.ops.lora_matmul``.

TARGET: TPU. Validated with interpret=True vs ``kernels/ref.py``
(``ref.lora_matmul`` — also the CPU execution path), and compiled for
v5e by tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant import QTensor
from repro.kernels.quant_matmul import (HIGHEST, dequant_tile,
                                        groups_per_tile, pad_groups,
                                        row_tile)


def _dot(a, b, contract):
    """f32 x f32 at HIGHEST: both operands split into three bf16 terms,
    six MXU passes, every product exact and accumulated in f32."""
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               precision=HIGHEST,
                               preferred_element_type=jnp.float32)


def _top16(v):
    """f32 ``v`` cut to its top 16 bits (sign, exponent, 7 mantissa
    bits): a value bf16 holds exactly."""
    u = jax.lax.bitcast_convert_type(v, jnp.uint32)
    return jax.lax.bitcast_convert_type(u & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def split_bf16x3(w):
    """f32 ``w`` as bf16 ``(hi, mid, lo)`` whose f32 sum is ``w``
    exactly: ``hi`` and ``mid`` each cut 8 of the 24 mantissa bits off
    what is left, every remainder is exact in f32, and the last 8 bits
    fit ``lo``. Cutting rather than rounding matches the chip's own
    HIGHEST split: on a v5e the three-pass products agree with the f32
    HIGHEST route bitwise."""
    hi = _top16(w)
    r = w - hi
    mid = _top16(r)
    return (hi.astype(jnp.bfloat16), mid.astype(jnp.bfloat16),
            (r - mid).astype(jnp.bfloat16))


def _dot_bf16x3(x, w):
    """bf16 ``x (m, k)`` @ f32 ``w (k, n)`` in three bf16 passes: the
    products HIGHEST forms for an ``x`` that bf16 holds exactly."""
    p_hi, p_mid, p_lo = (jax.lax.dot_general(
        x, t, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) for t in split_bf16x3(w))
    return p_hi + (p_mid + p_lo)


def _lora_kernel(x_ref, q_ref, s_ref, a_ref, b_ref, o_ref, acc_ref, h_ref,
                 *, bits, mode, nk, scale):
    ni, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((ni == 0) & (ki == 0))
    def _init_h():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[...]                                  # (bm, gk·block)
    if x.dtype == jnp.bfloat16:
        mm = _dot_bf16x3
    else:
        x = x.astype(jnp.float32)
        mm = functools.partial(_dot, contract=((1,), (0,)))
    w = dequant_tile(q_ref, s_ref, bits=bits, mode=mode)
    acc_ref[...] += mm(x, w)

    @pl.when(ni == 0)
    def _h():
        # (bm, r) += x @ A over this K tile; complete at the end of the
        # row's first column sweep, reused by every later ni
        h_ref[...] += mm(x, a_ref[...].astype(jnp.float32))

    @pl.when(ki == nk - 1)
    def _flush():
        delta = _dot(h_ref[...], b_ref[...].astype(jnp.float32),
                     ((1,), (0,)))                  # (bm, bn) = h @ B
        o_ref[...] = (acc_ref[...] + scale * delta).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "block_m",
                                             "block_n", "interpret"))
def lora_matmul(x, qt: QTensor, a, b, *, scale: float, block_m=256,
                block_n=256, interpret=False):
    """``x (..., K) @ dequant(qt (K, N)) + scale·(x@A)@B -> (..., N)``
    in one kernel. ``qt`` may cover a K zero-padded to a block multiple
    (the odd-K ``blockwise_quant`` contract) — x and A zero-pad rows to
    match, which contracts identically. ``a``: (K, r); ``b``: (r, N).
    A bfloat16 ``x`` takes the three-pass route, any other dtype the
    f32 HIGHEST one (module docstring); the output is ``x.dtype``."""
    *lead, K = x.shape
    M = math.prod(lead)
    x2 = x.reshape(M, K)
    block = qt.block
    Kq = qt.q.shape[0] * block
    if Kq < K or (Kq - K) >= block:
        raise ValueError(
            f"quantized contraction dim {Kq} incompatible with "
            f"x's {K} (block {block})")
    if a.shape[0] != K:
        raise ValueError(f"LoRA A rows {a.shape[0]} != contraction {K}")
    N = qt.q.shape[-1]
    r = a.shape[-1]
    gk = groups_per_tile(qt.q.shape[0], block)
    bm = row_tile(block_m, M, x.dtype)
    bn = min(block_n, N)
    Np = -(-N // bn) * bn
    qv, sv = pad_groups(qt, gk, Np - N)
    Gp = qv.shape[0]
    Mp = -(-M // bm) * bm
    x2 = jnp.pad(x2, ((0, Mp - M), (0, Gp * block - K)))
    a2 = jnp.pad(a, ((0, Gp * block - K), (0, 0)))
    b2 = jnp.pad(b, ((0, 0), (0, Np - N)))
    rows = qv.shape[1]                     # block or block//2 (packed)
    nk = Gp // gk
    tk = gk * block
    out = pl.pallas_call(
        functools.partial(_lora_kernel, bits=qt.bits, mode=qt.mode,
                          nk=nk, scale=float(scale)),
        grid=(Mp // bm, Np // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, tk), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((gk, rows, bn), lambda mi, ni, ki: (ki, 0, ni)),
            pl.BlockSpec((gk, 1, bn), lambda mi, ni, ki: (ki, 0, ni)),
            pl.BlockSpec((tk, r), lambda mi, ni, ki: (ki, 0)),
            pl.BlockSpec((r, bn), lambda mi, ni, ki: (0, ni)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bm, r), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(x2, qv, sv, a2, b2)
    return out[:M, :N].reshape(*lead, N)


def _t_kernel(g_ref, q_ref, s_ref, o_ref, acc_ref, *, bits, mode, nn):
    ni = pl.program_id(2)

    @pl.when(ni == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g = g_ref[...].astype(jnp.float32)              # (bm, bn)
    w = dequant_tile(q_ref, s_ref, bits=bits, mode=mode)
    acc_ref[...] += _dot(g, w, ((1,), (1,)))        # (bm, tk) += g @ wᵀ

    @pl.when(ni == nn - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                             "interpret"))
def quant_matmul_t(g, qt: QTensor, *, block_m=256, block_n=256,
                   interpret=False):
    """``g (..., N) @ dequant(qt (K, N))ᵀ -> (..., Kq)`` — the
    transposed contraction of ``quant_matmul``, streaming the identical
    quantized tiles (the dx gemm of the fused LoRA VJP). The output
    covers the padded Kq; callers slice ``[..., :K]``."""
    *lead, N = g.shape
    M = math.prod(lead)
    g2 = g.reshape(M, N)
    if N != qt.q.shape[-1]:
        raise ValueError(
            f"contraction dim {N} != quantized N {qt.q.shape[-1]}")
    block = qt.block
    Kq = qt.q.shape[0] * block
    gk = groups_per_tile(qt.q.shape[0], block)
    bm = row_tile(block_m, M, g.dtype)
    bn = min(block_n, N)
    Np = -(-N // bn) * bn
    qv, sv = pad_groups(qt, gk, Np - N)
    Gp = qv.shape[0]
    Mp = -(-M // bm) * bm
    g2 = jnp.pad(g2, ((0, Mp - M), (0, Np - N)))
    rows = qv.shape[1]
    tk = gk * block
    nn = Np // bn
    out = pl.pallas_call(
        functools.partial(_t_kernel, bits=qt.bits, mode=qt.mode, nn=nn),
        grid=(Mp // bm, Gp // gk, nn),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda mi, ki, ni: (mi, ni)),
            pl.BlockSpec((gk, rows, bn), lambda mi, ki, ni: (ki, 0, ni)),
            pl.BlockSpec((gk, 1, bn), lambda mi, ki, ni: (ki, 0, ni)),
        ],
        out_specs=pl.BlockSpec((bm, tk), lambda mi, ki, ni: (mi, ki)),
        out_shape=jax.ShapeDtypeStruct((Mp, Gp * block), g.dtype),
        scratch_shapes=[pltpu.VMEM((bm, tk), jnp.float32)],
        interpret=interpret,
    )(g2, qv, sv)
    return out[:M, :Kq].reshape(*lead, Kq)
