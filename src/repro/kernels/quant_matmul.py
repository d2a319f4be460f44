"""Pallas TPU fused dequant-matmul — the QLoRA backbone hot path (§III-C).

Computes y = x @ dequant(W_q) without ever materializing the dequantized
weight in HBM: int8 / packed-int4 / NF4 tiles stream HBM→VMEM, are
dequantized in-register, and feed the MXU directly. Quantization blocks
run along the contraction dim; one K tile spans as many quant groups as
it takes to fill whole 128-lane tiles of x (two groups at the FL path's
block 64), so the grid's minormost dimension walks K tiles with a f32
accumulator tile in VMEM scratch.

TARGET: TPU. Validated with interpret=True vs kernels/ref.py, and
compiled for v5e by tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant import QTensor, nf4_lookup

LANES = 128
HIGHEST = jax.lax.Precision.HIGHEST


def groups_per_tile(n_groups: int, block: int) -> int:
    """Quant groups one K tile spans: the fewest whose rows fill whole
    128-lane tiles (Mosaic tiles the minor dim by 128), or all of them
    when the whole contraction is shorter than that."""
    return min(math.lcm(block, LANES) // block, n_groups)


def pad_groups(qt: QTensor, gk: int, n_pad: int):
    """``qt``'s payload and scales padded to a multiple of ``gk`` quant
    groups and ``n_pad`` extra columns. Pad groups and columns carry zero
    scales, so they dequantize to exact zeros and contract inertly."""
    G = qt.q.shape[0]
    g_pad = -G % gk
    if not (g_pad or n_pad):
        return qt.q, qt.scales
    pad = ((0, g_pad), (0, 0), (0, n_pad))
    return jnp.pad(qt.q, pad), jnp.pad(qt.scales, pad)


def dequant_tile(q_ref, s_ref, *, bits, mode):
    """Dequantize one ``(gk, block[/2], bn)`` VMEM tile in-register into
    ``(gk·block, bn)`` f32: unpack int4 halves, map NF4 codes through
    the codebook, apply each group's absmax scale. Shared by
    ``quant_matmul`` and the fused LoRA kernels (``kernels.lora_matmul``)
    so all stream the identical quantized layout."""
    tiles = []
    for j in range(q_ref.shape[0]):
        qv = q_ref[j].astype(jnp.int32)             # (block[/2], bn)
        if bits == 4:
            # packed row i: high nibble = row i, low = row i + block/2
            qv = jnp.concatenate([qv >> 4, qv & 0xF], axis=0)
            w = nf4_lookup(qv) if mode == "nf4" else \
                (qv - 8).astype(jnp.float32)
        else:
            w = qv.astype(jnp.float32)
        tiles.append(w * s_ref[j])
    return tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, axis=0)


def _kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, bits, mode, nk):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)              # (bm, gk·block)
    w = dequant_tile(q_ref, s_ref, bits=bits, mode=mode)
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def row_tile(block_m: int, M: int, dtype) -> int:
    """Row tile: ``block_m`` rounded to the dtype's sublane multiple
    (8 rows of f32, 16 of bf16), or all of M when M fits in one tile."""
    sub = 32 // jnp.dtype(dtype).itemsize
    if M <= block_m:
        return M
    return max(sub, block_m // sub * sub)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                             "interpret"))
def quant_matmul(x, qt: QTensor, *, block_m=256, block_n=256,
                 interpret=False):
    """x: (..., K) @ dequant(qt (K, N)) -> (..., N). ``qt`` may cover a
    K zero-padded to a block multiple (the odd-K ``blockwise_quant``
    contract); x zero-pads to match — the last block then contracts
    defined zeros instead of out-of-bounds reads."""
    *lead, K = x.shape
    M = math.prod(lead)
    x2 = x.reshape(M, K)
    block = qt.block
    Kq = qt.q.shape[0] * block
    if Kq < K or (Kq - K) >= block:
        raise ValueError(
            f"quantized contraction dim {Kq} incompatible with "
            f"x's {K} (block {block})")
    N = qt.q.shape[-1]
    gk = groups_per_tile(qt.q.shape[0], block)
    bm = row_tile(block_m, M, x.dtype)
    bn = min(block_n, N)
    Np = -(-N // bn) * bn
    qv, sv = pad_groups(qt, gk, Np - N)
    Gp = qv.shape[0]
    Mp = -(-M // bm) * bm
    x2 = jnp.pad(x2, ((0, Mp - M), (0, Gp * block - K)))
    rows = qv.shape[1]                     # block or block//2 (packed)
    nk = Gp // gk
    out = pl.pallas_call(
        functools.partial(_kernel, bits=qt.bits, mode=qt.mode, nk=nk),
        grid=(Mp // bm, Np // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, gk * block), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((gk, rows, bn), lambda mi, ni, ki: (ki, 0, ni)),
            pl.BlockSpec((gk, 1, bn), lambda mi, ni, ki: (ki, 0, ni)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x2, qv, sv)
    return out[:M, :N].reshape(*lead, N)
