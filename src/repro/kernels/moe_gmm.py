"""Pallas TPU grouped NF4 matmul over the experts of a dropless MoE.

The routed token copies arrive sorted by expert, each expert's group
padded to a whole number of ``tm``-row tiles (``models/moe.py``), so
every row tile belongs to one expert. A scalar-prefetched tile -> expert
map picks, for row tile ``mi``, the quantized weight tiles of expert
``tile_expert[mi]`` from the stacked ``(E, K, N)`` QTensor; they stream
HBM -> VMEM and are dequantized in-register as in
``kernels.quant_matmul`` (the shared ``dequant_tile``). Every row tile
of the static buffer is computed, those past the groups too (their rows
are zeros and so are their outputs): the work of a call is fixed by its
shapes and does not follow the routing.

``moe_gmm`` (instruction ``moe_gmm_fwd``): ``x (R, K) @ W_e (K, N)``.
``moe_gmm_t`` (instruction ``moe_gmm_dx``): ``g (R, N) @ W_eᵀ`` — the
input gradient; the experts are frozen, so there is no weight gradient.

Precision. Every product is the one a float32 gemm at ``HIGHEST``
forms: a bfloat16 operand meets the dequantized f32 weight split into
three bfloat16 terms (``kernels.lora_matmul.split_bf16x3``), three MXU
passes; any other dtype takes the f32 HIGHEST dot. Accumulation is f32.

TARGET: TPU. Validated with interpret=True against ``kernels/ref``'s
per-expert einsum, and compiled for v5e by tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant import QTensor
from repro.kernels.lora_matmul import split_bf16x3
from repro.kernels.quant_matmul import HIGHEST, dequant_tile, groups_per_tile

VMEM_LIMIT = 64 * 1024 * 1024


def _mm(a, w, contract):
    """``a`` against the f32 weight tile ``w`` over ``contract``: three
    exact bf16 passes for a bf16 ``a``, f32 HIGHEST otherwise."""
    dims = (contract, ((), ()))
    if a.dtype == jnp.bfloat16:
        p_hi, p_mid, p_lo = (jax.lax.dot_general(
            a, t, dims, preferred_element_type=jnp.float32)
            for t in split_bf16x3(w))
        return p_hi + (p_mid + p_lo)
    return jax.lax.dot_general(a.astype(jnp.float32), w, dims,
                               precision=HIGHEST,
                               preferred_element_type=jnp.float32)


def _kernel(te_ref, a_ref, q_ref, s_ref, o_ref, acc_ref, *, bits, mode,
            n_inner, contract):
    inner = pl.program_id(2)

    @pl.when(inner == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = dequant_tile(q_ref, s_ref, bits=bits, mode=mode)
    acc_ref[...] += _mm(a_ref[...], w, contract)

    @pl.when(inner == n_inner - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _padded(qt: QTensor, gk: int, bn: int):
    """Payload and scales padded to whole K and N tiles (zero scales:
    the pads dequantize to zeros)."""
    E, G, _, N = qt.q.shape
    pad = ((0, 0), (0, -G % gk), (0, 0), (0, -N % bn))
    return jnp.pad(qt.q, pad), jnp.pad(qt.scales, pad)


def _tiles(qt: QTensor, block_k: int, block_n: int):
    gk = max(groups_per_tile(qt.q.shape[1], qt.block),
             block_k // qt.block)
    gk = min(gk, qt.q.shape[1])
    bn = min(block_n, qt.q.shape[-1])
    return gk, bn


def _call(a, qt, tile_expert, *, transpose, block_k, block_n, interpret):
    R = a.shape[0]
    tm = R // tile_expert.shape[0]
    gk, bn = _tiles(qt, block_k, block_n)
    qv, sv = _padded(qt, gk, bn)
    _, Gp, rows, Np = qv.shape
    tk, nk, nn = gk * qt.block, Gp // gk, Np // bn
    if transpose:       # g (R, N) @ Wᵀ -> (R, K): out tile (mi, ki), inner ni
        a = jnp.pad(a, ((0, 0), (0, Np - a.shape[1])))
        grid, n_inner, out_cols, name = (R // tm, nk, nn), nn, Gp * qt.block, \
            "moe_gmm_dx"
        a_spec = pl.BlockSpec((tm, bn), lambda mi, ki, ni, te: (mi, ni))
        w_idx = lambda mi, ki, ni, te: (te[mi], ki, 0, ni)
        o_spec = pl.BlockSpec((tm, tk), lambda mi, ki, ni, te: (mi, ki))
        contract, acc = ((1,), (1,)), (tm, tk)
    else:               # x (R, K) @ W -> (R, N): out tile (mi, ni), inner ki
        a = jnp.pad(a, ((0, 0), (0, Gp * qt.block - a.shape[1])))
        grid, n_inner, out_cols, name = (R // tm, nn, nk), nk, Np, \
            "moe_gmm_fwd"
        a_spec = pl.BlockSpec((tm, tk), lambda mi, ni, ki, te: (mi, ki))
        w_idx = lambda mi, ni, ki, te: (te[mi], ki, 0, ni)
        o_spec = pl.BlockSpec((tm, bn), lambda mi, ni, ki, te: (mi, ni))
        contract, acc = ((1,), (0,)), (tm, bn)
    sq = pl.Squeezed()
    out = pl.pallas_call(
        functools.partial(_kernel, bits=qt.bits, mode=qt.mode,
                          n_inner=n_inner, contract=contract),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid,
            in_specs=[a_spec,
                      pl.BlockSpec((sq, gk, rows, bn), w_idx),
                      pl.BlockSpec((sq, gk, 1, bn), w_idx)],
            out_specs=o_spec,
            scratch_shapes=[pltpu.VMEM(acc, jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((R, out_cols), a.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=name,
    )(tile_expert.astype(jnp.int32), a, qv, sv)
    return out


@functools.partial(jax.jit, static_argnames=("block_k", "block_n",
                                             "interpret"))
def moe_gmm(x, qt: QTensor, tile_expert, *, block_k=512, block_n=512,
            interpret=False):
    """``x (R, K)``, rows grouped by expert in tiles of ``R /
    len(tile_expert)``; ``qt``: stacked ``(E, K, N)`` QTensor;
    ``tile_expert (R/tm,)`` int32. Returns ``(R, N)`` in ``x.dtype``."""
    N = qt.q.shape[-1]
    return _call(x, qt, tile_expert, transpose=False,
                 block_k=block_k, block_n=block_n,
                 interpret=interpret)[:, :N]


@functools.partial(jax.jit, static_argnames=("block_k", "block_n",
                                             "interpret"))
def moe_gmm_t(g, qt: QTensor, tile_expert, *, block_k=512, block_n=512,
              interpret=False):
    """``g (R, N) @ W_eᵀ -> (R, K)`` row tile by row tile: the input
    gradient of :func:`moe_gmm`, streaming the same quantized tiles."""
    K = qt.q.shape[1] * qt.block
    return _call(g, qt, tile_expert, transpose=True,
                 block_k=block_k, block_n=block_n,
                 interpret=interpret)[:, :K]
