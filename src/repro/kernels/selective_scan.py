"""Pallas TPU selective scan (Mamba-1 recurrence), forward and backward.

    h_t = exp(Δ_t ⊗ A) ∘ h_{t-1} + (Δ_t x_t) ⊗ B_t,   y_t = ⟨h_t, C_t⟩

Layout. The state of a 128-channel block of d_inner is held as ``(N,
128)``: the channels on the lanes, the N state entries on the sublanes,
so each time step is a few full-vreg VPU ops. Δ and x arrive as ``(S/8,
8, d_inner)`` (eight time steps a sublane tile, indexed by a leading
dimension inside the chunk loop), and B and C lane-broadcast as ``(S/8,
8, N, 128)`` so that ``B_t`` is already the ``(N, 128)`` column the
update multiplies — no lane/sublane relayout inside the loop.

Grid ``(B, S/chunk, d_inner/128)`` with the channel blocks minormost:
B and C are fetched once per chunk and reused by every channel block,
and each channel block's running state lives in a ``(nd, N, 128)`` VMEM
scratch across chunks.

``ssm_scan_fwd`` writes y, the final state, and the state entering each
chunk, ``(B, S/chunk, N, d_inner)`` f32 — the backward's only residual
beyond the inputs. ``ssm_scan_bwd`` walks the chunks in reverse: it
recomputes the chunk's states in VMEM from its boundary state, then runs
the reverse recurrence carrying ``dh`` and emits exact cotangents of Δ,
x and A, and of B and C as per-lane partials ``(S, N, 128)`` that the
caller sums (the sum over channel blocks is taken inside the kernel).

Padded time steps carry Δ = 0, so a = 1 and b = 0: the state passes
through unchanged, and their cotangents vanish or are sliced off.

TARGET: TPU. Validated with interpret=True against ``kernels/ref``'s
sequential scan and its autodiff, and compiled for v5e by
tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUB = 8
VMEM_LIMIT = 64 * 1024 * 1024


def chunk_len(S: int, chunk: int) -> int:
    """Time steps a grid step covers: ``chunk``, or all of a shorter S,
    in whole sublane tiles."""
    return min(chunk, -(-S // SUB) * SUB)


def _rows(blk, i):
    return blk[i:i + 1, :]                          # (1, 128) of (8, 128)


def _put_row(acc, i, row):
    ids = jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
    return jnp.where(ids == i, jnp.broadcast_to(row, acc.shape), acc)


def _fwd_kernel(dt_ref, x_ref, b_ref, c_ref, a_ref, y_ref, hs_ref, hl_ref,
                h_scr, *, groups):
    ci, di = pl.program_id(1), pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[di] = jnp.zeros(h_scr.shape[1:], jnp.float32)

    A = a_ref[...]                                  # (N, 128)
    h0 = h_scr[di]
    hs_ref[0, 0] = h0

    def group(g, h):
        dtb, xb = dt_ref[0, g], x_ref[0, g]         # (8, 128)
        yb = jnp.zeros_like(dtb)
        for i in range(SUB):
            dt_i = _rows(dtb, i)
            h = jnp.exp(dt_i * A) * h + (dt_i * _rows(xb, i)) * b_ref[0, g, i]
            yb = _put_row(yb, i, jnp.sum(h * c_ref[0, g, i], axis=0,
                                         keepdims=True))
        y_ref[0, g] = yb
        return h

    h = jax.lax.fori_loop(0, groups, group, h0)
    h_scr[di] = h
    hl_ref[0] = h


def _bwd_kernel(dt_ref, x_ref, b_ref, c_ref, a_ref, gy_ref, hs_ref, ghl_ref,
                ddt_ref, dx_ref, db_ref, dc_ref, da_ref, dh_scr, hist,
                *, groups):
    bi, ci, di = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when((bi == 0) & (ci == 0))
    def _init_da():
        da_ref[di] = jnp.zeros(da_ref.shape[1:], jnp.float32)

    @pl.when(ci == 0)
    def _init_dh():
        dh_scr[di] = ghl_ref[0]

    @pl.when(di == 0)
    def _init_dbc():
        db_ref[...] = jnp.zeros_like(db_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    A = a_ref[...]
    hist[0] = hs_ref[0, 0]

    def fwd_group(g, h):                            # the chunk's states
        dtb, xb = dt_ref[0, g], x_ref[0, g]
        for i in range(SUB):
            dt_i = _rows(dtb, i)
            h = jnp.exp(dt_i * A) * h + (dt_i * _rows(xb, i)) * b_ref[0, g, i]
            hist[g * SUB + i + 1] = h
        return h

    jax.lax.fori_loop(0, groups, fwd_group, hist[0])

    def bwd_group(k, carry):
        dh, dA = carry
        g = groups - 1 - k
        dtb, xb, gyb = dt_ref[0, g], x_ref[0, g], gy_ref[0, g]
        ddt_b, dx_b = jnp.zeros_like(dtb), jnp.zeros_like(dtb)
        for i in reversed(range(SUB)):
            t = g * SUB + i
            dt_i, x_i, gy_i = _rows(dtb, i), _rows(xb, i), _rows(gyb, i)
            b_t = b_ref[0, g, i]
            h_t = hist[t + 1]
            dh = dh + c_ref[0, g, i] * gy_i         # dL/dh_t
            dc_ref[0, g, i] += h_t * gy_i
            dtx = dt_i * x_i
            db_ref[0, g, i] += dh * dtx
            d_dtx = jnp.sum(dh * b_t, axis=0, keepdims=True)
            a = jnp.exp(dt_i * A)
            daa = dh * hist[t] * a                  # dL/d(Δ_t A)
            ddt_b = _put_row(ddt_b, i, jnp.sum(daa * A, axis=0,
                                               keepdims=True) + d_dtx * x_i)
            dx_b = _put_row(dx_b, i, d_dtx * dt_i)
            dA = dA + daa * dt_i
            dh = dh * a                             # dL/dh_{t-1} so far
        ddt_ref[0, g] = ddt_b
        dx_ref[0, g] = dx_b
        return dh, dA

    dh, dA = jax.lax.fori_loop(0, groups, bwd_group,
                               (dh_scr[di], jnp.zeros_like(A)))
    dh_scr[di] = dh
    da_ref[di] += dA


def _layout(dt, x, Bm, Cm, A, L):
    """Pad S to a chunk multiple and d_inner to whole lane blocks (pad
    channels have Δ = 0 and stay at zero), and lay the operands out for
    the grid."""
    B, S, di = x.shape
    N = A.shape[-1]
    Sp = -(-S // L) * L
    dp = -(-di // LANES) * LANES
    pad = lambda t, c=0: jnp.pad(t.astype(jnp.float32),
                                 ((0, 0), (0, Sp - S), (0, c)))
    rows = lambda t: pad(t, dp - di).reshape(B, Sp // SUB, SUB, dp)
    lanes = lambda t: jnp.broadcast_to(
        pad(t)[..., None], (B, Sp, N, LANES)).reshape(
            B, Sp // SUB, SUB, N, LANES)
    At = jnp.pad(A.astype(jnp.float32).T, ((0, 0), (0, dp - di)))
    return rows(dt), rows(x), lanes(Bm), lanes(Cm), At, Sp, dp


def _specs(L, N, nc, reverse=False):
    c = (lambda ci: nc - 1 - ci) if reverse else (lambda ci: ci)
    g = L // SUB
    row = pl.BlockSpec((1, g, SUB, LANES), lambda b, ci, d: (b, c(ci), 0, d))
    lane = pl.BlockSpec((1, g, SUB, N, LANES),
                        lambda b, ci, d: (b, c(ci), 0, 0, 0))
    at = pl.BlockSpec((N, LANES), lambda b, ci, d: (0, d))
    return row, lane, at


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def scan_fwd(dt, x, Bm, Cm, A, *, chunk=128, interpret=False):
    """dt, x: (B, S, di); Bm, Cm: (B, S, N); A: (di, N); h0 = 0.
    Returns (y (B, S, di) f32, h_last (B, di, N) f32, the state entering
    each chunk (B, S/chunk, N, d_inner padded to lanes) f32)."""
    B, S, di = x.shape
    N = A.shape[-1]
    L = chunk_len(S, chunk)
    dt4, x4, bb, cb, At, Sp, dp = _layout(dt, x, Bm, Cm, A, L)
    nc, nd = Sp // L, dp // LANES
    row, lane, at = _specs(L, N, nc)
    y4, hs, hl = pl.pallas_call(
        functools.partial(_fwd_kernel, groups=L // SUB),
        grid=(B, nc, nd),
        in_specs=[row, row, lane, lane, at],
        out_specs=[row,
                   pl.BlockSpec((1, 1, N, LANES),
                                lambda b, ci, d: (b, ci, 0, d)),
                   pl.BlockSpec((1, N, LANES), lambda b, ci, d: (b, 0, d))],
        out_shape=[jax.ShapeDtypeStruct((B, Sp // SUB, SUB, dp),
                                        jnp.float32),
                   jax.ShapeDtypeStruct((B, nc, N, dp), jnp.float32),
                   jax.ShapeDtypeStruct((B, N, dp), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((nd, N, LANES), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
        name="ssm_scan_fwd",
    )(dt4, x4, bb, cb, At)
    y = y4.reshape(B, Sp, dp)[:, :S, :di]
    return y, jnp.swapaxes(hl, 1, 2)[:, :di], hs


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def scan_bwd(dt, x, Bm, Cm, A, hs, gy, gh, *, chunk=128, interpret=False):
    """Cotangents of :func:`scan_fwd`'s inputs from those of its y and
    h_last (``gy`` (B, S, di), ``gh`` (B, di, N)) and its chunk states.
    Returns (ddt, dx, dB, dC, dA) in the inputs' shapes, f32."""
    B, S, di = x.shape
    N = A.shape[-1]
    L = chunk_len(S, chunk)
    dt4, x4, bb, cb, At, Sp, dp = _layout(dt, x, Bm, Cm, A, L)
    gy4 = jnp.pad(gy.astype(jnp.float32),
                  ((0, 0), (0, Sp - S), (0, dp - di))).reshape(
                      B, Sp // SUB, SUB, dp)
    gh4 = jnp.pad(jnp.swapaxes(gh, 1, 2).astype(jnp.float32),
                  ((0, 0), (0, 0), (0, dp - di)))
    nc, nd = Sp // L, dp // LANES
    row, lane, at = _specs(L, N, nc, reverse=True)
    rs = lambda *sh: jax.ShapeDtypeStruct(sh, jnp.float32)
    ddt4, dx4, dbw, dcw, da3 = pl.pallas_call(
        functools.partial(_bwd_kernel, groups=L // SUB),
        grid=(B, nc, nd),
        in_specs=[row, row, lane, lane, at, row,
                  pl.BlockSpec((1, 1, N, LANES),
                               lambda b, ci, d: (b, nc - 1 - ci, 0, d)),
                  pl.BlockSpec((1, N, LANES), lambda b, ci, d: (b, 0, d))],
        out_specs=[row, row, lane, lane,
                   pl.BlockSpec((nd, N, LANES), lambda b, ci, d: (0, 0, 0))],
        out_shape=[rs(B, Sp // SUB, SUB, dp), rs(B, Sp // SUB, SUB, dp),
                   rs(B, Sp // SUB, SUB, N, LANES),
                   rs(B, Sp // SUB, SUB, N, LANES), rs(nd, N, LANES)],
        scratch_shapes=[pltpu.VMEM((nd, N, LANES), jnp.float32),
                        pltpu.VMEM((L + 1, N, LANES), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
        name="ssm_scan_bwd",
    )(dt4, x4, bb, cb, At, gy4, hs, gh4)
    cut = lambda t: t.reshape(B, Sp, *t.shape[3:])[:, :S]
    dA = da3.transpose(1, 0, 2).reshape(N, dp)[:, :di].T
    return (cut(ddt4)[..., :di], cut(dx4)[..., :di], cut(dbw).sum(-1),
            cut(dcw).sum(-1), dA)
