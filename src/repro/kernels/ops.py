"""jit-ready wrappers around the attention / quantization hot spots.

Dispatch:
- Pallas TPU kernels, compiled, whenever the backend is a TPU;
- Pallas in interpret mode on another backend only when asked for
  (``REPRO_PALLAS=interpret``, the CPU kernel tests);
- under a production mesh Runtime, an explicit ``shard_map`` distribution
  (batch → dp axes, query heads padded to the ``model`` axis, KV expanded
  per local head; decode uses flash-decoding log-sum-exp combination over
  the slot-sharded cache) — relying on GSPMD propagation through the
  blocked-softmax scan replicates K/V across the batch axis, which is
  exactly the failure the explicit mapping removes;
- plain jnp reference otherwise (unit tests, CPU examples).
"""
from __future__ import annotations

import dataclasses
import os
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import quant as qlib
from repro.kernels import autotune, ref
from repro.models import runtime as rt_lib

_FORCE = os.environ.get("REPRO_PALLAS", "")  # "interpret" | "tpu" | ""


def _use_pallas() -> bool:
    """A TPU backend always takes the compiled kernels, whatever
    ``REPRO_PALLAS`` says. Elsewhere Pallas runs only in interpret mode,
    and only when asked for; asking for ``tpu`` there is an error, never
    a quiet interpret-mode run."""
    backend = jax.default_backend()
    if backend == "tpu":
        return True
    if _FORCE == "tpu":
        raise RuntimeError(
            f"REPRO_PALLAS=tpu but the JAX backend is {backend!r}: "
            "there is no TPU to compile the Pallas kernels for")
    return _FORCE == "interpret"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# -- trace-time path counters ------------------------------------------
# Incremented when a dispatch wrapper *traces* (once per compile, not per
# step), so CI can assert which implementation a program actually took —
# the "no silent fallback" guard: reset, build the program, then check
# e.g. KERNEL_TRACES["lora_linear_fused"] > 0.
KERNEL_TRACES: dict = {}


def trace_count(name: str, n: int = 1) -> None:
    KERNEL_TRACES[name] = KERNEL_TRACES.get(name, 0) + int(n)


def reset_kernel_traces() -> None:
    KERNEL_TRACES.clear()


def _kernel_flash(q, k, v, *, causal, window, q_chunk=512, k_chunk=512):
    if _use_pallas():
        from repro.kernels import flash_attention as fk
        trace_count("flash_attention_pallas")
        return fk.flash_attention(q, k, v, causal=causal, window=window,
                                  interpret=_interpret())
    trace_count("flash_attention_ref")
    return ref.flash_attention(q, k, v, causal=causal, window=window,
                               q_chunk=q_chunk, k_chunk=k_chunk)


def flash_attention(q, k, v, *, causal=True, window=None,
                    q_chunk=512, k_chunk=512):
    rt = rt_lib.get_runtime()
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    if rt is None:
        return _kernel_flash(q, k, v, causal=causal, window=window,
                             q_chunk=q_chunk, k_chunk=k_chunk)
    mesh, m, dp = rt.mesh, rt.tp_size, rt.dp_axes
    dp_sz = rt.dp_size
    if B % dp_sz:
        dp, dp_sz = (), 1
    G = H // Hkv
    Hp = -(-H // m) * m
    if Hp != H:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Hp - H), (0, 0)))
    Hl = Hp // m

    def local(q_l, k_l, v_l):
        r = lax.axis_index(rt.tp_axis)
        gids = r * Hl + jnp.arange(Hl)
        kv_ids = jnp.clip(gids, 0, H - 1) // G
        k_e = jnp.take(k_l, kv_ids, axis=2)
        v_e = jnp.take(v_l, kv_ids, axis=2)
        return _kernel_flash(q_l, k_e, v_e, causal=causal, window=window,
                             q_chunk=q_chunk, k_chunk=k_chunk)

    out = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(dp or None, None, rt.tp_axis, None),
                  P(dp or None, None, None, None),
                  P(dp or None, None, None, None)),
        out_specs=P(dp or None, None, rt.tp_axis, None),
        check_vma=False)(q, k, v)
    return out[:, :, :H]


def decode_attention(q, k_cache, v_cache, slot_pos):
    rt = rt_lib.get_runtime()
    B, _, H, D = q.shape
    M = k_cache.shape[1]
    if rt is None or M % rt.tp_size:
        return ref.decode_attention(q, k_cache, v_cache, slot_pos)
    mesh, dp = rt.mesh, rt.dp_axes
    if B % rt.dp_size:
        dp = ()

    def local(q_l, k_l, v_l, sp_l):
        mi, li, acci = ref.decode_attention_partial(q_l, k_l, v_l, sp_l)
        mg = lax.pmax(mi, rt.tp_axis)
        corr = jnp.exp(mi - mg)
        lg = lax.psum(li * corr, rt.tp_axis)
        accg = lax.psum(acci * corr[..., None], rt.tp_axis)
        out = accg / jnp.maximum(lg, 1e-30)[..., None]
        Bl = q_l.shape[0]
        return out.reshape(Bl, 1, H, D).astype(q_l.dtype)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(dp or None, None, None, None),
                  P(dp or None, rt.tp_axis, None, None),
                  P(dp or None, rt.tp_axis, None, None),
                  P(None, rt.tp_axis)),
        out_specs=P(dp or None, None, None, None),
        check_vma=False)(q, k_cache, v_cache, slot_pos)


def selective_scan(dt, x, Bm, Cm, A):
    """Mamba-1 recurrence from a zero state -> (y, h_last): the Pallas
    forward and backward kernels under a custom VJP on TPU; ``None`` on
    CPU, where the caller differentiates its chunked associative scan
    (models/ssm.py calls this from inside its shard_map body)."""
    if _use_pallas():
        trace_count("ssm_scan_pallas")
        return _ssm_scan(_interpret(), dt, x, Bm, Cm, A)
    return None


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ssm_scan(interpret, dt, x, Bm, Cm, A):
    return _ssm_scan_fwd(interpret, dt, x, Bm, Cm, A)[0]


def _ssm_scan_fwd(interpret, dt, x, Bm, Cm, A):
    from repro.kernels import selective_scan as sk
    y, h, hs = sk.scan_fwd(dt, x, Bm, Cm, A, interpret=interpret)
    return (y, h), (dt, x, Bm, Cm, A, hs)


def _ssm_scan_bwd(interpret, res, g):
    """The residuals are the inputs and the state entering each chunk
    (``(B, S/chunk, N, d_inner)``): the backward kernel recomputes each
    chunk's states from it, never the (B, S, d_inner, N) tensor."""
    from repro.kernels import selective_scan as sk
    trace_count("ssm_scan_pallas_bwd")
    dt, x, Bm, Cm, A, hs = res
    grads = sk.scan_bwd(dt, x, Bm, Cm, A, hs, *g, interpret=interpret)
    return tuple(d.astype(v.dtype) for d, v in zip(grads,
                                                   (dt, x, Bm, Cm, A)))


_ssm_scan.defvjp(_ssm_scan_fwd, _ssm_scan_bwd)


def _no_grad(v):
    """The cotangent of an input that is not trained: float0 for integers
    and a QTensor's codes, zeros for floats and a QTensor's scales."""
    import numpy as np
    if isinstance(v, qlib.QTensor):
        return dataclasses.replace(
            v, q=np.zeros(v.q.shape, jax.dtypes.float0),
            scales=jnp.zeros_like(v.scales))
    if jnp.issubdtype(jnp.result_type(v), jnp.floating):
        return jnp.zeros_like(v)
    return np.zeros(jnp.shape(v), jax.dtypes.float0)


def _gmm(x, w, tile_expert, *, transpose):
    if isinstance(w, qlib.QTensor) and _use_pallas():
        from repro.kernels import moe_gmm as gk
        fn = gk.moe_gmm_t if transpose else gk.moe_gmm
        return fn(x, w, tile_expert, interpret=_interpret())
    return (ref.moe_gmm_t if transpose else ref.moe_gmm)(
        x, w, tile_expert)


@jax.custom_vjp
def _moe_gmm(x, w, tile_expert):
    return _gmm(x, w, tile_expert, transpose=False)


def _moe_gmm_fwd(x, w, tile_expert):
    return _moe_gmm(x, w, tile_expert), (w, tile_expert)


def _moe_gmm_bwd(res, g):
    w, tile_expert = res
    pallas = isinstance(w, qlib.QTensor) and _use_pallas()
    trace_count("moe_gmm_t_pallas" if pallas else "moe_gmm_t_ref")
    dx = _gmm(g, w, tile_expert, transpose=True)
    return dx, _no_grad(w), _no_grad(tile_expert)


_moe_gmm.defvjp(_moe_gmm_fwd, _moe_gmm_bwd)


def moe_gmm(x, w, tile_expert):
    """Grouped matmul of expert-sorted rows (``kernels.moe_gmm``): for a
    quantized ``w`` the Pallas kernel and its transposed twin for ``dx``
    on TPU, the per-expert jnp oracle elsewhere (and for a dense ``w``).
    The experts are frozen: no ``dW``."""
    pallas = isinstance(w, qlib.QTensor) and _use_pallas()
    trace_count("moe_gmm_pallas" if pallas else "moe_gmm_ref")
    return _moe_gmm(x, w, tile_expert)


def quant_matmul(x, qt: qlib.QTensor):
    # qt.q.ndim == 3 means a plain 2-D weight: (G, block[/2], N)
    if _use_pallas():
        from repro.kernels import quant_matmul as qk
        if qt.q.ndim == 3:
            K, N = x.shape[-1], qt.q.shape[-1]
            M = 1
            for s in x.shape[:-1]:
                M *= s
            bm, bn = autotune.lookup("quant_matmul", M, K, N,
                                     bits=qt.bits, mode=qt.mode)
            trace_count("quant_matmul_pallas")
            return qk.quant_matmul(x, qt, block_m=bm, block_n=bn,
                                   interpret=_interpret())
        if qt.q.ndim == 4:
            # stacked (per-client) QTensor — the serve plane's vmapped
            # per-tenant slabs: vmap the Pallas kernel over the stack
            # axis of both operands (x: (T, [M,] K); qt.q: (T, G, ·, N))
            if x.shape[0] != qt.q.shape[0]:
                raise ValueError(
                    f"stacked quant_matmul needs matching stack dims: "
                    f"x {x.shape} vs qt.q {qt.q.shape}")
            trace_count("quant_matmul_pallas_stacked")
            fn = partial(qk.quant_matmul, interpret=_interpret())
            return jax.vmap(fn)(x, qt)
        # >1 stack axis has no Pallas mapping yet; with Pallas forced a
        # silent ref fallback would hide exactly the regression the CI
        # guards look for, so report it loudly instead.
        raise NotImplementedError(
            f"quant_matmul: no Pallas path for qt.q.ndim={qt.q.ndim} "
            "(>1 stack axis); flatten the stack axes first")
    trace_count("quant_matmul_ref")
    return ref.quant_matmul(x, qt)


# -- fused LoRA matmul (the QLoRA arm's whole linear layer) ------------
def _lora_fwd_impl(scale, x, w, a, b):
    if isinstance(w, qlib.QTensor) and _use_pallas() and w.q.ndim == 3:
        from repro.kernels import lora_matmul as lk
        K, N = x.shape[-1], w.q.shape[-1]
        M = 1
        for s in x.shape[:-1]:
            M *= s
        bm, bn = autotune.lookup("lora_matmul", M, K, N, bits=w.bits,
                                 mode=w.mode)
        trace_count("lora_matmul_pallas")
        if x.dtype == jnp.bfloat16:
            trace_count("lora_matmul_pallas_bf16x3")
        return lk.lora_matmul(x, w, a, b, scale=scale, block_m=bm,
                              block_n=bn, interpret=_interpret())
    trace_count("lora_matmul_ref")
    return ref.lora_matmul(x, w, a, b, scale=scale)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _lora_mm(scale, x, w, a, b):
    return _lora_fwd_impl(scale, x, w, a, b)


def _lora_fwd(scale, x, w, a, b):
    if isinstance(w, qlib.QTensor) and \
            not (_use_pallas() and w.q.ndim == 3):
        # ref path: the forward materializes the dequantized weight
        # anyway, so save it as a residual — the backward's Wᵀ gemm then
        # reuses it instead of re-dequantizing (exactly what autodiff of
        # the einsum chain would do)
        trace_count("lora_matmul_ref")
        wd = qlib.dequantize(w, jnp.float32)[:x.shape[-1]]
        return ref.lora_matmul(x, wd, a, b, scale=scale), \
            (x, w, a, b, wd)
    return _lora_fwd_impl(scale, x, w, a, b), (x, w, a, b, None)


def _mm(a, b):
    """float32 gemm at full precision, for the backward's products: the
    fused op accumulates in fp32 on every backend, and a TPU's default
    rounds operands to bfloat16. (The Pallas forward keeps a bf16 x in
    bf16 and forms the same products in three passes,
    ``kernels.lora_matmul``.)"""
    return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)


def _lora_bwd(scale, res, g):
    x, w, a, b, wd = res
    K = x.shape[-1]
    x2 = x.reshape(-1, K).astype(jnp.float32)
    g2 = g.reshape(-1, g.shape[-1]).astype(jnp.float32)
    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)
    gb = _mm(g2, bf.T)                               # (M, r)
    if isinstance(w, qlib.QTensor):
        if wd is not None:
            dxw = _mm(g2, wd.T)                      # (M, K) exactly
        elif _use_pallas() and w.q.ndim == 3:
            from repro.kernels import lora_matmul as lk
            trace_count("quant_matmul_t_pallas")
            dxw = lk.quant_matmul_t(g2, w,
                                    interpret=_interpret())[:, :K]
        else:
            dxw = _mm(g2, qlib.dequantize(w, jnp.float32).T)[:, :K]
        # the quantized payload is not differentiable: int8/uint8 codes
        # take a float0 cotangent, the f32 scales a symbolic zero
        import numpy as np
        dw = dataclasses.replace(
            w, q=np.zeros(w.q.shape, jax.dtypes.float0),
            scales=jnp.zeros_like(w.scales))
    else:
        wf = w.astype(jnp.float32)
        dxw = _mm(g2, wf.T)
        dw = _mm(x2.T, g2).astype(w.dtype)
    dx = (dxw + scale * _mm(gb, af.T)).reshape(x.shape).astype(x.dtype)
    da = (scale * _mm(x2.T, gb)).astype(a.dtype)
    db = (scale * _mm(_mm(x2, af).T, g2)).astype(b.dtype)
    return dx, dw, da, db


_lora_mm.defvjp(_lora_fwd, _lora_bwd)


def lora_matmul(x, w, a, b, *, scale: float):
    """``y = x @ W + scale·(x@A)@B`` as ONE op with fp32 accumulation
    and a custom VJP (dx through Wᵀ + BᵀAᵀ, dA/dB through the same
    tiled gemms). ``w`` may be a QTensor — streamed quantized through
    the fused Pallas kernel on TPU/interpret, ``kernels.ref`` (also
    fp32-fused) elsewhere — or a dense matrix."""
    return _lora_mm(float(scale), x, w, a, b)


def blockwise_quant(x, *, bits=8, block=128, mode="linear"):
    if _use_pallas() and x.ndim == 2 and mode != "nf4":
        from repro.kernels import blockwise_quant as bk
        return bk.blockwise_quant(x, bits=bits, block=block,
                                  interpret=_interpret())
    return ref.blockwise_quant(x, bits=bits, block=block, mode=mode)
