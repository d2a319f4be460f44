"""Per-kernel validation: Pallas (interpret mode — executes the kernel body
on CPU) vs the pure-jnp oracle in kernels/ref.py, swept over shapes,
dtypes, GQA ratios, masks, and quantization modes."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quant as qlib
from repro.kernels import ref
from repro.kernels.blockwise_quant import blockwise_quant
from repro.kernels.flash_attention import flash_attention
from repro.kernels.quant_matmul import quant_matmul


@pytest.mark.parametrize("B,S,H,Hkv,D", [
    (2, 128, 4, 4, 64),      # MHA
    (2, 128, 4, 2, 64),      # GQA 2:1
    (1, 256, 8, 1, 32),      # MQA
    (2, 100, 4, 2, 64),      # non-multiple S (padding path)
])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 64),
                                           (False, None)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_vs_ref(B, S, H, Hkv, D, causal, window, dtype, rng):
    q = jnp.asarray(rng.randn(B, S, H, D), dtype)
    k = jnp.asarray(rng.randn(B, S, Hkv, D), dtype)
    v = jnp.asarray(rng.randn(B, S, Hkv, D), dtype)
    want = ref.flash_attention(q, k, v, causal=causal, window=window)
    got = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=64, block_k=64, interpret=True)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)


@pytest.mark.parametrize("B,S,H,Hkv,D,causal,window", [
    (2, 128, 4, 2, 64, True, None),     # GQA, causal, 2x2 blocks
    (2, 100, 4, 2, 64, True, 48),       # sliding window, padded S
    (1, 77, 4, 4, 32, False, None),     # bidirectional, CLIP's 77
    (2, 100, 4, 1, 32, False, None),    # MQA, padded keys masked
])
def test_flash_vjp_vs_ref(B, S, H, Hkv, D, causal, window, rng):
    """The Pallas backward kernels (dq; dk/dv summed over each kv head's
    query group) == autodiff of the blocked jnp oracle."""
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, Hkv, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, Hkv, D), jnp.float32)
    ct = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    got = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=causal, window=window, block_q=64, block_k=64,
        interpret=True) * ct), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda q, k, v: jnp.sum(ref.flash_attention(
        q, k, v, causal=causal, window=window) * ct),
        argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=2e-5 * max(1.0, float(
                                       jnp.abs(w).max())), err_msg=name)


def test_flash_vs_naive_softmax(rng):
    """The blocked oracle itself against a plain softmax attention."""
    B, S, H, D = 2, 64, 4, 32
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -1e30)
    want = jnp.einsum("bhqk,bkhd->bqhd",
                      jax.nn.softmax(s, -1), v)
    got = ref.flash_attention(q, k, v, causal=True, q_chunk=16, k_chunk=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5)


@pytest.mark.parametrize("bits,mode", [(8, "linear"), (4, "linear"),
                                       (4, "nf4")])
@pytest.mark.parametrize("K,N,block", [(128, 64, 64), (256, 96, 128),
                                       (512, 33, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quant_matmul_vs_ref(bits, mode, K, N, block, dtype, rng):
    w = jnp.asarray(rng.randn(K, N), jnp.float32)
    qt = qlib.quantize(w, bits=bits, block=block, mode=mode)
    x = jnp.asarray(rng.randn(2, 7, K), dtype)
    want = ref.quant_matmul(x, qt)
    got = quant_matmul(x, qt, block_m=8, block_n=32, interpret=True)
    tol = 1e-3 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol * float(jnp.abs(want).max()))


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("K,N,block", [(128, 32, 64), (256, 100, 128)])
def test_blockwise_quant_vs_ref(bits, K, N, block, rng):
    x = jnp.asarray(rng.randn(K, N), jnp.float32)
    want = ref.blockwise_quant(x, bits=bits, block=block)
    got = blockwise_quant(x, bits=bits, block=block, block_n=32,
                          interpret=True)
    assert (np.asarray(want.q) == np.asarray(got.q)).all()
    np.testing.assert_allclose(np.asarray(want.scales),
                               np.asarray(got.scales), rtol=1e-6)


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("K,N,block", [(130, 33, 64), (190, 40, 128),
                                       (70, 20, 64)])
def test_blockwise_quant_odd_K_pads_contraction_dim(bits, K, N, block,
                                                    rng):
    """K not divisible by the block must zero-pad the contraction dim
    (like N already pads to block_n) instead of asserting: the result
    equals the reference on the zero-padded input exactly — pad rows
    never perturb a block's absmax scale — and dequantizes back to the
    original values (zeros past K)."""
    x = jnp.asarray(rng.randn(K, N), jnp.float32)
    got = blockwise_quant(x, bits=bits, block=block, block_n=32,
                          interpret=True)
    blk = min(block, K)
    Kp = -(-K // blk) * blk
    xp = jnp.pad(x, ((0, Kp - K), (0, 0)))
    want = ref.blockwise_quant(xp, bits=bits, block=block)
    assert got.orig_shape == (K, N)
    # the jnp fallback path (ops.blockwise_quant without Pallas) shares
    # the pad contract: odd K works and matches quantizing padded input
    ref_odd = ref.blockwise_quant(x, bits=bits, block=block)
    assert ref_odd.orig_shape == (K, N)
    assert (np.asarray(ref_odd.q) == np.asarray(want.q)).all()
    np.testing.assert_allclose(np.asarray(ref_odd.scales),
                               np.asarray(want.scales), rtol=1e-6)
    assert (np.asarray(want.q) == np.asarray(got.q)).all()
    np.testing.assert_allclose(np.asarray(want.scales),
                               np.asarray(got.scales), rtol=1e-6)
    deq = np.asarray(qlib.dequantize(got))
    assert deq.shape == (Kp, N)
    np.testing.assert_array_equal(deq[K:], 0)
    scale_bound = np.asarray(want.scales).max()
    np.testing.assert_allclose(deq[:K], np.asarray(x),
                               atol=1.2 * scale_bound)
    # both matmul consumers accept the padded-K payload: x's
    # contraction dim pads with zeros (contracts exactly like slicing)
    xin = jnp.asarray(rng.randn(3, K), jnp.float32)
    want_mm = np.asarray(xin) @ deq[:K]
    np.testing.assert_allclose(np.asarray(ref.quant_matmul(xin, got)),
                               want_mm, atol=1e-4, rtol=1e-5)
    got_mm = quant_matmul(xin, got, block_m=8, block_n=32,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got_mm), want_mm,
                               atol=1e-3 * max(1.0, np.abs(
                                   want_mm).max()))


def test_decode_attention_matches_flash_last_token(rng):
    """decode against a fully-valid cache == last row of full attention."""
    B, S, H, Hkv, D = 2, 32, 4, 2, 16
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, Hkv, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, Hkv, D), jnp.float32)
    full = ref.flash_attention(q, k, v, causal=True)
    got = ref.decode_attention(q[:, -1:], k, v,
                               jnp.arange(S, dtype=jnp.int32)[None])
    np.testing.assert_allclose(np.asarray(got[:, 0]),
                               np.asarray(full[:, -1]), atol=1e-5)


def _scan_inputs(rng, B, S, di, N):
    dt = jnp.asarray(np.abs(rng.randn(B, S, di)) * 0.1, jnp.float32)
    x = jnp.asarray(rng.randn(B, S, di), jnp.float32)
    Bm = jnp.asarray(rng.randn(B, S, N), jnp.float32)
    Cm = jnp.asarray(rng.randn(B, S, N), jnp.float32)
    A = jnp.asarray(-np.abs(rng.randn(di, N)), jnp.float32)
    return dt, x, Bm, Cm, A


@pytest.mark.parametrize("B,S,di,N,ch", [
    (2, 64, 32, 8, 16),
    (1, 50, 16, 4, 32),        # non-multiple S (padding path)
    (2, 96, 64, 16, 48),
    (1, 40, 256, 16, 16),      # two lane blocks of d_inner
])
def test_selective_scan_vs_ref(B, S, di, N, ch, rng):
    dt, x, Bm, Cm, A = _scan_inputs(rng, B, S, di, N)
    from repro.kernels.selective_scan import chunk_len, scan_fwd
    y0, h0 = ref.selective_scan(dt, x, Bm, Cm, A)
    y1, h1, hs = scan_fwd(dt, x, Bm, Cm, A, chunk=ch, interpret=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), atol=1e-5)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h0), atol=1e-5)
    # the state entering the last chunk is the recurrence's before it
    L, c = chunk_len(S, ch), hs.shape[1] - 1
    _, h_c = ref.selective_scan(*(t[:, :c * L] for t in (dt, x, Bm, Cm)), A)
    np.testing.assert_allclose(np.asarray(hs[:, c, :, :di]),
                               np.asarray(jnp.swapaxes(h_c, 1, 2)),
                               atol=1e-5)


@pytest.mark.parametrize("B,S,di,N,ch", [
    (2, 37, 32, 8, 16),        # S padded to 48: the pad steps' cotangents
    (1, 64, 256, 16, 32),      # two lane blocks, two chunks
])
def test_selective_scan_vjp_vs_ref(B, S, di, N, ch, rng):
    """The backward kernel's cotangents of all five inputs, from those of
    y and of the final state, against ``jax.vjp`` of the sequential
    oracle."""
    from repro.kernels import selective_scan as sk
    args = _scan_inputs(rng, B, S, di, N)
    gy = jnp.asarray(rng.randn(B, S, di), jnp.float32)
    gh = jnp.asarray(rng.randn(B, di, N), jnp.float32)
    _, vjp = jax.vjp(ref.selective_scan, *args)
    want = vjp((gy, gh))
    _, _, hs = sk.scan_fwd(*args, chunk=ch, interpret=True)
    got = sk.scan_bwd(*args, hs, gy, gh, chunk=ch, interpret=True)
    for name, g, w in zip(("dt", "x", "B", "C", "A"), got, want):
        assert g.shape == w.shape, name
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-4, err_msg=name)


def test_selective_scan_op_is_differentiable(rng, monkeypatch):
    """``ops.selective_scan`` on the Pallas path (interpret mode) trains:
    its custom VJP gives ``jax.grad`` the backward kernel's cotangents,
    and the counters show both kernels were taken."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_FORCE", "interpret")
    args = _scan_inputs(rng, 1, 24, 32, 8)
    ops.reset_kernel_traces()
    loss = lambda f: lambda *a: jnp.sum(jnp.sin(f(*a)[0]))
    got = jax.grad(loss(ops.selective_scan), argnums=range(5))(*args)
    want = jax.grad(loss(ref.selective_scan), argnums=range(5))(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-5)
    assert ops.KERNEL_TRACES["ssm_scan_pallas"] >= 1
    assert ops.KERNEL_TRACES["ssm_scan_pallas_bwd"] >= 1


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_gmm_vs_per_expert_einsum(dtype, rng):
    """The grouped NF4 matmul and its transposed twin against a plain
    per-expert einsum: an empty expert group, a group of several tiles,
    and trailing tiles of zero rows past the groups (as
    ``models.moe.group_by_expert`` leaves them) that must read zero."""
    from repro.kernels import moe_gmm as gk
    E, K, N, tm = 4, 256, 384, 16
    qt = qlib.quantize(jnp.asarray(rng.randn(E, K, N), jnp.float32),
                       bits=4, block=64, mode="nf4")
    w = np.asarray(qlib.dequantize(qt, jnp.float32))
    te = np.array([0, 0, 2, 3, 3, 3, 3, 3], np.int32)   # expert 1 empty
    nt = 6
    used = np.repeat(np.arange(8) < nt, tm)[:, None]
    x = jnp.asarray(rng.randn(8 * tm, K) * used, dtype)
    g = jnp.asarray(rng.randn(8 * tm, N) * used, dtype)
    rows = np.repeat(te, tm)
    xf, gf = np.asarray(x, np.float64), np.asarray(g, np.float64)
    want = np.where(used, np.einsum("rk,rkn->rn", xf, w[rows]), 0.0)
    want_t = np.where(used, np.einsum("rn,rkn->rk", gf, w[rows]), 0.0)
    got = gk.moe_gmm(x, qt, jnp.asarray(te), block_k=128, block_n=128,
                     interpret=True)
    got_t = gk.moe_gmm_t(g, qt, jnp.asarray(te), block_k=128, block_n=128,
                         interpret=True)
    assert got.dtype == got_t.dtype == dtype
    # f32: summation order; bf16: the output's own rounding
    tol = 1e-5 if dtype == jnp.float32 else 2 ** -8
    for a, b in ((got, want), (got_t, want_t)):
        a = np.asarray(a, np.float64)
        np.testing.assert_allclose(a, b, rtol=tol,
                                   atol=tol * np.abs(b).max())
        assert not a[~used[:, 0]].any()
    np.testing.assert_allclose(
        np.asarray(ref.moe_gmm(x, qt, jnp.asarray(te)), np.float64), want,
        rtol=tol, atol=tol * np.abs(want).max())


def test_selective_scan_ref_matches_mamba_chunked(rng):
    """The Pallas oracle and the model's chunked associative scan agree."""
    from repro.models.ssm import _chunked_ssm_scan
    B, S, di, N = 2, 40, 16, 8
    dt = jnp.asarray(np.abs(rng.randn(B, S, di)) * 0.1, jnp.float32)
    x = jnp.asarray(rng.randn(B, S, di), jnp.float32)
    Bm = jnp.asarray(rng.randn(B, S, N), jnp.float32)
    Cm = jnp.asarray(rng.randn(B, S, N), jnp.float32)
    A = jnp.asarray(-np.abs(rng.randn(di, N)), jnp.float32)
    y0, h0 = ref.selective_scan(dt, x, Bm, Cm, A)
    y1, h1 = _chunked_ssm_scan(dt, A, Bm, Cm, x,
                               jnp.zeros((B, di, N)), 16)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), atol=1e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h0), atol=1e-4)


def test_decode_attention_partial_combine(rng):
    """flash-decoding: log-sum-exp combination of slot shards == full."""
    B, H, Hkv, D, M = 2, 4, 2, 16, 32
    q = jnp.asarray(rng.randn(B, 1, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, M, Hkv, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, M, Hkv, D), jnp.float32)
    sp = jnp.where(jnp.arange(M) < 20, jnp.arange(M), -1)[None]
    want = ref.decode_attention(q, k, v, sp)
    halves = [(k[:, :16], v[:, :16], sp[:, :16]),
              (k[:, 16:], v[:, 16:], sp[:, 16:])]
    parts = [ref.decode_attention_partial(q, *h) for h in halves]
    m = jnp.maximum(parts[0][0], parts[1][0])
    l = sum(p[1] * jnp.exp(p[0] - m) for p in parts)
    acc = sum(p[2] * jnp.exp(p[0] - m)[..., None] for p in parts)
    got = (acc / l[..., None]).reshape(B, 1, H, D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5)


# ---------------------------------------------------------------------
# fused LoRA matmul: one kernel == the einsum chain, forward + backward
# ---------------------------------------------------------------------
from hypothesis import given, settings, strategies as st

from repro.kernels import autotune, ops as kops
from repro.kernels.lora_matmul import (lora_matmul, quant_matmul_t,
                                       split_bf16x3)


def _lora_chain(x, qt, a, b, scale):
    """The legacy einsum chain core.lora.linear used to build: base
    quant matmul + separately-computed low-rank delta (fp32)."""
    xf = x.astype(jnp.float32)
    base = ref.quant_matmul(xf, qt)
    h = jnp.einsum("...k,kr->...r", xf, a.astype(jnp.float32))
    d = jnp.einsum("...r,rn->...n", h, b.astype(jnp.float32))
    return (base + scale * d).astype(x.dtype)


@pytest.mark.parametrize("bits,mode", [(8, "linear"), (4, "linear"),
                                       (4, "nf4")])
@pytest.mark.parametrize("K,N,r", [(128, 96, 4), (200, 64, 8),
                                   (64, 33, 4)])   # 200: odd-K pad path
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_lora_kernel_vs_chain_forward(bits, mode, K, N, r, dtype,
                                            rng):
    M, scale = 17, 2.0
    x = jnp.asarray(rng.randn(M, K), dtype)
    w = jnp.asarray(rng.randn(K, N), jnp.float32)
    a = jnp.asarray(rng.randn(K, r) * 0.1, jnp.float32)
    b = jnp.asarray(rng.randn(r, N) * 0.1, jnp.float32)
    qt = ref.blockwise_quant(w, bits=bits, block=128, mode=mode)
    want = _lora_chain(x, qt, a, b, scale)
    got = lora_matmul(x, qt, a, b, scale=scale, block_m=8, block_n=32,
                      interpret=True)
    assert got.dtype == x.dtype
    tol = (1e-5 if dtype == jnp.float32 else 2e-2) * max(
        1.0, float(jnp.abs(want.astype(jnp.float32)).max()))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)


@pytest.mark.parametrize("values", ["random", "nf4_scaled", "zero_pad"])
def test_split_bf16x3_is_exact(values, rng):
    """The bf16 route's weight split loses nothing: the three terms sum
    back to the f32 value exactly (two terms would not)."""
    if values == "random":
        w = rng.randn(512, 256) * np.exp2(rng.randint(-20, 20, (512, 256)))
    elif values == "nf4_scaled":       # dequantized NF4: code x absmax
        codes = np.asarray(qlib.NF4_CODE, np.float32)
        w = codes[rng.randint(0, 16, (512, 256))] * \
            np.abs(rng.randn(512, 1)).astype(np.float32)
    else:                              # pad groups carry zero scales
        w = np.zeros((512, 256))
    w = jnp.asarray(w, jnp.float32)
    terms = split_bf16x3(w)
    assert all(t.dtype == jnp.bfloat16 for t in terms)
    hi, mid, lo = (t.astype(jnp.float32) for t in terms)
    np.testing.assert_array_equal(np.asarray(hi + mid + lo), np.asarray(w))
    if values != "zero_pad":
        assert bool(jnp.any(hi + mid != w))


@pytest.mark.parametrize("bits,mode", [(8, "linear"), (4, "nf4")])
@pytest.mark.parametrize("M,K,N,block", [(40, 384, 96, 128),
                                         (33, 256, 160, 64)])
def test_fused_lora_bf16_route_matches_highest(bits, mode, M, K, N, block,
                                               rng):
    """A bf16 x takes the three-pass route; the same values as f32 take
    HIGHEST. Same products, so the bf16 outputs agree bitwise nearly
    everywhere, and everywhere within one bf16 ulp plus what the two
    f32 accumulation orders may differ by (which is more than an ulp
    only where the sum nearly cancels). N spans several column tiles
    (x@A reused for ni > 0), K several K tiles."""
    scale = 2.0
    x = jnp.asarray(rng.randn(M, K), jnp.bfloat16)
    w = jnp.asarray(rng.randn(K, N), jnp.float32)
    a = jnp.asarray(rng.randn(K, 8) * 0.3, jnp.float32)
    b = jnp.asarray(rng.randn(8, N) * 0.3, jnp.float32)
    qt = ref.blockwise_quant(w, bits=bits, block=block, mode=mode)
    run = functools.partial(lora_matmul, qt=qt, a=a, b=b, scale=scale,
                            block_m=16, block_n=32, interpret=True)
    got = run(x)
    want = run(x.astype(jnp.float32)).astype(jnp.bfloat16)
    assert got.dtype == jnp.bfloat16
    got, want = (np.asarray(v, np.float32) for v in (got, want))
    assert (got == want).mean() >= 0.99
    ax = np.abs(np.asarray(x, np.float64))
    aw = np.abs(np.asarray(qlib.dequantize(qt, jnp.float32), np.float64))
    mag = ax @ aw[:K] + scale * (ax @ np.abs(np.asarray(a, np.float64))) \
        @ np.abs(np.asarray(b, np.float64))
    reorder = 2 * (K + 16) * 2.0 ** -24 * mag     # both routes' f32 sums
    ulp = np.exp2(np.floor(np.log2(np.maximum(np.abs(want), 2.0 ** -126)))
                  - 7)
    assert np.all(np.abs(got - want) <= ulp + reorder)


@pytest.mark.parametrize("bits,mode", [(8, "linear"), (4, "nf4")])
def test_fused_lora_f32_route_reuses_h_across_column_tiles(bits, mode,
                                                           rng):
    """The f32 route, N over five column tiles and K over three K
    tiles: every ni's flush carries the full x@A of its row tile, so
    the output matches the chain at 1e-5 with a LoRA term that is not
    small against the base."""
    M, K, N, scale = 24, 384, 160, 2.0
    x = jnp.asarray(rng.randn(M, K), jnp.float32)
    w = jnp.asarray(rng.randn(K, N), jnp.float32)
    a = jnp.asarray(rng.randn(K, 8) * 0.5, jnp.float32)
    b = jnp.asarray(rng.randn(8, N) * 0.5, jnp.float32)
    qt = ref.blockwise_quant(w, bits=bits, block=128, mode=mode)
    want = _lora_chain(x, qt, a, b, scale)
    got = lora_matmul(x, qt, a, b, scale=scale, block_m=8, block_n=32,
                      interpret=True)
    tol = 1e-5 * max(1.0, float(jnp.abs(want).max()))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lora_op_counts_bf16x3_route(dtype, rng, monkeypatch):
    """ops counts the three-pass route at trace time for bf16 x only."""
    monkeypatch.setattr(kops, "_FORCE", "interpret")
    x = jnp.asarray(rng.randn(9, 128), dtype)
    qt = ref.blockwise_quant(jnp.asarray(rng.randn(128, 64), jnp.float32),
                             bits=4, block=64, mode="nf4")
    a = jnp.zeros((128, 4), jnp.float32)
    b = jnp.zeros((4, 64), jnp.float32)
    kops.reset_kernel_traces()
    jax.eval_shape(lambda x: kops.lora_matmul(x, qt, a, b, scale=2.0), x)
    assert kops.KERNEL_TRACES.get("lora_matmul_pallas", 0) == 1
    assert kops.KERNEL_TRACES.get("lora_matmul_pallas_bf16x3", 0) == \
        (1 if dtype == jnp.bfloat16 else 0)


@pytest.mark.parametrize("bits,mode", [(8, "linear"), (4, "nf4")])
@pytest.mark.parametrize("K", [128, 200])          # 200: odd-K pad path
def test_quant_matmul_t_vs_ref(bits, mode, K, rng):
    N = 96
    g = jnp.asarray(rng.randn(13, N), jnp.float32)
    w = jnp.asarray(rng.randn(K, N), jnp.float32)
    qt = ref.blockwise_quant(w, bits=bits, block=128, mode=mode)
    wd = qlib.dequantize(qt, jnp.float32)           # (Kq, N)
    want = g @ wd.T
    got = quant_matmul_t(g, qt, block_m=8, block_n=32, interpret=True)
    tol = 1e-5 * max(1.0, float(jnp.abs(want).max()))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=tol)


@pytest.mark.parametrize("force", ["", "interpret"])
@pytest.mark.parametrize("bits,mode,K", [(8, "linear", 128),
                                         (4, "nf4", 200)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_lora_op_backward_vs_chain(force, bits, mode, K, dtype,
                                         rng, monkeypatch):
    """ops.lora_matmul's custom VJP (dx through Wᵀ + BᵀAᵀ, dA/dB through
    the tiled gemms) == jax.grad of the einsum chain, on both the ref
    path and the Pallas interpret path (which exercises
    quant_matmul_t)."""
    monkeypatch.setattr(kops, "_FORCE", force)
    N, r, scale = 64, 4, 2.0
    x = jnp.asarray(rng.randn(9, K), dtype)
    w = jnp.asarray(rng.randn(K, N), jnp.float32)
    a = jnp.asarray(rng.randn(K, r) * 0.1, jnp.float32)
    b = jnp.asarray(rng.randn(r, N) * 0.1, jnp.float32)
    qt = ref.blockwise_quant(w, bits=bits, block=128, mode=mode)
    ct = jnp.asarray(rng.randn(9, N), jnp.float32)

    def loss_fused(x, a, b):
        y = kops.lora_matmul(x, qt, a, b, scale=scale)
        return jnp.sum(y.astype(jnp.float32) * ct)

    def loss_chain(x, a, b):
        return jnp.sum(_lora_chain(x, qt, a, b, scale)
                       .astype(jnp.float32) * ct)

    y_f = kops.lora_matmul(x, qt, a, b, scale=scale)
    y_c = _lora_chain(x, qt, a, b, scale)
    ftol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(y_f, np.float32), np.asarray(y_c, np.float32),
        atol=ftol * max(1.0, float(jnp.abs(y_c.astype(jnp.float32)).max())))
    got = jax.grad(loss_fused, argnums=(0, 1, 2))(x, a, b)
    want = jax.grad(loss_chain, argnums=(0, 1, 2))(x, a, b)
    for gf, gc, name in zip(got, want, ("dx", "da", "db")):
        assert gf.dtype == gc.dtype, name
        scale_t = max(1.0, float(jnp.abs(gc.astype(jnp.float32)).max()))
        tol = (1e-5 if gc.dtype == jnp.float32 else 2e-2) * scale_t
        np.testing.assert_allclose(np.asarray(gf, np.float32),
                                   np.asarray(gc, np.float32),
                                   atol=tol, err_msg=name)


def test_fused_lora_dense_w_grad_includes_dw(rng):
    x = jnp.asarray(rng.randn(7, 32), jnp.float32)
    w = jnp.asarray(rng.randn(32, 16), jnp.float32)
    a = jnp.asarray(rng.randn(32, 4) * 0.1, jnp.float32)
    b = jnp.asarray(rng.randn(4, 16) * 0.1, jnp.float32)
    ct = jnp.asarray(rng.randn(7, 16), jnp.float32)
    gw = jax.grad(lambda w: jnp.sum(
        kops.lora_matmul(x, w, a, b, scale=2.0) * ct))(w)
    rw = jax.grad(lambda w: jnp.sum(
        (x @ w + 2.0 * (x @ a) @ b) * ct))(w)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(rw), atol=1e-4)


@settings(max_examples=15, deadline=None)
@given(M=st.integers(1, 24), K=st.sampled_from([64, 128, 150, 256]),
       N=st.sampled_from([32, 64, 96]), r=st.sampled_from([2, 4, 8]),
       bits=st.sampled_from([8, 4]),
       scale=st.floats(0.25, 4.0))
def test_fused_lora_property_fwd_bwd(M, K, N, r, bits, scale):
    """Hypothesis sweep: fused op == chain, forward and backward, over
    random geometry (incl. non-multiple K) on the ref path."""
    rng = np.random.RandomState(M * 1000 + K + N + r + bits)
    x = jnp.asarray(rng.randn(M, K), jnp.float32)
    w = jnp.asarray(rng.randn(K, N), jnp.float32)
    a = jnp.asarray(rng.randn(K, r) * 0.1, jnp.float32)
    b = jnp.asarray(rng.randn(r, N) * 0.1, jnp.float32)
    qt = ref.blockwise_quant(w, bits=bits, block=128)
    ct = jnp.asarray(rng.randn(M, N), jnp.float32)
    y_f = kops.lora_matmul(x, qt, a, b, scale=scale)
    y_c = _lora_chain(x, qt, a, b, scale)
    s0 = max(1.0, float(jnp.abs(y_c).max()))
    np.testing.assert_allclose(np.asarray(y_f), np.asarray(y_c),
                               atol=1e-5 * s0)
    got = jax.grad(lambda x, a, b: jnp.sum(
        kops.lora_matmul(x, qt, a, b, scale=scale) * ct),
        argnums=(0, 1, 2))(x, a, b)
    want = jax.grad(lambda x, a, b: jnp.sum(
        _lora_chain(x, qt, a, b, scale) * ct),
        argnums=(0, 1, 2))(x, a, b)
    for gf, gc in zip(got, want):
        s1 = max(1.0, float(jnp.abs(gc).max()))
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gc),
                                   atol=2e-5 * s1)


def test_quant_matmul_stacked_takes_pallas_when_forced(rng, monkeypatch):
    """ops.quant_matmul must not silently fall back to ref for the
    stacked (per-client serve) QTensor layout when Pallas is forced —
    it vmaps the kernel over the stack axis, and loudly rejects layouts
    it has no mapping for."""
    monkeypatch.setattr(kops, "_FORCE", "interpret")
    kops.reset_kernel_traces()
    T, K, N = 3, 64, 32
    w = jnp.asarray(rng.randn(T, K, N), jnp.float32)
    qt = qlib.quantize(w, bits=8, block=64)
    assert qt.q.ndim == 4
    x = jnp.asarray(rng.randn(T, K), jnp.float32)
    got = kops.quant_matmul(x, qt)
    wd = qlib.dequantize(qt, jnp.float32)
    want = (x[:, None, :] @ wd)[:, 0, :]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5 * max(1.0, float(jnp.abs(want).max())))
    assert kops.KERNEL_TRACES.get("quant_matmul_pallas_stacked", 0) >= 1
    assert kops.KERNEL_TRACES.get("quant_matmul_ref", 0) == 0
    # batched rows per stack entry
    xb = jnp.asarray(rng.randn(T, 5, K), jnp.float32)
    got_b = kops.quant_matmul(xb, qt)
    np.testing.assert_allclose(np.asarray(got_b), np.asarray(xb @ wd),
                               atol=1e-4)
    # no mapping for >1 stack axis: loud, not silent
    w5 = jnp.asarray(rng.randn(2, 2, K, N), jnp.float32)
    qt5 = qlib.quantize(w5, bits=8, block=64)
    with pytest.raises(NotImplementedError):
        kops.quant_matmul(jnp.asarray(rng.randn(2, 2, K), jnp.float32),
                          qt5)


# ---------------------------------------------------------------------
# autotune: persisted winners, deterministic second sweep (zero compiles)
# ---------------------------------------------------------------------
def test_autotune_sweep_caches_and_charges(tmp_path, rng, monkeypatch):
    from repro.fl import runtime as runtime_lib
    path = str(tmp_path / "autotune.json")
    autotune.clear()
    rt = runtime_lib.ProgramRuntime()
    x = jnp.asarray(rng.randn(16, 128), jnp.float32)
    w = jnp.asarray(rng.randn(128, 64), jnp.float32)
    qt = ref.blockwise_quant(w, bits=8, block=128)

    def build(bm, bn):
        return lambda: quant_matmul(x, qt, block_m=bm, block_n=bn,
                                    interpret=True)

    r1 = autotune.sweep("quant_matmul", build, 16, 128, 64, bits=8,
                        mode="linear", runtime=rt, path=path,
                        candidates=((8, 32), (16, 64)), iters=1)
    assert r1.swept and r1.n_candidates == 2
    assert rt.stats()["autotune_quant_matmul"]["n_compiles"] == 2
    t1 = rt.compile_time_s
    assert t1 > 0
    # second sweep: pure cache hit — zero new compiles in the ledger
    r2 = autotune.sweep("quant_matmul", build, 16, 128, 64, bits=8,
                        mode="linear", runtime=rt, path=path,
                        candidates=((8, 32), (16, 64)), iters=1)
    assert not r2.swept and r2.best == r1.best
    assert rt.stats()["autotune_quant_matmul"]["n_compiles"] == 2
    assert rt.compile_time_s == t1
    # lookup returns the winner without sweeping; M buckets to pow2
    assert autotune.lookup("quant_matmul", 16, 128, 64, bits=8,
                           mode="linear", path=path) == r1.best
    assert autotune.lookup("quant_matmul", 13, 128, 64, bits=8,
                           mode="linear", path=path) == r1.best
    # unseen shape falls back to the default, still without sweeping
    assert autotune.lookup("quant_matmul", 16, 256, 64, bits=8,
                           mode="linear", path=path) == \
        autotune.DEFAULT_BLOCKS
    # a fresh in-process cache reloads the persisted JSON winners
    autotune.clear()
    assert autotune.lookup("quant_matmul", 16, 128, 64, bits=8,
                           mode="linear", path=path) == r1.best
    autotune.clear()


# ---------------------------------------------------------------------
# int8 quantized-compute GAN gemms
# ---------------------------------------------------------------------
def test_quant_gemm_int8_close_to_fp(rng):
    from repro.kernels import gan_conv
    x = jnp.asarray(rng.randn(37, 100), jnp.float32)
    w = jnp.asarray(rng.randn(100, 24), jnp.float32)
    y8 = gan_conv.quant_gemm_int8(x, w)
    y = x @ w
    rel = float(jnp.abs(y8 - y).max() / jnp.abs(y).max())
    assert rel < 3e-2           # blockwise int8 compute, fp32 accum
    # exact-zero blocks stay exact zeros
    assert float(jnp.abs(gan_conv.quant_gemm_int8(
        jnp.zeros((4, 64)), w[:64])).max()) == 0.0


@pytest.mark.parametrize("op,hw,ci,co", [
    ("conv", 16, 6, 12), ("conv", 8, 16, 24),
    ("convT", 8, 16, 16), ("convT", 16, 16, 3),   # co<8: contrib form
])
def test_gan_conv_int8_close_to_fp_with_grads(op, hw, ci, co, rng):
    from repro.kernels import gan_conv
    x = jnp.asarray(rng.randn(2, hw, hw, ci), jnp.float32)
    w = jnp.asarray(rng.randn(4, 4, ci, co) * 0.1, jnp.float32)
    fp = getattr(gan_conv, f"{'conv' if op == 'conv' else 'convT'}4x4_s2")
    q8 = getattr(gan_conv,
                 f"{'conv' if op == 'conv' else 'convT'}4x4_s2_int8")
    want = fp(x, w)
    got = q8(x, w)
    assert got.shape == want.shape
    rel = float(jnp.abs(got - want).max() /
                max(1e-6, float(jnp.abs(want).max())))
    assert rel < 3e-2
    ct = jnp.asarray(rng.randn(*want.shape), jnp.float32)
    gx, gw = jax.grad(lambda x, w: jnp.sum(q8(x, w) * ct),
                      argnums=(0, 1))(x, w)
    rx, rw = jax.grad(lambda x, w: jnp.sum(fp(x, w) * ct),
                      argnums=(0, 1))(x, w)
    for g, r_ in ((gx, rx), (gw, rw)):
        assert bool(jnp.isfinite(g).all())
        cos = float((g * r_).sum() /
                    (jnp.linalg.norm(g) * jnp.linalg.norm(r_)))
        assert cos > 0.99       # straight-through grads track the fp map
