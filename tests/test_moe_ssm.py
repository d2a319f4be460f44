"""MoE routing semantics and recurrent-scan equivalences."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_reduced
from repro.core import quant as qlib
from repro.kernels import ref
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib


def _moe_cfg(cf=8.0):
    return get_reduced("qwen3-moe-235b-a22b").replace(capacity_factor=cf)


def test_moe_matches_dense_oracle(rng):
    """With no capacity drops, gather/scatter MoE == per-token loop."""
    cfg = _moe_cfg()
    p = moe_lib.init_experts(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jnp.asarray(rng.randn(3, 4, cfg.d_model) * 0.3, jnp.float32)
    y, _ = moe_lib.moe_ffn(p, x, cfg)
    T = 12
    x2 = np.asarray(x.reshape(T, cfg.d_model))
    probs = np.asarray(jax.nn.softmax(x2 @ np.asarray(p["router"]), -1))
    want = np.zeros_like(x2)
    for t in range(T):
        top = np.argsort(-probs[t])[:cfg.experts_per_token]
        gates = probs[t][top] / probs[t][top].sum()
        for g, e in zip(gates, top):
            wg, wu, wd = (np.asarray(p[n][e]) for n in ("wg", "wu", "wd"))
            h = (x2[t] @ wg)
            h = h / (1 + np.exp(-h)) * (x2[t] @ wu)
            want[t] += g * (h @ wd)
    np.testing.assert_allclose(np.asarray(y).reshape(T, -1), want,
                               atol=2e-4)


def test_moe_capacity_drops_tokens(rng):
    """A tiny capacity factor must drop load (output norm decreases)."""
    cfg_hi = _moe_cfg(8.0)
    cfg_lo = _moe_cfg(0.05)
    p = moe_lib.init_experts(jax.random.PRNGKey(0), cfg_hi, jnp.float32)
    x = jnp.asarray(rng.randn(2, 16, cfg_hi.d_model), jnp.float32)
    y_hi, _ = moe_lib.moe_ffn(p, x, cfg_hi)
    y_lo, _ = moe_lib.moe_ffn(p, x, cfg_lo)
    assert float(jnp.linalg.norm(y_lo)) < float(jnp.linalg.norm(y_hi))


def test_moe_aux_loss_balanced_vs_collapsed():
    cfg = _moe_cfg()
    T, E = 512, cfg.n_experts
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(T, cfg.d_model), jnp.float32)
    p = moe_lib.init_experts(jax.random.PRNGKey(0), cfg, jnp.float32)
    _, ids, aux_rand = moe_lib._route(p["router"], x, cfg)
    collapsed = dict(p, router=p["router"] * 0.0 + jnp.eye(
        cfg.d_model, E) * 50.0)
    _, _, aux_coll = moe_lib._route(collapsed["router"], x, cfg)
    assert float(aux_coll) > float(aux_rand)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 64), st.integers(1, 8), st.integers(0, 100))
def test_slot_assignment_capacity_invariant(T, C, seed):
    rng = np.random.RandomState(seed)
    ids = jnp.asarray(rng.randint(0, 4, T), jnp.int32)
    order, sorted_ids, slot, keep = moe_lib._slot_assignment(ids, 4, C)
    s, sl, kp = (np.asarray(v) for v in (sorted_ids, slot, keep))
    # kept slots are unique per (expert, slot) and below capacity
    pairs = {(int(e), int(x)) for e, x, k in zip(s, sl, kp) if k}
    assert len(pairs) == int(kp.sum())
    assert all(x < C for _, x in pairs)
    # at most C kept per expert
    for e in range(4):
        assert int((kp & (s == e)).sum()) <= C


def test_chunked_scan_matches_loop(rng):
    B, S, D = 2, 37, 5
    a = jnp.asarray(np.exp(-np.abs(rng.randn(B, S, D))), jnp.float32)
    b = jnp.asarray(rng.randn(B, S, D), jnp.float32)
    h0 = jnp.asarray(rng.randn(B, D), jnp.float32)
    h_all, h_last = ssm_lib.chunked_linear_scan(a, b, h0, chunk=8)
    h = np.asarray(h0)
    want = []
    for t in range(S):
        h = np.asarray(a[:, t]) * h + np.asarray(b[:, t])
        want.append(h.copy())
    want = np.stack(want, 1)
    np.testing.assert_allclose(np.asarray(h_all), want, atol=1e-5)
    np.testing.assert_allclose(np.asarray(h_last), want[:, -1], atol=1e-5)


def test_mamba_block_decode_equivalence(rng):
    cfg = get_reduced("falcon-mamba-7b")
    p = ssm_lib.init_mamba(jax.random.PRNGKey(0), cfg, jnp.float32)
    B, S = 2, 11
    x = jnp.asarray(rng.randn(B, S, cfg.d_model) * 0.3, jnp.float32)
    y_full, cache_full = ssm_lib.mamba_block(p, x, cfg)
    cache = {"h": jnp.zeros((B, cfg.d_inner, cfg.ssm_state)),
             "conv": jnp.zeros((B, cfg.ssm_conv - 1, cfg.d_inner))}
    outs = []
    for t in range(S):
        o, cache = ssm_lib.mamba_decode(p, x[:, t:t + 1], cache, cfg)
        outs.append(o)
    y_step = jnp.concatenate(outs, 1)
    np.testing.assert_allclose(np.asarray(y_step), np.asarray(y_full),
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(cache["h"]),
                               np.asarray(cache_full["h"]), atol=1e-4)


def _per_token_moe(p, x2, cfg, renorm):
    """Each token through its top-k experts one at a time (numpy)."""
    probs = np.asarray(jax.nn.softmax(x2 @ np.asarray(p["router"]), -1))
    w = {n: np.asarray(qlib.dequantize(p[n], jnp.float32)
                       if isinstance(p[n], qlib.QTensor) else p[n])
         for n in ("wg", "wu", "wd")}
    want = np.zeros_like(x2)
    for t in range(x2.shape[0]):
        top = np.argsort(-probs[t], kind="stable")[:cfg.experts_per_token]
        gates = probs[t][top]
        if renorm:
            gates = gates / gates.sum()
        for g, e in zip(gates, top):
            h = x2[t] @ w["wg"][e]
            h = h / (1 + np.exp(-h)) * (x2[t] @ w["wu"][e])
            want[t] += g * (h @ w["wd"][e])
    return want


@pytest.mark.parametrize("adversarial", [False, True],
                         ids=["random_router", "one_expert_takes_all"])
def test_moe_dropless_matches_per_token_oracle(adversarial, rng):
    """The dropless path (grouped NF4 matmul, gates not renormalized, as
    Jamba routes) computes every token copy: with a router that sends
    every token's first choice to expert 0 — four times the fair share
    of rows — it still matches the per-token oracle, and its statistics
    say so."""
    cfg = get_reduced("jamba2-mini")
    p = moe_lib.init_experts(
        jax.random.PRNGKey(0), cfg, jnp.float32,
        quantize=lambda t: qlib.quantize_tree(t, bits=4, block=64,
                                              mode="nf4"))
    T, E, k = 40, cfg.n_experts, cfg.experts_per_token
    x = rng.randn(T, cfg.d_model).astype(np.float32) * 0.3
    if adversarial:
        x[:, 0] = 4.0
        p = dict(p, router=p["router"].at[0, 0].set(30.0))
    y, aux, stats = moe_lib.moe_dropless(p, jnp.asarray(x), cfg, tm=16)
    want = _per_token_moe(p, x, cfg, renorm=False)
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-5, atol=1e-5)
    ids = np.asarray(moe_lib._route(p["router"], jnp.asarray(x), cfg)[1])
    counts = np.bincount(ids.ravel(), minlength=E)
    if adversarial:
        assert counts[0] == T
    assert float(stats["moe_load_max"]) == pytest.approx(
        counts.max() * E / (T * k))
    rows = sum(-(-c // 16) * 16 for c in counts)
    assert float(stats["moe_pad_rows"]) == rows - T * k


def test_group_by_expert_tiles(rng):
    """Every copy lands in a row of its expert's tiles, one copy a row,
    no copy dropped; the tiles in use are those the groups fill."""
    E, tm = 5, 8
    ids = jnp.asarray(rng.randint(0, E, 37).astype(np.int32).clip(0, 3))
    order, dest, te, nt, counts = moe_lib.group_by_expert(ids, E, tm)
    order, dest, te = map(np.asarray, (order, dest, te))
    assert len(set(dest.tolist())) == 37
    assert (te[dest // tm] == np.asarray(ids)[order]).all()
    assert int(nt) == sum(-(-c // tm) for c in np.asarray(counts))
    assert len(te) * tm == -(-(37 + E * (tm - 1)) // tm) * tm


def test_chunked_ssm_scan_h_last_at_padded_s(rng):
    """At an S the chunk does not divide, the padded steps (Δ = 0: a = 1,
    b = 0) leave the state as the last real step left it."""
    B, S, di, N = 2, 37, 16, 8
    dt = jnp.asarray(np.abs(rng.randn(B, S, di)) * 0.1, jnp.float32)
    x = jnp.asarray(rng.randn(B, S, di), jnp.float32)
    Bm = jnp.asarray(rng.randn(B, S, N), jnp.float32)
    Cm = jnp.asarray(rng.randn(B, S, N), jnp.float32)
    A = jnp.asarray(-np.abs(rng.randn(di, N)), jnp.float32)
    h0 = jnp.asarray(rng.randn(B, di, N), jnp.float32)
    y0, h_want = ref.selective_scan(dt, x, Bm, Cm, A, h0)
    y1, h_last = ssm_lib._chunked_ssm_scan(dt, A, Bm, Cm, x, h0, 16)
    np.testing.assert_allclose(np.asarray(h_last), np.asarray(h_want),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), atol=1e-4)


def test_jamba_mamba_block_decode_equivalence(rng):
    """Jamba's Mamba (conv bias, RMSNorms on Δ, B and C): the full-sequence
    block and token-by-token decoding agree; falcon-mamba's parameters
    carry none of the additions."""
    cfg = get_reduced("jamba2-mini")
    p = ssm_lib.init_mamba(jax.random.PRNGKey(0), cfg, jnp.float32)
    assert {"conv_b", "dt_norm", "b_norm", "c_norm"} <= set(p)
    p = dict(p, dt_norm=p["dt_norm"] + 0.3, b_norm=p["b_norm"] - 0.2)
    falcon = ssm_lib.init_mamba(jax.random.PRNGKey(0),
                                get_reduced("falcon-mamba-7b"), jnp.float32)
    assert not {"conv_b", "dt_norm", "b_norm", "c_norm"} & set(falcon)
    B, S = 2, 9
    x = jnp.asarray(rng.randn(B, S, cfg.d_model) * 0.3, jnp.float32)
    y_full, cache_full = ssm_lib.mamba_block(p, x, cfg)
    cache = {"h": jnp.zeros((B, cfg.d_inner, cfg.ssm_state)),
             "conv": jnp.zeros((B, cfg.ssm_conv - 1, cfg.d_inner))}
    outs = []
    for t in range(S):
        o, cache = ssm_lib.mamba_decode(p, x[:, t:t + 1], cache, cfg)
        outs.append(o)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, 1)),
                               np.asarray(y_full), atol=2e-4)
    np.testing.assert_allclose(np.asarray(cache["h"]),
                               np.asarray(cache_full["h"]), atol=1e-4)
