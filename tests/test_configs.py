"""The assigned architecture table, verified literally."""
import pytest

from repro.configs import ARCHS, INPUT_SHAPES, get_config, get_reduced

EXPECTED = {
    # arch: (family, L, d_model, H, kv, d_ff, vocab)
    "yi-9b": ("dense", 48, 4096, 32, 4, 11008, 64000),
    "qwen3-moe-235b-a22b": ("moe", 94, 4096, 64, 4, 1536, 151936),
    "h2o-danube-3-4b": ("dense", 24, 3840, 32, 8, 10240, 32000),
    "whisper-medium": ("encdec", 24, 1024, 16, 16, 4096, 51865),
    "falcon-mamba-7b": ("ssm", 64, 4096, 0, 0, 0, 65024),
    "llava-next-34b": ("vlm", 60, 7168, 56, 8, 20480, 64000),
    "codeqwen1.5-7b": ("dense", 32, 4096, 32, 32, 13440, 92416),
    "recurrentgemma-2b": ("hybrid", 26, 2560, 10, 1, 7680, 256000),
    "kimi-k2-1t-a32b": ("moe", 61, 7168, 64, 8, 2048, 163840),
    "starcoder2-15b": ("dense", 40, 6144, 48, 4, 24576, 49152),
}


# registered besides the assigned ten: the hybrid SSM/MoE client
REGISTERED = {**EXPECTED,
              "jamba2-mini": ("hybrid_moe", 32, 4096, 32, 8, 14336, 65536)}


def test_all_ten_assigned():
    assert set(ARCHS) == set(EXPECTED)


@pytest.mark.parametrize("arch", sorted(REGISTERED))
def test_exact_table(arch):
    fam, L, d, H, kv, ff, V = REGISTERED[arch]
    c = get_config(arch)
    assert (c.family, c.n_layers, c.d_model, c.n_heads, c.n_kv_heads,
            c.d_ff, c.vocab_size) == (fam, L, d, H, kv, ff, V)
    assert c.source


def test_moe_details():
    q = get_config("qwen3-moe-235b-a22b")
    assert (q.n_experts, q.experts_per_token) == (128, 8)
    k = get_config("kimi-k2-1t-a32b")
    assert (k.n_experts, k.experts_per_token, k.n_shared_experts,
            k.first_k_dense) == (384, 8, 1, 1)


def test_special_structure():
    assert get_config("h2o-danube-3-4b").window == 4096
    assert get_config("recurrentgemma-2b").attn_pattern == (
        "rglru", "rglru", "attn")
    assert get_config("falcon-mamba-7b").ssm_state == 16
    assert get_config("whisper-medium").encoder_layers == 24
    assert get_config("whisper-medium").n_frames == 1500
    assert get_config("llava-next-34b").n_patches == 576


def test_param_counts_plausible():
    """6·N·D sanity: totals within ~25% of the published sizes."""
    approx = {"yi-9b": 8.8e9, "falcon-mamba-7b": 7.3e9,
              "starcoder2-15b": 15e9, "llava-next-34b": 34e9,
              "codeqwen1.5-7b": 7.2e9}
    for arch, want in approx.items():
        n = get_config(arch).param_count()
        assert 0.7 * want < n < 1.35 * want, (arch, n)


def test_kimi_is_trillion_scale():
    n = get_config("kimi-k2-1t-a32b").param_count()
    assert 0.8e12 < n < 1.3e12, n
    a = get_config("kimi-k2-1t-a32b").param_count(active_only=True)
    assert a < 6e10, a


def test_input_shapes_table():
    t = INPUT_SHAPES
    assert (t["train_4k"].seq_len, t["train_4k"].global_batch) == \
        (4096, 256)
    assert (t["prefill_32k"].seq_len, t["prefill_32k"].global_batch) == \
        (32768, 32)
    assert (t["decode_32k"].seq_len, t["decode_32k"].global_batch) == \
        (32768, 128)
    assert (t["long_500k"].seq_len, t["long_500k"].global_batch) == \
        (524288, 1)


@pytest.mark.parametrize("arch", sorted(EXPECTED))
def test_reduced_is_same_family(arch):
    r = get_reduced(arch)
    c = get_config(arch)
    assert r.family == c.family
    assert r.attn_pattern == c.attn_pattern
    assert (r.window is None) == (c.window is None)


def test_jamba_pattern_and_param_count():
    """Jamba2-Mini (config.json): attention iff i % 8 == 4, experts iff
    i % 2 == 1, 16 experts top-2, Mamba d_state 16 / d_conv 4 / expand 2
    / dt_rank 256; 51.5 B parameters (52B-A12B as published)."""
    c = get_config("jamba2-mini")
    kinds = c.layer_kinds()
    assert [i for i, k in enumerate(kinds) if k == "attn"] == \
        [4, 12, 20, 28]
    assert [i for i in range(32) if c.expert_layer(i)] == \
        list(range(1, 32, 2))
    assert (c.n_experts, c.experts_per_token, c.n_shared_experts) == \
        (16, 2, 0)
    assert (c.ssm_state, c.ssm_conv, c.d_inner, c.dt_rank) == \
        (16, 4, 8192, 256)
    assert not c.router_renorm
    assert not c.use_rope and c.max_pos == 0
    assert c.period == 8
    assert abs(c.param_count() / 51.5e9 - 1) < 0.02
    assert 11.5e9 < c.param_count(active_only=True) < 12.5e9
    r = get_reduced("jamba2-mini")
    assert r.family == c.family and r.n_layers == c.period
    assert r.attn_pattern == c.attn_pattern
