"""Operation and byte counters against hand counts at the cells'
shapes, and the peaks table."""
import json
import os

import pytest

from bench.lib import flops, peaks

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def load(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def test_lora_matmul_call_at_trunk_shapes():
    # M = 64 x 77 tokens, K = 768, N = 3072, rank 16, NF4 block 64,
    # bf16 activations, float32 LoRA factors
    c = flops.lora_matmul_call(4928, 768, 3072, 16, x_bytes=2, block=64)
    assert c["flops"] == 2 * 4928 * 768 * 3072 + \
        2 * 4928 * 16 * (768 + 3072)
    assert c["bytes"] == (4928 * 768 * 2 + 768 * 3072 / 2 +
                          12 * 3072 * 4 + 768 * 16 * 4 + 16 * 3072 * 4 +
                          4928 * 3072 * 2)


def test_quant_matmul_t_call_and_bound():
    c = flops.quant_matmul_t_call(4928, 768, 768, block=64)
    assert c["flops"] == 2 * 4928 * 768 * 768
    assert c["bytes"] == 4928 * 768 * 4 + 768 * 768 / 2 + 12 * 768 * 4 + \
        4928 * 768 * 4
    pk = peaks.peaks("TPU v5 lite")
    # 5.8 GFLOP over 30.6 MB: ~190 FLOP/B against a ridge of ~240
    assert flops.bound(c, pk) == "memory"
    big = flops.lora_matmul_call(4928, 768, 3072, 16, x_bytes=2, block=64)
    assert flops.bound(big, pk) == "compute"
    assert flops.least_time(big, pk) == pytest.approx(
        big["flops"] / 197e12)


def test_trunk_step_hand_count():
    m = load("bench/configs/clip-text-l14.json")["model"]
    T, d, f, V, r, S = 64 * 77, 768, 3072, 49408, 16, 77
    proj = 2 * T * (4 * d * d + 2 * d * f)
    lora = 2 * T * r * (4 * 2 * d + 2 * (d + f))
    att = 4 * 64 * d * S * (S + 1) / 2
    layers = 12 * (2 * proj + 3 * att + 3 * lora)
    adapter = 3 * 2 * T * 6 * d * d + 3 * att
    head = 2 * 2 * T * d * V
    assert flops.trunk_step(m, 64, 77) == pytest.approx(
        layers + adapter + head)
    # ~2.6 TFLOP a step, ~533 MFLOP a token
    assert 2.5e12 < flops.trunk_step(m, 64, 77) < 2.7e12


def test_clip_round_hand_count():
    c = load("bench/configs/clip-vit-b32.json")
    t = load("bench/traffic/fl_qlora_k8.json")
    S, d, f, r = 50, 768, 3072, 4
    frozen = 2 * S * (4 * d * d + 2 * d * f)
    att = 4 * S * S * d
    lora = 4 * 2 * S * r * 2 * d
    per = 12 * (2 * frozen + 3 * att + 3 * lora) + \
        3 * 2 * 6 * d * d + 2 * (2 * d * 512 + 2 * 512 * 7)
    n = 8 * 8 * 32
    got = flops.clip_round(c["model"], t, use_lora=True, rank=4)
    assert got == pytest.approx(n * per)
    assert 3.4e13 < got < 3.7e13                  # ~35 TFLOP a round
    fed = flops.clip_round(c["model"], t, use_lora=False, rank=4)
    assert fed == pytest.approx(n * (3 * 2 * 6 * d * d +
                                     2 * (2 * d * 512 + 2 * 512 * 7)))


def test_peaks_known_and_unknown():
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("cpu")
