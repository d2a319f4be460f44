"""The Jamba step driver end to end at a tiny size on the CPU, past the
harness's look for a chip, with its faults planted underneath; and its
counters."""
import copy
import json

import pytest

from bench.tests import tiny

CELL = "jamba2mini-p8-qlora-s2048"
SEED = 2**31 + 11


def tiny_cell():
    """The cell's files with every width cut for the CPU: one whole
    period (8 layers) is kept. The program computes in float32 here: at
    32 tokens a step a bfloat16 rounding that flips one token's second
    expert moves the loss by a thousandth, while in float32 the program
    and the reference agree to rounding, so any fault stands out."""
    from bench import run as run_lib
    w, config, traffic = run_lib.cell(tiny.spec(), CELL)
    config, traffic = copy.deepcopy(config), copy.deepcopy(traffic)
    config["model"].update(d_model=128, n_heads=4, n_kv_heads=2,
                           head_dim=32, d_ff=256, vocab_size=512,
                           n_experts=4, ssm_state=8, ssm_dt_rank=8,
                           lora_rank=4, adapter_heads=4, dtype="float32")
    traffic.update(seq=32, vocab=512, docs_per_client=8)
    return w, config, traffic


@pytest.fixture(scope="module")
def ref_out():
    """The tiny cell's reference steps, shared by the fault tests."""
    from bench.drivers import jamba_step
    _, config, traffic = tiny_cell()
    return jamba_step.reference(config, traffic, SEED)


def run_tiny(capsys, **kw):
    from bench import run as run_lib
    w, config, traffic = tiny_cell()
    rc = run_lib.run_cell(tiny.args(CELL, **kw), w, config, traffic,
                          tiny.spec(), tiny.CPU_PEAKS)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct(capsys):
    """At ``reduced()``'s widths in float32 the program's losses, first
    gradient and change after three Adam steps are the reference's to
    float32 rounding (read 2e-7, 3e-6 and 6e-6)."""
    out = run_tiny(capsys)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert set(out["checks"]) == {"loss_gap", "grad_gap", "change_gap"}
    assert all(c["value"] < 1e-4 for c in out["checks"].values()), \
        out["checks"]


def _unchanged(monkeypatch):
    from repro.core import optim
    monkeypatch.setattr(optim, "adam_update",
                        lambda g, s, p, **kw: (p, s._replace(
                            step=s.step + 1)))


def _half_tokens(monkeypatch):
    from repro.core import losses
    real = losses.cross_entropy

    def half(logits, labels, mask=None):
        h = labels.shape[1] // 2
        return real(logits[:, :h], labels[:, :h],
                    None if mask is None else mask[:, :h])
    monkeypatch.setattr(losses, "cross_entropy", half)


def _capacity(monkeypatch):
    """A capacity-bounded router in the dropless one's place: copies past
    the fair share are dropped."""
    from repro.models import moe
    monkeypatch.setattr(moe, "moe_dropless", lambda p, x, cfg, **kw: (
        *moe._moe_local(p, x, cfg.replace(capacity_factor=1.0)),
        {"moe_load_max": 0.0 * x[0, 0], "moe_pad_rows": 0.0 * x[0, 0]}))


@pytest.mark.parametrize("fault", [_unchanged, _half_tokens, _capacity],
                         ids=["state_unchanged", "half_tokens",
                              "dropping_router"])
def test_fault_is_not_correct(fault, monkeypatch, ref_out):
    """The program's first steps with a fault planted, against the
    reference, fail at least one of the cell's limits."""
    from bench.drivers import jamba_step
    fault(monkeypatch)
    _, config, traffic = tiny_cell()
    step, frozen, tr, opt, toks = jamba_step.build(config, traffic, SEED)
    _, _, snaps, g1, losses = jamba_step.first_steps(step, frozen, tr, opt,
                                                     toks)
    got = jamba_step.trunk_step.numbers(snaps, g1, losses, *ref_out)
    assert any(got[k] > v for k, v in traffic["limits"].items()), got


def test_traced_numbers_from_layer_counts():
    """The step's operations and the kernels' calls count routed rows
    only, and the scan's calls carry bytes and no operations."""
    from bench.lib import flops, flops_jamba, ref_jamba
    _, config, traffic = tiny_cell()
    m = config["model"]
    kinds = ref_jamba.sizes(config)["kinds"]
    assert kinds == [("mamba", "mlp"), ("mamba", "moe"), ("mamba", "mlp"),
                     ("mamba", "moe"), ("attn", "mlp"), ("mamba", "moe"),
                     ("mamba", "mlp"), ("mamba", "moe")]
    fwd, dx = flops_jamba.gmm_calls(m, 1, 2048)
    Tk, d, ff = 2048 * 2, m["d_model"], m["d_ff"]
    assert [c["flops"] for c in fwd] == [flops.mm(Tk, d, ff)] * 2 + [
        flops.mm(Tk, ff, d)]
    assert sorted(c["flops"] for c in dx) == sorted(c["flops"] for c in fwd)
    # the fused LoRA kernels: attention's four projections (GQA k, v)
    # and each of the four dense FFNs' three, once each a period
    lf, lb = flops_jamba.lora_calls(m, kinds, 1, 64, x_bytes=4)
    qd, kvd = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    want = sorted([(d, qd), (d, kvd), (d, kvd), (qd, d)] +
                  4 * [(d, ff), (d, ff), (ff, d)])
    assert sorted(c["flops"] for c in lb) == sorted(
        flops.mm(64, k, n) for k, n in want)
    assert sorted(c["flops"] for c in lf) == sorted(
        flops.mm(64, k, n) + flops.lora_fwd(64, k, n, m["lora_rank"])
        for k, n in want)
    sf, sb = flops_jamba.scan_calls(m, 1, 2048)
    assert sf[0]["flops"] == sb[0]["flops"] == 0 and sb[0]["bytes"] > \
        sf[0]["bytes"] > 0
    # one more MoE layer in the pattern adds its routed products alone
    more = kinds + [("mamba", "moe")]
    extra = flops_jamba.step(m, more, 1, 64) - flops_jamba.step(
        m, kinds, 1, 64)
    di, N, R, r, E = 2 * d, m["ssm_state"], m["ssm_dt_rank"], \
        m["lora_rank"], m["n_experts"]
    T = 64
    mamba = sum(2 * flops.mm(T, a, b) + 3 * flops.lora_fwd(T, a, b, r)
                for a, b in [(d, di), (di, d)]) + 2 * (
        flops.mm(T, d, di) + flops.mm(T, di, R + 2 * N) + flops.mm(T, R, di))
    moe = 2 * (flops.mm(T, d, E) + 2 * flops.mm(2 * T, d, ff) +
               flops.mm(2 * T, ff, d))
    assert extra == pytest.approx(mamba + moe)


def test_new_readers_on_a_hand_trace():
    """The cell's six per-layer readers, as the harness loads them, on a
    hand-built trace carrying the new kernels' instruction names; on a
    trace without them the rooflines read nothing."""
    import os
    from bench import run as run_lib
    from bench.lib import harness
    from bench.lib import trace as T
    dev = [T.Event("moe_gmm_fwd.3", 0.0, 1.0),
           T.Event("moe_gmm_dx.4", 1.0, 2.0),
           T.Event("ssm_scan_fwd.5", 3.0, 0.5),
           T.Event("ssm_scan_bwd.6", 3.5, 1.0)]
    tr = T.Trace(devices=[dev], host=[], t0=0.0, t1=10.0)
    pk = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}
    scan = [{"flops": 0.0, "bytes": 2e10}]
    layer = {"work": 2, "flops_per_work": 1e12,
             "kernels": {"moe_gmm_fwd": [{"flops": 5e11, "bytes": 1e9}],
                         "moe_gmm_dx": [{"flops": 5e11, "bytes": 1e9}],
                         "ssm_scan_fwd": scan, "ssm_scan_bwd": scan}}
    want = {"device_idle.jamba": 55.0, "mfu.jamba_step": 20.0,
            "roofline.moe_gmm_fwd": 50.0, "roofline.moe_gmm_dx": 25.0,
            "roofline.ssm_scan_fwd": 40.0, "roofline.ssm_scan_bwd": 20.0}
    metrics = [m for m in tiny.spec()["per_layer"]
               if CELL in m.get("workloads", [])]
    assert sorted(m["name"] for m in metrics) == sorted(want)
    bare = T.Trace(devices=[[T.Event("fusion.1", 0.0, 1.0)]], host=[],
                   t0=0.0, t1=10.0)
    for m in metrics:
        reader = run_lib.load_module(os.path.join(
            tiny.BENCH, "metrics", m["name"] + ".py"), "t_" + m["name"])
        x = harness.Reading(trace=tr, layer=layer, peaks=pk, config={},
                            traffic={})
        assert reader.read(x) == pytest.approx(want[m["name"]]), m["name"]
        if m["name"].startswith("roofline."):
            assert reader.read(harness.Reading(
                trace=bare, layer=layer, peaks=pk, config={},
                traffic={})) is None
