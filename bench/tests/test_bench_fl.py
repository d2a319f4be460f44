"""The federated-round driver end to end at a tiny size on the CPU,
past the harness's look for a chip: sound runs come out correct, and
each fault a round can have, planted in the program underneath the
timed path, makes ``correct`` false."""
import pytest

from bench.tests import tiny

CELLS = ["fl-b32-qlora-k8"]


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload, capsys):
    out = tiny.run_tiny(workload, capsys)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"round_s", "setup_s"}
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == set(tiny.cell(workload)[2]["limits"])


def test_traced_run_reports_per_layer_metrics(capsys):
    out = tiny.run_tiny("fl-b32-qlora-k8", capsys, trace=1)
    assert out["correct"] is True
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    # the CPU trace has no device plane: the device readers find nothing
    assert "device_idle.round" not in out["metrics"]
    assert out["metrics"]["mfu.round"]["unit"] == "%"


def _unchanged(monkeypatch):
    from repro.fl import server
    monkeypatch.setattr(server, "aggregate_stacked",
                        lambda g, weights, delta: g)


def _half_batch(monkeypatch):
    from repro.core import losses
    real = losses.cross_entropy

    def half(logits, labels, mask=None):
        h = labels.shape[0] // 2
        return real(logits[:h], labels[:h],
                    None if mask is None else mask[:h])
    monkeypatch.setattr(losses, "cross_entropy", half)


@pytest.mark.parametrize("fault", [_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
@pytest.mark.parametrize("workload", CELLS)
def test_fault_is_not_correct(workload, fault, monkeypatch, capsys):
    fault(monkeypatch)
    out = tiny.run_tiny(workload, capsys)
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


def test_control_reads_above_the_program():
    """The control (the reference in bfloat16 in the program's place)
    and the half-batch fault read far above sound runs of the program."""
    from bench import run as run_lib
    import os
    _, config, traffic = tiny.cell("fl-b32-qlora-k8")
    drv = run_lib.load_module(os.path.join(tiny.BENCH, "drivers",
                                           "fl_round.py"), "fl_round_t")
    got = drv.readings(config, traffic, 5, program=True, control=True)
    keys = traffic["limits"]
    prog = max(got["program"][k] for k in keys)
    for kind in ("control", "half_batch"):
        assert max(got[kind][k] for k in keys) > 10 * prog, got
