"""The trunk-step driver end to end at a tiny size on the CPU, past the
harness's look for a chip, with its faults planted underneath."""
import os

import pytest

from bench.tests import tiny

CELL = "trunk-l14text-qlora-s77"


def test_sound_run_is_correct(capsys):
    out = tiny.run_tiny(CELL, capsys)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert set(out["checks"]) == {"loss_gap", "grad_gap", "change_gap"}


def _unchanged(monkeypatch):
    from repro.core import optim
    monkeypatch.setattr(optim, "adam_update",
                        lambda g, s, p, **kw: (p, s._replace(
                            step=s.step + 1)))


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
def test_fault_is_not_correct(fault, monkeypatch, capsys):
    fault(monkeypatch)
    out = tiny.run_tiny(CELL, capsys)
    assert out["correct"] is False


def test_control_reads_above_the_program():
    """The control (the reference with float8 products in the program's
    place) and the half-batch fault read far above the program."""
    from bench import run as run_lib
    _, config, traffic = tiny.cell(CELL)
    drv = run_lib.load_module(os.path.join(tiny.BENCH, "drivers",
                                           "trunk_step.py"), "trunk_t")
    got = drv.readings(config, traffic, 5, program=True, control=True)
    keys = traffic["limits"]
    prog = max(got["program"][k] for k in keys)
    for kind in ("control", "half_batch"):
        assert max(got[kind][k] for k in keys) > 3 * prog, got
