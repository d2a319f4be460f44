"""The numbers that decide ``correct`` for a training cell, on hand-built
trees: which leaves count, the gap of norms, and the element-wise error
of an update that a gap of norms cannot see."""
import numpy as np
import pytest

from bench.lib import compare


def _tree(a, b, c):
    return {"a": np.asarray(a, np.float64), "b": np.asarray(b, np.float64),
            "c": np.asarray(c, np.float64)}


P0 = _tree([0.0, 0.0, 0.0, 0.0], [1.0, 1.0], [0.0, 0.0])
R1 = _tree([1.0, -1.0, 1.0, -1.0], [1.5, 0.5], [0.1, 0.0])
KEEP = ["['a']", "['b']", "['c']"]


def test_kept_leaves_drop_a_gradient_nought_to_rounding():
    got = compare.kept_leaves({"x": 1.0, "y": 2.0, "z": 1e-6, "w": 0.0})
    assert got == ["x", "y"]


@pytest.mark.parametrize("p1, want", [
    (R1, {"['a']": 0.0, "['b']": 0.0, "['c']": 0.0}),
    # the state left unchanged: every leaf's whole update is missing
    (P0, {"['a']": 1.0, "['b']": 1.0, "['c']": 0.1 * np.sqrt(2)}),
], ids=["same_update", "state_unchanged"])
def test_error_gaps(p1, want):
    got = compare.error_gaps(P0, p1, P0, R1, KEEP)
    assert got == pytest.approx(want)


def test_direction_change_shows_in_error_not_in_norm_gap():
    """Adam's steps keep their size when their signs flip: the gap of
    norms reads nothing, the element-wise error reads the flip."""
    flipped = _tree([1.0, 1.0, 1.0, -1.0], [1.5, 0.5], [0.1, 0.0])
    norms = lambda t: compare.leaf_norms(compare.diff(t, P0))
    gap, _ = compare.norm_gap(norms(flipped), norms(R1), KEEP)
    assert gap == pytest.approx(0.0)
    err = compare.error_gaps(P0, flipped, P0, R1, KEEP)
    assert err["['a']"] == pytest.approx(1.0)
    assert err["['b']"] == err["['c']"] == 0.0


def test_leaf_gaps_use_the_median_leaf_for_small_leaves():
    ref = {"x": 1.0, "y": 2.0, "z": 0.01}
    prog = {"x": 1.1, "y": 2.0, "z": 0.02}
    got = compare.leaf_gaps(prog, ref, ["x", "y", "z"])
    # z's gap of 0.01 is measured against the median leaf (1.0), not 0.01
    assert got == pytest.approx({"x": 0.1, "y": 0.0, "z": 0.01})


def test_loss_gap_is_infinite_for_a_non_finite_loss():
    assert compare.loss_gap([1.0, np.nan], [1.0, 1.0]) == float("inf")
    assert compare.loss_gap([1.1, 2.0], [1.0, 2.0]) == pytest.approx(0.1)
