"""The numbers that decide ``correct`` for a training cell.

For each of the first steps (a local step, or a federated round) the
loss; the first update as the optimizer gets it; and the parameters'
change after the compared steps. The last two are compared leaf by leaf
as norms: the gap between the program's norm and the reference's,
measured against the reference's norm of that leaf or of the median
leaf, whichever is larger, and the worst leaf counts. Leaves whose
reference gradient is nought to rounding (under a thousandth of the
median leaf's) move under Adam by round-off alone and are left out.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

import jax
import numpy as np

EXCLUDE_BELOW = 1e-3


def leaf_norms(tree) -> Dict[str, float]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): float(np.linalg.norm(
        np.asarray(l, np.float64).ravel())) for p, l in flat}


def diff(a, b):
    return jax.tree.map(lambda x, y: np.asarray(x, np.float64) -
                        np.asarray(y, np.float64), a, b)


def kept_leaves(ref_grad_norms: Dict[str, float]) -> List[str]:
    """Leaves whose reference gradient norm (the largest over the
    compared steps) is at least a thousandth of the median leaf's, the
    median taken over the leaves with any gradient."""
    med = float(np.median([v for v in ref_grad_norms.values() if v > 0]))
    return sorted(k for k, v in ref_grad_norms.items()
                  if v >= EXCLUDE_BELOW * med)


def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float],
              keep: Iterable[str]) -> Dict[str, float]:
    """Each kept leaf's |‖prog‖ − ‖ref‖| / max(‖ref‖, median ‖ref‖)."""
    keep = list(keep)
    med = float(np.median([ref[k] for k in keep]))
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
            for k in keep}


def error_gaps(p0, p1, r0, r1, keep: Iterable[str]) -> Dict[str, float]:
    """Each kept leaf's element-wise error of an update: ‖(p1 − p0) −
    (r1 − r0)‖ / max(‖r1 − r0‖, median ‖r1 − r0‖). Unlike a gap of norms
    it sees a change of direction, which Adam's per-element
    normalization leaves at the same norm."""
    keep = list(keep)
    e = leaf_norms(diff(diff(p1, p0), diff(r1, r0)))
    n = leaf_norms(diff(r1, r0))
    med = float(np.median([n[k] for k in keep]))
    return {k: e[k] / max(n[k], med, 1e-30) for k in keep}


def norm_gap(prog: Dict[str, float], ref: Dict[str, float],
             keep: Iterable[str]) -> Tuple[float, str]:
    """The worst kept leaf's gap (:func:`leaf_gaps`); returns (gap,
    leaf)."""
    gaps = leaf_gaps(prog, ref, keep)
    at = max(gaps, key=lambda k: (not np.isfinite(gaps[k]), gaps[k]))
    g = gaps[at]
    return (g if np.isfinite(g) else float("inf")), at


def loss_gap(prog, ref) -> float:
    """Largest relative gap between matching losses."""
    p = np.asarray(prog, np.float64).ravel()
    r = np.asarray(ref, np.float64).ravel()
    g = np.abs(p - r) / np.maximum(np.abs(r), 1e-30)
    return float(np.max(g)) if np.all(np.isfinite(p)) else float("inf")


