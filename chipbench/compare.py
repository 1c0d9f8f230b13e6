"""The numbers that decide ``correct``: the program against the plain reference.

Over the first compared steps of a training cell:

* ``loss``: the largest relative gap of a step's loss.
* ``grad``: the first gradient as the optimizer got it, by the median leaf.
  Not by the worst leaf: the worst leaf is a BatchNorm scale or shift of an
  early layer, whose gradient is a sum over every position in which the
  terms cancel, and which moves by up to about its whole norm between two
  computations at the same precision that differ only in the order of their
  float32 sums.
* ``change``: each leaf's change over the compared steps, by the worst leaf,
  among the leaves that the reference's gradient moves.

A leaf's gap is the gap between the program's norm and the reference's, not
the norm of their difference, over the reference's norm of that leaf or of
the median leaf, whichever is larger (some gradients are all but zero). The
norm of the difference does not separate the faults: the gradient differs
element by element by 0.2 to 0.3 of its norm between the program and the
reference (see ``PERF.md``). A leaf whose reference gradient is under a
thousandth of the median leaf's (a bias that a following BatchNorm cancels)
moves under Adam by round-off alone and is left out of ``change`` by that
rule, not by name.
"""
from __future__ import annotations

from typing import Dict, Iterable, Optional, Sequence, Set

import jax
import numpy as np

MOVING = 1e-3     # of the median leaf's gradient norm


def _norms(tree) -> Dict[str, float]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): float(np.linalg.norm(
        np.asarray(x, np.float64).ravel())) for p, x in flat}


def loss_gap(program: Sequence[float], ref: Sequence[float]) -> float:
    if len(program) != len(ref):
        return float("inf")
    return max(abs(p - r) / max(abs(r), 1e-30) for p, r in zip(program, ref))


def moving_leaves(ref_grad) -> Set[str]:
    """Leaves whose reference gradient is at least ``MOVING`` of the median
    leaf's."""
    n = _norms(ref_grad)
    med = float(np.median(list(n.values())))
    return {k for k, v in n.items() if v >= MOVING * med}


def leaf_gaps(program, ref, keep: Optional[Iterable[str]] = None) -> Dict[str, float]:
    """Each leaf's gap of norms, over the larger of its reference norm and
    the median leaf's."""
    pn, rn = _norms(program), _norms(ref)
    if set(pn) != set(rn):
        return {"<tree mismatch>": float("inf")}
    keys = sorted(rn if keep is None else set(keep) & set(rn))
    med = float(np.median([rn[k] for k in keys]))
    return {k: abs(pn[k] - rn[k]) / max(rn[k], med, 1e-30) for k in keys}


def worst_leaf_gap(program, ref, keep: Optional[Iterable[str]] = None) -> float:
    return max(leaf_gaps(program, ref, keep).values())


def median_leaf_gap(program, ref) -> float:
    return float(np.median(list(leaf_gaps(program, ref).values())))
