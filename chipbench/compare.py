"""The numbers that decide ``correct`` for a training cell.

The program and the reference each run the cell's first steps from the same
initial weights on the same batches.  Three numbers compare them:

* ``loss_gap``: the relative gap between the first step's two losses;
* ``grad_gap``: the first step's gradient as the optimizer got it (read back
  from its first moment), by the worst leaf;
* ``update_gap``: the parameters' change over the first steps, by the worst
  leaf.

The first step's loss is compared, not the later ones: Adam's first step
moves every weight by the learning rate times the sign of its gradient, so
a gradient element that is nought to rounding can take opposite signs in
the program and the reference, and the later losses then part by far more
than rounding (PERF.md, Findings, PR 12).  The later steps' gaps are still
printed, as readings.

A leaf's gap is ``| ||a|| - ||r|| |`` (the gap between the two norms, not the
norm of the difference) over the larger of the reference leaf's norm and the
median leaf's norm, since some gradients are all but zero.  Leaves whose
reference gradient is under a thousandth of the median leaf's move under
Adam by round-off alone; they are left out of ``update_gap``.
"""
from __future__ import annotations

import numpy as np

NEGLIGIBLE_GRAD = 1e-3


def _leaves(tree) -> list:
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(jax.tree_util.keystr(p), np.asarray(v, np.float64))
            for p, v in flat]


def _norms(tree) -> dict:
    return {k: float(np.linalg.norm(v)) for k, v in _leaves(tree)}


def leaf_gaps(prog, ref, keep=None) -> dict:
    """Each leaf's gap (see the module docstring)."""
    a, r = _norms(prog), _norms(ref)
    names = [k for k in r if keep is None or k in keep]
    median = float(np.median([r[k] for k in names]))
    return {k: abs(a[k] - r[k]) / max(r[k], median, 1e-30) for k in names}


def worst_leaf_gap(prog, ref, keep=None) -> tuple:
    """``(gap, leaf)`` of the worst leaf."""
    gaps = leaf_gaps(prog, ref, keep)
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def sign_flips(prog, ref) -> dict:
    """Per leaf, the elements of two gradients whose signs differ, and the
    largest of them relative to the leaf's largest element."""
    out = {}
    r = dict(_leaves(ref))
    for k, a in _leaves(prog):
        flip = np.sign(a) != np.sign(r[k])
        if flip.any():
            out[k] = (int(flip.sum()), float(np.abs(r[k][flip]).max()
                                             / np.abs(r[k]).max()))
    return out


def _delta(after, before):
    import jax
    return jax.tree.map(lambda x, y: np.asarray(x, np.float64)
                        - np.asarray(y, np.float64), after, before)


def training_numbers(prog: dict, ref: dict) -> tuple:
    """``(numbers, notes)``.  ``prog`` and ``ref`` each hold ``losses``,
    ``first_grad``, ``params0`` and ``params_after``."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    step_gaps = np.abs(lp - lr) / np.maximum(np.abs(lr), 1e-30)
    grad_gap, grad_leaf = worst_leaf_gap(prog["first_grad"],
                                         ref["first_grad"])
    g = _norms(ref["first_grad"])
    median = float(np.median(list(g.values())))
    keep = {k for k, v in g.items() if v >= NEGLIGIBLE_GRAD * median}
    update_gap, update_leaf = worst_leaf_gap(
        _delta(prog["params_after"], prog["params0"]),
        _delta(ref["params_after"], ref["params0"]), keep)
    flips = sign_flips(prog["first_grad"], ref["first_grad"])
    notes = [f"losses program {lp.tolist()} reference {lr.tolist()}",
             f"worst grad leaf {grad_leaf}, worst update leaf {update_leaf}"
             f"; leaves left out of the update: {sorted(set(g) - keep)}",
             f"first-gradient elements of opposite sign (count, largest "
             f"relative size) by leaf: {flips}"]
    if not np.all(np.isfinite(lp)):
        step_gaps[:] = float("inf")
    updates = leaf_gaps(_delta(prog["params_after"], prog["params0"]),
                        _delta(ref["params_after"], ref["params0"]), keep)
    return ({"loss_gap": float(step_gaps[0]), "grad_gap": grad_gap,
             "update_gap": update_gap,
             "loss_gap_all_steps": float(np.max(step_gaps)),
             "update_gap_median": float(np.median(list(updates.values()))),
             "sign_flips": int(sum(n for n, _ in flips.values()))}, notes)
