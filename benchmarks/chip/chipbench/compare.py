"""The arithmetic of the numbers that decide ``correct``.

Training (per run): each of the first three steps' loss against the
reference's; the norm of the first gradient as the optimizer got it, leaf
by leaf; the norm of each leaf's change over the three steps. A leaf's gap
is ``|prog - ref| / max(ref, median leaf's ref)``, and the number is the
worst leaf's. Leaves whose first reference gradient is under a thousandth
of the median leaf's move under Adam by round-off alone; they are left out
of the change.

Inference: the worst row's relative logit error ``||prog - ref|| / ||ref||``.
"""
from __future__ import annotations

import statistics

NEGLIGIBLE = 1e-3


def rel(prog: float, ref: float) -> float:
    return abs(prog - ref) / max(abs(ref), 1e-30)


def worst_leaf(prog: dict, ref: dict, skip=()) -> tuple:
    """``(gap, leaf)`` of the leaf whose norm is farthest from the
    reference's, against that norm or the median leaf's, whichever is
    larger. A leaf missing on the program's side reads as norm 0."""
    med = statistics.median(ref.values())
    gaps = {k: abs(prog.get(k, 0.0) - r) / max(r, med, 1e-30)
            for k, r in ref.items() if k not in skip}
    leaf = max(gaps, key=gaps.get)
    return gaps[leaf], leaf


def negligible(grad_norms: dict) -> set:
    med = statistics.median(grad_norms.values())
    return {k for k, v in grad_norms.items() if v < NEGLIGIBLE * med}


def train_numbers(prog: dict, ref: dict) -> tuple:
    """``(numbers, detail)`` from two readings, each
    ``{"losses": [...], "grad1": {leaf: norm}, "change": {leaf: norm}}``."""
    if len(prog["losses"]) != len(ref["losses"]):
        raise ValueError("the program and the reference took different "
                         "numbers of steps")
    loss_gap = max(rel(p, r) for p, r in zip(prog["losses"], ref["losses"]))
    g_gap, g_leaf = worst_leaf(prog["grad1"], ref["grad1"])
    skip = negligible(ref["grad1"])
    c_gap, c_leaf = worst_leaf(prog["change"], ref["change"], skip)
    return ({"loss_gap": loss_gap, "grad1_gap": g_gap,
             "change3_gap": c_gap},
            {"grad1_worst_leaf": g_leaf, "change3_worst_leaf": c_leaf,
             "left_out_of_change": sorted(skip),
             "losses": prog["losses"], "ref_losses": ref["losses"]})
