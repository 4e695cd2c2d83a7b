"""The comparison that decides ``correct`` for a training cell.

Three numbers, each against its limit from ``limits/<cell>.json``:

  * ``loss_gap``: the largest relative gap between the program's and the
    reference's loss over the checked steps;
  * ``grad_gap``: the worst leaf's gap between the norms of the first
    gradient as the optimizer holds it (first moment after step 1);
  * ``update_gap``: the worst leaf's gap between the norms of the parameters'
    change over the checked steps.

A leaf's gap is measured against the reference's norm of that leaf or of the
median leaf, whichever is larger.  ``update_gap`` leaves out leaves whose
reference gradient is under a thousandth of the median leaf's (they move by
round-off alone).  A number that is not finite reads as infinity.
"""
from __future__ import annotations

import math
import statistics

MOVED = 1e-3
NUMBERS = ("loss_gap", "grad_gap", "update_gap")


def _gap(p, r, floor):
    g = abs(p - r) / floor
    return g if math.isfinite(g) else math.inf


def _worst_leaf(prog, ref, keep):
    floor = statistics.median([r for r, k in zip(ref, keep) if k])
    return max((_gap(p, r, max(r, floor)), i)
               for i, (p, r, k) in enumerate(zip(prog, ref, keep)) if k)


def numbers(prog: dict, ref: dict) -> dict:
    """The three numbers and the worst leaf's index for the two leaf gaps.

    Each side holds ``losses``, ``grad_norms`` and ``update_norms``."""
    loss = max(_gap(p, r, abs(r)) for p, r in zip(prog["losses"], ref["losses"]))
    g_med = statistics.median(ref["grad_norms"])
    moved = [g >= MOVED * g_med for g in ref["grad_norms"]]
    grad, gi = _worst_leaf(prog["grad_norms"], ref["grad_norms"], [True] * len(moved))
    upd, ui = _worst_leaf(prog["update_norms"], ref["update_norms"], moved)
    return {"loss_gap": loss, "grad_gap": grad, "update_gap": upd,
            "worst_grad_leaf": gi, "worst_update_leaf": ui}


def judge(nums: dict, limits: dict):
    """(correct, {name: {"value", "limit"}}) over the three numbers."""
    shown = {k: {"value": nums[k], "limit": limits[k]} for k in NUMBERS}
    return all(v["value"] <= v["limit"] for v in shown.values()), shown
