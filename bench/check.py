"""The numbers that decide ``correct``, each against its limit.

Training (three steps of the program against three of the reference):
  loss_gap    max over steps of |loss - ref loss| / |ref loss|
  grad_gap    worst leaf of | |g| - |g_ref| | / max(|g_ref|, median leaf
              |g_ref|), g the first step's gradient as the optimizer got it
              (after clipping)
  change_gap  the same for the parameters' change over the three steps,
              over the leaves whose reference gradient is at least 1e-3 of
              the median leaf's (a gradient that is nought to rounding
              moves a leaf under Adam by round-off alone)

Serving:
  gap         the widest gap, in logits, by which a served token lies below
              the reference's best logit at the position that produced it

Every cell:
  window_compiles  programs compiled (or loaded from the compile cache)
              inside the measured window; the limit is 0, since set-up
              runs every shape the window uses

A number that is not finite fails.  The limits live in the cell's file
(``bench/cells/<cell>.json``), with the readings they were set from in
PERF.md.
"""
from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

MOVED_FLOOR = 1e-3


def _gap(a, b, floor):
    return abs(a - b) / max(b, floor)


def train_numbers(prog: dict, ref: dict) -> Dict[str, float]:
    """prog/ref: {"losses": [3], "grad": {leaf: norm}, "change": {leaf:
    norm}}."""
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(prog["losses"], ref["losses"], strict=True))
    g_med = float(np.median(list(ref["grad"].values())))
    grad_gap = max(_gap(prog["grad"][k], g, g_med)
                   for k, g in ref["grad"].items())
    moved = [k for k, g in ref["grad"].items() if g >= MOVED_FLOOR * g_med]
    c_med = float(np.median([ref["change"][k] for k in moved]))
    change_gap = max(_gap(prog["change"][k], ref["change"][k], c_med)
                     for k in moved)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "change_gap": change_gap}


def limits(cell) -> Dict[str, float]:
    """The cell's limits (``bench/cells/<cell>.json``) and the limit 0 on
    compiles inside the window."""
    return dict(cell.cell["limits"], window_compiles=0)


def judge(numbers: Dict[str, float], limits: Dict[str, float]):
    """(correct, compared): every number finite and at most its limit."""
    compared = {k: {"value": float(v), "limit": limits[k]}
                for k, v in numbers.items()}
    ok = all(math.isfinite(v) and v <= limits[k] for k, v in numbers.items())
    return ok, compared


def worst_leaves(prog: dict, ref: dict, n: int = 3) -> List[str]:
    """The leaves that set grad_gap, for the log."""
    g_med = float(np.median(list(ref["grad"].values())))
    order = sorted(ref["grad"], key=lambda k: -_gap(prog["grad"][k],
                                                    ref["grad"][k], g_med))
    return [f"{k}: {prog['grad'][k]:.6g} vs {ref['grad'][k]:.6g}"
            for k in order[:n]]
