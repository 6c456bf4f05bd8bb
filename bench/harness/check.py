"""The comparison that decides ``correct`` for a training cell.

The program's readings come from the first three steps that the timed
path itself ran (its own compiled step, fed by the benchmark's feed):

* the loss of each of the three steps;
* the norm of each leaf of the first step's gradient as the optimizer got
  it (after clipping), worked out from Adam's first moment after one step,
  ``mu = (1 - b1) * g``, and of each row of its token embedding (one row
  per vocabulary entry);
* the norm of each leaf's change over the three steps, from the float32
  master weights that the fourth step receives.

The reference gives the same readings from its own three steps on the same
batches.  Three numbers are compared, each against its own limit:

* ``loss_rel``: the largest relative gap of a step's loss;
* ``grad_leaf``: over leaves, the largest gap between the program's and the
  reference's gradient norm, over the larger of the reference's norm of
  that leaf and of the median leaf;
* ``grad_row``: the same over the rows of the token embedding's gradient,
  against the median row: a token fed wrongly moves its row, which no
  norm of a whole leaf resolves;
* ``delta_leaf``: the same for the change of each leaf over three steps,
  over the leaves whose reference gradient is at least a thousandth of the
  median leaf's (a leaf whose gradient is nought to rounding moves under
  Adam by round-off alone).
"""

from __future__ import annotations

import math

import numpy as np

__all__ = ["Readings", "compare", "NAMES"]

NAMES = ("loss_rel", "grad_leaf", "grad_row", "delta_leaf")
DEAD_LEAF = 1e-3


class Readings:
    def __init__(self, losses, grad_norms, change_norms, row_norms):
        self.losses = [float(x) for x in losses]
        self.grad_norms = np.asarray(grad_norms, np.float64)
        self.change_norms = np.asarray(change_norms, np.float64)
        self.row_norms = np.asarray(row_norms, np.float64)

    def as_dict(self) -> dict:
        return {"losses": self.losses,
                "grad_norms": self.grad_norms.tolist(),
                "change_norms": self.change_norms.tolist()}


def _worst_leaf(got: np.ndarray, ref: np.ndarray) -> float:
    if got.shape != ref.shape or ref.size == 0:
        return math.inf
    floor = np.maximum(ref, np.median(ref))
    gap = np.abs(got - ref) / np.where(floor > 0, floor, 1.0)
    return float(np.max(gap)) if np.all(np.isfinite(gap)) else math.inf


def compare(prog: Readings, ref: Readings) -> dict:
    """{number: value} of the three compared numbers."""
    if len(prog.losses) != len(ref.losses):
        loss_rel = math.inf
    else:
        gaps = [abs(a - b) / abs(b) for a, b in zip(prog.losses, ref.losses)]
        loss_rel = max(gaps) if all(map(math.isfinite, gaps)) else math.inf
    keep = ref.grad_norms >= DEAD_LEAF * np.median(ref.grad_norms)
    if prog.change_norms.shape != ref.change_norms.shape:
        delta = math.inf
    else:
        delta = _worst_leaf(prog.change_norms[keep], ref.change_norms[keep])
    return {"loss_rel": float(loss_rel),
            "grad_leaf": _worst_leaf(prog.grad_norms, ref.grad_norms),
            "grad_row": _worst_leaf(prog.row_norms, ref.row_norms),
            "delta_leaf": delta}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {number: {"value", "limit"}}).  A number that is not
    finite always fails."""
    out, ok = {}, True
    for name in NAMES:
        v, lim = numbers[name], limits[name]
        out[name] = {"value": v, "limit": lim}
        if not math.isfinite(v) or v > lim:
            ok = False
    return ok, out
