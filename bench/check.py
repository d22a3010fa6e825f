"""The comparison that decides ``correct`` for a training cell.

The numbers (each compared only where ``bench/limits/<cell>.json`` gives
it a limit; the limits file says why the others are not compared):

* ``loss_gap`` — the largest relative gap between the program's and the
  reference's loss over the first steps;
* ``grad_norm_gap`` — the first gradient as the optimizer got it, read
  from its state after one step (the moment ``m = (1 - b1)·band``): per
  leaf and layer, the gap between the program's norm and the reference's,
  over the larger of the reference's norm and the median slice's; the
  worst slice;
* ``update_norm_gap`` — the same measure of the parameters' change after
  the first steps, the worst slice; ``update_median_gap`` — its median
  slice.  Slices whose reference gradient is under a thousandth of the
  median slice's (a key bias under softmax: zero but for rounding) move
  under Adam by round-off alone and are left out of both.

A number that is not finite compares as infinite, so it fails.
"""

from __future__ import annotations

import math
from typing import Dict, Optional

import numpy as np

NEGLIGIBLE = 1e-3   # of the median slice's reference gradient norm


def _flat(d: Dict[str, np.ndarray], keys) -> np.ndarray:
    return np.concatenate([np.asarray(d[k], np.float64).reshape(-1)
                           for k in keys])


def norm_gaps(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
              keep: Optional[Dict[str, np.ndarray]] = None) -> np.ndarray:
    """``|‖prog‖ - ‖ref‖| / max(‖ref‖, median ‖ref‖)`` per slice
    (``keep``: boolean masks of the slices that count)."""
    if set(prog) != set(ref):
        return np.array([math.inf])
    keys = sorted(ref)
    p, r = _flat(prog, keys), _flat(ref, keys)
    if p.shape != r.shape:
        return np.array([math.inf])
    if keep is not None:
        k = _flat(keep, keys).astype(bool)
        p, r = p[k], r[k]
    if not np.all(np.isfinite(p)):
        return np.array([math.inf])
    floor = np.median(r)
    return np.abs(p - r) / np.maximum(r, floor)


def loss_gap(prog_losses, ref_losses) -> float:
    if len(prog_losses) != len(ref_losses):
        return math.inf
    p = np.asarray(prog_losses, np.float64)
    r = np.asarray(ref_losses, np.float64)
    if not np.all(np.isfinite(p)):
        return math.inf
    return float(np.max(np.abs(p - r) / np.abs(r)))


def moving_slices(grad_full: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """Masks of the slices whose reference gradient is not negligible."""
    med = np.median(_flat(grad_full, sorted(grad_full)))
    return {k: np.asarray(v) >= NEGLIGIBLE * med
            for k, v in grad_full.items()}


def numbers(prog: dict, ref: dict) -> dict:
    """Every number above.  ``prog`` and ``ref`` hold ``losses``,
    ``grad_band`` and ``change`` (``ref`` also ``grad_full``)."""
    grad = norm_gaps(prog["grad_band"], ref["grad_band"])
    change = norm_gaps(prog["change"], ref["change"],
                       moving_slices(ref["grad_full"]))
    return {"loss_gap": loss_gap(prog["losses"], ref["losses"]),
            "grad_norm_gap": float(np.max(grad)),
            "update_norm_gap": float(np.max(change)),
            "update_median_gap": float(np.median(change))}


def compare(prog: dict, ref: dict, limits: dict) -> tuple:
    """``(correct, {name: (value, limit)})`` over the numbers ``limits``
    names (keys starting with ``_`` are notes)."""
    nums = numbers(prog, ref)
    check = {k: (nums[k], float(v)) for k, v in limits.items()
             if not k.startswith("_")}
    correct = bool(check) and all(v <= lim for v, lim in check.values())
    return correct, check
