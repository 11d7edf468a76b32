"""The comparison that decides `correct` for a training cell.

Per leaf, the gap of a norm is |norm_program - norm_reference| over the
larger of the leaf's reference norm and the reference's median leaf norm.
From those gaps:

  update_gap          the first update as the optimizer applied it
                      (theta_0 - theta_1): the worst leaf;
  change_gap          the change after the checked steps (theta_n -
                      theta_0), over the leaves whose first reference
                      gradient is at least a thousandth of the median leaf's
                      (a leaf the loss does not reach moves by round-off
                      alone): the worst leaf;
  update_gap_median,  the same two, the median leaf instead of the worst.
  change_gap_median
  update_gap.<group>, the worst leaf of the first two among the leaves of
  change_gap.<group>  a group that the cell's limits file names under
                      "groups" (path prefixes).

A cell compares the numbers its bench/limits/<cell>.json names, each
against its limit; the others are printed.  `loss_gap`, the largest
|loss_program - loss_reference| / |loss_reference| over the checked steps,
is printed and compared by no cell: at random weights the loss barely
depends on the matmul precision, and neither the control nor the faults
move it past what sound runs read (PERF.md gives the readings).
"""
from __future__ import annotations

import math

import numpy as np

NUMBERS = ("loss_gap", "update_gap", "change_gap", "update_gap_median",
           "change_gap_median")
MOVED = 1e-3          # share of the median leaf gradient a leaf must reach


def leaf_gaps(prog, ref, keep=None):
    """Per-leaf |prog - ref| / max(ref, median ref) (NaN where not kept)."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    keep = np.ones(ref.shape, bool) if keep is None else keep
    floor = np.maximum(ref, np.median(ref[keep]))
    return np.where(keep, np.abs(prog - ref) / floor, np.nan)


def names(limits_file: dict) -> set:
    """Every number a cell with this limits file can compare."""
    return set(NUMBERS) | {f"{k}.{g}" for g in limits_file.get("groups", {})
                           for k in ("update_gap", "change_gap")}


def compare(prog: dict, ref: dict, groups: dict | None = None) -> dict:
    """prog/ref: {"losses", "first_update", "change"} (+ ref "first_grad",
    "leaves"); groups: {name: [path prefix, ...]}."""
    lp = np.asarray(prog["losses"], np.float64)
    lr = np.asarray(ref["losses"], np.float64)
    g = np.asarray(ref["first_grad"], np.float64)
    update = leaf_gaps(prog["first_update"], ref["first_update"])
    change = leaf_gaps(prog["change"], ref["change"],
                       g >= MOVED * np.median(g))
    out = {"loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
           "update_gap": float(np.nanmax(update)),
           "change_gap": float(np.nanmax(change)),
           "update_gap_median": float(np.nanmedian(update)),
           "change_gap_median": float(np.nanmedian(change))}
    for name, prefixes in (groups or {}).items():
        inside = np.array([p.startswith(tuple(prefixes))
                           for p in ref["leaves"]])
        out[f"update_gap.{name}"] = float(np.nanmax(update[inside]))
        out[f"change_gap.{name}"] = float(np.nanmax(change[inside]))
    return out


def worst_leaves(prog: dict, ref: dict, n: int = 3) -> dict:
    """The n leaves with the largest gaps, per number, for the log."""
    out = {}
    for k in ("first_update", "change"):
        gaps = leaf_gaps(prog[k], ref[k])
        top = np.argsort(-np.nan_to_num(gaps, nan=-1.0))[:n]
        out[k] = [(ref["leaves"][i], round(float(gaps[i]), 5),
                   float(prog[k][i]), float(ref[k][i])) for i in top]
    return out


def verdict(numbers: dict, limits: dict) -> bool:
    """Every number the cell's limits name is finite and within its limit."""
    return all(math.isfinite(numbers[k]) and numbers[k] <= limits[k]
               for k in limits)
