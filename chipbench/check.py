"""The comparison that decides ``correct``.

The program's first three training steps, driven through the window's own
entry points in set-up, against the plain reference's three steps on the
same weights and rows.  Three numbers; a cell compares those that its
limits file (``chipbench/limits/<cell>.json``) gives a limit, each its
own (``PERF.md`` gives the readings each limit was set from):

* ``first_loss_gap``: the gap between the program's loss and the
  reference's at the first step, in nats: the model's forward pass in the
  configuration's precision, before any gossip;
* ``grad_gap``: the first gradient as the optimizer gets it, read from the
  momentum after one step (``g + wd x``): the worst leaf's gap between the
  program's norm and the reference's, over the reference's norm of that
  leaf or of the median leaf, whichever is larger;
* ``change_gap``: the parameters' change after three steps, the same gap
  for the median leaf, over the leaves whose reference gradient is at
  least a thousandth of the median leaf's (the rest move by round-off
  alone).

Leaves are the stacked ``[n, ...]`` leaves: all workers together.  The
losses of later steps and the worst leaf's change are not compared: 1-bit
codes turn a gradient's round-off into whole jumps of single parameters,
so those readings swing from seed to seed (``PERF.md`` has the readings).
"""
from __future__ import annotations

import math
from typing import Dict

import numpy as np

NAMES = ("first_loss_gap", "grad_gap", "change_gap")
KEEP_SHARE = 1e-3


def leaf_gaps(prog, ref, keep=None) -> np.ndarray:
    """Per leaf: the gap of the two norms over the reference's norm of
    that leaf or of the median leaf, whichever is larger."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if keep is None:
        keep = np.ones(ref.shape, bool)
    base = max(float(np.median(ref[keep])), np.finfo(np.float64).tiny)
    return (np.abs(prog - ref) / np.maximum(ref, base))[keep]


def kept(ref: dict) -> np.ndarray:
    grad = np.asarray(ref["grad"], np.float64)
    return grad >= KEEP_SHARE * np.median(grad)


def numbers(prog: dict, ref: dict) -> Dict[str, float]:
    """``prog``/``ref``: ``loss`` (per step), ``grad`` and ``change``
    (per leaf)."""
    return {
        "first_loss_gap": float(abs(prog["loss"][0] - ref["loss"][0])),
        "grad_gap": float(np.max(leaf_gaps(prog["grad"], ref["grad"]))),
        "change_gap": float(np.median(leaf_gaps(prog["change"],
                                                ref["change"], kept(ref)))),
    }


def readings(prog: dict, ref: dict) -> Dict[str, float]:
    """The compared numbers and those that are not compared, for setting
    and explaining the limits."""
    return dict(
        numbers(prog, ref),
        loss_gap_by_step=[float(abs(a - b))
                          for a, b in zip(prog["loss"], ref["loss"])],
        worst_change_gap=float(np.max(leaf_gaps(
            prog["change"], ref["change"], kept(ref)))),
        median_grad_gap=float(np.median(leaf_gaps(prog["grad"],
                                                  ref["grad"]))))


def judge(values: Dict[str, float], limits: Dict[str, float]) -> dict:
    """Each number that has a limit, beside it; a number that is not
    finite fails."""
    return {k: {"value": values[k], "limit": lim,
                "ok": bool(math.isfinite(values[k]) and values[k] <= lim)}
            for k, lim in limits.items()}
