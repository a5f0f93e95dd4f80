"""The numbers the check compares, each against its limit.

Training, each by the worst leaf, relative to the reference's norm of
that leaf or of the median leaf, whichever is larger:

  grad1_gap    the gap between the norms of the program's first gradient
               and the reference's;
  dtheta_gap   the same for the change of the params over the first
               calls, leaving out leaves whose reference gradient is
               under a thousandth of the median leaf's (such a leaf moves
               by round-off alone);
  grad1_diff   the norm of the difference of the two first gradients.

And relative to the reference's norm of that one leaf:

  head_grad1_diff
               the norm of the difference of the two first gradients of
               the output head (the leaf the reference names ``head``).
               Norms alone cannot tell a drop in precision from the
               program's own rounding; a difference can. The head's
               gradient, taken at the seeded weights before any update,
               depends on the forward pass alone; deeper leaves' also on
               the backward through the recurrences, which rounding alone
               turns by most of its norm at long sequences.

``loss_gap`` and ``grad1_diff`` are reported; a cell compares those its
limits name.
"""
from __future__ import annotations

import statistics

import numpy as np

GRAD_FLOOR = 1e-3


def _worst(values: dict, ref_norms: dict) -> tuple:
    """(worst of ``values[k]`` over max(ref_norms[k], median), its leaf)."""
    med = statistics.median(ref_norms[k] for k in values)
    worst, leaf = 0.0, None
    for k, v in values.items():
        rel = v / max(ref_norms[k], med, 1e-30)
        if rel > worst:
            worst, leaf = rel, k
    return worst, leaf


def norm_gap(prog: dict, ref: dict, keep=None) -> tuple:
    """(worst relative gap of the norms, its leaf)."""
    gaps = {k: abs(prog[k] - ref[k]) for k in ref if keep is None or k in keep}
    return _worst(gaps, ref)


def diff_norms(prog_leaves: dict, ref_leaves: dict) -> dict:
    """{leaf: norm of the difference}, leaf by leaf on the host."""
    return {k: float(np.linalg.norm((prog_leaves[k] - r).ravel()))
            for k, r in ref_leaves.items()}


def diff_rels(prog: dict, ref: dict) -> dict:
    """{leaf: norm of the first gradients' difference over the larger of
    the reference's norm of that leaf and of the median leaf}."""
    med = statistics.median(ref["grad1"].values())
    diffs = diff_norms(prog["grad1_leaves"], ref["grad1_leaves"])
    return {k: v / max(ref["grad1"][k], med, 1e-30) for k, v in diffs.items()}


def train_numbers(prog: dict, ref: dict) -> dict:
    """{name: (value, detail)} for the readings of the first calls."""
    gmed = statistics.median(ref["grad1"].values())
    moving = {k for k, v in ref["grad1"].items() if v >= GRAD_FLOOR * gmed}
    loss = max(abs(a - b) for a, b in zip(prog["loss"], ref["loss"]))
    g, gleaf = norm_gap(prog["grad1"], ref["grad1"])
    d, dleaf = norm_gap(prog["dtheta"], ref["dtheta"], keep=moving)
    diffs = diff_norms(prog["grad1_leaves"], ref["grad1_leaves"])
    diff, fleaf = _worst(diffs, ref["grad1"])
    head = ref["head"]
    return {"loss_gap": (loss, f"losses {prog['loss']} vs {ref['loss']}"),
            "grad1_gap": (g, gleaf), "dtheta_gap": (d, dleaf),
            "grad1_diff": (diff, fleaf),
            "head_grad1_diff": (diffs[head] / max(ref["grad1"][head], 1e-30),
                                head)}


def train_checks(prog: dict, ref: dict, limits: dict) -> list:
    """[(name, value, limit)] for every number that has a limit."""
    nums = train_numbers(prog, ref)
    return [(k, nums[k][0], limits[k]) for k in limits]


def loggable(readings: dict) -> dict:
    """``readings`` without the whole leaves, for a log line."""
    if "grad1_leaves" in readings:
        return {k: v for k, v in readings.items() if k != "grad1_leaves"}
    return {k: loggable(v) if isinstance(v, dict) else v
            for k, v in readings.items()}
