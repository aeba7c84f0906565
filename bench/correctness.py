"""The comparison that decides ``correct``: what the timed path produced in
its first rounds against the plain reference over the same weights and
batches.

Three numbers, each held to the cell's limit (``limits/<workload>.json``):

``loss_gap``
    the worst of the first rounds' ``|loss - ref| / |ref|`` (the
    communication step's mean loss over the sites).
``grad_gap``
    the worst leaf's ``|norm - ref norm|`` over the larger of that leaf's
    reference norm and the median leaf's, for the first gradient as the
    optimizer state holds it after round 1 (norms over all sites).
``change_gap``
    the same for the parameters' change after the first rounds. Leaves whose
    reference gradient is under a thousandth of the median leaf's move by
    round-off alone and are left out.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import numpy as np

__all__ = ["NUMBERS", "readings", "judge"]

NUMBERS = ("loss_gap", "grad_gap", "change_gap")
#: a leaf whose reference gradient norm is under this share of the median
#: leaf's moves by round-off alone
STILL_LEAF = 1e-3


def _worst_leaf(got: Dict[str, float], ref: Dict[str, float], leaves) -> float:
    floor = float(np.median([ref[k] for k in leaves]))
    return max(abs(got[k] - ref[k]) / max(ref[k], floor) for k in leaves)


def readings(prog: dict, ref: dict) -> Dict[str, float]:
    """``prog`` and ``ref`` each hold ``losses`` (per round), ``grad`` and
    ``change`` (per-leaf norms keyed by tree path)."""
    if set(prog["grad"]) != set(ref["grad"]):
        raise ValueError("program and reference disagree on the leaves")
    gmed = float(np.median(list(ref["grad"].values())))
    moving = [k for k, v in ref["grad"].items() if v >= STILL_LEAF * gmed]
    return {
        "loss_gap": max(abs(a - b) / abs(b)
                        for a, b in zip(prog["losses"], ref["losses"])),
        "grad_gap": _worst_leaf(prog["grad"], ref["grad"], list(ref["grad"])),
        "change_gap": _worst_leaf(prog["change"], ref["change"], moving),
    }


def judge(numbers: Dict[str, float], limits: dict) -> Tuple[bool, dict]:
    """Each number against its limit; a number that is not finite fails."""
    checks, ok = {}, True
    for k in NUMBERS:
        v, lim = numbers[k], float(limits[k]["limit"])
        checks[k] = {"value": v, "limit": lim}
        ok = ok and math.isfinite(v) and v <= lim
    return ok, checks
