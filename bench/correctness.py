"""The comparison that decides ``correct``: what the timed path produced in
its first rounds against the plain reference over the same weights and
batches.

Each number is held to the cell's limit (``limits/<workload>.json``). The
first three are read for every cell; a cell's limits file may name more,
and only those it names are judged.

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
``site_total_change_gap``
    each site's ``|norm - ref norm| / ref norm`` for its whole change after
    the first rounds (the moving leaves together), the worst site. A number
    over all sites averages out rounding that is independent from site to
    site, and a worst-leaf number is set by the program's own bfloat16
    activations on small leaves; this one is neither.
"""

from __future__ import annotations

import math
from typing import Dict, List, Tuple

import numpy as np

__all__ = ["NUMBERS", "ALWAYS", "over_sites", "readings", "judge"]

NUMBERS = ("loss_gap", "grad_gap", "change_gap", "site_total_change_gap")
#: the numbers every cell's limits file names
ALWAYS = NUMBERS[:3]
#: a leaf whose reference gradient norm is under this share of the median
#: leaf's moves by round-off alone
STILL_LEAF = 1e-3


def over_sites(sites: List[Dict[str, float]]) -> Dict[str, float]:
    """Per-leaf norms over all sites from each site's own."""
    return {k: math.sqrt(sum(s[k] ** 2 for s in sites)) for k in sites[0]}


def _worst_leaf(got: Dict[str, float], ref: Dict[str, float], leaves) -> float:
    floor = float(np.median([ref[k] for k in leaves]))
    return max(abs(got[k] - ref[k]) / max(ref[k], floor) for k in leaves)


def _total(norms: Dict[str, float], leaves) -> float:
    return math.sqrt(sum(norms[k] ** 2 for k in leaves))


def readings(prog: dict, ref: dict) -> Dict[str, float]:
    """``prog`` and ``ref`` each hold ``losses`` (per round) and
    ``grad_sites`` and ``change_sites`` (each site's per-leaf norms keyed by
    tree path)."""
    grad, rgrad = over_sites(prog["grad_sites"]), over_sites(ref["grad_sites"])
    if set(grad) != set(rgrad):
        raise ValueError("program and reference disagree on the leaves")
    gmed = float(np.median(list(rgrad.values())))
    moving = [k for k, v in rgrad.items() if v >= STILL_LEAF * gmed]
    return {
        "loss_gap": max(abs(a - b) / abs(b)
                        for a, b in zip(prog["losses"], ref["losses"])),
        "grad_gap": _worst_leaf(grad, rgrad, list(rgrad)),
        "change_gap": _worst_leaf(over_sites(prog["change_sites"]),
                                  over_sites(ref["change_sites"]), moving),
        "site_total_change_gap": max(
            abs(_total(c, moving) - _total(r, moving)) / _total(r, moving)
            for c, r in zip(prog["change_sites"], ref["change_sites"])),
    }


def judge(numbers: Dict[str, float], limits: dict) -> Tuple[bool, dict]:
    """Each number the limits name against its limit; a number that is not
    finite fails."""
    missing = [k for k in ALWAYS if k not in limits]
    if missing or not set(limits) <= set(NUMBERS):
        raise ValueError(f"limits must name {ALWAYS} and only {NUMBERS}: "
                         f"{sorted(limits)}")
    checks, ok = {}, True
    for k in NUMBERS:
        if k not in limits:
            continue
        v, lim = numbers[k], float(limits[k]["limit"])
        checks[k] = {"value": v, "limit": lim}
        ok = ok and math.isfinite(v) and v <= lim
    return ok, checks
