"""The comparison that decides ``correct`` for a training cell.

Both sides hand in the same three readings of the first dispatch (see
``reference/common.follow``): each step's loss, and per leaf the norm of the
optimizer's velocity and of the parameters' change after the last step. The
numbers compared are gaps between the program's reading and the reference's:

* ``loss_gap``     worst step: |L_program - L_reference| / |L_reference|
* ``velocity_gap`` worst leaf: | ||v_p|| - ||v_r|| | / max(||v_r||, median leaf's)
* ``change_gap``   the same for the parameters' change; a leaf whose
  reference velocity is under a thousandth of the median leaf's is left out
  (its gradient is nought to rounding, so it moves by round-off alone)

Each has a limit of its own in the cell's file; ``correct`` is all within.
A missing or non-finite reading is a failure, not a pass.
"""
from __future__ import annotations

import math
import statistics

DEAD_LEAF_SHARE = 1e-3


def _leaf_gaps(prog: dict, ref: dict, keep=None) -> dict:
    floor = statistics.median(ref.values())
    out = {}
    for k, r in ref.items():
        if keep is not None and k not in keep:
            continue
        gap = abs(prog.get(k, float("nan")) - r) / max(r, floor, 1e-30)
        out[k] = gap if math.isfinite(gap) else float("inf")
    return out


def _worst(per: dict):
    at = max(per, key=per.get)
    return per[at], at


def _median(per: dict):
    return statistics.median(per.values()), f"median of {len(per)} leaves"


def gaps(prog: dict, ref: dict) -> dict:
    """``{name: (value, where)}`` for the three numbers compared."""
    out = {}
    if len(prog["losses"]) != len(ref["losses"]):
        out["loss_gap"] = (float("inf"), "steps")
    else:
        per = [abs(p - r) / max(abs(r), 1e-30) if math.isfinite(p)
               else float("inf") for p, r in zip(prog["losses"], ref["losses"])]
        out["loss_gap"] = (max(per), f"step{per.index(max(per)) + 1}")
    rv = ref["velocity_norm"]
    floor = statistics.median(rv.values())
    alive = {k for k, v in rv.items() if v >= DEAD_LEAF_SHARE * floor}
    vel = _leaf_gaps(prog["velocity_norm"], rv)
    chg = _leaf_gaps(prog["change_norm"], ref["change_norm"], keep=alive)
    out["velocity_gap"], out["change_gap"] = _worst(vel), _worst(chg)
    out["velocity_gap_median"] = _median(vel)
    out["change_gap_median"] = _median(chg)
    return out


def decide(prog: dict, ref: dict, limits: dict):
    """-> (correct, {name: {"value", "limit", "at"}}) — every limit named in
    the cell's file is held; a gap with no limit there is reported only."""
    report, ok = {}, True
    for name, (value, at) in gaps(prog, ref).items():
        limit = limits.get(name)
        report[name] = {"value": value, "limit": limit, "at": at}
        if limit is not None and not value <= limit:
            ok = False
    return ok and bool(limits), report
