"""The numbers that decide ``correct``, each beside its limit.

A cell's training run is compared with the reference over its first three
steps (the set-up steps, which run through ``Heta.fit`` like the window):

* ``loss_gap``: the largest |loss(program) - loss(reference)| over the
  three steps.
* ``grad_gap``: the first gradient as the optimizer received it, read back
  from the program's Adam state after one step (m / (1 - b1)), against the
  reference's first gradient; per leaf the gap between the two norms (not
  the norm of the difference) over the larger of the reference leaf's norm
  and the median leaf's; the worst leaf and copy.
* ``change_gap``: the same for each leaf's change after three steps (the
  weights before the fourth step minus the initial weights), over the
  leaves whose reference gradient is at least a thousandth of the median
  leaf's (the others move by round-off alone under Adam).
* ``batch_faults``: how many ways the trained batches break the sampler's
  guarantees (``reference.check_batch``); exact, limit 0.

The limits live in ``bench/limits/<cell>.json`` beside the readings they
were set from.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

B1 = 0.9
MOVING = 1e-3  # a leaf moves when its reference gradient >= this x median


def _norm(a) -> float:
    return float(np.linalg.norm(np.asarray(a, np.float64).ravel()))


def _slice(a, shape):
    return np.asarray(a)[tuple(slice(0, s) for s in shape)]


def norm_gap(prog: Dict[str, List[np.ndarray]], ref: Dict[str, np.ndarray],
             keys) -> tuple:
    """Worst |‖prog‖ - ‖ref‖| / max(‖ref‖, median ‖ref‖) over ``keys``
    and every program copy of each leaf; returns (gap, leaf)."""
    keys = list(keys)
    ref_norms = {k: _norm(ref[k]) for k in keys}
    med = float(np.median(list(ref_norms.values()))) if keys else 0.0
    worst, where = 0.0, None
    for k in keys:
        copies = prog.get(k)
        if not copies:
            return 1.0, f"{k} (missing)"
        den = max(ref_norms[k], med)
        for c in copies:
            gap = abs(_norm(_slice(c, ref[k].shape)) - ref_norms[k]) / den
            if gap > worst:
                worst, where = gap, k
    return worst, where


def readings(prog: dict, ref: dict, init: Dict[str, np.ndarray],
             batch_faults: int) -> Dict[str, dict]:
    """``prog``: {"losses", "m1": {leaf: [copies of Adam m after step 1]},
    "params3": {leaf: [copies after step 3]}}; ``ref``: what
    ``reference.train`` returns; ``init``: the initial weights (and rows,
    as ``table/<type>``, where they train)."""
    loss_gap = float(np.max(np.abs(np.asarray(prog["losses"][:3], np.float64)
                                   - np.asarray(ref["losses"][:3], np.float64))))
    grads = {k: [np.asarray(m) / (1.0 - B1) for m in v]
             for k, v in prog["m1"].items()}
    keys = sorted(ref["grads"])
    grad_gap, grad_leaf = norm_gap(grads, ref["grads"], keys)
    gnorm = {k: _norm(ref["grads"][k]) for k in keys}
    med = float(np.median(list(gnorm.values())))
    moving = [k for k in keys if gnorm[k] >= MOVING * med]
    ref_change = {k: np.asarray(ref["params"][k]) - init[k] for k in moving}
    prog_change = {k: [_slice(c, init[k].shape) - init[k]
                       for c in prog["params3"].get(k, [])] for k in moving}
    change_gap, change_leaf = norm_gap(prog_change, ref_change, moving)
    return {
        "loss_gap": {"value": loss_gap},
        "grad_gap": {"value": grad_gap, "leaf": grad_leaf},
        "change_gap": {"value": change_gap, "leaf": change_leaf,
                       "leaves": len(moving), "of": len(keys)},
        "batch_faults": {"value": int(batch_faults)},
    }


def judge(read: Dict[str, dict], limits: Dict[str, dict]) -> bool:
    """Every number at or under its limit (a NaN fails)."""
    ok = True
    for name, lim in limits.items():
        r = read[name]
        r["limit"] = lim["limit"]
        v = r["value"]
        ok &= bool(np.isfinite(v) and v <= lim["limit"])
    return ok
