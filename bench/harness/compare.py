"""The comparison that decides `correct`.

Every number compared is a relative error max|got - want| / max|want| of
one output against the plain reference, and passes when it is at most its
limit (`limits` in the configuration file).  A reference with no energy,
a shape mismatch or a NaN all read as infinitely wrong.
"""
from __future__ import annotations

import math

import numpy as np


def rel_err(got, want, dtype=np.float64) -> float:
    got = np.asarray(got, dtype)
    want = np.asarray(want, dtype)
    if got.shape != want.shape:
        return math.inf
    scale = float(np.max(np.abs(want)))
    if not scale > 0.0:
        return math.inf
    err = float(np.max(np.abs(got - want))) / scale
    return math.inf if math.isnan(err) else err


def passes(value: float, limit: float) -> bool:
    return value <= limit


def propagate_checks(unit_traces, ref_traces, got_state, want_state, limits):
    """Every unit's traces against the reference's, and the last unit's
    final wavefields, field by field (each against its own max).
    Returns ([(name, worst value, limit)], failed units)."""
    t_errs = [rel_err(tr, ref_traces) for tr in unit_traces]
    w_err = max(rel_err(g, w, np.float32)
                for g, w in zip(got_state, want_state))
    lt, lw = limits["traces_rel_err"], limits["wavefield_rel_err"]
    failed = sum(not passes(e, lt) for e in t_errs[:-1])
    failed += not (passes(t_errs[-1], lt) and passes(w_err, lw))
    return ([("traces_rel_err", max(t_errs), lt),
             ("wavefield_rel_err", w_err, lw)], failed)


def survey_checks(got: dict, want: dict, limits):
    """Each shot's traces, every time the window produced them, against
    that shot's reference.  Returns ([(name, worst value, limit)], failed
    shots)."""
    lt = limits["traces_rel_err"]
    errs = [rel_err(tr, want[i]) for i, trs in got.items() for tr in trs]
    failed = sum(not passes(e, lt) for e in errs)
    return [("traces_rel_err", max(errs), lt)], failed
