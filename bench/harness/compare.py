"""Numbers compared with the reference, and their limits."""
from __future__ import annotations

import math

import numpy as np


def rel_gap(got, ref) -> float:
    """Largest ``|got - ref| / |ref|``; infinite where either is not finite
    or the shapes differ, so that a missing answer can never pass."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if got.shape != ref.shape:
        return math.inf
    if got.size == 0:
        return 0.0
    if not (np.isfinite(got).all() and np.isfinite(ref).all()):
        return math.inf
    scale = np.maximum(np.abs(ref), np.finfo(np.float64).tiny)
    return float(np.max(np.abs(got - ref) / scale))


def judge(numbers: dict, limits: dict) -> dict:
    """Each number beside its limit; a number without a limit, or one that
    is not finite, fails."""
    out = {}
    for name, value in numbers.items():
        limit = limits.get(name, {}).get("limit")
        ok = (limit is not None and value is not None
              and math.isfinite(value) and value <= limit)
        out[name] = {"value": value, "limit": limit, "ok": bool(ok)}
    for name in limits:
        if name not in out:
            out[name] = {"value": None, "limit": limits[name]["limit"],
                         "ok": False}
    return out
