"""Comparisons with the plain reference."""
from __future__ import annotations

import math

import numpy as np


def _leaves(tree) -> dict:
    """Flatten a nested dict of arrays to ``{"a/b": array}``."""
    out = {}

    def walk(prefix, t):
        if isinstance(t, dict):
            for k in sorted(t):
                walk(f"{prefix}/{k}" if prefix else str(k), t[k])
        elif isinstance(t, (list, tuple)):
            for i, v in enumerate(t):
                walk(f"{prefix}/{i}" if prefix else str(i), v)
        else:
            out[prefix] = np.asarray(t, np.float64)

    walk("", tree)
    return out


def leaf_norms(tree) -> dict:
    return {k: float(np.linalg.norm(v)) for k, v in _leaves(tree).items()}


def leaf_gaps(prog, ref, grad_ref) -> dict:
    """Each leaf's gap between the program's norm and the reference's, over
    the reference's norm of that leaf or of the median leaf, whichever is
    larger. Leaves whose reference gradient is under a thousandth of the
    median leaf's are left out: they move by round-off alone. A leaf that is
    not finite on the program's side reads inf."""
    pn, rn, gn = leaf_norms(prog), leaf_norms(ref), leaf_norms(grad_ref)
    if pn.keys() != rn.keys():
        return {"structure": math.inf}
    median_g = float(np.median(list(gn.values())))
    median_r = float(np.median(list(rn.values())))
    gaps = {}
    for k in rn:
        if gn.get(k, 0.0) < 1e-3 * median_g:
            continue
        gap = abs(pn[k] - rn[k]) / max(rn[k], median_r, 1e-30)
        gaps[k] = gap if math.isfinite(pn[k]) else math.inf
    return gaps


def worst(gaps: dict) -> float:
    return max(gaps.values()) if gaps else math.inf


def median(gaps: dict) -> float:
    return float(np.median(list(gaps.values()))) if gaps else math.inf


def rel_gap(prog: float, ref: float) -> float:
    if not (math.isfinite(prog) and math.isfinite(ref)):
        return math.inf
    return abs(prog - ref) / max(abs(ref), 1e-30)


def tree_sub(a, b):
    la, lb = _leaves(a), _leaves(b)
    return {k: la[k] - lb[k] for k in la}
