"""What the per-layer metric readers share. A reader returns None when its
cell has nothing for it to read; it never returns 0 for a share."""
from __future__ import annotations

import math

from . import trace as tr


def traced(ctx: dict, driver: str):
    """The flattened trace of the window, when this is a traced ``driver`` cell."""
    t = ctx.get("trace_data")
    if t is None or ctx["workload"]["driver"] != driver or not t["devices"]:
        return None
    return t


def share(num: float, den: float):
    if not den or not math.isfinite(num) or not math.isfinite(den) or num <= 0:
        return None
    return 100.0 * num / den


def idle_share(ctx: dict, driver: str):
    t = traced(ctx, driver)
    return None if t is None else 100.0 * tr.idle_share(t)
