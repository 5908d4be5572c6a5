"""Device time by compiled program, read from a flattened trace: the
executions of a program (its events on the ``XLA Modules`` line, named
``jit_<function>(<id>)``) and the operations that run inside them."""
from __future__ import annotations

from typing import Callable, List, Optional, Tuple

import numpy as np

from . import trace as tr


def runs(dev: dict, needle: str, t0: float, t1: float) -> List[Tuple[float, float]]:
    """(start, end) of each execution, in the window, of a program whose
    name holds ``needle``, in order."""
    return sorted((m[1], m[1] + m[2]) for m in dev["modules"]
                  if needle in m[0] and m[1] >= t0 and m[1] + m[2] <= t1)


def op_seconds(dev: dict, needle: str, t0: float, t1: float,
               pred: Optional[Callable[[int], bool]] = None) -> float:
    """Device seconds of the operations that start inside an execution of
    the program (and satisfy ``pred``), their intervals' union in the window."""
    ops = tr.ops_array(dev)
    keep = tr.in_modules(dev, needle)(ops)
    if pred is not None:
        keep &= np.array([bool(pred(int(i))) for i in ops[:, 0]], bool).reshape(-1)
    ops = ops[keep]
    s, e = tr.union_arrays(*tr.clip_arrays(ops[:, 1], ops[:, 1] + ops[:, 2], t0, t1))
    return float(np.sum(e - s)) / 1e9


def idle_between(trace: dict, dev: dict, spans: List[Tuple[float, float]], skip_host: str) -> List[float]:
    """Device idle seconds between each execution and the next, leaving out
    the pairs during which the host was in a ``skip_host`` span."""
    waits = [(h[1], h[1] + h[2]) for h in trace["host"] if h[0] == skip_host]
    out = []
    for (_, a), (b, _) in zip(spans, spans[1:]):
        if b <= a or any(s < b and e > a for s, e in waits):
            continue
        out.append(sum(g1 - g0 for g0, g1 in tr.device_gaps(dev, a, b)) / 1e9)
    return out
