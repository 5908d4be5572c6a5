"""The profiler trace: capture around a window, flatten it, and reduce it to
device busy time, idle gaps and per-scope or per-kernel sums.

A flattened trace is a dict, the form the committed test traces keep::

    {"window_ns": [t0, t1],
     "devices": {"0": {"names": [op text, ...],        # interned
                       "scopes": [scope path, ...],    # per name, may be ""
                       "ops": [[name_id, start_ns, dur_ns], ...],
                       "modules": [[name, start_ns, dur_ns], ...]}},
     "host": [[name, start_ns, dur_ns, thread], ...]}

Device planes are ``/device:TPU:<n>``. On each, the ``XLA Ops`` line holds
the operations, named by their HLO instruction (``%fusion.379 = ...``), and
the ``XLA Modules`` line the compiled programs. An op's scope path (the
``jax.named_scope`` names it was issued under) is not in the trace: it comes
from the ``op_name`` metadata of the compiled program's HLO, which the
driver hands to :func:`attach_scopes`. Host spans are the harness's
``jax.profiler.TraceAnnotation`` names (``bench.*``).
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
import tempfile
from typing import Callable, Dict, List, Sequence, Tuple

import numpy as np

HOST_PREFIX = "bench."
PALLAS = 'custom_call_target="tpu_custom_call"'


@contextlib.contextmanager
def profiled(jax, enabled: bool):
    """Yields a holder whose ``trace`` is the flattened trace once the block
    has ended (None when ``enabled`` is false)."""

    class Holder:
        trace = None

    h = Holder()
    if not enabled:
        yield h
        return
    d = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        jax.profiler.start_trace(d)
        try:
            yield h
        finally:
            jax.profiler.stop_trace()
        files = glob.glob(os.path.join(d, "plugins", "profile", "*", "*.xplane.pb"))
        if not files:
            raise RuntimeError(f"the profiler wrote no trace under {d}")
        h.trace = extract(jax, files[0])
    finally:
        shutil.rmtree(d, ignore_errors=True)


def extract(jax, path: str) -> dict:
    """Flatten an ``.xplane.pb`` file."""
    pd = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, dict] = {}
    host: List[list] = []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            dev = devices.setdefault(m.group(1), {"names": [], "scopes": [], "ops": [], "modules": []})
            index: Dict[str, int] = {}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    rows = []
                    for ev in line.events:
                        name = ev.name
                        i = index.get(name)
                        if i is None:
                            i = index[name] = len(dev["names"])
                            dev["names"].append(name)
                            dev["scopes"].append("")
                        rows.append((i, ev.start_ns, ev.duration_ns))
                    dev["ops"] = np.asarray(rows, np.float64).reshape(-1, 3)
                elif line.name == "XLA Modules":
                    dev["modules"] += [[ev.name, ev.start_ns, ev.duration_ns] for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append([ev.name, ev.start_ns, ev.duration_ns, line.name])
    marks = [h for h in host if h[0] == "bench.window"]
    if marks:
        t0, t1 = min(h[1] for h in marks), max(h[1] + h[2] for h in marks)
    else:
        ops = [ops_array(d) for d in devices.values() if len(d["ops"])]
        t0 = min(float(o[:, 1].min()) for o in ops) if ops else 0.0
        t1 = max(float((o[:, 1] + o[:, 2]).max()) for o in ops) if ops else 0.0
    return {"window_ns": [t0, t1], "devices": devices, "host": host}


def ops_array(dev: dict) -> np.ndarray:
    return np.asarray(dev["ops"], np.float64).reshape(-1, 3)


def instruction(name: str) -> str:
    """``%fusion.379 = u32[1] ...`` -> ``fusion.379``."""
    return name.split(" = ", 1)[0].lstrip("%").strip()


def hlo_scopes(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` metadata, from a compiled program's HLO."""
    out = {}
    for m in re.finditer(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?metadata=\{[^}]*?op_name=\"([^\"]*)\"", hlo_text, re.M):
        out.setdefault(m.group(1), m.group(2))
    return out


def attach_scopes(trace: dict, scopes: Dict[str, str], module_needle: str) -> None:
    """Give each op that runs inside a program whose name holds
    ``module_needle`` the scope path its instruction has in that program."""
    for dev in trace["devices"].values():
        inside = in_modules(dev, module_needle)
        ops = ops_array(dev)
        ids = {int(i) for i, ok in zip(ops[:, 0], inside(ops)) if ok}
        for i in ids:
            dev["scopes"][i] = scopes.get(instruction(dev["names"][i]), "")


# -- reductions ----------------------------------------------------------------

Interval = Tuple[float, float]


def union_arrays(starts: np.ndarray, ends: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Merge intervals; returns the disjoint, sorted (starts, ends)."""
    if len(starts) == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    run_end = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > run_end[:-1]
    idx = np.flatnonzero(new)
    return s[idx], np.append(run_end[idx[1:] - 1], run_end[-1])


def union(intervals) -> List[Interval]:
    iv = np.asarray(list(intervals), np.float64).reshape(-1, 2)
    s, e = union_arrays(iv[:, 0], iv[:, 1])
    return list(zip(s.tolist(), e.tolist()))


def clip_arrays(starts, ends, t0, t1):
    s, e = np.maximum(starts, t0), np.minimum(ends, t1)
    keep = e > s
    return s[keep], e[keep]


def clip(intervals, t0: float, t1: float) -> List[Interval]:
    iv = np.asarray(list(intervals), np.float64).reshape(-1, 2)
    s, e = clip_arrays(iv[:, 0], iv[:, 1], t0, t1)
    return list(zip(s.tolist(), e.tolist()))


def busy_ns(intervals) -> float:
    return sum(e - s for s, e in union(intervals))


def gaps(intervals, t0: float, t1: float) -> List[Interval]:
    """The idle stretches of [t0, t1] that no interval covers."""
    out, cur = [], t0
    for s, e in union(clip(intervals, t0, t1)):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if t1 > cur:
        out.append((cur, t1))
    return out


def _select(dev: dict, pred: Callable[[int], bool]) -> np.ndarray:
    ops = ops_array(dev)
    table = np.array([bool(pred(i)) for i in range(len(dev["names"]))] + [False])
    ids = ops[:, 0].astype(np.int64)
    return ops[table[ids]]


def ops_time(dev: dict, pred: Callable[[int], bool], t0: float, t1: float) -> float:
    """Device seconds of the ops whose name id satisfies ``pred``, in [t0, t1]."""
    ops = _select(dev, pred)
    s, e = clip_arrays(ops[:, 1], ops[:, 1] + ops[:, 2], t0, t1)
    s, e = union_arrays(s, e)
    return float(np.sum(e - s)) / 1e9


def all_ops(dev: dict) -> Callable[[int], bool]:
    return lambda i: True


def in_scope(dev: dict, scope: str) -> Callable[[int], bool]:
    """Ops issued under ``jax.named_scope(scope)``: the scope is a component
    of the op's scope path."""
    pat = re.compile(r"(^|/)" + re.escape(scope) + r"($|/)")
    return lambda i: bool(pat.search(dev["scopes"][i]))


def is_pallas(dev: dict) -> Callable[[int], bool]:
    """A Pallas kernel: a ``tpu_custom_call``."""
    return lambda i: PALLAS in dev["names"][i]


def in_modules(dev: dict, needle: str):
    """A vectorized test: which of an (N, 3) op array's rows start inside a
    program whose name holds ``needle``."""
    spans = np.array(sorted((m[1], m[1] + m[2]) for m in dev["modules"] if needle in m[0]), np.float64).reshape(-1, 2)

    def test(ops: np.ndarray) -> np.ndarray:
        if not len(spans):
            return np.zeros(len(ops), bool)
        i = np.searchsorted(spans[:, 0], ops[:, 1], side="right") - 1
        ok = i >= 0
        ok[ok] = ops[ok, 1] < spans[i[ok], 1]
        return ok

    return test


def window(trace: dict) -> Tuple[float, float]:
    return trace["window_ns"][0], trace["window_ns"][1]


def window_s(trace: dict) -> float:
    t0, t1 = window(trace)
    return (t1 - t0) / 1e9


def devices(trace: dict) -> Sequence[dict]:
    return [trace["devices"][k] for k in sorted(trace["devices"], key=int)]


def busy_s(trace: dict) -> float:
    """Seconds in which an operation ran, averaged over the chips."""
    t0, t1 = window(trace)
    devs = devices(trace)
    if not devs:
        return 0.0
    return sum(ops_time(d, all_ops(d), t0, t1) for d in devs) / len(devs)


def idle_share(trace: dict) -> float:
    w = window_s(trace)
    return 1.0 - busy_s(trace) / w if w > 0 else float("nan")


def device_gaps(dev: dict, t0: float, t1: float) -> List[Interval]:
    ops = ops_array(dev)
    s, e = union_arrays(*clip_arrays(ops[:, 1], ops[:, 1] + ops[:, 2], t0, t1))
    starts = np.concatenate([[t0], e])
    ends = np.concatenate([s, [t1]])
    keep = ends > starts
    return list(zip(starts[keep].tolist(), ends[keep].tolist()))


def host_label(trace: dict, t0: float, t1: float) -> str:
    """The innermost harness span that covers most of [t0, t1]."""
    best, best_cover, best_len = "no host span", 0.0, float("inf")
    for name, s, d, _ in trace["host"]:
        if name == "bench.window":
            continue
        cover = min(t1, s + d) - max(t0, s)
        if cover <= 0:
            continue
        if cover > best_cover * 1.0001 or (cover >= best_cover * 0.9999 and d < best_len):
            best, best_cover, best_len = name, cover, d
    return best


TRANSFORMS = ("jvp", "transpose", "vmap", "remat", "checkpoint")
STRUCTURAL = re.compile(r"^(|while|body|cond|closed_call|checkpoint|remat|scan|branch_\d+_fun|p?jit\(.*\))$")


def _unwrap(component: str) -> Tuple[List[str], str]:
    """``transpose(jvp(ofl.bank.g0))`` -> (["transpose", "jvp"], "ofl.bank.g0")."""
    wraps = []
    while True:
        m = re.match(r"^(\w+)\((.*)\)$", component)
        if not m or m.group(1) not in TRANSFORMS:
            return wraps, component
        wraps.append(m.group(1))
        component = m.group(2)


def op_label(dev: dict, i: int) -> str:
    """Group key for the breakdown, most telling first: the innermost named
    scope of the op's scope path, the transforms it runs under (outermost
    first), the op's kind, then the outer named scopes, e.g.
    ``ofl.bank.g0 transpose(jvp) convolution / ofl.gen.boost``. The kind is
    the instruction name without its number, or for a bare ``fusion`` the
    primitive it was built from. Control flow and ``jit(...)`` are not named
    scopes; an op under none leads with its program, and one with no scope
    path is its kind alone."""
    kind = re.sub(r"\.\d+$", "", instruction(dev["names"][i]))
    scope = dev["scopes"][i].split(";")[0]
    if not scope:
        return kind[:120]
    path = scope.split("/")
    if kind == "fusion":
        kind = path[-1]  # a bare fusion is named by the primitive it was built from
    parts = [_unwrap(c) for c in path[:-1]]
    named = [k for k, (_, name) in enumerate(parts) if not STRUCTURAL.match(name)]
    if not named:
        named = [k for k, (_, name) in enumerate(parts) if name.startswith(("jit(", "pjit("))][:1]
    if not named:
        return kind[:120]
    k = named[-1]
    wraps = [w for ws, _ in parts for w in ws]
    label = [parts[k][1]]
    if wraps:
        label.append("(".join(wraps) + ")" * (len(wraps) - 1))
    label.append(kind)
    outer = "/".join(parts[j][1] for j in named[:-1])
    return (" ".join(label) + (f" / {outer}" if outer else ""))[:120]


CONTAINERS = re.compile(r"\s(while|conditional|call)\(")


def is_container(dev: dict) -> Callable[[int], bool]:
    """Control flow (``while``, ``conditional``, ``call``): its event spans
    the ops of its body, which the trace lists too."""
    return lambda i: bool(CONTAINERS.search(dev["names"][i]))


def breakdown(trace: dict, top: int = 10) -> dict:
    """The device operations that took most time (control-flow containers
    left out: their bodies' ops are counted) and the longest idle gaps, by
    what the host was doing, on the first chip."""
    t0, t1 = window(trace)
    devs = devices(trace)
    if not devs:
        return {"device_ops": [], "idle_gaps": []}
    dev = devs[0]
    container = is_container(dev)
    ops = _select(dev, lambda i: not container(i))
    s, e = clip_arrays(ops[:, 1], ops[:, 1] + ops[:, 2], t0, t1)
    keep = (np.minimum(ops[:, 1] + ops[:, 2], t1) > np.maximum(ops[:, 1], t0))
    per: Dict[str, float] = {}
    labels = {}
    for i, d in zip(ops[keep, 0].astype(int).tolist(), (e - s).tolist()):
        lab = labels.get(i)
        if lab is None:
            lab = labels[i] = op_label(dev, i)
        per[lab] = per.get(lab, 0.0) + d / 1e9
    top_ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(device_gaps(dev, t0, t1), key=lambda g: g[0] - g[1])[:top]
    return {
        "device_ops": [[name, secs] for name, secs in top_ops],
        "idle_gaps": [[host_label(trace, a, b), (b - a) / 1e9] for a, b in idle],
    }
