"""Three-term roofline model from a compiled dry-run artifact.

    compute    = HLO_FLOPs / (chips × peak_FLOP/s)
    memory     = HLO_bytes / (chips × HBM_bw)
    collective = collective_bytes / (chips × link_bw)

``compiled.cost_analysis()`` reports *per-device* flops/bytes of the SPMD
module, so ``flops_per_device = HLO_FLOPs / chips`` already — the terms
below divide per-device quantities by per-chip rates (algebraically the
same as the global formulas). Collective bytes are NOT in cost_analysis:
we parse the post-partitioning HLO text and sum the output-shape bytes of
every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute instruction (per-device sizes; a documented proxy for
link traffic — e.g. a ring all-gather moves (n−1)/n of the output per
link, which we absorb into the single-link-bandwidth constant).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, Optional, Tuple

import numpy as np

# TPU v5e per-chip constants (assignment sheet)
@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    name: str = "tpu_v5e"
    peak_flops: float = 197e12  # bf16 FLOP/s
    hbm_bw: float = 819e9  # bytes/s
    link_bw: float = 50e9  # bytes/s per ICI link
    hbm_bytes: float = 16 * 1024**3


V5E = HardwareSpec()

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
    "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

# one HLO instruction:  %x = f32[8,128]{1,0} all-gather(...)   or tuple types
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(type_str: str) -> int:
    """Sum bytes over every tensor in an HLO type string (handles tuples)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-collective-kind output bytes (per-device) summed over the module.
    ``-start`` variants are counted; ``-done`` twins are skipped to avoid
    double counting."""
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"%?[\w.\-]+\s*=\s*(.+?)\s+([\w\-]+)\(", line)
        if not m:
            continue
        type_str, op = m.groups()
        base = op
        if base.endswith("-start"):
            base = base[: -len("-start")]
        elif base.endswith("-done"):
            continue
        if base in _COLLECTIVES:
            out[base] += _shape_bytes(type_str)
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6·N_active·D (train) or 2·N_active·D (fwd-only), the
    "useful" compute yardstick. D = tokens processed this step."""
    n_active = cfg.param_count(active_only=True)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    tokens = shape.global_batch  # one token per sequence
    return 2.0 * n_active * tokens


def roofline_report(
    compiled,
    num_chips: int,
    cfg=None,
    shape=None,
    hw: HardwareSpec = V5E,
    hlo_text: Optional[str] = None,
) -> Dict[str, Any]:
    """Derive the three roofline terms (+ memory fit + useful-FLOPs ratio)
    from a compiled dry-run artifact.

    XLA's ``cost_analysis`` counts while-loop bodies ONCE (verified: an
    8-step scan of a 256³ matmul reports one iteration), so flops/bytes/
    collectives come from :class:`repro.roofline.hlo_cost.HloCost`, which
    walks the post-SPMD HLO text and scales every loop body by its static
    trip count. The raw cost_analysis numbers are retained for reference.
    """
    from repro.roofline.hlo_cost import HloCost

    cost = compiled.cost_analysis()
    text = hlo_text if hlo_text is not None else compiled.as_text()
    walk = HloCost(text).totals()
    flops_dev = float(walk["flops"])
    bytes_dev = float(walk["bytes"])
    coll = {k: int(walk[k]) for k in _COLLECTIVES}
    coll["total"] = int(walk["coll_total"])

    compute_s = flops_dev / hw.peak_flops
    memory_s = bytes_dev / hw.hbm_bw
    collective_s = coll["total"] / hw.link_bw
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    dominant = max(terms, key=terms.get)

    mem = compiled.memory_analysis()
    mem_info = {}
    for attr in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "generated_code_size_in_bytes",
    ):
        mem_info[attr] = int(getattr(mem, attr, 0) or 0)
    peak_bytes = (
        mem_info["argument_size_in_bytes"] + mem_info["temp_size_in_bytes"]
    )

    report: Dict[str, Any] = {
        "chips": num_chips,
        "hlo_flops_per_device": flops_dev,
        "hlo_flops_global": flops_dev * num_chips,
        "hlo_bytes_per_device": bytes_dev,
        "raw_cost_analysis_flops": float(cost.get("flops", 0.0)),  # while=1 caveat
        "collective_bytes_per_device": coll["total"],
        "collectives": {k: v for k, v in coll.items() if k != "total"},
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
        "dominant": dominant,
        "bound_s": terms[dominant],
        "memory_analysis": mem_info,
        "peak_bytes_per_device": peak_bytes,
        "fits_hbm": peak_bytes <= hw.hbm_bytes,
    }
    if cfg is not None and shape is not None:
        mf = model_flops(cfg, shape)
        report["model_flops"] = mf
        global_flops = flops_dev * num_chips
        report["useful_flops_ratio"] = mf / global_flops if global_flops else 0.0
        # step-time bound and MFU if perfectly overlapped
        report["mfu_bound"] = (
            mf / (num_chips * hw.peak_flops) / terms[dominant] if terms[dominant] else 0.0
        )
    return report
