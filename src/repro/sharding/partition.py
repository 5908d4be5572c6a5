"""Divisibility-aware parameter/activation partitioning.

The framework uses *logical* axis names in rules and resolves them against
whatever mesh is in context:

=============  =====================================================
logical axis   mesh axes it maps to
=============  =====================================================
``batch``      ``("pod", "data")`` — data parallel (pod folds in)
``fsdp``       ``("pod", "data")`` — fully-sharded parameter dim
``tp``         ``("model",)``     — tensor-parallel dim
``experts``    ``("model",)``     — expert-parallel dim (MoE)
``seq``        ``("model",)``     — sequence-sharded KV cache (decode)
=============  =====================================================

Resolution checks divisibility of the array dim against the mesh-axis-size
product; when it does not divide, it retries progressively smaller axis
subsets and finally falls back to replication. This single mechanism is what
lets one rule set serve smollm's 9 heads and qwen3's 64 heads, mixtral's 8
experts and qwen3-moe's 128, granite's 49155 vocab and qwen's 151936.

Rules are matched on parameter *path suffixes*. Parameters may carry extra
leading dims (a scan-over-layers ``L`` dim, a stacked-clients ``K`` dim for
the Co-Boosting ensemble); those are padded with ``None`` automatically.
"""
from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.utils.trees import tree_map_with_path

LogicalSpec = Tuple[Optional[str], ...]

_LOGICAL_TO_MESH: Dict[str, Tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "fsdp": ("pod", "data"),
    # stacked client axis of a grouped ClientBank (core/client_bank.py):
    # clients within a homogeneous group data-parallelize across the mesh
    "clients": ("pod", "data"),
    "tp": ("model",),
    "experts": ("model",),
    "seq": ("model",),
    "heads": ("model",),
    "vocab": ("model",),
}

# (path regex, [candidate logical specs in preference order])
LOGICAL_RULES: List[Tuple[str, List[LogicalSpec]]] = [
    (r"embed/table$", [("vocab", "fsdp"), (None, "fsdp")]),
    (r"lm_head/kernel$", [("fsdp", "vocab"), ("fsdp", None)]),
    (r"pred_head/kernel$", [("fsdp", "vocab"), ("fsdp", None)]),
    # attention
    (r"attn/w[qkv]$", [("fsdp", "heads", None), ("fsdp", None, None)]),
    (r"attn/wo$", [("heads", None, "fsdp"), (None, None, "fsdp")]),
    (r"attn/[qk]_norm$", [(None,)]),
    # dense MLP
    (r"mlp/w[ig]$", [("fsdp", "tp")]),
    (r"mlp/wo$", [("tp", "fsdp")]),
    # MoE
    (r"moe/router$", [("fsdp", None)]),
    (r"moe/w[ig]$", [("experts", "fsdp", None), (None, "fsdp", "tp")]),
    (r"moe/wo$", [("experts", None, "fsdp"), (None, "tp", "fsdp")]),
    # mamba
    (r"mamba/in_proj$", [("fsdp", "tp")]),
    (r"mamba/conv$", [(None, "tp")]),
    (r"mamba/x_proj$", [("tp", None)]),
    (r"mamba/dt_proj$", [(None, "tp")]),
    (r"mamba/A_log$", [("tp", None)]),
    (r"mamba/D$", [("tp",)]),
    (r"mamba/out_proj$", [("tp", "fsdp")]),
    # xlstm
    (r"xlstm/in_proj$", [("fsdp", "tp")]),
    (r"xlstm/w[qkv]$", [("fsdp", "heads", None), ("fsdp", None, None)]),
    (r"xlstm/gates$", [("fsdp", None)]),
    (r"xlstm/out_proj$", [("tp", "fsdp")]),
    (r"xlstm/r[zifo]$", [("heads", None, None), (None, None, None)]),
    # vision / audio frontend projector stubs
    (r"projector/kernel$", [("fsdp", "tp")]),
    # norms, biases, scalars
    (r"(scale|bias|b)$", [(None,)]),
]


def _mesh_axes() -> Dict[str, int]:
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return {}
    return dict(mesh.shape)


def resolve_rule(
    logical: LogicalSpec,
    shape: Sequence[int],
    mesh_axes: Dict[str, int],
) -> P:
    """Resolve one logical spec against a concrete shape + mesh.

    For each dim, keep the largest prefix-product of candidate mesh axes that
    divides the dim size; axes already used by an earlier dim are skipped
    (a mesh axis may appear at most once in a PartitionSpec).
    """
    used: set = set()
    out: List[Any] = []
    ndims = len(shape)
    # pad leading Nones for stacked/scanned extra dims
    spec = (None,) * (ndims - len(logical)) + tuple(logical)
    for dim, name in zip(shape, spec):
        if name is None:
            out.append(None)
            continue
        cands = [a for a in _LOGICAL_TO_MESH[name] if a in mesh_axes and a not in used]
        chosen: List[str] = []
        prod = 1
        for a in cands:
            if dim % (prod * mesh_axes[a]) == 0:
                chosen.append(a)
                prod *= mesh_axes[a]
        if not chosen:
            out.append(None)
        else:
            used.update(chosen)
            out.append(tuple(chosen) if len(chosen) > 1 else chosen[0])
    return P(*out)


def logical_to_pspec(logical: LogicalSpec, shape: Sequence[int]) -> P:
    return resolve_rule(logical, shape, _mesh_axes())


def _match(path: str) -> Optional[List[LogicalSpec]]:
    for pattern, candidates in LOGICAL_RULES:
        if re.search(pattern, path):
            return candidates
    return None


def _score(spec: P) -> int:
    n = 0
    for s in spec:
        if s is None:
            continue
        n += len(s) if isinstance(s, tuple) else 1
    return n


def infer_param_specs(params: Any, mesh_axes: Optional[Dict[str, int]] = None) -> Any:
    """Build a PartitionSpec tree for a param tree (of arrays or
    ShapeDtypeStructs). Resolves against the mesh currently in context
    unless ``mesh_axes`` is given explicitly — a fleet shards each replica
    against ITS mesh slice without entering N global mesh contexts."""
    mesh_axes = _mesh_axes() if mesh_axes is None else dict(mesh_axes)

    def infer(path: str, leaf) -> P:
        if not mesh_axes:
            return P()
        candidates = _match(path)
        if candidates is None:
            return P(*([None] * len(leaf.shape)))
        best = None
        for logical in candidates:
            spec = resolve_rule(logical, leaf.shape, mesh_axes)
            if best is None or _score(spec) > _score(best):
                best = spec
        return best

    return tree_map_with_path(infer, params)


def batch_pspec(batch_size: int, extra_dims: int = 1) -> P:
    """PartitionSpec for a batched activation: shard dim0 over data axes if
    divisible, remaining dims replicated."""
    mesh_axes = _mesh_axes()
    if not mesh_axes:
        return P()
    spec = resolve_rule(("batch",), (batch_size,), mesh_axes)
    return P(spec[0], *([None] * extra_dims))


def activation_pspec(shape: Sequence[int], logical: LogicalSpec) -> P:
    return resolve_rule(logical, shape, _mesh_axes())


_STATE_RULES: List[Tuple[str, LogicalSpec]] = [
    # attention KV cache (G, B, S, K, hd): batch over data, seq over model
    (r"/(k|v)$", (None, "batch", "seq", None, None)),
    # mamba conv tail (G, B, K-1, inner) and state h (G, B, inner, N)
    (r"/conv$", (None, "batch", None, "tp")),
    (r"/h$", (None, "batch", "tp", None)),
    # mLSTM / sLSTM per-head states
    (r"/C$", (None, "batch", "heads", None, None)),
    (r"/(n|c)$", (None, "batch", "heads", None)),
    (r"/m$", (None, "batch", "heads")),
]


def decode_state_specs(state: Any, mesh_axes: Optional[Dict[str, int]] = None) -> Any:
    """PartitionSpec tree for a decode/prefill state pytree (KV caches are
    sequence-sharded over the model axis; SSM states channel-sharded)."""
    mesh_axes = _mesh_axes() if mesh_axes is None else dict(mesh_axes)

    def infer(path: str, leaf) -> P:
        if not mesh_axes:
            return P()
        for pattern, logical in _STATE_RULES:
            if re.search(pattern, path):
                spec = logical[-leaf.ndim :] if len(logical) >= leaf.ndim else logical
                return resolve_rule(spec, leaf.shape, mesh_axes)
        return P(*([None] * leaf.ndim))

    return tree_map_with_path(infer, state)


# Serving-engine state (repro.serve.engine.DecodeState). Unlike the
# training/prefill state above, the batch dim here is SLOTS — requests land
# on arbitrary slots at arbitrary times, so the slot dim stays replicated
# and parallelism comes from the heads/channel dims (tensor-parallel decode:
# every model shard serves every slot, holding only its heads' pages).
_ENGINE_STATE_RULES: List[Tuple[str, LogicalSpec]] = [
    # paged KV pools (G, pool_pages, page, KH, hd): heads over the model
    # axis — each shard holds EVERY page's slice of ITS kv-heads, so page
    # ids (and the host free list) stay global and the handoff scatter is
    # shard-local. Never shard the page dim: ids are data, not layout.
    (r"/(k|v)_pages$", (None, None, None, "heads", None)),
    # dense engine KV (G, slots, cache_len, KH, hd): same heads split
    (r"/(k|v)$", (None, None, None, "heads", None)),
    # recurrent carries, per-slot dense: channel-sharded like training state
    (r"/conv$", (None, None, None, "tp")),
    (r"/h$", (None, None, "tp", None)),
    (r"/C$", (None, None, "heads", None, None)),
    (r"/(n|c)$", (None, None, "heads", None)),
    (r"/m$", (None, None, "heads")),
]


def shard_engine_state(state: Any, mesh_axes: Optional[Dict[str, int]] = None) -> Any:
    """PartitionSpec tree for a serving-engine ``DecodeState``: KV page
    pools / dense caches sharded along the heads axis, recurrent carries
    channel-sharded, and every slot-bookkeeping leaf (positions, budgets,
    output rows, page tables, rng) replicated — the host mutates those by
    slot id and the numbers must read the same from every shard.

    The rules match on path SUFFIXES, so they apply to any pytree that
    nests a cache under an extra prefix — the speculative drafter's dense
    state (wrapped as ``{"draft": ...}`` by ``SpecDecoder.reset``) picks up
    the same ``/k``, ``/v`` heads split as the target's dense engine
    state without a drafter-specific rule."""
    mesh_axes = _mesh_axes() if mesh_axes is None else dict(mesh_axes)

    def infer(path: str, leaf) -> P:
        if not mesh_axes or leaf.ndim == 0:
            return P()
        for pattern, logical in _ENGINE_STATE_RULES:
            if re.search(pattern, path):
                spec = logical[-leaf.ndim :] if len(logical) >= leaf.ndim else logical
                return resolve_rule(spec, leaf.shape, mesh_axes)
        return P(*([None] * leaf.ndim))

    return tree_map_with_path(infer, state)


def constrain(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """Sharding-constrain an activation by logical axis names. No-op when no
    mesh is in context (unit tests / single-device runs)."""
    mesh_axes = _mesh_axes()
    if not mesh_axes:
        return x
    spec = resolve_rule(tuple(logical), x.shape, mesh_axes)
    return jax.lax.with_sharding_constraint(x, spec)
