"""ClientBank: the grouped client-ensemble engine (hundreds-of-clients OFL).

``make_logits_all`` evaluates K heterogeneous clients as a python-unrolled
loop — O(K) trace cost and K serialized small forwards, which is exactly
where the Table 6 many-client regimes die. The bank instead groups clients
by (apply fn, param structure): each group's params stack into a single
leading-axis pytree and the whole group runs as ONE ``jax.vmap`` forward, so
trace cost and dispatch structure are O(#groups) = O(#architectures), not
O(K). The stacked rows concatenate in group order and a static gather
restores the original client order, so the output is the same ``(K, B, C)``
stack every consumer (generator adversarial loss, DHS perturbation, EE
weight search, fused-epoch KD) already eats — the bank is a drop-in
``logits_all_fn`` with its grouped params as the ``client_params`` pytree.

Two scale levers on top of the grouping:

* ``scan_chunk`` — a group larger than the chunk evaluates as a
  ``lax.scan`` over vmapped chunks, bounding live activations to
  (chunk, B, C) instead of (group, B, C) (the trace stays O(1) per group
  either way; this is the memory knob for hundreds of clients).
* client-axis mesh sharding — each group's stacked params (and its logits)
  are sharding-constrained along the ``clients`` logical axis
  (:mod:`repro.sharding.partition` maps it to the data mesh axes), so large
  homogeneous groups data-parallelize across the mesh with no driver
  changes.

Outputs are normalized to the ensemble dtype (f32) at this boundary —
mixed-dtype markets (a bf16 client next to f32 ones) produce a
deterministic f32 stack instead of whatever ``jnp.stack`` promotion was
implied by client order.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ensemble import ENSEMBLE_DTYPE, make_logits_all
from repro.utils.scope import scoped
from repro.utils.trees import tree_stack, tree_unstack

#: The named scope of the client ensemble's forward (and, through autodiff,
#: its backward) wherever a program calls it.
BANK_SCOPE = "ofl.bank"


def _apply_key(fn: Callable) -> Any:
    """A hashable grouping key for an apply fn. ``functools.partial`` is
    destructured (two ``partial(cnn_apply, "mlp")`` objects must group
    together even though partial hashes by identity); anything unhashable
    falls back to object identity — worst case a singleton group, never a
    wrong group."""
    if isinstance(fn, functools.partial):
        kw = tuple(sorted(fn.keywords.items())) if fn.keywords else ()
        key = ("partial", _apply_key(fn.func), fn.args, kw)
    else:
        key = ("fn", fn)
    try:
        hash(key)
        return key
    except TypeError:
        return ("id", id(fn))


def _params_key(params: Any) -> Any:
    leaves, treedef = jax.tree_util.tree_flatten(params)
    return (treedef, tuple((tuple(l.shape), jnp.dtype(l.dtype).str) for l in leaves))


def _constrain_clients(tree: Any) -> Any:
    """Shard the leading (client) axis of a stacked group tree along the
    data mesh axes when a mesh is in context (no-op otherwise — unit tests
    and single-device runs)."""
    from repro.sharding.partition import constrain

    return jax.tree_util.tree_map(
        lambda l: constrain(l, "clients", *([None] * (l.ndim - 1))), tree
    )


@dataclasses.dataclass(frozen=True)
class ClientBank:
    """Static (host-side) description of a grouped client ensemble.

    The bank itself holds no arrays: its grouped params travel separately as
    a ``tuple`` of stacked pytrees (one per group, clients on the leading
    axis) — a plain jax pytree that threads through jitted programs exactly
    where the old per-client params tuple did. Build with
    :meth:`ClientBank.build`, evaluate with :meth:`logits_all`.
    """

    applies: Tuple[Callable, ...]  # one apply fn per group
    counts: Tuple[int, ...]  # clients per group
    order: Tuple[int, ...]  # original client index of each stacked row
    scan_chunk: int = 0
    shard_clients: bool = True

    @property
    def num_clients(self) -> int:
        return len(self.order)

    @property
    def num_groups(self) -> int:
        return len(self.applies)

    @property
    def is_client_ordered(self) -> bool:
        return self.order == tuple(range(self.num_clients))

    @classmethod
    def build(
        cls,
        apply_fns: Sequence[Callable],
        params_list: Sequence[Any],
        scan_chunk: int = 0,
        shard_clients: bool = True,
    ) -> Tuple["ClientBank", Tuple[Any, ...]]:
        """Group clients by (apply fn, param treedef + leaf shapes/dtypes)
        and stack each group. Returns ``(bank, bank_params)``; grouping
        preserves first-seen group order and within-group client order, so a
        homogeneous market is one group with ``order == range(K)``."""
        assert len(apply_fns) == len(params_list), (len(apply_fns), len(params_list))
        groups: Dict[Any, int] = {}
        applies: List[Callable] = []
        members: List[List[int]] = []
        for k, (fn, p) in enumerate(zip(apply_fns, params_list)):
            key = (_apply_key(fn), _params_key(p))
            g = groups.get(key)
            if g is None:
                g = groups[key] = len(applies)
                applies.append(fn)
                members.append([])
            members[g].append(k)
        bank = cls(
            applies=tuple(applies),
            counts=tuple(len(m) for m in members),
            order=tuple(k for m in members for k in m),
            scan_chunk=int(scan_chunk),
            shard_clients=shard_clients,
        )
        bank_params = tuple(
            tree_stack([params_list[k] for k in m]) for m in members
        )
        return bank, bank_params

    # -- forward ------------------------------------------------------------

    def _group_logits(self, g: int, stacked: Any, x: jax.Array) -> jax.Array:
        """One group's (n_g, B, C) client logits: a single vmapped forward,
        or a scan over vmapped chunks when the group outgrows scan_chunk."""
        apply_fn, n = self.applies[g], self.counts[g]
        if self.shard_clients:
            stacked = _constrain_clients(stacked)
        fwd = jax.vmap(apply_fn, in_axes=(0, None))
        c = self.scan_chunk
        if c <= 0 or n <= c:
            out = fwd(stacked, x)
        else:
            pad = (-n) % c
            if pad:
                stacked = jax.tree_util.tree_map(
                    lambda l: jnp.concatenate([l, l[:pad]], axis=0), stacked
                )
            chunked = jax.tree_util.tree_map(
                lambda l: l.reshape((n + pad) // c, c, *l.shape[1:]), stacked
            )
            _, outs = jax.lax.scan(
                lambda _, ch: (None, fwd(ch, x)), None, chunked
            )
            out = outs.reshape(-1, *outs.shape[2:])[:n]
        out = out.astype(ENSEMBLE_DTYPE)
        if self.shard_clients:
            out = _constrain_clients(out)
        return out

    def logits_all(self, bank_params: Tuple[Any, ...], x: jax.Array) -> jax.Array:
        """f(bank_params, x) -> (K, B, C) stacked client logits in ORIGINAL
        client order — the drop-in replacement for the fn built by
        :func:`repro.core.ensemble.make_logits_all`. Its ops carry the named
        scope ``ofl.bank``, each group's ``ofl.bank.g<i>`` inside it."""
        with jax.named_scope(BANK_SCOPE):
            outs = []
            for g, sp in enumerate(bank_params):
                with jax.named_scope(f"{BANK_SCOPE}.g{g}"):
                    outs.append(self._group_logits(g, sp, x))
            stacked = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)
            if self.is_client_ordered:
                return stacked
            inv = np.argsort(np.asarray(self.order))
            return jnp.take(stacked, jnp.asarray(inv), axis=0)

    # -- interop ------------------------------------------------------------

    def unstack_params(self, bank_params: Tuple[Any, ...]) -> List[Any]:
        """Back to the per-client params list, in original client order."""
        rows = []
        for n, sp in zip(self.counts, bank_params):
            rows.extend(tree_unstack(sp, n))
        out: List[Any] = [None] * self.num_clients
        for row, k in zip(rows, self.order):
            out[k] = row
        return out

    def stack_params(self, params_list: Sequence[Any]) -> Tuple[Any, ...]:
        """Regroup a client-ordered params list into this bank's layout."""
        assert len(params_list) == self.num_clients
        out, at = [], 0
        for n in self.counts:
            out.append(tree_stack([params_list[k] for k in self.order[at : at + n]]))
            at += n
        return tuple(out)

    def client_apply(self, k: int) -> Callable:
        """The apply fn of original client ``k``."""
        at = 0
        for g, n in enumerate(self.counts):
            if k in self.order[at : at + n]:
                return self.applies[g]
            at += n
        raise IndexError(k)


ENSEMBLE_IMPLS = ("grouped", "looped")


def make_ensemble(
    apply_fns: Sequence[Callable],
    params_list: Sequence[Any],
    impl: str = "grouped",
    scan_chunk: int = 0,
    shard_clients: bool = True,
) -> Tuple[Callable, Any]:
    """The one ensemble-construction entry every method driver uses.

    Returns ``(logits_all_fn, ensemble_params)`` where
    ``logits_all_fn(ensemble_params, x) -> (K, B, C)`` in client order:

    * ``impl="grouped"`` — a :class:`ClientBank` (params stacked per arch
      group, vmapped group forwards; the production path);
    * ``impl="looped"``  — the original python-unrolled per-client loop over
      a tuple of param trees (the parity baseline and the legacy driver's
      path).

    Either way the callable runs under the named scope ``ofl.bank``.
    """
    if impl == "looped":
        return scoped(BANK_SCOPE, make_logits_all(list(apply_fns))), tuple(params_list)
    if impl != "grouped":
        raise ValueError(f"unknown ensemble impl {impl!r}; expected one of {ENSEMBLE_IMPLS}")
    bank, bank_params = ClientBank.build(
        apply_fns, params_list, scan_chunk=scan_chunk, shard_clients=shard_clients
    )
    return bank.logits_all, bank_params
