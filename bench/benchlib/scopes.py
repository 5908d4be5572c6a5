"""Named scopes of the epoch program's networks, read from a flattened trace
whose ops carry their scope paths (``trace.attach_scopes``).

A ``jax.named_scope`` opened inside a differentiated function reaches the
``op_name`` wrapped by the transform: ``jvp(ofl.bank)`` in the forward pass,
``transpose(jvp(ofl.bank))`` in the backward, ``vmap(...)`` under a vmap.
So a scope matches a component of the path, bare or inside any number of
such wrappers; components are separated by ``/`` (and ``;`` where XLA joins
the names of merged ops). A scope never matches a name it is only a prefix
of: ``ofl.bank`` does not match ``ofl.bankx``, nor ``ofl.bank.g0`` (whose
ops also sit under an ``ofl.bank`` component).
"""
from __future__ import annotations

import re
from typing import Callable

from . import readers, trace as tr


def pattern(scope: str) -> re.Pattern:
    """Matches a scope path that has ``scope`` as a component, bare or
    wrapped by transforms."""
    return re.compile(r"(?:^|[/;])(?:[\w.\-]*\()*" + re.escape(scope) + r"\)*(?:$|[/;])")


def in_scope(dev: dict, scope: str) -> Callable[[int], bool]:
    """Ops whose scope path holds ``scope``, bare or wrapped."""
    pat = pattern(scope)
    return lambda i: bool(pat.search(dev["scopes"][i]))


def scope_ms(ctx: dict, scope: str):
    """Device milliseconds per epoch of the ops under ``scope``: the union
    of their intervals in the window (an op under nested or overlapping
    scopes counts once), averaged over the traced epochs and the chips.
    None when the cell is not traced or nothing runs under the scope."""
    t = readers.traced(ctx, "ofl")
    if t is None:
        return None
    t0, t1 = tr.window(t)
    devs = tr.devices(t)
    secs = sum(tr.ops_time(d, in_scope(d, scope), t0, t1) for d in devs) / len(devs)
    return 1000.0 * secs / ctx["epochs_traced"] if secs > 0 else None
