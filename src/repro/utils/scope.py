"""Named scopes for the networks inside a jitted program.

``jax.named_scope`` adds only the ``op_name`` metadata of the ops it
encloses; the compiled program keeps the same operations. Under autodiff the
outermost scope of a differentiated function is wrapped by the transform
(``jvp(ofl.bank)`` in the forward, ``transpose(jvp(ofl.bank))`` in the
backward); scopes opened inside it stay bare.
"""
from __future__ import annotations

from typing import Callable

import jax


def scoped(scope: str, fn: Callable) -> Callable:
    """``fn`` run under ``jax.named_scope(scope)``."""

    def call(*args, **kwargs):
        with jax.named_scope(scope):
            return fn(*args, **kwargs)

    return call
