"""The client ensemble A_w (Eq. 2) over possibly-heterogeneous client models.

Clients are (apply_fn, params) pairs; ``make_logits_all`` builds a single
traced function producing the (n, B, C) stack of client logits, which every
downstream component (generator loss, DHS perturbation, EE weight search,
distillation) consumes. For homogeneous clients the stacked form is a single
vmapped forward, for heterogeneous ones a python-unrolled trace — either
way one jitted program.
"""
from __future__ import annotations

from typing import Any, Callable, List, Sequence, Tuple

import jax
import jax.numpy as jnp

# The stack dtype every consumer of logits_all sees. Clients may run in
# mixed precision (a bf16 client next to f32 ones); normalizing each
# client's output here makes the (n, B, C) stack deterministic instead of
# inheriting whatever promotion jnp.stack derives from client order.
ENSEMBLE_DTYPE = jnp.float32


def uniform_weights(n: int) -> jax.Array:
    return jnp.full((n,), 1.0 / n, jnp.float32)


def data_amount_weights(sizes: Sequence[int]) -> jax.Array:
    s = jnp.asarray(sizes, jnp.float32)
    return s / jnp.sum(s)


def make_logits_all(apply_fns: List[Callable]) -> Callable:
    """Returns f(client_params_list, x) -> (n, B, C) stacked client logits."""

    def logits_all(client_params: List[Any], x: jax.Array) -> jax.Array:
        outs = [f(p, x).astype(ENSEMBLE_DTYPE) for f, p in zip(apply_fns, client_params)]
        return jnp.stack(outs, axis=0)

    return logits_all


def make_logits_all_stacked(apply_fn: Callable) -> Callable:
    """Homogeneous fast path: one vmap over a stacked param tree (clients on
    the leading axis — this is the form the distributed LM ensemble uses)."""

    def logits_all(stacked_params: Any, x: jax.Array) -> jax.Array:
        out = jax.vmap(apply_fn, in_axes=(0, None))(stacked_params, x)
        return out.astype(ENSEMBLE_DTYPE)

    return logits_all


def ensemble_logits(logits_all: jax.Array, w: jax.Array) -> jax.Array:
    """A_w(x) = Σ_k w_k f_k(x). logits_all: (n, B, C); w: (n,). Exact f32 on
    every backend (TPU's default f32 matmul would take bf16 inputs)."""
    return jnp.einsum(
        "k,k...->...", w.astype(jnp.float32), logits_all.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )


def ensemble_accuracy(logits_all: jax.Array, w: jax.Array, labels: jax.Array) -> jax.Array:
    pred = jnp.argmax(ensemble_logits(logits_all, w), axis=-1)
    return jnp.mean((pred == labels).astype(jnp.float32))
