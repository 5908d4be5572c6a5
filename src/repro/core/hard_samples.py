"""Diverse hard-sample construction (DHS, Eq. 9–10).

One backward step through the ensemble seeks the input-space direction that
maximizes ``uᵀA_w(x)`` for a random u ~ Unif[−1,1]^C, then perturbs the
sample by ε along the L2-normalized gradient:

    x̃ = x + ε · ∇_x(uᵀA_w(x)) / ‖∇_x(uᵀA_w(x))‖₂

The randomness in u makes repeated visits to the same stored sample produce
*different* hard variants, which is why we apply it on the fly at sampling
time rather than once per epoch (equivalent under Algorithm 1, cheaper in
memory).
"""
from __future__ import annotations

from typing import Any, Callable, List

import jax
import jax.numpy as jnp

from repro.core.ensemble import ensemble_logits


def diversify(
    logits_all_fn: Callable,
    client_params: Any,
    w: jax.Array,
    x: jax.Array,
    key: jax.Array,
    epsilon: float,
) -> jax.Array:
    """Apply Eq. 10 to a batch x (B, ...). Returns x̃ of the same shape. Its
    ops (a bank forward and the input gradient) carry the named scope
    ``ofl.dhs``."""

    def scalar(x_in):
        la = logits_all_fn(client_params, x_in)  # (n, B, C)
        ens = ensemble_logits(la, w)  # (B, C)
        u = jax.random.uniform(key, ens.shape, jnp.float32, -1.0, 1.0)
        return jnp.sum(u * ens)

    with jax.named_scope("ofl.dhs"):
        g = jax.grad(scalar)(x)
        flat = g.reshape(g.shape[0], -1).astype(jnp.float32)
        norm = jnp.linalg.norm(flat, axis=-1)[:, None]
        direction = (flat / jnp.maximum(norm, 1e-12)).reshape(g.shape)
        return (x.astype(jnp.float32) + epsilon * direction).astype(x.dtype)
