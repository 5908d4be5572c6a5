"""Pure-jnp oracle for the fused GHM-weighted CE kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def ghm_ce_ref(
    client_logits: jax.Array,
    labels: jax.Array,
    w: jax.Array,
    weighted: bool = True,
    stop_difficulty_grad: bool = False,
) -> jax.Array:
    """client_logits: (K, B, V); labels: (B,); w: (K,). Per-sample d·CE.
    ``stop_difficulty_grad`` treats d(x) as a constant under autodiff (the
    Eq. 6 generator-loss convention, matching ``ghs_loss``)."""
    # HIGHEST: exact f32 on every backend (TPU's default f32 matmul takes
    # bf16 inputs), the same arithmetic as the kernel's f32 combine
    t = jnp.einsum(
        "k,kbv->bv", w.astype(jnp.float32), client_logits.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    lse = jax.scipy.special.logsumexp(t, axis=-1)
    ly = jnp.take_along_axis(t, labels[:, None].astype(jnp.int32), axis=-1)[:, 0]
    nll = lse - ly
    if not weighted:
        return nll
    d = 1.0 - jnp.exp(ly - lse)
    if stop_difficulty_grad:
        d = jax.lax.stop_gradient(d)
    return d * nll
