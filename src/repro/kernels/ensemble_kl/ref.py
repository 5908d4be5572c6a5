"""Pure-jnp oracle for the fused ensemble-KL kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def ensemble_kl_ref(
    client_logits: jax.Array, student_logits: jax.Array, w: jax.Array, temperature: float = 1.0
) -> jax.Array:
    """client_logits: (K, B, V); student_logits: (B, V); w: (K,).
    Returns per-sample KL(softmax(A_w/T) ‖ softmax(s/T))·T², shape (B,)."""
    # HIGHEST: exact f32 on every backend (TPU's default f32 matmul takes
    # bf16 inputs), the same arithmetic as the kernel's f32 combine
    t = jnp.einsum(
        "k,kbv->bv", w.astype(jnp.float32), client_logits.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    t = t / temperature
    s = student_logits.astype(jnp.float32) / temperature
    lt = jax.nn.log_softmax(t, axis=-1)
    ls = jax.nn.log_softmax(s, axis=-1)
    return jnp.sum(jnp.exp(lt) * (lt - ls), axis=-1) * (temperature**2)
