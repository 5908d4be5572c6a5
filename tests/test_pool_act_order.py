"""ReLU after the 2x2 max-pool in the cnn5 and cnn2 client networks.

ReLU is monotone, so ``relu(max_pool(x)) == max_pool(relu(x))`` holds
exactly. The gradient agrees too: where a window's max is above zero both
orders send it to the same first-max position, and elsewhere both send
zero. So the networks pool the convolution's output first and apply ReLU
to the quarter-size pooled tensor; under autodiff no full-resolution
activation or ReLU mask is then kept.

These tests pin the identity bit for bit (one op on awkward inputs, and the
whole vmapped client bank against a copy of the old relu-then-pool apply)
and that the grouped bank's input gradient holds no ReLU at a
convolution's full output size.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.client_bank import make_ensemble
from repro.models.cnn import cnn_apply, conv2d, init_cnn, max_pool

pytestmark = pytest.mark.tier1

CLASSES, IMAGE, K, B = 10, (32, 32, 3), 10, 16


def _pool_input(kind: str) -> jax.Array:
    key = jax.random.key(3)
    if kind == "random":
        return jax.random.normal(key, (2, 8, 8, 4))
    if kind == "tied":  # rounded to halves: many windows hold equal maxima
        return jnp.round(2.0 * jax.random.normal(key, (2, 8, 8, 4))) / 2.0
    if kind == "all_negative":
        return -jnp.abs(jax.random.normal(key, (2, 8, 8, 4))) - 0.5
    if kind == "all_zero":
        return jnp.zeros((2, 8, 8, 4))
    if kind == "odd_extent":  # a 7x9 map: the VALID pool drops the last row and column
        return jax.random.normal(key, (2, 7, 9, 4))
    raise ValueError(kind)


@pytest.mark.parametrize("kind", ["random", "tied", "all_negative", "all_zero", "odd_extent"])
def test_relu_commutes_with_max_pool_bitwise(kind):
    """Forward and VJP of ``relu(max_pool(x))`` equal ``max_pool(relu(x))``."""
    x = _pool_input(kind)
    after, vjp_after = jax.vjp(lambda v: jax.nn.relu(max_pool(v)), x)
    before, vjp_before = jax.vjp(lambda v: max_pool(jax.nn.relu(v)), x)
    np.testing.assert_array_equal(np.asarray(after), np.asarray(before))
    ct = jax.random.normal(jax.random.key(4), after.shape)
    (g_after,), (g_before,) = vjp_after(ct), vjp_before(ct)
    np.testing.assert_array_equal(np.asarray(g_after), np.asarray(g_before))
    # all-negative and all-zero inputs pass no gradient; the others do
    assert bool(jnp.any(g_after != 0)) == (kind not in ("all_negative", "all_zero"))


def _relu_then_pool_apply(p, x):
    """The cnn5 / cnn2 forward as it was, ReLU before each pool."""
    x = jax.nn.relu(conv2d(x, p["c1"]))
    x = max_pool(x)
    x = jax.nn.relu(conv2d(x, p["c2"]))
    x = max_pool(x)
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ p["f1"])
    x = jax.nn.relu(x @ p["f2"])
    return x @ p["out"]


def _bank(arch: str, apply):
    clients = [init_cnn(jax.random.key(i), arch, CLASSES, IMAGE) for i in range(K)]
    return make_ensemble([apply] * K, clients)


def _logits_and_input_grad(logits_all, bank_params, x, ct):
    out, vjp = jax.vjp(lambda v: logits_all(bank_params, v), x)
    return out, vjp(ct)[0]


@pytest.mark.parametrize("arch", ["cnn5", "cnn2"])
def test_bank_matches_relu_then_pool_bitwise(arch):
    """The vmapped bank (K=10, b=16) gives the old order's logits and input
    gradient bit for bit."""
    x = jax.random.normal(jax.random.key(1), (B, *IMAGE))
    ct = jax.random.normal(jax.random.key(2), (K, B, CLASSES))
    run = jax.jit(_logits_and_input_grad, static_argnums=0)
    got = run(*_bank(arch, partial(cnn_apply, arch)), x, ct)
    want = run(*_bank(arch, _relu_then_pool_apply), x, ct)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in its params."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _is_zero(v) -> bool:
    return hasattr(v, "val") and np.ndim(v.val) == 0 and float(v.val) == 0.0


def test_bank_input_grad_has_no_full_resolution_relu():
    """In the cnn5 bank's input gradient, no ReLU (``max`` with 0, or
    ``custom_jvp_call`` named relu) and no ReLU mask (``gt`` with 0) reads a
    tensor of a convolution's full output size, which is the size of each
    pool's input."""
    logits_all, bank_params = _bank("cnn5", partial(cnn_apply, "cnn5"))
    x = jnp.zeros((B, *IMAGE))
    jaxpr = jax.make_jaxpr(jax.grad(lambda v: logits_all(bank_params, v).sum()))(x).jaxpr
    eqns = list(_eqns(jaxpr))
    full = {e.invars[0].aval.size for e in eqns if e.primitive.name == "reduce_window_max"}
    pooled = {e.outvars[0].aval.size for e in eqns if e.primitive.name == "reduce_window_max"}
    assert len(full) == 2, full
    relu_sizes = set()
    for e in eqns:
        name = e.primitive.name
        if (name == "custom_jvp_call" and e.params.get("name") == "relu") or (
            name in ("max", "gt") and any(_is_zero(v) for v in e.invars)
        ):
            relu_sizes.update(v.aval.size for v in e.invars if not _is_zero(v))
    assert not relu_sizes & full, (relu_sizes, full)
    assert pooled <= relu_sizes, (pooled, relu_sizes)  # the ReLUs run on the pools' outputs
