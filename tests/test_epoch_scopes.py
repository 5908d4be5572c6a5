"""The named scopes of the networks inside the fused Co-Boosting epoch.

The compiled program's ``op_name`` metadata is what a device trace's ops are
attributed by: ``ofl.bank`` (and ``ofl.bank.g<i>`` per architecture group),
``ofl.gen.net``, ``ofl.server`` and ``ofl.dhs`` must reach it, in the
forward (``jvp(...)``) and backward (``transpose(jvp(...))``) forms autodiff
gives them, and nest inside the phase scopes ``ofl.gen.boost``,
``ofl.ee.weight_search`` and ``ofl.kd``.
"""
from __future__ import annotations

import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest

from repro.config.train import OFLConfig
from repro.core.client_bank import make_ensemble
from repro.core.coboosting import default_image_setup, init_synth_buffer
from repro.core.ensemble import uniform_weights
from repro.core.epoch import distill_schedule, make_coboost_epoch
from repro.models.cnn import cnn_apply, init_cnn

pytestmark = pytest.mark.tier1

CLASSES, SHAPE, K = 4, (8, 8, 3), 3
NETWORKS = ("ofl.bank", "ofl.gen.net", "ofl.server", "ofl.dhs")


def _compiled_op_names(impl: str) -> list:
    cfg = OFLConfig(num_clients=K, epochs=1, gen_iters=2, batch_size=4, latent_dim=6, buffer_batches=2,
                    ensemble_impl=impl)
    apply = partial(cnn_apply, "cnn2")
    clients = [init_cnn(jax.random.key(i), "cnn2", CLASSES, SHAPE) for i in range(K)]
    logits_all, client_params = make_ensemble([apply] * K, clients, impl=impl)
    server = init_cnn(jax.random.key(9), "cnn2", CLASSES, SHAPE)
    gen_apply, gen = default_image_setup(jax.random.key(5), cfg, CLASSES, SHAPE)
    step, gen_opt, srv_opt = make_coboost_epoch(logits_all, apply, gen_apply, cfg, K, CLASSES)
    slot_order, n_valid = distill_schedule(0, cfg.buffer_batches)
    args = (server, srv_opt.init(server), gen, gen_opt.init(gen), uniform_weights(K),
            init_synth_buffer(gen_apply, gen, cfg), jax.random.key(0), jnp.zeros((), jnp.int32),
            slot_order, n_valid, client_params)
    text = step.lower(*args).compile().as_text()
    return sorted(set(re.findall(r'op_name="([^"]*)"', text)))


@pytest.fixture(scope="module")
def grouped():
    return _compiled_op_names("grouped")


def components(path: str) -> list:
    return re.split(r"[/;]", path)


def bare(component: str) -> str:
    """``transpose(jvp(ofl.bank))`` -> ``ofl.bank``."""
    return re.sub(r"^(?:[\w.\-]*\()*([^()]*)\)*$", r"\1", component)


def under(path: str, scope: str) -> bool:
    return any(bare(c) == scope for c in components(path))


def test_every_network_scope_reaches_the_compiled_program(grouped):
    for scope in NETWORKS + ("ofl.bank.g0",):
        assert any(under(p, scope) for p in grouped), scope


@pytest.mark.parametrize("scope", ["ofl.bank", "ofl.gen.net"])
def test_forward_and_backward_forms(grouped, scope):
    comps = {c for p in grouped for c in components(p)}
    assert f"jvp({scope})" in comps, scope
    assert f"transpose(jvp({scope}))" in comps, scope


def test_group_scope_nests_inside_the_bank(grouped):
    for p in grouped:
        if under(p, "ofl.bank.g0"):
            assert under(p, "ofl.bank"), p


@pytest.mark.parametrize("scope, phases", [
    ("ofl.gen.net", ("ofl.gen.boost",)),
    ("ofl.server", ("ofl.gen.boost", "ofl.kd")),
    ("ofl.dhs", ("ofl.ee.weight_search", "ofl.kd")),
    ("ofl.bank", ("ofl.gen.boost", "ofl.ee.weight_search", "ofl.kd")),
])
def test_phase_scopes_enclose_the_networks(grouped, scope, phases):
    """Every op of the program (a full path from ``jit(epoch_step)``; the
    reducers' bodies carry a relative one) that runs under a network's
    scope runs under one of the phases that use it."""
    full = [p for p in grouped if p.startswith("jit(epoch_step)/") and under(p, scope)]
    assert full, scope
    for p in full:
        assert any(under(p, ph) for ph in phases), p


def test_looped_bank_is_scoped():
    names = _compiled_op_names("looped")
    assert any(under(p, "ofl.bank") for p in names)
    assert not any(under(p, "ofl.bank.g0") for p in names)
