"""Co-Boosting (Algorithm 1) — the paper's primary contribution.

Each global epoch:
  1. *Data boosting* — ``T_G`` generator steps on Eq. 8 (difficulty-weighted
     CE against the current ensemble + adversarial server disagreement),
     then the fresh batch joins the synthetic buffer D_S.
  2. *DHS* — samples drawn from D_S are diversified on the fly by the
     one-step input perturbation of Eq. 10.
  3. *Ensemble boosting (EE)* — one sign-gradient step (Eq. 12) on the
     ensembling weights w over the hard samples.
  4. *Distillation* — SGD-momentum steps on the temperature-KL between the
     re-weighted ensemble and the server (Eq. 4).

Component toggles (``use_ghs`` / ``use_dhs`` / ``use_ee`` / ``use_adv``)
reproduce the Table 7 ablation; with all off the loop degenerates to the
DENSE-style base pipeline (CE-only generator, uniform ensemble).

Two epoch drivers share this module's loss machinery:

  * ``driver="fused"`` (default) — the whole epoch is one jitted program
    over the device-resident ring buffer (:mod:`repro.core.epoch`): O(1)
    dispatches per epoch, losses synced only at eval boundaries. Its Eq. 4 /
    Eq. 6 losses follow ``cfg.backend_for("loss")`` (the fused differentiable
    Pallas kernels on TPU, the jnp composition elsewhere).
  * ``driver="legacy"`` — DEPRECATED alias scheduled for removal: the
    original python loop, one jitted program per stage and per replay batch
    (it never routes through the Pallas kernels). The parity contract has
    moved onto ``backend="ref"`` vs ``backend="pallas-interpret"`` of the
    fused driver (tests/grad_harness.py), so the legacy loop is no longer
    the oracle — selecting it emits a :class:`DeprecationWarning`.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.config.train import OFLConfig, TrainConfig
from repro.core.buffer import ReplayBuffer, buffer_as_lists, buffer_init
from repro.core.client_bank import make_ensemble
from repro.core.ensemble import ensemble_logits, uniform_weights
from repro.core.epoch import _sample_zy, distill_schedule, make_coboost_epoch
from repro.core.hard_samples import diversify
from repro.core.hardness import generator_loss
from repro.core.losses import kl_loss
from repro.core.weight_search import update_weights
from repro.models.generator import image_generator, init_image_generator
from repro.optim import adam, constant_schedule, sgdm
from repro.optim.optimizers import apply_updates
from repro.utils import get_logger

log = get_logger("coboosting")


def _warn_legacy_driver() -> None:
    """``driver="legacy"`` is a deprecated alias scheduled for removal.

    The per-batch python loop stopped being the parity oracle when the
    kernel contract moved to ``backend="ref"`` vs ``backend="pallas*"`` of
    the fused driver (both passes — see tests/grad_harness.py); it survives
    only as a dispatch-overhead benchmark baseline."""
    warnings.warn(
        "driver='legacy' is deprecated and scheduled for removal: use the "
        "fused driver (default) with backend='ref' for a pure-jnp oracle run",
        DeprecationWarning,
        stacklevel=3,
    )


@dataclasses.dataclass
class OFLState:
    """Mutable python-side state of the OFL run."""

    server_params: Any
    gen_params: Any
    weights: jax.Array
    buffer_x: List[jax.Array]
    buffer_y: List[jax.Array]
    history: List[Dict[str, float]]
    buffer: Optional[ReplayBuffer] = None
    dispatch_count: int = 0  # fused-driver epoch_step calls (O(1)/epoch)


def init_synth_buffer(gen_apply: Callable, gen_params: Any, cfg: OFLConfig) -> ReplayBuffer:
    """Preallocate the ring from the generator's output spec (no forward)."""
    z = jax.ShapeDtypeStruct((cfg.batch_size, cfg.latent_dim), jnp.float32)
    y = jax.ShapeDtypeStruct((cfg.batch_size,), jnp.int32)
    xs = jax.eval_shape(gen_apply, gen_params, z, y)
    return buffer_init(cfg.buffer_batches, xs.shape, xs.dtype)


def make_generator_phase(
    logits_all_fn: Callable,
    server_apply: Callable,
    gen_apply: Callable,
    cfg: OFLConfig,
):
    """One jitted program running the T_G generator updates of Algorithm 1
    lines 5–9 (Adam on Eq. 8)."""
    opt = adam(constant_schedule(cfg.gen_lr))

    def loss_fn(gen_params, z, y, client_params, w, server_params):
        x = gen_apply(gen_params, z, y)
        la = logits_all_fn(client_params, x)
        ens = ensemble_logits(la, w)
        s_logits = server_apply(server_params, x)
        return generator_loss(
            ens,
            s_logits,
            y,
            beta=cfg.beta,
            use_ghs=cfg.use_ghs,
            use_adv=cfg.use_adv,
            kl_temperature=cfg.gen_kl_temperature,
        )

    @jax.jit
    def phase(gen_params, opt_state, z, y, client_params, w, server_params):
        def body(i, carry):
            gp, st = carry
            loss, grads = jax.value_and_grad(loss_fn)(gp, z, y, client_params, w, server_params)
            updates, st = opt.update(grads, st, gp, i)
            gp = apply_updates(gp, updates)
            return gp, st

        gen_params, opt_state = jax.lax.fori_loop(0, cfg.gen_iters, body, (gen_params, opt_state))
        final_loss = loss_fn(gen_params, z, y, client_params, w, server_params)
        return gen_params, opt_state, final_loss

    return phase, opt


def make_distill_step(
    logits_all_fn: Callable,
    server_apply: Callable,
    cfg: OFLConfig,
):
    """One jitted server distillation step (Eq. 4) with optional on-the-fly
    DHS diversification (Eq. 10)."""
    opt = sgdm(constant_schedule(cfg.server_lr), momentum=0.9)

    def loss_fn(server_params, x, client_params, w):
        la = logits_all_fn(client_params, x)
        ens = ensemble_logits(la, w)
        s_logits = server_apply(server_params, x)
        return kl_loss(ens, s_logits, cfg.kd_temperature)

    @jax.jit
    def step(server_params, opt_state, x, key, client_params, w, step_idx):
        if cfg.use_dhs:
            x = diversify(logits_all_fn, client_params, w, x, key, cfg.epsilon)
        loss, grads = jax.value_and_grad(loss_fn)(server_params, x, client_params, w)
        updates, opt_state = opt.update(grads, opt_state, server_params, step_idx)
        server_params = apply_updates(server_params, updates)
        return server_params, opt_state, loss

    return step, opt


def make_ee_step(logits_all_fn: Callable, cfg: OFLConfig, num_clients: int):
    """One jitted Eq. 12 sign step on the ensembling weights (on hard
    samples)."""
    mu = cfg.mu / num_clients

    @jax.jit
    def step(w, x, y, key, client_params):
        if cfg.use_dhs:
            x = diversify(logits_all_fn, client_params, w, x, key, cfg.epsilon)
        la = logits_all_fn(client_params, x)
        return update_weights(w, la, y, mu)

    return step


def _completed(result: jax.Array, since: float) -> float:
    """Wait for an epoch's ``result``; observe its ``ofl.epoch.step_s`` from
    ``since`` (the previous completion) and return the completion time."""
    result.block_until_ready()
    now = time.perf_counter()
    obs.observe("ofl.epoch.step_s", now - since, driver="fused")
    return now


def run_coboosting(
    client_applies: List[Callable],
    client_params: List[Any],
    server_apply: Callable,
    server_params: Any,
    gen_apply: Callable,
    gen_params: Any,
    cfg: OFLConfig,
    num_classes: int,
    key: jax.Array,
    eval_fn: Optional[Callable] = None,
    eval_every: int = 50,
    init_weights: Optional[jax.Array] = None,
    driver: str = "fused",
) -> OFLState:
    """Algorithm 1. ``eval_fn(server_params, w) -> dict`` is called every
    ``eval_every`` epochs for history logging. ``driver`` selects the fused
    single-dispatch epoch program (whose distillation/generator losses run
    the ``cfg.backend_for("loss")`` kernel path) or the legacy per-batch python
    loop (always pure jnp — the parity baseline).

    NOTE: the fused driver donates the caller's ``server_params`` /
    ``gen_params`` (and derived state) to the epoch program — they are
    invalidated after the first epoch; copy them first if you need them
    again (e.g. for a legacy A/B run from the same init)."""
    n = len(client_applies)
    # fused driver: client forwards run through the grouped ClientBank
    # (cfg.ensemble_impl, O(#groups) trace) — the legacy driver always uses
    # the python-unrolled per-client loop, keeping it the parity baseline.
    impl = cfg.ensemble_impl if driver == "fused" else "looped"
    logits_all_fn, client_params = make_ensemble(
        client_applies, client_params, impl=impl, scan_chunk=cfg.ensemble_scan_chunk
    )
    w = uniform_weights(n) if init_weights is None else init_weights

    if driver == "fused":
        epoch_step, gen_opt, srv_opt = make_coboost_epoch(
            logits_all_fn, server_apply, gen_apply, cfg, n, num_classes
        )
        gen_opt_state = gen_opt.init(gen_params)
        srv_opt_state = srv_opt.init(server_params)
        buf = init_synth_buffer(gen_apply, gen_params, cfg)
        state = OFLState(server_params, gen_params, w, [], [], [])
        srv_steps = jnp.zeros((), jnp.int32)
        # ofl.epoch.step_s times execution, one epoch deep: with the registry
        # on, each epoch is dispatched before the previous one is waited for,
        # and the sample is the interval between their completions (the
        # first from the loop's start, so it holds the compile). With the
        # registry off nothing here blocks.
        timed = obs.registry().enabled
        pending, t_done = None, time.perf_counter()
        for epoch in range(cfg.epochs):
            slot_order, n_valid = distill_schedule(epoch, cfg.buffer_batches)
            # the span brackets the DISPATCH of the fused program. Per-phase
            # and per-network device time comes from the jax.named_scopes
            # inside the program (visible under --profile-dir).
            with obs.span("ofl.epoch", epoch=epoch, driver="fused"):
                (
                    state.server_params, srv_opt_state, state.gen_params, gen_opt_state,
                    state.weights, buf, key, srv_steps, gloss, dmean,
                ) = epoch_step(
                    state.server_params, srv_opt_state, state.gen_params, gen_opt_state,
                    state.weights, buf, key, srv_steps, slot_order, n_valid, client_params,
                )
            state.dispatch_count += 1
            if timed:
                if pending is not None:
                    t_done = _completed(pending, t_done)
                pending = dmean
            obs.inc("ofl.epoch.count")
            obs.inc("ofl.epoch.dispatches")
            obs.inc("ofl.gen.steps", cfg.gen_iters)
            if cfg.use_ee:
                obs.inc("ofl.ee.steps")
            obs.inc("ofl.kd.steps", int(n_valid))
            if eval_fn is not None and ((epoch + 1) % eval_every == 0 or epoch == cfg.epochs - 1):
                if timed:  # the evaluation waits for this epoch anyway
                    _completed(pending, t_done)
                    pending = None
                metrics = eval_fn(state.server_params, state.weights)
                metrics.update(epoch=epoch, gen_loss=float(gloss), distill_loss=float(dmean))
                state.history.append(metrics)
                log.info(
                    "epoch %d gen=%.4f distill=%.4f %s",
                    epoch, float(gloss), float(dmean),
                    {k: round(v, 4) for k, v in metrics.items() if isinstance(v, float)},
                )
                t_done = time.perf_counter()  # the next epoch starts after it
        if pending is not None:
            _completed(pending, t_done)
        state.buffer = buf
        state.buffer_x, state.buffer_y = buffer_as_lists(buf)
        return state
    if driver != "legacy":
        raise ValueError(f"unknown driver {driver!r}")
    _warn_legacy_driver()

    gen_phase, gen_opt = make_generator_phase(logits_all_fn, server_apply, gen_apply, cfg)
    distill_step, srv_opt = make_distill_step(logits_all_fn, server_apply, cfg)
    ee_step = make_ee_step(logits_all_fn, cfg, n)

    gen_opt_state = gen_opt.init(gen_params)
    srv_opt_state = srv_opt.init(server_params)

    state = OFLState(server_params, gen_params, w, [], [], [])
    srv_step_idx = 0
    for epoch in range(cfg.epochs):
        t_ep = time.perf_counter()
        key, k1, k2, k3 = jax.random.split(key, 4)
        # 1. generator phase (lines 5–9)
        z, y = _sample_zy(k1, cfg.batch_size, cfg.latent_dim, num_classes)
        with obs.span("ofl.gen.boost", epoch=epoch, iters=cfg.gen_iters):
            state.gen_params, gen_opt_state, gloss = gen_phase(
                state.gen_params, gen_opt_state, z, y, client_params, state.weights, state.server_params
            )
        obs.inc("ofl.gen.steps", cfg.gen_iters)
        obs.inc("ofl.epoch.dispatches")
        x_new = gen_apply(state.gen_params, z, y)
        state.buffer_x.append(x_new)
        state.buffer_y.append(y)
        if len(state.buffer_x) > cfg.buffer_batches:
            state.buffer_x.pop(0)
            state.buffer_y.pop(0)

        # 2–3. EE on the (diversified) fresh hard batch (lines 11–14)
        if cfg.use_ee:
            with obs.span("ofl.ee.weight_search", epoch=epoch):
                state.weights = ee_step(state.weights, x_new, y, k2, client_params)
            obs.inc("ofl.ee.steps")
            obs.inc("ofl.epoch.dispatches")

        # 4. server distillation over the replay buffer (lines 16–18)
        dlosses = []
        with obs.span("ofl.kd", epoch=epoch, batches=len(state.buffer_x)):
            for bi in np.random.RandomState(epoch).permutation(len(state.buffer_x)):
                k3, kb = jax.random.split(k3)
                state.server_params, srv_opt_state, dl = distill_step(
                    state.server_params,
                    srv_opt_state,
                    state.buffer_x[bi],
                    kb,
                    client_params,
                    state.weights,
                    jnp.asarray(srv_step_idx, jnp.int32),
                )
                obs.inc("ofl.kd.steps")
                obs.inc("ofl.epoch.dispatches")
                srv_step_idx += 1
                dlosses.append(dl)  # device scalar — no per-batch host sync
        obs.observe("ofl.epoch.step_s", time.perf_counter() - t_ep, driver="legacy")
        obs.inc("ofl.epoch.count")

        if eval_fn is not None and ((epoch + 1) % eval_every == 0 or epoch == cfg.epochs - 1):
            dmean = float(np.mean(jax.device_get(dlosses)))
            metrics = eval_fn(state.server_params, state.weights)
            metrics.update(epoch=epoch, gen_loss=float(gloss), distill_loss=dmean)
            state.history.append(metrics)
            log.info(
                "epoch %d gen=%.4f distill=%.4f %s",
                epoch,
                float(gloss),
                dmean,
                {k: round(v, 4) for k, v in metrics.items() if isinstance(v, float)},
            )
    return state


def default_image_setup(key, cfg: OFLConfig, num_classes: int, image_shape: Tuple[int, int, int]):
    """Convenience: init the paper's DCGAN-style generator + its apply fn."""
    gen_params = init_image_generator(key, cfg.latent_dim, num_classes, image_shape)
    gen_apply = lambda p, z, y: image_generator(p, z, y, image_shape)
    return gen_apply, gen_params
