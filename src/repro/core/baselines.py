"""One-shot FL baselines the paper compares against (Table 1).

* FedAvg  — parameter averaging (homogeneous archs only).
* FedENS  — the uniform-weight logit ensemble, no distillation.
* FedDF   — ensemble distillation on an available (validation) dataset.
* F-DAFL  — data-free KD: generator trained with CE + information-entropy
            (the DAFL losses), uniform ensemble, then distill.
* F-ADI   — data-free KD: DeepInversion-style direct noise optimization
            with CE + TV/L2 image priors, uniform ensemble, then distill.
* DENSE   — generator trained with CE + a batch-diversity term, uniform
            ensemble, then distill.

All reuse the distillation machinery of :mod:`repro.core.coboosting`; the
only differences are the synthesis objective and the fixed uniform weights,
which is exactly the contrast the paper draws (no co-boosting of data and
ensemble). Under ``driver="fused"`` every distillation sweep here (DENSE,
F-DAFL, F-ADI, FedDF) runs the Eq. 4 loss through the ``cfg.backend_for("loss")``
kernel path of :func:`repro.core.epoch.make_kd_loss` — forward AND backward
(the kernels carry fused Pallas VJPs; ``backend="ref"`` is the pure-jnp
oracle). ``driver="legacy"`` is a deprecated alias scheduled for removal.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.train import OFLConfig
from repro.core.buffer import buffer_as_lists, buffer_init
from repro.core.client_bank import make_ensemble
from repro.core.coboosting import (
    OFLState,
    _sample_zy,
    _warn_legacy_driver,
    init_synth_buffer,
    make_distill_step,
)
from repro.core.epoch import distill_schedule, make_adi_epoch, make_coboost_epoch, make_feddf_epoch
from repro.core.ensemble import ensemble_logits, uniform_weights
from repro.core.losses import ce_loss, ce_per_sample, entropy, kl_loss
from repro.optim import adam, constant_schedule
from repro.optim.optimizers import apply_updates
from repro.utils import get_logger, tree_stack

log = get_logger("baselines")


# ---------------------------------------------------------------------------
# FedAvg


def fedavg(client_params: List[Any], sizes: Optional[Sequence[int]] = None) -> Any:
    """Data-amount-weighted parameter average (homogeneous archs only)."""
    n = len(client_params)
    ws = np.full((n,), 1.0 / n) if sizes is None else np.asarray(sizes, np.float64) / np.sum(sizes)
    stacked = tree_stack(client_params)
    w = jnp.asarray(ws, jnp.float32)

    def avg(leaf):
        return jnp.tensordot(w, leaf.astype(jnp.float32), axes=1).astype(leaf.dtype)

    return jax.tree_util.tree_map(avg, stacked)


# ---------------------------------------------------------------------------
# generator objectives for the data-free baselines


def _dafl_loss(ens, y, x):
    """DAFL: one-hot CE + information entropy (encourage class balance)."""
    return ce_loss(ens, y) - 5.0 * entropy(jnp.mean(ens, axis=0, keepdims=True))


def _dense_loss(ens, y, x):
    """DENSE: CE + batch diversity (push samples apart in pixel space)."""
    b = x.shape[0]
    flat = x.reshape(b, -1)
    d2 = jnp.sum(jnp.square(flat[:, None] - flat[None, :]), axis=-1)
    div = -jnp.mean(d2) / flat.shape[-1]
    return ce_loss(ens, y) + 0.1 * div


def _tv_l2(x):
    tv = jnp.mean(jnp.abs(x[:, 1:] - x[:, :-1])) + jnp.mean(jnp.abs(x[:, :, 1:] - x[:, :, :-1]))
    return tv + 1e-3 * jnp.mean(jnp.square(x))


GEN_OBJECTIVES: Dict[str, Callable] = {
    "f_dafl": _dafl_loss,
    "dense": _dense_loss,
}


def run_generator_baseline(
    method: str,
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
    driver: str = "fused",
) -> OFLState:
    """F-DAFL / DENSE: two-stage synth→distill with a fixed uniform ensemble.
    The fused driver donates the caller's server/gen params — invalidated
    after epoch 0; copy first if reused."""
    objective = GEN_OBJECTIVES[method]
    n = len(client_applies)
    impl = cfg.ensemble_impl if driver == "fused" else "looped"
    logits_all_fn, client_params = make_ensemble(
        client_applies, client_params, impl=impl, scan_chunk=cfg.ensemble_scan_chunk
    )
    w = uniform_weights(n)

    if driver == "fused":
        epoch_step, gen_opt, srv_opt = make_coboost_epoch(
            logits_all_fn, server_apply, gen_apply, cfg, n, num_classes,
            gen_objective=objective, use_ee=False, distill_dhs=False,
        )
        gen_opt_state = gen_opt.init(gen_params)
        srv_opt_state = srv_opt.init(server_params)
        buf = init_synth_buffer(gen_apply, gen_params, cfg)
        state = OFLState(server_params, gen_params, w, [], [], [])
        srv_steps = jnp.zeros((), jnp.int32)
        for epoch in range(cfg.epochs):
            slot_order, n_valid = distill_schedule(epoch, cfg.buffer_batches)
            (
                state.server_params, srv_opt_state, state.gen_params, gen_opt_state,
                w, buf, key, srv_steps, gloss, dmean,
            ) = epoch_step(
                state.server_params, srv_opt_state, state.gen_params, gen_opt_state,
                w, buf, key, srv_steps, slot_order, n_valid, client_params,
            )
            state.weights = w
            state.dispatch_count += 1
            if eval_fn is not None and ((epoch + 1) % eval_every == 0 or epoch == cfg.epochs - 1):
                metrics = eval_fn(state.server_params, w)
                metrics.update(epoch=epoch, gen_loss=float(gloss), distill_loss=float(dmean))
                state.history.append(metrics)
                log.info("[%s] epoch %d %s", method, epoch, {k: round(v, 4) for k, v in metrics.items() if isinstance(v, float)})
        state.buffer = buf
        state.buffer_x, state.buffer_y = buffer_as_lists(buf)
        return state
    if driver != "legacy":
        raise ValueError(f"unknown driver {driver!r}")
    _warn_legacy_driver()

    gen_opt = adam(constant_schedule(cfg.gen_lr))

    def gen_loss_fn(gp, z, y, cp):
        x = gen_apply(gp, z, y)
        ens = ensemble_logits(logits_all_fn(cp, x), w)
        return objective(ens, y, x)

    @jax.jit
    def gen_phase(gp, opt_state, z, y, cp):
        def body(i, carry):
            gp, st = carry
            loss, grads = jax.value_and_grad(gen_loss_fn)(gp, z, y, cp)
            updates, st = gen_opt.update(grads, st, gp, i)
            return apply_updates(gp, updates), st

        gp, opt_state = jax.lax.fori_loop(0, cfg.gen_iters, body, (gp, opt_state))
        return gp, opt_state, gen_loss_fn(gp, z, y, cp)

    no_dhs_cfg = dataclasses.replace(cfg, use_dhs=False)
    distill_step, srv_opt = make_distill_step(logits_all_fn, server_apply, no_dhs_cfg)

    gen_opt_state = gen_opt.init(gen_params)
    srv_opt_state = srv_opt.init(server_params)
    state = OFLState(server_params, gen_params, w, [], [], [])
    step_idx = 0
    for epoch in range(cfg.epochs):
        key, k1, k3 = jax.random.split(key, 3)
        z, y = _sample_zy(k1, cfg.batch_size, cfg.latent_dim, num_classes)
        state.gen_params, gen_opt_state, gloss = gen_phase(
            state.gen_params, gen_opt_state, z, y, client_params
        )
        state.buffer_x.append(gen_apply(state.gen_params, z, y))
        state.buffer_y.append(y)
        if len(state.buffer_x) > cfg.buffer_batches:
            state.buffer_x.pop(0)
            state.buffer_y.pop(0)
        dlosses = []
        for bi in np.random.RandomState(epoch).permutation(len(state.buffer_x)):
            k3, kb = jax.random.split(k3)
            state.server_params, srv_opt_state, dl = distill_step(
                state.server_params,
                srv_opt_state,
                state.buffer_x[bi],
                kb,
                client_params,
                w,
                jnp.asarray(step_idx, jnp.int32),
            )
            step_idx += 1
            dlosses.append(dl)  # device scalar — no per-batch host sync
        if eval_fn is not None and ((epoch + 1) % eval_every == 0 or epoch == cfg.epochs - 1):
            metrics = eval_fn(state.server_params, w)
            metrics.update(
                epoch=epoch, gen_loss=float(gloss),
                distill_loss=float(np.mean(jax.device_get(dlosses))),
            )
            state.history.append(metrics)
            log.info("[%s] epoch %d %s", method, epoch, {k: round(v, 4) for k, v in metrics.items() if isinstance(v, float)})
    return state


def run_adi_baseline(
    client_applies: List[Callable],
    client_params: List[Any],
    server_apply: Callable,
    server_params: Any,
    image_shape: Tuple[int, int, int],
    cfg: OFLConfig,
    num_classes: int,
    key: jax.Array,
    eval_fn: Optional[Callable] = None,
    eval_every: int = 50,
    driver: str = "fused",
) -> OFLState:
    """F-ADI: optimize pixel batches directly (DeepInversion without BN
    statistics — our clients are GroupNorm, so only image priors apply)."""
    n = len(client_applies)
    impl = cfg.ensemble_impl if driver == "fused" else "looped"
    logits_all_fn, client_params = make_ensemble(
        client_applies, client_params, impl=impl, scan_chunk=cfg.ensemble_scan_chunk
    )
    w = uniform_weights(n)
    opt = adam(constant_schedule(0.05))

    def inv_loss(x, y, cp):
        ens = ensemble_logits(logits_all_fn(cp, x), w)
        return ce_loss(ens, y) + 2.5e-2 * _tv_l2(x)

    if driver == "fused":
        epoch_step, srv_opt = make_adi_epoch(
            logits_all_fn, server_apply, image_shape, cfg, num_classes, inv_loss
        )
        srv_opt_state = srv_opt.init(server_params)
        buf = buffer_init(cfg.buffer_batches, (cfg.batch_size, *image_shape))
        state = OFLState(server_params, None, w, [], [], [])
        srv_steps = jnp.zeros((), jnp.int32)
        for epoch in range(cfg.epochs):
            slot_order, n_valid = distill_schedule(epoch, cfg.buffer_batches)
            state.server_params, srv_opt_state, buf, key, srv_steps, _ = epoch_step(
                state.server_params, srv_opt_state, w, buf, key, srv_steps,
                slot_order, n_valid, client_params,
            )
            state.dispatch_count += 1
            if eval_fn is not None and ((epoch + 1) % eval_every == 0 or epoch == cfg.epochs - 1):
                metrics = eval_fn(state.server_params, w)
                metrics["epoch"] = epoch
                state.history.append(metrics)
                log.info("[f_adi] epoch %d %s", epoch, {k: round(v, 4) for k, v in metrics.items() if isinstance(v, float)})
        state.buffer = buf
        state.buffer_x, state.buffer_y = buffer_as_lists(buf)
        return state
    if driver != "legacy":
        raise ValueError(f"unknown driver {driver!r}")
    _warn_legacy_driver()

    @jax.jit
    def synth_phase(x, y, cp):
        st = opt.init(x)

        def body(i, carry):
            x, st = carry
            loss, g = jax.value_and_grad(inv_loss)(x, y, cp)
            updates, st = opt.update(g, st, x, i)
            return apply_updates(x, updates), st

        x, _ = jax.lax.fori_loop(0, cfg.gen_iters, body, (x, st))
        return jnp.clip(x, -1.0, 1.0)

    distill_step, srv_opt = make_distill_step(
        logits_all_fn, server_apply, dataclasses.replace(cfg, use_dhs=False)
    )
    srv_opt_state = srv_opt.init(server_params)
    state = OFLState(server_params, None, w, [], [], [])
    step_idx = 0
    for epoch in range(cfg.epochs):
        key, k1, k2, k3 = jax.random.split(key, 4)
        y = jax.random.randint(k1, (cfg.batch_size,), 0, num_classes)
        x0 = jax.random.normal(k2, (cfg.batch_size, *image_shape)) * 0.5
        x = synth_phase(x0, y, client_params)
        state.buffer_x.append(x)
        state.buffer_y.append(y)
        if len(state.buffer_x) > cfg.buffer_batches:
            state.buffer_x.pop(0)
            state.buffer_y.pop(0)
        for bi in np.random.RandomState(epoch).permutation(len(state.buffer_x)):
            k3, kb = jax.random.split(k3)
            state.server_params, srv_opt_state, dl = distill_step(
                state.server_params, srv_opt_state, state.buffer_x[bi], kb, client_params, w,
                jnp.asarray(step_idx, jnp.int32),
            )
            step_idx += 1
        if eval_fn is not None and ((epoch + 1) % eval_every == 0 or epoch == cfg.epochs - 1):
            metrics = eval_fn(state.server_params, w)
            metrics["epoch"] = epoch
            state.history.append(metrics)
            log.info("[f_adi] epoch %d %s", epoch, {k: round(v, 4) for k, v in metrics.items() if isinstance(v, float)})
    return state


def run_feddf(
    client_applies: List[Callable],
    client_params: List[Any],
    server_apply: Callable,
    server_params: Any,
    val_x: jax.Array,
    cfg: OFLConfig,
    key: jax.Array,
    eval_fn: Optional[Callable] = None,
    eval_every: int = 50,
    driver: str = "fused",
) -> OFLState:
    """FedDF: distill the uniform ensemble on real validation data (the
    paper marks this baseline as impractical — it needs data)."""
    n = len(client_applies)
    impl = cfg.ensemble_impl if driver == "fused" else "looped"
    logits_all_fn, client_params = make_ensemble(
        client_applies, client_params, impl=impl, scan_chunk=cfg.ensemble_scan_chunk
    )
    w = uniform_weights(n)
    nb = val_x.shape[0] // cfg.batch_size

    if driver == "fused":
        epoch_step, srv_opt = make_feddf_epoch(logits_all_fn, server_apply, cfg)
        srv_opt_state = srv_opt.init(server_params)
        val_batches = val_x[: nb * cfg.batch_size].reshape(nb, cfg.batch_size, *val_x.shape[1:])
        state = OFLState(server_params, None, w, [], [], [])
        srv_steps = jnp.zeros((), jnp.int32)
        for epoch in range(cfg.epochs):
            order = jnp.asarray(np.random.RandomState(epoch).permutation(nb).astype(np.int32))
            state.server_params, srv_opt_state, key, srv_steps, _ = epoch_step(
                state.server_params, srv_opt_state, key, srv_steps, order, val_batches, w, client_params
            )
            state.dispatch_count += 1
            if eval_fn is not None and ((epoch + 1) % eval_every == 0 or epoch == cfg.epochs - 1):
                metrics = eval_fn(state.server_params, w)
                metrics["epoch"] = epoch
                state.history.append(metrics)
                log.info("[feddf] epoch %d %s", epoch, {k: round(v, 4) for k, v in metrics.items() if isinstance(v, float)})
        return state
    if driver != "legacy":
        raise ValueError(f"unknown driver {driver!r}")
    _warn_legacy_driver()

    distill_step, srv_opt = make_distill_step(
        logits_all_fn, server_apply, dataclasses.replace(cfg, use_dhs=False)
    )
    srv_opt_state = srv_opt.init(server_params)
    state = OFLState(server_params, None, w, [], [], [])
    step_idx = 0
    for epoch in range(cfg.epochs):
        key, k3 = jax.random.split(key)
        order = np.random.RandomState(epoch).permutation(nb)
        for bi in order:
            k3, kb = jax.random.split(k3)
            xb = val_x[bi * cfg.batch_size : (bi + 1) * cfg.batch_size]
            state.server_params, srv_opt_state, dl = distill_step(
                state.server_params, srv_opt_state, xb, kb, client_params, w,
                jnp.asarray(step_idx, jnp.int32),
            )
            step_idx += 1
        if eval_fn is not None and ((epoch + 1) % eval_every == 0 or epoch == cfg.epochs - 1):
            metrics = eval_fn(state.server_params, w)
            metrics["epoch"] = epoch
            state.history.append(metrics)
            log.info("[feddf] epoch %d %s", epoch, {k: round(v, 4) for k, v in metrics.items() if isinstance(v, float)})
    return state
