"""Fused one-epoch OFL programs: O(1) dispatches per global epoch.

The legacy drivers (``run_coboosting`` and the shared loops in
:mod:`repro.core.baselines` — both deprecated aliases now) dispatch one
jitted ``distill_step`` per replay batch and ``float()`` the scalar loss
each iteration — O(buffer) dispatches plus O(buffer) host syncs per epoch. Here the whole epoch (generator phase →
buffer append → EE step → distillation sweep) is ONE jitted program per
method: the synthetic buffer is the device-resident ring of
:mod:`repro.core.buffer` and the distillation sweep is a ``lax.scan`` over
physical buffer slots, with masked validity while the ring is warming up.
Losses accumulate on device; the host converts them only at eval boundaries.

Parity contract with the legacy loops (pinned by tests/test_buffer_epoch.py):

  * identical PRNG split structure — the per-epoch key splits and the
    per-batch ``k3, kb = split(k3)`` chain happen in the same order, so the
    same stream drives generator noise, DHS directions and labels;
  * identical batch visit order — the host replays the legacy
    ``np.random.RandomState(epoch).permutation(len(buffer))`` and maps
    logical indices to ring slots (:func:`distill_schedule`); padding slots
    are appended AFTER the valid ones so the split chain stays aligned;
  * identical optimizer-step indexing — the server step counter advances
    only on valid (unmasked) scan iterations.

Server/optimizer/buffer state is donated back to the program on every
backend, so the CPU tests alias buffers exactly as the chip does.

The Eq. 4 / Eq. 6 losses inside these programs route through the
differentiable fused Pallas kernels (:mod:`repro.kernels`) according to
``cfg.backend_for("loss")`` — the backend covers BOTH passes: every
``jax.grad`` these epoch programs take through ``ensemble_kl`` / ``ghm_ce``
runs the fused Pallas backward kernels under "pallas"/"pallas-interpret",
and plain autodiff of the jnp oracle under "ref". "auto" runs the compiled
kernels on TPU and the pure-jnp composition elsewhere (see
:mod:`repro.kernels.dispatch`), so the CPU parity contract with the legacy
loops below is preserved bit-for-bit; the end-to-end grad contract is
ref-vs-interpret parity per method (tests/grad_harness.py).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.train import OFLConfig
from repro.core.buffer import ReplayBuffer, buffer_append, buffer_get
from repro.core.ensemble import ensemble_logits
from repro.core.hard_samples import diversify
from repro.core.hardness import generator_loss
from repro.core.losses import kl_loss
from repro.core.weight_search import update_weights
from repro.kernels import ensemble_kl, ghm_ce
from repro.kernels.dispatch import resolve
from repro.optim import adam, constant_schedule, sgdm
from repro.optim.optimizers import apply_updates
from repro.utils.scope import scoped


def distill_schedule(epoch: int, capacity: int) -> Tuple[jax.Array, jax.Array]:
    """Host-side replica of the legacy per-epoch sweep schedule.

    After epoch ``epoch``'s append the ring holds ``min(epoch+1, capacity)``
    batches and ``ptr == (epoch+1) % capacity``; the legacy loop visits
    logical indices in ``np.random.RandomState(epoch).permutation(size)``
    order. Returns a fixed-shape ``(capacity,)`` slot order (valid slots
    first, zero padding after) plus the valid count — fixed shapes mean no
    recompilation across the warm-up epochs.
    """
    size = min(epoch + 1, capacity)
    ptr = (epoch + 1) % capacity
    perm = np.random.RandomState(epoch).permutation(size)
    order = np.zeros((capacity,), np.int32)
    order[:size] = (ptr - size + perm) % capacity
    return jnp.asarray(order), jnp.asarray(size, jnp.int32)


def _masked_update(valid, old, new):
    return jax.tree_util.tree_map(lambda a, b: jnp.where(valid, b, a), old, new)


def make_kd_loss(
    logits_all_fn: Callable,
    server_apply: Callable,
    temperature: float,
    kernel_backend: str = "auto",
):
    """Eq. 4: temperature-KL between the re-weighted ensemble and the server.

    ``kernel_backend`` (resolved once, at make time) routes the loss through
    the differentiable fused :func:`repro.kernels.ensemble_kl` kernel — the
    Pallas paths never materialize A_w in the forward pass — or through the
    legacy jnp composition (``"ref"``; the auto choice off-TPU). The server
    runs under the named scope ``ofl.server``."""
    backend = resolve("loss", kernel_backend)
    server_apply = scoped("ofl.server", server_apply)

    if backend == "ref":

        def loss_fn(server_params, x, client_params, w):
            ens = ensemble_logits(logits_all_fn(client_params, x), w)
            return kl_loss(ens, server_apply(server_params, x), temperature)

    else:

        def loss_fn(server_params, x, client_params, w):
            la = logits_all_fn(client_params, x)
            s_logits = server_apply(server_params, x)
            return jnp.mean(ensemble_kl(la, s_logits, w, temperature=temperature, backend=backend))

    return loss_fn


def make_distill_sweep(
    logits_all_fn: Callable,
    server_apply: Callable,
    srv_opt,
    cfg: OFLConfig,
    use_dhs: bool,
):
    """The fused replacement for the per-batch ``distill_step`` loop: one
    ``lax.scan`` over ring slots, masked while the buffer warms up."""
    loss_fn = make_kd_loss(logits_all_fn, server_apply, cfg.kd_temperature, cfg.backend_for("loss"))

    def sweep(server_params, srv_opt_state, buf, k3, w, client_params, slot_order, n_valid, srv_step0):
        def body(carry, xs):
            sp, st, k, step, dsum, dcnt = carry
            slot, pos = xs
            k, kb = jax.random.split(k)
            x, _ = buffer_get(buf, slot)
            if use_dhs:
                x = diversify(logits_all_fn, client_params, w, x, kb, cfg.epsilon)
            loss, grads = jax.value_and_grad(loss_fn)(sp, x, client_params, w)
            updates, st2 = srv_opt.update(grads, st, sp, step)
            sp2 = apply_updates(sp, updates)
            valid = pos < n_valid
            sp = _masked_update(valid, sp, sp2)
            st = _masked_update(valid, st, st2)
            dsum = dsum + jnp.where(valid, loss, 0.0)
            dcnt = dcnt + valid.astype(jnp.int32)
            step = step + valid.astype(jnp.int32)
            return (sp, st, k, step, dsum, dcnt), None

        cap = buf.capacity
        init = (
            server_params,
            srv_opt_state,
            k3,
            jnp.asarray(srv_step0, jnp.int32),
            jnp.zeros((), jnp.float32),
            jnp.zeros((), jnp.int32),
        )
        (sp, st, _, step, dsum, dcnt), _ = jax.lax.scan(
            body, init, (slot_order, jnp.arange(cap, dtype=jnp.int32))
        )
        return sp, st, step, dsum / jnp.maximum(dcnt, 1).astype(jnp.float32)

    return sweep


def _sample_zy(key, batch: int, latent: int, num_classes: int):
    kz, ky = jax.random.split(key)
    z = jax.random.normal(kz, (batch, latent))
    y = jax.random.randint(ky, (batch,), 0, num_classes)
    return z, y


def make_coboost_epoch(
    logits_all_fn: Callable,
    server_apply: Callable,
    gen_apply: Callable,
    cfg: OFLConfig,
    num_clients: int,
    num_classes: int,
    gen_objective: Optional[Callable] = None,
    use_ee: Optional[bool] = None,
    distill_dhs: Optional[bool] = None,
):
    """One fused Algorithm-1 epoch. With ``gen_objective`` set (a
    ``f(ens, y, x) -> loss``) and ``use_ee=False`` this is also the DENSE /
    F-DAFL epoch — the contrast the paper draws is exactly which generator
    objective runs and whether the ensemble weights move.

    Returns ``(epoch_step, gen_opt, srv_opt)``; ``epoch_step`` maps

        (server_params, srv_opt_state, gen_params, gen_opt_state, w, buf,
         key, srv_step0, slot_order, n_valid, client_params)
        -> (server_params, srv_opt_state, gen_params, gen_opt_state, w, buf,
            key', srv_steps, gloss, dmean)
    """
    # the networks' named scopes (the KD sweep's make_kd_loss names the
    # server there; the client bank names itself, DHS its own)
    server_net = scoped("ofl.server", server_apply)
    gen_apply = scoped("ofl.gen.net", gen_apply)
    gen_opt = adam(constant_schedule(cfg.gen_lr))
    srv_opt = sgdm(constant_schedule(cfg.server_lr), momentum=0.9)
    use_ee = cfg.use_ee if use_ee is None else use_ee
    distill_dhs = cfg.use_dhs if distill_dhs is None else distill_dhs
    mu = cfg.mu / num_clients
    # legacy run_coboosting splits 4 keys per epoch, the generator baselines 3;
    # any EE variant needs the 4th key so k2 never aliases the distill chain
    nsplit = 4 if (gen_objective is None or use_ee) else 3

    backend = resolve("loss", cfg.backend_for("loss"))

    def gen_loss_fn(gp, z, y, client_params, w, server_params):
        x = gen_apply(gp, z, y)
        la = logits_all_fn(client_params, x)
        if gen_objective is not None:
            return gen_objective(ensemble_logits(la, w), y, x)
        if backend == "ref":
            s_logits = server_net(server_params, x)
            return generator_loss(
                ensemble_logits(la, w),
                s_logits,
                y,
                beta=cfg.beta,
                use_ghs=cfg.use_ghs,
                use_adv=cfg.use_adv,
                kl_temperature=cfg.gen_kl_temperature,
            )
        # kernel path for Eq. 8: L_H via the fused GHM-CE (difficulty is
        # stop-gradiented, matching ghs_loss) + β·L_A via the fused KL, both
        # without materializing A_w in the forward pass
        loss = jnp.mean(
            ghm_ce(la, y, w, weighted=cfg.use_ghs, backend=backend, stop_difficulty_grad=True)
        )
        if cfg.use_adv:
            s_logits = server_net(server_params, x)
            loss = loss - cfg.beta * jnp.mean(
                ensemble_kl(la, s_logits, w, temperature=cfg.gen_kl_temperature, backend=backend)
            )
        return loss

    sweep = make_distill_sweep(logits_all_fn, server_apply, srv_opt, cfg, distill_dhs)

    def epoch_step(
        server_params, srv_opt_state, gen_params, gen_opt_state, w, buf,
        key, srv_step0, slot_order, n_valid, client_params,
    ):
        keys = jax.random.split(key, nsplit)
        key, k1, k3 = keys[0], keys[1], keys[-1]

        # jax.named_scope annotates the XLA ops of each Algorithm-1 phase —
        # zero host cost, but an --profile-dir device trace shows the phases
        # as named regions lining up with the host-side ofl.epoch span. The
        # networks' scopes (ofl.bank, ofl.gen.net, ofl.server, ofl.dhs) nest
        # inside them.
        # 1. generator phase (Algorithm 1 lines 5-9)
        with jax.named_scope("ofl.gen.boost"):
            z, y = _sample_zy(k1, cfg.batch_size, cfg.latent_dim, num_classes)

            def gbody(i, carry):
                gp, st = carry
                _, grads = jax.value_and_grad(gen_loss_fn)(gp, z, y, client_params, w, server_params)
                updates, st = gen_opt.update(grads, st, gp, i)
                return apply_updates(gp, updates), st

            gen_params, gen_opt_state = jax.lax.fori_loop(
                0, cfg.gen_iters, gbody, (gen_params, gen_opt_state)
            )
            gloss = gen_loss_fn(gen_params, z, y, client_params, w, server_params)
            x_new = gen_apply(gen_params, z, y)
            buf = buffer_append(buf, x_new, y)

        # 2-3. EE on the (diversified) fresh hard batch (lines 11-14). The
        # Eq. 11/12 CE-over-ensemble + w-cotangent runs inside the fused
        # ghm_ce(weighted=False) kernel on the Pallas backends.
        if use_ee:
            with jax.named_scope("ofl.ee.weight_search"):
                k2 = keys[2]
                xe = diversify(logits_all_fn, client_params, w, x_new, k2, cfg.epsilon) if cfg.use_dhs else x_new
                w = update_weights(w, logits_all_fn(client_params, xe), y, mu, backend=backend)

        # 4. server distillation over the replay ring (lines 16-18)
        with jax.named_scope("ofl.kd"):
            server_params, srv_opt_state, srv_steps, dmean = sweep(
                server_params, srv_opt_state, buf, k3, w, client_params, slot_order, n_valid, srv_step0
            )
        return (
            server_params, srv_opt_state, gen_params, gen_opt_state, w, buf,
            key, srv_steps, gloss, dmean,
        )

    return jax.jit(epoch_step, donate_argnums=(0, 1, 2, 3, 4, 5)), gen_opt, srv_opt


def make_adi_epoch(
    logits_all_fn: Callable,
    server_apply: Callable,
    image_shape: Tuple[int, int, int],
    cfg: OFLConfig,
    num_classes: int,
    inv_loss: Callable,
):
    """F-ADI fused epoch: direct pixel-batch optimization instead of a
    generator, then the same append + distillation sweep (no DHS)."""
    synth_opt = adam(constant_schedule(0.05))
    srv_opt = sgdm(constant_schedule(cfg.server_lr), momentum=0.9)
    sweep = make_distill_sweep(logits_all_fn, server_apply, srv_opt, cfg, use_dhs=False)

    def epoch_step(server_params, srv_opt_state, w, buf, key, srv_step0, slot_order, n_valid, client_params):
        key, k1, k2, k3 = jax.random.split(key, 4)
        y = jax.random.randint(k1, (cfg.batch_size,), 0, num_classes)
        x0 = jax.random.normal(k2, (cfg.batch_size, *image_shape)) * 0.5
        st0 = synth_opt.init(x0)

        def body(i, carry):
            x, st = carry
            _, g = jax.value_and_grad(inv_loss)(x, y, client_params)
            updates, st = synth_opt.update(g, st, x, i)
            return apply_updates(x, updates), st

        x, _ = jax.lax.fori_loop(0, cfg.gen_iters, body, (x0, st0))
        x = jnp.clip(x, -1.0, 1.0)
        buf = buffer_append(buf, x, y)
        server_params, srv_opt_state, srv_steps, dmean = sweep(
            server_params, srv_opt_state, buf, k3, w, client_params, slot_order, n_valid, srv_step0
        )
        return server_params, srv_opt_state, buf, key, srv_steps, dmean

    return jax.jit(epoch_step, donate_argnums=(0, 1, 3)), srv_opt


def make_feddf_epoch(logits_all_fn: Callable, server_apply: Callable, cfg: OFLConfig):
    """FedDF fused epoch: one scan over the (pre-stacked, fixed-size) real
    validation batches in a host-supplied permutation — no buffer, no mask."""
    srv_opt = sgdm(constant_schedule(cfg.server_lr), momentum=0.9)
    loss_fn = make_kd_loss(logits_all_fn, server_apply, cfg.kd_temperature, cfg.backend_for("loss"))

    def epoch_step(server_params, srv_opt_state, key, srv_step0, order, val_batches, w, client_params):
        key, k3 = jax.random.split(key)

        def body(carry, bi):
            sp, st, k, step = carry
            k, kb = jax.random.split(k)
            xb = jax.lax.dynamic_index_in_dim(val_batches, bi, 0, keepdims=False)
            loss, grads = jax.value_and_grad(loss_fn)(sp, xb, client_params, w)
            updates, st = srv_opt.update(grads, st, sp, step)
            return (apply_updates(sp, updates), st, k, step + 1), loss

        init = (server_params, srv_opt_state, k3, jnp.asarray(srv_step0, jnp.int32))
        (sp, st, _, step), losses = jax.lax.scan(body, init, order)
        return sp, st, key, step, jnp.mean(losses)

    return jax.jit(epoch_step, donate_argnums=(0, 1)), srv_opt
