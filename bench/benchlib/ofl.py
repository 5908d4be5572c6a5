"""The OFL cell kind: Co-Boosting epochs of the program's fused epoch.

Set-up builds one object, the program's compiled epoch
(``repro.core.epoch.make_coboost_epoch``, the program ``run_coboosting``
dispatches once per epoch) with its state, drives it through the first
``check_epochs`` epochs (a key of the traffic file) and keeps what the
comparison needs; the window then runs the same object on, epoch after
epoch, for ``--seconds``. The host loop is ``run_coboosting``'s: the ring's
slot order from ``distill_schedule``, then one dispatch.
"""
from __future__ import annotations

import math
import time
from functools import partial

import numpy as np

from . import common, compare


def _host(jax, tree):
    return jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), jax.device_get(tree))


def ofl_config(cfg: dict, backend: str):
    from repro.config.train import OFLConfig
    from repro.kernels import BackendPolicy

    return OFLConfig(
        num_clients=cfg["clients"], epochs=10**9, local_epochs=0,
        gen_iters=cfg["gen_iters"], gen_lr=cfg["gen_lr"], server_lr=cfg["server_lr"],
        batch_size=cfg["batch_size"], latent_dim=cfg["generator"]["latent_dim"],
        kd_temperature=cfg["kd_temperature"], gen_kl_temperature=cfg["gen_kl_temperature"],
        beta=cfg["beta"], epsilon=cfg["epsilon"], mu=cfg["mu"], buffer_batches=cfg["buffer_batches"],
        use_ghs=cfg["use_ghs"], use_dhs=cfg["use_dhs"], use_ee=cfg["use_ee"], use_adv=cfg["use_adv"],
        backend=BackendPolicy(default=backend),
    )


class Program:
    """The program's epoch for one configuration, reusable across seeds."""

    def __init__(self, jax, cfg: dict, backend: str):
        from repro.core.client_bank import make_ensemble
        from repro.core.epoch import make_coboost_epoch
        from repro.models.cnn import cnn_apply
        from repro.models.generator import image_generator

        self.jax, self.cfg = jax, cfg
        self.ocfg = ofl_config(cfg, backend)
        self.gen_apply = partial(image_generator, out_shape=tuple(cfg["image"]), base=cfg["generator"]["base"])
        self.client_apply = partial(cnn_apply, cfg["client_arch"])
        self.server_apply = partial(cnn_apply, cfg["server_arch"])
        self._make_ensemble = make_ensemble
        self._make_epoch = make_coboost_epoch
        self.epoch_step = None

    def start(self, weights) -> None:
        """Hand the program the harness's weights (it donates them)."""
        from repro.core.coboosting import init_synth_buffer
        from repro.core.ensemble import uniform_weights

        jnp = self.jax.numpy
        clients, server, gen = weights
        k = len(clients)
        logits_all_fn, self.client_params = self._make_ensemble(
            [self.client_apply] * k, list(clients), impl=self.ocfg.ensemble_impl,
            scan_chunk=self.ocfg.ensemble_scan_chunk,
        )
        if self.epoch_step is None:
            self.epoch_step, self.gen_opt, self.srv_opt = self._make_epoch(
                logits_all_fn, self.server_apply, self.gen_apply, self.ocfg, k, self.cfg["classes"]
            )
        self.state = [
            server, self.srv_opt.init(server), gen, self.gen_opt.init(gen), uniform_weights(k),
            init_synth_buffer(self.gen_apply, gen, self.ocfg), None, jnp.zeros((), jnp.int32),
        ]
        self.epoch = 0

    def step(self, key=None):
        """Dispatch one epoch; returns its (gen_loss, kd_mean) device scalars."""
        from repro.core.epoch import distill_schedule

        s = self.state
        if key is not None:
            s[6] = key
        slot_order, n_valid = distill_schedule(self.epoch, self.ocfg.buffer_batches)
        (s[0], s[1], s[2], s[3], s[4], s[5], s[6], s[7], gloss, dmean) = self.epoch_step(
            s[0], s[1], s[2], s[3], s[4], s[5], s[6], s[7], slot_order, n_valid, self.client_params
        )
        self.epoch += 1
        return gloss, dmean

    def checked_epochs(self, key, n: int) -> dict:
        """The first ``n`` epochs, with the readings the reference checks:
        the losses of each, the server momentum after the first KD step (the
        first gradient; epoch 0 makes one), the generator and its Adam
        momentum after epoch 0, and the server after the ``n``."""
        out = {"gen_loss": [], "kd_loss": [], "first_grad": None}
        for e in range(n):
            gloss, dmean = self.step(key if e == 0 else None)
            if e == 0:
                out["first_grad"] = _host(self.jax, self.state[1]["m"])
                out["gen"] = _host(self.jax, self.state[2])
                out["gen_m"] = _host(self.jax, self.state[3]["m"])
            out["gen_loss"].append(float(gloss))
            out["kd_loss"].append(float(dmean))
        out["server"] = _host(self.jax, self.state[0])
        return out

    def hlo_text(self) -> str:
        """The compiled epoch program's HLO, as the window ran it."""
        from repro.core.epoch import distill_schedule

        s = self.state
        slot_order, n_valid = distill_schedule(self.epoch, self.ocfg.buffer_batches)
        return self.epoch_step.lower(*s, slot_order, n_valid, self.client_params).compile().as_text()

    def free(self) -> None:
        self.state = None
        self.client_params = None


def readings(got: dict, ref: dict, server0, gen0) -> dict:
    """Every number the comparison can hold ``got`` (the program, or what is
    put in its place) to against the reference ``ref``:

    - ``loss_gap``: each checked epoch's generator and KD loss, the worst
      relative gap; ``loss_gap_epoch0`` the same over epoch 0 alone;
    - ``first_grad_gap``: the first KD gradient, by the worst leaf
      (``first_grad_median``: the median leaf);
    - ``gen_change_gap``: the generator's change over epoch 0's T_G Adam
      steps, by the worst leaf (``gen_change_median``);
    - ``update_gap``: the server's change over the checked epochs, by the
      worst leaf (``update_median``).

    A workload's ``checks`` names the ones a run compares, with limits."""
    loss = [max(compare.rel_gap(got["gen_loss"][e], ref["gen_loss"][e]),
                compare.rel_gap(got["kd_loss"][e], ref["kd_loss"][e])) for e in range(len(ref["gen_loss"]))]
    sub = compare.tree_sub
    grad = compare.leaf_gaps(got["first_grad"], ref["first_grad"], ref["first_grad"])
    gen = compare.leaf_gaps(sub(got["gen"], gen0), sub(ref["gen"], gen0), ref["gen_m"])
    upd = compare.leaf_gaps(sub(got["server"], server0), sub(ref["server"], server0), ref["first_grad"])
    return {
        "loss_gap": max(loss), "loss_gap_epoch0": loss[0],
        "first_grad_gap": compare.worst(grad), "first_grad_median": compare.median(grad),
        "gen_change_gap": compare.worst(gen), "gen_change_median": compare.median(gen),
        "update_gap": compare.worst(upd), "update_median": compare.median(upd),
    }


def readings_checks(got: dict, ref: dict, server0, gen0, limits: dict) -> dict:
    """The numbers the workload's ``checks`` name, each beside its limit."""
    values = readings(got, ref, server0, gen0)
    return {name: common.check(values[name], limit) for name, limit in limits.items()}


def run_cell(ctx: dict) -> dict:
    """One run of an OFL cell; ``ctx`` carries jax, the cell's files, seed,
    seconds, trace flag, devices and the start time."""
    jax = ctx["jax"]
    cfg, work = ctx["config"], ctx["workload"]
    ref = common.reference_of(cfg["name"])
    key = common.seed_key(jax, ctx["seed"])
    wkey, rkey = jax.random.split(key)

    n_check = ctx["traffic"]["check_epochs"]
    prog = Program(jax, cfg, cfg["backend"])
    weights = ref.make_weights(wkey, cfg)
    server0, gen0 = ref.to_host(weights[1]), ref.to_host(weights[2])
    prog.start(weights)
    del weights
    got = prog.checked_epochs(rkey, n_check)
    setup_s = time.perf_counter() - ctx["t_start"]

    clock = ctx["compile_clock"]
    c0 = clock.snapshot()
    from . import trace as tr

    with tr.profiled(jax, ctx["trace"]) as prof:
        with jax.profiler.TraceAnnotation("bench.window"):
            t0 = time.perf_counter()
            n, prev, step_end = 0, None, t0
            longest = (0.0, 0.0)  # the longest epoch the host saw, and when it ended
            while True:
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    _, dmean = prog.step()
                n += 1
                if prev is not None:
                    with jax.profiler.TraceAnnotation("bench.wait"):
                        prev.block_until_ready()
                prev = dmean
                now = time.perf_counter()
                longest = max(longest, (now - step_end, now - t0))
                step_end = now
                if now - t0 >= ctx["seconds"]:
                    break
            with jax.profiler.TraceAnnotation("bench.wait"):
                last = float(prev)
            t1 = time.perf_counter()
    c1 = clock.snapshot()
    mem = common.peak_memory(ctx["devices"])
    if ctx["trace"]:
        tr.attach_scopes(prof.trace, tr.hlo_scopes(prog.hlo_text()), "epoch_step")
    prog.free()

    r = ref.run(ref.make_weights(wkey, cfg), rkey, cfg, n_check, precision=work.get("reference_precision"))
    checks = readings_checks(got, r, server0, gen0, work["checks"])
    window = t1 - t0
    metrics = {}
    if ctx["trace"]:
        ctx.update(trace_data=prof.trace, epochs_traced=n, epoch_indices=range(n_check, n_check + n))
    else:
        metrics["ofl_epoch_ms"] = common.metric(1000.0 * window / n, "ms")
        metrics["setup_s"] = common.metric(setup_s, "s")
    return {
        "attempted": n,
        "failed": 0 if math.isfinite(last) else n,
        "metrics": metrics,
        "memory_peak_bytes": mem,
        "checks": checks,
        "notes": {"compiles_in_window": c1[0] - c0[0], "window_s": window, "epochs": n,
                  "longest_host_step_s": longest[0], "longest_host_step_at_s": longest[1],
                  "ref_gen_loss": r["gen_loss"], "prog_gen_loss": got["gen_loss"]},
    }
