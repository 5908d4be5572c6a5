#!/usr/bin/env python3
"""Drive the system's main paths once on a TPU and check what comes out.

    python chip_smoke.py               # one chip: OFL Co-Boosting + smollm-135m serving
    python chip_smoke.py --four-chips  # four chips: 4 one-chip fleet replicas vs one engine

Default phases (one chip, compiled Pallas kernels throughout):

* ``ofl``   — Co-Boosting through ``repro.launch.ofl.run_method`` on a
  ``build_market_grouped`` market at the paper's CIFAR-10 widths (K=10,
  Dir(0.1), 32x32x3, 10 classes, b=128, latent 100, T_G=30, cnn5 server).
  Only the epoch counts are cut. Then one epoch from the same state under
  ``pallas`` and under ``ref``; their server parameters must agree.
* ``serve`` — smollm-135m at its published widths (bf16 activations) through
  the launcher's ``build_fleet`` + ``ContinuousScheduler``, paged KV. Every
  request must complete its full budget; prefill logits and one cached
  decode step's logits must agree with the ``ref`` backend.

``--four-chips`` runs only the fleet check: four one-chip replicas behind
``FleetRouter`` against one colocated engine on the same request stream.
Greedy tokens must be bitwise equal, and each replica's params and KV pool
must live on its own device.

Everything runs in this one process. With no TPU, or without the ``repro``
package beside this file, it exits non-zero after a one-line reason. The
last line of stdout is ``{"ok": true, "device": {...}}``; every other result
goes on earlier lines. Compile and run seconds are set-up timings, not
performance numbers.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from functools import partial
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

SEED = 0
REF = "ref"  # the jnp reference backend every kernel result is checked against

# --- OFL: OFLConfig's paper values (CIFAR-10), epoch counts cut ---------------
OFL_CLASSES = 10
OFL_IMAGE = (32, 32, 3)
OFL_TRAIN_PER_CLASS = 5000  # CIFAR-10 train split
OFL_TEST_PER_CLASS = 1000  # CIFAR-10 test split
OFL_EPOCHS = 2  # paper T = 500
OFL_LOCAL_EPOCHS = 2  # paper local training = 300 epochs
# The fused losses on one real batch (trained-client logits of test images,
# random-init server logits), kernel vs ref: ||delta|| / ||ref|| of the loss
# and of every cotangent. Both sides are f32 with the same formulas; they
# differ in reduction order and in exp/log rounding, a few ulps (~1e-7) per
# element. 1e-4 is far above that and far below any wrong term.
OFL_LOSS_TOL = 1e-4
# One Co-Boosting epoch from the same state, kernel vs ref:
# ||theta_kernel - theta_ref|| / ||theta_ref - theta_0||, the gap relative to
# the epoch's own server update. The two runs differ only by the rounding
# above, but the epoch amplifies it: Adam's first generator steps and the
# DHS (Eq. 10) and EE (Eq. 12) sign steps turn ulp-level differences in
# near-zero gradient entries into whole steps, and bf16 matmul passes round
# the perturbed inputs differently. A run on a wrong trajectory (a wrong
# kernel gradient) sits near sqrt(2), the ratio for two uncorrelated updates
# of equal size; 0.5 separates the two.
OFL_PARAM_TOL = 0.5

# --- serving: smollm-135m, published widths ------------------------------------
SERVE_ARCH = "smollm-135m"
SERVE_REQUESTS = 8
SERVE_PROMPT = 256
SERVE_GEN = 32
SERVE_SLOTS = 8
SERVE_PAGE = 16
# Logit gap, kernel vs ref, as max|delta| over the std of the ref logits.
# Activations are bf16 (8 mantissa bits, ~4e-3 relative per rounding); the
# kernels accumulate attention in f32 and round once, the ref rounds at
# other points, and those differences pass through 30 residual layers.
# Random-init logits are nearly uniform, so the gap is scaled by their
# spread, not their size. 0.25 of a std is far above that rounding and far
# below a wrong mask or a wrong page, which moves logits by a full std.
SERVE_LOGIT_TOL = 0.25


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=float), flush=True)


class CompileClock:
    """Sums XLA backend-compile seconds and persistent-cache hits, as JAX's
    monitoring events report them, between ``start`` and ``read``."""

    def __init__(self, jax):
        from jax._src import dispatch

        self.secs = 0.0
        self.hits = 0
        event = dispatch.BACKEND_COMPILE_EVENT

        def on_duration(name, secs, **_):
            if name == event:
                self.secs += secs

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def start(self):
        self._t0, self._c0, self._h0 = time.perf_counter(), self.secs, self.hits

    def read(self) -> dict:
        wall = time.perf_counter() - self._t0
        comp = self.secs - self._c0
        return {
            "wall_s": wall,
            "compile_s": comp,
            "run_s": max(wall - comp, 0.0),
            "cache_hits": self.hits - self._h0,
        }


def _finite_tree(jax, tree) -> bool:
    import numpy as np

    return all(bool(np.isfinite(np.asarray(x)).all()) for x in jax.tree_util.tree_leaves(tree))


def _tree_norm(jax, tree) -> float:
    import numpy as np

    return float(np.sqrt(sum(float(np.sum(np.square(np.asarray(x, np.float64))))
                             for x in jax.tree_util.tree_leaves(tree))))


def ofl_phase(jax, clock, backend: str, *, per_class=OFL_TRAIN_PER_CLASS,
              test_per_class=OFL_TEST_PER_CLASS, gen_iters=None, batch=None) -> dict:
    """Co-Boosting through the launcher's ``run_method``, then the one-epoch
    kernel-vs-ref parity check. Keyword sizes exist only for rehearsals at a
    small scale; the chip run uses the paper values."""
    from repro.config.train import OFLConfig
    from repro.core import default_image_setup, run_coboosting
    from repro.data import make_synth_images
    from repro.fed import build_market_grouped
    from repro.kernels import BackendPolicy
    from repro.launch.ofl import run_method
    from repro.models.cnn import cnn_apply, init_cnn

    paper = OFLConfig()
    cfg = OFLConfig(
        num_clients=10, partition="dirichlet", alpha=0.1,
        epochs=OFL_EPOCHS, local_epochs=OFL_LOCAL_EPOCHS,
        backend=BackendPolicy(default=backend), seed=SEED,
    )
    if gen_iters is not None:
        cfg = dataclasses.replace(cfg, gen_iters=gen_iters)
    if batch is not None:
        cfg = dataclasses.replace(cfg, batch_size=batch, local_batch_size=batch)
    emit("ofl.config", backend=backend, clients=cfg.num_clients, alpha=cfg.alpha,
         image=list(OFL_IMAGE), classes=OFL_CLASSES, batch=cfg.batch_size,
         latent=cfg.latent_dim, gen_iters=cfg.gen_iters, server="cnn5",
         train_per_class=per_class,
         cuts={"epochs": [paper.epochs, cfg.epochs],
               "local_epochs": [paper.local_epochs, cfg.local_epochs]})

    clock.start()
    x, y = make_synth_images(SEED, OFL_CLASSES, per_class, OFL_IMAGE)
    test_x, test_y = make_synth_images(SEED + 1, OFL_CLASSES, test_per_class, OFL_IMAGE)
    emit("ofl.data", images=int(len(y)), test_images=int(len(test_y)), **clock.read())

    clock.start()
    bank, bank_params, sizes, _ = build_market_grouped(SEED, x, y, cfg, OFL_CLASSES)
    params = bank.unstack_params(bank_params)
    applies = [bank.client_apply(k) for k in range(bank.num_clients)]
    jax.block_until_ready(params)
    if not _finite_tree(jax, params):
        raise RuntimeError("ofl.market: non-finite client parameters")
    emit("ofl.market", shard_sizes=sizes, **clock.read())

    clock.start()
    result = run_method(
        "coboosting", cfg, OFL_CLASSES, OFL_IMAGE, applies, params, sizes,
        x, test_x, test_y, "cnn5", SEED, eval_every=cfg.epochs,
    )
    timing = clock.read()
    vals = {k: float(v) for k, v in result.items() if isinstance(v, (int, float))}
    if not all(math.isfinite(v) for v in vals.values()):
        raise RuntimeError(f"ofl.coboosting: non-finite result {vals}")
    for k in ("server_acc", "ensemble_acc"):
        if not 0.0 <= vals[k] <= 1.0:
            raise RuntimeError(f"ofl.coboosting: {k}={vals[k]} outside [0, 1]")
    emit("ofl.coboosting", epochs=cfg.epochs, **vals, **timing)

    _loss_parity(jax, clock, bank, bank_params, test_x, test_y, cfg, backend)

    # one epoch from the same state, kernel vs ref
    server_apply = partial(cnn_apply, "cnn5")

    def init_state():
        server = init_cnn(jax.random.key(SEED + 77), "cnn5", OFL_CLASSES, OFL_IMAGE)
        gen_apply, gen = default_image_setup(jax.random.key(SEED + 5), cfg, OFL_CLASSES, OFL_IMAGE)
        return server, gen_apply, gen

    def one_epoch(b):
        c = dataclasses.replace(cfg, epochs=1, backend=BackendPolicy(default=b))
        server, gen_apply, gen = init_state()  # donated by the epoch program
        clock.start()
        st = run_coboosting(
            applies, params, server_apply, server, gen_apply, gen, c,
            OFL_CLASSES, jax.random.key(SEED),
        )
        out = jax.device_get(st.server_params)
        emit(f"ofl.epoch.{b}", **clock.read())
        return out

    theta0 = jax.device_get(init_state()[0])
    theta_k = one_epoch(backend)
    theta_r = one_epoch(REF)
    tm = jax.tree_util.tree_map
    gap = _tree_norm(jax, tm(lambda a, b: a - b, theta_k, theta_r))
    update = _tree_norm(jax, tm(lambda a, b: a - b, theta_r, theta0))
    rel = gap / max(update, 1e-30)
    ok = _finite_tree(jax, theta_k) and update > 0 and rel <= OFL_PARAM_TOL
    emit("ofl.parity", kernel=backend, ref=REF, param_gap=gap,
         epoch_update=update, rel_gap=rel, tol=OFL_PARAM_TOL, ok=ok)
    if not ok:
        raise RuntimeError(f"ofl.parity: rel_gap={rel} > tol={OFL_PARAM_TOL} (or non-finite)")
    return vals


def _loss_parity(jax, clock, bank, bank_params, x, y, cfg, backend) -> None:
    """Value and every cotangent of the two fused losses, kernel vs ref, on
    the epoch's real shapes: K client logits of one batch of b test images."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ensemble_kl, ghm_ce
    from repro.models.cnn import cnn_apply, init_cnn

    b = cfg.batch_size
    xb, yb = jnp.asarray(x[:b]), jnp.asarray(y[:b])
    la = bank.logits_all(bank_params, xb)  # (K, b, classes)
    server = init_cnn(jax.random.key(SEED + 77), "cnn5", OFL_CLASSES, OFL_IMAGE)
    s_logits = cnn_apply("cnn5", server, xb)
    w = jax.random.dirichlet(jax.random.key(SEED + 3), jnp.ones((cfg.num_clients,)))
    losses = {
        "ensemble_kl": (lambda c, s, w_, bk: ensemble_kl(
            c, s, w_, temperature=cfg.kd_temperature, backend=bk), (0, 1, 2)),
        "ghm_ce": (lambda c, s, w_, bk: ghm_ce(
            c, yb, w_, weighted=True, stop_difficulty_grad=True, backend=bk), (0, 2)),
        "ghm_ce_plain": (lambda c, s, w_, bk: ghm_ce(
            c, yb, w_, weighted=False, backend=bk), (0, 2)),
    }
    clock.start()
    gaps = {}
    for name, (fn, argnums) in losses.items():
        def value_and_grads(bk, fn=fn, argnums=argnums):
            loss = lambda c, s, w_: jnp.mean(fn(c, s, w_, bk))
            return jax.jit(jax.value_and_grad(loss, argnums=argnums))(la, s_logits, w)

        k_out, r_out = value_and_grads(backend), value_and_grads(REF)
        names = ("value",) + tuple(("d_client", "d_student", "d_w")[i] for i in argnums)
        for part, kv, rv in zip(names,
                                (k_out[0],) + tuple(k_out[1]), (r_out[0],) + tuple(r_out[1])):
            kv, rv = np.asarray(kv, np.float64), np.asarray(rv, np.float64)
            gaps[f"{name}.{part}"] = float(np.linalg.norm(kv - rv) / max(np.linalg.norm(rv), 1e-30))
    worst = max(gaps.values())
    emit("ofl.parity.losses", kernel=backend, ref=REF, rel_gaps=gaps,
         worst=worst, tol=OFL_LOSS_TOL, **clock.read())
    if not worst <= OFL_LOSS_TOL:
        raise RuntimeError(f"ofl.parity.losses: worst rel gap {worst} > {OFL_LOSS_TOL}: {gaps}")


def _serve_setup(backend: str, replicas: int = 1, *,
                 requests=SERVE_REQUESTS, prompt=SERVE_PROMPT, gen=SERVE_GEN,
                 slots=SERVE_SLOTS, reduced=False):
    from repro.config import get_arch, reduced_variant
    from repro.kernels import BackendPolicy
    from repro.launch.serve import build_parser, validate_args

    argv = [
        "--arch", SERVE_ARCH, "--engine", "continuous", "--requests", str(requests),
        "--prompt-len", str(prompt), "--gen", str(gen), "--max-slots", str(slots),
        "--kv-layout", "paged", "--page-size", str(SERVE_PAGE),
        "--backend", backend, "--replicas", str(replicas), "--seed", str(SEED),
    ]
    args = build_parser().parse_args(argv)
    cfg = get_arch(SERVE_ARCH)
    if reduced:
        cfg = reduced_variant(cfg)
    validate_args(args, cfg)
    return args, cfg.replace(backend=BackendPolicy(default=backend))


def _requests(cfg, args):
    import numpy as np

    from repro.data import make_token_stream
    from repro.serve import Request

    data = make_token_stream(SEED, cfg.vocab_size, args.requests, args.prompt_len)
    return [
        Request(rid=i, tokens=data["tokens"][i].astype(np.int32), max_new_tokens=args.gen)
        for i in range(args.requests)
    ]


def _run_fleet(jax, clock, name, args, cfg, params, reqs):
    from repro.launch.serve import build_fleet
    from repro.serve import ContinuousScheduler, FleetRouter

    clock.start()
    engines = build_fleet(args, cfg, params)
    for eng in engines:
        eng.warmup(reqs[0].tokens, min(2, args.gen))
    emit(f"{name}.warmup", engines=len(engines), **clock.read())
    sched = ContinuousScheduler(engines[0]) if len(engines) == 1 else FleetRouter(engines)
    clock.start()
    comps = sched.run(reqs)
    timing = clock.read()
    short = [c.rid for c in comps if len(c.tokens) != args.gen]
    if len(comps) != len(reqs) or short:
        raise RuntimeError(
            f"{name}: {len(comps)}/{len(reqs)} requests completed, short budgets: {short}"
        )
    emit(f"{name}.run", requests=len(comps), tokens=int(sum(len(c.tokens) for c in comps)),
         replicas_used=sorted({c.replica for c in comps}), **timing)
    return engines, comps


def _logit_gap(np, k, r) -> dict:
    k, r = np.asarray(k, np.float64), np.asarray(r, np.float64)
    spread = float(r.std())
    return {"max_abs": float(np.abs(k - r).max()), "ref_std": spread,
            "rel": float(np.abs(k - r).max()) / max(spread, 1e-30)}


def serve_phase(jax, clock, backend: str, *, reduced=False, **sizes) -> None:
    """smollm-135m through the launcher's fleet builder and scheduler, then
    kernel-vs-ref parity of prefill logits and one paged decode step."""
    import jax.numpy as jnp
    import numpy as np

    from repro.models import init_lm, init_lm_state, lm_decode, lm_prefill

    args, cfg = _serve_setup(backend, reduced=reduced, **sizes)
    emit("serve.config", arch=cfg.name, backend=backend, layers=cfg.num_layers,
         d_model=cfg.d_model, heads=cfg.num_heads, kv_heads=cfg.num_kv_heads,
         head_dim=cfg.head_dim_, vocab=cfg.vocab_size, dtype=cfg.dtype,
         requests=args.requests, prompt=args.prompt_len, gen=args.gen,
         max_slots=args.max_slots, page_size=args.page_size)
    params = init_lm(cfg, jax.random.key(SEED))
    reqs = _requests(cfg, args)
    _run_fleet(jax, clock, "serve", args, cfg, params, reqs)

    # parity: prefill logits and one paged decode step, kernel vs ref
    b, s, ps = min(4, len(reqs)), args.prompt_len, args.page_size
    max_seq = -(-(s + 1) // ps) * ps
    toks = jnp.asarray(np.stack([r.tokens for r in reqs[:b]]))
    cfg_r = cfg.replace(backend=cfg.backend.replace(default=REF))

    def prefill(c):
        fn = jax.jit(lambda p, t, st: lm_prefill(p, c, {"tokens": t}, st))
        return fn(params, toks, init_lm_state(c, b, max_seq))

    clock.start()
    logits_k, _ = prefill(cfg)
    logits_r, state_r = prefill(cfg_r)
    pre = _logit_gap(np, logits_k, logits_r)
    emit("serve.parity.prefill", **pre, tol=SERVE_LOGIT_TOL, **clock.read())

    # the ref prefill's dense cache re-viewed as pages: row i owns pages
    # [i*W, (i+1)*W) of the pool, in order
    w = max_seq // ps

    def as_pages(x):  # (G, B, max_seq, KH, hd) -> (G, B*W, ps, KH, hd)
        return x.reshape(x.shape[0], b * w, ps, *x.shape[3:])

    paged = {key: {"k_pages": as_pages(sub["k"]), "v_pages": as_pages(sub["v"])}
             for key, sub in state_r.items()}
    table = jnp.arange(b * w, dtype=jnp.int32).reshape(b, w)
    tok = jnp.argmax(logits_r[:, -1], axis=-1).astype(jnp.int32)[:, None]
    pos = jnp.full((b,), s, jnp.int32)

    def decode(c):
        fn = jax.jit(lambda p, t, st, q, tb: lm_decode(p, c, t, st, q, tb)[0])
        return fn(params, tok, paged, pos, table)

    clock.start()
    dec = _logit_gap(np, decode(cfg), decode(cfg_r))
    emit("serve.parity.decode", **dec, tol=SERVE_LOGIT_TOL, **clock.read())
    bad = [n for n, g in (("prefill", pre), ("decode", dec))
           if not (np.isfinite(g["max_abs"]) and g["rel"] <= SERVE_LOGIT_TOL)]
    if bad:
        raise RuntimeError(f"serve.parity: {bad} logits differ from ref beyond {SERVE_LOGIT_TOL}")


def four_chip_phase(jax, clock, backend: str, *, reduced=False, **sizes) -> None:
    """4 one-chip replicas behind FleetRouter vs one colocated engine."""
    import numpy as np

    from repro.models import init_lm

    n = len(jax.devices())
    if n != 4:
        raise RuntimeError(f"--four-chips needs 4 devices, JAX sees {n}")
    args4, cfg = _serve_setup(backend, replicas=4, reduced=reduced, **sizes)
    args1, _ = _serve_setup(backend, replicas=1, reduced=reduced, **sizes)
    params = init_lm(cfg, jax.random.key(SEED))
    reqs = _requests(cfg, args4)
    emit("fleet.config", arch=cfg.name, backend=backend, replicas=4,
         requests=len(reqs), prompt=args4.prompt_len, gen=args4.gen)

    _, single = _run_fleet(jax, clock, "fleet.single", args1, cfg, params, reqs)
    engines, fleet = _run_fleet(jax, clock, "fleet.replicas", args4, cfg, params, reqs)

    ref = {c.rid: c.tokens for c in single}
    mismatched = [c.rid for c in fleet if not np.array_equal(c.tokens, ref[c.rid])]

    def devices_of(tree):
        return {d.id for leaf in jax.tree_util.tree_leaves(tree) for d in leaf.devices()}

    placement = []
    for i, eng in enumerate(engines):
        placement.append({
            "replica": i,
            "params": sorted(devices_of(eng.decode.params)),
            "prefill_params": sorted(devices_of(eng.prefill.params)),
            "kv_pool": sorted(devices_of(eng._state.kv)),
        })
    owned = [p["params"] for p in placement]
    own_device = all(
        len(p["params"]) == 1 and p["params"] == p["prefill_params"] == p["kv_pool"]
        for p in placement
    ) and len({tuple(o) for o in owned}) == len(engines)
    emit("fleet.parity", mismatched=mismatched, served_by=sorted({c.replica for c in fleet}),
         placement=placement, own_device=own_device)
    if mismatched:
        raise RuntimeError(f"fleet: greedy tokens differ from the single engine for {mismatched}")
    if not own_device:
        raise RuntimeError(f"fleet: replica state is not one device per replica: {placement}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run only the 4-replica fleet check (needs 4 chips)")
    args = p.parse_args(argv)

    if not (SRC / "repro" / "launch" / "ofl.py").is_file():
        print(f"chip_smoke: the repro package is missing ({SRC / 'repro'})", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX's first device is {dev.platform!r}", file=sys.stderr)
        return 1

    from repro.utils.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}
    emit("device", **device, jax=jax.__version__, compile_cache=cache)
    clock = CompileClock(jax)
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase(jax, clock, "pallas")
    else:
        ofl_phase(jax, clock, "pallas")
        serve_phase(jax, clock, "pallas")
    emit("done", wall_s=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
