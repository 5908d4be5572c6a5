"""One-shot federated learning pipeline driver — the paper end to end.

    PYTHONPATH=src python -m repro.launch.ofl --method coboosting \
        --clients 5 --alpha 0.1 --epochs 40

Builds the model market (synthetic images, Dirichlet/C_cls/lognormal
partition, SGD-m local training), then runs the chosen server-side method
and reports server / ensemble test accuracy.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
from functools import partial

import jax
import numpy as np

from repro import obs
from repro.config.train import OFLConfig
from repro.core import (
    default_image_setup,
    fedavg,
    run_adi_baseline,
    run_coboosting,
    run_feddf,
    run_generator_baseline,
    uniform_weights,
)
from repro.data import make_synth_images
from repro.fed import build_market, build_market_grouped, market_eval_fn
from repro.kernels import KERNEL_BACKENDS, policy_from_flags
from repro.models.cnn import cnn_apply, init_cnn
from repro.utils import get_logger
from repro.utils.compile_cache import enable_compile_cache

log = get_logger("ofl")

METHODS = ("coboosting", "dense", "f_dafl", "f_adi", "feddf", "fedavg", "fedens")


def run_method(
    method: str,
    cfg: OFLConfig,
    num_classes: int,
    image_shape,
    applies,
    params,
    sizes,
    train_x,
    test_x,
    test_y,
    server_arch: str,
    seed: int,
    eval_every: int = 50,
    driver: str = "fused",
):
    """Dispatch one OFL method; returns {'server_acc':…, 'ensemble_acc':…},
    except ``fedens`` which trains no server and returns ``ensemble_acc``
    only. ``driver`` selects the fused single-dispatch epoch engine
    (default) or the legacy per-batch loop for every distillation-based
    method."""
    server_apply = partial(cnn_apply, server_arch)
    server_params = init_cnn(jax.random.key(seed + 77), server_arch, num_classes, image_shape)
    eval_fn = market_eval_fn(applies, params, server_apply, test_x, test_y)
    key = jax.random.key(seed)

    if method == "fedavg":
        avg = fedavg(params, sizes)
        return eval_fn(avg, uniform_weights(len(params)))
    if method == "fedens":
        # no server is trained here — evaluating the fresh random init would
        # record a meaningless server_acc next to the real ensemble number
        return eval_fn(None, uniform_weights(len(params)))
    if method == "feddf":
        st = run_feddf(
            applies, params, server_apply, server_params, train_x, cfg, key,
            eval_fn, eval_every, driver=driver,
        )
        return st.history[-1]
    if method == "f_adi":
        st = run_adi_baseline(
            applies, params, server_apply, server_params, image_shape, cfg, num_classes, key,
            eval_fn, eval_every, driver=driver,
        )
        return st.history[-1]
    if method in ("dense", "f_dafl"):
        gen_apply, gen_params = default_image_setup(jax.random.key(seed + 5), cfg, num_classes, image_shape)
        st = run_generator_baseline(
            method, applies, params, server_apply, server_params, gen_apply, gen_params,
            cfg, num_classes, key, eval_fn, eval_every, driver=driver,
        )
        return st.history[-1]
    # coboosting (+ ablations via component flags on cfg)
    gen_apply, gen_params = default_image_setup(jax.random.key(seed + 5), cfg, num_classes, image_shape)
    st = run_coboosting(
        applies, params, server_apply, server_params, gen_apply, gen_params,
        cfg, num_classes, key, eval_fn, eval_every, driver=driver,
    )
    return st.history[-1]


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--method", default="coboosting", choices=METHODS)
    p.add_argument("--clients", type=int, default=5)
    p.add_argument("--alpha", type=float, default=0.1)
    p.add_argument("--partition", default="dirichlet", choices=("dirichlet", "c_cls", "iid"))
    p.add_argument("--c-cls", type=int, default=2)
    p.add_argument("--sigma", type=float, default=0.0, help="lognormal size skew")
    p.add_argument("--classes", type=int, default=6)
    p.add_argument("--image", type=int, default=16)
    p.add_argument("--per-class", type=int, default=150)
    p.add_argument("--epochs", type=int, default=30)
    p.add_argument("--gen-iters", type=int, default=10)
    p.add_argument("--batch", type=int, default=64)
    p.add_argument("--local-epochs", type=int, default=15)
    p.add_argument("--client-archs", default="", help="comma list (heterogeneous market)")
    p.add_argument("--server-arch", default="cnn5")
    p.add_argument("--driver", default="fused", choices=("fused", "legacy"),
                   help="epoch engine: fused scan (O(1) dispatch) or legacy per-batch loop")
    p.add_argument("--no-ghs", action="store_true")
    p.add_argument("--no-dhs", action="store_true")
    p.add_argument("--no-ee", action="store_true")
    p.add_argument("--no-adv", action="store_true",
                   help="drop the adversarial generator term L_A (independent "
                        "of --no-ghs, so every Table 7 row is reachable)")
    p.add_argument("--backend", default=None, choices=KERNEL_BACKENDS,
                   help="kernel backend for every dispatched op: auto "
                        "(pallas on TPU, jnp ref elsewhere) | pallas | "
                        "pallas-interpret | ref")
    p.add_argument("--kernel-backend", default=None, choices=KERNEL_BACKENDS,
                   help="DEPRECATED: use --backend (this alias sets only the "
                        "fused-loss op)")
    p.add_argument("--ensemble-impl", default="grouped", choices=("grouped", "looped"),
                   help="client forward engine: grouped ClientBank (one vmap "
                        "per arch group) or the K-way looped baseline")
    p.add_argument("--ensemble-scan-chunk", type=int, default=0,
                   help=">0: scan over vmapped chunks of this many clients "
                        "inside each group (memory bound at large K)")
    p.add_argument("--grouped-market", action="store_true",
                   help="vmap local client training within arch groups "
                        "(build_market_grouped) instead of the per-client loop")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out", default=None)
    # telemetry (repro.obs) — off by default, zero-cost when off
    p.add_argument("--metrics-out", default=None, metavar="PATH.jsonl",
                   help="dump the ofl.* metrics registry (epoch/phase "
                        "counters + the ofl.epoch.step_s histogram, each "
                        "epoch's execution time, one epoch in flight) as "
                        "JSONL plus a .prom Prometheus-text sibling at exit")
    p.add_argument("--trace-out", default=None, metavar="PATH.json",
                   help="record host-side phase spans and dump Chrome "
                        "trace-event JSON (Perfetto-loadable) at exit")
    p.add_argument("--profile-dir", default=None,
                   help="also run a JAX profiler trace into this directory "
                        "(the fused epoch's jax.named_scope phases and "
                        "networks show up in the device timeline)")
    args = p.parse_args()
    enable_compile_cache()
    obs.configure(
        metrics=bool(args.metrics_out),
        trace=bool(args.trace_out),
        profile_dir=args.profile_dir,
    )

    shape = (args.image, args.image, 3)
    cfg = OFLConfig(
        num_clients=args.clients,
        partition=args.partition,
        alpha=args.alpha,
        c_cls=args.c_cls,
        lognormal_sigma=args.sigma,
        local_epochs=args.local_epochs,
        epochs=args.epochs,
        gen_iters=args.gen_iters,
        batch_size=args.batch,
        latent_dim=32,
        buffer_batches=4,
        use_ghs=not args.no_ghs,
        use_dhs=not args.no_dhs,
        use_ee=not args.no_ee,
        use_adv=not args.no_adv,
        backend=policy_from_flags(backend=args.backend, kernel_backend=args.kernel_backend),
        ensemble_impl=args.ensemble_impl,
        ensemble_scan_chunk=args.ensemble_scan_chunk,
        seed=args.seed,
    )
    x, y = make_synth_images(args.seed, args.classes, args.per_class, shape)
    test_x, test_y = make_synth_images(args.seed + 1, args.classes, max(40, args.per_class // 4), shape)
    archs = args.client_archs.split(",") if args.client_archs else None
    if args.grouped_market:
        bank, bank_params, sizes, _ = build_market_grouped(args.seed, x, y, cfg, args.classes, archs)
        params = bank.unstack_params(bank_params)
        applies = [bank.client_apply(k) for k in range(bank.num_clients)]
    else:
        applies, params, sizes, _ = build_market(args.seed, x, y, cfg, args.classes, archs)

    result = run_method(
        args.method, cfg, args.classes, shape, applies, params, sizes,
        x, test_x, test_y, args.server_arch, args.seed, eval_every=max(args.epochs // 3, 1),
        driver=args.driver,
    )
    result = {k: v for k, v in result.items() if isinstance(v, (int, float))}
    log.info("[%s] %s", args.method, result)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"method": args.method, **result}, f, indent=1)
    if args.profile_dir:
        jax.profiler.stop_trace()
    if args.metrics_out:
        obs.registry().dump(args.metrics_out)
        log.info("metrics snapshot -> %s (+ .prom)", args.metrics_out)
    if args.trace_out:
        obs.tracer().dump(args.trace_out)
        log.info("trace -> %s (%d events)", args.trace_out, len(obs.tracer()))


if __name__ == "__main__":
    main()
