"""End-to-end LM training driver.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
        --steps 300 --batch 8 --seq 256 --reduced

``--reduced`` swaps in the smoke-scale variant of the arch (this container
is a 1-CPU host); on real hardware drop it and pass ``--mesh production``.
Data is the seeded hidden-Markov token stream, so loss visibly drops below
the uniform floor within a few hundred steps.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_checkpoint
from repro.config import TrainConfig, get_arch, reduced_variant
from repro.data import make_token_stream
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import init_lm
from repro.runtime import make_train_step
from repro.utils import get_logger, tree_size
from repro.utils.compile_cache import enable_compile_cache

log = get_logger("train")


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="smollm-135m")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=256)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--optimizer", default="adamw")
    p.add_argument("--reduced", action="store_true", help="smoke-scale variant")
    p.add_argument("--mesh", default="host", choices=("host", "production", "multipod"))
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced_variant(cfg).replace(dtype="float32", param_dtype="float32")
    if cfg.family in ("audio",):
        raise SystemExit("use launch.train for LM archs; hubert trains via lm_loss on frames")

    mesh = {
        "host": make_host_mesh,
        "production": make_production_mesh,
        "multipod": lambda: make_production_mesh(multi_pod=True),
    }[args.mesh]()

    tc = TrainConfig(
        optimizer=args.optimizer,
        learning_rate=args.lr,
        schedule="linear_warmup_cosine",
        warmup_steps=max(args.steps // 20, 1),
        total_steps=args.steps,
        seed=args.seed,
    )
    with jax.set_mesh(mesh):
        params = init_lm(cfg, jax.random.key(args.seed))
        step_fn = make_train_step(cfg, tc)
        opt_state = step_fn.optimizer.init(params)
        jit_step = jax.jit(step_fn, donate_argnums=(0, 1))
        log.info("arch=%s params=%.1fM mesh=%s", cfg.name, tree_size(params) / 1e6, mesh.shape)

        t0 = time.time()
        losses = []
        for i in range(args.steps):
            data = make_token_stream(args.seed * 10_000 + i, cfg.vocab_size, args.batch, args.seq)
            batch = {k: jnp.asarray(v) for k, v in data.items()}
            if cfg.family == "vlm":
                pre = cfg.num_prefix_tokens
                rng = np.random.RandomState(i)
                batch["prefix"] = jnp.asarray(
                    rng.randn(args.batch, pre, cfg.frontend_dim).astype(np.float32) * 0.02
                )
            params, opt_state, metrics = jit_step(params, opt_state, batch, jnp.asarray(i))
            losses.append(float(metrics["loss"]))
            if (i + 1) % args.log_every == 0 or i == 0:
                log.info(
                    "step %4d loss=%.4f (avg10=%.4f) %.2fs/step",
                    i,
                    losses[-1],
                    float(np.mean(losses[-10:])),
                    (time.time() - t0) / (i + 1),
                )
        log.info(
            "done: first-10 avg=%.4f last-10 avg=%.4f (uniform floor=%.4f)",
            float(np.mean(losses[:10])),
            float(np.mean(losses[-10:])),
            float(np.log(cfg.vocab_size)),
        )
        if args.ckpt_dir:
            path = save_checkpoint(args.ckpt_dir, args.steps, params, {"arch": cfg.name})
            log.info("checkpoint saved: %s", path)


if __name__ == "__main__":
    main()
