"""§Perf hillclimb driver (deliverable g): the three selected pairs, each
iterated hypothesis → change → measure on the dominant roofline term.

    PYTHONPATH=src python -m benchmarks.perf_hillclimb [--pair NAME] [--list-pairs]

Every iteration re-lowers + recompiles the production program with one
lever changed and reports the three roofline terms; the narrative lives in
EXPERIMENTS.md §Perf. NOTE: must run in a fresh process (sets the 512-device
dry-run XLA flag).
"""
import os

os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=512"

import argparse
import dataclasses
import json
import time
from typing import Callable, Optional

from repro.launch.dryrun import dryrun_one
from repro.utils import get_logger

log = get_logger("hillclimb")


def show(tag, rec):
    if rec["status"] != "ok":
        log.error("%s: %s %s", tag, rec["status"], rec.get("error", rec.get("reason")))
        return rec
    log.info(
        "%-38s c=%8.4fs m=%8.4fs k=%8.4fs dom=%-10s ratio=%5.3f hbm=%5.1fG fits=%s",
        tag,
        rec["compute_s"],
        rec["memory_s"],
        rec["collective_s"],
        rec["dominant"],
        rec.get("useful_flops_ratio", 0),
        rec["peak_bytes_per_device"] / 2**30,
        rec["fits_hbm"],
    )
    return rec


def pair_qwen3moe(out):
    """Worst roofline fraction: qwen3-moe-235b × train_4k.
    H1: the GShard dispatch/combine einsums (2·T·E·C·d each, ≈10³× the
        useful expert FLOPs at E=128, C=160) dominate compute → scatter
        dispatch removes them.
    H2: f32 momentum+grads are ~7.6 GB/dev of the HBM overrun → bf16 slots.
    H3: dispatch-einsum FLOPs scale with capacity C ∝ group size → smaller
        groups shrink the einsum even without the scatter rewrite."""
    a, s = "qwen3-moe-235b-a22b", "train_4k"
    out["qwen3moe:baseline(einsum,f32-slots)"] = show(
        "qwen3moe baseline einsum/f32", dryrun_one(a, s, verbose=False)
    )
    out["qwen3moe:it1(scatter)"] = show(
        "it1 moe_impl=scatter", dryrun_one(a, s, verbose=False, overrides={"moe_impl": "scatter"})
    )
    out["qwen3moe:it2(scatter+bf16-slots)"] = show(
        "it2 +bf16 momentum/grads",
        dryrun_one(
            a, s, verbose=False,
            overrides={"moe_impl": "scatter"},
            tc_overrides={"state_dtype": "bfloat16", "grad_dtype": "bfloat16"},
        ),
    )
    out["qwen3moe:it3(einsum,group512)"] = show(
        "it3 einsum group=512 (capacity lever)",
        dryrun_one(a, s, verbose=False, overrides={"moe_group_size": 512}),
    )
    out["qwen3moe:it4(group512+bf16+micro4)"] = show(
        "it4 group512 + bf16 slots + microbatch=4",
        dryrun_one(
            a, s, verbose=False, overrides={"moe_group_size": 512},
            tc_overrides={"state_dtype": "bfloat16", "grad_dtype": "bfloat16", "microbatches": 4},
        ),
    )
    out["qwen3moe:it5(group512+bf16+micro8)"] = show(
        "it5 group512 + bf16 slots + microbatch=8 (FITS)",
        dryrun_one(
            a, s, verbose=False, overrides={"moe_group_size": 512},
            tc_overrides={"state_dtype": "bfloat16", "grad_dtype": "bfloat16", "microbatches": 8},
        ),
    )


def pair_mixtral(out):
    """Most collective-bound: mixtral-8x7b × train_4k.
    H1: 8 experts cannot shard the 16-wide model axis → the rules fall back
        to tensor-parallel d_ff, paying an all-reduce per expert matmul; a
        (32, 8) mesh lets experts shard fully (expert parallelism).
    H2: the scatter dispatch removes the dispatch-einsum FLOPs/bytes on top."""
    a, s = "mixtral-8x7b", "train_4k"
    out["mixtral:baseline(16x16)"] = show(
        "mixtral baseline 16x16", dryrun_one(a, s, verbose=False)
    )
    out["mixtral:it1(mesh32x8)"] = show(
        "it1 mesh=32x8 (expert parallel)", dryrun_one(a, s, verbose=False, mesh_shape="32x8")
    )
    out["mixtral:it2(mesh32x8+scatter)"] = show(
        "it2 +scatter dispatch",
        dryrun_one(a, s, verbose=False, mesh_shape="32x8", overrides={"moe_impl": "scatter"}),
    )
    out["mixtral:it3(mesh32x8+scatter+bf16)"] = show(
        "it3 +bf16 slots",
        dryrun_one(
            a, s, verbose=False, mesh_shape="32x8",
            overrides={"moe_impl": "scatter"},
            tc_overrides={"state_dtype": "bfloat16", "grad_dtype": "bfloat16"},
        ),
    )
    out["mixtral:it4(mesh32x8+group512)"] = show(
        "it4 mesh32x8 einsum group=512 (E·C 8× smaller)",
        dryrun_one(a, s, verbose=False, mesh_shape="32x8", overrides={"moe_group_size": 512}),
    )
    out["mixtral:it6(mesh32x8+group512+micro8)"] = show(
        "it6 +microbatch=8",
        dryrun_one(
            a, s, verbose=False, mesh_shape="32x8",
            overrides={"moe_group_size": 512},
            tc_overrides={"microbatches": 8},
        ),
    )
    out["mixtral:it8(mesh32x8+group512+micro16)"] = show(
        "it8 +microbatch=16 (FITS)",
        dryrun_one(
            a, s, verbose=False, mesh_shape="32x8",
            overrides={"moe_group_size": 512},
            tc_overrides={"microbatches": 16},
        ),
    )


def pair_coboost(out):
    """Most paper-representative: the K=4-client Co-Boosting distillation
    step on granite-3-2b × train_4k.
    H1: accumulating the teacher ensemble as full (B,S,V) f32 logits is the
        memory hot spot (≈0.8 GB/dev × several live copies at 49k vocab) →
        chunking the KL over the sequence (heads factored out of the
        forwards) bounds live vocab tensors to (B, chunk, V).
    H2: bf16 optimizer slots shave the server-side state."""
    a, s = "granite-3-2b", "train_4k"
    out["coboost:baseline(K4)"] = show(
        "coboost baseline K=4", dryrun_one(a, s, verbose=False, coboost_clients=4)
    )
    out["coboost:it1(kl_chunk512)"] = show(
        "it1 kl_chunk=512", dryrun_one(a, s, verbose=False, coboost_clients=4, kl_chunk=512)
    )
    out["coboost:it2(kl_chunk512+bf16)"] = show(
        "it2 +bf16 slots",
        dryrun_one(
            a, s, verbose=False, coboost_clients=4, kl_chunk=512,
            tc_overrides={"state_dtype": "bfloat16", "grad_dtype": "bfloat16"},
        ),
    )


def _coboost_ab(arms, cfg, classes, shape, short, long, archs=None, grouped_market=False):
    """Shared live-market Co-Boosting A/B harness: each arm is
    ``(name, cfg_overrides, run_kwargs)``, timed as the difference of a long
    and a short run so compile + market setup cancel. Returns the epochs/sec
    record plus each arm's final server params (for parity checks).
    ``archs`` (one per client, default all-mlp) makes the market
    heterogeneous; ``grouped_market=True`` trains the clients through the
    vmapped build_market_grouped path (one program per arch group — the only
    sane way to stand up a K=64 market on CPU)."""
    from functools import partial

    import jax

    from repro.core import default_image_setup, run_coboosting
    from repro.data import make_synth_images
    from repro.fed import build_market, build_market_grouped
    from repro.models.cnn import cnn_apply, init_cnn

    x, y = make_synth_images(0, classes, 40, shape)
    archs = list(archs) if archs else ["mlp"] * cfg.num_clients
    if grouped_market:
        bank, bank_params, _, _ = build_market_grouped(0, x, y, cfg, classes, archs=archs)
        params = bank.unstack_params(bank_params)
        applies = [bank.client_apply(k) for k in range(bank.num_clients)]
    else:
        applies, params, _, _ = build_market(0, x, y, cfg, classes, archs=archs)
    server_apply = partial(cnn_apply, "mlp")

    def run(cfg_overrides, run_kwargs, epochs):
        c = dataclasses.replace(cfg, epochs=epochs, **cfg_overrides)
        sp = init_cnn(jax.random.key(99), "mlp", classes, shape)
        gen_apply, gp = default_image_setup(jax.random.key(5), c, classes, shape)
        t0 = time.time()
        st = run_coboosting(
            applies, params, server_apply, sp, gen_apply, gp, c, classes,
            jax.random.key(0), **run_kwargs,
        )
        jax.block_until_ready(st.server_params)
        return time.time() - t0, st

    rec, finals = {"status": "ok", "epochs": long - short}, {}
    for name, cfg_overrides, run_kwargs in arms:
        dt_long, st = run(cfg_overrides, run_kwargs, long)
        dt_short, _ = run(cfg_overrides, run_kwargs, short)
        finals[name] = st.server_params
        rec[f"{name}_epochs_per_sec"] = round((long - short) / max(dt_long - dt_short, 1e-9), 3)
    return rec, finals


def pair_epochdrv(out):
    """Epoch-driver hillclimb (the device-resident buffer PR's headline
    number): Co-Boosting epochs/sec, fused single-dispatch scan engine vs
    the legacy per-batch dispatch loop, on a miniature live market."""
    from repro.config.train import OFLConfig

    cfg = OFLConfig(
        num_clients=3, local_epochs=2, local_batch_size=16,
        gen_iters=4, batch_size=16, latent_dim=8, buffer_batches=6,
    )
    rec, _ = _coboost_ab(
        [("legacy", {}, {"driver": "legacy"}), ("fused", {}, {"driver": "fused"})],
        cfg, classes=4, shape=(8, 8, 3), short=4, long=16,
    )
    rec["buffer_batches"] = cfg.buffer_batches
    rec["speedup"] = round(rec["fused_epochs_per_sec"] / rec["legacy_epochs_per_sec"], 3)
    log.info(
        "epochdrv: fused=%.2f ep/s legacy=%.2f ep/s speedup=%.2fx (buffer=%d)",
        rec["fused_epochs_per_sec"], rec["legacy_epochs_per_sec"], rec["speedup"],
        cfg.buffer_batches,
    )
    out["epochdrv:fused_vs_legacy"] = rec


def pair_kernelpath(out):
    """Kernel-vs-ref loss path A/B under the fused epoch engine: Co-Boosting
    with the Eq. 4/Eq. 6 losses routed through the differentiable Pallas
    kernels (compiled on TPU, interpreter elsewhere) vs the pure-jnp ref
    composition, same PRNG stream. Reports epochs/sec for both arms, a
    loss-op microbench with a forward-only arm AND a full train-step
    (forward+backward+update) arm — the passes the fused Pallas VJPs now
    own — plus the final-server-params and one-step grad parity gaps.
    Off-TPU the interpreter arm is expected
    to be much slower — the number that matters there is the parity gap; the
    speed story is the TPU run."""
    import jax
    import jax.numpy as jnp

    from repro.config.train import OFLConfig
    from repro.kernels import kernel_arm

    arm = kernel_arm()
    cfg = OFLConfig(
        num_clients=3, local_epochs=2, local_batch_size=16,
        gen_iters=3, batch_size=16, latent_dim=8, buffer_batches=4,
    )
    rec, finals = _coboost_ab(
        [("ref", {"kernel_backend": "ref"}, {}), ("kernel", {"kernel_backend": arm}, {})],
        cfg, classes=4, shape=(8, 8, 3), short=2, long=6,
    )
    rec["kernel_arm"] = arm
    rec["jax_backend"] = jax.default_backend()
    rec["kernel_vs_ref_speedup"] = round(
        rec["kernel_epochs_per_sec"] / rec["ref_epochs_per_sec"], 3
    )
    rec["server_params_max_diff"] = float(
        max(
            jnp.max(jnp.abs(u.astype(jnp.float32) - v.astype(jnp.float32)))
            for u, v in zip(
                jax.tree_util.tree_leaves(finals["ref"]),
                jax.tree_util.tree_leaves(finals["kernel"]),
            )
        )
    )
    log.info(
        "kernelpath: kernel(%s)=%.2f ep/s ref=%.2f ep/s speedup=%.2fx parity=%.2e",
        arm, rec["kernel_epochs_per_sec"], rec["ref_epochs_per_sec"],
        rec["kernel_vs_ref_speedup"], rec["server_params_max_diff"],
    )

    # --- loss-op microbench: forward-only vs full train step (fwd+bwd) ---
    # Now that the Pallas backwards are fused kernels behind the same
    # custom_vjp, the A/B must separate the two passes: the forward-only arm
    # times just the dispatched loss eval, the train-step arm times a whole
    # value_and_grad + SGD update through BOTH losses (the distillation hot
    # path the fused VJPs serve). Same long-minus-short timing so dispatch
    # and compile cancel.
    from functools import partial

    from repro.kernels import ensemble_kl, ghm_ce

    K, B, V, D = 3, 32, 256, 64
    ks = jax.random.split(jax.random.key(7), 5)
    cl = jax.random.normal(ks[0], (K, B, V)) * 2.0
    x = jax.random.normal(ks[1], (B, D))
    w = jax.nn.softmax(jax.random.normal(ks[2], (K,)))
    labels = jax.random.randint(ks[3], (B,), 0, V)
    head = {
        "w": jax.random.normal(ks[4], (D, V)) / jnp.sqrt(D),
        "b": jnp.zeros((V,)),
    }

    def loss(params, backend):
        st = x @ params["w"] + params["b"]
        return jnp.mean(ensemble_kl(cl, st, w, temperature=4.0, backend=backend)) + jnp.mean(
            ghm_ce(cl, labels, w, backend=backend)
        )

    def train_step(params, backend):
        val, g = jax.value_and_grad(partial(loss, backend=backend))(params)
        return jax.tree_util.tree_map(lambda p, d: p - 0.1 * d, params, g), val

    def steps_per_sec(fn, short=3, long=13):
        def run(n):
            t0 = time.time()
            for _ in range(n):
                r = fn()
            jax.block_until_ready(r)
            return time.time() - t0

        run(1)  # compile
        dt_long, dt_short = run(long), run(short)
        return (long - short) / max(dt_long - dt_short, 1e-9)

    for mode, fn in (
        ("fwd", lambda backend: jax.jit(partial(loss, backend=backend))),
        ("train_step", lambda backend: jax.jit(partial(train_step, backend=backend))),
    ):
        for name, backend in (("ref", "ref"), ("kernel", arm)):
            f = fn(backend)
            thunk = (lambda f=f: f(head)) if mode == "fwd" else (lambda f=f: f(head)[0])
            rec[f"{mode}_{name}_steps_per_sec"] = round(steps_per_sec(thunk), 2)
        rec[f"{mode}_kernel_vs_ref_speedup"] = round(
            rec[f"{mode}_kernel_steps_per_sec"] / max(rec[f"{mode}_ref_steps_per_sec"], 1e-9), 3
        )
    # one-step grad parity on the exact microbench program
    g_ref = jax.grad(partial(loss, backend="ref"))(head)
    g_ker = jax.grad(partial(loss, backend=arm))(head)
    rec["train_step_grads_max_diff"] = float(
        max(
            jnp.max(jnp.abs(u - v))
            for u, v in zip(
                jax.tree_util.tree_leaves(g_ref), jax.tree_util.tree_leaves(g_ker)
            )
        )
    )
    rec["microbench_kbvd"] = [K, B, V, D]
    log.info(
        "kernelpath microbench (K=%d B=%d V=%d): fwd kernel=%.1f ref=%.1f it/s "
        "(%.2fx) | train-step kernel=%.1f ref=%.1f it/s (%.2fx) grad-parity=%.2e",
        K, B, V,
        rec["fwd_kernel_steps_per_sec"], rec["fwd_ref_steps_per_sec"],
        rec["fwd_kernel_vs_ref_speedup"],
        rec["train_step_kernel_steps_per_sec"], rec["train_step_ref_steps_per_sec"],
        rec["train_step_kernel_vs_ref_speedup"], rec["train_step_grads_max_diff"],
    )
    out["kernelpath:kernel_vs_ref"] = rec


def pair_servepath(out):
    """Serving-path A/B (the continuous-batching PR's headline number):
    R staggered requests with RAGGED generation budgets against the reduced
    smollm-135m server model, continuous slot engine vs the fused
    static-batch baseline. Static pays twice: each batch dispatches only
    once its last member has arrived, and the whole batch decodes to its
    LONGEST member's budget (the tail bubble — short requests ride along as
    dead slots). The engine admits each prompt on arrival and refills a slot
    the moment its sequence drains — that is the tok/s and latency gap, and
    it is budget-raggedness-shaped, not hardware-speed-shaped."""
    import jax
    import numpy as np

    from repro.config import get_arch, reduced_variant
    from repro.models import init_lm
    from repro.serve import (
        ContinuousScheduler, EngineConfig, ServeEngine, ragged_stream,
        static_generate, with_arrivals,
    )
    from repro.serve.metrics import percentile as pct

    # serve-scale quick variant: deep/wide enough that a decode step costs
    # ~5ms — the regime the engine exists for. At the 2-layer smoke scale
    # a decode step is ~1ms and BOTH arms are pure dispatch overhead, which
    # measures the host, not the batching policy.
    cfg = reduced_variant(get_arch("smollm-135m")).replace(
        dtype="float32", param_dtype="float32", num_layers=4, d_model=256,
    )
    params = init_lm(cfg, jax.random.key(0))
    R, PROMPT, MAX_GEN, BATCH, REPEATS = 16, 32, 48, 4, 5
    prompts, budgets = ragged_stream(cfg.vocab_size, R, PROMPT, MAX_GEN, seed=0)

    engine = ServeEngine(
        cfg, params,
        EngineConfig(max_slots=BATCH, max_seq=PROMPT + MAX_GEN, max_new=MAX_GEN, decode_chunk=8),
    )
    sched = ContinuousScheduler(engine)

    def mk_requests(dt):
        return with_arrivals(prompts, budgets, dt)

    def run_static(dt):
        """Batches of BATCH in arrival order; each batch dispatches once its
        last member has arrived and decodes to its longest budget (every
        request's tokens land when the single fused dispatch returns).
        Useful tok/s counts only each request's own budget."""
        lat, t0, useful = [], time.time(), 0
        for b0 in range(0, R, BATCH):
            ridx = list(range(b0, min(b0 + BATCH, R)))
            gate = max(i * dt for i in ridx)
            wait = t0 + gate - time.time()
            if wait > 0:
                time.sleep(wait)
            toks = np.stack([prompts[i] for i in ridx])
            gen = max(budgets[i] for i in ridx)
            jax.block_until_ready(
                static_generate(params, cfg, {"tokens": jax.numpy.asarray(toks)}, gen)
            )
            t_done = time.time() - t0
            useful += sum(budgets[i] for i in ridx)
            lat += [t_done - i * dt for i in ridx]
        return useful / max(time.time() - t0, 1e-9), lat

    def run_continuous(dt):
        t0 = time.time()
        comps = sched.run(mk_requests(dt))
        wall = time.time() - t0
        return sum(len(c.tokens) for c in comps) / max(wall, 1e-9), [c.latency for c in comps]

    # warm both compile caches, then calibrate the arrival gap to the
    # hardware: all R requests arrive within ~half the static arm's total
    # service time. Staggered enough that admission interleaves with decode,
    # loaded enough that freed slots always have queued work to grab — the
    # regime continuous batching exists for (light load degenerates to both
    # engines idling at the arrival rate; heavy load is pure batch service).
    run_static(0.0)
    t0 = time.time()
    run_static(0.0)
    dt = max((time.time() - t0) / (2 * R), 1e-3)
    engine.warmup(prompts[0])  # every pow2 admit size + the chunk program
    run_continuous(0.0)

    # median of interleaved repeats: the per-run service time is small at
    # quick scale, so a single OS hiccup would otherwise decide the A/B
    st_runs, ct_runs = [], []
    for _ in range(REPEATS):
        st_runs.append(run_static(dt))
        ct_runs.append(run_continuous(dt))
    st_tps, st_lat = sorted(st_runs, key=lambda r: r[0])[REPEATS // 2]
    ct_tps, ct_lat = sorted(ct_runs, key=lambda r: r[0])[REPEATS // 2]
    rec = {
        "status": "ok",
        "requests": R, "prompt_len": PROMPT,
        "budgets": budgets, "batch_and_slots": BATCH, "arrival_dt_s": round(dt, 4),
        "static_tok_per_s": round(st_tps, 2),
        "continuous_tok_per_s": round(ct_tps, 2),
        "speedup": round(ct_tps / max(st_tps, 1e-9), 3),
        "static_p50_s": round(pct(st_lat, 50), 4),
        "static_p95_s": round(pct(st_lat, 95), 4),
        "continuous_p50_s": round(pct(ct_lat, 50), 4),
        "continuous_p95_s": round(pct(ct_lat, 95), 4),
        "decode_chunks": engine.stats["decode_chunks"],
        "host_syncs": engine.stats["host_syncs"],
        "jax_backend": jax.default_backend(),
    }
    log.info(
        "servepath: continuous=%.1f tok/s static=%.1f tok/s speedup=%.2fx "
        "p95 %.3fs vs %.3fs (dt=%.3fs)",
        ct_tps, st_tps, rec["speedup"], rec["continuous_p95_s"], rec["static_p95_s"], dt,
    )
    out["servepath:continuous_vs_static"] = rec


def pair_decodepath(out):
    """Decode-path A/B (the paged-KV PR's headline number): the SAME
    continuous engine + scheduler on both arms, R staggered requests with
    RAGGED budgets — only the KV layout differs. ``paged`` runs the KVPool +
    flash-decode path (``decode_backend="auto"``: the compiled Pallas kernel
    on TPU, its blocked-jnp ref twin elsewhere — auto never interprets, so
    the CPU number is an honest layout comparison); ``dense`` is the
    per-slot-rectangle + small-SDPA baseline. Median of interleaved repeats,
    staggered arrivals calibrated exactly like servepath."""
    import jax

    from repro.config import get_arch, reduced_variant
    from repro.kernels.dispatch import resolve_backend
    from repro.models import init_lm
    from repro.serve import (
        ContinuousScheduler, EngineConfig, ServeEngine, ragged_stream, with_arrivals,
    )
    from repro.serve.metrics import percentile as pct

    cfg = reduced_variant(get_arch("smollm-135m")).replace(
        dtype="float32", param_dtype="float32", num_layers=4, d_model=256,
    )
    params = init_lm(cfg, jax.random.key(0))
    R, PROMPT, MAX_GEN, SLOTS, REPEATS = 16, 32, 48, 4, 5
    PAGE = 16
    prompts, budgets = ragged_stream(cfg.vocab_size, R, PROMPT, MAX_GEN, seed=0)

    def mk_engine(layout):
        return ServeEngine(
            cfg, params,
            EngineConfig(
                max_slots=SLOTS, max_seq=PROMPT + MAX_GEN, max_new=MAX_GEN,
                decode_chunk=8, kv_layout=layout, page_size=PAGE,
            ),
        )

    engines = {"dense": mk_engine("dense"), "paged": mk_engine("paged")}
    scheds = {k: ContinuousScheduler(e) for k, e in engines.items()}

    def run_arm(name, dt):
        t0 = time.time()
        comps = scheds[name].run(with_arrivals(prompts, budgets, dt))
        wall = time.time() - t0
        return sum(len(c.tokens) for c in comps) / max(wall, 1e-9), [c.latency for c in comps]

    # warm both compile caches, calibrate arrivals to the dense arm's service
    # time (both arms then see the identical arrival schedule)
    for name, eng in engines.items():
        eng.warmup(prompts[0])
        run_arm(name, 0.0)
    t0 = time.time()
    run_arm("dense", 0.0)
    dt = max((time.time() - t0) / (2 * R), 1e-3)

    runs = {"dense": [], "paged": []}
    for _ in range(REPEATS):
        for name in ("dense", "paged"):
            runs[name].append(run_arm(name, dt))
    med = {k: sorted(v, key=lambda r: r[0])[REPEATS // 2] for k, v in runs.items()}
    pool = engines["paged"].pool
    rec = {
        "status": "ok",
        "requests": R, "prompt_len": PROMPT, "budgets": budgets,
        "slots": SLOTS, "page_size": PAGE, "pool_pages": pool.n_pages,
        "arrival_dt_s": round(dt, 4),
        "decode_backend": resolve_backend("auto"),
        "dense_tok_per_s": round(med["dense"][0], 2),
        "paged_tok_per_s": round(med["paged"][0], 2),
        "speedup": round(med["paged"][0] / max(med["dense"][0], 1e-9), 3),
        "dense_p50_s": round(pct(med["dense"][1], 50), 4),
        "dense_p95_s": round(pct(med["dense"][1], 95), 4),
        "paged_p50_s": round(pct(med["paged"][1], 50), 4),
        "paged_p95_s": round(pct(med["paged"][1], 95), 4),
        "page_appends": engines["paged"].stats["page_appends"],
        "jax_backend": jax.default_backend(),
    }
    log.info(
        "decodepath: paged=%.1f tok/s dense=%.1f tok/s speedup=%.2fx "
        "p95 %.3fs vs %.3fs (backend=%s, %d pages x %d)",
        rec["paged_tok_per_s"], rec["dense_tok_per_s"], rec["speedup"],
        rec["paged_p95_s"], rec["dense_p95_s"], rec["decode_backend"],
        rec["pool_pages"], PAGE,
    )
    out["decodepath:paged_vs_dense"] = rec


def pair_fleetpath(out):
    """Fleet-path A/B (the serving-fleet PR's headline number): the SAME
    staggered ragged request stream against (A) one monolithic colocated
    ServeEngine with 2N slots and (B) a FleetRouter over two N-slot
    replicas — equal total slot/pool capacity — with replica 0 running as
    an explicitly disaggregated prefill/decode worker pair (the handoff
    path in the timed loop). Both arms run meshless on this process's
    devices, so the CPU number isolates the ROUTING + handoff overhead
    (parity of tokens is pinned by tests/test_fleet.py); the fleet's win on
    real hardware is replicas on disjoint mesh slices. Reports tok/s and
    end-to-end p50/p95 like the other serve pairs PLUS the queue-wait
    percentiles (admitted - arrival) that the Completion split now makes
    visible — the router-attributable share of latency."""
    import jax

    from repro.config import get_arch, reduced_variant
    from repro.models import init_lm
    from repro.serve import (
        ContinuousScheduler, EngineConfig, FleetRouter, ServeEngine,
        ragged_stream, with_arrivals,
    )
    from repro.serve.metrics import percentile as pct

    cfg = reduced_variant(get_arch("smollm-135m")).replace(
        dtype="float32", param_dtype="float32", num_layers=4, d_model=256,
    )
    params = init_lm(cfg, jax.random.key(0))
    R, PROMPT, MAX_GEN, SLOTS, REPEATS = 16, 32, 48, 4, 5
    prompts, budgets = ragged_stream(cfg.vocab_size, R, PROMPT, MAX_GEN, seed=0)

    def mk_ecfg(slots, disagg=False):
        return EngineConfig(
            max_slots=slots, max_seq=PROMPT + MAX_GEN, max_new=MAX_GEN,
            decode_chunk=8, disagg=disagg,
        )

    mono = ServeEngine(cfg, params, mk_ecfg(SLOTS))
    replicas = [
        ServeEngine(cfg, params, mk_ecfg(SLOTS // 2, disagg=True)),
        ServeEngine(cfg, params, mk_ecfg(SLOTS // 2)),
    ]
    arms = {
        "mono": ContinuousScheduler(mono),
        "fleet": FleetRouter(replicas),
    }

    def run_arm(name, dt):
        t0 = time.time()
        comps = arms[name].run(with_arrivals(prompts, budgets, dt))
        wall = time.time() - t0
        return (
            sum(len(c.tokens) for c in comps) / max(wall, 1e-9),
            [c.latency for c in comps],
            [c.queue_wait for c in comps],
        )

    # warm every compile cache (both replicas + the monolith), calibrate the
    # arrival gap to the monolith's service time exactly like servepath
    for eng in [mono] + replicas:
        eng.warmup(prompts[0])
    run_arm("mono", 0.0)
    run_arm("fleet", 0.0)
    t0 = time.time()
    run_arm("mono", 0.0)
    dt = max((time.time() - t0) / (2 * R), 1e-3)

    runs = {"mono": [], "fleet": []}
    for _ in range(REPEATS):
        for name in ("mono", "fleet"):
            runs[name].append(run_arm(name, dt))
    med = {k: sorted(v, key=lambda r: r[0])[REPEATS // 2] for k, v in runs.items()}
    rec = {
        "status": "ok",
        "requests": R, "prompt_len": PROMPT, "budgets": budgets,
        "mono_slots": SLOTS, "fleet_replicas": len(replicas),
        "fleet_slots_per_replica": SLOTS // 2, "disagg_replicas": 1,
        "arrival_dt_s": round(dt, 4),
        "mono_tok_per_s": round(med["mono"][0], 2),
        "fleet_tok_per_s": round(med["fleet"][0], 2),
        "speedup": round(med["fleet"][0] / max(med["mono"][0], 1e-9), 3),
        "mono_p50_s": round(pct(med["mono"][1], 50), 4),
        "mono_p95_s": round(pct(med["mono"][1], 95), 4),
        "fleet_p50_s": round(pct(med["fleet"][1], 50), 4),
        "fleet_p95_s": round(pct(med["fleet"][1], 95), 4),
        "mono_queue_wait_p50_s": round(pct(med["mono"][2], 50), 4),
        "mono_queue_wait_p95_s": round(pct(med["mono"][2], 95), 4),
        "fleet_queue_wait_p50_s": round(pct(med["fleet"][2], 50), 4),
        "fleet_queue_wait_p95_s": round(pct(med["fleet"][2], 95), 4),
        "handoffs": sum(e.stats["handoffs"] for e in replicas),
        "requeued": arms["fleet"].stats["requeued"],
        "jax_backend": jax.default_backend(),
    }
    # telemetry-on guard arm: the SAME fleet stream with the process-global
    # span tracer + registry enabled (what --trace-out/--metrics-out switch
    # on). Spans bracket once-per-dispatch host actions only, so the enabled
    # path must stay within run-to-run noise of the plain fleet arm —
    # telemetry_overhead drifting above ~1.05 flags a hot-path regression.
    from repro import obs

    obs.configure(metrics=True, trace=True)
    try:
        tel = [run_arm("fleet", dt) for _ in range(REPEATS)]
    finally:
        trace_events = len(obs.tracer())
        obs.configure(metrics=False, trace=False)
    tel_tok = sorted(t[0] for t in tel)[REPEATS // 2]
    rec["fleet_telemetry_tok_per_s"] = round(tel_tok, 2)
    rec["telemetry_overhead"] = round(med["fleet"][0] / max(tel_tok, 1e-9), 3)
    rec["telemetry_trace_events"] = trace_events
    log.info(
        "fleetpath: fleet=%.1f tok/s mono=%.1f tok/s speedup=%.2fx "
        "p95 %.3fs vs %.3fs queue-wait p95 %.3fs vs %.3fs (%d handoffs) "
        "telemetry-on=%.1f tok/s (overhead %.2fx, %d spans)",
        rec["fleet_tok_per_s"], rec["mono_tok_per_s"], rec["speedup"],
        rec["fleet_p95_s"], rec["mono_p95_s"],
        rec["fleet_queue_wait_p95_s"], rec["mono_queue_wait_p95_s"],
        rec["handoffs"], rec["fleet_telemetry_tok_per_s"],
        rec["telemetry_overhead"], rec["telemetry_trace_events"],
    )
    out["fleetpath:router_disagg_vs_mono"] = rec


def pair_specpath(out):
    """Shared-prefix + speculative-decoding A/B (the prefix-cache PR's
    headline number): the SAME hot-prefix request stream — >=50% of prompts
    open with a common 24-token head — against (A) the plain paged engine
    and (B) the same engine with the radix prefix cache and the
    ensemble-drafter speculative decoder enabled. The headline is PREFILL
    WORK: hot admissions splice the shared head's pages out of the cache
    and prefill only the uncovered tail, so pages_allocated and
    prefill_tokens drop roughly with the shared fraction while greedy
    tokens stay bitwise identical (pinned by tests/test_serve.py).

    The drafter is the target itself (same config + params): a random-init
    repro has no trained drafter/target pair, so the pair exercises the
    MATCHED-drafter limit — acceptance ~1.0, every verify certifying k+1
    tokens — which checks the full draft/verify/emit path at its ceiling;
    any registry drafter plugs into the same (dcfg, dparams) slot. tok/s,
    p50/p95, prefix hit rate and draft acceptance rate are all recorded."""
    import jax

    from repro.config import get_arch, reduced_variant
    from repro.models import init_lm
    from repro.serve import (
        ContinuousScheduler, EngineConfig, ServeEngine, hot_prefix_stream,
        with_arrivals,
    )
    from repro.serve.metrics import percentile as pct

    cfg = reduced_variant(get_arch("smollm-135m")).replace(
        dtype="float32", param_dtype="float32", num_layers=4, d_model=256,
    )
    params = init_lm(cfg, jax.random.key(0))
    R, PROMPT, MAX_GEN, SLOTS, REPEATS = 16, 32, 48, 4, 5
    PAGE, SHARED, HEAD, SPEC_K = 8, 0.6, 24, 4
    prompts, budgets = hot_prefix_stream(
        cfg.vocab_size, R, PROMPT, MAX_GEN, seed=0,
        shared_fraction=SHARED, prefix_len=HEAD,
    )

    def mk_ecfg(**kw):
        # prefill_bucket == page size: a spliced admission's uncovered tail
        # bills its true length instead of padding back up to the default
        # 32-token bucket (plain prompts are exactly 32 tokens either way).
        return EngineConfig(
            max_slots=SLOTS, max_seq=PROMPT + MAX_GEN, max_new=MAX_GEN,
            decode_chunk=8, kv_layout="paged", page_size=PAGE,
            prefill_bucket=PAGE, **kw,
        )

    engines = {
        "plain": ServeEngine(cfg, params, mk_ecfg()),
        "boosted": ServeEngine(
            cfg, params, mk_ecfg(prefix_cache=True, spec_k=SPEC_K),
            drafter=(cfg, params),
        ),
    }
    scheds = {k: ContinuousScheduler(e) for k, e in engines.items()}

    def run_arm(name, dt):
        t0 = time.time()
        comps = scheds[name].run(with_arrivals(prompts, budgets, dt))
        wall = time.time() - t0
        return sum(len(c.tokens) for c in comps) / max(wall, 1e-9), [c.latency for c in comps]

    # warm both compile caches (the boosted warmup also traces the splice
    # and spec programs), calibrate arrivals to the plain arm's service time
    for name, eng in engines.items():
        eng.warmup(prompts[0])
        run_arm(name, 0.0)
    t0 = time.time()
    run_arm("plain", 0.0)
    dt = max((time.time() - t0) / (2 * R), 1e-3)

    runs = {"plain": [], "boosted": []}
    for _ in range(REPEATS):
        for name in ("plain", "boosted"):
            runs[name].append(run_arm(name, dt))
    med = {k: sorted(v, key=lambda r: r[0])[REPEATS // 2] for k, v in runs.items()}
    # schedulers reset the engine (and its stats) at the top of every run,
    # so each stats dict now holds exactly the LAST timed pass of the stream
    ps, bs = engines["plain"].stats, engines["boosted"].stats
    admitted = max(bs["admitted"], 1)
    proposed = max(bs["draft_proposed"], 1)
    rec = {
        "status": "ok",
        "requests": R, "prompt_len": PROMPT, "budgets": budgets,
        "slots": SLOTS, "page_size": PAGE, "spec_k": SPEC_K,
        "shared_fraction": SHARED, "prefix_len": HEAD,
        "arrival_dt_s": round(dt, 4),
        "plain_tok_per_s": round(med["plain"][0], 2),
        "boosted_tok_per_s": round(med["boosted"][0], 2),
        "speedup": round(med["boosted"][0] / max(med["plain"][0], 1e-9), 3),
        "plain_p50_s": round(pct(med["plain"][1], 50), 4),
        "plain_p95_s": round(pct(med["plain"][1], 95), 4),
        "boosted_p50_s": round(pct(med["boosted"][1], 50), 4),
        "boosted_p95_s": round(pct(med["boosted"][1], 95), 4),
        # the headline: prefill work per pass of the identical stream
        "plain_prefill_tokens": ps["prefill_tokens"],
        "boosted_prefill_tokens": bs["prefill_tokens"],
        "plain_pages_allocated": ps["pages_allocated"],
        "boosted_pages_allocated": bs["pages_allocated"],
        "plain_prefill_dispatches": ps["prefill_dispatches"],
        "boosted_prefill_dispatches": bs["prefill_dispatches"],
        "prefix_hit_rate": round(bs["spliced_admissions"] / admitted, 3),
        "spliced_admissions": bs["spliced_admissions"],
        "spliced_pages": bs["spliced_pages"],
        "cow_copies": bs["cow_copies"],
        "draft_acceptance_rate": round(bs["draft_accepted"] / proposed, 3),
        "spec_steps": bs["spec_steps"],
        "jax_backend": jax.default_backend(),
    }
    log.info(
        "specpath: boosted=%.1f tok/s plain=%.1f tok/s speedup=%.2fx | "
        "prefill tokens %d->%d pages %d->%d dispatches %d->%d | "
        "hit rate %.0f%% (%d spliced pages, %d CoW) acceptance %.0f%%",
        rec["boosted_tok_per_s"], rec["plain_tok_per_s"], rec["speedup"],
        rec["plain_prefill_tokens"], rec["boosted_prefill_tokens"],
        rec["plain_pages_allocated"], rec["boosted_pages_allocated"],
        rec["plain_prefill_dispatches"], rec["boosted_prefill_dispatches"],
        100 * rec["prefix_hit_rate"], rec["spliced_pages"], rec["cow_copies"],
        100 * rec["draft_acceptance_rate"],
    )
    out["specpath:prefix_spec_vs_plain"] = rec


def _ensemblepath_setup(args):
    """Parse --ks into the K sweep (setup hook)."""
    spec = getattr(args, "ks", "") or "8,32"
    return {"ks": [int(k) for k in spec.split(",")]}


def pair_ensemblepath(out, args=None, ctx=None):
    """Grouped-ensemble A/B (the ClientBank PR's headline number): the SAME
    fused Co-Boosting epoch program on a MIXED-ARCH live market, client
    forwards routed through the grouped ClientBank (one vmap per arch group,
    O(#groups) trace) vs the K-way python-unrolled loop (O(K) trace). Same
    PRNG stream, so the final server params double as the parity check.

    The headline is END-TO-END epochs/sec for a quick-scale run, compile
    included: the bank's O(#groups) trace collapses the unrolled program's
    trace+compile cost, which at K=32 dwarfs the steady-state epochs of a
    short run (and grows with K, while the bank's stays flat). Steady-state
    s/epoch and trace+compile seconds are reported separately so the two
    effects stay distinguishable. Sweeps K via --ks (default 8,32; the full
    story adds 64)."""
    import dataclasses as _dc
    import time as _time
    from functools import partial

    import jax
    import jax.numpy as jnp

    from repro.config.train import OFLConfig
    from repro.core import default_image_setup, run_coboosting
    from repro.data import make_synth_images
    from repro.fed import build_market_grouped
    from repro.models.cnn import cnn_apply, init_cnn

    classes, shape = 4, (8, 8, 3)
    SHORT, LONG = 2, 10
    x, y = make_synth_images(0, classes, 40, shape)
    for K in (ctx or _ensemblepath_setup(args))["ks"]:
        cfg = OFLConfig(
            num_clients=K, local_epochs=1, local_batch_size=16,
            gen_iters=3, batch_size=16, latent_dim=8, buffer_batches=4,
        )
        archs = [("mlp", "cnn2")[k % 2] for k in range(K)]  # 2 arch groups
        bank, bank_params, _, _ = build_market_grouped(0, x, y, cfg, classes, archs=archs)
        params = bank.unstack_params(bank_params)
        applies = [bank.client_apply(k) for k in range(K)]
        server_apply = partial(cnn_apply, "mlp")

        def run(impl, epochs):
            # each call builds fresh jitted programs, so one wall-clock run
            # is exactly trace+compile + epochs * steady
            c = _dc.replace(cfg, epochs=epochs, ensemble_impl=impl)
            sp = init_cnn(jax.random.key(99), "mlp", classes, shape)
            gen_apply, gp = default_image_setup(jax.random.key(5), c, classes, shape)
            t0 = _time.time()
            st = run_coboosting(
                applies, params, server_apply, sp, gen_apply, gp, c, classes,
                jax.random.key(0),
            )
            jax.block_until_ready(st.server_params)
            return _time.time() - t0, st

        rec = {"status": "ok", "epochs": LONG, "num_clients": K,
               "num_groups": bank.num_groups, "jax_backend": jax.default_backend()}
        finals = {}
        for impl in ("looped", "grouped"):
            t_long, st = run(impl, LONG)
            t_short, _ = run(impl, SHORT)
            finals[impl] = st.server_params
            steady = max(t_long - t_short, 1e-9) / (LONG - SHORT)
            rec[f"{impl}_epochs_per_sec"] = round(LONG / t_long, 3)
            rec[f"{impl}_steady_s_per_epoch"] = round(steady, 3)
            rec[f"{impl}_compile_s"] = round(max(t_long - LONG * steady, 0.0), 3)
        rec["speedup"] = round(
            rec["grouped_epochs_per_sec"] / rec["looped_epochs_per_sec"], 3
        )
        rec["compile_speedup"] = round(
            rec["looped_compile_s"] / max(rec["grouped_compile_s"], 1e-9), 3
        )
        rec["server_params_max_diff"] = float(
            max(
                jnp.max(jnp.abs(u.astype(jnp.float32) - v.astype(jnp.float32)))
                for u, v in zip(
                    jax.tree_util.tree_leaves(finals["looped"]),
                    jax.tree_util.tree_leaves(finals["grouped"]),
                )
            )
        )
        log.info(
            "ensemblepath K=%d: grouped=%.2f ep/s looped=%.2f ep/s speedup=%.2fx "
            "(compile %.1fs vs %.1fs, steady %.2f vs %.2f s/ep) parity=%.2e (%d groups)",
            K, rec["grouped_epochs_per_sec"], rec["looped_epochs_per_sec"],
            rec["speedup"], rec["grouped_compile_s"], rec["looped_compile_s"],
            rec["grouped_steady_s_per_epoch"], rec["looped_steady_s_per_epoch"],
            rec["server_params_max_diff"], rec["num_groups"],
        )
        out[f"ensemblepath:K{K}"] = rec


def _ensemblepath_report(out):
    """Report hook: one summary line over the K sweep."""
    recs = {k: v for k, v in out.items() if k.startswith("ensemblepath:")}
    if recs:
        log.info(
            "ensemblepath summary: %s",
            {k.split(":")[1]: f'{v["speedup"]}x' for k, v in recs.items()},
        )


@dataclasses.dataclass(frozen=True)
class PairSpec:
    """One registry entry: ``setup(args) -> ctx`` builds shared context,
    ``run(out, args, ctx)`` fills ``out`` with records, ``report(out)``
    prints a cross-record summary. Legacy single-argument pair functions are
    adapted via :func:`_nullary`."""

    help: str
    run: Callable
    setup: Optional[Callable] = None
    report: Optional[Callable] = None

    def execute(self, out, args):
        ctx = self.setup(args) if self.setup else None
        self.run(out, args, ctx)
        if self.report:
            self.report(out)


def _nullary(fn):
    """Adapt a classic ``fn(out)`` pair function to the hook signature."""
    return lambda out, args, ctx: fn(out)


PAIRS = {
    "qwen3moe": PairSpec(
        help="MoE dryrun hillclimb: qwen3-moe-235b x train_4k (worst roofline)",
        run=_nullary(pair_qwen3moe),
    ),
    "mixtral": PairSpec(
        help="MoE dryrun hillclimb: mixtral-8x7b x train_4k (most collective-bound)",
        run=_nullary(pair_mixtral),
    ),
    "coboost": PairSpec(
        help="LM-scale Co-Boosting distillation dryrun: granite-3-2b x train_4k",
        run=_nullary(pair_coboost),
    ),
    "epochdrv": PairSpec(
        help="fused single-dispatch epoch engine vs legacy per-batch loop (live market)",
        run=_nullary(pair_epochdrv),
    ),
    "kernelpath": PairSpec(
        help="Pallas fused-loss kernels vs pure-jnp ref under the fused epoch engine",
        run=_nullary(pair_kernelpath),
    ),
    "servepath": PairSpec(
        help="continuous-batching engine vs fused static-batch serving",
        run=_nullary(pair_servepath),
    ),
    "decodepath": PairSpec(
        help="paged KVPool + flash-decode vs dense per-slot KV + SDPA",
        run=_nullary(pair_decodepath),
    ),
    "fleetpath": PairSpec(
        help="routed fleet (2 replicas, one disaggregated pair) vs monolithic engine",
        run=_nullary(pair_fleetpath),
    ),
    "specpath": PairSpec(
        help="radix prefix cache + speculative decoding vs plain paged engine "
             "on hot-prefix traffic",
        run=_nullary(pair_specpath),
    ),
    "ensemblepath": PairSpec(
        help="grouped ClientBank ensemble vs K-way looped client forwards (mixed archs)",
        run=pair_ensemblepath,
        setup=_ensemblepath_setup,
        report=_ensemblepath_report,
    ),
}


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--pair", default="all", choices=list(PAIRS) + ["all"])
    p.add_argument("--list-pairs", action="store_true", help="print the registry and exit")
    p.add_argument("--ks", default="", help="ensemblepath client-count sweep, e.g. 8,32,64")
    p.add_argument("--out", default="results/perf_hillclimb.json")
    args = p.parse_args()
    if args.list_pairs:
        for name, spec in PAIRS.items():
            print(f"{name:14s} {spec.help}")
        return
    out = {}
    for name, spec in PAIRS.items():
        if args.pair in (name, "all"):
            spec.execute(out, args)
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1, default=str)


if __name__ == "__main__":
    main()
