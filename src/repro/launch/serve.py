"""Serving driver for the distilled server LM: continuous-batching fleet
(default) or the fused static-batch baseline.

    # continuous batching: staggered requests through the slot engine
    # (paged KV pool + flash-decode by default; --kv-layout dense for the
    # per-slot-rectangle SDPA baseline)
    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --reduced \
        --engine continuous --requests 8 --request-rate 20 --max-slots 4 \
        --page-size 16 --pool-pages 0

    # serving FLEET: N engine replicas behind the least-loaded router, each
    # replica optionally a disaggregated prefill/decode pair on disjoint
    # mesh halves (needs >= 2 devices per replica to actually split)
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --reduced \
        --engine continuous --replicas 2 --disagg --requests 8

    # static baseline: one batch, prefill + single-dispatch decode
    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b \
        --reduced --engine static --batch 4 --prompt-len 64 --gen 32

Argument validation fails fast — encoder-only archs, vlm continuous
serving, ``--disagg`` with the dense KV layout, and unsupported static mesh
shapes are rejected with a clear message BEFORE any device allocation, and
the exact fleet EngineConfig/KVPool pair is dry-constructed pre-device.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.config import get_arch, reduced_variant
from repro.data import make_token_stream
from repro.launch.mesh import (
    disagg_submeshes,
    make_fleet_mesh,
    make_host_mesh,
    make_production_mesh,
    replica_meshes,
)
from repro.models import group_pattern, init_lm
from repro.serve import (
    ContinuousScheduler,
    EngineConfig,
    FleetRouter,
    KVPool,
    Request,
    ServeEngine,
    hot_prefix_stream,
    latency_summary,
    static_generate,
)
from repro.kernels import policy_from_flags
from repro.utils import get_logger
from repro.utils.compile_cache import enable_compile_cache

log = get_logger("serve")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default="smollm-135m")
    p.add_argument("--engine", default="continuous", choices=("continuous", "static"))
    p.add_argument("--reduced", action="store_true")
    p.add_argument("--mesh", default="host", choices=("host", "production", "multipod"))
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--prompt-len", type=int, default=64)
    p.add_argument("--gen", type=int, default=32)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--backend", default=None,
                   choices=("auto", "pallas", "pallas-interpret", "ref"),
                   help="kernel backend for every dispatched op (attn + decode)")
    p.add_argument("--attn-backend", default=None,
                   choices=("auto", "pallas", "pallas-interpret", "ref"),
                   help="DEPRECATED: use --backend (this alias sets only the attn op)")
    # static arm
    p.add_argument("--batch", type=int, default=4)
    # continuous arm
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--request-rate", type=float, default=0.0,
                   help="arrivals per second (0 = all at t=0)")
    p.add_argument("--max-slots", type=int, default=4)
    p.add_argument("--decode-chunk", type=int, default=8)
    # fleet topology (continuous arm)
    p.add_argument("--replicas", type=int, default=1,
                   help="engine replicas behind the least-loaded router "
                        "(each replica shards over its own mesh slice)")
    p.add_argument("--disagg", action="store_true",
                   help="split each replica into a disaggregated prefill/decode "
                        "worker pair (paged KV layout only; the pair colocates "
                        "on a single-device replica)")
    # prefix cache + speculative decoding (continuous arm)
    p.add_argument("--prefix-cache", action="store_true",
                   help="radix prefix cache over refcounted KV pages: hot "
                        "admissions splice resident prompt pages and prefill "
                        "only the uncovered tail (paged layout only)")
    p.add_argument("--spec-decode", action="store_true",
                   help="speculative decoding: a small drafter proposes "
                        "--spec-k tokens per step, the target verifies them "
                        "in one batched forward (greedy/temperature 0 only)")
    p.add_argument("--drafter", default="smollm-135m",
                   help="registry arch drafting for --spec-decode (reduced "
                        "alongside --reduced; must share the target's vocab "
                        "and be attention-only with a full cache)")
    p.add_argument("--spec-k", type=int, default=4,
                   help="draft tokens per verify step for --spec-decode")
    p.add_argument("--hot-fraction", type=float, default=0.0,
                   help="fraction of requests sharing a hot prompt prefix "
                        "(exercises --prefix-cache; 0 = fully cold traffic)")
    # paged KV pool (continuous arm)
    p.add_argument("--kv-layout", default="paged", choices=("paged", "dense"),
                   help="paged: KVPool + flash-decode; dense: per-slot rectangle + SDPA")
    p.add_argument("--page-size", type=int, default=16,
                   help="tokens per KV page (power of two)")
    p.add_argument("--pool-pages", type=int, default=0,
                   help="KV pool capacity in pages (0 = full per-slot capacity)")
    p.add_argument("--decode-backend", default=None,
                   choices=("auto", "pallas", "pallas-interpret", "ref"),
                   help="DEPRECATED: use --backend (this alias sets only the "
                        "paged decode op)")
    # telemetry (repro.obs) — off by default, zero-cost when off
    p.add_argument("--metrics-out", default=None, metavar="PATH.jsonl",
                   help="dump the metrics registry as JSONL (plus a .prom "
                        "Prometheus-text sibling) at exit; also routes every "
                        "replica's stats into one shared registry with "
                        "replica labels")
    p.add_argument("--trace-out", default=None, metavar="PATH.json",
                   help="record host-side spans (route/admit/prefill/handoff/"
                        "decode-chunk/...) and dump Chrome trace-event JSON "
                        "(Perfetto-loadable) at exit")
    p.add_argument("--profile-dir", default=None,
                   help="also run a JAX profiler trace into this directory, "
                        "bridging every span to a TraceAnnotation so host "
                        "and device timelines line up")
    return p


def _finalize_telemetry(args, engines=()) -> None:
    """Publish end-of-run KV/prefix gauges and dump the artifacts the flags
    asked for (the validator in :mod:`repro.obs.validate` gates them in CI)."""
    for eng in engines:
        eng.publish_gauges()
    if args.profile_dir:
        jax.profiler.stop_trace()
    if args.metrics_out:
        obs.registry().dump(args.metrics_out)
        log.info("metrics snapshot -> %s (+ .prom)", args.metrics_out)
    if args.trace_out:
        obs.tracer().dump(args.trace_out)
        log.info("trace -> %s (%d events)", args.trace_out, len(obs.tracer()))


def _effective_replicas(args) -> int:
    """``--mesh multipod`` serves one fleet per pod: a decode engine is a
    single-pod program (the pod axis is a DCN boundary), so each of the two
    pods carries its own replica group behind the shared router."""
    return args.replicas * (2 if args.mesh == "multipod" else 1)


def validate_args(args, cfg) -> None:
    """Fail fast, with a clear message, before any device allocation."""
    if cfg.is_encoder_only:
        raise SystemExit(
            f"{cfg.name} is encoder-only: no autoregressive decode, nothing to "
            "serve (DESIGN.md skip). Pick a decoder arch."
        )
    if args.mesh == "multipod" and args.engine == "static":
        raise SystemExit(
            "--mesh multipod is not supported for static serving: the fused "
            "static program is single-pod (the pod axis is data-parallel "
            "replication). Use --engine continuous, which runs one engine "
            "replica group per pod behind the fleet router."
        )
    if args.prompt_len < 1 or args.gen < 1:
        raise SystemExit(f"--prompt-len ({args.prompt_len}) and --gen ({args.gen}) must be >= 1")
    if args.engine == "static" and args.batch < 1:
        raise SystemExit(f"--batch must be >= 1, got {args.batch}")
    if args.replicas < 1:
        raise SystemExit(f"--replicas must be >= 1, got {args.replicas}")
    if (args.replicas > 1 or args.disagg) and args.engine != "continuous":
        raise SystemExit(
            "--replicas/--disagg describe the continuous serving fleet; the "
            "static baseline is a single fused program. Use --engine continuous."
        )
    if args.engine == "continuous":
        if cfg.frontend == "vision":
            raise SystemExit(
                f"{cfg.name} is a vlm: the continuous engine does not thread "
                "per-request vision prefix embeddings through admission yet — "
                "use --engine static (which feeds the prefix at prefill)."
            )
        if args.max_slots < 1:
            raise SystemExit(f"--max-slots must be >= 1, got {args.max_slots}")
        if args.requests < 1:
            raise SystemExit(f"--requests must be >= 1, got {args.requests}")
        if args.request_rate < 0:
            raise SystemExit(f"--request-rate must be >= 0, got {args.request_rate}")
        if args.decode_chunk < 1:
            raise SystemExit(f"--decode-chunk must be >= 1, got {args.decode_chunk}")
        if args.kv_layout == "paged" and args.pool_pages < 0:
            raise SystemExit(f"--pool-pages must be >= 0, got {args.pool_pages}")
        if args.disagg and args.kv_layout == "dense":
            raise SystemExit(
                '--disagg requires --kv-layout paged: the prefill->decode '
                "handoff moves sealed KV PAGES between worker pools, and the "
                "dense per-slot rectangle has no page units to hand off."
            )
        if args.prefix_cache and args.kv_layout == "dense":
            raise SystemExit(
                "--prefix-cache requires --kv-layout paged: prefix sharing IS "
                "page-table splicing — the dense per-slot rectangle has no "
                "page units to share."
            )
        if not 0.0 <= args.hot_fraction <= 1.0:
            raise SystemExit(f"--hot-fraction must be in [0, 1], got {args.hot_fraction}")
        if args.spec_decode:
            if args.spec_k < 1:
                raise SystemExit(f"--spec-k must be >= 1, got {args.spec_k}")
            if args.kv_layout != "paged":
                raise SystemExit(
                    "--spec-decode requires --kv-layout paged: the batched "
                    "verify is an extend over the page-table cache view."
                )
            if args.temperature > 0.0:
                raise SystemExit(
                    "--spec-decode requires --temperature 0: the accept-"
                    "longest-greedy-run verify is a greedy parity contract."
                )
            dcfg = _drafter_config(args)
            non_attn = sorted({m for m, _ in group_pattern(dcfg) if m != "attn"})
            if non_attn:
                raise SystemExit(
                    f"--drafter {dcfg.name} has {non_attn} mixers: a recurrent "
                    "carry cannot roll back past a rejected draft. Draft with "
                    "an attention-only arch."
                )
            if dcfg.sliding_window > 0:
                raise SystemExit(
                    f"--drafter {dcfg.name} uses a sliding window "
                    f"({dcfg.sliding_window}): the ring cache cannot roll back "
                    "rejected drafts (stale writes alias earlier positions). "
                    "Draft with a full-attention arch."
                )
            if dcfg.vocab_size != cfg.vocab_size:
                raise SystemExit(
                    f"--drafter {dcfg.name} vocab ({dcfg.vocab_size}) does not "
                    f"match {cfg.name} ({cfg.vocab_size}): drafted token ids "
                    "would be meaningless to the verifier."
                )
        # dry-construct the exact EngineConfig (and, for the paged layout,
        # the KVPool — which bills the pool floor against the MODEL's cache
        # length) that every fleet replica will build: both are pure-host,
        # so the full paged consistency matrix (including disagg) dies HERE,
        # not after init_lm
        try:
            ecfg = _continuous_engine_config(args)
            has_attn = any(m == "attn" for m, _ in group_pattern(cfg))
            if args.disagg and not has_attn:
                raise ValueError(
                    f"{cfg.name} has no attention layers: its serving state "
                    "degrades to the dense layout, which has no page units to "
                    "hand off — --disagg needs an attention arch."
                )
            if args.kv_layout == "paged" and has_attn:  # pure-SSM runs dense
                KVPool(cfg, ecfg)
        except ValueError as ex:
            raise SystemExit(str(ex))


def run_static(args, cfg, params) -> None:
    data = make_token_stream(args.seed, cfg.vocab_size, args.batch, args.prompt_len)
    batch = {"tokens": jnp.asarray(data["tokens"][:, : args.prompt_len])}
    if cfg.family == "vlm":
        rng = np.random.RandomState(args.seed)
        batch["prefix"] = jnp.asarray(
            rng.randn(args.batch, cfg.num_prefix_tokens, cfg.frontend_dim).astype(np.float32) * 0.02
        )
    # compile, then time: prefill + whole decode is ONE dispatch; tokens
    # accumulate on device (no per-token host sync) and cross once at the end
    gen_fn = lambda: static_generate(
        params, cfg, batch, args.gen, temperature=args.temperature,
        key=jax.random.key(args.seed),
    )
    jax.block_until_ready(gen_fn())
    t0 = time.time()
    out = np.asarray(gen_fn())
    dt = time.time() - t0
    toks = args.batch * args.gen
    log.info("static: %d tokens in %.3fs (%.1f tok/s, 1 dispatch)", toks, dt, toks / max(dt, 1e-9))
    log.info("sample continuation (seq 0): %s", out[0, :16].tolist())
    _finalize_telemetry(args)


def _drafter_config(args):
    """The drafter ModelConfig for --spec-decode: reduced alongside the
    target (a full-size drafter against a reduced target would be slower
    than the thing it accelerates)."""
    dcfg = get_arch(args.drafter)
    if args.reduced:
        dcfg = reduced_variant(dcfg).replace(dtype="float32", param_dtype="float32")
    return dcfg


def _continuous_engine_config(args) -> EngineConfig:
    max_seq = args.prompt_len + args.gen
    if args.kv_layout == "paged":
        # the page-table extent must recover the logical cache length exactly
        max_seq = -(-max_seq // args.page_size) * args.page_size
    return EngineConfig(
        max_slots=args.max_slots,
        max_seq=max_seq,
        max_new=args.gen,
        decode_chunk=args.decode_chunk,
        temperature=args.temperature,
        seed=args.seed,
        kv_layout=args.kv_layout,
        page_size=args.page_size,
        pool_pages=args.pool_pages,
        disagg=args.disagg,
        prefix_cache=args.prefix_cache,
        spec_k=args.spec_k if args.spec_decode else 0,
    )


def build_fleet(args, cfg, params) -> list:
    """Construct the engine replicas. With more than one device the fleet
    mesh splits them ``replicas × (data=1) × model`` and each engine shards
    over its slice (``--disagg`` further halves a slice into the prefill and
    decode workers' submeshes); on one device the replicas colocate meshless
    (distinct pools and programs, shared device) — same topology, same
    router, degenerate placement."""
    replicas = _effective_replicas(args)
    ecfg = _continuous_engine_config(args)
    drafter = None
    if args.spec_decode:
        dcfg = _drafter_config(args)
        drafter = (dcfg, init_lm(dcfg, jax.random.key(args.seed + 1)))
    n_dev = len(jax.devices())
    if n_dev > 1 and replicas > 1:
        subs = replica_meshes(make_fleet_mesh(replicas))
    else:
        subs = [None] * replicas
    # with --metrics-out every replica's stats land in the process-global
    # registry under its replica label (one snapshot for the whole fleet);
    # without it each engine keeps its private always-on registry
    registry = obs.registry() if args.metrics_out else None
    engines = []
    for i, sub in enumerate(subs):
        pmesh = dmesh = sub
        if args.disagg and sub is not None:
            pmesh, dmesh = disagg_submeshes(sub)
        engines.append(
            ServeEngine(
                cfg, params, ecfg, mesh=dmesh, prefill_mesh=pmesh, drafter=drafter,
                registry=registry, replica=i,
            )
        )
    return engines


def run_continuous(args, cfg, params) -> None:
    dt = 1.0 / args.request_rate if args.request_rate > 0 else 0.0
    if args.hot_fraction > 0:
        prompts, _ = hot_prefix_stream(
            cfg.vocab_size, args.requests, args.prompt_len, args.gen,
            seed=args.seed, shared_fraction=args.hot_fraction,
        )
    else:
        data = make_token_stream(args.seed, cfg.vocab_size, args.requests, args.prompt_len)
        prompts = [
            data["tokens"][i, : args.prompt_len].astype(np.int32)
            for i in range(args.requests)
        ]
    requests = [
        Request(rid=i, tokens=p, max_new_tokens=args.gen, arrival=i * dt)
        for i, p in enumerate(prompts)
    ]
    engines = build_fleet(args, cfg, params)
    sched = (
        ContinuousScheduler(engines[0]) if len(engines) == 1 else FleetRouter(engines)
    )
    # compile every admit size + the chunk program on every replica before
    # timing (replicas over identical mesh slices share the compile cache)
    for eng in engines:
        eng.warmup(requests[0].tokens, min(2, args.gen))
    t0 = time.time()
    completions = sched.run(requests)
    wall = time.time() - t0
    # one summary shape for every path — the N=1 ContinuousScheduler run
    # reports the same queue-wait split the fleet always has (the deferral
    # latency a single tight engine causes is just as real as a router's)
    s = latency_summary(completions, wall)
    log.info(
        "fleet[%d%s]: %d reqs, %d tokens in %.3fs (%.1f tok/s) "
        "p50=%.3fs p95=%.3fs queue-wait p50=%.3fs p95=%.3fs",
        len(engines), "+disagg" if args.disagg else "",
        len(completions), int(s["tokens"]), wall, s["tok_per_s"],
        s["p50_s"], s["p95_s"], s["queue_wait_p50_s"], s["queue_wait_p95_s"],
    )
    for i, eng in enumerate(engines):
        served = sum(1 for c in completions if c.replica == i)
        log.info(
            "replica %d: %d reqs, %d decode chunks, %d host syncs, %d prefills, "
            "%d handoffs",
            i, served, eng.stats["decode_chunks"], eng.stats["host_syncs"],
            eng.stats["prefill_dispatches"], eng.stats["handoffs"],
        )
        if eng.pool is not None:
            log.info(
                "replica %d kv pool: %d pages x %d tokens (%s layout), "
                "%d decode-time appends",
                i, eng.pool.n_pages, eng.pool.page_size, eng.layout,
                eng.stats["page_appends"],
            )
        if args.prefix_cache:
            admitted = max(eng.stats["admitted"], 1)
            log.info(
                "replica %d prefix cache: %d/%d admissions spliced "
                "(hit rate %.0f%%), %d pages reused, %d CoW copies",
                i, eng.stats["spliced_admissions"], eng.stats["admitted"],
                100.0 * eng.stats["spliced_admissions"] / admitted,
                eng.stats["spliced_pages"], eng.stats["cow_copies"],
            )
        if args.spec_decode:
            proposed = max(eng.stats["draft_proposed"], 1)
            log.info(
                "replica %d spec decode: %d verify steps, %d/%d drafts "
                "accepted (%.0f%%)",
                i, eng.stats["spec_steps"], eng.stats["draft_accepted"],
                eng.stats["draft_proposed"],
                100.0 * eng.stats["draft_accepted"] / proposed,
            )
    if isinstance(sched, FleetRouter) and len(engines) > 1:
        log.info(
            "router: %d routed, %d requeued-on-defer, %d prefix-affinity hits",
            sched.stats["routed"], sched.stats["requeued"],
            sched.stats["affinity_hits"],
        )
    log.info("sample continuation (rid 0): %s", completions[0].tokens[:16].tolist())
    _finalize_telemetry(args, engines)


def main() -> None:
    args = build_parser().parse_args()
    cfg = get_arch(args.arch)
    if args.reduced:
        # reduce BEFORE validating: the paged-pool floor bills against the
        # model's actual cache length (a reduced variant clamps the window)
        cfg = reduced_variant(cfg).replace(dtype="float32", param_dtype="float32")
    validate_args(args, cfg)  # before any device/mesh work
    enable_compile_cache()
    obs.configure(
        metrics=bool(args.metrics_out),
        trace=bool(args.trace_out),
        profile_dir=args.profile_dir,
    )
    cfg = cfg.replace(backend=policy_from_flags(
        backend=args.backend,
        attn_backend=args.attn_backend,
        decode_backend=args.decode_backend,
    ))
    fleet = args.engine == "continuous" and (
        _effective_replicas(args) > 1 or args.disagg
    )
    if fleet:
        # no global mesh context: each replica shards params/state against
        # ITS submesh explicitly (a context mesh with a replica axis would
        # leak into init-time sharding constraints)
        params = init_lm(cfg, jax.random.key(args.seed))
        run_continuous(args, cfg, params)
        return
    mesh = {"host": make_host_mesh, "production": make_production_mesh}[args.mesh]()
    with jax.set_mesh(mesh):
        params = init_lm(cfg, jax.random.key(args.seed))
        if args.engine == "static":
            run_static(args, cfg, params)
        else:
            run_continuous(args, cfg, params)


if __name__ == "__main__":
    main()
