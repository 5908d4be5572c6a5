"""The serve cell kind: the program's continuous-batching engine under
open-loop traffic.

Set-up builds one engine the way the serving launcher builds a replica
(``repro.launch.serve``: its parser, its argument audit and its
``EngineConfig``, with ``prefill_bucket`` from the configuration, for which
the launcher has no flag; one ``ServeEngine`` on one device, as
``build_fleet`` makes it) behind its ``ContinuousScheduler``, on weights
from the reference's ``make_weights``. It then warms every program the
window can reach: the prefill and adoption of each prompt bucket at each
power-of-two burst size up to the slot count, the decode chunk, and the
page-table update of every size; and runs a short checked burst through the
scheduler.

The window offers the traffic (``benchlib.traffic``) for ``--seconds``:
each request is due at its arrival time, counted from the window's start on
the scheduler's own clock, and the window closes when every offered request
has finished. Every metric is over exactly those requests. A request that
has not finished a minute (the cell's ``tail_s``) after the offer's end, or
that comes back short or with a token outside the vocabulary, counts in
``failed``.

``correct`` compares what the window served, once the window has closed
and the engine is freed: for a sample of finished requests drawn from the
seed, with the longest among them, the plain reference runs once over each
prompt with its served tokens, and each served token's reference logit is
read against the reference's best at the position it was chosen:
``served_token_gap`` is the widest gap. The first token of each comes from
the prefill program, the others from the paged decode chunks over the
prefill's pages; the notes give the widest gap of each.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import time

import numpy as np

from . import common, traffic

CHUNK_PROGRAM = "_chunk_fn"  # the decode chunk's jitted function, as the trace names its module


class Deadline(RuntimeError):
    """The window's requests did not all finish in time."""


class WindowClock:
    """The scheduler's clock for one window (``MonotonicClock``'s interface):
    seconds since it was made, a ``bench.sleep`` span while the scheduler
    waits for the next arrival, and ``Deadline`` past ``deadline_s``."""

    def __init__(self, jax, deadline_s: float):
        self._jax = jax
        self.deadline_s = deadline_s
        self._t0 = time.monotonic()
        self._last = 0.0
        # the longest the scheduler went between two reads, sleeps left out,
        # and when that step ended
        self.longest_step_s = self.longest_step_at_s = 0.0

    def now(self) -> float:
        t = time.monotonic() - self._t0
        if t - self._last > self.longest_step_s:
            self.longest_step_s, self.longest_step_at_s = t - self._last, t
        self._last = t
        if t > self.deadline_s:
            raise Deadline(f"requests unfinished {t - self.deadline_s:.1f} s past the deadline")
        return t

    def sleep(self, dt: float) -> None:
        if dt > 0:
            with self._jax.profiler.TraceAnnotation("bench.sleep"):
                time.sleep(dt)
        self._last = time.monotonic() - self._t0


# -- the engine's private state ------------------------------------------------
# Every read the driver makes of state the program keeps private, in one place,
# so that a change to the engine breaks here and nowhere else. Each goes once
# the program gives it a public accessor (PERF.md, section 7).


def launcher_engine_config(launch, args):
    """The serving launcher's EngineConfig for its parsed arguments."""
    return launch._continuous_engine_config(args)


def page_table(eng):
    """The decode worker's page table on the device."""
    return eng.decode._state.page_table


def resident_slots(eng) -> dict:
    """The decode worker's resident slots: slot -> (prompt length, budget)."""
    return eng.decode._meta


def chunk_hlo(eng) -> str:
    """The HLO text of the compiled decode chunk program."""
    d = eng.decode
    return d._chunk_jit.lower(d.params, d._state).compile().as_text()


def model_config(cfg: dict):
    """The program's ModelConfig for the configuration file: the registry's
    entry named by ``arch``, then the published Llama-style sizes, the
    RMSNorm epsilon and the precision from the file (``head_dim`` where the
    file gives one, else ``hidden_size / num_attention_heads``), then the
    file's ``program`` object, a map of ModelConfig field to value, for
    whatever else the model states (experts held, latent widths, per-layer
    attention kinds, multipliers). A name there that is not a ModelConfig
    field is an error."""
    from repro.config import ModelConfig, get_arch
    from repro.kernels import BackendPolicy

    program = cfg.get("program", {})
    unknown = sorted(set(program) - {f.name for f in dataclasses.fields(ModelConfig)})
    if unknown:
        raise ValueError(f"configuration {cfg['name']!r}: 'program' names {unknown}, which are not ModelConfig fields")
    return get_arch(cfg["arch"]).replace(
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"], num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim", cfg["hidden_size"] // cfg["num_attention_heads"]),
        d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], tie_embeddings=cfg["tie_word_embeddings"],
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"], act=cfg["hidden_act"],
        dtype=cfg["dtype"], param_dtype=cfg["param_dtype"],
        backend=BackendPolicy(default=cfg["backend"]),
    ).replace(**program)


def engine_config(mcfg, cfg: dict, seed: int):
    """The EngineConfig the serving launcher builds for these settings."""
    from repro.launch import serve as launch

    e = cfg["engine"]
    argv = [
        "--arch", cfg["arch"], "--engine", "continuous", "--max-slots", str(e["max_slots"]),
        "--prompt-len", str(e["max_seq"] - e["max_new"]), "--gen", str(e["max_new"]),
        "--decode-chunk", str(e["decode_chunk"]), "--kv-layout", e["kv_layout"],
        "--page-size", str(e["page_size"]), "--temperature", str(e["temperature"]),
        "--seed", str(seed & 0x7FFFFFFF),
    ] + (["--prefix-cache"] if e["prefix_cache"] else []) + (["--spec-decode"] if e["spec_decode"] else [])
    args = launch.build_parser().parse_args(argv)
    launch.validate_args(args, mcfg)
    ecfg = dataclasses.replace(launcher_engine_config(launch, args), prefill_bucket=e["prefill_bucket"])
    if ecfg.max_seq != e["max_seq"]:
        raise ValueError(f"the launcher makes max_seq {ecfg.max_seq}, the configuration states {e['max_seq']}")
    return ecfg


class Program:
    """One engine and its scheduler for one configuration, reusable across
    windows."""

    def __init__(self, jax, cfg: dict, seed: int):
        self.jax, self.cfg = jax, cfg
        self.mcfg = model_config(cfg)
        self.ecfg = engine_config(self.mcfg, cfg, seed)
        self.engine = self.sched = None
        self.warm_notes = {}

    def start(self, weights) -> None:
        from repro.serve import ContinuousScheduler, ServeEngine

        self.engine = ServeEngine(self.mcfg, weights, self.ecfg)
        self.sched = ContinuousScheduler(self.engine)

    def buckets(self, max_prompt: int) -> list:
        return sorted({self.engine.bucket_len(p) for p in range(1, max_prompt + 1)})

    def warm(self, max_prompt: int) -> None:
        """Compile, or load from the cache, every program the window can
        reach, and run each once."""
        eng, e = self.engine, self.ecfg
        rng = np.random.Generator(np.random.PCG64(0))
        vocab = self.mcfg.vocab_size
        bursts = {}
        t0 = time.perf_counter()
        for lb in self.buckets(max_prompt):
            n = 1
            while n <= e.max_slots:
                eng.reset()
                reqs = [(rng.integers(0, vocab, lb).astype(np.int32), 2) for _ in range(n)]
                if eng.max_admissible(reqs) < n:
                    break
                eng.admit_many(reqs)
                eng.decode_chunk()
                eng.sync()
                bursts[lb] = n
                n *= 2
        t1 = time.perf_counter()
        sizes = table_update_sizes(e.max_slots, eng.pool.pages_per_slot)
        table = page_table(eng)
        jnp = self.jax.numpy
        for n in sizes:
            idx = [0] * n
            out = table.at[jnp.asarray(idx, jnp.int32), jnp.asarray(idx, jnp.int32)].set(jnp.asarray(idx, jnp.int32))
        out.block_until_ready()
        eng.reset()
        self.warm_notes = {"buckets": {str(k): v for k, v in bursts.items()}, "table_sizes": len(sizes),
                           "programs_s": t1 - t0, "table_s": time.perf_counter() - t1}

    def serve(self, requests, clock) -> list:
        self.sched.clock = clock
        return self.sched.run(requests)

    def checked_burst(self, max_prompt: int, tail_s: float) -> int:
        """A few requests through the scheduler at once; returns how many
        came back wrong or not within ``tail_s``."""
        from repro.serve import Request

        rng = np.random.Generator(np.random.PCG64(1))
        lb = self.buckets(max_prompt)[0]
        budget = 2 * self.ecfg.decode_chunk + 1
        reqs = [Request(rid=i, tokens=rng.integers(0, self.mcfg.vocab_size, lb).astype(np.int32),
                        max_new_tokens=budget) for i in range(4)]
        try:
            comps = self.serve(reqs, WindowClock(self.jax, tail_s))
        except Deadline:
            comps = []
        return len(reqs) - len(valid(comps, {r.rid: r for r in reqs}, self.mcfg.vocab_size))

    def free(self) -> None:
        self.engine = self.sched = None
        gc.collect()


def table_update_sizes(slots: int, width: int) -> list:
    """Every length of the page-table update a decode chunk can make,
    largest first. ``_ensure_chunk_pages`` writes, in one eager scatter
    (compiled per length), every entry of each slot evicted since the last
    chunk (``width`` each) and one appended page for each resident slot
    whose next chunk crosses a page boundary (a chunk is shorter than a
    page): ``width * s + g`` entries with ``s + g <= slots``. Each length
    costs nine small programs in JAX's in-memory caches, which hold 8192:
    warming only these lengths, the common short ones last, keeps every one
    of them there."""
    return sorted({width * s + g for s in range(slots + 1) for g in range(slots - s + 1)} - {0}, reverse=True)


def valid(comps, by_rid: dict, vocab: int) -> list:
    """The completions that served their whole budget, every token in the
    vocabulary."""
    out = []
    for c in comps:
        t = np.asarray(c.tokens)
        if len(t) == by_rid[c.rid].max_new_tokens and t.min(initial=0) >= 0 and t.max(initial=0) < vocab:
            out.append(c)
    return out


def window_requests(mix: dict, seed: int, seconds: float, vocab: int):
    from repro.serve import Request

    return [Request(rid=i, tokens=toks, max_new_tokens=n, arrival=t)
            for i, (t, toks, n) in enumerate(traffic.requests(mix, seed, seconds, vocab))]


def sample(comps, by_rid: dict, seed: int, drawn: int) -> list:
    """``drawn`` finished requests drawn from the seed, and the longest."""
    if not comps:
        return []
    longest = max(comps, key=lambda c: (len(by_rid[c.rid].tokens) + len(c.tokens), -c.rid))
    rest = [c for c in comps if c is not longest]
    rng = np.random.Generator(np.random.PCG64(seed))
    picked = rng.choice(len(rest), size=min(drawn, len(rest)), replace=False) if rest else []
    return [longest] + [rest[int(i)] for i in sorted(picked)]


def readings(jax, ref, cfg: dict, weights, picked, by_rid: dict, max_seq: int, max_new: int,
             control: str | None = None) -> dict:
    """The numbers a run can compare over the sampled requests: the widest
    gap of every served token (``served_token_gap``), of the first tokens
    alone (chosen by the prefill program) and of the later ones (the paged
    decode chunks). With ``control``, the tokens are the ones the reference
    at that precision puts first instead."""
    if control is None:
        fn = jax.jit(lambda p, s, r, t: ref.served_gaps(p, s, r, t, cfg))
    else:
        fn = jax.jit(lambda p, s, r, t: ref.control_gaps(p, s, r, cfg, control))
    first, later = [], []
    for c in picked:
        prompt, out = by_rid[c.rid].tokens, np.asarray(c.tokens, np.int32)
        p, n = len(prompt), len(out)
        seq = np.zeros(max_seq, np.int32)
        seq[:p], seq[p:p + n] = prompt, out
        rows = p - 1 + np.minimum(np.arange(max_new), n - 1)
        served = np.zeros(max_new, np.int32)
        served[:n] = out
        gaps = np.asarray(fn(weights, seq, rows.astype(np.int32), served), np.float64)[:n]
        first.append(gaps[0])
        later.extend(gaps[1:])
    worst = lambda xs: float(np.max(xs)) if len(xs) else math.inf  # noqa: E731
    return {"served_token_gap": worst(first + later), "first_token_gap": worst(first),
            "decode_token_gap": worst(later), "tokens_compared": len(first) + len(later)}


def traced_engine(jax, eng, log: dict) -> None:
    """Spans around the engine's host calls, and the counter of each decode
    chunk's slot contexts that the flash-decode roofline reads: per step,
    the contexts of the active slots summed, and how many there were."""
    def span(name, fn):
        def call(*a, **kw):
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **kw)
        return call

    prev = {}
    real_sync = eng.sync

    def sync():
        active, n_out = real_sync()
        steps = {}
        for slot, (true_len, _) in resident_slots(eng).items():
            n0, n1 = prev.get(slot, 1), int(n_out[slot])
            for j, n in enumerate(range(n0, n1)):
                ctx, rows = steps.get(j, (0, 0))
                steps[j] = (ctx + true_len + n, rows + 1)
            if active[slot]:
                prev[slot] = n1
            else:
                prev.pop(slot, None)
        log["decode_steps"].extend(steps[j] for j in sorted(steps))
        return active, n_out

    eng.sync = span("bench.sync", sync)
    eng.decode_chunk = span("bench.chunk", eng.decode_chunk)
    eng.admit_many = span("bench.admit", eng.admit_many)
    eng.fetch = span("bench.fetch", eng.fetch)


def run_cell(ctx: dict) -> dict:
    """One run of a serve cell; ``ctx`` carries jax, the cell's files, seed,
    seconds, trace flag, devices and the start time."""
    jax = ctx["jax"]
    cfg, work, mix = ctx["config"], ctx["workload"], ctx["traffic"]
    e = cfg["engine"]
    if mix["prompt"]["max"] + mix["output"]["max"] > e["max_seq"] or mix["output"]["max"] > e["max_new"]:
        raise ValueError("the traffic's longest request does not fit the engine's max_seq and max_new")
    ref = common.reference_of(cfg["name"])
    wkey = common.seed_key(jax, ctx["seed"])

    prog = Program(jax, cfg, ctx["seed"])
    prog.start(ref.make_weights(wkey, cfg))
    prog.warm(mix["prompt"]["max"])
    burst_failed = prog.checked_burst(mix["prompt"]["max"], work["tail_s"])
    reqs = window_requests(mix, ctx["seed"], ctx["seconds"], cfg["vocab_size"])
    by_rid = {r.rid: r for r in reqs}
    log = {"decode_steps": []}
    if ctx["trace"]:
        traced_engine(jax, prog.engine, log)
    setup_s = time.perf_counter() - ctx["t_start"]

    from . import trace as tr

    clock = ctx["compile_clock"]
    c0 = clock.snapshot()
    comps, late = [], None
    wclock = WindowClock(jax, ctx["seconds"] + work["tail_s"])
    with tr.profiled(jax, ctx["trace"]) as prof:
        with jax.profiler.TraceAnnotation("bench.window"):
            t0 = time.perf_counter()
            try:
                comps = prog.serve(reqs, wclock)
            except Deadline as err:
                late = str(err)
            window = time.perf_counter() - t0
    c1 = clock.snapshot()
    mem = common.peak_memory(ctx["devices"])
    if ctx["trace"]:
        tr.attach_scopes(prof.trace, tr.hlo_scopes(chunk_hlo(prog.engine)), CHUNK_PROGRAM)
    prog.free()

    good = valid(comps, by_rid, cfg["vocab_size"])
    picked = sample(good, by_rid, ctx["seed"], work["sample_drawn"])
    weights = ref.make_weights(wkey, cfg)
    values = readings(jax, ref, cfg, weights, picked, by_rid, e["max_seq"], e["max_new"])
    checks = {name: common.check(values[name], limit) for name, limit in work["checks"].items()}

    stats = window_stats(reqs, good, window, ctx["seconds"])
    metrics = {}
    if ctx["trace"]:
        ctx.update(trace_data=prof.trace, serve={
            "requests": [(len(by_rid[c.rid].tokens), len(c.tokens)) for c in good],
            "queue_wait_s": [c.queue_wait for c in good], "decode_steps": log["decode_steps"],
            "chunk_program": CHUNK_PROGRAM,
        })
    else:
        metrics["serve_tpot_ms"] = common.metric(stats["tpot_ms"], "ms")
        metrics["serve_norm_latency_ms"] = common.metric(stats["norm_latency_ms"], "ms")
        metrics["setup_s"] = common.metric(setup_s, "s")
    return {
        "attempted": len(reqs),
        "failed": len(reqs) - len(good) + burst_failed,
        "metrics": metrics,
        "memory_peak_bytes": mem,
        "checks": checks,
        "notes": {
            "compiles_in_window": c1[0] - c0[0], "compile_s_in_window": c1[1] - c0[1],
            "late": late, "burst_failed": burst_failed, **stats,
            "first_token_gap": values["first_token_gap"], "decode_token_gap": values["decode_token_gap"],
            "tokens_compared": values["tokens_compared"], "longest_host_step_s": wclock.longest_step_s,
            "longest_host_step_at_s": wclock.longest_step_at_s, "warm": prog.warm_notes,
        },
    }


def window_stats(reqs, good, window: float, seconds: float) -> dict:
    """One window's statistics over its offered requests ``reqs`` and the
    ``good`` completions among them, ``window`` seconds from the first
    offer to the last finish, the offer lasting ``seconds``. A request that
    never came back counts with the time the window lasted.

    ``norm_latency_ms``, vLLM's normalized latency: the mean of (finish -
    due time) / output tokens. ``tpot_ms``: the mean of (finish -
    admission) / output tokens, the same without the queue wait."""
    done = {c.rid for c in good}
    lost = [r for r in reqs if r.rid not in done]
    norm = [(c.finished - c.arrival) / len(c.tokens) for c in good]
    norm += [(window - r.arrival) / r.max_new_tokens for r in lost]
    tpot = [(c.finished - c.admitted) / len(c.tokens) for c in good]
    tpot += [(window - r.arrival) / r.max_new_tokens for r in lost]
    lat, waits = [c.latency for c in good], [c.queue_wait for c in good]
    tokens = sum(len(c.tokens) for c in good)
    return {
        "norm_latency_ms": 1000.0 * float(np.mean(norm)) if norm else math.nan,
        "tpot_ms": 1000.0 * float(np.mean(tpot)) if tpot else math.nan,
        "offered": len(reqs), "finished": len(good), "window_s": window, "drain_s": window - seconds,
        "finished_by_offer_end_plus_10s": sum(c.finished <= seconds + 10.0 for c in good) / max(len(reqs), 1),
        "tokens": tokens, "tokens_per_s": tokens / window,
        "latency_p50_s": _pct(lat, 50), "latency_p95_s": _pct(lat, 95),
        "queue_wait_p50_s": _pct(waits, 50), "queue_wait_p95_s": _pct(waits, 95),
    }


def _pct(xs, q):
    return float(np.percentile(xs, q)) if len(xs) else math.nan
