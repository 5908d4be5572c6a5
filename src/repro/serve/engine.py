"""Serving engines for the distilled server LM: a prefill/decode worker pair
composed either colocated (the classic :class:`ServeEngine`) or
disaggregated behind an explicit KV handoff.

The monolithic slot engine of earlier revisions is now TWO jitted programs
owned by two workers:

  * :class:`PrefillWorker` — **compute-bound admission**: prefills a bucketed
    burst of prompts in one dispatch (padded up to a ``prefill_bucket``
    multiple so ragged lengths share compilations; the pad tail is never
    attended because decode overwrites position ``p`` before reading it),
    samples each row's first token from its true-last-prompt-position logits,
    and SEALS the result into a :class:`KVHandoff`: attention KV re-viewed as
    page units ``(G, N, n_alloc, page, KH, hd)`` plus the dense rows of any
    recurrent mixer state. A staging :class:`~repro.serve.kv_pool.KVPool`
    accounts the in-flight handoff pages (backpressure: a prefill worker
    cannot run unboundedly ahead of decode capacity); the sealed buffers
    themselves travel with the handoff.
  * :class:`DecodeWorker` — **bandwidth-bound decode**: owns the device-
    resident per-slot :class:`DecodeState` (each request lives in one of
    ``max_slots`` slots with its OWN position counter), ``adopt``s handoffs
    (pool ids allocated in ITS pool, sealed pages scattered into ITS buffers
    — pure data movement, no model forward), and runs ``lax.while_loop``
    decode chunks with on-device sampling. The host reads back only the tiny
    ``(active, n_out)`` vectors once per chunk (``sync``) and a finished
    request's token row once at eviction (``fetch``).

Because adoption is data movement, a request prefilled by one worker can
land on a DIFFERENT worker's pool than it decodes from — that is the
disaggregation seam (``EngineConfig.disagg``; paged-only, since the dense
per-slot rectangle has no page units to hand off). The classic
:class:`ServeEngine` survives as a thin colocated composition of the two
workers sharing one stats dict — the parity oracle and the default on one
device. Both compositions run the SAME two programs, so fleet==engine greedy
token parity is structural, not coincidental.

Two KV layouts (``EngineConfig.kv_layout``): **paged** (default) — a shared
page pool; admission allocates the pages the bucketed prefill fills, decode
appends a page when a slot's position crosses a page boundary (checked once
per chunk, host-side), eviction returns the slot's pages, and decode
attention takes the page-table view through the flash-decode dispatch.
**dense** — the per-slot ``(slots, cache_len, ...)`` rectangle attending via
the small SDPA path; the parity baseline, and what pure-SSM archs (nothing
to page) silently degrade to.

Inactive slots ride along in the batched decode (their position is frozen,
so they idempotently rewrite one cache location). The dense layout absorbs
those writes in the slot's own row; the paged layout re-aims every
idle/evicted slot's page-table row at the pool's never-allocated SCRATCH
page before the next chunk, because its old pages may already belong to
another slot (a stale row was a real cross-slot clobber, pinned by
``test_engine_paged_idle_slots_cannot_clobber``).

``stats`` counts dispatches and host syncs; tests pin host syncs = O(1) per
decode chunk, independent of chunk length and token count. The stats are no
longer a free dict: they are a :class:`repro.obs.StatsView` over a metrics
registry (``serve.*`` namespace, declared once in :mod:`repro.obs.names`,
labelled with the replica id) — a bare engine gets a private registry, a
fleet launcher passes one shared registry so replicas aggregate. Host-side
spans (``obs.span``) bracket every hot-path action (prefill → handoff →
adopt → decode chunk → sync); they never force a device sync, so the
O(1)-syncs-per-chunk contract is telemetry-independent.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, MutableMapping, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.models import group_pattern, init_lm_state, lm_decode, lm_extend, lm_prefill
from repro.obs import KV_GAUGES, SERVE_ENGINE_METRICS, MetricsRegistry, StatsView
from repro.serve.kv_pool import KVPool
from repro.sharding import infer_param_specs, shard_engine_state

KV_LAYOUTS = ("paged", "dense")


def sample_tokens(logits: jax.Array, key: jax.Array, temperature: float) -> jax.Array:
    """On-device sampling. logits: (B, V) -> (B,) int32. ``temperature <= 0``
    is greedy (argmax); otherwise temperature-scaled categorical."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, logits.astype(jnp.float32) / temperature, axis=-1).astype(
        jnp.int32
    )


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Continuous-batching knobs (the model itself comes from ModelConfig).

    Construction fails fast on inconsistent paged-KV knobs — BEFORE any
    device allocation (same contract as the launch arg audit)."""

    max_slots: int = 4  # concurrent sequences resident on device
    max_seq: int = 256  # per-slot cache length (prompt + generation)
    max_new: int = 64  # output-buffer width (per-request budget <= this)
    decode_chunk: int = 16  # decode steps per dispatch (and per host sync)
    prefill_bucket: int = 32  # prompts pad up to a multiple of this
    temperature: float = 0.0  # 0 => greedy
    eos_token: int = -1  # <0 => disabled (synthetic streams have no EOS)
    seed: int = 0
    kv_layout: str = "paged"  # paged (KVPool + flash-decode) | dense (SDPA)
    page_size: int = 16  # tokens per KV page (power of two)
    pool_pages: int = 0  # pool capacity; 0 => max_slots × full per-slot width
    disagg: bool = False  # prefill and decode as separate fleet workers
    prefix_cache: bool = False  # radix prefix cache over refcounted pages
    spec_k: int = 0  # speculative decoding: drafts per verify step (0 = off)

    def __post_init__(self):
        for field in ("max_slots", "max_seq", "max_new", "decode_chunk", "prefill_bucket"):
            if getattr(self, field) < 1:
                raise ValueError(f"EngineConfig.{field} must be >= 1, got {getattr(self, field)}")
        if self.kv_layout not in KV_LAYOUTS:
            raise ValueError(
                f"EngineConfig.kv_layout must be one of {KV_LAYOUTS}, got {self.kv_layout!r}"
            )
        if self.spec_k < 0:
            raise ValueError(f"EngineConfig.spec_k must be >= 0, got {self.spec_k}")
        if self.spec_k and self.temperature > 0.0:
            raise ValueError(
                "speculative decoding (spec_k > 0) requires temperature=0: the "
                "accept-longest-greedy-run verify is a GREEDY parity contract; "
                "sampled drafts would need rejection sampling the engine does "
                "not implement. Drop --spec-decode or set --temperature 0."
            )
        if self.kv_layout != "paged":
            if self.disagg:
                raise ValueError(
                    'disagg=True requires kv_layout="paged": the prefill->decode '
                    "handoff moves sealed KV PAGES between worker pools, and the "
                    "dense per-slot rectangle has no page units to hand off. Drop "
                    "--disagg or use --kv-layout paged."
                )
            if self.prefix_cache:
                raise ValueError(
                    'prefix_cache=True requires kv_layout="paged": prefix sharing '
                    "IS page-table splicing — the dense per-slot rectangle has no "
                    "page units to share. Drop --prefix-cache or use --kv-layout "
                    "paged."
                )
            return
        if self.page_size < 1 or (self.page_size & (self.page_size - 1)):
            raise ValueError(
                f"EngineConfig.page_size must be a power of two, got {self.page_size} "
                "(page offsets are bit-sliced from positions; the pool and the "
                "flash-decode BlockSpecs both assume it)"
            )
        if self.max_seq % self.page_size:
            raise ValueError(
                f"EngineConfig.max_seq={self.max_seq} must be a multiple of "
                f"page_size={self.page_size} so the page-table extent recovers the "
                "logical cache length exactly (round max_seq up)"
            )
        if self.pool_pages and self.pool_pages < self.max_slots:
            raise ValueError(
                f"pool_pages={self.pool_pages} < max_slots={self.max_slots}: "
                "every live slot needs at least one page"
            )
        # the pool-vs-burst floor needs the MODEL's cache length (an SWA ring
        # bills far fewer pages than bucket_min tokens suggest), so it lives
        # in KVPool.__init__ — still pure-host, still pre-device


class DecodeState(NamedTuple):
    """The device-resident per-slot state threaded through decode chunks."""

    kv: Any  # model state pytree, leaves (G, max_slots, ...) or paged pools
    last_tok: jax.Array  # (S, 1) int32 — last sampled token per slot
    pos: jax.Array  # (S,) int32 — position the next decode step writes
    active: jax.Array  # (S,) bool
    out: jax.Array  # (S, max_new) int32 — generated tokens per slot
    n_out: jax.Array  # (S,) int32 — tokens generated so far
    budget: jax.Array  # (S,) int32 — per-request generation budget
    rng: jax.Array  # PRNG key for sampling
    page_table: jax.Array  # (S, W) int32 — per-slot page ids ((S, 1) dummy when dense)


class KVHandoff(NamedTuple):
    """One sealed prefill burst in flight between a prefill worker and a
    decode worker. ``sealed`` carries the page-unit attention KV (and dense
    rows for recurrent mixers); ids are pool-local and never travel — the
    adopting pool assigns its own."""

    sealed: Any  # device pytree; attn leaves (G, N, n_alloc, page, KH, hd)
    first_tok: jax.Array  # (N,) int32 — first sampled token per row
    true_lens: np.ndarray  # (N,) host — true prompt lengths
    budgets: np.ndarray  # (N,) host — generation budgets
    n_alloc: int  # sealed pages per row (0 for the dense layout)
    staging_id: int  # staging-pool reservation on the source (-1 when none)
    source: Any  # the PrefillWorker that sealed this burst
    tokens: Any = None  # (N, bucket) host prompt tokens — the adopting side
    # feeds them to the speculative drafter's own prefill (and could re-derive
    # prefix-cache keys); pure metadata, never needed by the target model

    @property
    def n(self) -> int:
        return len(self.true_lens)


def bucket_len(cfg, ecfg: EngineConfig, prompt_len: int) -> int:
    """The padded prefill length a prompt compiles under."""
    if cfg.family in ("ssm", "hybrid"):
        # a recurrent carry (mamba/xlstm state) absorbs pad tokens — the
        # prefill must stop exactly at the prompt end, so recurrent archs
        # compile one prefill per distinct prompt length instead of per
        # bucket. Attention caches are position-addressed: the pad tail
        # is overwritten before it is ever attended, so bucketing is safe.
        return prompt_len
    b = ecfg.prefill_bucket
    lb = min(-(-prompt_len // b) * b, ecfg.max_seq)
    if cfg.sliding_window > 0:
        # the SWA cache is a ring of min(window, max_seq) slots holding
        # the LAST cache-len prefill positions; padding past the ring
        # length would evict real prompt tokens in favor of pad garbage.
        cl = min(cfg.sliding_window, ecfg.max_seq)
        lb = prompt_len if prompt_len > cl else min(lb, cl)
    return lb


def _engine_layout(cfg, ecfg: EngineConfig) -> str:
    has_attn = any(mixer == "attn" for mixer, _ in group_pattern(cfg))
    # pure-SSM archs have no KV to page: degrade to the dense state layout
    return ecfg.kv_layout if has_attn else "dense"


def _require_extend_capable(cfg, ecfg: EngineConfig, feature: str) -> None:
    """Both prefix sharing and speculative verify run :func:`lm_extend` —
    "prefill semantics starting mid-cache" — which only attention caches
    support: a recurrent carry cannot start mid-sequence (splice) or roll
    back rejected positions (verify), and an SWA ring wraps writes into
    pages another request may share. Fail fast, pre-device."""
    from repro.models.attention import cache_len

    non_attn = [m for m, _ in group_pattern(cfg) if m != "attn"]
    if non_attn:
        raise ValueError(
            f"{cfg.name}: {feature} requires attention-only mixers, found "
            f"{sorted(set(non_attn))} — a recurrent carry cannot be spliced "
            "mid-sequence or rolled back after a rejected draft"
        )
    if cache_len(cfg, ecfg.max_seq) != ecfg.max_seq:
        raise ValueError(
            f"{cfg.name}: {feature} requires a full (non-ring) KV cache, but "
            f"sliding_window={cfg.sliding_window} < max_seq={ecfg.max_seq} "
            "makes decode writes wrap into earlier pages — a wrapped write "
            "would land in a page another request shares"
        )


def _fresh_stats(registry: Optional[MetricsRegistry] = None, replica: int = 0) -> StatsView:
    """One engine's stats: a dict-shaped view over the ``serve.*`` metric
    namespace (every key declared once in ``repro.obs.names`` — the old
    hand-maintained literal dict could drift against the router's). The
    draft_*/spec_steps values are mirrors of on-device counters, refreshed
    at sync() — they ride the existing once-per-chunk host transfer."""
    if registry is None:
        registry = MetricsRegistry()  # private, always-on: stats must count
    return registry.view(SERVE_ENGINE_METRICS, replica=replica)


def _shard_params(params, mesh):
    """Place a per-replica copy of the params on ``mesh`` (tensor-parallel
    along the rules of sharding/partition.py)."""
    specs = infer_param_specs(params, mesh_axes=dict(mesh.shape))
    shardings = jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), specs, is_leaf=lambda x: isinstance(x, P)
    )
    return jax.device_put(params, shardings)


class PrefillWorker:
    """Compute-bound half of the serving pair: bucketed prefill admission
    sealed into :class:`KVHandoff`s. Owns its own jitted program, rng chain
    and (paged layout) a staging pool bounding in-flight handoff pages."""

    def __init__(self, cfg, params, ecfg: EngineConfig, *, mesh=None,
                 stats: Optional[MutableMapping] = None, replica: int = 0):
        self.cfg = cfg
        self.ecfg = ecfg
        self.mesh = mesh
        self.replica = replica
        self.params = _shard_params(params, mesh) if mesh is not None else params
        self.layout = _engine_layout(cfg, ecfg)
        self.staging: Optional[KVPool] = KVPool(cfg, ecfg) if self.layout == "paged" else None
        self.stats = stats if stats is not None else _fresh_stats(replica=replica)
        self._prefill_jit = jax.jit(self._prefill_fn)
        self.reset()

    def reset(self) -> None:
        self._rng = jax.random.key(self.ecfg.seed + 1)  # decode chain owns seed
        if self.staging is not None:
            self.staging.reset()

    def bucket_len(self, prompt_len: int) -> int:
        return bucket_len(self.cfg, self.ecfg, prompt_len)

    def _prefill_fn(self, params, rng, tokens, true_lens):
        """ONE dispatch per (bucket, burst-size) combination: prefill N
        prompts, sample first tokens, seal attention KV into page units.
        N and the bucket length are compile-time constants per call."""
        cfg, e = self.cfg, self.ecfg
        n = tokens.shape[0]
        rng, key = jax.random.split(rng)
        st1 = init_lm_state(cfg, n, e.max_seq)
        logits, st1 = lm_prefill(params, cfg, {"tokens": tokens}, st1, last_index=true_lens - 1)
        toks0 = sample_tokens(logits[:, 0], key, e.temperature)  # (N,)
        if self.layout != "paged":
            return rng, st1, toks0
        ps = self.staging.page_size
        n_alloc = self.staging.required_pages(tokens.shape[1])
        sealed: Dict[str, Any] = {}
        for i, (mixer, _) in enumerate(group_pattern(cfg)):
            key_i = f"p{i}"
            if mixer != "attn":
                sealed[key_i] = st1[key_i]  # recurrent carry: dense rows
                continue
            sub = {}
            for pages_name, dense_name in (("k_pages", "k"), ("v_pages", "v")):
                one = st1[key_i][dense_name]  # (G, N, cl, KH, hd)
                g_, _, cl_, kh_, hd_ = one.shape
                pad = (-cl_) % ps
                if pad:
                    one = jnp.pad(one, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
                # re-view the bucketed prefill as page units and keep only the
                # pages it actually filled — the sealed shape the adopting
                # pool scatters verbatim
                sub[pages_name] = one.reshape(g_, n, -1, ps, kh_, hd_)[:, :, :n_alloc]
            sealed[key_i] = sub
        return rng, sealed, toks0

    def prefill_group(self, group) -> KVHandoff:
        """Prefill one same-bucket group of ``(tokens, budget)`` pairs in a
        single dispatch and seal it for handoff. The caller (admit_many or a
        router) is responsible for power-of-two group sizing so the compiled
        program set stays O(log max_slots) per bucket."""
        n = len(group)
        lb = self.bucket_len(max(len(t) for t, _ in group))
        padded = np.zeros((n, lb), np.int32)
        lens = np.zeros((n,), np.int32)
        buds = np.zeros((n,), np.int32)
        for j, (tokens, budget) in enumerate(group):
            padded[j, : len(tokens)] = tokens
            lens[j], buds[j] = len(tokens), budget
        staging_id, n_alloc = -1, 0
        if self.staging is not None:
            # backpressure: the staging pool caps how many sealed-but-not-
            # adopted pages can be in flight; adopt() donates them back. The
            # reservation id comes from the pool's own staging counter so
            # reset() can account (and reclaim) in-flight handoffs.
            n_alloc = self.staging.required_pages(lb)
            staging_id, _ = self.staging.stage(n * n_alloc)
        with obs.span("serve.prefill", replica=self.replica, n=n, bucket=lb):
            self._rng, sealed, toks0 = self._prefill_jit(
                self.params, self._rng, jnp.asarray(padded), jnp.asarray(lens)
            )
        self.stats["prefill_dispatches"] += 1
        self.stats["prefill_tokens"] += n * lb
        return KVHandoff(
            sealed=sealed, first_tok=toks0, true_lens=lens, budgets=buds,
            n_alloc=n_alloc, staging_id=staging_id, source=self, tokens=padded,
        )

    def release(self, handoff: KVHandoff) -> None:
        """Donate a handoff's staging reservation back (the adopting worker
        has issued its copy of the sealed pages)."""
        if self.staging is not None and handoff.staging_id >= 0:
            self.staging.donate(handoff.staging_id)


class DecodeWorker:
    """Bandwidth-bound half of the serving pair: owns the slots, the KV pool
    and the chunked decode program; ingests sealed prefills via ``adopt``.

    Two opt-in accelerations live here because they need the pool and the
    slot state: the **radix prefix cache** (``ecfg.prefix_cache`` — hot
    admissions splice resident pages and prefill only the tail, via
    :meth:`admit_spliced`; spliced admissions must run on THIS worker, not
    the prefill worker, because the matched pages are resident in THIS pool)
    and **speculative decoding** (``ecfg.spec_k`` + a ``drafter`` — the
    chunk program drafts/verifies through :class:`repro.serve.spec_decode.
    SpecDecoder`)."""

    def __init__(self, cfg, params, ecfg: EngineConfig, *, mesh=None,
                 stats: Optional[MutableMapping] = None, drafter=None, replica: int = 0):
        self.cfg = cfg
        self.ecfg = ecfg
        self.mesh = mesh
        self.replica = replica
        self.params = _shard_params(params, mesh) if mesh is not None else params
        self.layout = _engine_layout(cfg, ecfg)
        self.pool: Optional[KVPool] = KVPool(cfg, ecfg) if self.layout == "paged" else None
        self.stats = stats if stats is not None else _fresh_stats(replica=replica)
        self.free_slots: List[int] = list(range(ecfg.max_slots))
        self._state: Optional[DecodeState] = None
        # host-side per-slot metadata for page planning: (true_len, budget)
        # and a conservative position estimate (reconciled downward at sync)
        self._meta: Dict[int, Tuple[int, int]] = {}
        self._pos_est: Dict[int, int] = {}
        # pages a slot borrowed from the prefix cache instead of allocating
        # (its billed load is discounted by exactly this many pages)
        self._spliced: Dict[int, int] = {}
        self.prefix = None
        if ecfg.prefix_cache:
            if self.layout != "paged":
                raise ValueError(
                    f"{cfg.name}: prefix_cache requires the paged layout, but this "
                    "arch has no attention KV to page (it degrades to dense)"
                )
            _require_extend_capable(cfg, ecfg, "prefix_cache")
            from repro.serve.prefix_cache import PrefixCache

            self.prefix = PrefixCache(self.pool)
        self._spec = None
        if ecfg.spec_k > 0:
            if drafter is None:
                raise ValueError(
                    "spec_k > 0 but no drafter: pass drafter=(cfg, params) — any "
                    "registry config with attention-only mixers (e.g. a reduced "
                    "smollm-135m) can draft"
                )
            if self.layout != "paged":
                raise ValueError(
                    f"{cfg.name}: spec_decode requires the paged layout — the "
                    "batched verify is an lm_extend over the page-table view"
                )
            _require_extend_capable(cfg, ecfg, "spec_decode")
            from repro.serve.spec_decode import SpecDecoder

            self._spec = SpecDecoder(self, drafter[0], drafter[1], ecfg.spec_k)
        elif drafter is not None:
            raise ValueError("drafter given but spec_k == 0: set spec_k to enable it")
        # evicted slots whose table rows still point at returned pages; their
        # ride-along writes must be re-aimed at the scratch page before the
        # next chunk (unless adoption rewrites the row first)
        self._adopt_jit = jax.jit(self._adopt_fn)
        self._splice_jit = jax.jit(self._splice_fn)
        self._cow_jit = jax.jit(self._cow_fn)
        self._stale_slots: set = set()
        self._chunk_jit = jax.jit(self._chunk_fn, donate_argnums=(1,))
        self.reset()

    # -- device programs ----------------------------------------------------

    def _splice_dense(self, kv, st1, slots, n: int):
        """Per-row dense splice: each prefilled row lands on its slot's batch
        index in every state leaf. n <= max_slots: unrolled."""
        for i in range(n):
            kv = jax.tree_util.tree_map(
                lambda big, one: jax.lax.dynamic_update_slice(
                    big,
                    jax.lax.dynamic_slice_in_dim(one, i, 1, axis=1).astype(big.dtype),
                    (0, slots[i]) + (0,) * (big.ndim - 2),
                ),
                kv,
                st1,
            )
        return kv

    def _adopt_fn(self, ds: DecodeState, sealed, toks0, slots, true_lens, budgets,
                  table_rows, page_ids):
        """Ingest one sealed burst: PURE data movement (no model forward).
        Paged: sealed page units scatter into this worker's pool buffers at
        the ids its pool assigned (one scatter per leaf for the whole burst —
        page ids are disjoint across rows by the allocator invariant, so the
        (N, n_alloc) index array never collides); recurrent mixer states stay
        per-slot dense. Dense: per-row dynamic-update splice. Either way the
        slot bookkeeping vectors are rewritten for the adopted rows."""
        n = toks0.shape[0]
        if self.layout == "paged":
            kv = dict(ds.kv)
            for i, (mixer, _) in enumerate(group_pattern(self.cfg)):
                key = f"p{i}"
                if mixer != "attn":
                    kv[key] = self._splice_dense(kv[key], sealed[key], slots, n)
                    continue
                sub = dict(kv[key])
                for pages_name in ("k_pages", "v_pages"):
                    big = sub[pages_name]  # (G, P, ps, KH, hd)
                    sub[pages_name] = big.at[:, page_ids].set(
                        sealed[key][pages_name].astype(big.dtype)
                    )
                kv[key] = sub
            page_table = ds.page_table.at[slots].set(table_rows)
        else:
            kv = self._splice_dense(ds.kv, sealed, slots, n)
            page_table = ds.page_table
        return DecodeState(
            kv=kv,
            last_tok=ds.last_tok.at[slots, 0].set(toks0),
            pos=ds.pos.at[slots].set(true_lens),
            active=ds.active.at[slots].set(budgets > 1),
            out=ds.out.at[slots].set(0).at[slots, 0].set(toks0),
            n_out=ds.n_out.at[slots].set(1),
            budget=ds.budget.at[slots].set(budgets),
            rng=ds.rng,
            page_table=page_table,
        )

    def _attn_page_map(self, kv, fn):
        """Apply ``fn`` to every attention page-pool leaf of a kv pytree."""
        kv = dict(kv)
        for i, (mixer, _) in enumerate(group_pattern(self.cfg)):
            if mixer != "attn":
                continue
            key = f"p{i}"
            sub = dict(kv[key])
            for name in ("k_pages", "v_pages"):
                sub[name] = fn(sub[name])
            kv[key] = sub
        return kv

    def _cow_fn(self, ds: DecodeState, src, dst):
        """Copy-on-write device half: duplicate pages ``src`` (M,) into
        ``dst`` (M,) on every attention leaf ((G, P, page, KH, hd) — page dim
        is axis 1). The host half (KVPool.cow) already swapped the table
        entry; ``src == dst == scratch`` rows are harmless self-copies."""
        kv = self._attn_page_map(ds.kv, lambda big: big.at[:, dst].set(big[:, src]))
        return ds._replace(kv=kv)

    def _splice_fn(self, params, ds: DecodeState, tokens, slot, start, last_idx,
                   budget, true_len, table_row, cow_src, cow_dst):
        """One hot-prefix admission in ONE dispatch: the copy-on-write page
        duplicate (scratch→scratch when none is needed), the slot's new
        page-table row (spliced prefix pages + fresh tail pages), the tail
        extend (only the tokens the radix match did NOT cover — this is the
        whole point: a hot admission prefills ``tokens.shape[1]`` positions
        instead of the full prompt), first-token sampling from the true last
        prompt position, and the slot bookkeeping rewrite."""
        e = self.ecfg
        kv = self._attn_page_map(
            ds.kv, lambda big: big.at[:, cow_dst].set(big[:, cow_src])
        )
        page_table = ds.page_table.at[slot].set(table_row)
        logits, kv = lm_extend(
            params, self.cfg, tokens, kv, jnp.reshape(start, (1,)), table_row[None, :]
        )
        rng, key = jax.random.split(ds.rng)
        tok0 = sample_tokens(logits[:, last_idx], key, e.temperature)  # (1,)
        return DecodeState(
            kv=kv,
            last_tok=ds.last_tok.at[slot, 0].set(tok0[0]),
            pos=ds.pos.at[slot].set(true_len),
            active=ds.active.at[slot].set(budget > 1),
            out=ds.out.at[slot].set(0).at[slot, 0].set(tok0[0]),
            n_out=ds.n_out.at[slot].set(1),
            budget=ds.budget.at[slot].set(budget),
            rng=rng,
            page_table=page_table,
        )

    def _chunk_fn(self, params, ds: DecodeState):
        cfg, e = self.cfg, self.ecfg
        rows = jnp.arange(e.max_slots, dtype=jnp.int32)
        paged = self.layout == "paged"

        def cond(carry):
            i, s = carry
            return (i < e.decode_chunk) & jnp.any(s.active)

        def body(carry):
            i, s = carry
            logits, kv = lm_decode(
                params, cfg, s.last_tok, s.kv, s.pos,
                page_table=s.page_table if paged else None,
            )
            rng, ks = jax.random.split(s.rng)
            nxt = sample_tokens(logits[:, -1], ks, e.temperature)
            write = s.active & (s.n_out < e.max_new)
            idx = jnp.minimum(s.n_out, e.max_new - 1)
            out = s.out.at[rows, idx].set(jnp.where(write, nxt, s.out[rows, idx]))
            n_out = s.n_out + write.astype(jnp.int32)
            finished = n_out >= s.budget
            if e.eos_token >= 0:
                finished |= (nxt == e.eos_token) & s.active
            return i + 1, DecodeState(
                kv=kv,
                last_tok=jnp.where(s.active[:, None], nxt[:, None], s.last_tok),
                pos=s.pos + s.active.astype(jnp.int32),
                active=s.active & ~finished,
                out=out,
                n_out=n_out,
                budget=s.budget,
                rng=rng,
                page_table=s.page_table,
            )

        _, ds = jax.lax.while_loop(cond, body, (jnp.zeros((), jnp.int32), ds))
        return ds

    # -- host API -----------------------------------------------------------

    def reset(self) -> None:
        """(Re)build the device state: all slots free, caches zeroed. Stats
        are NOT zeroed here — the shared dict belongs to the composition
        (ServeEngine.reset) or to the caller of a bare worker."""
        cfg, e = self.cfg, self.ecfg
        self.free_slots = list(range(e.max_slots))
        self._meta = {}
        self._pos_est = {}
        self._spliced = {}
        self._stale_slots = set()
        if self.prefix is not None:
            self.prefix.clear()  # refcounts are wiped by pool.reset() below
        if self._spec is not None:
            self._spec.reset()
        if self.pool is not None:
            self.pool.reset()
            # +1: the scratch page — the write target of idle slots' frozen
            # ride-along positions (never allocated, reads always masked)
            kv = init_lm_state(
                cfg, e.max_slots, e.max_seq,
                kv_pages=self.pool.n_pages + 1, kv_page_size=self.pool.page_size,
            )
            width = self.pool.pages_per_slot
            table0 = jnp.full((e.max_slots, width), self.pool.scratch_page, jnp.int32)
        else:
            kv = init_lm_state(cfg, e.max_slots, e.max_seq)
            width = 1
            table0 = jnp.zeros((e.max_slots, width), jnp.int32)
        state = DecodeState(
            kv=kv,
            last_tok=jnp.zeros((e.max_slots, 1), jnp.int32),
            pos=jnp.zeros((e.max_slots,), jnp.int32),
            active=jnp.zeros((e.max_slots,), bool),
            out=jnp.zeros((e.max_slots, e.max_new), jnp.int32),
            n_out=jnp.zeros((e.max_slots,), jnp.int32),
            budget=jnp.zeros((e.max_slots,), jnp.int32),
            rng=jax.random.key(e.seed),
            page_table=table0,
        )
        if self.mesh is not None:
            # shard the engine state over this worker's mesh slice (page
            # pools and caches along the heads axis; bookkeeping replicated)
            specs = shard_engine_state(state, mesh_axes=dict(self.mesh.shape))
            shardings = jax.tree_util.tree_map(
                lambda spec: NamedSharding(self.mesh, spec), specs,
                is_leaf=lambda x: isinstance(x, P),
            )
            state = jax.device_put(state, shardings)
        self._state = state

    def _lifetime_pages(self, prompt_len: int, budget: int) -> int:
        """A request's TOTAL page bill over its life: the bucketed prefill
        plus every decode position its budget can reach (ring-clamped)."""
        lb = bucket_len(self.cfg, self.ecfg, prompt_len)
        return self.pool.required_pages(max(lb, prompt_len + budget))

    def request_load(self, prompt_len: int, budget: int) -> int:
        """The admission-load unit a router bills for one request: lifetime
        pages in the paged layout, one slot otherwise."""
        if self.pool is None:
            return 1
        return self._lifetime_pages(prompt_len, budget)

    def billed_pages(self) -> int:
        """Resident load: lifetime page bill of every resident request
        (paged) or the resident count (dense). Spliced pages are DISCOUNTED —
        a request serving its prompt off shared prefix pages loads the pool
        (and the router's least-loaded comparison) only by the pages it
        privately grows into."""
        if self.pool is None:
            return self.ecfg.max_slots - len(self.free_slots)
        return sum(
            self._lifetime_pages(tl, b) - self._spliced.get(slot, 0)
            for slot, (tl, b) in self._meta.items()
        )

    def prefix_probe(self, tokens) -> int:
        """Resident full prefix pages for a prompt (0 without the cache) —
        read-only: no LRU touch, so capacity checks and router affinity
        probes never age the cache."""
        if self.prefix is None:
            return 0
        return self.prefix.probe(np.asarray(tokens, np.int32).reshape(-1))

    def _headroom(self) -> int:
        """Pages obtainable for growth right now: the free list plus every
        cache-only page eviction could reclaim on demand."""
        free = self.pool.free_pages
        if self.prefix is not None:
            free += self.prefix.reclaimable()
        return free

    def _committed_growth(self) -> int:
        """Pages resident requests may still demand: lifetime bill minus the
        pages already in their tables (attached prefix pages count — they
        never need re-allocating)."""
        return sum(
            max(self._lifetime_pages(tl, b) - len(self.pool.owned(slot)), 0)
            for slot, (tl, b) in self._meta.items()
        )

    def _make_room(self, n_pages: int) -> None:
        """Ensure ``n_pages`` are on the free list, evicting LRU cache-only
        pages if needed (their refcount drops to zero — truly orphaned)."""
        if self.prefix is not None and n_pages > self.pool.free_pages:
            self.prefix.make_room(n_pages - self.pool.free_pages)

    def can_ever_admit(self, prompt_len: int, budget: int) -> bool:
        """Whether an EMPTY instance of this worker could admit the request
        (its lifetime bill fits the whole pool). A router uses this to fail
        fast on requests no amount of draining can make admissible."""
        if self.pool is None:
            return True
        return self._lifetime_pages(prompt_len, budget) <= self.pool.n_pages

    def max_admissible(self, requests) -> int:
        """Largest prefix of ``requests`` ((tokens, budget) pairs) admissible
        RIGHT NOW: bounded by free slots and, in the paged layout, by pool
        capacity net of every RESIDENT request's remaining growth. Billing
        lifetimes (not just prefills — budgets are known at admission) means
        residents can always grow to their full budget: a scheduler that
        admits through this can never hit mid-decode pool exhaustion; a
        tight pool defers requests instead of crashing the run.

        With the prefix cache, capacity = free pages + reclaimable cache
        pages, and each candidate still bills its FULL lifetime: a spliced
        admission consumes ``lifetime - matched`` fresh pages but pins its
        ``matched`` pages un-reclaimable (and the r==0 boundary case trades
        one splice for one CoW page), so lifetime is the exact worst-case
        claim either way — the sharing win shows up in residents' committed
        growth (attached pages are already in their tables), not in an
        optimistic candidate discount."""
        n = min(len(requests), len(self.free_slots))
        if self.pool is None:
            return n
        free = self._headroom() - self._committed_growth()
        count = 0
        for tokens, budget in list(requests)[:n]:
            tokens = np.asarray(tokens, np.int32).reshape(-1)
            need = self._lifetime_pages(len(tokens), budget)
            if need > free:
                break
            free -= need
            count += 1
        return count

    def adopt(self, handoff: KVHandoff) -> List[int]:
        """Land one sealed burst on this worker's slots/pool. Atomic w.r.t.
        pool exhaustion: the whole burst's page bill is checked before a slot
        is popped or a page adopted, so a caller that catches the error has a
        clean worker and an intact handoff to retry elsewhere."""
        n = handoff.n
        if n > len(self.free_slots):
            raise RuntimeError(
                f"{n} adoptions but only {len(self.free_slots)} free slots"
            )
        if self.pool is not None:
            if handoff.n_alloc == 0:
                raise ValueError(
                    "dense handoff offered to a paged decode worker: the "
                    "prefill and decode halves of a pair must share kv_layout"
                )
            self._make_room(n * handoff.n_alloc)
            if n * handoff.n_alloc > self.pool.free_pages:
                raise RuntimeError(
                    f"KV pool cannot adopt this burst: its sealed prefills need "
                    f"{n * handoff.n_alloc} pages but only {self.pool.free_pages}/"
                    f"{self.pool.n_pages} are free (page_size={self.pool.page_size}). "
                    "Adopt fewer requests, raise --pool-pages, or lower --max-slots."
                )
        sealed, toks0 = handoff.sealed, handoff.first_tok
        if self.mesh is not None and getattr(handoff.source, "mesh", None) is not self.mesh:
            # cross-worker transport: the sealed buffers were produced on the
            # prefill worker's mesh slice — replicate them onto ours (the
            # ICI/DCN hop of a real disaggregated fleet)
            with obs.span("serve.handoff", replica=self.replica, n=n):
                rep = NamedSharding(self.mesh, P())
                sealed, toks0 = jax.device_put((sealed, toks0), rep)
        gslots = [self.free_slots.pop() for _ in range(n)]
        width = self.pool.pages_per_slot if self.pool is not None else 1
        table_rows = np.zeros((n, width), np.int32)
        page_ids = np.zeros((n, max(handoff.n_alloc, 1)), np.int32)
        for j, slot in enumerate(gslots):
            if self.pool is not None:
                page_ids[j] = self.pool.adopt(slot, handoff.n_alloc)
                table_rows[j] = self.pool.table_row(slot)
                self._meta[slot] = (int(handoff.true_lens[j]), int(handoff.budgets[j]))
                self._pos_est[slot] = int(handoff.true_lens[j])
                self._spliced[slot] = 0
                self._stale_slots.discard(slot)  # row fully rewritten
        self.stats["pages_allocated"] += n * max(handoff.n_alloc, 0)
        with obs.span("serve.adopt", replica=self.replica, n=n):
            self._state = self._adopt_jit(
                self._state,
                sealed,
                toks0,
                jnp.asarray(gslots, jnp.int32),
                jnp.asarray(handoff.true_lens),
                jnp.asarray(handoff.budgets),
                jnp.asarray(table_rows),
                jnp.asarray(page_ids),
            )
        handoff.source.release(handoff)
        if self._spec is not None:
            self._spec.on_admit(
                gslots,
                np.asarray(handoff.tokens),
                [int(t) for t in np.asarray(handoff.true_lens)],
            )
        self.stats["admitted"] += n
        self.stats["handoffs"] += 1
        return gslots

    def admit_spliced(self, tokens, budget: int) -> Optional[int]:
        """Hot-prefix admission: splice the longest resident radix run into a
        fresh slot's page table (``KVPool.attach``) and prefill ONLY the
        uncovered tail — the prompt's cached pages are never recomputed.
        Returns the slot id, or ``None`` when the cache holds no full page of
        this prompt (the caller falls back to the classic prefill path).

        Must run on THIS worker (never the prefill half of a disaggregated
        pair): the matched pages are resident in THIS pool's device buffers.

        Token parity with the cold path is the contract: the spliced pages
        hold bitwise the KV a full prefill of the same prompt would produce
        (same params, same positions), and the tail extend reproduces prefill
        semantics for the rest — so greedy outputs match the cold admission
        bitwise. The one boundary case is a prompt the cache covers ENTIRELY
        (tail length 0): the last prompt token's logits must be recomputed to
        sample the first output, and that replay re-writes one position in
        the final matched page — which other requests may share, and whose
        reduction order a different batch shape could perturb. The replay
        therefore ALWAYS goes through copy-on-write, never writes the shared
        page."""
        if self.prefix is None:
            return None
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        true_len = len(tokens)
        pages = self.prefix.match(tokens)
        if not pages:
            return None
        if not self.free_slots:
            raise RuntimeError("spliced admission with no free slot")
        e, ps = self.ecfg, self.pool.page_size
        m = len(pages)
        r = true_len - m * ps  # uncovered tail tokens
        fresh = self.pool.required_pages(true_len) - m + (1 if r == 0 else 0)
        self._make_room(fresh)
        if fresh > self.pool.free_pages:
            raise RuntimeError(
                f"KV pool cannot admit this spliced request: its tail needs "
                f"{fresh} fresh pages but only {self.pool.free_pages}/"
                f"{self.pool.n_pages} are free (page_size={ps}). Drain a "
                "request, raise --pool-pages, or lower --max-slots."
            )
        slot = self.free_slots.pop()
        self.pool.attach(slot, pages)
        cow_src = cow_dst = self.pool.scratch_page  # harmless self-copy
        if r > 0:
            self.pool.alloc(slot, self.pool.required_pages(true_len))
            tb = min(-(-r // e.prefill_bucket) * e.prefill_bucket, e.max_seq)
            start, last_idx = m * ps, r - 1
            tail = np.zeros((1, tb), np.int32)
            tail[0, :r] = tokens[m * ps :]
        else:
            # fully-covered prompt: CoW the final matched page, then replay
            # the last prompt token into the private copy to recover its
            # logits (the cache stores KV, not logits)
            cow_src, cow_dst = self.pool.cow(slot, m - 1)
            if cow_src != cow_dst:
                self.stats["cow_copies"] += 1
            tb, start, last_idx = 1, true_len - 1, 0
            tail = tokens[None, -1:].copy()
        table_row = self.pool.table_row(slot)  # AFTER cow: private ids only
        # scalars ride as traced device values so the compiled program is
        # keyed on the tail bucket alone, not on slot/length combinations
        with obs.span("serve.splice", replica=self.replica, matched=m, tail=tb):
            self._state = self._splice_jit(
                self.params,
                self._state,
                jnp.asarray(tail),
                jnp.asarray(slot, jnp.int32),
                jnp.asarray(start, jnp.int32),
                jnp.asarray(last_idx, jnp.int32),
                jnp.asarray(budget, jnp.int32),
                jnp.asarray(true_len, jnp.int32),
                jnp.asarray(table_row),
                jnp.asarray(cow_src, jnp.int32),
                jnp.asarray(cow_dst, jnp.int32),
            )
        self._meta[slot] = (true_len, budget)
        self._pos_est[slot] = true_len
        self._spliced[slot] = m
        self._stale_slots.discard(slot)  # row fully rewritten by the splice
        if self._spec is not None:
            # the drafter shares no pages — it prefills the FULL prompt into
            # its own dense cache (cheap: the drafter is small by design)
            lb = bucket_len(self.cfg, e, true_len)
            padded = np.zeros((1, lb), np.int32)
            padded[0, :true_len] = tokens
            self._spec.on_admit([slot], padded, [true_len])
        self.stats["admitted"] += 1
        self.stats["prefix_hits"] += 1
        self.stats["spliced_admissions"] += 1
        self.stats["spliced_pages"] += m
        self.stats["prefill_tokens"] += tb
        self.stats["pages_allocated"] += fresh
        return slot

    def _ensure_chunk_pages(self) -> None:
        """Grow resident slots' page tables to cover the positions the next
        chunk can write. The estimate only moves DOWN at sync reconciliation,
        so back-to-back chunks without a sync stay safe (a page is appended
        at worst one chunk early, never late — late would silently write
        through a padding table entry)."""
        e = self.ecfg
        # a speculative chunk's verify extend can write up to steps*(k+1)
        # positions (plus rejected-draft garbage the NEXT verify overwrites —
        # writes past the planned coverage redirect to the scratch page)
        horizon = self._spec.horizon if self._spec is not None else e.decode_chunk
        # phase 1 — PLAN, no mutation: the chunk's total page bill, so
        # exhaustion raises with the engine untouched (stale set intact,
        # pool unallocated — a caller that catches can drain and retry;
        # committing anything partially here would either forget a stale
        # row, re-opening the cross-slot clobber, or leave a slot owning
        # pages its device table never maps)
        growth: List[Tuple[int, int, int]] = []  # (slot, have, need)
        cows: List[Tuple[int, int]] = []  # (slot, page idx) to copy-on-write
        total_new = 0
        for slot, (true_len, budget) in self._meta.items():
            est = self._pos_est[slot]
            end = min(est + horizon, true_len + budget)
            need = self.pool.required_pages(end)
            owned = self.pool.owned(slot)
            if need > len(owned):
                growth.append((slot, len(owned), need))
                total_new += need - len(owned)
            # a write crossing into a page another slot (or the prefix cache)
            # still references must copy first — sharing is read-only
            ps = self.pool.page_size
            for idx in range(est // ps, min(-(-end // ps), len(owned))):
                if self.pool.refcount(owned[idx]) > 1:
                    cows.append((slot, idx))
                    total_new += 1
        self._make_room(total_new)
        if total_new > self.pool.free_pages:
            raise RuntimeError(
                f"KV pool exhausted mid-decode: growing {len(growth)} slot(s) for "
                f"the next chunk needs {total_new} pages but only "
                f"{self.pool.free_pages}/{self.pool.n_pages} are free "
                f"(page_size={self.pool.page_size}). Raise --pool-pages or admit "
                "fewer/shorter requests; the engine state is unchanged."
            )
        # phase 2 — COMMIT: allocations cannot fail now. Evicted slots'
        # stale rows are re-aimed at the scratch page in the same table
        # update (their frozen ride-along writes must not land on pages the
        # pool may reissue); the stale set is cleared only after the device
        # table actually carries the re-aim.
        upd_rows: List[int] = []
        upd_cols: List[int] = []
        upd_vals: List[int] = []
        for slot in sorted(self._stale_slots):
            for k in range(self.pool.pages_per_slot):
                upd_rows.append(slot)
                upd_cols.append(k)
                upd_vals.append(self.pool.scratch_page)
            self.stats["table_resets"] += 1
        for slot, have, need in growth:
            pages = self.pool.alloc(slot, need)
            for k in range(have, need):
                upd_rows.append(slot)
                upd_cols.append(k)
                upd_vals.append(pages[k])
            self.stats["page_appends"] += need - have
            self.stats["pages_allocated"] += need - have
        cow_src: List[int] = []
        cow_dst: List[int] = []
        for slot, idx in cows:
            src, dst = self.pool.cow(slot, idx)
            if src == dst:
                continue  # became private since the plan (same-batch dedup)
            cow_src.append(src)
            cow_dst.append(dst)
            upd_rows.append(slot)
            upd_cols.append(idx)
            upd_vals.append(dst)
            self.stats["cow_copies"] += 1
            self.stats["pages_allocated"] += 1
        for slot, (true_len, budget) in self._meta.items():
            self._pos_est[slot] = min(
                self._pos_est[slot] + horizon, true_len + budget - 1
            )
        if upd_rows:
            self._state = self._state._replace(
                page_table=self._state.page_table.at[
                    jnp.asarray(upd_rows, jnp.int32), jnp.asarray(upd_cols, jnp.int32)
                ].set(jnp.asarray(upd_vals, jnp.int32))
            )
        if cow_src:
            self._state = self._cow_jit(
                self._state, jnp.asarray(cow_src, jnp.int32), jnp.asarray(cow_dst, jnp.int32)
            )
        self._stale_slots.clear()

    def decode_chunk(self) -> None:
        """Up to ``decode_chunk`` batched decode steps in ONE dispatch (or,
        with speculative decoding on, the draft/verify chunk program). The
        span brackets dispatch submission only — no sync is forced, so the
        O(1)-host-syncs-per-chunk contract holds with tracing on."""
        with obs.span("serve.decode_chunk", replica=self.replica):
            if self.pool is not None:
                self._ensure_chunk_pages()
            if self._spec is not None:
                self._spec.chunk()
            else:
                self._state = self._chunk_jit(self.params, self._state)
        self.stats["decode_chunks"] += 1

    def sync(self):
        """The once-per-chunk host sync: (active, n_out) as numpy, fetched
        in a single device-to-host transfer. Also reconciles the paged
        layout's conservative per-slot position estimates to the truth, and
        (spec mode) refreshes the draft counters' host mirrors — the
        counters ride the SAME transfer, costing no extra sync."""
        with obs.span("serve.sync", replica=self.replica):
            if self._spec is not None:
                active, n_out = self._spec.sync()
            else:
                active, n_out = jax.device_get((self._state.active, self._state.n_out))
        self.stats["host_syncs"] += 1
        if self.pool is not None:
            for slot, (true_len, _) in self._meta.items():
                self._pos_est[slot] = true_len + int(n_out[slot]) - 1
        return active, n_out

    def publish_gauges(self) -> None:
        """Push the pool/prefix occupancy gauges into the stats registry —
        called at snapshot/dump time (occupancy is a point-in-time value;
        sampling it per chunk would be noise, not signal)."""
        if not isinstance(self.stats, StatsView):
            return
        reg, labels = self.stats.registry, self.stats.labels
        if self.pool is not None:
            reg.set_gauge(KV_GAUGES["free_pages"], self.pool.free_pages, **labels)
            reg.set_gauge(KV_GAUGES["pages_in_use"], self.pool.pages_in_use, **labels)
            reg.set_gauge(KV_GAUGES["capacity_pages"], self.pool.n_pages, **labels)
        if self.prefix is not None:
            reg.set_gauge(
                KV_GAUGES["reclaimable_pages"], self.prefix.reclaimable(), **labels
            )

    def fetch(self, slot: int, n_out: int) -> np.ndarray:
        """Copy a finished slot's generated tokens to host and free the slot
        (returning its truly-orphaned pages to the pool in the paged layout —
        pages the prefix cache pins stay resident for future splices)."""
        toks = np.asarray(self._state.out[slot])[:n_out]
        self.free_slots.append(slot)
        if self.pool is not None:
            self.pool.free_slot(slot)
            self._meta.pop(slot, None)
            self._pos_est.pop(slot, None)
            self._spliced.pop(slot, None)
            self._stale_slots.add(slot)
        self.stats["evicted"] += 1
        return toks


class ServeEngine:
    """One fleet replica: a :class:`PrefillWorker` and a
    :class:`DecodeWorker` composed behind the classic engine API that
    :class:`repro.serve.scheduler.FleetRouter` (and its N=1 case,
    ``ContinuousScheduler``) drives from the request queue.

    Colocated by default: both workers share the params and (if given) the
    same mesh slice. With ``ecfg.disagg`` (or distinct ``prefill_mesh``/
    ``mesh``) the pair is disaggregated — prefill seals pages on its slice,
    adoption scatters them into the decode worker's pool, the classic
    production split of compute-bound admission from bandwidth-bound decode.
    Either way admission runs the SAME two programs, so the colocated engine
    is the disaggregated pair's parity oracle by construction."""

    def __init__(self, cfg, params, ecfg: EngineConfig, *, mesh=None, prefill_mesh=None,
                 drafter=None, registry: Optional[MetricsRegistry] = None,
                 replica: int = 0):
        if cfg.is_encoder_only:
            raise ValueError(f"{cfg.name} is encoder-only: nothing to decode")
        if cfg.frontend == "vision":
            raise ValueError(
                f"{cfg.name} needs per-request vision prefix embeddings, which "
                "the slot engine does not thread through admission yet; serve "
                "vlm archs with the static batch path"
            )
        self.cfg = cfg
        self.params = params
        self.ecfg = ecfg
        self.layout = _engine_layout(cfg, ecfg)
        if ecfg.disagg and self.layout != "paged":
            raise ValueError(
                f"{cfg.name} has no attention layers: its serving state degrades "
                "to the dense layout, which has no page units to hand off — a "
                "disaggregated prefill/decode pair is paged-only. Drop --disagg."
            )
        self.replica = replica
        self.stats: StatsView = _fresh_stats(registry, replica)
        self.prefill = PrefillWorker(
            cfg, params, ecfg, mesh=prefill_mesh if prefill_mesh is not None else mesh,
            stats=self.stats, replica=replica,
        )
        self.decode = DecodeWorker(
            cfg, params, ecfg, mesh=mesh, stats=self.stats, drafter=drafter,
            replica=replica,
        )

    # -- delegation (the device state lives on the workers) -----------------

    @property
    def pool(self) -> Optional[KVPool]:
        return self.decode.pool

    @property
    def prefix(self):
        """The decode worker's radix prefix cache (None when disabled)."""
        return self.decode.prefix

    @property
    def free_slots(self) -> List[int]:
        return self.decode.free_slots

    @property
    def _state(self) -> Optional[DecodeState]:
        return self.decode._state

    @property
    def _meta(self) -> Dict[int, Tuple[int, int]]:
        return self.decode._meta

    @property
    def _stale_slots(self) -> set:
        return self.decode._stale_slots

    def reset(self) -> None:
        """(Re)build both workers' device state and zero the shared stats
        (so a warm-up run never contaminates timed counters)."""
        for k in list(self.stats):
            self.stats[k] = 0
        self.prefill.reset()
        self.decode.reset()

    def bucket_len(self, prompt_len: int) -> int:
        return bucket_len(self.cfg, self.ecfg, prompt_len)

    def request_load(self, prompt_len: int, budget: int) -> int:
        return self.decode.request_load(prompt_len, budget)

    def billed_pages(self) -> int:
        return self.decode.billed_pages()

    def can_ever_admit(self, prompt_len: int, budget: int) -> bool:
        return self.decode.can_ever_admit(prompt_len, budget)

    def max_admissible(self, requests) -> int:
        return self.decode.max_admissible(requests)

    def prefix_hit_pages(self, tokens) -> int:
        """Resident full prefix pages for a prompt (0 without the cache) —
        the router's prefix-affinity signal. Read-only: never ages the LRU."""
        return self.decode.prefix_probe(tokens)

    def admit(self, tokens: np.ndarray, max_new_tokens: int) -> int:
        """Prefill one prompt (1-D int32) into a free slot; returns its id."""
        return self.admit_many([(tokens, max_new_tokens)])[0]

    def admit_many(self, requests) -> List[int]:
        """Admit several prompts; returns their slots, input-aligned.

        Prompts sharing a bucket length prefill together: each group is
        split into power-of-two admission batches (4+2+1…) so the set of
        compiled (bucket, N) programs stays O(log max_slots) per bucket
        instead of one per burst size — a freed-slot refill after warm-up
        never hits the compiler. Each group is ONE prefill dispatch sealed
        into a KVHandoff and ONE adoption scatter on the decode worker."""
        e = self.ecfg
        prepped = []
        for tokens, max_new_tokens in requests:
            tokens = np.asarray(tokens, np.int32).reshape(-1)
            if len(tokens) + max_new_tokens > e.max_seq:
                raise ValueError(
                    f"prompt ({len(tokens)}) + budget ({max_new_tokens}) exceeds max_seq={e.max_seq}"
                )
            if not 1 <= max_new_tokens <= e.max_new:
                raise ValueError(
                    f"max_new_tokens must be in [1, {e.max_new}], got {max_new_tokens}"
                )
            prepped.append((tokens, max_new_tokens))
        if len(prepped) > len(self.free_slots):
            raise RuntimeError(
                f"{len(prepped)} admissions but only {len(self.free_slots)} free slots"
            )
        prefix = self.decode.prefix
        hot: List[int] = []
        if prefix is not None:
            # probe (read-only) BEFORE any admission: intra-burst duplicates
            # do not share with each other — sharing materializes across
            # scheduler ticks, once the first copy's pages are indexed below
            hot = [
                i for i, (tokens, _) in enumerate(prepped)
                if self.decode.prefix_probe(tokens) > 0
            ]
        if self.pool is not None:
            # admission is ATOMIC w.r.t. pool exhaustion: check the whole
            # burst's page bill before prefilling, popping a slot or adopting
            # a page, so a caller that catches the error has a clean engine
            # (no half-admitted rows, no leaked slots/pages) and can retry
            # with a smaller burst. Hot requests bill only their uncovered
            # tail (+1 for the fully-covered replay's CoW page) — evicting a
            # matched page to make room frees exactly the page its splice
            # would have saved, so the bill stays sufficient either way.
            ps = self.pool.page_size
            need = 0
            hot_idx = set(hot)
            for i, (tokens, _) in enumerate(prepped):
                if i in hot_idx:
                    m = self.decode.prefix_probe(tokens)
                    r = len(tokens) - m * ps
                    need += self.pool.required_pages(len(tokens)) - m + (1 if r == 0 else 0)
                else:
                    need += self.pool.required_pages(self.bucket_len(len(tokens)))
            self.decode._make_room(need)
            if need > self.pool.free_pages:
                raise RuntimeError(
                    f"KV pool cannot admit this burst: its bucketed prefills need "
                    f"{need} pages but only {self.pool.free_pages}/{self.pool.n_pages} "
                    f"are free (page_size={self.pool.page_size}). Admit fewer "
                    "requests, raise --pool-pages, or lower --max-slots."
                )
        slots = [0] * len(prepped)
        cold: List[int] = []
        hot_set = set(hot)
        for i in range(len(prepped)):
            if i not in hot_set:
                cold.append(i)
                continue
            tokens, budget = prepped[i]
            slot = self.decode.admit_spliced(tokens, budget)
            if slot is None:  # match evicted since the probe: classic path
                cold.append(i)
            else:
                slots[i] = slot
        by_bucket: Dict[int, List[int]] = {}
        for i in cold:
            by_bucket.setdefault(self.bucket_len(len(prepped[i][0])), []).append(i)
        for lb, idxs in by_bucket.items():
            while idxs:
                n = 1 << (len(idxs).bit_length() - 1)  # largest pow2 <= len
                group, idxs = idxs[:n], idxs[n:]
                handoff = self.prefill.prefill_group([prepped[i] for i in group])
                gslots = self.decode.adopt(handoff)
                for j, i in enumerate(group):
                    slots[i] = gslots[j]
        if prefix is not None:
            # index every admitted prompt's full pages — spliced prompts map
            # their chunks to the very pages they attached, so only fresh
            # tails add nodes; the NEXT burst with these prefixes splices
            for i, (tokens, _) in enumerate(prepped):
                prefix.insert(tokens, self.pool.owned(slots[i]))
        return slots

    def warmup(self, prompt: np.ndarray, budget: int = 2) -> None:
        """Compile every admission program a serving run can hit — one per
        power-of-two burst size up to ``max_slots`` for ``prompt``'s bucket —
        plus the decode-chunk program, then reset. Without this, the first
        burst of a previously-unseen size pays XLA compilation mid-serving."""
        budget = min(budget, self.ecfg.max_new)
        n = 1
        while n <= self.ecfg.max_slots:
            self.reset()
            reqs = [(prompt, budget)] * n
            if self.max_admissible(reqs) < n:
                break  # a tight pool caps the burst; larger sizes can't fit either
            self.admit_many(reqs)
            self.decode_chunk()
            self.sync()
            n *= 2
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if (
            self.decode.prefix is not None
            and len(prompt) >= self.ecfg.page_size
            and self.ecfg.max_slots >= 2
            and len(prompt) + 1 + budget <= self.ecfg.max_seq
        ):
            # compile both splice programs: admit cold (seeds the cache), then
            # re-admit the same prompt (fully-covered replay, tail bucket 1)
            # and a one-token-longer prompt (tail extend, one prefill bucket)
            self.reset()
            self.admit(prompt, budget)
            if self.max_admissible([(prompt, budget)]) >= 1:
                self.admit(prompt, budget)
            longer = np.concatenate([prompt, prompt[-1:]])
            if self.max_admissible([(longer, budget)]) >= 1:
                self.admit(longer, budget)
            self.decode_chunk()
            self.sync()
        self.reset()

    def decode_chunk(self) -> None:
        self.decode.decode_chunk()

    def sync(self):
        return self.decode.sync()

    def fetch(self, slot: int, n_out: int) -> np.ndarray:
        return self.decode.fetch(slot, n_out)

    def publish_gauges(self) -> None:
        """Push pool/prefix occupancy gauges into the stats registry."""
        self.decode.publish_gauges()
