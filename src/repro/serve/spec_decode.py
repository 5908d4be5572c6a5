"""Speculative decoding for the continuous-batching engine.

A small **drafter** model proposes ``k`` greedy tokens per step from its own
dense per-slot cache; the target :class:`repro.serve.engine.DecodeWorker`
verifies all of them (plus one bonus position) in ONE batched
:func:`repro.models.lm_extend` forward and accepts the longest run that
matches its own greedy choices. Every emitted token is the TARGET's argmax —
**greedy token parity with the non-speculative engine is the contract**; the
drafter only decides how many target tokens one dispatch can certify, never
what they are. Per verify step the target runs one (S, k+1)-token forward
instead of up to ``k+1`` single-token decodes, so a well-matched drafter
turns memory-bound decode latency into compute the small model prepays.

Rollback discipline (why the gates below exist):

* the TARGET writes draft KV at ``pos..pos+k`` during verify; rejected
  positions are never attended (the causal mask stops at each query) and the
  next verify's write range always covers them — a full attention cache
  rolls back for free. An SWA ring does NOT: wrapped writes alias earlier
  positions, so spec mode requires a full cache (``_require_extend_capable``)
  — and a recurrent carry cannot roll back at all.
* the DRAFTER's dense cache holds the accepted prefix exactly (a draft is
  only "kept" where it matched the target), garbage past the new position is
  overwritten before it is ever attended — the same write-before-attend
  invariant the bucketed prefill relies on. The drafter must therefore also
  be attention-only with a full cache; pure-SSM drafters are rejected at
  construction, not mid-serving.

The device step/proposed/accepted counters ride the engine's existing
once-per-chunk host sync — speculative serving adds ZERO extra transfers.
"""
from __future__ import annotations

from typing import Any, List

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models import group_pattern, init_lm_state, lm_decode, lm_extend, lm_prefill


class SpecDecoder:
    """Drafter-side state + the fused draft/verify chunk program. Owned by a
    :class:`repro.serve.engine.DecodeWorker` (``ecfg.spec_k > 0``); the
    worker delegates ``decode_chunk``/``sync`` here and forwards every
    admission so the drafter can prefill its own cache."""

    def __init__(self, worker, dcfg, dparams, k: int):
        non_attn = sorted({m for m, _ in group_pattern(dcfg) if m != "attn"})
        if non_attn:
            raise ValueError(
                f"drafter {dcfg.name}: speculative drafting requires attention-"
                f"only mixers, found {non_attn} — a recurrent carry cannot roll "
                "back past a rejected draft"
            )
        if dcfg.sliding_window > 0:
            raise ValueError(
                f"drafter {dcfg.name}: sliding_window={dcfg.sliding_window} makes "
                "the drafter cache a ring — stale rejected-draft writes would "
                "alias earlier positions after rollback. Draft with a full-"
                "attention config."
            )
        if dcfg.vocab_size != worker.cfg.vocab_size:
            raise ValueError(
                f"drafter {dcfg.name} vocab ({dcfg.vocab_size}) != target "
                f"{worker.cfg.name} vocab ({worker.cfg.vocab_size}): drafted ids "
                "would be meaningless to the verifier — pick a same-tokenizer "
                "drafter"
            )
        self.worker = worker
        self.dcfg = dcfg
        self.k = int(k)
        # one verify certifies up to k+1 tokens, so a chunk of decode_chunk
        # token-steps needs ~decode_chunk/(k+1) verify steps; the worker's
        # page planning uses `horizon` (tokens a chunk may emit)
        self.steps = max(1, worker.ecfg.decode_chunk // (self.k + 1))
        self.horizon = self.steps * (self.k + 1)
        if worker.mesh is not None:
            from repro.serve.engine import _shard_params

            dparams = _shard_params(dparams, worker.mesh)
        self.dparams = dparams
        self._draft: Any = None
        self._proposed = jnp.zeros((), jnp.int32)
        self._accepted = jnp.zeros((), jnp.int32)
        self._nsteps = jnp.zeros((), jnp.int32)
        self._chunk_jit = jax.jit(self._chunk_fn, donate_argnums=(2, 3))
        self._prefill_jit = jax.jit(self._prefill_fn)

    # -- device programs ----------------------------------------------------

    def _prefill_fn(self, dparams, tokens, slots, draft):
        """Drafter prompt prefill for one admitted group: fill a fresh
        (N, max_seq) dense state and splice each row onto its slot. Compiled
        per (N, bucket) like the target's own prefill. Pad-tail garbage past
        each true length is overwritten by sequential drafting before it is
        ever attended."""
        e = self.worker.ecfg
        n = tokens.shape[0]
        st1 = init_lm_state(self.dcfg, n, e.max_seq)
        _, st1 = lm_prefill(dparams, self.dcfg, {"tokens": tokens}, st1)

        def splice(big, one):
            for i in range(n):
                big = jax.lax.dynamic_update_slice(
                    big,
                    jax.lax.dynamic_slice_in_dim(one, i, 1, axis=1).astype(big.dtype),
                    (0, slots[i]) + (0,) * (big.ndim - 2),
                )
            return big

        return jax.tree_util.tree_map(splice, draft, st1)

    def _chunk_fn(self, params, dparams, ds, draft, proposed, accepted, nsteps):
        """Up to ``steps`` draft→verify rounds in ONE dispatch. Each round:
        the drafter greedily unrolls k tokens from the batch's last tokens,
        the target scores ``[last_tok, d_1..d_k]`` in one extend, and the
        longest draft run matching the target's own argmax is emitted (plus
        the bonus token the verify got for free). Emission replicates the
        non-speculative chunk's masking token-for-token, so budgets, EOS and
        output rows behave identically — only the dispatch count differs."""
        w = self.worker
        cfg, dcfg, e, k = w.cfg, self.dcfg, w.ecfg, self.k
        rows = jnp.arange(e.max_slots, dtype=jnp.int32)

        def cond(carry):
            i, s, d, p, a, ns = carry
            return (i < self.steps) & jnp.any(s.active)

        def body(carry):
            i, s, d, p, a, ns = carry
            # 1) draft: k greedy single-token steps (unrolled; the drafter is
            # small by design). Inactive slots ride along rewriting their
            # frozen position in their OWN dense rows — harmless, as in the
            # non-speculative chunk.
            dt, dpos, drafts = s.last_tok, s.pos, []
            for _ in range(k):
                dlog, d = lm_decode(dparams, dcfg, dt, d, dpos)
                nxt = jnp.argmax(dlog[:, -1], axis=-1).astype(jnp.int32)  # (S,)
                drafts.append(nxt)
                dt, dpos = nxt[:, None], dpos + 1
            # one extra cache-fill step: when every draft is accepted plus
            # the bonus token, the next round resumes at pos+k+1 — position
            # pos+k (token d_k) must already be in the drafter's cache or it
            # would draft against a hole and never be accepted again
            _, d = lm_decode(dparams, dcfg, dt, d, dpos)
            dmat = jnp.stack(drafts, axis=1)  # (S, k)
            # 2) verify: ONE target forward over [last_tok, d_1..d_k] at
            # pos..pos+k. tgt[:, j] is the target's greedy choice after
            # consuming x[:, :j+1] — exactly what the non-spec engine would
            # have sampled at that step, provided all earlier drafts matched.
            x = jnp.concatenate([s.last_tok, dmat], axis=1)  # (S, k+1)
            vlog, kv = lm_extend(params, cfg, x, s.kv, s.pos, s.page_table)
            tgt = jnp.argmax(vlog, axis=-1).astype(jnp.int32)  # (S, k+1)
            match = (dmat == tgt[:, :k]).astype(jnp.int32)
            n_acc = jnp.cumprod(match, axis=1).sum(axis=1)  # (S,) in [0, k]
            p = p + k * jnp.sum(s.active.astype(jnp.int32))
            a = a + jnp.sum(jnp.where(s.active, n_acc, 0))
            ns = ns + 1

            # 3) emit tgt[:, 0..n_acc] per slot through the SAME per-token
            # masking as the non-speculative body (budget, max_new, EOS) —
            # candidate j simply "doesn't happen" for slots whose accepted
            # run ended earlier, like an inactive slot skipping a step
            def emit(j, c):
                out, n_out, act, last, pos = c
                tok = tgt[:, j]
                step = act & (j <= n_acc)
                write = step & (n_out < e.max_new)
                idx = jnp.minimum(n_out, e.max_new - 1)
                out = out.at[rows, idx].set(jnp.where(write, tok, out[rows, idx]))
                n_out = n_out + write.astype(jnp.int32)
                finished = n_out >= s.budget
                if e.eos_token >= 0:
                    finished |= (tok == e.eos_token) & step
                last = jnp.where(step[:, None], tok[:, None], last)
                pos = pos + step.astype(jnp.int32)
                return out, n_out, act & ~finished, last, pos

            out, n_out, active, last_tok, pos = jax.lax.fori_loop(
                0, k + 1, emit, (s.out, s.n_out, s.active, s.last_tok, s.pos)
            )
            s = s._replace(
                kv=kv, last_tok=last_tok, pos=pos, active=active,
                out=out, n_out=n_out,
            )
            return i + 1, s, d, p, a, ns

        _, ds, draft, proposed, accepted, nsteps = jax.lax.while_loop(
            cond, body, (jnp.zeros((), jnp.int32), ds, draft, proposed, accepted, nsteps)
        )
        return ds, draft, proposed, accepted, nsteps

    # -- host API -----------------------------------------------------------

    def reset(self) -> None:
        """(Re)build the drafter's dense cache (all slots) and zero the
        device counters."""
        w = self.worker
        draft = init_lm_state(self.dcfg, w.ecfg.max_slots, w.ecfg.max_seq)
        if w.mesh is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            from repro.sharding.partition import shard_engine_state

            # the drafter cache shards by the same /k, /v suffix rules as the
            # target's dense engine state (heads over the model axis)
            specs = shard_engine_state({"draft": draft}, mesh_axes=dict(w.mesh.shape))
            shardings = jax.tree_util.tree_map(
                lambda spec: NamedSharding(w.mesh, spec), specs["draft"],
                is_leaf=lambda s: isinstance(s, P),
            )
            draft = jax.device_put(draft, shardings)
        self._draft = draft
        self._proposed = jnp.zeros((), jnp.int32)
        self._accepted = jnp.zeros((), jnp.int32)
        self._nsteps = jnp.zeros((), jnp.int32)

    def on_admit(self, slots: List[int], token_rows: np.ndarray, true_lens) -> None:
        """Prefill the drafter's cache rows for an admitted group. The
        drafter shares no pages with anyone — it always consumes the FULL
        (bucket-padded) prompt, even when the target spliced its prefix."""
        self._draft = self._prefill_jit(
            self.dparams,
            jnp.asarray(np.asarray(token_rows, np.int32)),
            jnp.asarray(np.asarray(slots, np.int32)),
            self._draft,
        )

    def chunk(self) -> None:
        """One fused draft/verify chunk; replaces the worker's plain chunk."""
        w = self.worker
        with obs.span("serve.spec.verify", replica=w.replica):
            (w._state, self._draft, self._proposed, self._accepted,
             self._nsteps) = self._chunk_jit(
                w.params, self.dparams, w._state, self._draft,
                self._proposed, self._accepted, self._nsteps,
            )

    def sync(self):
        """The worker's host sync, with the draft counters riding the SAME
        device-to-host transfer. The stats mirrors are cumulative-since-reset
        (assigned, not incremented)."""
        s = self.worker._state
        active, n_out, p, a, ns = jax.device_get(
            (s.active, s.n_out, self._proposed, self._accepted, self._nsteps)
        )
        st = self.worker.stats
        st["draft_proposed"] = int(p)
        st["draft_accepted"] = int(a)
        st["spec_steps"] = int(ns)
        return active, n_out
