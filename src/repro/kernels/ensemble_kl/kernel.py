"""Fused weighted-ensemble + temperature-KL Pallas TPU kernel.

Eq. 4 of the paper evaluates KL(A_w(x) ‖ f_S(x)) where A_w = Σ_k w_k·f_k is
the weighted client-logit ensemble. Materializing A_w for an LLM vocab
(e.g. 151,936) means an extra K×(B,V) + (B,V) HBM round-trip per step. This
kernel streams (K, bb, bv) client-logit tiles and (bb, bv) student tiles
through VMEM, combines them with w on the fly, and maintains *online*
softmax statistics so the KL per sample is produced in a single pass:

    KL·T² where  KL = N/D − (log D + m_t) + (log D_s + m_s)
    N  = Σ_v e^{t_v−m_t}·(t_v − s_v),  D = Σ_v e^{t_v−m_t}
    (t, s are the temperature-scaled teacher/student logits)

Grid: (batch_tiles, vocab_tiles); vocab is the minor (fastest) grid dim so
the five (bb,) accumulators live in VMEM scratch across a vocab sweep.
Blocks are (8·n, 128·m)-aligned for the VPU; the combine is a K-step fma,
not an MXU matmul — this kernel is memory-bound by design (the roofline win
is removing the A_w HBM materialization).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import tile_padding

NEG = -1e30


def _kernel(w_ref, client_ref, student_ref, out_ref, lset_ref, lses_ref, mt_ref, dt_ref, nt_ref, ms_ref, ds_ref, *, temperature: float, num_vocab_tiles: int, vocab: int, block_v: int):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        mt_ref[...] = jnp.full_like(mt_ref, NEG)
        dt_ref[...] = jnp.zeros_like(dt_ref)
        nt_ref[...] = jnp.zeros_like(nt_ref)
        ms_ref[...] = jnp.full_like(ms_ref, NEG)
        ds_ref[...] = jnp.zeros_like(ds_ref)

    w = w_ref[...]  # (K, 1) f32
    cl = client_ref[...].astype(jnp.float32)  # (K, bb, bv)
    t = jnp.sum(w[:, :, None] * cl, axis=0) / temperature  # (bb, bv)
    s = student_ref[...].astype(jnp.float32) / temperature

    # mask the padded vocab tail
    col = vi * block_v + jax.lax.broadcasted_iota(jnp.int32, t.shape, 1)
    valid = col < vocab
    t = jnp.where(valid, t, NEG)
    s_for_lse = jnp.where(valid, s, NEG)
    diff = jnp.where(valid, t - s, 0.0)

    # online teacher stats
    mt_old = mt_ref[...]
    mt_new = jnp.maximum(mt_old, jnp.max(t, axis=-1, keepdims=True))
    corr_t = jnp.exp(mt_old - mt_new)
    p = jnp.exp(t - mt_new)
    dt_ref[...] = dt_ref[...] * corr_t + jnp.sum(p, axis=-1, keepdims=True)
    nt_ref[...] = nt_ref[...] * corr_t + jnp.sum(p * diff, axis=-1, keepdims=True)
    mt_ref[...] = mt_new

    # online student logsumexp
    ms_old = ms_ref[...]
    ms_new = jnp.maximum(ms_old, jnp.max(s_for_lse, axis=-1, keepdims=True))
    ds_ref[...] = ds_ref[...] * jnp.exp(ms_old - ms_new) + jnp.sum(
        jnp.exp(s_for_lse - ms_new), axis=-1, keepdims=True
    )
    ms_ref[...] = ms_new

    @pl.when(vi == num_vocab_tiles - 1)
    def _final():
        d = dt_ref[...]
        lse_t = jnp.log(d) + mt_ref[...]
        lse_s = jnp.log(ds_ref[...]) + ms_ref[...]
        kl = nt_ref[...] / d - lse_t + lse_s
        out_ref[...] = (kl * (temperature**2)).astype(out_ref.dtype)
        # the online-softmax statistics double as the VJP residuals
        lset_ref[...] = lse_t.astype(lset_ref.dtype)
        lses_ref[...] = lse_s.astype(lses_ref.dtype)


def _bwd_kernel(
    w_ref,
    client_ref,
    student_ref,
    g_ref,
    out_ref,
    lset_ref,
    lses_ref,
    gcl_ref,
    gst_ref,
    gw_ref,
    *,
    temperature: float,
    vocab: int,
    block_v: int,
):
    """One (batch, vocab) tile of the Eq. 4 VJP (see ops.py for the math).

    Everything is recomputed tile-resident from the forward's online-softmax
    residuals: the weighted combine t = A_w/T is rebuilt from the streamed
    client tile (A_w itself never exists in HBM, same as the forward), p and
    q come from the saved logsumexps, and the three cotangents are emitted in
    the same sweep — g_cl and g_st tile-by-tile, g_w accumulated in a
    revisited (K, 1) output block that stays VMEM-resident across the whole
    grid (its index map is constant)."""
    bi = pl.program_id(0)
    vi = pl.program_id(1)

    @pl.when((bi == 0) & (vi == 0))
    def _init():
        gw_ref[...] = jnp.zeros_like(gw_ref)

    w = w_ref[...]  # (K, 1) f32
    cl = client_ref[...].astype(jnp.float32)  # (K, bb, bv)
    t = jnp.sum(w[:, :, None] * cl, axis=0) / temperature  # (bb, bv)
    s = student_ref[...].astype(jnp.float32) / temperature

    col = vi * block_v + jax.lax.broadcasted_iota(jnp.int32, t.shape, 1)
    valid = col < vocab
    t = jnp.where(valid, t, NEG)
    s = jnp.where(valid, s, NEG)

    lse_t = lset_ref[...]  # (bb, 1)
    lse_s = lses_ref[...]
    g = g_ref[...]
    kl_u = out_ref[...] / (temperature * temperature)  # unscaled KL from the primal

    p = jnp.exp(t - lse_t)  # exact 0 on the padded vocab tail
    q = jnp.exp(s - lse_s)
    gT = g * temperature  # (bb, 1)
    g_ens = gT * (p * ((t - lse_t) - (s - lse_s) - kl_u))
    g_ens = jnp.where(valid, g_ens, 0.0)

    gcl_ref[...] = (w[:, :, None] * g_ens[None]).astype(gcl_ref.dtype)
    gst_ref[...] = (gT * (q - p)).astype(gst_ref.dtype)
    # reduce one axis at a time with every value kept 2-D: Mosaic has no
    # layout for the 1-D (K,) intermediate of a two-axis reduction
    gw_ref[...] += jnp.sum(jnp.sum(cl * g_ens[None], axis=2), axis=1, keepdims=True)


def ensemble_kl_bwd_pallas(
    client_logits: jax.Array,
    student_logits: jax.Array,
    w: jax.Array,
    g: jax.Array,
    out: jax.Array,
    lse_t: jax.Array,
    lse_s: jax.Array,
    temperature: float = 1.0,
    *,
    block_b: int = 8,
    block_v: int = 512,
    interpret: bool = False,
):
    """Fused backward for :func:`ensemble_kl_pallas`.

    ``g`` is the per-sample cotangent (B,); ``out``/``lse_t``/``lse_s`` are
    the forward's primal output and online-softmax residuals. Returns
    ``(g_client, g_student, g_w)`` with the input dtypes — one streamed pass
    over the same (batch, vocab) grid as the forward, never materializing
    A_w (or any K×(B,V) f32 temporary beyond the cotangent itself)."""
    k, b, v = client_logits.shape
    block_b, block_v, pb, pv = tile_padding(b, v, block_b, block_v)
    if pb or pv:
        client_logits = jnp.pad(client_logits, ((0, 0), (0, pb), (0, pv)))
        student_logits = jnp.pad(student_logits, ((0, pb), (0, pv)))
    if pb:
        # padded rows carry a zero cotangent: every padded-row grad is zero
        g = jnp.pad(g, ((0, pb),))
        out = jnp.pad(out, ((0, pb),))
        lse_t = jnp.pad(lse_t, ((0, pb),))
        lse_s = jnp.pad(lse_s, ((0, pb),))
    bp, vp = b + pb, v + pv
    nb, nv = bp // block_b, vp // block_v

    row = lambda x: x.astype(jnp.float32).reshape(bp, 1)
    g_cl, g_st, g_w = pl.pallas_call(
        functools.partial(
            _bwd_kernel, temperature=float(temperature), vocab=v, block_v=block_v
        ),
        grid=(nb, nv),
        in_specs=[
            pl.BlockSpec((k, 1), lambda bi, vi: (0, 0)),
            pl.BlockSpec((k, block_b, block_v), lambda bi, vi: (0, bi, vi)),
            pl.BlockSpec((block_b, block_v), lambda bi, vi: (bi, vi)),
            pl.BlockSpec((block_b, 1), lambda bi, vi: (bi, 0)),
            pl.BlockSpec((block_b, 1), lambda bi, vi: (bi, 0)),
            pl.BlockSpec((block_b, 1), lambda bi, vi: (bi, 0)),
            pl.BlockSpec((block_b, 1), lambda bi, vi: (bi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((k, block_b, block_v), lambda bi, vi: (0, bi, vi)),
            pl.BlockSpec((block_b, block_v), lambda bi, vi: (bi, vi)),
            pl.BlockSpec((k, 1), lambda bi, vi: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, bp, vp), client_logits.dtype),
            jax.ShapeDtypeStruct((bp, vp), student_logits.dtype),
            jax.ShapeDtypeStruct((k, 1), jnp.float32),
        ],
        interpret=interpret,
        name="ensemble_kl_bwd",
    )(
        w.astype(jnp.float32).reshape(k, 1),
        client_logits,
        student_logits,
        row(g),
        row(out),
        row(lse_t),
        row(lse_s),
    )
    return g_cl[:, :b, :v], g_st[:b, :v], g_w[:, 0].astype(w.dtype)


def ensemble_kl_pallas(
    client_logits: jax.Array,
    student_logits: jax.Array,
    w: jax.Array,
    temperature: float = 1.0,
    *,
    block_b: int = 8,
    block_v: int = 512,
    interpret: bool = False,
    return_stats: bool = False,
):
    """client_logits: (K, B, V); student_logits: (B, V); w: (K,).
    Returns per-sample KL·T² of shape (B,); with ``return_stats=True`` also
    the teacher/student logsumexp over the T-scaled logits (the VJP
    residuals), each (B,).

    Tiles never shrink below the (8, 128) VPU alignment: short batches and
    narrow vocabs are zero-padded up to the block instead (padded rows are
    computed on benign zeros and sliced off; the padded vocab tail is masked
    inside the kernel)."""
    k, b, v = client_logits.shape
    block_b, block_v, pb, pv = tile_padding(b, v, block_b, block_v)
    if pb or pv:
        client_logits = jnp.pad(client_logits, ((0, 0), (0, pb), (0, pv)))
        student_logits = jnp.pad(student_logits, ((0, pb), (0, pv)))
    bp, vp = b + pb, v + pv
    nb, nv = bp // block_b, vp // block_v

    out, lse_t, lse_s = pl.pallas_call(
        functools.partial(
            _kernel,
            temperature=float(temperature),
            num_vocab_tiles=nv,
            vocab=v,
            block_v=block_v,
        ),
        grid=(nb, nv),
        in_specs=[
            pl.BlockSpec((k, 1), lambda bi, vi: (0, 0)),
            pl.BlockSpec((k, block_b, block_v), lambda bi, vi: (0, bi, vi)),
            pl.BlockSpec((block_b, block_v), lambda bi, vi: (bi, vi)),
        ],
        out_specs=[pl.BlockSpec((block_b, 1), lambda bi, vi: (bi, 0))] * 3,
        out_shape=[jax.ShapeDtypeStruct((bp, 1), jnp.float32)] * 3,
        scratch_shapes=[pltpu.VMEM((block_b, 1), jnp.float32) for _ in range(5)],
        interpret=interpret,
        name="ensemble_kl_fwd",
    )(w.astype(jnp.float32).reshape(k, 1), client_logits, student_logits)
    if return_stats:
        return out[:b, 0], lse_t[:b, 0], lse_s[:b, 0]
    return out[:b, 0]
