"""Fused GHM-difficulty-weighted cross-entropy (Eq. 5–6) Pallas TPU kernel.

The hard-sample generator loss weights each sample's CE by its difficulty
d = 1 − softmax(A_w(x))_y. Both quantities come from the same softmax
statistics, so the kernel computes the weighted ensemble tile, the online
logsumexp, and the label logit in one vocab sweep:

    lse  = m + log Σ e^{t−m}        (online across vocab tiles)
    l_y  = t[label]                 (picked up in the tile that owns label)
    out  = (1 − e^{l_y − lse}) · (lse − l_y)

Grid: (batch_tiles, vocab_tiles), vocab minor; scratch: m, d, ly per row.
Labels ride along as a (bb, 1) int32 block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import tile_padding

NEG = -1e30


def _kernel(
    w_ref,
    client_ref,
    label_ref,
    out_ref,
    lse_ref,
    lyo_ref,
    m_ref,
    d_ref,
    ly_ref,
    *,
    num_vocab_tiles: int,
    vocab: int,
    block_v: int,
    weighted: bool,
):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        d_ref[...] = jnp.zeros_like(d_ref)
        ly_ref[...] = jnp.zeros_like(ly_ref)

    w = w_ref[...]  # (K, 1)
    cl = client_ref[...].astype(jnp.float32)  # (K, bb, bv)
    t = jnp.sum(w[:, :, None] * cl, axis=0)  # (bb, bv)

    col = vi * block_v + jax.lax.broadcasted_iota(jnp.int32, t.shape, 1)
    valid = col < vocab
    t = jnp.where(valid, t, NEG)

    m_old = m_ref[...]
    m_new = jnp.maximum(m_old, jnp.max(t, axis=-1, keepdims=True))
    d_ref[...] = d_ref[...] * jnp.exp(m_old - m_new) + jnp.sum(
        jnp.exp(t - m_new), axis=-1, keepdims=True
    )
    m_ref[...] = m_new

    labels = label_ref[...]  # (bb, 1) int32
    hit = col == labels  # (bb, bv)
    ly_ref[...] += jnp.sum(jnp.where(hit, t, 0.0), axis=-1, keepdims=True)

    @pl.when(vi == num_vocab_tiles - 1)
    def _final():
        lse = jnp.log(d_ref[...]) + m_ref[...]
        ly = ly_ref[...]
        nll = lse - ly
        if weighted:
            d_hard = 1.0 - jnp.exp(ly - lse)  # Eq. 5
            nll = d_hard * nll  # Eq. 6
        out_ref[...] = nll.astype(out_ref.dtype)
        # the online-softmax statistics double as the VJP residuals
        lse_ref[...] = lse.astype(lse_ref.dtype)
        lyo_ref[...] = ly.astype(lyo_ref.dtype)


def _bwd_kernel(
    w_ref,
    client_ref,
    label_ref,
    g_ref,
    lse_ref,
    ly_ref,
    gcl_ref,
    gw_ref,
    *,
    vocab: int,
    block_v: int,
    weighted: bool,
    stop_difficulty_grad: bool,
):
    """One (batch, vocab) tile of the Eq. 5–6 VJP (see ops.py for the math).

    d(out)/dt factors as coeff · (p − e): ``p`` is rebuilt per tile from the
    saved logsumexp, the one-hot ``e`` from the label block, and the per-row
    ``coeff`` (which mode-switches on ``weighted``/``stop_difficulty_grad``)
    costs only the (bb, 1) residuals. g_cl streams out tile-by-tile; g_w
    accumulates in a VMEM-resident (K, 1) block across the whole grid."""
    bi = pl.program_id(0)
    vi = pl.program_id(1)

    @pl.when((bi == 0) & (vi == 0))
    def _init():
        gw_ref[...] = jnp.zeros_like(gw_ref)

    w = w_ref[...]  # (K, 1) f32
    cl = client_ref[...].astype(jnp.float32)  # (K, bb, bv)
    t = jnp.sum(w[:, :, None] * cl, axis=0)  # (bb, bv)

    col = vi * block_v + jax.lax.broadcasted_iota(jnp.int32, t.shape, 1)
    valid = col < vocab
    t = jnp.where(valid, t, NEG)

    lse = lse_ref[...]  # (bb, 1)
    ly = ly_ref[...]
    g = g_ref[...]
    p = jnp.exp(t - lse)  # exact 0 on the padded vocab tail
    onehot = (col == label_ref[...]).astype(jnp.float32)  # (bb, bv)

    if not weighted:
        coeff = jnp.ones_like(lse)
    else:
        py = jnp.exp(ly - lse)
        coeff = 1.0 - py
        if not stop_difficulty_grad:
            coeff = coeff + py * (lse - ly)

    g_t = (g * coeff) * (p - onehot)
    g_t = jnp.where(valid, g_t, 0.0)
    gcl_ref[...] = (w[:, :, None] * g_t[None]).astype(gcl_ref.dtype)
    # reduce one axis at a time with every value kept 2-D: Mosaic has no
    # layout for the 1-D (K,) intermediate of a two-axis reduction
    gw_ref[...] += jnp.sum(jnp.sum(cl * g_t[None], axis=2), axis=1, keepdims=True)


def ghm_ce_bwd_pallas(
    client_logits: jax.Array,
    labels: jax.Array,
    w: jax.Array,
    g: jax.Array,
    lse: jax.Array,
    ly: jax.Array,
    *,
    weighted: bool = True,
    stop_difficulty_grad: bool = False,
    block_b: int = 8,
    block_v: int = 512,
    interpret: bool = False,
):
    """Fused backward for :func:`ghm_ce_pallas`.

    ``g`` is the per-sample cotangent (B,); ``lse``/``ly`` the forward's
    online residuals (ensemble logsumexp + label logit). Returns
    ``(g_client, g_w)`` with the input dtypes; labels are integer and carry
    no cotangent. Same grid and streaming discipline as the forward."""
    k, b, v = client_logits.shape
    block_b, block_v, pb, pv = tile_padding(b, v, block_b, block_v)
    if pb or pv:
        client_logits = jnp.pad(client_logits, ((0, 0), (0, pb), (0, pv)))
    if pb:
        # padded rows carry label 0 and a ZERO cotangent — every grad is zero
        labels = jnp.pad(labels, ((0, pb),))
        g = jnp.pad(g, ((0, pb),))
        lse = jnp.pad(lse, ((0, pb),))
        ly = jnp.pad(ly, ((0, pb),))
    bp, vp = b + pb, v + pv
    nb, nv = bp // block_b, vp // block_v

    row = lambda x: x.astype(jnp.float32).reshape(bp, 1)
    g_cl, g_w = pl.pallas_call(
        functools.partial(
            _bwd_kernel, vocab=v, block_v=block_v,
            weighted=weighted, stop_difficulty_grad=stop_difficulty_grad,
        ),
        grid=(nb, nv),
        in_specs=[
            pl.BlockSpec((k, 1), lambda bi, vi: (0, 0)),
            pl.BlockSpec((k, block_b, block_v), lambda bi, vi: (0, bi, vi)),
            pl.BlockSpec((block_b, 1), lambda bi, vi: (bi, 0)),
            pl.BlockSpec((block_b, 1), lambda bi, vi: (bi, 0)),
            pl.BlockSpec((block_b, 1), lambda bi, vi: (bi, 0)),
            pl.BlockSpec((block_b, 1), lambda bi, vi: (bi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((k, block_b, block_v), lambda bi, vi: (0, bi, vi)),
            pl.BlockSpec((k, 1), lambda bi, vi: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, bp, vp), client_logits.dtype),
            jax.ShapeDtypeStruct((k, 1), jnp.float32),
        ],
        interpret=interpret,
        name="ghm_ce_bwd",
    )(
        w.astype(jnp.float32).reshape(k, 1),
        client_logits,
        labels.astype(jnp.int32).reshape(bp, 1),
        row(g),
        row(lse),
        row(ly),
    )
    return g_cl[:, :b, :v], g_w[:, 0].astype(w.dtype)


def ghm_ce_pallas(
    client_logits: jax.Array,
    labels: jax.Array,
    w: jax.Array,
    *,
    weighted: bool = True,
    block_b: int = 8,
    block_v: int = 512,
    interpret: bool = False,
    return_stats: bool = False,
):
    """client_logits: (K, B, V); labels: (B,) int32; w: (K,).
    Returns per-sample d·CE (or plain CE when ``weighted=False``), (B,);
    with ``return_stats=True`` also the ensemble logsumexp and label logit
    (the VJP residuals), each (B,).

    Tiles never shrink below the (8, 128) VPU alignment: short batches and
    narrow vocabs are zero-padded up to the block instead (padded rows are
    computed on benign zeros and sliced off; the padded vocab tail is masked
    inside the kernel)."""
    k, b, v = client_logits.shape
    block_b, block_v, pb, pv = tile_padding(b, v, block_b, block_v)
    if pb or pv:
        client_logits = jnp.pad(client_logits, ((0, 0), (0, pb), (0, pv)))
    if pb:
        labels = jnp.pad(labels, ((0, pb),))
    bp, vp = b + pb, v + pv
    nb, nv = bp // block_b, vp // block_v

    out, lse, ly = pl.pallas_call(
        functools.partial(
            _kernel, num_vocab_tiles=nv, vocab=v, block_v=block_v, weighted=weighted
        ),
        grid=(nb, nv),
        in_specs=[
            pl.BlockSpec((k, 1), lambda bi, vi: (0, 0)),
            pl.BlockSpec((k, block_b, block_v), lambda bi, vi: (0, bi, vi)),
            pl.BlockSpec((block_b, 1), lambda bi, vi: (bi, 0)),
        ],
        out_specs=[pl.BlockSpec((block_b, 1), lambda bi, vi: (bi, 0))] * 3,
        out_shape=[jax.ShapeDtypeStruct((bp, 1), jnp.float32)] * 3,
        scratch_shapes=[pltpu.VMEM((block_b, 1), jnp.float32) for _ in range(3)],
        interpret=interpret,
        name="ghm_ce_fwd",
    )(
        w.astype(jnp.float32).reshape(k, 1),
        client_logits,
        labels.astype(jnp.int32).reshape(bp, 1),
    )
    if return_stats:
        return out[:b, 0], lse[:b, 0], ly[:b, 0]
    return out[:b, 0]
