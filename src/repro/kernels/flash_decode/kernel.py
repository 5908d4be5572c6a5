"""Paged flash-decode (Sq = 1) attention Pallas TPU kernel.

Decode attention against the :class:`repro.serve.kv_pool.KVPool` paged cache:
each grid step gathers ONE fixed-size KV page through the per-slot page table
and folds it into VMEM-resident online-softmax statistics, so HBM traffic is
the live pages only — never a dense ``(slots, max_len)`` rectangle.

  * grid ``(B, W)`` — pages minor, so the (m, l, acc) scratch carries one
    row's statistics across its page sweep. Each step DMAs one whole page,
    every KV head at once: a ``(1, ps, KH, hd)`` block spans the full
    ``(KH, hd)`` minor dims, which is the only KV-head blocking the TPU's
    (8, 128) tiling accepts when ``KH`` is not a multiple of 8;
  * the page table and per-row positions ride in as **scalar prefetch**
    (:class:`pltpu.PrefetchScalarGridSpec`): the K/V BlockSpec index maps read
    ``table[b, w]`` to DMA the right page — the gather happens in the
    pipeline, not the kernel body;
  * masking reconstructs each logical index's absolute position from the
    row's position scalar (sliding-window ring math identical to the dense
    ``attn_decode``), and fully-masked pages are skipped via ``@pl.when``;
  * GQA puts the ``q_per_kv`` query heads of one (row, kv-head) pair on the
    MXU tile's sublanes — tiny tiles (g ≤ 8 rows), which is the nature of
    Sq=1 decode; the kernel body loops over the KV heads of the page (a
    static unroll). Batching across slots is the engine's job, not the
    grid's.

Unallocated page-table entries point at the pool's scratch page — a valid
page id whose reads are fully masked (it exists as a safe DMA/write target;
see :class:`repro.serve.kv_pool.KVPool`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(
    table_ref,  # scalar prefetch: (B, W) int32 page table
    pos_ref,  # scalar prefetch: (B,) int32 per-row positions
    q_ref,  # (1, KH, G, hd)
    k_ref,  # (1, ps, KH, hd) — the page picked by the index map
    v_ref,
    o_ref,  # (1, KH, G, hd)
    m_ref,  # VMEM (KH, G, 1)
    l_ref,  # VMEM (KH, G, 1)
    acc_ref,  # VMEM (KH, G, hd)
    *,
    window: int,
    softcap: float,
    page_size: int,
    num_kv_heads: int,
    num_pages: int,
    cache_len: int,
    scale: float,
):
    b = pl.program_id(0)
    wi = pl.program_id(1)

    @pl.when(wi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    p_b = pos_ref[b]
    # a page is live iff some logical index in [wi·ps, wi·ps + ps) is valid:
    # windowless caches fill front-to-back (live iff base <= p); ring caches
    # are live everywhere once wrapped, and front-to-back before that.
    base = wi * page_size
    page_live = (base <= p_b) & (base < cache_len)
    if window > 0:
        page_live |= (p_b >= cache_len) & (base < cache_len)

    @pl.when(page_live)
    def _page():
        j = base + jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)
        if window > 0:
            slot_w = p_b % cache_len
            wrap = (p_b // cache_len) * cache_len
            k_pos = jnp.where(j <= slot_w, wrap + j, wrap - cache_len + j)
            ok = (k_pos >= 0) & (k_pos <= p_b) & (k_pos > p_b - window)
        else:
            ok = j <= p_b
        ok &= j < cache_len  # (1, ps), shared by every head of the page
        for h in range(num_kv_heads):
            q = q_ref[0, h].astype(jnp.float32) * scale  # (G, hd)
            k = k_ref[0, :, h].astype(jnp.float32)  # (ps, hd)
            v = v_ref[0, :, h].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )  # (G, ps)
            if softcap > 0:
                s = jnp.tanh(s / softcap) * softcap
            s = jnp.where(ok, s, NEG)

            m_old = m_ref[h]
            m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
            p_exp = jnp.exp(s - m_new)
            corr = jnp.exp(m_old - m_new)
            l_ref[h] = l_ref[h] * corr + jnp.sum(p_exp, axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
                p_exp, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
            m_ref[h] = m_new

    @pl.when(wi == num_pages - 1)
    def _final():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_decode_pallas(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_table: jax.Array,
    pos: jax.Array,
    *,
    window: int = 0,
    softcap: float = 0.0,
    cache_len: int = 0,
    interpret: bool = False,
) -> jax.Array:
    """q: (B, H, hd); k_pages/v_pages: (P, ps, KH, hd) with H % KH == 0;
    page_table: (B, W) int32; pos: (B,) int32. Returns (B, H, hd)."""
    b, h, hd = q.shape
    ps, kh = k_pages.shape[1], k_pages.shape[2]
    w = page_table.shape[1]
    g = h // kh
    cl = cache_len or w * ps
    qf = q.reshape(b, kh, g, hd)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, w),
        in_specs=[
            pl.BlockSpec((1, kh, g, hd), lambda bi, wi, tbl, psc: (bi, 0, 0, 0)),
            pl.BlockSpec((1, ps, kh, hd), lambda bi, wi, tbl, psc: (tbl[bi, wi], 0, 0, 0)),
            pl.BlockSpec((1, ps, kh, hd), lambda bi, wi, tbl, psc: (tbl[bi, wi], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, kh, g, hd), lambda bi, wi, tbl, psc: (bi, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kh, g, 1), jnp.float32),
            pltpu.VMEM((kh, g, 1), jnp.float32),
            pltpu.VMEM((kh, g, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _kernel,
            window=window,
            softcap=softcap,
            page_size=ps,
            num_kv_heads=kh,
            num_pages=w,
            cache_len=cl,
            scale=1.0 / float(hd) ** 0.5,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, g, hd), q.dtype),
        interpret=interpret,
        name="flash_decode",
    )(page_table.astype(jnp.int32), pos.reshape(-1).astype(jnp.int32), qf, k_pages, v_pages)
    return out.reshape(b, h, hd)
