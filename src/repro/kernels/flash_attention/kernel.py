"""Blocked causal / sliding-window attention Pallas TPU kernel.

The long-context shapes (prefill_32k, long_500k SWA) make attention the
compute hot spot; this kernel is the TPU tiling of the online-softmax
algorithm (same math as :func:`repro.models.attention.flash_attn_jax`, its
lowering-friendly jnp twin):

  * grid (batch·kv_head·q_per_kv, q_tiles, kv_tiles) — kv minor so the
    (m, l, acc) statistics stay in VMEM scratch across a kv sweep;
  * blocks (block_q, head_dim) / (block_kv, head_dim) — head_dim padded to
    the 128-lane width, block_q a multiple of 8 sublanes; the s·v product
    hits the MXU with both contraction dims 128-aligned;
  * causal and sliding-window masks are computed from program ids, and
    fully-masked kv tiles are skipped via the mask check inside @pl.when
    (interpret mode runs them; on TPU the compiler hoists the branch).

GQA is handled by folding q_per_kv into the grid's batch dim so each kernel
instance sees exactly one (q-head, kv-head) pair — no head broadcast inside.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    *refs,  # [lse_ref,] m_ref, l_ref, acc_ref
    return_lse: bool,
    causal: bool,
    window: int,
    softcap: float,
    block_q: int,
    block_kv: int,
    num_kv_tiles: int,
    seq_k: int,
    scale: float,
):
    lse_ref = refs[0] if return_lse else None
    m_ref, l_ref, acc_ref = refs[-3:]
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale  # (bq, hd)
    k = k_ref[0].astype(jnp.float32)  # (bkv, hd)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bkv)
    if softcap > 0:
        s = jnp.tanh(s / softcap) * softcap

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    ok = k_pos < seq_k
    if causal:
        ok &= k_pos <= q_pos
    if window > 0:
        ok &= k_pos > q_pos - window
    s = jnp.where(ok, s, NEG)

    m_old = m_ref[...]
    m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_old - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ()))
    )
    m_ref[...] = m_new

    @pl.when(ki == num_kv_tiles - 1)
    def _final():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)
        if return_lse:
            l, m = l_ref[...], m_ref[...]
            # fully-masked rows (padding beyond an SWA tail) get a huge lse so
            # a recompute backward's p = exp(s - lse) underflows to exactly 0
            lse_ref[0] = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), 1e30)


def _mask_and_p(qs, kb, lse, qi, ki, *, causal, window, softcap, block_q, block_kv, seq_k):
    """Rebuild one (bq, bkv) probability tile from the saved lse.

    Returns (p, dact): the exact forward probabilities (p = exp(s − lse) is
    0 on masked/padded columns because s = NEG there, and 0 on fully-masked
    rows because their saved lse is 1e30) and the softcap chain factor
    dact = 1 − tanh²(u/cap) evaluated at the pre-cap scores (1 without
    softcap)."""
    s = jax.lax.dot_general(qs, kb, (((1,), (1,)), ((), ())))  # (bq, bkv)
    if softcap > 0:
        t = jnp.tanh(s / softcap)
        dact = 1.0 - t * t
        s = t * softcap
    else:
        dact = 1.0
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    ok = k_pos < seq_k
    if causal:
        ok &= k_pos <= q_pos
    if window > 0:
        ok &= k_pos > q_pos - window
    s = jnp.where(ok, s, NEG)
    p = jnp.exp(s - lse)
    return p, dact


def _bwd_dq_kernel(
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    lse_ref,
    delta_ref,
    dq_ref,
    acc_ref,
    *,
    causal: bool,
    window: int,
    softcap: float,
    block_q: int,
    block_kv: int,
    num_kv_tiles: int,
    seq_k: int,
    scale: float,
):
    """dq pass: kv minor, so the (bq, hd) dq accumulator stays in VMEM
    scratch across a kv sweep — the score tile is recomputed from the saved
    lse, never re-materialized in HBM."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qs = q_ref[0].astype(jnp.float32) * scale  # (bq, hd)
    kb = k_ref[0].astype(jnp.float32)  # (bkv, hd)
    vb = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)  # (bq, hd)
    lse = lse_ref[0]  # (bq, 1)
    delta = delta_ref[0]

    p, dact = _mask_and_p(
        qs, kb, lse, qi, ki, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_kv=block_kv, seq_k=seq_k,
    )
    dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())))  # (bq, bkv)
    du = p * (dp - delta) * dact  # grad wrt the pre-cap scores u = qs·kᵀ
    acc_ref[...] += jax.lax.dot_general(du, kb, (((1,), (0,)), ((), ())))

    @pl.when(ki == num_kv_tiles - 1)
    def _final():
        dq_ref[0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    lse_ref,
    delta_ref,
    dk_ref,
    dv_ref,
    dk_acc,
    dv_acc,
    *,
    causal: bool,
    window: int,
    softcap: float,
    block_q: int,
    block_kv: int,
    num_q_tiles: int,
    seq_k: int,
    scale: float,
):
    """dk/dv pass: q minor, so the two (bkv, hd) accumulators stay in VMEM
    scratch across a q sweep. Emits per-q-head dk/dv (the wrapper reduces
    the GQA broadcast over g outside)."""
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    qs = q_ref[0].astype(jnp.float32) * scale  # (bq, hd)
    kb = k_ref[0].astype(jnp.float32)  # (bkv, hd)
    vb = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0]  # (bq, 1)
    delta = delta_ref[0]

    p, dact = _mask_and_p(
        qs, kb, lse, qi, ki, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_kv=block_kv, seq_k=seq_k,
    )
    dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())))
    du = p * (dp - delta) * dact
    dv_acc[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())))  # pᵀ·do
    dk_acc[...] += jax.lax.dot_general(du, qs, (((0,), (0,)), ((), ())))  # duᵀ·qs

    @pl.when(qi == num_q_tiles - 1)
    def _final():
        dk_ref[0] = dk_acc[...]
        dv_ref[0] = dv_acc[...]


def flash_attention_bwd_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    out: jax.Array,
    lse: jax.Array,
    dout: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    block_q: int = 256,
    block_kv: int = 256,
    interpret: bool = False,
):
    """Fused backward for :func:`flash_attention_pallas`.

    ``out``/``lse`` are the forward's output and per-row logsumexp
    (``return_lse=True``); ``dout`` the output cotangent. Returns
    ``(dq, dk, dv)`` with the input dtypes. Two streamed passes over the
    forward's tiling — dq with kv minor, dk/dv with q minor — each
    rebuilding the probability tile from the saved lse instead of
    re-materializing score blocks; delta = Σ dout·out is the only jnp
    precompute (O(S·hd)). dk/dv come out per q-head and are reduced over
    the GQA group outside the kernel."""
    b, sq, h, hd = q.shape
    sk, kh = k.shape[1], k.shape[2]
    g = h // kh
    scale = 1.0 / (hd**0.5)
    block_q = min(block_q, max(8, sq))
    block_kv = min(block_kv, max(8, sk))
    pq = (-sq) % block_q
    pk = (-sk) % block_kv

    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)  # (B,Sq,H)
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
        dout = jnp.pad(dout, ((0, 0), (0, pq), (0, 0), (0, 0)))
        # padded q rows: lse=1e30 makes p underflow to exact 0, delta=0
        lse = jnp.pad(lse, ((0, 0), (0, pq), (0, 0)), constant_values=1e30)
        delta = jnp.pad(delta, ((0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    sqp, skp = sq + pq, sk + pk
    nq, nk = sqp // block_q, skp // block_kv

    bhg = b * kh * g
    qf = q.reshape(b, sqp, kh, g, hd).transpose(0, 2, 3, 1, 4).reshape(bhg, sqp, hd)
    dof = dout.reshape(b, sqp, kh, g, hd).transpose(0, 2, 3, 1, 4).reshape(bhg, sqp, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kh, skp, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kh, skp, hd)
    # per-row statistics as (BHG, S, 1) columns: a (block_q, 1) block spans
    # the full minor dim, which the TPU's (8, 128) tiling accepts
    lsef = lse.reshape(b, sqp, kh, g).transpose(0, 2, 3, 1).reshape(bhg, sqp, 1)
    deltaf = delta.reshape(b, sqp, kh, g).transpose(0, 2, 3, 1).reshape(bhg, sqp, 1)

    common = dict(
        causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_kv=block_kv, seq_k=sk, scale=scale,
    )
    in_specs_q_minorless = [  # shared operand layout for both passes
        pl.BlockSpec((1, block_q, hd), lambda bh, i, j, g=g: (bh, i, 0)),
        pl.BlockSpec((1, block_kv, hd), lambda bh, i, j, g=g: (bh // g, j, 0)),
        pl.BlockSpec((1, block_kv, hd), lambda bh, i, j, g=g: (bh // g, j, 0)),
        pl.BlockSpec((1, block_q, hd), lambda bh, i, j: (bh, i, 0)),
        pl.BlockSpec((1, block_q, 1), lambda bh, i, j: (bh, i, 0)),
        pl.BlockSpec((1, block_q, 1), lambda bh, i, j: (bh, i, 0)),
    ]

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, num_kv_tiles=nk, **common),
        grid=(bhg, nq, nk),
        in_specs=in_specs_q_minorless,
        out_specs=pl.BlockSpec((1, block_q, hd), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bhg, sqp, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32)],
        interpret=interpret,
        name="flash_attention_dq",
    )(qf, kf, vf, dof, lsef, deltaf)

    # q-minor pass: same operands, grid dims (bh, ki, qi) — swap the maps
    in_specs_kv = [
        pl.BlockSpec((1, block_q, hd), lambda bh, ki, qi: (bh, qi, 0)),
        pl.BlockSpec((1, block_kv, hd), lambda bh, ki, qi, g=g: (bh // g, ki, 0)),
        pl.BlockSpec((1, block_kv, hd), lambda bh, ki, qi, g=g: (bh // g, ki, 0)),
        pl.BlockSpec((1, block_q, hd), lambda bh, ki, qi: (bh, qi, 0)),
        pl.BlockSpec((1, block_q, 1), lambda bh, ki, qi: (bh, qi, 0)),
        pl.BlockSpec((1, block_q, 1), lambda bh, ki, qi: (bh, qi, 0)),
    ]
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, num_q_tiles=nq, **common),
        grid=(bhg, nk, nq),
        in_specs=in_specs_kv,
        out_specs=[pl.BlockSpec((1, block_kv, hd), lambda bh, ki, qi: (bh, ki, 0))] * 2,
        out_shape=[jax.ShapeDtypeStruct((bhg, skp, hd), jnp.float32)] * 2,
        scratch_shapes=[pltpu.VMEM((block_kv, hd), jnp.float32)] * 2,
        interpret=interpret,
        name="flash_attention_dkdv",
    )(qf, kf, vf, dof, lsef, deltaf)

    dq = dq.reshape(b, kh, g, sqp, hd).transpose(0, 3, 1, 2, 4).reshape(b, sqp, h, hd)
    # reduce the GQA group onto the kv heads, then restore (B, Sk, KH, hd)
    dk = dk_h.reshape(b, kh, g, skp, hd).sum(2).transpose(0, 2, 1, 3)
    dv = dv_h.reshape(b, kh, g, skp, hd).sum(2).transpose(0, 2, 1, 3)
    return (
        dq[:, :sq],
        dk[:, :sk].astype(k.dtype),
        dv[:, :sk].astype(v.dtype),
    )


def flash_attention_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    block_q: int = 256,
    block_kv: int = 256,
    interpret: bool = False,
    return_lse: bool = False,
):
    """q: (B, Sq, H, hd); k, v: (B, Sk, KH, hd), H % KH == 0.
    Returns (B, Sq, H, hd), plus the per-row logsumexp (B, Sq, H) when
    ``return_lse`` (the residual a recompute backward needs)."""
    b, sq, h, hd = q.shape
    sk, kh = k.shape[1], k.shape[2]
    g = h // kh
    scale = 1.0 / (hd**0.5)
    block_q = min(block_q, max(8, sq))
    block_kv = min(block_kv, max(8, sk))
    pq = (-sq) % block_q
    pk = (-sk) % block_kv
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    sqp, skp = sq + pq, sk + pk
    nq, nk = sqp // block_q, skp // block_kv

    # fold (B, KH, G) into one grid dim; layout (BHG, S, hd)
    bhg = b * kh * g
    qf = q.reshape(b, sqp, kh, g, hd).transpose(0, 2, 3, 1, 4).reshape(bhg, sqp, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kh, skp, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kh, skp, hd)

    out_specs = [pl.BlockSpec((1, block_q, hd), lambda bh, qi, ki: (bh, qi, 0))]
    out_shape = [jax.ShapeDtypeStruct((bhg, sqp, hd), q.dtype)]
    if return_lse:
        out_specs.append(pl.BlockSpec((1, block_q, 1), lambda bh, qi, ki: (bh, qi, 0)))
        out_shape.append(jax.ShapeDtypeStruct((bhg, sqp, 1), jnp.float32))
    res = pl.pallas_call(
        functools.partial(
            _kernel,
            return_lse=return_lse,
            causal=causal,
            window=window,
            softcap=softcap,
            block_q=block_q,
            block_kv=block_kv,
            num_kv_tiles=nk,
            seq_k=sk,
            scale=scale,
        ),
        grid=(bhg, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_kv, hd), lambda bh, qi, ki: (bh // g, ki, 0)),
            pl.BlockSpec((1, block_kv, hd), lambda bh, qi, ki: (bh // g, ki, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(qf, kf, vf)
    out = res[0].reshape(b, kh, g, sqp, hd).transpose(0, 3, 1, 2, 4).reshape(b, sqp, h, hd)
    if not return_lse:
        return out[:, :sq]
    lse = res[1].reshape(b, kh, g, sqp).transpose(0, 3, 1, 2).reshape(b, sqp, h)
    return out[:, :sq], lse[:, :sq]
