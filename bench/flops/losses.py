"""Operations and bytes of the fused loss kernels, from shapes.

``ensemble_kl`` and ``ghm_ce`` read the (K, B, C) client logits in float32
and reduce them with the (K,) weights; the backward kernels write the
logits' cotangent back. Bytes are what the algorithm must move, unpadded.
"""
from __future__ import annotations

F32 = 4


def ensemble_kl_fwd(k: int, b: int, c: int) -> tuple:
    flops = 2.0 * k * b * c + 12.0 * b * c
    nbytes = F32 * (k * b * c + b * c + k + b + 2 * b)
    return flops, nbytes


def ensemble_kl_bwd(k: int, b: int, c: int) -> tuple:
    flops = 4.0 * k * b * c + 16.0 * b * c
    nbytes = F32 * (2 * k * b * c + 2 * b * c + 2 * k + 4 * b)
    return flops, nbytes


def ghm_ce_fwd(k: int, b: int, c: int) -> tuple:
    flops = 2.0 * k * b * c + 6.0 * b * c
    nbytes = F32 * (k * b * c + b + k + b + 2 * b)
    return flops, nbytes


def ghm_ce_bwd(k: int, b: int, c: int) -> tuple:
    flops = 4.0 * k * b * c + 10.0 * b * c
    nbytes = F32 * (2 * k * b * c + 2 * k + 5 * b)
    return flops, nbytes


def epoch_calls(cfg: dict, kd_batches: int) -> list:
    """The loss-kernel calls of one epoch as (flops, bytes) pairs: per
    generator step GHM-CE and ensemble-KL forward and backward, the
    generator loss once more forward, EE's plain CE forward and backward,
    and one ensemble-KL forward and backward per KD step."""
    k, b, c = cfg["clients"], cfg["batch_size"], cfg["classes"]
    gen_step = [ghm_ce_fwd(k, b, c), ghm_ce_bwd(k, b, c), ensemble_kl_fwd(k, b, c), ensemble_kl_bwd(k, b, c)]
    calls = gen_step * cfg["gen_iters"]
    calls += [ghm_ce_fwd(k, b, c), ensemble_kl_fwd(k, b, c)]
    calls += [ghm_ce_fwd(k, b, c), ghm_ce_bwd(k, b, c)]
    calls += [ensemble_kl_fwd(k, b, c), ensemble_kl_bwd(k, b, c)] * kd_batches
    return calls


def least_time(calls, peak_flops: float, peak_bytes: float) -> float:
    """The roofline's least time of a set of calls: each bound by the larger
    of its operations and its bytes over the chip's peaks."""
    return sum(max(f / peak_flops, nb / peak_bytes) for f, nb in calls)
