"""ofl_mfu: the whole epoch's share of the chip's bf16 peak. The operations
the algorithm needs per epoch (bench/flops/ofl.py, from shapes), times the
epochs per second of the traced window, over the peak. Moves ofl_epoch_ms."""
from benchlib import readers, trace as tr
from flops import ofl as fo


def read(ctx):
    t = readers.traced(ctx, "ofl")
    if t is None:
        return None
    cfg = ctx["config"]
    work = sum(fo.epoch(cfg, fo.kd_batches(e, cfg["buffer_batches"])) for e in ctx["epoch_indices"])
    return readers.share(work / tr.window_s(t), ctx["peaks"]["bf16_flops_per_s"])
