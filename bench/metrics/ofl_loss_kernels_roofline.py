"""ofl_loss_kernels_roofline: the fused loss kernels' (ensemble_kl, ghm_ce,
forward and backward) least time on the roofline, from their operations
and bytes by shape (bench/flops/losses.py), over their device time in the
traced epochs. They are the epoch program's only Pallas kernels, so every
``tpu_custom_call`` of the window is theirs. Moves ofl_epoch_ms."""
from benchlib import readers, trace as tr
from flops import losses, ofl as fo


def read(ctx):
    t = readers.traced(ctx, "ofl")
    if t is None:
        return None
    cfg, peaks = ctx["config"], ctx["peaks"]
    t0, t1 = tr.window(t)
    devs = tr.devices(t)
    kernel_s = sum(tr.ops_time(d, tr.is_pallas(d), t0, t1) for d in devs) / len(devs)
    least = sum(
        losses.least_time(losses.epoch_calls(cfg, fo.kd_batches(e, cfg["buffer_batches"])),
                          peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"])
        for e in ctx["epoch_indices"]
    )
    return readers.share(least, kernel_s)
