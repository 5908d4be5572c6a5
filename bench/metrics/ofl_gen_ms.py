"""ofl_gen_ms: device milliseconds per epoch of the operations under the
epoch program's named scope ``ofl.gen.boost``, averaged over the traced epochs
and the chips. Moves ofl_epoch_ms."""
from benchlib import readers, trace as tr


def read(ctx):
    t = readers.traced(ctx, "ofl")
    if t is None:
        return None
    t0, t1 = tr.window(t)
    devs = tr.devices(t)
    secs = sum(tr.ops_time(d, tr.in_scope(d, "ofl.gen.boost"), t0, t1) for d in devs) / len(devs)
    return 1000.0 * secs / ctx["epochs_traced"] if secs > 0 else None
