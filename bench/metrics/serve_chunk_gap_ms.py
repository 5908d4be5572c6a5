"""serve_chunk_gap_ms: the mean device idle between one execution of the
decode-chunk program and the next, in milliseconds, on the first chip: the
scheduler's host work between chunks (sync, eviction, admission planning,
page-table updates). Pairs between which the scheduler slept for the next
arrival (the harness's bench.sleep span) are left out. Moves
serve_tpot_ms."""
import numpy as np

from benchlib import programs, readers, trace as tr


def read(ctx):
    t = readers.traced(ctx, "serve")
    if t is None:
        return None
    t0, t1 = tr.window(t)
    dev = tr.devices(t)[0]
    gaps = programs.idle_between(t, dev, programs.runs(dev, ctx["serve"]["chunk_program"], t0, t1), "bench.sleep")
    return 1000.0 * float(np.mean(gaps)) if gaps else None
