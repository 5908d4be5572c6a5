"""serve_decode_ms: device milliseconds per decode chunk: the union of the
operations inside the executions of the engine's decode-chunk program in the
traced window, over the number of executions, averaged over the chips. A
chunk is up to decode_chunk batched steps over every slot. Moves
serve_tpot_ms."""
from benchlib import programs, readers, trace as tr


def read(ctx):
    t = readers.traced(ctx, "serve")
    if t is None:
        return None
    needle = ctx["serve"]["chunk_program"]
    t0, t1 = tr.window(t)
    devs = tr.devices(t)
    chunks = len(programs.runs(devs[0], needle, t0, t1))
    if not chunks:
        return None
    secs = sum(programs.op_seconds(d, needle, t0, t1) for d in devs) / len(devs)
    return 1000.0 * secs / chunks
