"""serve_mfu: the whole window's share of the chip's bf16 peak. The
operations the window's finished requests need (bench/flops/serve.py: each
prompt's prefill, one decode step per later token; no padding, no idle
slots), over the traced window's seconds, over the peak. Moves
serve_tpot_ms."""
from benchlib import readers, trace as tr
from flops import serve as fs


def read(ctx):
    t = readers.traced(ctx, "serve")
    if t is None:
        return None
    work = sum(fs.request(ctx["config"], p, n) for p, n in ctx["serve"]["requests"])
    return readers.share(work / tr.window_s(t), ctx["peaks"]["bf16_flops_per_s"])
