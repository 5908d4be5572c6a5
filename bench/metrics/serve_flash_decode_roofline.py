"""serve_flash_decode_roofline: the paged decode attention kernel's
(flash_decode) least time on the roofline over its device time in the
traced window. Its calls are the Pallas kernels inside the decode-chunk
program, one per layer and step. The least time of a call is the larger of
its operations over the bf16 peak and the bytes it must read (each active
slot's keys and values over its context) over the HBM bandwidth
(bench/flops/serve.py), from the serve harness's count of every step's
active slots and contexts. Moves serve_tpot_ms."""
from benchlib import programs, readers, trace as tr
from flops import serve as fs


def read(ctx):
    t = readers.traced(ctx, "serve")
    if t is None:
        return None
    needle = ctx["serve"]["chunk_program"]
    t0, t1 = tr.window(t)
    devs = tr.devices(t)
    kernel_s = sum(programs.op_seconds(d, needle, t0, t1, tr.is_pallas(d)) for d in devs) / len(devs)
    peaks = ctx["peaks"]
    least = fs.flash_decode_least_s(ctx["config"], ctx["serve"]["decode_steps"],
                                    peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"])
    return readers.share(least, kernel_s)
