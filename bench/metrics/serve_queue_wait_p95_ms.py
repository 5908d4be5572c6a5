"""serve_queue_wait_p95_ms: the 95th percentile, over the window's finished
requests, of the scheduler's queue wait: the stamp taken before the
admitting prefill is dispatched (Completion.admitted) minus the request's
due time. Moves serve_norm_latency_ms."""
import numpy as np


def read(ctx):
    s = ctx.get("serve") if ctx["workload"]["driver"] == "serve" else None
    if not s or not s["queue_wait_s"]:
        return None
    return 1000.0 * float(np.percentile(s["queue_wait_s"], 95))
