#!/usr/bin/env python3
"""Many windows of a serve cell in one process, for what a cell's fixed
numbers are set from (the benchmark's own runs never run this).

    python3 bench/serve_sweep.py --workload <cell> --seconds 30 --rates 1,1.5,2
    python3 bench/serve_sweep.py --workload <cell> --seconds 30 --seeds 1,2,3

``--rates``: the knee. One engine, set up and warmed once, serves the
cell's traffic at each rate in turn (run seed ``RATE_SEED``); one JSON
line per rate with the window's statistics as a run reports them
(normalized latency, tokens per second, the tails), then the knee
(``knee``): the highest rate up to which no backlog builds.

``--seeds``: the readings behind the limits of ``correct``. At the cell's
own rate, one window per seed on that seed's weights (handed to the warmed
engine in place of the last seed's, so nothing compiles again), then the
compared numbers for the program's served tokens and for the control: the
reference with float8 products, reading the gap of the token it puts first
at the same positions. One JSON line per seed.

Without a TPU it exits non-zero, as ``run.py`` does.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchlib import common  # noqa: E402

RATE_SEED = 2**31 + 11
QUEUE_P95_S = 1.0  # about one decode chunk and one prefill: no backlog behind it


def window(jax, prog, serve, mix, seed, seconds, tail_s, vocab):
    """One window on the warmed engine: its requests, the valid completions
    and the row of its statistics (``serve.window_stats``, the run's own)."""
    reqs = serve.window_requests(mix, seed, seconds, vocab)
    by_rid = {r.rid: r for r in reqs}
    clock = serve.WindowClock(jax, seconds + tail_s)
    t0 = time.perf_counter()
    try:
        comps, late = prog.serve(reqs, clock), None
    except serve.Deadline as err:
        comps, late = [], str(err)
    wall = time.perf_counter() - t0
    good = serve.valid(comps, by_rid, vocab)
    row = {"late": late, **serve.window_stats(reqs, good, wall, seconds),
           "longest_host_step_s": clock.longest_step_s, "longest_host_step_at_s": clock.longest_step_at_s}
    return by_rid, good, row


def knee(rows) -> float | None:
    """The highest swept rate up to which no backlog builds: every rate at
    or below it finished all it was offered with a p95 queue wait under
    ``QUEUE_P95_S``."""
    best = None
    for row in sorted(rows, key=lambda r: r["rate"]):
        if row["late"] or row["finished"] < row["offered"] or not row["queue_wait_p95_s"] < QUEUE_P95_S:
            break
        best = row["rate"]
    return best


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", default="")
    p.add_argument("--seeds", default="")
    args = p.parse_args(argv)
    try:
        common.need_program()
        work = common.load_json("workloads", args.workload)
        cfg = common.load_json("configs", work["config"])
        mix = common.load_json("traffic", work["traffic"])
        import jax

        common.need_chips(jax, work["chips"])
    except common.Refused as e:
        print(f"serve_sweep: {e}", file=sys.stderr)
        return 2
    common.enable_cache(jax)
    from benchlib import serve

    ref = common.reference_of(cfg["name"])
    e, vocab = cfg["engine"], cfg["vocab_size"]
    rates = [float(r) for r in args.rates.split(",") if r]
    seeds = [int(s) for s in args.seeds.split(",") if s]
    first = seeds[0] if seeds else RATE_SEED
    prog = serve.Program(jax, cfg, first)
    prog.start(ref.make_weights(common.seed_key(jax, first), cfg))
    t = time.perf_counter()
    prog.warm(mix["prompt"]["max"])
    print(json.dumps({"warm_s": time.perf_counter() - t, "warm": prog.warm_notes}), flush=True)
    rows = []
    for rate in rates:
        _, _, row = window(jax, prog, serve, dict(mix, rate=rate), RATE_SEED, args.seconds, 600.0, vocab)
        rows.append(dict(row, rate=rate))
        print(json.dumps(rows[-1]), flush=True)
    if rows:
        print(json.dumps({"knee": knee(rows)}), flush=True)
    for seed in seeds:
        weights = ref.make_weights(common.seed_key(jax, seed), cfg)
        eng = prog.engine
        eng.params = eng.prefill.params = eng.decode.params = weights
        by_rid, good, row = window(jax, prog, serve, mix, seed, args.seconds, work["tail_s"], vocab)
        picked = serve.sample(good, by_rid, seed, work["sample_drawn"])
        row["seed"] = seed
        row["program"] = serve.readings(jax, ref, cfg, weights, picked, by_rid, e["max_seq"], e["max_new"])
        row["control_fp8"] = serve.readings(jax, ref, cfg, weights, picked, by_rid, e["max_seq"], e["max_new"],
                                            control="fp8")
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
