#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is ``bench/workloads/<cell>.json``; it names its configuration
(``bench/configs/``), its traffic (``bench/traffic/``) and its driver kind.
``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, each read by ``bench/metrics/<metric>.py`` from the
profiler trace of the window and the program's counters.

The last line of stdout is one JSON object with ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when tracing), and
``checks`` last: each number compared with the plain reference beside its
limit, which also end stderr. Without a TPU, without the chips the cell
asks for, or without the program beside ``bench/``, it exits non-zero and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchlib import common  # noqa: E402


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def cell_files(name: str) -> tuple:
    work = common.load_json("workloads", name)
    return work, common.load_json("configs", work["config"]), common.load_json("traffic", work["traffic"])


def reported(man: dict, section: str, cell: str, e2e: set) -> list:
    """The manifest's metrics of ``section`` that this cell reports: those
    that list it, or, without a list, every metric (end-to-end) or every
    one that moves a metric this cell reports (per-layer)."""
    out = []
    for m in man[section]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif section == "end_to_end" or m["moves"] in e2e:
            out.append(m)
    return out


def per_layer(ctx: dict, metrics: list) -> dict:
    out = {}
    for m in metrics:
        reader = common.load_module(common.BENCH / "metrics" / f"{m['name']}.py")
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = common.metric(value, m["unit"])
    return out


def run(args, *, cpu_peaks: dict | None = None) -> dict:
    """One run of a cell; returns the result line. ``cpu_peaks`` (the
    harness's own CPU tests only) skips the look for a chip and stands in
    for the peaks table, which has no CPU."""
    common.need_program()
    man = common.manifest()
    work, cfg, traffic = cell_files(args.workload)
    import jax

    if cpu_peaks is not None:
        devices = jax.devices()[: work["chips"]]
    else:
        devices = common.need_chips(jax, work["chips"])
    common.enable_cache(jax)
    from benchlib import ofl, serve, trace

    kind = {"ofl": ofl, "serve": serve}[work["driver"]]
    ctx = {
        "jax": jax, "workload": work, "config": cfg, "traffic": traffic, "name": args.workload,
        "seed": args.seed, "seconds": args.seconds, "trace": bool(args.trace), "devices": devices,
        "t_start": T_START, "compile_clock": common.CompileClock(jax),
    }
    out = kind.run_cell(ctx)
    e2e = reported(man, "end_to_end", args.workload, set())
    e2e_names = {m["name"] for m in e2e}
    device = common.device_info(devices)
    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    result = {"correct": common.checks_pass(out["checks"]) and out["failed"] == 0,
              "attempted": out["attempted"], "failed": out["failed"]}
    if args.trace:
        ctx["peaks"] = cpu_peaks if cpu_peaks is not None else common.peaks_for(device["kind"])
        metrics = per_layer(ctx, reported(man, "per_layer", args.workload, e2e_names))
        tr = ctx["trace_data"]
        device["busy_s"] = trace.busy_s(tr)
        device["window_s"] = trace.window_s(tr)
        result.update(metrics=metrics, device=device, breakdown=trace.breakdown(tr))
    else:
        missing = e2e_names - set(out["metrics"])
        if missing:
            raise RuntimeError(f"the cell's driver measured no {sorted(missing)}")
        result.update(metrics={k: v for k, v in out["metrics"].items() if k in e2e_names}, device=device)
    print(f"notes {out['notes']}", file=sys.stderr, flush=True)
    result["checks"] = out["checks"]
    return result


def main(argv=None) -> int:
    args = parse(argv)
    try:
        result = run(args)
    except common.Refused as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    common.emit_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
