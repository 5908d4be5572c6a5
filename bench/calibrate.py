#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, many seeds in one
process (the benchmark's own runs never run this).

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 [--control-seeds 1,2,3]

For each seed it prints, as one JSON line, every number a run of the cell
can compare (``benchlib.ofl.readings``), read against the plain reference at
the configuration's precision (``<variant>``) and against the reference with
exact f32 products (``<variant>_vs_highest``):

- ``program``: the program (the lower readings);
- ``witness_highest``: the reference with exact f32 products, against the
  one at the configuration's precision (how far product rounding alone
  moves the epochs);

and for each control seed:

- ``control_bf16``: the reference in bfloat16, one precision below the
  configuration's, put in the program's place (an upper reading);
- ``control_fp8_products``: the reference with float8 products, one step
  below the program's one-pass bfloat16 products;
- ``fault_half_batch``: the reference with half of each batch left out of
  the losses' means, put in the program's place.

A state left unchanged reads 1 in every change-based number and needs no
run. Without a TPU it exits non-zero, as ``run.py`` does.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchlib import common  # noqa: E402


def ofl_readings(jax, cfg, n_check, seeds, control_seeds):
    import jax.numpy as jnp

    from benchlib import ofl

    ref = common.reference_of(cfg["name"])
    prog = ofl.Program(jax, cfg, cfg["backend"])
    for seed in sorted(set(seeds) | set(control_seeds)):
        t = time.perf_counter()
        wkey, rkey = jax.random.split(common.seed_key(jax, seed))
        weights = ref.make_weights(wkey, cfg)
        server0, gen0 = ref.to_host(weights[1]), ref.to_host(weights[2])
        r = ref.run(weights, rkey, cfg, n_check)
        r_highest = ref.run(weights, rkey, cfg, n_check, precision="highest")
        row = {"seed": seed, "reference": _detail(r, server0), "reference_highest": _detail(r_highest, server0)}
        variants = {}
        if seed in seeds:
            prog.start(ref.make_weights(wkey, cfg))
            variants["program"] = prog.checked_epochs(rkey, n_check)
            prog.free()
            row["witness_highest"] = ofl.readings(r_highest, r, server0, gen0)
        if seed in control_seeds:
            variants["control_bf16"] = ref.run(weights, rkey, cfg, n_check, dtype=jnp.bfloat16)
            variants["control_fp8_products"] = ref.run(weights, rkey, cfg, n_check, products="fp8")
            variants["fault_half_batch"] = ref.run(weights, rkey, cfg, n_check, rows=cfg["batch_size"] // 2)
        for name, got in variants.items():
            row[name] = ofl.readings(got, r, server0, gen0)
            row[name + "_vs_highest"] = ofl.readings(got, r_highest, server0, gen0)
            row[name + "_detail"] = _detail(got, server0)
        row["s"] = time.perf_counter() - t
        print(json.dumps(row), flush=True)


def _detail(got: dict, server0) -> dict:
    """Per-epoch losses and per-leaf norms, for the look behind a limit."""
    from benchlib import compare

    return {
        "gen_loss": got["gen_loss"], "kd_loss": got["kd_loss"],
        "first_grad_norms": compare.leaf_norms(got["first_grad"]),
        "update_norms": compare.leaf_norms(compare.tree_sub(got["server"], server0)),
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", default="")
    args = p.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]
    try:
        common.need_program()
        work = common.load_json("workloads", args.workload)
        cfg = common.load_json("configs", work["config"])
        tcfg = common.load_json("traffic", work["traffic"])
        import jax

        common.need_chips(jax, work["chips"])
    except common.Refused as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    common.enable_cache(jax)
    ofl_readings(jax, cfg, tcfg["check_epochs"], seeds, control_seeds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
