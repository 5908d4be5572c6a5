import os
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=512"
"""Multi-pod dry-run driver (deliverable e).

Lowers + compiles every (architecture × input shape) step program against
the production mesh — 16×16 single-pod and 2×16×16 two-pod — and records
memory_analysis / cost_analysis / collective schedule for the roofline.

The two lines above run BEFORE any other import: jax locks the device
count at first init, and only the dry-run is allowed to see 512 placeholder
CPU devices (smoke tests and benches see 1).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod both \
      --out results/dryrun.json
"""
import argparse
import json
import time
import traceback
from typing import Any, Dict

import jax

from repro.config import INPUT_SHAPES, arch_supports_shape, get_arch, list_archs
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import build_coboost_program, build_program
from repro.roofline import roofline_report
from repro.utils import get_logger

log = get_logger("dryrun")


def _parse_value(v: str):
    for cast in (int, float):
        try:
            return cast(v)
        except ValueError:
            pass
    if v in ("true", "True"):
        return True
    if v in ("false", "False"):
        return False
    return v


def _custom_mesh(spec: str):
    dims = tuple(int(d) for d in spec.split("x"))
    axes = {2: ("data", "model"), 3: ("pod", "data", "model")}[len(dims)]
    return jax.make_mesh(dims, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def dryrun_one(
    arch: str,
    shape_name: str,
    multi_pod: bool = False,
    verbose: bool = True,
    coboost_clients: int = 0,
    cfg_override=None,
    overrides: Dict[str, Any] = None,
    tc_overrides: Dict[str, Any] = None,
    mesh_shape: str = "",
    kl_chunk: int = 0,
) -> Dict[str, Any]:
    """Lower + compile one (arch, shape, mesh) combination; returns the
    roofline record (or a skip/error record). ``coboost_clients > 0`` lowers
    the paper-technique ensemble-distillation step instead of the plain
    step. ``overrides``/``tc_overrides``/``mesh_shape``/``kl_chunk`` are the
    §Perf hillclimb levers."""
    from repro.launch.specs import DRYRUN_TC

    cfg = cfg_override if cfg_override is not None else get_arch(arch)
    if overrides:
        cfg = cfg.replace(**overrides)
    tc = DRYRUN_TC
    if tc_overrides:
        import dataclasses as _dc

        tc = _dc.replace(tc, **tc_overrides)
    shape = INPUT_SHAPES[shape_name]
    mesh_name = mesh_shape or ("2x16x16" if multi_pod else "16x16")
    rec: Dict[str, Any] = {"arch": arch, "shape": shape_name, "mesh": mesh_name}
    if coboost_clients:
        rec["coboost_clients"] = coboost_clients
    if overrides:
        rec["overrides"] = overrides
    if tc_overrides:
        rec["tc_overrides"] = tc_overrides
    if kl_chunk:
        rec["kl_chunk"] = kl_chunk
    skip = arch_supports_shape(cfg, shape)
    if skip:
        rec.update(status="skip", reason=skip)
        return rec
    mesh = _custom_mesh(mesh_shape) if mesh_shape else make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    with jax.set_mesh(mesh):
        if coboost_clients:
            fn, args, in_sh, out_sh = build_coboost_program(
                cfg, shape, coboost_clients, tc=tc, kl_chunk=kl_chunk
            )
        else:
            fn, args, in_sh, out_sh = build_program(cfg, shape, tc=tc)
        lowered = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        hlo = compiled.as_text()
        report = roofline_report(compiled, mesh.size, cfg, shape, hlo_text=hlo)
    rec.update(
        status="ok",
        kind=shape.kind,
        lower_s=round(t_lower, 1),
        compile_s=round(t_compile, 1),
        **report,
    )
    if verbose:
        print(compiled.memory_analysis())
        print({k: v for k, v in compiled.cost_analysis().items() if k in ("flops", "bytes accessed")})
    return rec


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default=None, help="architecture id (see --list)")
    p.add_argument("--shape", default=None, choices=list(INPUT_SHAPES))
    p.add_argument("--all", action="store_true", help="every (arch, shape) pair")
    p.add_argument("--multi-pod", default="single", choices=("single", "multi", "both"))
    p.add_argument("--out", default=None, help="append JSON records here")
    p.add_argument("--list", action="store_true")
    p.add_argument(
        "--coboost",
        type=int,
        default=0,
        metavar="K",
        help="lower the K-client Co-Boosting distillation step instead",
    )
    p.add_argument(
        "--override", action="append", default=[], metavar="K=V",
        help="ModelConfig field override (e.g. moe_impl=scatter)",
    )
    p.add_argument(
        "--tc-override", action="append", default=[], metavar="K=V",
        help="TrainConfig field override (e.g. state_dtype=bfloat16)",
    )
    p.add_argument("--mesh-shape", default="", help="custom mesh, e.g. 32x8 or 2x32x8")
    p.add_argument("--kl-chunk", type=int, default=0, help="chunked distill-KL (coboost)")
    args = p.parse_args()
    overrides = dict(kv.split("=", 1) for kv in args.override)
    overrides = {k: _parse_value(v) for k, v in overrides.items()}
    tc_overrides = dict(kv.split("=", 1) for kv in args.tc_override)
    tc_overrides = {k: _parse_value(v) for k, v in tc_overrides.items()}

    if args.list:
        for a in list_archs():
            print(a)
        return

    pairs = []
    archs = list_archs() if (args.all or not args.arch) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                pairs.append((a, s, mp))

    records = []
    n_ok = n_skip = n_err = 0
    for a, s, mp in pairs:
        label = f"{a} × {s} × {'2x16x16' if mp else '16x16'}"
        try:
            rec = dryrun_one(
                a, s, multi_pod=mp, verbose=not args.all, coboost_clients=args.coboost,
                overrides=overrides, tc_overrides=tc_overrides,
                mesh_shape=args.mesh_shape, kl_chunk=args.kl_chunk,
            )
        except Exception as e:  # a failure here is a bug in the system
            rec = {
                "arch": a, "shape": s, "mesh": "2x16x16" if mp else "16x16",
                "status": "error", "error": f"{type(e).__name__}: {e}",
            }
            traceback.print_exc()
        records.append(rec)
        if rec["status"] == "ok":
            n_ok += 1
            log.info(
                "%s OK compile=%.0fs dominant=%s bound=%.4fs fits=%s",
                label, rec["compile_s"], rec["dominant"], rec["bound_s"], rec["fits_hbm"],
            )
        elif rec["status"] == "skip":
            n_skip += 1
            log.info("%s SKIP (%s)", label, rec["reason"])
        else:
            n_err += 1
            log.error("%s ERROR %s", label, rec["error"])
        if args.out:
            with open(args.out, "w") as f:
                json.dump(records, f, indent=1)
    log.info("dry-run done: %d ok, %d skip, %d error", n_ok, n_skip, n_err)
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
