"""What every cell shares: locating files by name, the chip check, the
compile cache, seeds, the compile clock and the result line."""
from __future__ import annotations

import json
import math
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
CHECKOUT = BENCH.parent
SRC = CHECKOUT / "src"
CACHE_DIR = CHECKOUT / ".jax_cache"


class Refused(RuntimeError):
    """The run cannot be made here (no chip, too few chips, no program)."""


def load_json(kind: str, name: str) -> dict:
    """``bench/<kind>/<name>.json``: a configuration, workload or traffic mix."""
    path = BENCH / kind / f"{name}.json"
    if not path.is_file():
        raise Refused(f"no {kind[:-1]} named {name!r} ({path})")
    return json.loads(path.read_text())


def load_module(path: Path):
    """Import a file by path: configurations' references and metric readers
    are named after entries whose names are not Python identifiers."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(f"bench_{path.stem.replace('-', '_').replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_of(config_name: str):
    """The plain reference that sits beside a configuration's file."""
    return load_module(BENCH / "configs" / f"{config_name}.py")


def manifest() -> dict:
    path = CHECKOUT / "BENCHMARK.json"
    if not path.is_file():
        raise Refused(f"no BENCHMARK.json at {path}")
    return json.loads(path.read_text())


def need_program() -> None:
    if not (SRC / "repro" / "core" / "epoch.py").is_file():
        raise Refused(f"the program under test is missing ({SRC / 'repro'})")
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


def need_chips(jax, chips: int):
    """The devices the cell runs on; refuses anything but TPUs enough."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX's first device is {devs[0].platform!r}")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def enable_cache(jax) -> str:
    """JAX's persistent cache at a fixed path inside the checkout, every
    program kept, no size cap: a cell's second run compiles nothing."""
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return str(CACHE_DIR)


def seed_key(jax, seed: int):
    """A PRNG key from a seed of any size up to 64 bits."""
    if not 0 <= seed < 2**64:
        raise ValueError(f"--seed must be in [0, 2**64), got {seed}")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


class CompileClock:
    """Counts XLA backend compiles and their seconds as JAX's monitoring
    events report them (a program loaded from the persistent cache counts
    too, with its load time)."""

    def __init__(self, jax):
        from jax._src import dispatch

        self.compiles = 0
        self.compile_s = 0.0
        event = dispatch.BACKEND_COMPILE_EVENT

        def on_duration(name, secs, **_):
            if name == event:
                self.compiles += 1
                self.compile_s += secs

        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def snapshot(self) -> tuple:
        return self.compiles, self.compile_s


def peak_memory(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices]
    return max(peaks)


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}


def peaks_for(kind: str) -> dict:
    """The chip's published peaks; a device kind missing from the table is
    an error, never a default."""
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise Refused(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


def metric(value, unit: str) -> dict:
    return {"value": float(value), "unit": unit}


def check(value: float, limit: float) -> dict:
    """One compared number beside its limit; NaN never passes."""
    return {"value": float(value), "limit": float(limit)}


def checks_pass(checks: dict) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"] for c in checks.values())


def emit_result(result: dict) -> None:
    """The compared numbers as the last lines of stderr, then the result
    (``checks`` its last key) as the last line of stdout."""
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)

