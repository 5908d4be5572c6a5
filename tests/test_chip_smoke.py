"""``chip_smoke.py`` off the chip: its refusals, and each phase rehearsed on
the CPU with interpret-mode kernels at a small scale.

On a TPU the phases run the compiled kernels at full width. Here the same
code runs with ``pallas-interpret`` and cut sizes, so a fault in the
script's own checks (request accounting, parity bookkeeping, per-replica
placement) shows before a chip run. Every run is a subprocess: the fleet
phase needs four host devices, which XLA fixes when it starts.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


def _run(argv, cwd=ROOT, **env):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"), **env)
    return subprocess.run(argv, cwd=cwd, env=env, capture_output=True, text=True, timeout=900)


def _phases(code: str, **env) -> dict:
    out = _run([sys.executable, "-c", "import jax, chip_smoke as cs\n" + code], **env)
    assert out.returncode == 0, out.stderr[-4000:]
    recs = [json.loads(line) for line in out.stdout.splitlines() if line.startswith("{")]
    return {r["phase"]: r for r in recs}


def test_refuses_without_a_tpu():
    out = _run([sys.executable, str(SCRIPT)])
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert out.stdout == ""


def test_refuses_without_the_package(tmp_path):
    shutil.copy(SCRIPT, tmp_path)
    out = _run([sys.executable, "chip_smoke.py"], cwd=tmp_path)
    assert out.returncode != 0
    assert "repro package is missing" in out.stderr
    assert out.stdout == ""


def test_ofl_phase_rehearsal():
    ph = _phases(
        'cs.ofl_phase(jax, cs.CompileClock(jax), "pallas-interpret", '
        "per_class=10, test_per_class=20, gen_iters=1, batch=8)"
    )
    assert ph["ofl.coboosting"]["epochs"] == 2
    assert ph["ofl.parity.losses"]["worst"] <= ph["ofl.parity.losses"]["tol"]
    assert ph["ofl.parity"]["ok"] and ph["ofl.parity"]["epoch_update"] > 0


def test_serve_phase_rehearsal():
    ph = _phases(
        'cs.serve_phase(jax, cs.CompileClock(jax), "pallas-interpret", reduced=True, '
        "requests=2, prompt=32, gen=4, slots=2)"
    )
    assert ph["serve.run"]["requests"] == 2 and ph["serve.run"]["tokens"] == 8
    for part in ("prefill", "decode"):
        assert ph[f"serve.parity.{part}"]["rel"] <= ph[f"serve.parity.{part}"]["tol"]


def test_four_chip_phase_rehearsal_on_four_host_devices():
    flags = os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
    ph = _phases(
        'cs.four_chip_phase(jax, cs.CompileClock(jax), "pallas-interpret", reduced=True, '
        "requests=8, prompt=32, gen=8, slots=4)",
        XLA_FLAGS=flags,
    )
    fleet = ph["fleet.parity"]
    assert fleet["mismatched"] == [] and fleet["own_device"]
    assert fleet["served_by"] == [0, 1, 2, 3]
    assert [p["params"] for p in fleet["placement"]] == [[0], [1], [2], [3]]
