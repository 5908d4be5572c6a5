"""bench/run.py refuses to run without a TPU or without the program."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import benchpath  # noqa: F401
from benchlib import common

ROOT = benchpath.BENCH.parent
ARGS = ["--workload", "ofl.cifar10-cnn5-k10", "--seed", str(2**31 + 7), "--seconds", "1", "--trace", "0"]


def _run(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(extra_env or {})
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    r = _run(ROOT)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert r.stdout.strip() == ""


def test_refuses_with_only_the_benchmark(tmp_path):
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in man["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p, ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0
    assert "program under test is missing" in r.stderr
    assert r.stdout.strip() == ""


def test_unknown_cell_and_device_kind_are_errors():
    with pytest.raises(common.Refused):
        common.load_json("workloads", "no-such-cell")
    with pytest.raises(common.Refused):
        common.peaks_for("TPU v0 imaginary")


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 5, 2**40 + 3])
def test_seed_keys_take_large_seeds(seed):
    import jax

    a = jax.random.key_data(common.seed_key(jax, seed))
    b = jax.random.key_data(common.seed_key(jax, seed))
    assert (a == b).all()
    if seed:
        assert not (a == jax.random.key_data(common.seed_key(jax, seed - 1))).all()
