"""Where the persistent compilation cache goes (``repro.utils.compile_cache``).

``JAX_COMPILATION_CACHE_DIR``, when set, belongs to whoever runs the program:
JAX reads it and the helper sets nothing. Otherwise the cache is the fixed
``<checkout>/.jax_cache``, so a second run of the same command hits it.
"""
from __future__ import annotations

import os
import subprocess
import sys
import uuid
from pathlib import Path

import jax
import pytest

from repro.utils.compile_cache import CHECKOUT_CACHE_DIR, enable_compile_cache

ROOT = Path(__file__).resolve().parents[1]

# one small program compiled in a fresh process; prints the cache hits. The
# constant in argv[1] is baked into the program, so each test gets its own
# cache key and its first run cannot hit an entry an earlier run left.
PROBE = """
import sys
import numpy as np
import jax, jax.numpy as jnp
from repro.utils.compile_cache import enable_compile_cache
hits = []
jax.monitoring.register_event_listener(
    lambda name, **_: hits.append(name) if name == "/jax/compilation_cache/cache_hits" else None)
enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
c = float(sys.argv[1])
jax.block_until_ready(jax.jit(lambda x: jnp.sin(x) @ x.T + c)(np.ones((8, 8), np.float32)))
print(len(hits))
"""


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_in_the_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert CHECKOUT_CACHE_DIR == ROOT / ".jax_cache"
    assert enable_compile_cache() == str(CHECKOUT_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE_DIR)


def _listing(d: Path):
    return sorted(os.listdir(d)) if d.is_dir() else []


@pytest.mark.parametrize("where", ["env", "checkout"])
def test_second_run_hits_the_cache_and_nothing_else(tmp_path, where):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    cache = CHECKOUT_CACHE_DIR
    if where == "env":
        cache = tmp_path / "cache"
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    const = str(uuid.uuid4().int % 10**9 / 7.0)
    checkout_before = _listing(CHECKOUT_CACHE_DIR)

    def run() -> int:
        out = subprocess.run(
            [sys.executable, "-c", PROBE, const], cwd=tmp_path, env=env,
            capture_output=True, text=True, timeout=300,
        )
        assert out.returncode == 0, out.stderr[-4000:]
        return int(out.stdout.split()[-1])

    assert run() == 0
    written = _listing(cache)
    assert written
    assert run() >= 1
    if where == "env":
        assert _listing(CHECKOUT_CACHE_DIR) == checkout_before
    else:
        assert set(written) - set(checkout_before)


# the OFL evaluator on two cnn5 clients at CIFAR-10 shape (2.2M params each)
EVAL_PROBE = """
from functools import partial
import numpy as np
import jax, jax.numpy as jnp
from repro.fed.market import market_eval_fn
from repro.models.cnn import cnn_apply, init_cnn
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
shape = (32, 32, 3)
params = [init_cnn(jax.random.key(k), "cnn5", 10, shape) for k in range(2)]
apply = partial(cnn_apply, "cnn5")
x, y = np.zeros((8,) + shape, np.float32), np.zeros(8, np.int32)
market_eval_fn([apply] * 2, params, apply, x, y, batch_size=8)(params[0], jnp.full((2,), 0.5))
print(sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(params[0])))
"""


def test_eval_program_takes_client_weights_as_arguments(tmp_path):
    """Client weights baked into a compiled program as constants make every
    cache entry as large as the market (one per batch shape) and evict
    everything else from a size-capped cache."""
    cache = tmp_path / "cache"
    env = dict(
        os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"),
        JAX_COMPILATION_CACHE_DIR=str(cache),
    )
    out = subprocess.run(
        [sys.executable, "-c", EVAL_PROBE], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    client_bytes = int(out.stdout.split()[-1])
    entries = {p.name: p.stat().st_size for p in cache.iterdir() if "batch_preds" in p.name}
    assert entries
    assert max(entries.values()) < client_bytes / 10, entries
