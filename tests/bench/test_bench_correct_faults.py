"""``correct`` at a size a CPU test run can hold: a sound run passes, and
the timed path broken underneath, or the control put in its place, fails.

Each test drives the whole of ``bench/run.py``'s run except its look for a
chip: tiny shapes of the cell's configuration, its own limits, the ``ref``
kernel backend (the Pallas kernels need the chip)."""
import pytest

import benchpath  # noqa: F401
import run as bench_run
from benchlib import common, ofl

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def tiny(kind, name, orig=common.load_json):
    d = orig(kind, name)
    if kind == "configs" and name == "ofl-cifar10-cnn5":
        d.update(image=[8, 8, 3], batch_size=8, gen_iters=3, clients=3, backend="ref")
        d["cnn5"] = {"conv_channels": [4, 8], "conv_kernel": 5, "fc_widths": [16, 8]}
        d["generator"] = {"kind": "dcgan", "latent_dim": 6, "base": 4}
    return d


@pytest.fixture
def small(monkeypatch):
    monkeypatch.setattr(common, "load_json", tiny)
    # the checkout's compile cache is the chip runs' own: a CPU test run
    # neither writes to it nor changes the process's cache settings
    monkeypatch.setattr(common, "enable_cache", lambda jax: None)


def run_cell(cell, seed=2**31 + 99, seconds=2.0):
    args = bench_run.parse(["--workload", cell, "--seed", str(seed), "--seconds", str(seconds), "--trace", "0"])
    return bench_run.run(args, cpu_peaks=PEAKS)


def failed_checks(res):
    return [k for k, c in res["checks"].items() if not c["value"] <= c["limit"]]


OFL = "ofl.cifar10-cnn5-k10"


def test_ofl_sound_run_is_correct(small):
    res = run_cell(OFL)
    assert res["correct"], res["checks"]
    assert res["metrics"]["ofl_epoch_ms"]["value"] > 0 and res["attempted"] > 0


def test_ofl_same_seed_same_weights_and_readings(small):
    ref = common.reference_of("ofl-cifar10-cnn5")
    cfg = tiny("configs", "ofl-cifar10-cnn5")
    import jax

    seed = 2**33 + 17
    a = jax.tree_util.tree_leaves(ref.make_weights(common.seed_key(jax, seed), cfg))
    b = jax.tree_util.tree_leaves(ref.make_weights(common.seed_key(jax, seed), cfg))
    c = jax.tree_util.tree_leaves(ref.make_weights(common.seed_key(jax, seed + 1), cfg))
    assert all((x == y).all() for x, y in zip(a, b)) and not all((x == z).all() for x, z in zip(a, c))
    first, second = run_cell(OFL, seed=seed), run_cell(OFL, seed=seed)
    assert {k: c["value"] for k, c in first["checks"].items()} == {k: c["value"] for k, c in second["checks"].items()}


def test_ofl_state_left_unchanged_fails(small, monkeypatch):
    import jax
    import jax.numpy as jnp

    real = ofl.Program.step

    def frozen(self, key=None):
        kept = jax.tree_util.tree_map(jnp.copy, self.state[:6])
        out = real(self, key)
        self.state[:6] = kept
        return out

    monkeypatch.setattr(ofl.Program, "step", frozen)
    res = run_cell(OFL)
    assert not res["correct"]
    failed = set(failed_checks(res))
    assert "first_grad_gap" in failed and any(k.startswith("update") for k in failed), failed


def test_ofl_half_batch_fails(small, monkeypatch):
    from repro.core import epoch

    real = epoch.make_kd_loss

    def half(*a, **kw):
        fn = real(*a, **kw)
        return lambda sp, x, cp, w: fn(sp, x[: x.shape[0] // 2], cp, w)

    monkeypatch.setattr(epoch, "make_kd_loss", half)
    res = run_cell(OFL)
    assert not res["correct"], res["checks"]


def test_ofl_control_fails(small, monkeypatch):
    """The reference with float8 products, one step below the program's
    one-pass bfloat16 products, put in the program's place."""
    import jax.numpy as jnp

    def control(self, key, n):
        ref = common.reference_of(self.cfg["name"])
        self.state[6] = key  # the window runs the program on from here
        return ref.run(self._weights, key, self.cfg, n, products="fp8")

    real_start = ofl.Program.start

    def start(self, weights):
        import jax

        self._weights = jax.tree_util.tree_map(jnp.copy, weights)
        real_start(self, weights)

    monkeypatch.setattr(ofl.Program, "start", start)
    monkeypatch.setattr(ofl.Program, "checked_epochs", control)
    res = run_cell(OFL)
    assert not res["correct"], res["checks"]
