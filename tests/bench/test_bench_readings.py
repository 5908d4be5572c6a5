"""The numbers the OFL cell compares, on hand-made readings."""
import math

import numpy as np
import pytest

import benchpath  # noqa: F401
from benchlib import compare, ofl


def tree(scale, small=1.0):
    return {"a": np.full((4,), 1.0 * scale), "b": np.full((2, 2), 2.0 * scale), "c": np.full((3,), 1e-6 * small)}


def readings_of(server, gen, grad, gen_loss=(1.0, 2.0, 3.0), kd_loss=(4.0, 5.0, 6.0)):
    return {"gen_loss": list(gen_loss), "kd_loss": list(kd_loss), "first_grad": grad,
            "gen": gen, "gen_m": tree(1.0), "server": server}


def test_equal_readings_read_zero():
    zero = {k: np.zeros_like(v) for k, v in tree(1.0).items()}
    r = readings_of(tree(2.0), tree(3.0), tree(1.0))
    got = ofl.readings(r, r, zero, zero)
    assert set(got) == {"loss_gap", "loss_gap_epoch0", "first_grad_gap", "first_grad_median",
                        "gen_change_gap", "gen_change_median", "update_gap", "update_median"}
    assert all(v == 0.0 for v in got.values())


def test_losses_worst_epoch_and_epoch0():
    zero = {k: np.zeros_like(v) for k, v in tree(1.0).items()}
    ref = readings_of(tree(2.0), tree(3.0), tree(1.0))
    got = readings_of(tree(2.0), tree(3.0), tree(1.0), gen_loss=(1.01, 2.0, 3.3), kd_loss=(4.0, 5.1, 6.0))
    r = ofl.readings(got, ref, zero, zero)
    assert r["loss_gap"] == pytest.approx(0.1) and r["loss_gap_epoch0"] == pytest.approx(0.01)


def test_leaf_gaps_worst_median_and_the_rounding_rule():
    zero = {k: np.zeros_like(v) for k, v in tree(1.0).items()}
    ref = readings_of(tree(1.0), tree(1.0), tree(1.0))
    # leaf "a" moved 10% more, "b" as the reference, "c" a thousand times
    # more: but "c"'s reference gradient is under 1e-3 of the median leaf's,
    # so it moves by round-off alone and is left out
    server = {"a": np.full((4,), 1.1), "b": np.full((2, 2), 2.0), "c": np.full((3,), 1e-3)}
    r = ofl.readings(readings_of(server, tree(1.0), tree(1.0)), ref, zero, zero)
    # "a": norms 2.2 against 2.0, over the larger of 2.0 and the median leaf's 2.0
    assert r["update_gap"] == pytest.approx(0.1)
    assert r["update_median"] == pytest.approx(0.05)
    gaps = compare.leaf_gaps(server, tree(1.0), tree(1.0))
    assert set(gaps) == {"a", "b"}


def test_state_left_unchanged_reads_one_and_nan_never_passes():
    zero = {k: np.zeros_like(v) for k, v in tree(1.0).items()}
    ref = readings_of(tree(1.0), tree(1.0), tree(1.0))
    r = ofl.readings(readings_of(zero, zero, zero), ref, zero, zero)
    for k in ("first_grad_gap", "gen_change_gap", "update_gap", "update_median"):
        assert r[k] == pytest.approx(1.0)
    bad = dict(tree(1.0), a=np.full((4,), np.nan))
    r = ofl.readings(readings_of(bad, tree(1.0), tree(1.0)), ref, zero, zero)
    assert math.isinf(r["update_gap"])
    checks = ofl.readings_checks(readings_of(bad, tree(1.0), tree(1.0)), ref, zero, zero, {"update_gap": 0.5})
    assert list(checks) == ["update_gap"] and not checks["update_gap"]["value"] <= 0.5
