"""The reduction from a flattened profiler trace to busy time, idle gaps,
scope and kernel sums: on hand-made intervals, and on slices of traces
recorded on a TPU v5e (committed under data/)."""
import gzip
import json
from pathlib import Path

import pytest

import benchpath  # noqa: F401
from benchlib import trace as tr

DATA = Path(__file__).resolve().parent / "data"
RECORDED = sorted(DATA.glob("*_slice.json.gz"))


NAMES = [
    "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
    "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p), kind=kOutput",
    '%jvp_jit_ensemble_kl_.3 = f32[8,1]{1,0} custom-call(f32[8]{0} %p), custom_call_target="tpu_custom_call"',
    "%fusion.4 = f32[8]{0} fusion(f32[8]{0} %q), kind=kOutput",
    "%copy.5 = f32[8]{0} copy(f32[8]{0} %q)",
    "%while.6 = (f32[8]{0}) while((f32[8]{0}) %t), condition=%cond, body=%body",
]
HLO = """
  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%c1, metadata={op_name="jit(epoch_step)/ofl.gen.boost/while/body/conv" source_file="x.py"}
  %fusion.2 = f32[8]{0} fusion(f32[8]{0} %p), kind=kOutput, metadata={op_name="jit(epoch_step)/ofl.gen.boost/while/body/dot"}
  %jvp_jit_ensemble_kl_.3 = f32[8,1]{1,0} custom-call(f32[8]{0} %p), metadata={op_name="jit(epoch_step)/ofl.kd/pallas_call"}
  ROOT %fusion.4 = f32[8]{0} fusion(f32[8]{0} %q), kind=kOutput, metadata={op_name="jit(epoch_step)/ofl.kd/scan/body/dot"}
  %copy.5 = f32[8]{0} copy(f32[8]{0} %q), metadata={op_name="jit(epoch_step)/copy"}
"""


def hand_trace():
    ops = [[0, 0, 10], [1, 5, 10], [2, 30, 5], [3, 50, 20], [4, 90, 10], [5, 0, 15]]
    modules = [["jit_epoch_step(1)", 0, 40], ["jit_epoch_step(1)", 45, 30], ["jit_other", 80, 25]]
    host = [["bench.window", 0, 100, "python"], ["bench.dispatch", 35, 20, "python"], ["bench.wait", 70, 25, "python"]]
    dev = {"names": list(NAMES), "scopes": [""] * len(NAMES), "ops": ops, "modules": modules}
    return {"window_ns": [0, 100], "devices": {"0": dev}, "host": host}


def test_scopes_from_the_compiled_hlo():
    scopes = tr.hlo_scopes(HLO)
    assert scopes["fusion.1"] == "jit(epoch_step)/ofl.gen.boost/while/body/conv"
    assert scopes["fusion.4"] == "jit(epoch_step)/ofl.kd/scan/body/dot"
    assert tr.instruction(NAMES[2]) == "jvp_jit_ensemble_kl_.3"
    t = hand_trace()
    tr.attach_scopes(t, scopes, "epoch_step")  # the op at 90 runs outside it
    assert t["devices"]["0"]["scopes"][:4] == [scopes[tr.instruction(n)] for n in NAMES[:4]]
    assert t["devices"]["0"]["scopes"][4] == ""
    assert tr.is_container(t["devices"]["0"])(5) and not tr.is_container(t["devices"]["0"])(0)


def test_union_and_gaps_by_hand():
    assert tr.union([(5, 15), (0, 10), (30, 35), (35, 40)]) == [(0, 15), (30, 40)]
    assert tr.busy_ns([(5, 15), (0, 10), (30, 35)]) == 20
    assert tr.gaps([(5, 15), (0, 10), (30, 35)], 0, 50) == [(15, 30), (35, 50)]
    assert tr.gaps([(-5, 3)], 0, 10) == [(3, 10)]
    assert tr.clip([(-5, 3), (8, 20), (30, 40)], 0, 10) == [(0, 3), (8, 10)]


def test_busy_idle_and_sums_by_hand():
    t = hand_trace()
    assert tr.window_s(t) == pytest.approx(100e-9)
    # busy: [0,15] + [30,35] + [50,70] + [90,100] = 15 + 5 + 20 + 10 = 50 ns
    assert tr.busy_s(t) == pytest.approx(50e-9)
    assert tr.idle_share(t) == pytest.approx(0.5)
    tr.attach_scopes(t, tr.hlo_scopes(HLO), "epoch_step")
    dev = tr.devices(t)[0]
    assert tr.ops_time(dev, tr.in_scope(dev, "ofl.gen.boost"), 0, 100) == pytest.approx(15e-9)
    assert tr.ops_time(dev, tr.in_scope(dev, "ofl.kd"), 0, 100) == pytest.approx(25e-9)
    assert tr.ops_time(dev, tr.in_scope(dev, "ofl.k"), 0, 100) == 0.0
    assert tr.ops_time(dev, tr.is_pallas(dev), 0, 100) == pytest.approx(5e-9)
    assert list(tr.in_modules(dev, "epoch_step")(tr.ops_array(dev))) == [True, True, True, True, False, True]


def test_breakdown_by_hand():
    t = hand_trace()
    tr.attach_scopes(t, tr.hlo_scopes(HLO), "epoch_step")
    b = tr.breakdown(t)
    names = [n for n, _ in b["device_ops"]]
    assert names[0] == "ofl.kd dot" and len(names) == 5
    assert "copy" in names  # an op with no scope is grouped by its kind
    assert sum(s for _, s in b["device_ops"]) == pytest.approx(55e-9)
    assert b["idle_gaps"][0] == ["bench.wait", pytest.approx(20e-9)]
    assert sorted(name for name, _ in b["idle_gaps"][1:]) == ["bench.dispatch", "no host span"]
    assert len(b["idle_gaps"]) == 3


@pytest.mark.parametrize("scope, name, label", [
    ("jit(epoch_step)/ofl.gen.boost/while/body/closed_call/transpose(jvp(ofl.bank.g0))/conv_general_dilated",
     "%convolution.12 = f32[8]{0} convolution(f32[8]{0} %p)", "ofl.bank.g0 transpose(jvp) convolution / ofl.gen.boost"),
    ("jit(epoch_step)/ofl.gen.boost/while/body/closed_call/ofl.bank/jvp()/ofl.bank.g0/reduce_window_max",
     "%fusion.7 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", "ofl.bank.g0 jvp reduce_window_max / ofl.gen.boost/ofl.bank"),
    ("jit(epoch_step)/ofl.kd/while/body/closed_call/jvp(jit(ghm_ce))/pallas_call",
     '%ghm_ce_fwd.3 = f32[8]{0} custom-call(f32[8]{0} %p), custom_call_target="tpu_custom_call"', "ofl.kd jvp ghm_ce_fwd"),
    ("jit(_chunk_fn)/while/body/dot_general", "%fusion.40 = f32[8]{0} fusion(f32[8]{0} %p), kind=kOutput",
     "jit(_chunk_fn) dot_general"),
    ("", "%copy-start.2 = f32[8]{0} copy-start(f32[8]{0} %p)", "copy-start"),
])
def test_op_label_leads_with_the_innermost_scope_and_the_kind(scope, name, label):
    dev = {"names": [name], "scopes": [scope]}
    assert tr.op_label(dev, 0) == label


@pytest.mark.parametrize("path", RECORDED, ids=[p.name for p in RECORDED])
def test_recorded_slice(path):
    with gzip.open(path, "rt") as f:
        t = json.load(f)
    assert t["devices"], "the recorded slice holds a TPU plane"
    busy, window = tr.busy_s(t), tr.window_s(t)
    assert 0 < busy <= window * (1 + 1e-9)
    assert 0.0 <= tr.idle_share(t) < 1.0
    dev = tr.devices(t)[0]
    t0, t1 = tr.window(t)
    assert tr.ops_time(dev, tr.all_ops(dev), t0, t1) == pytest.approx(busy)
    b = tr.breakdown(t)
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert sum(s for _, s in b["idle_gaps"]) <= window - busy + 1e-12
    gen = tr.ops_time(dev, tr.in_scope(dev, "ofl.gen.boost"), t0, t1)
    kd = tr.ops_time(dev, tr.in_scope(dev, "ofl.kd"), t0, t1)
    assert gen > 0 and gen + kd <= busy * (1 + 1e-9)
    assert tr.ops_time(dev, tr.is_pallas(dev), t0, t1) > 0
