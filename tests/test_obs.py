"""Telemetry subsystem tests (repro.obs).

* registry: counter/gauge/histogram semantics, labels, snapshot/Prometheus
  export, the disabled no-op fast path;
* StatsView: the dict-shaped adapter the serving components mutate through —
  old ``stats["x"] += 1`` call sites must keep working verbatim, unknown
  keys must raise (drift guard);
* tracer: nested spans nest correctly, Chrome trace JSON round-trips
  ``json.loads`` with per-thread monotonic ``ts``, a disabled tracer records
  nothing and costs one shared no-op context; an enabled one enters a
  ``TraceAnnotation`` per span, and its ``ts`` is the profiler's wall clock;
* ``ofl.epoch.step_s``: execution time, one epoch in flight, and no wait
  when metrics are off;
* the serving hot path: enabling trace/metrics must not add host syncs to a
  decode chunk (the O(1)-syncs-per-chunk contract), and what a smoke run
  increments must match the namespace ``repro.obs.names`` declares;
* per-request timelines: ``Completion.first_token``/TTFT and the
  ``latency_summary`` percentiles;
* the artifact validator CI runs, and the REPRO_LOG_LEVEL logging knob.
"""
from __future__ import annotations

import json
import logging

import jax
import numpy as np
import pytest

from repro import obs
from repro.config import ModelConfig
from repro.models import init_lm
from repro.obs import (
    KV_GAUGES,
    REQUIRED_SERVE_KEYS,
    SERVE_ENGINE_METRICS,
    MetricsRegistry,
    SpanTracer,
    serve_namespace,
)
from repro.obs.tracer import _NULL_SPAN
from repro.obs.validate import validate_metrics, validate_trace
from repro.serve import (
    ContinuousScheduler,
    EngineConfig,
    ManualClock,
    Request,
    ServeEngine,
)
from repro.serve.metrics import latency_summary
from repro.serve.scheduler import Completion


def _mk(**kw):
    base = dict(
        name="t", family="dense", num_layers=2, d_model=32, num_heads=4,
        num_kv_heads=2, head_dim=8, d_ff=64, vocab_size=64, scan_layers=False,
        remat=False, dtype="float32", param_dtype="float32",
    )
    base.update(kw)
    return ModelConfig(**base)


@pytest.fixture
def global_obs_off():
    """Tests that flip the process-global telemetry restore the default."""
    yield
    obs.configure(metrics=False, trace=False)
    obs.tracer().clear()
    obs.registry().reset()


# ---------------------------------------------------------------------------
# metrics registry


def test_registry_counters_gauges_histograms():
    reg = MetricsRegistry()
    reg.inc("a.b", 2)
    reg.inc("a.b", 3)
    reg.inc("a.b", 1, replica=1)
    reg.set_gauge("g.x", 7.5, replica=0)
    for v in (1.0, 2.0, 3.0, 4.0):
        reg.observe("h.t", v)
    assert reg.value("a.b") == 5
    assert reg.value("a.b", replica=1) == 1
    assert reg.total("a.b") == 6
    assert reg.names("a.") == ["a.b"]
    recs = {(r["name"], tuple(sorted(r["labels"].items()))): r for r in reg.snapshot()}
    assert recs[("a.b", ())]["value"] == 5
    assert recs[("g.x", (("replica", "0"),))]["type"] == "gauge"
    h = recs[("h.t", ())]
    assert h["count"] == 4 and h["sum"] == 10.0 and h["min"] == 1.0 and h["max"] == 4.0
    assert h["p50"] == 2.5


def test_registry_label_order_is_canonical():
    reg = MetricsRegistry()
    reg.inc("x", 1, a=1, b=2)
    reg.inc("x", 1, b=2, a=1)  # same series regardless of kwarg order
    assert reg.value("x", a=1, b=2) == 2


def test_registry_disabled_is_noop():
    reg = MetricsRegistry(enabled=False)
    reg.inc("a.b")
    reg.set_gauge("g", 1)
    reg.observe("h", 1.0)
    assert reg.snapshot() == []
    assert reg.value("a.b") == 0


def test_registry_histogram_ring_bound():
    reg = MetricsRegistry(hist_capacity=8)
    for i in range(50):
        reg.observe("h", float(i))
    (rec,) = reg.snapshot()
    assert rec["count"] == 8
    assert rec["min"] == 42.0  # oldest samples dropped


def test_prometheus_export_shape():
    reg = MetricsRegistry()
    reg.inc("serve.admit.requests", 3, replica=0)
    reg.observe("serve.request.ttft_s", 0.5)
    text = reg.to_prometheus()
    assert '# TYPE serve_admit_requests counter' in text
    assert 'serve_admit_requests{replica="0"} 3' in text
    assert '# TYPE serve_request_ttft_s summary' in text
    assert 'serve_request_ttft_s{quantile="0.5"} 0.5' in text
    assert 'serve_request_ttft_s_count 1' in text


def test_registry_dump_writes_jsonl_and_prom(tmp_path):
    reg = MetricsRegistry()
    reg.inc("a.b", 4)
    out = tmp_path / "m.jsonl"
    reg.dump(str(out))
    [rec] = [json.loads(l) for l in out.read_text().splitlines()]
    assert rec == {"name": "a.b", "type": "counter", "labels": {}, "value": 4}
    assert (tmp_path / "m.prom").read_text().startswith("# TYPE a_b counter")


# ---------------------------------------------------------------------------
# StatsView


def test_stats_view_preserves_dict_semantics():
    reg = MetricsRegistry()
    st = reg.view({"hits": "c.hits", "misses": "c.misses"}, replica=3)
    st["hits"] += 1
    st["hits"] += 1
    st["misses"] = 5  # plain assignment (the spec_decode mirror idiom)
    assert st["hits"] == 2 and isinstance(st["hits"], int)
    assert dict(st) == {"hits": 2, "misses": 5}
    assert len(st) == 2 and "hits" in st and "other" not in st
    # mutations landed in the namespaced labelled series
    assert reg.value("c.hits", replica=3) == 2
    # reset-by-iteration, as ServeEngine.reset()/FleetRouter.run() do
    for k in list(st):
        st[k] = 0
    assert dict(st) == {"hits": 0, "misses": 0}


def test_stats_view_rejects_unknown_keys():
    st = MetricsRegistry().view({"hits": "c.hits"})
    with pytest.raises(KeyError):
        st["typo"] += 1
    with pytest.raises(KeyError):
        st["typo"] = 1
    with pytest.raises(TypeError):
        del st["hits"]


# ---------------------------------------------------------------------------
# span tracer


def test_nested_spans_nest_correctly():
    tr = SpanTracer()
    tr.enabled = True
    with tr.span("outer", kind="parent"):
        with tr.span("inner"):
            pass
        with tr.span("inner2"):
            pass
    evs = {e["name"]: e for e in tr.events()}
    assert set(evs) == {"outer", "inner", "inner2"}
    outer, inner = evs["outer"], evs["inner"]
    assert inner["args"]["parent"] == "outer"
    assert evs["inner2"]["args"]["parent"] == "outer"
    assert "parent" not in outer.get("args", {})
    # containment: the child interval sits inside the parent's
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    # export order: parent precedes the children it contains
    assert [e["name"] for e in tr.events()][0] == "outer"


def test_trace_json_roundtrip_and_monotonic_ts(tmp_path):
    tr = SpanTracer()
    tr.enabled = True
    for i in range(5):
        with tr.span("step", i=i):
            with tr.span("sub"):
                pass
    doc = json.loads(json.dumps(tr.to_chrome_trace()))
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert len(evs) == 10
    last = {}
    for ev in evs:
        assert ev["ph"] == "X" and "dur" in ev
        tid = ev["tid"]
        assert ev["ts"] >= last.get(tid, float("-inf"))
        last[tid] = ev["ts"]
    # and the dumped file passes the CI validator
    p = tmp_path / "trace.json"
    tr.dump(str(p))
    assert len(validate_trace(str(p))) == 10


def test_disabled_tracer_records_nothing():
    tr = SpanTracer()
    s1 = tr.span("a")
    s2 = tr.span("b", x=1)
    assert s1 is s2 is _NULL_SPAN  # shared no-op: no per-call allocation
    with s1:
        tr.instant("marker")
    assert len(tr) == 0 and tr.events() == []


def test_tracer_ring_is_bounded():
    tr = SpanTracer(capacity=16)
    tr.enabled = True
    for i in range(100):
        with tr.span("s", i=i):
            pass
    assert len(tr) == 16


# ---------------------------------------------------------------------------
# serving hot path: sync contract + namespace drift guard


def _run_tiny_engine(registry=None, gen=6):
    cfg = _mk()
    params = init_lm(cfg, jax.random.key(0))
    eng = ServeEngine(
        cfg, params,
        EngineConfig(max_slots=2, max_seq=32, max_new=8, decode_chunk=4,
                     kv_layout="paged", page_size=8),
        registry=registry,
    )
    prompts = [np.arange(6, dtype=np.int32) % cfg.vocab_size,
               (np.arange(7, dtype=np.int32) * 3) % cfg.vocab_size]
    sched = ContinuousScheduler(eng, clock=ManualClock(tick=0.01))
    comps = sched.run(
        [Request(rid=i, tokens=p, max_new_tokens=gen, arrival=0.0)
         for i, p in enumerate(prompts)]
    )
    return eng, sched, comps


def test_telemetry_adds_no_host_syncs(global_obs_off):
    """The O(1)-syncs-per-chunk contract holds with telemetry disabled AND
    enabled: spans bracket host actions, they never force a device sync."""
    eng_off, _, _ = _run_tiny_engine()
    assert len(obs.tracer()) == 0  # disabled tracer saw the whole run
    assert eng_off.stats["host_syncs"] == eng_off.stats["decode_chunks"]

    obs.configure(metrics=True, trace=True)
    eng_on, _, _ = _run_tiny_engine()
    assert eng_on.stats["host_syncs"] == eng_on.stats["decode_chunks"]
    assert eng_on.stats["host_syncs"] == eng_off.stats["host_syncs"]
    assert len(obs.tracer()) > 0  # enabled tracer actually recorded spans
    names = {e["name"] for e in obs.tracer().events()}
    assert {"serve.decode_chunk", "serve.prefill", "serve.admit"} <= names


def test_serve_namespace_matches_smoke_run():
    """Drift guard: everything a paged smoke run touches is declared in
    repro.obs.names, and the run increments at least the required floor."""
    reg = MetricsRegistry()
    eng, sched, comps = _run_tiny_engine(registry=reg)
    assert len(comps) == 2
    eng.publish_gauges()
    touched = set(reg.names("serve."))
    assert touched <= serve_namespace()
    assert set(REQUIRED_SERVE_KEYS) <= touched
    # pool gauges always publish; reclaimable_pages needs --prefix-cache
    assert {KV_GAUGES[k] for k in ("free_pages", "pages_in_use", "capacity_pages")} <= touched
    # the engine's stats keys are exactly the declared schema
    assert set(eng.stats) == set(SERVE_ENGINE_METRICS)
    # fleet aggregation: engine counters land with the replica label
    assert reg.value("serve.decode.chunks", replica=0) == eng.stats["decode_chunks"]


# ---------------------------------------------------------------------------
# per-request timelines (TTFT)


def test_completion_ttft_and_summary():
    c = Completion(rid=0, prompt_len=4, tokens=np.zeros(3, np.int32),
                   arrival=1.0, admitted=1.5, finished=3.0, first_token=1.75)
    assert c.ttft == pytest.approx(0.75)
    assert c.queue_wait == pytest.approx(0.5)
    legacy = Completion(rid=1, prompt_len=4, tokens=np.zeros(3, np.int32),
                        arrival=0.0, admitted=0.5, finished=2.0)
    assert legacy.ttft is None
    s = latency_summary([c, legacy], wall_s=2.0)
    assert s["ttft_p50_s"] == pytest.approx(0.75)  # None-TTFT rows excluded
    assert s["ttft_p95_s"] == pytest.approx(0.75)
    assert s["tokens"] == 6.0


def test_scheduler_stamps_first_token():
    reg = MetricsRegistry()
    _, sched, comps = _run_tiny_engine(registry=reg)
    for c in comps:
        assert c.first_token is not None
        # admitted is stamped before the prefill dispatch, first_token after
        assert c.arrival <= c.admitted < c.first_token <= c.finished
        assert c.ttft >= c.queue_wait
    for name in ("serve.request.latency_s", "serve.request.queue_wait_s",
                 "serve.request.ttft_s"):
        (rec,) = [r for r in reg.snapshot() if r["name"] == name]
        assert rec["count"] == len(comps)


# ---------------------------------------------------------------------------
# validator


def test_validate_trace_rejects_malformed(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"traceEvents": [
        {"name": "a", "ph": "X", "ts": 10.0, "dur": 1.0, "tid": 1},
        {"name": "b", "ph": "X", "ts": 5.0, "dur": 1.0, "tid": 1},
    ]}))
    with pytest.raises(ValueError, match="non-monotonic"):
        validate_trace(str(bad))
    bad.write_text(json.dumps({"traceEvents": [{"name": "a", "ph": "X", "ts": 1.0}]}))
    with pytest.raises(ValueError, match="missing dur"):
        validate_trace(str(bad))
    bad.write_text("not json")
    with pytest.raises(json.JSONDecodeError):
        validate_trace(str(bad))


def test_validate_metrics_requires_serve_keys(tmp_path):
    reg = MetricsRegistry()
    reg.inc("serve.admit.requests")
    p = tmp_path / "m.jsonl"
    reg.dump(str(p))
    with pytest.raises(ValueError, match="missing required keys"):
        validate_metrics(str(p))
    for name in REQUIRED_SERVE_KEYS:
        reg.inc(name)
    reg.dump(str(p))
    assert len(validate_metrics(str(p))) == len(REQUIRED_SERVE_KEYS)


# ---------------------------------------------------------------------------
# logging knob


def test_log_level_env_and_set_level(monkeypatch):
    from repro.utils.logging import _level_from_env, set_level

    monkeypatch.setenv("REPRO_LOG_LEVEL", "debug")
    assert _level_from_env() == logging.DEBUG
    monkeypatch.setenv("REPRO_LOG_LEVEL", "WARNING")
    assert _level_from_env() == logging.WARNING
    monkeypatch.setenv("REPRO_LOG_LEVEL", "15")
    assert _level_from_env() == 15
    monkeypatch.setenv("REPRO_LOG_LEVEL", "bogus")
    assert _level_from_env() == logging.INFO
    monkeypatch.delenv("REPRO_LOG_LEVEL")
    assert _level_from_env() == logging.INFO

    root = logging.getLogger("repro")
    before = root.level
    try:
        set_level("error")
        assert root.level == logging.ERROR
        set_level(logging.DEBUG)
        assert root.level == logging.DEBUG
        with pytest.raises(ValueError):
            set_level("nope")
    finally:
        root.setLevel(before)


# ---------------------------------------------------------------------------
# spans on the profiler's clock


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: records what enters
    and exits."""

    def __init__(self):
        self.log = []
        outer = self

        class Ann:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                outer.log.append(("enter", self.name))

            def __exit__(self, *exc):
                outer.log.append(("exit", self.name))

        self.cls = Ann


def test_spans_reach_trace_annotation_however_the_profile_started(monkeypatch):
    ann = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann.cls)
    tr = SpanTracer()
    with tr.span("off"):
        pass
    assert ann.log == []  # a disabled tracer stays the shared no-op
    tr.enabled = True
    with tr.span("outer"):
        with tr.span("inner"):
            pass
    assert ann.log == [("enter", "outer"), ("enter", "inner"), ("exit", "inner"), ("exit", "outer")]


def test_exported_ts_is_wall_clock_and_monotonic():
    import threading
    import time

    tr = SpanTracer()
    tr.enabled = True
    tr.clear()

    def work(n):
        for i in range(n):
            with tr.span("step", i=i):
                tr.instant("mark")

    threads = [threading.Thread(target=work, args=(20,)) for _ in range(3)]
    for t in threads:
        t.start()
    work(20)
    for t in threads:
        t.join()
    now_us = time.time_ns() / 1e3
    doc = tr.to_chrome_trace()
    assert doc["otherData"]["clock"] == "unix"
    assert abs(doc["otherData"]["origin_unix_ns"] / 1e3 - now_us) < 1e6
    evs = doc["traceEvents"]
    assert len(evs) == 160
    last = {}
    for ev in evs:
        assert abs(ev["ts"] - now_us) < 1e6, ev
        assert ev["ts"] >= last.get(ev["tid"], float("-inf"))
        last[ev["tid"]] = ev["ts"]


def test_span_start_matches_the_profiler_host_plane(tmp_path):
    """A span's start in the exported JSON and in a profile's host plane
    (started with ``jax.profiler.trace``, not through obs) agree within
    1 ms."""
    import glob
    import os
    import time

    tr = SpanTracer()
    tr.enabled = True
    tr.clear()
    with jax.profiler.trace(str(tmp_path)):
        for i in range(3):
            with tr.span("ofl.epoch", epoch=i):
                time.sleep(0.002)
    path = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))[0]
    pd = jax.profiler.ProfileData.from_file(path)
    origin = dict(pd.find_plane_with_name("Task Environment").stats)["profile_start_time"]
    host = sorted(origin + ev.start_ns for plane in pd.planes if plane.name.startswith("/host:")
                  for line in plane.lines for ev in line.events if ev.name == "ofl.epoch")
    ours = sorted(ev["ts"] * 1e3 for ev in tr.events() if ev["name"] == "ofl.epoch")
    assert len(host) == len(ours) == 3
    assert max(abs(a - b) for a, b in zip(host, ours)) < 1e6


# ---------------------------------------------------------------------------
# ofl.epoch.step_s: execution time, one epoch in flight


def _tiny_coboosting(epochs, eval_every):
    from functools import partial

    from repro.config.train import OFLConfig
    from repro.core import default_image_setup, run_coboosting
    from repro.models.cnn import cnn_apply, init_cnn

    classes, shape = 4, (8, 8, 3)
    cfg = OFLConfig(num_clients=2, epochs=epochs, gen_iters=2, batch_size=4, latent_dim=6, buffer_batches=2)
    apply = partial(cnn_apply, "mlp")
    clients = [init_cnn(jax.random.key(i), "mlp", classes, shape) for i in range(2)]
    server = init_cnn(jax.random.key(9), "mlp", classes, shape)
    gen_apply, gen = default_image_setup(jax.random.key(5), cfg, classes, shape)
    eval_fn = (lambda sp, w: {"acc": 0.0}) if eval_every else None
    return run_coboosting([apply] * 2, clients, apply, server, gen_apply, gen, cfg, classes,
                          jax.random.key(0), eval_fn=eval_fn, eval_every=eval_every or 1)


@pytest.mark.parametrize("eval_every", [None, 1, 2], ids=["no-eval", "eval-each", "eval-every-2"])
def test_epoch_step_s_times_execution(global_obs_off, tmp_path, eval_every):
    from repro.obs.validate import main as validate_main

    obs.configure(metrics=True, trace=True)
    obs.registry().reset()
    _tiny_coboosting(3, eval_every)
    snap = {(r["name"], tuple(sorted(r["labels"].items()))): r for r in obs.registry().snapshot()}
    h = snap[("ofl.epoch.step_s", (("driver", "fused"),))]
    assert h["type"] == "histogram" and h["count"] == 3 and h["min"] > 0
    assert not obs.registry().names("ofl.gen.step_s") + obs.registry().names("ofl.ee.step_s") \
        + obs.registry().names("ofl.kd.step_s")
    metrics, trace = tmp_path / "m.jsonl", tmp_path / "t.json"
    obs.registry().dump(str(metrics))
    obs.tracer().dump(str(trace))
    assert validate_main(["--train", "--metrics", str(metrics), "--trace", str(trace)]) == 0


def test_epoch_step_s_blocks_nothing_when_metrics_are_off(global_obs_off, monkeypatch):
    from repro.core import coboosting

    waited = []
    monkeypatch.setattr(coboosting, "_completed", lambda result, since: waited.append(result) or since)
    obs.configure(metrics=False, trace=True)
    _tiny_coboosting(3, None)
    assert waited == []
