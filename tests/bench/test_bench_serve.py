"""The serve cell on the CPU: its traffic, the reference against the
program's model, a whole run through ``bench/run.py`` at a reduced size,
the timed path broken underneath, and the readers' cells.

Runs use the ``ref`` kernel backend (the Pallas kernels need the chip) and a
reduced smollm: 2 of its layers at the published widths, a 2048-token
vocabulary, 4 slots of 128 positions. The widths keep the logits' spread,
so the cell's own limit holds here too."""
import math

import numpy as np
import pytest

import benchpath  # noqa: F401
import run as bench_run
from benchlib import common, serve, traffic

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "serve.smollm-135m.chat"
TINY_MODEL = dict(num_hidden_layers=2, vocab_size=2048, backend="ref")
TINY_ENGINE = dict(max_slots=4, max_seq=128, max_new=32, page_size=16, prefill_bucket=32, decode_chunk=4)
TINY_MIX = dict(rate=4.0, prompt={"median": 40, "sigma": 0.5, "min": 8, "max": 96},
                output={"median": 12, "sigma": 0.5, "min": 4, "max": 32})


def tiny(kind, name, orig=common.load_json):
    d = orig(kind, name)
    if kind == "configs" and name == "serve-smollm-135m":
        d.update(TINY_MODEL)
        d["engine"] = dict(d["engine"], **TINY_ENGINE)
    if kind == "traffic" and name == "chat":
        d.update(TINY_MIX)
    if kind == "workloads" and name == CELL:
        d["tail_s"] = 5
    return d


@pytest.fixture
def small(monkeypatch):
    monkeypatch.setattr(common, "load_json", tiny)
    # the checkout's compile cache is the chip runs' own
    monkeypatch.setattr(common, "enable_cache", lambda jax: None)


def run_cell(seed=2**31 + 99, seconds=2.0, trace=0):
    args = bench_run.parse(["--workload", CELL, "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)])
    return bench_run.run(args, cpu_peaks=PEAKS)


# -- traffic -------------------------------------------------------------------


def test_traffic_is_the_seed_s_and_keeps_its_work_across_seeds():
    mix = common.load_json("traffic", "chat")
    a = traffic.requests(mix, 2**31 + 5, 30.0, 49152)
    b = traffic.requests(mix, 2**31 + 5, 30.0, 49152)
    c = traffic.requests(mix, 2**33 + 1, 30.0, 49152)
    assert [(t, n) for t, _, n in a] == [(t, n) for t, _, n in b]
    assert all((x[1] == y[1]).all() for x, y in zip(a, b))
    # another seed: the same arrivals and the same lengths, paired otherwise
    assert [t for t, _, _ in a] == [t for t, _, _ in c]
    pairs = lambda r: sorted((len(p), n) for _, p, n in r)  # noqa: E731
    assert pairs(a) == pairs(c) and [len(p) for _, p, _ in a] != [len(p) for _, p, _ in c]
    assert len(a) == pytest.approx(30.0 * mix["rate"], rel=0.5)


def test_lengths_match_the_medians_and_truncation_redraws():
    mix = common.load_json("traffic", "chat")
    rng = np.random.Generator(np.random.PCG64(7))
    n = 40000
    for spec in (mix["prompt"], mix["output"]):
        y = traffic.lengths(rng, spec, n)
        assert y.min() >= spec["min"] and y.max() <= spec["max"]
        # the lengths drawn, truncation and all, have the stated median
        assert abs(np.median(y) / spec["median"] - 1) < 0.02
        # redrawn, not clipped: the top length is no likelier than its neighbour
        assert np.sum(y == spec["max"]) < 3 * np.sum(y == spec["max"] - 1) + 10
        # the conditioned distribution: the in-range draws of the whole lognormal
        x = np.rint(rng.lognormal(traffic.location(spec), spec["sigma"], size=n))
        inside = x[(x >= spec["min"]) & (x <= spec["max"])]
        assert abs(np.median(y) / np.median(inside) - 1) < 0.03


@pytest.mark.parametrize("spec", [{"median": 1020, "sigma": 0.8, "min": 32, "max": 1792},
                                  {"median": 129, "sigma": 0.8, "min": 8, "max": 256},
                                  {"median": 40, "sigma": 0.5, "min": 8, "max": 96}])
def test_location_puts_the_truncated_median_on_the_stated_one(spec):
    from statistics import NormalDist

    mu, sigma = traffic.location(spec), spec["sigma"]
    cdf = lambda x: NormalDist(mu, sigma).cdf(math.log(x))  # noqa: E731
    a, b = cdf(spec["min"] - 0.5), cdf(spec["max"] + 0.5)
    assert (cdf(spec["median"]) - a) / (b - a) == pytest.approx(0.5, abs=1e-9)


def test_offered_lengths_have_the_source_medians():
    """The lengths a window offers, over a long offer: the medians the
    traffic file cites (Splitwise's Azure conversation trace)."""
    mix = common.load_json("traffic", "chat")
    reqs = traffic.requests(mix, 2**31 + 21, 3000.0, 49152)
    assert len(reqs) > 5000
    prompts = np.array([len(p) for _, p, _ in reqs])
    outputs = np.array([n for _, _, n in reqs])
    assert abs(np.median(prompts) / mix["prompt"]["median"] - 1) < 0.03
    assert abs(np.median(outputs) / mix["output"]["median"] - 1) < 0.03
    assert prompts.max() <= mix["prompt"]["max"] and outputs.max() <= mix["output"]["max"]


# -- the reference against the program's model --------------------------------


def test_reference_agrees_with_prefill_and_paged_decode_logits():
    import jax
    import jax.numpy as jnp

    from repro.models import init_lm_state, lm_decode, lm_prefill

    cfg = tiny("configs", "serve-smollm-135m")
    cfg["dtype"] = "float32"
    ref = common.reference_of("serve-smollm-135m")
    mcfg = serve.model_config(cfg)
    params = ref.make_weights(common.seed_key(jax, 2**31 + 3), cfg)
    rng = np.random.Generator(np.random.PCG64(3))
    s, ps, max_seq = 37, 16, 64
    toks = rng.integers(0, cfg["vocab_size"], s).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        logits, st = lm_prefill(params, mcfg, {"tokens": jnp.asarray(toks[None])}, init_lm_state(mcfg, 1, max_seq))
        nxt = int(jnp.argmax(logits[0, -1]))
        pages = {k: {"k_pages": v["k"].reshape(v["k"].shape[0], -1, ps, *v["k"].shape[3:]),
                     "v_pages": v["v"].reshape(v["v"].shape[0], -1, ps, *v["v"].shape[3:])} for k, v in st.items()}
        table = jnp.arange(max_seq // ps, dtype=jnp.int32)[None]
        dec, _ = lm_decode(params, mcfg, jnp.asarray([[nxt]], jnp.int32), pages, jnp.asarray([s], jnp.int32), table)
    seq = np.concatenate([toks, [nxt]]).astype(np.int32)
    want = np.asarray(ref.logits(params, ref.hidden(params, jnp.asarray(seq), cfg)[s - 1:s + 1]))
    scale = np.abs(want).max()
    assert np.abs(np.asarray(logits[0, -1]) - want[0]).max() < 1e-4 * scale
    assert np.abs(np.asarray(dec[0, -1]) - want[1]).max() < 1e-4 * scale


def test_reference_weights_fit_the_program_s_tree():
    import jax

    from repro.models import init_lm

    cfg = tiny("configs", "serve-smollm-135m")
    ref = common.reference_of("serve-smollm-135m")
    ours = ref.make_weights(common.seed_key(jax, 5), cfg)
    theirs = init_lm(serve.model_config(cfg), jax.random.key(0))
    shape = lambda t: jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)), t)  # noqa: E731
    assert jax.tree_util.tree_structure(ours) == jax.tree_util.tree_structure(theirs)
    assert shape(ours) == shape(theirs)


def _llama_mapping(cfg):
    """The ModelConfig the serve driver built before configurations could
    state program fields: the registry entry, then the Llama keys."""
    from repro.config import get_arch
    from repro.kernels import BackendPolicy

    return get_arch(cfg["arch"]).replace(
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"], num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"], d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], tie_embeddings=cfg["tie_word_embeddings"],
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"], act=cfg["hidden_act"],
        dtype=cfg["dtype"], param_dtype=cfg["param_dtype"],
        backend=BackendPolicy(default=cfg["backend"]),
    )


def test_smollm_builds_the_same_model_config_as_before():
    import dataclasses

    cfg = common.load_json("configs", "serve-smollm-135m")
    got, want = serve.model_config(cfg), _llama_mapping(cfg)
    for f in dataclasses.fields(got):
        assert getattr(got, f.name) == getattr(want, f.name), f.name
    assert got == want and got.head_dim == 64


def test_program_object_sets_any_model_config_field_and_refuses_other_names():
    import dataclasses

    cfg = dict(common.load_json("configs", "serve-smollm-135m"), head_dim=80,
               program={"qk_norm": True, "num_experts": 8, "experts_per_token": 2, "sliding_window": 512})
    m = serve.model_config(cfg)
    assert m.head_dim == 80 and m.qk_norm and (m.num_experts, m.experts_per_token, m.sliding_window) == (8, 2, 512)
    base = serve.model_config(common.load_json("configs", "serve-smollm-135m"))
    for f in dataclasses.fields(base):
        value = getattr(base, f.name)
        stated = dict(cfg, program={f.name: value})
        assert getattr(serve.model_config(stated), f.name) == value, f.name
    with pytest.raises(ValueError, match="not_a_field"):
        serve.model_config(dict(cfg, program={"num_layers": 2, "not_a_field": 1}))


# -- whole runs ----------------------------------------------------------------


def test_sound_run_is_correct_and_prints_every_check(small):
    res = run_cell()
    assert res["correct"] and res["failed"] == 0, res["checks"]
    assert set(res["checks"]) == set(common.load_json("workloads", CELL)["checks"])
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())
    assert res["metrics"]["serve_norm_latency_ms"]["value"] > 0 and res["metrics"]["setup_s"]["value"] > 0
    assert 0 < res["metrics"]["serve_tpot_ms"]["value"] <= res["metrics"]["serve_norm_latency_ms"]["value"]
    assert res["attempted"] > 0 and list(res)[-1] == "checks"


def test_nothing_compiles_in_the_window(small, monkeypatch):
    """Set-up warms every program the window reaches: the run's compile
    clock counts no compile between the window's start and its close. The
    offer outruns the 4 slots, so requests queue and are admitted in bursts
    of several, as they are at the cell's rate on the chip."""
    import jax

    def crowded(kind, name):
        d = tiny(kind, name)
        if kind == "traffic":
            d["rate"] = 30.0
        if kind == "workloads":
            d["tail_s"] = 60
        return d

    monkeypatch.setattr(common, "load_json", crowded)
    notes = {}
    real = serve.run_cell

    def keep(ctx):
        out = real(ctx)
        notes.update(out["notes"])
        return out

    monkeypatch.setattr(serve, "run_cell", keep)
    jax.clear_caches()
    res = run_cell(seed=2**31 + 131, seconds=3.0)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 40, res["checks"]
    assert notes["compiles_in_window"] == 0, notes


def test_traced_run_on_the_cpu_reads_the_scheduler(small):
    res = run_cell(trace=1)
    assert res["correct"]
    assert res["metrics"]["serve_queue_wait_p95_ms"]["value"] >= 0
    assert not any(k.startswith("ofl_") for k in res["metrics"])


def test_token_altered_where_it_is_produced_fails(small, monkeypatch):
    from repro.serve import engine

    real = engine.sample_tokens

    def off_by_one(logits, key, temperature):
        return (real(logits, key, temperature) + 1) % logits.shape[-1]

    monkeypatch.setattr(engine, "sample_tokens", off_by_one)
    res = run_cell()
    assert not res["correct"]
    assert all(not c["value"] <= c["limit"] for c in res["checks"].values()), res["checks"]


def test_decode_through_the_wrong_pages_fails(small, monkeypatch):
    import jax.numpy as jnp

    from repro.serve.engine import DecodeWorker

    real = DecodeWorker._chunk_fn

    def wrong_pages(self, params, ds):
        out = real(self, params, ds._replace(page_table=jnp.roll(ds.page_table, 1, axis=0)))
        return out._replace(page_table=ds.page_table)

    monkeypatch.setattr(DecodeWorker, "_chunk_fn", wrong_pages)
    res = run_cell()
    assert not res["correct"]
    assert not res["checks"]["served_token_gap"]["value"] <= res["checks"]["served_token_gap"]["limit"]


def test_decode_step_that_returns_its_state_unchanged_fails(small, monkeypatch):
    from repro.serve.engine import DecodeWorker

    monkeypatch.setattr(DecodeWorker, "_chunk_fn", lambda self, params, ds: ds)
    res = run_cell(seconds=1.0)
    assert not res["correct"] and res["failed"] > 0


def test_control_in_the_program_s_place_fails(small):
    """The control: the reference with float8 products, one precision below
    the program's bfloat16, put in the program's place: at each position of
    a sound run's sampled requests, the token it puts first, read against
    the f32 reference. It fails the cell's limit; the program passes it."""
    import jax

    cfg = tiny("configs", "serve-smollm-135m")
    mix = tiny("traffic", "chat")
    work = tiny("workloads", CELL)
    ref = common.reference_of("serve-smollm-135m")
    prog = serve.Program(jax, cfg, 1)
    weights = ref.make_weights(common.seed_key(jax, 2**31 + 77), cfg)
    prog.start(weights)
    reqs = serve.window_requests(mix, 2**31 + 77, 2.0, cfg["vocab_size"])
    by_rid = {r.rid: r for r in reqs}
    good = serve.valid(prog.serve(reqs, serve.WindowClock(jax, 60.0)), by_rid, cfg["vocab_size"])
    picked = serve.sample(good, by_rid, 2**31 + 77, work["sample_drawn"])
    e = cfg["engine"]
    sound = serve.readings(jax, ref, cfg, weights, picked, by_rid, e["max_seq"], e["max_new"])
    control = serve.readings(jax, ref, cfg, weights, picked, by_rid, e["max_seq"], e["max_new"], control="fp8")
    limits = work["checks"]
    assert common.checks_pass({k: common.check(sound[k], limits[k]) for k in limits}), sound
    assert not common.checks_pass({k: common.check(control[k], limits[k]) for k in limits}), (control, limits)


def test_page_table_updates_stay_within_the_warmed_lengths(monkeypatch):
    """Every page-table update a window makes has a length that set-up
    warms (``serve.table_update_sizes``): a length outside it would compile
    inside the window."""
    import jax

    from repro.serve import engine as engine_mod

    seen, inside = [], []
    real_ensure, real_jnp = engine_mod.DecodeWorker._ensure_chunk_pages, engine_mod.jnp

    class Recorder:
        def __getattr__(self, name):
            return getattr(real_jnp, name)

        def asarray(self, x, *a, **kw):
            if inside and isinstance(x, list):
                seen.append(len(x))
            return real_jnp.asarray(x, *a, **kw)

    def ensure(self):
        inside.append(1)
        try:
            return real_ensure(self)
        finally:
            inside.pop()

    monkeypatch.setattr(engine_mod, "jnp", Recorder())
    monkeypatch.setattr(engine_mod.DecodeWorker, "_ensure_chunk_pages", ensure)
    cfg = tiny("configs", "serve-smollm-135m")
    ref = common.reference_of("serve-smollm-135m")
    prog = serve.Program(jax, cfg, 1)
    prog.start(ref.make_weights(common.seed_key(jax, 2**31 + 41), cfg))
    reqs = serve.window_requests(dict(tiny("traffic", "chat"), rate=12.0), 2**31 + 41, 2.0, cfg["vocab_size"])
    assert len(serve.valid(prog.serve(reqs, serve.WindowClock(jax, 60.0)), {r.rid: r for r in reqs},
                           cfg["vocab_size"])) == len(reqs)
    sizes = set(serve.table_update_sizes(prog.ecfg.max_slots, prog.engine.pool.pages_per_slot))
    assert seen and set(seen) <= sizes, sorted(set(seen) - sizes)
    # evicted slots' rows and appended pages both came by
    assert max(seen) >= prog.engine.pool.pages_per_slot


def test_window_stats_by_hand():
    from repro.serve import Completion, Request

    reqs = [Request(rid=i, tokens=np.zeros(10, np.int32), max_new_tokens=4, arrival=float(i)) for i in range(3)]
    good = [Completion(rid=0, prompt_len=10, tokens=np.zeros(4, np.int32), arrival=0.0, admitted=1.0, finished=3.0),
            Completion(rid=1, prompt_len=10, tokens=np.zeros(4, np.int32), arrival=1.0, admitted=1.0, finished=5.0)]
    st = serve.window_stats(reqs, good, 10.0, 6.0)
    # request 2 never came back: it counts with the window, (10 - 2) / 4
    assert st["norm_latency_ms"] == pytest.approx(1000 * (3 / 4 + 4 / 4 + 8 / 4) / 3)
    assert st["tpot_ms"] == pytest.approx(1000 * (2 / 4 + 4 / 4 + 8 / 4) / 3)
    assert (st["offered"], st["finished"], st["tokens"]) == (3, 2, 8)
    assert st["drain_s"] == 4.0 and st["tokens_per_s"] == pytest.approx(0.8)


def test_knee_is_the_last_rate_with_no_backlog():
    import serve_sweep

    row = lambda r, q, fin=10: {"rate": r, "late": None, "offered": 10, "finished": fin, "queue_wait_p95_s": q}  # noqa: E731
    rows = [row(3.0, 0.6), row(3.5, 0.7), row(4.0, 2.5), row(4.5, 0.8)]
    assert serve_sweep.knee(rows) == 3.5
    assert serve_sweep.knee([row(3.0, 0.6), row(3.5, 0.7, fin=9)]) == 3.0
    assert serve_sweep.knee([row(3.0, 1.5)]) is None


# -- readers -------------------------------------------------------------------


def _ctx(driver):
    from test_bench_trace_reduce import hand_trace

    return {"workload": {"driver": driver}, "trace_data": hand_trace(), "epochs_traced": 1,
            "epoch_indices": range(3, 4), "peaks": PEAKS,
            "config": common.load_json("configs", "serve-smollm-135m" if driver == "serve" else "ofl-cifar10-cnn5")}


def _readers(prefix):
    import json

    man = json.loads((benchpath.BENCH.parent / "BENCHMARK.json").read_text())
    return [m["name"] for m in man["per_layer"] if m["name"].startswith(prefix)]


@pytest.mark.parametrize("name", _readers("serve_"))
def test_serve_readers_read_nothing_in_an_ofl_cell(name):
    reader = common.load_module(benchpath.BENCH / "metrics" / f"{name}.py")
    assert reader.read(_ctx("ofl")) is None


@pytest.mark.parametrize("name", _readers("ofl_"))
def test_ofl_readers_read_nothing_in_a_serve_cell(name):
    ctx = _ctx("serve")
    ctx["serve"] = {"requests": [(100, 10)], "queue_wait_s": [0.1], "decode_steps": [(100, 1)], "chunk_program": "_chunk_fn"}
    reader = common.load_module(benchpath.BENCH / "metrics" / f"{name}.py")
    assert reader.read(ctx) is None


def test_serve_readers_by_hand():
    """Two decode chunks (programs at 0-30 and 50-90 ns, a Pallas op in
    each), a prefill between them, and a third chunk after a sleep."""
    names = ["%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
             '%flash_decode.2 = f32[8]{0} custom-call(f32[8]{0} %p), custom_call_target="tpu_custom_call"',
             "%fusion.3 = f32[8]{0} fusion(f32[8]{0} %q), kind=kLoop"]
    ops = [[0, 0, 10], [1, 10, 20], [2, 35, 5], [0, 50, 20], [1, 70, 20], [0, 200, 20]]
    modules = [["jit__chunk_fn(7)", 0, 30], ["jit__prefill_fn(8)", 35, 5], ["jit__chunk_fn(7)", 50, 40],
               ["jit__chunk_fn(7)", 200, 20]]
    host = [["bench.window", 0, 300, "python"], ["bench.sleep", 95, 100, "python"]]
    t = {"window_ns": [0, 300], "devices": {"0": {"names": names, "scopes": [""] * 3, "ops": ops, "modules": modules}},
         "host": host}
    cfg = common.load_json("configs", "serve-smollm-135m")
    ctx = {"workload": {"driver": "serve"}, "trace_data": t, "peaks": PEAKS, "config": cfg,
           "serve": {"requests": [(100, 10)], "queue_wait_s": [0.0, 0.2, 0.4], "chunk_program": "_chunk_fn",
                     "decode_steps": [(1000, 4), (2000, 8)]}}
    read = lambda n: common.load_module(benchpath.BENCH / "metrics" / f"{n}.py").read(ctx)  # noqa: E731
    # chunk ops: 10+20 + 20+20 + 20 = 90 ns over 3 chunks
    assert read("serve_decode_ms") == pytest.approx(1e-6 * 90 / 3)
    # between the first two chunks the device idles 30-35 and 40-50; the pair
    # around the sleep is left out
    assert read("serve_chunk_gap_ms") == pytest.approx(1e-6 * 15)
    from flops import serve as fs

    least = fs.flash_decode_least_s(cfg, [(1000, 4), (2000, 8)], PEAKS["bf16_flops_per_s"], PEAKS["hbm_bytes_per_s"])
    assert read("serve_flash_decode_roofline") == pytest.approx(100 * least / 40e-9)
    assert read("serve_queue_wait_p95_ms") == pytest.approx(1000 * np.percentile([0.0, 0.2, 0.4], 95))
    assert read("serve_idle_share") == pytest.approx(100 * (1 - 95 / 300))
    assert read("serve_mfu") == pytest.approx(100 * fs.request(cfg, 100, 10) / 300e-9 / PEAKS["bf16_flops_per_s"])


def test_request_flops_are_its_prefill_and_decode_steps():
    from flops import serve as fs

    cfg = common.load_json("configs", "serve-smollm-135m")
    p, n = 1020, 129
    want = fs.prefill(cfg, p) + sum(fs.decode_step(cfg, p + j) for j in range(1, n))
    assert fs.request(cfg, p, n) == pytest.approx(want, rel=1e-12)
    # SmolLM-135M: 30 x (576 x (576 + 2 x 192 + 576) + 3 x 576 x 1536) = 106.2M weights a token
    assert fs.token_matmuls(cfg) == pytest.approx(2 * 30 * (576 * 1536 + 3 * 576 * 1536))
