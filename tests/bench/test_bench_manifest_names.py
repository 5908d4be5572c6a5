"""BENCHMARK.json against the contract's names, units and files, and every
name it uses resolved to a file under bench/."""
import json
import re

import pytest

import benchpath  # noqa: F401

ROOT = benchpath.BENCH.parent
MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")


def line_ok(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys_and_command():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(MAN["command"]) <= 32 and all(line_ok(w) for w in MAN["command"])
    assert isinstance(MAN["run_seconds"], int) and 1 <= MAN["run_seconds"] <= 51
    assert 1 <= len(MAN["paths"]) <= 16
    for p in MAN["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p.split("/")
        assert (ROOT / p).is_dir()
    script = MAN["command"][1]
    assert any(script.startswith(p + "/") for p in MAN["paths"]) and (ROOT / script).is_file()
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_uniqueness():
    names = []
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in MAN[section]:
            assert NAME.match(e["name"]), e["name"]
            names.append((section if section in ("configs", "workloads") else "metric", e["name"]))
    assert len(names) == len(set(names))
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for w in MAN["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"]) and line_ok(w["why"])
        assert set(w) == {"name", "config", "traffic", "chips", "why"} and w["chips"] in (1, 4)
    pairs = [(w["config"], w["traffic"]) for w in MAN["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert sum(w["chips"] == 4 for w in MAN["workloads"]) <= max(1, len(MAN["workloads"]) // 2)


def test_end_to_end_bounds_and_sources():
    e2e = {m["name"]: m for m in MAN["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) <= 16
    for m in e2e.values():
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    cells = {w["name"] for w in MAN["workloads"]}
    for cell in cells:
        reported = [m for m in e2e.values() if "workloads" not in m or cell in m["workloads"]]
        assert len(reported) >= 2


def test_per_layer_entries():
    e2e = {m["name"] for m in MAN["end_to_end"]}
    cells = {w["name"] for w in MAN["workloads"]}
    layers = {}
    for m in MAN["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert m["moves"] in e2e and line_ok(m["layer"])
        assert set(m["workloads"]) <= cells
        if m["name"].endswith("_roofline") or "_roofline." in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())
    for cell in cells:
        assert any(cell in m.get("workloads", cells) for m in MAN["per_layer"])


@pytest.mark.parametrize("cell", [w["name"] for w in MAN["workloads"]])
def test_cell_files_resolve(cell):
    w = next(x for x in MAN["workloads"] if x["name"] == cell)
    work = json.loads((ROOT / "bench" / "workloads" / f"{cell}.json").read_text())
    assert work["config"] == w["config"] and work["traffic"] == w["traffic"] and work["chips"] == w["chips"]
    assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
    cfg = next(c for c in MAN["configs"] if c["name"] == w["config"])
    assert (ROOT / cfg["file"]).is_file() and (ROOT / "bench" / "configs" / f"{cfg['name']}.py").is_file()
    assert all(v > 0 for v in work["checks"].values())


def test_configs_and_readers():
    files = [c["file"] for c in MAN["configs"]]
    assert len(files) == len(set(files))
    for c in MAN["configs"]:
        assert line_ok(c["source"]) and line_ok(c["why"]) and len(c["reduced"]) <= 16
        body = json.loads((ROOT / c["file"]).read_text())
        assert body["name"] == c["name"] and sorted(body["reduced"]) == sorted(c["reduced"])
        for k in c["reduced"]:
            assert NAME.match(k) and not k.endswith(("_dim", "_rank", "_size")) and "hidden" not in k
    for m in MAN["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file(), m["name"]
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    assert peaks["source"] and peaks["devices"]["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert peaks["devices"]["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
