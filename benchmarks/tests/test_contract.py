"""Every name, unit and length in BENCHMARK.json fits the driver's contract,
and every file it names is where the harness will look for it."""

import json
import os
import re

from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def line_ok(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys(benchmark_json):
    assert set(benchmark_json) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"
    }
    assert benchmark_json["paths"] == ["benchmarks"]
    assert all(line_ok(w) for w in benchmark_json["command"])
    assert len(json.dumps(benchmark_json)) < 64 * 1024


def test_run_seconds_fits_the_full_check(benchmark_json):
    rs = benchmark_json["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    cells = 24
    assert (2 + 14 * cells) * (rs + 60) + cells * 2 * 90 + 1200 <= 43200


def test_configs(benchmark_json):
    names = set()
    for c in benchmark_json["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] not in names
        names.add(c["name"])
        assert line_ok(c["source"]) and line_ok(c["why"])
        assert c["file"].startswith("benchmarks/") and os.path.isfile(os.path.join(ROOT, c["file"]))
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and key in body, key
        assert body["source"] == c["source"]
        assert body["guarantees"], "a deployment states its guarantees"
    used = {w["config"] for w in benchmark_json["workloads"]}
    assert used == names, "every configuration is used by some cell"


def test_workloads(benchmark_json):
    names, pairs = set(), set()
    for w in benchmark_json["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        for key in ("name", "config", "traffic"):
            assert NAME.match(w[key]), w[key]
        assert w["name"] not in names and (w["config"], w["traffic"]) not in pairs
        names.add(w["name"])
        pairs.add((w["config"], w["traffic"]))
        assert w["chips"] in (1, 4) and line_ok(w["why"])
        traffic = os.path.join(BENCH, "traffic", w["traffic"] + ".json")
        with open(traffic) as f:
            kind = json.load(f)["kind"]
        assert os.path.isfile(os.path.join(BENCH, "generators", kind + ".py"))
    four = sum(w["chips"] == 4 for w in benchmark_json["workloads"])
    assert four <= max(1, len(names) // 2)


def test_metrics(benchmark_json):
    cells = {w["name"] for w in benchmark_json["workloads"]}
    seen = set()
    e2e = {}
    for m in benchmark_json["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        e2e[m["name"]] = set(m.get("workloads", cells))
    assert "setup_s" in e2e and e2e["setup_s"] == cells
    layers = set()
    for m in benchmark_json["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert line_ok(m["layer"])
        layers.add(m["layer"])
        where = set(m.get("workloads", cells))
        assert where <= e2e[m["moves"]], f"{m['name']} moves a metric its cells do not report"
        assert os.path.isfile(os.path.join(BENCH, "layers", m["name"] + ".py")), m["name"]
    for m in benchmark_json["end_to_end"] + benchmark_json["per_layer"]:
        assert NAME.match(m["name"]) and m["name"] not in seen
        seen.add(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells
    for cell in cells:
        assert any(cell in ws for name, ws in e2e.items() if name != "setup_s")
        assert any(cell in m.get("workloads", cells) for m in benchmark_json["per_layer"])
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"


def test_files_are_named_from_name_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base, dirs, files in os.walk(BENCH):
        dirs[:] = [d for d in dirs if d not in (".cache", "__pycache__")]
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), ROOT)
            assert ok.match(rel), rel
