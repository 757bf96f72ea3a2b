"""The light client's spans and counters, the five per-layer readers of the
light-client cell (`benchmarks/layers/light_*`) on synthetic spans, the
cell's entries in `BENCHMARK.json`, and its rehearsal on a CPU. No chip."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
CELL = "light4096-skip"
CONFIG = "valset-4096-rotating"
# name -> (unit, better, layer)
NEW = {
    "light_speculate_ms_per_sync.catchup": ("ms", "lower", "batch seam and engine"),
    "light_speculate_collect_ms_per_sync.catchup": ("ms", "lower", "callers"),
    "light_verify_ms_per_sync.catchup": ("ms", "lower", "callers"),
    "light_fetch_ms_per_sync.catchup": ("ms", "lower", "callers"),
    "light_hop_hit_pct.catchup": ("%", "higher", "callers"),
}
# the accepted metrics the cell reports: they read the chain's counters and the trace
TAKEN = ["device_lane_share_pct.catchup", "lanes_per_dispatch.catchup", "verify_device_ms.catchup",
         "compiles_in_window.catchup", "repeat_column_lane_share_pct.catchup"]


# -- the program's spans and counters -------------------------------------------------


def test_one_sync_records_its_spans_under_one_root(tmp_path, monkeypatch):
    monkeypatch.syspath_prepend(BENCH)
    import light_chain

    from cometbft_tpu.libs import trace
    from cometbft_tpu.libs.db import MemDB
    from cometbft_tpu.light import Client, LightStore, TrustOptions
    from cometbft_tpu.types.cmttime import Time

    inline = types.SimpleNamespace(map=lambda f, xs: list(map(f, xs)))
    marker = light_chain.build_chain(21, "light-spans", 16, 1, 30, 60, str(tmp_path), inline)
    encoded = light_chain.load_blocks(str(tmp_path), 30)
    primary = light_chain.make_provider(marker["chain_id"], encoded)
    witness = light_chain.make_provider(marker["chain_id"], encoded)
    client = Client(marker["chain_id"], TrustOptions(10**15, 1, bytes.fromhex(marker["header_hashes"]["1"])),
                    primary, [witness], LightStore(MemDB()))
    # On a thread of its own: a span another test left open on this one would be the sync's parent.
    sync_thread = threading.Thread(target=client.verify_light_block_at_height,
                                   args=(30, Time(light_chain.header_time(21, 30, 60).seconds + 60, 0)))
    with trace.capture():
        trace.clear()
        sync_thread.start()
        sync_thread.join(300)
        spans = trace.spans()
    assert not sync_thread.is_alive()
    assert {s["name"] for s in spans} <= set(trace.NAMES)
    (sync,) = [s for s in spans if s["name"] == "light.sync"]
    assert sync["parent"] is None
    light = [s for s in spans if s["name"].startswith("light.")]
    assert all(s["root"] == sync["id"] for s in light), "every span of the sync is under its root"
    counters = client.counters()
    assert sync["attrs"] == {"root": 1, "target": 30, "hops": counters["hops"], "refusals": counters["refusals"]}
    assert counters["refusals"] > 0 and counters["speculation_errors"] == 0
    kids = {s["name"] for s in spans if s["parent"] == sync["id"]}
    assert {"light.fetch", "light.verify", "light.speculate", "light.save", "light.detect"} <= kids
    fetches = [s for s in spans if s["name"] == "light.fetch"]
    assert [f["attrs"]["height"] for f in fetches][0] == 30 and all(f["attrs"]["bytes"] > 0 for f in fetches)
    assert len(fetches) == 1 + counters["pivot_fetches"] + 1, "target, pivots, the witness's target"
    hops = [s for s in spans if s["name"] == "light.verify"]
    assert {s["attrs"]["result"] for s in hops} == {"ok", "refused"}
    assert sum(s["attrs"]["result"] == "ok" for s in hops) == counters["hops"]
    assert all(set(s["attrs"]) == {"from", "to", "result"} for s in hops)
    by_id = {s["id"]: s for s in spans}
    kinds = {by_id[s["parent"]]["name"] for s in spans if s["name"] == "validation.verify_commit"}
    assert kinds == {"light.verify"}
    specs = [s for s in spans if s["name"] == "light.speculate"]
    assert len(specs) == counters["descents"] and all(s["attrs"]["lanes"] > 0 for s in specs)
    collects = [s for s in spans if s["name"] == "light.speculate_collect"]
    assert sorted(by_id[c["parent"]]["id"] for c in collects) == sorted(s["id"] for s in specs)
    (detect,) = [s for s in spans if s["name"] == "light.detect"]
    assert detect["attrs"] == {"witnesses": 1}


# -- the readers ---------------------------------------------------------------------


def _sp(i, name, t0, t1, parent=None, root=None, **attrs):
    return {"id": i, "parent": parent, "root": root or i, "name": name, "t0": t0, "t1": t1,
            "thread": "MainThread", "attrs": attrs}


def _sync(i, t0):
    """One sync of 1 s: a fetch of 100 ms, a prewarm of 200 ms (50 of
    collect), a refused hop of 10 ms and a verified one of 40 whose two
    seam calls offer 1,000 lanes, 900 of them hits."""
    return [
        {**_sp(i, "light.sync", t0, t0 + 1.0), "attrs": {"root": 1, "target": 500, "hops": 1, "refusals": 1}},
        _sp(i + 1, "light.fetch", t0, t0 + 0.1, parent=i, root=i, height=500, bytes=690_000),
        _sp(i + 2, "light.verify", t0 + 0.1, t0 + 0.11, parent=i, root=i, result="refused"),
        _sp(i + 3, "light.speculate", t0 + 0.11, t0 + 0.31, parent=i, root=i, hops=2, lanes=5462),
        _sp(i + 4, "light.speculate_collect", t0 + 0.11, t0 + 0.16, parent=i + 3, root=i),
        _sp(i + 5, "batch.verify", t0 + 0.16, t0 + 0.31, parent=i + 3, root=i, entries=5462, hits=0),
        _sp(i + 6, "light.verify", t0 + 0.31, t0 + 0.35, parent=i, root=i, result="ok"),
        _sp(i + 7, "validation.verify_commit", t0 + 0.31, t0 + 0.33, parent=i + 6, root=i, kind="trusting"),
        _sp(i + 8, "batch.verify", t0 + 0.32, t0 + 0.33, parent=i + 7, root=i, entries=400, hits=400),
        _sp(i + 9, "validation.verify_commit", t0 + 0.33, t0 + 0.35, parent=i + 6, root=i, kind="light"),
        _sp(i + 10, "batch.verify", t0 + 0.34, t0 + 0.35, parent=i + 9, root=i, entries=600, hits=500),
    ]


RING = (
    [_sp(1, "light.fetch", 0.5, 0.6, height=1, bytes=690_000)]  # the trust root's fetch: no sync's
    + _sync(10, 1.0) + _sync(30, 3.0)
    + _sync(50, 30.0)  # after the window
)


@pytest.fixture
def bench(monkeypatch):
    monkeypatch.syspath_prepend(BENCH)
    import harness

    from cometbft_tpu.libs import trace

    monkeypatch.setattr(trace, "dropped", lambda: 0)

    def load(name):
        path = os.path.join(BENCH, "layers", name + ".py")
        return harness.load_by_path(path, "layer_" + name.replace(".", "_")).read

    def obs(ring=()):
        monkeypatch.setattr(trace, "spans", lambda: list(ring))
        return types.SimpleNamespace(window=(0.0, 20.0), samples={}, counters_before={}, counters_after={})

    return load, obs


@pytest.mark.parametrize(
    "name, want",
    [
        ("light_fetch_ms_per_sync.catchup", 100.0),            # the root's fetch is no sync's
        ("light_speculate_ms_per_sync.catchup", 200.0),
        ("light_speculate_collect_ms_per_sync.catchup", 50.0),
        ("light_verify_ms_per_sync.catchup", 50.0),             # 10 refused + 40 verified
        ("light_hop_hit_pct.catchup", 90.0),                    # 900 of the hops' 1,000; the prewarm's 5,462 are not a hop's
    ],
)
def test_each_reader_finds_its_number_per_sync(bench, name, want):
    load, obs = bench
    assert load(name)(obs(RING), None) == pytest.approx(want)
    assert load(name)(obs([]), None) is None, "an untraced run"
    no_syncs = [s for s in RING if s["name"] != "light.sync"]
    assert load(name)(obs(no_syncs), None) is None, "a program without the light client's spans"


def test_a_ring_that_wrapped_inside_the_window_gives_nothing(bench, monkeypatch):
    from cometbft_tpu.libs import trace

    load, obs = bench
    monkeypatch.setattr(trace, "dropped", lambda: 3)
    for name in NEW:
        assert load(name)(obs(RING), None) is None


def test_hits_are_read_only_where_a_hop_checked_anything(bench):
    load, obs = bench
    refused_only = [s for s in _sync(10, 1.0) if s["id"] < 16]
    assert load("light_hop_hit_pct.catchup")(obs(refused_only), None) is None
    assert load("light_verify_ms_per_sync.catchup")(obs(refused_only), None) == pytest.approx(10.0)


# -- the benchmark's entries ---------------------------------------------------------


@pytest.fixture(scope="module")
def benchmark_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(NEW))
def test_each_new_metric_is_in_the_benchmark_once_with_the_cell(benchmark_json, name):
    unit, better, layer = NEW[name]
    (entry,) = [m for m in benchmark_json["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": unit, "better": better, "source": "program_span", "layer": layer,
                     "moves": "catchup_heights_per_s", "workloads": [CELL]}
    assert os.path.isfile(os.path.join(BENCH, "layers", name + ".py"))


def test_the_cell_its_configuration_and_its_files(benchmark_json):
    (cell,) = [w for w in benchmark_json["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "skip-500-headers", 1)
    (entry,) = [c for c in benchmark_json["configs"] if c["name"] == CONFIG]
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json" and config["source"] == entry["source"]
    assert entry["reduced"] == config["reduced"] == ["network"]
    assert (config["validators"], config["rotate"], config["light_blocks"], config["block_seconds"]) == (4096, 41, 507, 60)
    assert (config["trusting_period_s"], config["trust_level"], config["mode"], config["witnesses"]) == (
        14 * 86400, [1, 3], "skipping", 1)
    assert len(config["guarantees"]) == 4 and config["rehearsal"]["validators"] == 16
    assert traffic["kind"] == "light_skipping" and (traffic["roots"], traffic["headers"]) == (8, 500)
    assert traffic["roots"] + traffic["headers"] - 1 == config["light_blocks"]
    reh = {**config, **config["rehearsal"]}, {**traffic, **traffic["rehearsal"]}
    assert reh[1]["roots"] + reh[1]["headers"] - 1 == reh[0]["light_blocks"]
    for path in ("generators/light_skipping.py", "reference/light_skipping.py", "light_chain.py", "LIGHT.md"):
        assert os.path.isfile(os.path.join(BENCH, path)), path


def test_the_cell_is_on_the_lists_it_reports(benchmark_json):
    (e2e,) = [m for m in benchmark_json["end_to_end"] if m["name"] == "catchup_heights_per_s"]
    assert e2e["workloads"][-1] == CELL
    for m in benchmark_json["per_layer"]:
        want = m["name"] in TAKEN or m["name"] in NEW
        assert (CELL in m.get("workloads", [])) == want, m["name"]
        if want:
            assert m["workloads"][-1] == CELL and m["moves"] == "catchup_heights_per_s"
    assert [m["name"] for m in benchmark_json["per_layer"][-len(NEW):]] == list(NEW), "appended, in this order"


# -- the rehearsal -------------------------------------------------------------------


def test_the_cell_rehearses_to_a_result_line():
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "CMTPU_HYBRID_MIN": "8", "CMTPU_DEV_RATE": "1000",
           "CMTPU_HOST_RATE": "1000", "CMTPU_DEV_OVERHEAD_MS": "0", "CMTPU_VERIFY_CACHE_MAX": "64"}
    for k in ("CMTPU_BACKEND", "BENCH_RUN", "XLA_FLAGS"):
        env.pop(k, None)
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL, "--seed", str(2**31 + 7),
         "--seconds", "2", "--trace", "1", "--platform", "cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.splitlines()
    res = json.loads(lines[-1])
    assert res["correct"] is False and res["attempted"] > 0 and res["failed"] == 0
    assert set(NEW) <= set(res["metrics"])
    problems = [ln for ln in lines if ln.startswith("NOT CORRECT: ")]
    assert all("device" in p or "rehearsal" in p for p in problems), problems
    assert any(ln.startswith("tampered chain: ") and ln.endswith("problems 0") for ln in lines)
    assert any(ln.startswith("check dispatch: ") and "problems 0" in ln for ln in lines)
