"""The sixteen readers of program spans and counters (`layers/*.py` over
`spanlib.py`), on a recorded span list (`data/spans_recorded.json`: three
`verify_commit` of 48 signatures through the auto chain on XLA:CPU with the
hybrid splitting, and an 8-block blocksync over loopback) and on a few
spans written by hand. No JAX, no chip."""

import json
import os
import statistics
import sys
import types

import pytest

from conftest import BENCH

import harness
import spanlib

with open(os.path.join(BENCH, "tests", "data", "spans_recorded.json")) as f:
    RECORDED = json.load(f)

CELL_OF = {"commit": "commit10k-cold", "catchup": "qa175-blocksync"}


def _new_metrics(benchmark_json):
    old = 12  # the per-layer metrics of PR 23 come first and are not span readers
    return benchmark_json["per_layer"][old:]


def _reader(name):
    path = os.path.join(BENCH, "layers", name + ".py")
    return harness.load_by_path(path, "layer_" + name.replace(".", "_"))


def _obs(window, hybrid_before=None, hybrid_after=None):
    return types.SimpleNamespace(
        window=tuple(window),
        counters_before={"hybrid": hybrid_before or {}},
        counters_after={"hybrid": hybrid_after or {}},
    )


@pytest.fixture
def ring(monkeypatch):
    """Stands in for the program's ring: `ring(spans, dropped)` sets what
    `cometbft_tpu.libs.trace.spans()` / `.dropped()` give the readers."""
    fake = types.ModuleType("cometbft_tpu.libs.trace")
    state = {"spans": [], "dropped": 0}
    fake.spans = lambda: [dict(s) for s in state["spans"]]
    fake.dropped = lambda: state["dropped"]
    import cometbft_tpu.libs

    monkeypatch.setitem(sys.modules, "cometbft_tpu.libs.trace", fake)
    monkeypatch.setattr(cometbft_tpu.libs, "trace", fake, raising=False)

    def put(spans, dropped=0):
        state["spans"], state["dropped"] = spans, dropped

    return put


def _recorded_obs(kind):
    rec = RECORDED[kind]
    return _obs(rec["window"], rec.get("hybrid_before"), rec.get("hybrid_after"))


def test_sixteen_new_metrics_each_with_a_reader(benchmark_json):
    new = _new_metrics(benchmark_json)
    assert len(new) == 16
    for m in new:
        assert m["source"] in ("program_span", "program_counter")
        assert m["workloads"] == [CELL_OF[m["name"].rsplit(".", 1)[1]]]
        assert callable(_reader(m["name"]).read)


def test_every_reader_finds_something_on_the_recording(benchmark_json, ring):
    for m in _new_metrics(benchmark_json):
        kind = m["name"].rsplit(".", 1)[1]
        ring(RECORDED[kind]["spans"])
        value = _reader(m["name"]).read(_recorded_obs(kind), None)
        assert isinstance(value, float), m["name"]
        assert value >= 0.0, m["name"]
        if m["unit"] == "%":
            assert value <= 100.0, m["name"]


def test_none_on_an_empty_ring_and_on_a_wrapped_one(benchmark_json, ring):
    for m in _new_metrics(benchmark_json):
        if m["source"] != "program_span":
            continue
        kind = m["name"].rsplit(".", 1)[1]
        read, obs = _reader(m["name"]).read, _recorded_obs(kind)
        ring([])
        assert read(obs, None) is None, m["name"]
        # the ring pushed spans out after the window opened: what is left is a part
        ring(RECORDED[kind]["spans"], dropped=3)
        assert read(obs, None) is None, m["name"]
        # dropped only before the window: the oldest span left ended before it opened
        old = {"id": 0, "parent": None, "root": 0, "name": "engine.queue_wait",
               "t0": -2.0, "t1": -1.0, "thread": "x", "attrs": {}}
        ring([old] + RECORDED[kind]["spans"], dropped=3)
        assert read(obs, None) is not None, m["name"]


def test_none_without_a_tracer_in_the_program(benchmark_json, monkeypatch):
    monkeypatch.setitem(sys.modules, "cometbft_tpu.libs.trace", None)  # import fails
    for m in _new_metrics(benchmark_json):
        kind = m["name"].rsplit(".", 1)[1]
        assert _reader(m["name"]).read(_obs(RECORDED[kind]["window"]), None) is None, m["name"]


def test_the_recorded_values_against_a_plain_recount(ring):
    commit, catchup = RECORDED["commit"], RECORDED["catchup"]
    ms = lambda s: (s["t1"] - s["t0"]) * 1000.0
    ring(commit["spans"])
    obs = _recorded_obs("commit")
    ops = [s for s in commit["spans"] if s["name"] == "validation.verify_commit"]
    assert len(ops) == 3
    outside = []
    for op in ops:
        held = sum(ms(s) for s in commit["spans"] if s["name"] == "batch.dispatch" and s["root"] == op["id"])
        outside.append(ms(op) - held)
    assert _reader("caller_outside_seam_ms.commit").read(obs, None) == pytest.approx(statistics.median(outside))
    sb = [ms(s) for s in commit["spans"] if s["name"] == "validation.sign_bytes"]
    assert _reader("sign_bytes_ms.commit").read(obs, None) == pytest.approx(statistics.median(sb))
    assert _reader("planner_share_changes_pct.commit").read(obs, None) == pytest.approx(
        100.0 * (commit["hybrid_after"]["share_changes"] - commit["hybrid_before"]["share_changes"])
        / (commit["hybrid_after"]["split_calls"] - commit["hybrid_before"]["split_calls"])
    )
    ring(catchup["spans"])
    obs = _recorded_obs("catchup")
    heights = [s for s in catchup["spans"] if s["name"] == "blocksync.sync_one" and s["attrs"]["applied"]]
    assert len(heights) == 7
    validate = sum(ms(s) for s in catchup["spans"] if s["name"] == "state.validate")
    assert _reader("state_validate_ms_per_height.catchup").read(obs, None) == pytest.approx(validate / 7)
    assert sum(s["name"] == "state.validate" for s in catchup["spans"]) == 14, "twice a height"
    stores = sum(ms(s) for s in catchup["spans"]
                 if s["name"] in ("state.save_responses", "state.save_state", "store.save_block"))
    assert _reader("state_store_ms_per_height.catchup").read(obs, None) == pytest.approx(stores / 7)


def _span(i, name, t0, t1, parent=None, root=None, **attrs):
    return {"id": i, "parent": parent, "root": root if root is not None else i, "name": name,
            "t0": t0, "t1": t1, "thread": "t", "attrs": attrs}


def test_by_hand(ring):
    """Two operations: 100 ms with a 70 ms dispatch, 140 ms with a 90 ms
    one; a split call in each, the device ending 5 ms before and 30 ms after
    the host; a third verify_commit nested in something else is no operation."""
    spans = [
        _span(1, "validation.verify_commit", 0.000, 0.100),
        _span(2, "batch.cache_filter", 0.010, 0.014, parent=1, root=1),
        _span(3, "batch.dispatch", 0.015, 0.085, parent=1, root=1),
        _span(4, "hybrid.call", 0.016, 0.084, parent=3, root=1, route="split", share=8192),
        _span(5, "hybrid.host_msm", 0.030, 0.075, parent=4, root=1),
        _span(6, "device.run", 0.025, 0.070, parent=4, root=1),
        _span(7, "batch.cache_insert", 0.086, 0.092, parent=1, root=1),
        _span(11, "validation.verify_commit", 0.200, 0.340),
        _span(12, "batch.cache_filter", 0.210, 0.215, parent=11, root=11),
        _span(13, "batch.dispatch", 0.220, 0.310, parent=11, root=11),
        _span(14, "hybrid.call", 0.221, 0.309, parent=13, root=11, route="split", share=6144),
        _span(15, "hybrid.host_msm", 0.240, 0.305, parent=14, root=11),
        _span(16, "device.run", 0.230, 0.275, parent=14, root=11),
        _span(17, "batch.cache_insert", 0.312, 0.323, parent=11, root=11),
        _span(21, "hybrid.call", 0.400, 0.410, route="host", share=0),
        _span(22, "hybrid.host_msm", 0.401, 0.409, parent=21, root=21),
        _span(31, "validation.verify_commit", 0.500, 0.501, parent=30, root=30),
    ]
    ring(spans)
    obs = _obs((0.0, 1.0))
    assert _reader("caller_outside_seam_ms.commit").read(obs, None) == pytest.approx(40.0)  # 30, 50
    assert _reader("verified_cache_ms.commit").read(obs, None) == pytest.approx(13.0)  # 10, 16
    assert _reader("split_imbalance_ms.commit").read(obs, None) == pytest.approx(17.5)  # 5, 30
    # a window that holds the first operation only
    assert _reader("caller_outside_seam_ms.commit").read(_obs((0.0, 0.15)), None) == pytest.approx(30.0)
    assert _reader("pack_ms.commit").read(obs, None) is None  # no device.pack among them
    waits = [_span(100 + i, "engine.queue_wait", i, i + 0.001 * (i + 1)) for i in range(20)]
    ring(waits)
    assert spanlib.p95_ms(_obs((0.0, 100.0)), "engine.queue_wait") == pytest.approx(19.0)
    heights = [
        _span(1, "blocksync.sync_one", 0.0, 0.018, height=5, applied=True),
        _span(2, "batch.verify", 0.001, 0.002, parent=1, root=1, entries=175, hits=175),
        _span(3, "blocksync.sync_one", 0.02, 0.03, height=6, applied=False),
        _span(4, "batch.verify", 0.021, 0.029, parent=3, root=3, entries=175, hits=0),
        _span(5, "blocksync.prefetch", 0.0, 0.04, blocks=31),
        _span(6, "batch.verify", 0.001, 0.039, parent=5, root=5, entries=5425, hits=0),
        _span(7, "blocksync.fetch_wait", 0.03, 0.05, sleeps=2),
    ]
    ring(heights)
    obs = _obs((0.0, 1.0))
    assert _reader("serial_cache_hit_pct.catchup").read(obs, None) == pytest.approx(50.0)
    assert _reader("fetch_wait_ms_per_height.catchup").read(obs, None) == pytest.approx(20.0)
    assert _reader("verify_wait_ms_per_height.catchup").read(obs, None) == 0.0  # applied, never waited
    assert _reader("planner_share_changes_pct.commit").read(obs, None) is None  # no such counter
    assert _reader("planner_share_changes_pct.commit").read(
        _obs((0, 1), {"split_calls": 10, "share_changes": 4}, {"split_calls": 30, "share_changes": 19}), None
    ) == pytest.approx(75.0)
