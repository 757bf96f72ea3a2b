"""The four per-layer readers PR 36 adds (`benchmarks/layers/`), on synthetic
spans and counters: each finds its number, and gives nothing where the
program (the parent commit) or the run has nothing for it; `BENCHMARK.json`
lists them, the configuration, the cell and the cell's files consistently.
No chip."""

from __future__ import annotations

import json
import os
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
CELL = "bs1000"
CATCHUP = ["qa175-blocksync", "qa175-blocksync-load", CELL]
# name -> (unit, better, source, layer)
NEW = {
    "prefetch_ms_per_height.catchup": ("ms", "lower", "program_span", "callers"),
    "prefetch_collect_ms_per_height.catchup": ("ms", "lower", "program_span", "callers"),
    "cache_evicted_per_height.catchup": ("triples", "lower", "program_span", "batch seam and engine"),
    "repeat_column_lane_share_pct.catchup": ("%", "higher", "program_counter", "device tier"),
}
# the accepted catch-up metrics the cell does not take: an empty block has no tx to hash
NOT_TAKEN = {"tx_root_ms_per_height.catchup", "results_hash_ms_per_height.catchup"}


def _sp(i, name, t0, t1, parent=None, root=None, thread="blocksync-pool", **attrs):
    return {"id": i, "parent": parent, "root": root or i, "name": name, "t0": t0, "t1": t1,
            "thread": thread, "attrs": attrs}


def _window(i, t0, lanes, evicted):
    """One prefetch window on the worker thread: 10 ms of collect, then the seam."""
    return [
        _sp(i, "blocksync.prefetch", t0, t0 + 0.300, thread="blocksync-prefetch", blocks=31, lanes=lanes),
        _sp(i + 1, "blocksync.prefetch_collect", t0, t0 + 0.010, parent=i, root=i,
            thread="blocksync-prefetch", blocks=31, lanes=lanes),
        _sp(i + 2, "batch.verify", t0 + 0.010, t0 + 0.300, parent=i, root=i, thread="blocksync-prefetch",
            entries=lanes, hits=0, dups=0, dispatched=lanes, evicted=evicted, path="whole_miss"),
    ]


RING = (
    [_sp(1, "blocksync.sync_one", 1.0, 1.05, applied=True), _sp(2, "blocksync.sync_one", 2.0, 2.05, applied=True),
     _sp(3, "blocksync.sync_one", 3.0, 3.05, applied=True), _sp(4, "blocksync.sync_one", 4.0, 4.05, applied=True),
     _sp(5, "blocksync.sync_one", 5.0, 5.01, applied=False),  # refused: no height
     # the sync thread's own verify of a height: all hits, nothing evicted
     _sp(6, "batch.verify", 1.01, 1.02, parent=1, root=1, entries=1024, hits=1024, dups=0, dispatched=0,
         evicted=0, path="whole_hit")]
    + _window(10, 1.1, 31744, 32768) + _window(20, 3.1, 31744, 0)
    + _window(30, 30.0, 31744, 32768)  # outside the window
)


@pytest.fixture
def bench(monkeypatch):
    """(reader by metric name, obs over a ring and the hybrid tier's counters)."""
    monkeypatch.syspath_prepend(BENCH)  # as run.py has it: harness, spanlib, layerlib lie there
    import harness

    from cometbft_tpu.libs import trace

    monkeypatch.setattr(trace, "dropped", lambda: 0)

    def load(name):
        path = os.path.join(BENCH, "layers", name + ".py")
        return harness.load_by_path(path, "layer_" + name.replace(".", "_")).read

    def obs(ring=(), before=None, after=None):
        monkeypatch.setattr(trace, "spans", lambda: list(ring))
        return types.SimpleNamespace(
            window=(0.0, 20.0), samples={}, counters_before={"hybrid": before or {}},
            counters_after={"hybrid": after or {}})

    return load, obs


@pytest.mark.parametrize(
    "name, want",
    [
        ("prefetch_ms_per_height.catchup", 150.0),         # 2 x 300 ms over 4 applied heights
        ("prefetch_collect_ms_per_height.catchup", 5.0),   # 2 x 10 ms
        ("cache_evicted_per_height.catchup", 8192.0),      # one sweep of 32,768 in the window
    ],
)
def test_each_span_reader_finds_its_number_per_applied_height(bench, name, want):
    load, obs = bench
    assert load(name)(obs(RING), None) == pytest.approx(want)
    assert load(name)(obs([]), None) is None, "an untraced run"
    no_heights = [s for s in RING if s["name"] != "blocksync.sync_one"]
    assert load(name)(obs(no_heights), None) is None, "no applied height to divide by"


def test_a_ring_that_wrapped_inside_the_window_gives_nothing(bench, monkeypatch):
    from cometbft_tpu.libs import trace

    load, obs = bench
    monkeypatch.setattr(trace, "dropped", lambda: 3)  # pushed out after the window opened
    for name in NEW:
        if NEW[name][2] == "program_span":
            assert load(name)(obs(RING), None) is None


def test_the_parent_has_no_collect_span_and_its_reader_is_silent(bench, monkeypatch):
    """The parent commit's `trace.NAMES` lacks the span: nothing, not 0; the
    span it has had since PR 24 is read on both sides."""
    from cometbft_tpu.libs import trace

    load, obs = bench
    parent_ring = [s for s in RING if s["name"] != "blocksync.prefetch_collect"]
    monkeypatch.setattr(trace, "NAMES", tuple(n for n in trace.NAMES if n != "blocksync.prefetch_collect"))
    assert load("prefetch_collect_ms_per_height.catchup")(obs(parent_ring), None) is None
    assert load("prefetch_ms_per_height.catchup")(obs(parent_ring), None) == pytest.approx(150.0)


def test_evictions_are_read_only_off_spans_that_carry_them(bench):
    load, obs = bench
    older = [{**s, "attrs": {k: v for k, v in s["attrs"].items() if k != "evicted"}} for s in RING]
    assert load("cache_evicted_per_height.catchup")(obs(older), None) is None
    assert load("cache_evicted_per_height.catchup")(obs(RING[:6]), None) == 0.0, "heights, and no sweep"


@pytest.mark.parametrize(
    "before, after, want",
    [
        # 10 windows of 31,744 lanes, all in repeating columns, and 10 inline heights on the host
        ({"device_lanes": 100, "host_lanes": 50, "resident_repeat_lanes": 0},
         {"device_lanes": 100 + 317_440, "host_lanes": 50 + 10_240, "resident_repeat_lanes": 317_440},
         100.0 * 317_440 / 327_680),
        # a commit cell: one set's column, distinct keys, nothing repeats
        ({"device_lanes": 0, "host_lanes": 0, "resident_repeat_lanes": 0},
         {"device_lanes": 102_400, "host_lanes": 0, "resident_repeat_lanes": 0}, 0.0),
        ({"device_lanes": 7, "host_lanes": 7, "resident_repeat_lanes": 7},
         {"device_lanes": 7, "host_lanes": 7, "resident_repeat_lanes": 7}, None),  # nothing sent
        ({"device_lanes": 0, "host_lanes": 0}, {"device_lanes": 31_744, "host_lanes": 0}, None),  # the parent
    ],
    ids=["windows-and-inline-heights", "distinct-columns", "nothing-sent", "no-such-counter"],
)
def test_repeat_column_lane_share_reader(bench, before, after, want):
    load, obs = bench
    got = load("repeat_column_lane_share_pct.catchup")(obs(before=before, after=after), None)
    assert got == (None if want is None else pytest.approx(want))


@pytest.fixture(scope="module")
def benchmark_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(NEW))
def test_each_new_metric_is_in_the_benchmark_once_with_the_catch_up_cells(benchmark_json, name):
    unit, better, source, layer = NEW[name]
    (entry,) = [m for m in benchmark_json["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": unit, "better": better, "source": source, "layer": layer,
                     "moves": "catchup_heights_per_s", "workloads": CATCHUP}
    assert os.path.isfile(os.path.join(BENCH, "layers", name + ".py"))
    assert [m["name"] for m in benchmark_json["per_layer"][-4:]] == list(NEW), "appended, in this order"


def test_the_cell_its_configuration_and_its_files(benchmark_json):
    (cell,) = [w for w in benchmark_json["workloads"] if w["name"] == CELL]
    assert benchmark_json["workloads"][-1] is cell and benchmark_json["configs"][-1]["name"] == "valset-1024"
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("valset-1024", "replay-1000-blocks", 1)
    entry = benchmark_json["configs"][-1]
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert entry["file"] == "benchmarks/configs/valset-1024.json" and config["source"] == entry["source"]
    assert entry["reduced"] == config["reduced"] == ["nodes", "stores"]
    assert (config["validators"], config["blocks"]) == (1024, 1000)
    assert config["p2p"]["send_rate"] == config["p2p"]["recv_rate"] == 5_120_000
    assert len(config["guarantees"]) == 4 and config["rehearsal"] == {"validators": 8}
    # the chain is the source's 1,000 blocks: the window's heights, the warm-up's, and the two at the tip
    assert traffic["warmup_max_heights"] + traffic["measured_heights"] + 2 == config["blocks"]
    assert {k: traffic[k] for k in ("kind", "peers", "warmup_heights", "quiet_heights", "warmup_max_heights",
                                    "measured_heights", "tamper_height", "trace_seconds")} == {
        "kind": "blocksync_replay", "peers": 4, "warmup_heights": 96, "quiet_heights": 64,
        "warmup_max_heights": 198, "measured_heights": 800, "tamper_height": 40, "trace_seconds": 6.0}
    assert os.path.isfile(os.path.join(BENCH, "generators", traffic["kind"] + ".py"))
    assert os.path.isfile(os.path.join(BENCH, "reference", "commit_replay.py"))


def test_the_cell_is_on_the_lists_of_the_catch_up_metrics_it_can_report(benchmark_json):
    """Appended to `catchup_heights_per_s` and to every accepted `.catchup`
    metric but the two that read a block's transactions; on no `.commit` list."""
    (e2e,) = [m for m in benchmark_json["end_to_end"] if m["name"] == "catchup_heights_per_s"]
    assert e2e["workloads"] == CATCHUP
    for m in benchmark_json["per_layer"]:
        if m["name"].endswith(".catchup"):
            assert (CELL in m["workloads"]) == (m["name"] not in NOT_TAKEN), m["name"]
            if CELL in m["workloads"]:
                assert m["workloads"][-1] == CELL and m["moves"] == "catchup_heights_per_s"
        else:
            assert CELL not in m["workloads"], m["name"]
