"""The per-layer readers PR 26 adds (`benchmarks/layers/`), on recorded
counters and a reduced trace: each finds its number, and returns None where
the program (the parent commit) or the run has nothing for it. No chip."""

from __future__ import annotations

import json
import os
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
LOAD, X4 = "qa175-blocksync-load", "commit10k-cold-x4"
NEW = {
    "tx_root_ms_per_height.catchup": ("program_span", "callers", "catchup_heights_per_s", LOAD),
    "part_proofs_ms_per_height.catchup": ("program_span", "callers", "catchup_heights_per_s", LOAD),
    "results_hash_ms_per_height.catchup": ("program_span", "state and stores", "catchup_heights_per_s", LOAD),
    "peer_spread_pct.catchup": ("program_counter", "callers", "catchup_heights_per_s", LOAD),
    "recv_share_of_link_pct.catchup": ("program_counter", "callers", "catchup_heights_per_s", LOAD),
    "collective_ms.commit": ("device_trace", "device tier", "commit_verify_p50_ms", X4),
}


@pytest.fixture
def reader(monkeypatch):
    monkeypatch.syspath_prepend(BENCH)  # as run.py has it: harness, spanlib, layerlib lie there
    import harness

    def load(name):
        path = os.path.join(BENCH, "layers", name + ".py")
        return harness.load_by_path(path, "layer_" + name.replace(".", "_")).read

    return load


def _obs(first=None, last=None, seconds=10.0):
    samples = {} if last is None else {"reactor_counters": (first or {}, last, seconds)}
    return types.SimpleNamespace(window=(0.0, 20.0), samples=samples)


RUN = types.SimpleNamespace(config={"p2p": {"recv_rate": 5_120_000}}, trace=None)
AT_OPEN = {"requests_sent": 130, "requests_to_busiest_peer": 40, "peers_asked": 4,
           "block_bytes_received": 40_000_000,
           "requests_by_peer": {"a": 40, "b": 30, "c": 30, "d": 30}}


def _asked(sent, **by_peer):
    return {**AT_OPEN, "requests_sent": sent, "requests_by_peer": by_peer}



@pytest.mark.parametrize(
    "first, last, want",
    [
        (AT_OPEN, _asked(530, a=140, b=130, c=130, d=130), 75.0),
        (AT_OPEN, _asked(530, a=40, b=430, c=30, d=30), 0.0),
        # the peer asked most before the window is not the one asked most in it
        (AT_OPEN, _asked(530, a=60, b=230, c=130, d=110), 50.0),
        (AT_OPEN, _asked(330, a=140, c=130), 50.0),  # two peers left: the live ones are read
        (AT_OPEN, dict(AT_OPEN), None),  # nothing asked in the window
        ({"heights_applied": 3}, {"heights_applied": 9}, None),  # a program from before the counters
    ],
    ids=["four-share-evenly", "one-peer-asked", "busiest-changes", "peers-left", "nothing-asked",
         "keys-absent"],
)
def test_peer_spread_reader(reader, first, last, want):
    got = reader("peer_spread_pct.catchup")(_obs(first, last), RUN)
    assert got == (None if want is None else pytest.approx(want))


@pytest.mark.parametrize(
    "last, seconds, want",
    [
        ({**AT_OPEN, "block_bytes_received": 40_000_000 + 102_400_000}, 10.0, 50.0),
        ({**AT_OPEN, "block_bytes_received": 40_000_000 + 51_200_000, "peers_asked": 1}, 10.0, 100.0),
        ({**AT_OPEN, "block_bytes_received": 50_000_000}, 0.0, None),
        ({"heights_applied": 9}, 10.0, None),
    ],
    ids=["half-of-four-links", "one-link-full", "no-time", "keys-absent"],
)
def test_recv_share_of_link_reader(reader, last, seconds, want):
    got = reader("recv_share_of_link_pct.catchup")(_obs(AT_OPEN, last, seconds), RUN)
    assert got == (None if want is None else pytest.approx(want))


@pytest.mark.parametrize("name", [n for n in NEW if n.startswith(("peer_", "recv_"))])
def test_counter_readers_without_a_generator_that_kept_them(reader, name):
    assert reader(name)(_obs(), RUN) is None


@pytest.mark.parametrize(
    "name, span",
    [("tx_root_ms_per_height.catchup", "types.data_hash"),
     ("part_proofs_ms_per_height.catchup", "types.part_set_proofs"),
     ("results_hash_ms_per_height.catchup", "state.results_hash")],
)
def test_span_readers_sum_their_span_per_applied_height(reader, monkeypatch, name, span):
    from cometbft_tpu.libs import trace

    def sp(i, name, t0, t1, **attrs):
        return {"id": i, "parent": None, "root": i, "name": name, "t0": t0, "t1": t1,
                "thread": "sync", "attrs": attrs}

    spans = [sp(1, "blocksync.sync_one", 1.0, 1.03, applied=True),
             sp(2, "blocksync.sync_one", 2.0, 2.03, applied=True),
             sp(3, span, 1.001, 1.003), sp(4, span, 2.001, 2.004),
             sp(5, span, 30.0, 30.5)]  # outside the window
    monkeypatch.setattr(trace, "spans", lambda: spans)
    monkeypatch.setattr(trace, "dropped", lambda: 0)
    assert reader(name)(_obs(), RUN) == pytest.approx(2.5)
    monkeypatch.setattr(trace, "NAMES", tuple(n for n in trace.NAMES if n != span))
    assert reader(name)(_obs(), RUN) is None, "a program from before the span: missing, not 0"


@pytest.mark.parametrize(
    "trace_, want",
    [
        ({"chips": 4, "collective_s": 0.012, "modules": {"jit_verify_core": {"count": 8.0, "total_s": 0.4}}}, 1.5),
        ({"chips": 4, "collective_s": 0.0, "modules": {"jit_verify_core": {"count": 8.0, "total_s": 0.4}}}, 0.0),
        ({"chips": 1, "collective_s": 0.0, "modules": {"jit_verify_core": {"count": 8.0, "total_s": 0.4}}}, None),
        ({"chips": 4, "collective_s": 0.012, "modules": {}}, None),
        (None, None),
    ],
    ids=["permutes", "none-in-the-program", "one-chip", "no-verify-program", "untraced"],
)
def test_collective_reader(reader, trace_, want):
    got = reader("collective_ms.commit")(_obs(), types.SimpleNamespace(trace=trace_))
    assert got == (None if want is None else pytest.approx(want))


@pytest.mark.parametrize("name", sorted(NEW))
def test_each_new_metric_is_in_the_benchmark_once_with_its_cell(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    source, layer, moves, cell = NEW[name]
    entries = [m for m in bench["per_layer"] if m["name"] == name]
    assert len(entries) == 1
    # PR 36's cell keeps the reactor's counters and its blocks have two parts: appended where it reads
    later = ["bs1000"] if name.startswith(("part_proofs_", "peer_", "recv_")) else []
    assert {k: entries[0][k] for k in ("source", "layer", "moves", "workloads")} == {
        "source": source, "layer": layer, "moves": moves, "workloads": [cell] + later}
    assert os.path.isfile(os.path.join(BENCH, "layers", name + ".py"))


def test_the_new_cells_report_every_metric_of_their_kind():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    assert (cells[LOAD]["config"], cells[LOAD]["traffic"], cells[LOAD]["chips"]) == (
        "qa-175-loaded", "join-loaded-blocks", 1)
    assert (cells[X4]["config"], cells[X4]["traffic"], cells[X4]["chips"]) == (
        "valset-10000", "cold-commits-x4", 4)
    # On four chips every call goes to the mesh whole: no host share, no split,
    # so the readers of the split find nothing there and the cell is not on their lists.
    split_only = {"host_msm_ms.commit", "split_imbalance_ms.commit", "planner_share_changes_pct.commit"}
    for m in bench["per_layer"] + bench["end_to_end"]:
        lists = m.get("workloads")
        if lists is None:
            continue
        assert ("qa175-blocksync" in lists) <= (LOAD in lists), m["name"]
        assert ("commit10k-cold" in lists and m["name"] not in split_only) == (X4 in lists) or (
            m["name"] == "collective_ms.commit"), m["name"]
