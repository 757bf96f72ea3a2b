"""The per-layer readers ISSUE 30 adds for `commit10k-sidecar`
(`benchmarks/layers/`, helpers in `benchmarks/sidecarlib.py`), on recorded
spans of two processes and recorded counters: the merge puts a server span
under the node operation whose call holds it (`req` confirms), each reader
finds its number, and each returns None where the program (the parent
commit) or the run has nothing for it. No chip, no process."""

from __future__ import annotations

import json
import os
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
CELL = "commit10k-sidecar"
NEW = {
    "wire_ms.commit": ("ms", "program_span", "sidecar wire"),
    "wire_encode_ms.commit": ("ms", "program_span", "sidecar wire"),
    "wire_decode_ms.commit": ("ms", "program_span", "sidecar wire"),
    "queue_wait_ms.commit": ("ms", "program_span", "batch seam and engine"),
    "node_outside_seam_ms.commit": ("ms", "program_span", "callers"),
    "wire_bytes_per_sig.commit": ("bytes/sig", "program_counter", "sidecar wire"),
}


def _sp(i, name, t0, t1, parent=None, root=None, **attrs):
    return {"id": i, "parent": parent, "root": i if root is None else root, "name": name,
            "t0": t0, "t1": t1, "thread": "t", "attrs": attrs}


def _node_op(base_id, t, req):
    """One operation of the node, 100 ms: 10 ms of its own, a 90 ms dispatch
    holding 2 ms of queue and an 80 ms call (2 x 3 ms encode, 1 ms decode)."""
    r = base_id
    return [
        _sp(r, "validation.verify_commit", t, t + 0.100),
        _sp(r + 1, "batch.dispatch", t + 0.008, t + 0.098, parent=r, root=r),
        _sp(r + 2, "engine.queue_wait", t + 0.008, t + 0.010, parent=r + 1, root=r),
        _sp(r + 3, "grpc.call", t + 0.012, t + 0.092, parent=r + 1, root=r, req=req, lanes=100),
        _sp(r + 4, "grpc.encode", t + 0.012, t + 0.015, parent=r + 3, root=r, seq=0),
        _sp(r + 5, "grpc.encode", t + 0.016, t + 0.019, parent=r + 3, root=r, seq=1),
        _sp(r + 6, "grpc.wait", t + 0.020, t + 0.090, parent=r + 3, root=r),
        _sp(r + 7, "grpc.decode", t + 0.090, t + 0.091, parent=r + 3, root=r),
    ]


def _server_request(base_id, t, req):
    """The sidecar's side of that call, ids of its own process (they collide
    with the node's on purpose): 2 x 5 ms decode, 3 ms queue, a 50 ms
    hybrid.call, 0.5 ms encode."""
    r = base_id
    return [
        _sp(r, "sidecar.request", t + 0.014, t + 0.089, req=req, lanes=100),
        _sp(r + 1, "sidecar.decode", t + 0.014, t + 0.019, parent=r, root=r, seq=0),
        _sp(r + 2, "sidecar.decode", t + 0.020, t + 0.025, parent=r, root=r, seq=1),
        _sp(r + 3, "engine.queue_wait", t + 0.025, t + 0.028, parent=r, root=r),
        _sp(r + 4, "hybrid.call", t + 0.030, t + 0.080, parent=r, root=r, n=100),
        _sp(r + 5, "sidecar.encode", t + 0.0880, t + 0.0885, parent=r, root=r),
    ]


NODE = _node_op(1, 1.0, req=7) + _node_op(11, 2.0, req=9) + _node_op(21, 3.0, req=11)
SIDECAR = (_server_request(1, 1.0, req=7) + _server_request(11, 2.0, req=9)
           + _server_request(21, 3.0, req=12)  # another client's: the id says so
           + _server_request(31, 30.0, req=13))  # outside the window
GRPC0 = {"bytes_sent": 1000, "bytes_received": 100, "lanes_sent": 10}
GRPC1 = {"bytes_sent": 1000 + 448_000, "bytes_received": 100 + 2_000, "lanes_sent": 10 + 2_000}


@pytest.fixture
def bench(monkeypatch):
    """(reader by metric name, obs with the merged operations) as run.py has
    them: the sidecar's ring is this process's, the node's came over the pipe."""
    monkeypatch.syspath_prepend(BENCH)
    import harness
    import sidecarlib

    from cometbft_tpu.libs import trace

    monkeypatch.setattr(trace, "spans", lambda: SIDECAR)
    monkeypatch.setattr(trace, "dropped", lambda: 0)

    def load(name):
        path = os.path.join(BENCH, "layers", name + ".py")
        return harness.load_by_path(path, "layer_" + name.replace(".", "_")).read

    def obs(node_spans=NODE, grpc=(GRPC0, GRPC1), merged=True):
        o = types.SimpleNamespace(
            window=(0.0, 20.0),
            samples={} if node_spans is None else {"node_spans": node_spans, "node_dropped": 0},
            counters_before={"node": {"grpc": grpc[0]}} if grpc else {},
            counters_after={"node": {"grpc": grpc[1]}} if grpc else {},
        )
        if merged:
            o.samples["wire_ops"] = sidecarlib.merge(o)
        return o

    return load, obs


def test_the_merge_puts_server_spans_under_the_operation_whose_call_holds_them(bench):
    _, obs = bench
    ops = obs().samples["wire_ops"]
    assert [e["op"]["id"] for e in ops] == [1, 11, 21]
    assert [len(e["node"]) for e in ops] == [8, 8, 8]
    # the third call's interval holds a request with another id: not its own
    assert [len(e["sidecar"]) for e in ops] == [6, 6, 0]
    assert all(s["t0"] >= 1.0 and s["t1"] <= 1.1 for s in ops[0]["sidecar"])


@pytest.mark.parametrize(
    "name, want",
    [
        ("wire_ms.commit", 30.0),               # an 80 ms call holding a 50 ms hybrid.call
        ("wire_encode_ms.commit", 6.5),         # 2 x 3 ms in the node, 0.5 ms in the sidecar
        ("wire_decode_ms.commit", 11.0),        # 2 x 5 ms in the sidecar, 1 ms in the node
        ("queue_wait_ms.commit", 5.0),          # both engines: 2 + 3 ms
        ("node_outside_seam_ms.commit", 10.0),  # a 100 ms operation holding a 90 ms dispatch
        ("wire_bytes_per_sig.commit", 225.0),   # 450,000 bytes both ways for 2,000 lanes
    ],
)
def test_each_reader_finds_its_number(bench, name, want):
    load, obs = bench
    assert load(name)(obs(), None) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(n for n in NEW if n != "wire_bytes_per_sig.commit"))
def test_span_readers_give_none_without_the_nodes_spans(bench, name):
    """An untraced run, or a program without `trace.capture()` (the parent):
    the child ships no span, nothing is merged, the reading is missing."""
    load, obs = bench
    assert load(name)(obs(node_spans=None), None) is None
    assert load(name)(obs(node_spans=[]), None) is None
    assert load(name)(obs(merged=False), None) is None


@pytest.mark.parametrize(
    "grpc",
    [None, ({}, {}), ({"unary_calls": 1}, {"unary_calls": 9}), (GRPC0, dict(GRPC0))],
    ids=["no-node-counters", "empty", "a-client-from-before-the-byte-counters", "nothing-sent"],
)
def test_the_counter_reader_gives_none_without_its_counters(bench, grpc):
    load, obs = bench
    assert load("wire_bytes_per_sig.commit")(obs(grpc=grpc), None) is None


def test_a_wrapped_ring_is_not_read(bench, monkeypatch):
    from cometbft_tpu.libs import trace

    load, obs = bench
    monkeypatch.setattr(trace, "dropped", lambda: 3)  # pushed out after the window opened
    assert obs().samples["wire_ops"] is None
    assert load("wire_ms.commit")(obs(), None) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_each_new_metric_is_in_the_benchmark_once_with_its_cell(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    unit, source, layer = NEW[name]
    entries = [m for m in bench["per_layer"] if m["name"] == name]
    assert len(entries) == 1
    cells = entries[0].pop("workloads")
    assert cells[0] == CELL, "later cells are appended (tests/test_multinode_cell_readers.py)"
    assert entries[0] == {"name": name, "unit": unit, "better": "lower", "source": source,
                          "layer": layer, "moves": "commit_verify_p50_ms"}
    assert os.path.isfile(os.path.join(BENCH, "layers", name + ".py"))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "valset-10000-sidecar", "cold-commits-sidecar", 1)
