"""The readers ISSUE 32 adds for `commit10k-sidecar-4nodes`
(`benchmarks/layers/`, helpers in `benchmarks/multinodelib.py`) and the join
BY CONNECTION, on recorded spans of three processes and recorded counters:
two nodes whose calls overlap and whose request ids are equal each get their
own `sidecar.request`; a request that rode another's dispatch is given that
dispatch's spans; each reader finds its number and returns None where the
program (the parent commit) or the run has nothing for it; the readers of
`commit10k-sidecar` read the joined entries unchanged. ISSUE 33: a ring
recorded by a real engine in which three requests joined the dispatch in
flight reads as the benchmark's files (none edited) expect. No chip, no process."""

from __future__ import annotations

import json
import os
import threading
import time
import types

import pytest

from tests.test_sidecar_cell_readers import _node_op, _sp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
CELL = "commit10k-sidecar-4nodes"
NEW = {
    "requests_per_dispatch.commit": ("requests", "program_counter", "higher"),
    "dedup_lane_share_pct.commit": ("%", "program_counter", "higher"),
    "engine_merge_ms.commit": ("ms", "program_span", "lower"),
}
APPENDED = [
    "wire_ms.commit", "wire_encode_ms.commit", "wire_decode_ms.commit", "queue_wait_ms.commit",
    "node_outside_seam_ms.commit", "wire_bytes_per_sig.commit", "verify_device_ms.commit",
    "device_sigs_per_ms.commit", "lanes_per_dispatch.commit", "device_lane_share_pct.commit",
    "pack_ms.commit", "compiles_in_window.commit",
]


def _with(spans, **attrs):
    """The spans with `attrs` set on the call or the request among them."""
    return [{**s, "attrs": {**s["attrs"], **attrs}} if s["name"] in ("grpc.call", "sidecar.request")
            else s for s in spans]


def _request_of(base_id, t, req, conn, head_dispatch=None, merged=True):
    """The sidecar's side of one node's call at one height. Node A's (the
    head of a merged dispatch) holds the `engine.dispatch` and what is under
    it; node B's holds its own decode, queue wait and encode only."""
    r = base_id
    spans = [
        _sp(r, "sidecar.request", t + 0.014, t + 0.089, req=req, conn=conn, lanes=100),
        _sp(r + 1, "sidecar.decode", t + 0.014, t + 0.019, parent=r, root=r, seq=0),
        _sp(r + 2, "engine.queue_wait", t + 0.019, t + 0.028, parent=r, root=r),
        _sp(r + 8, "sidecar.encode", t + 0.0880, t + 0.0885, parent=r, root=r),
    ]
    if head_dispatch:
        spans += [
            _sp(r + 3, "engine.dispatch", t + 0.0281, t + 0.087, parent=r, root=r, **head_dispatch),
            _sp(r + 5, "hybrid.call", t + 0.035, t + 0.085, parent=r + 3, root=r, n=100),
            _sp(r + 6, "device.pack", t + 0.036, t + 0.040, parent=r + 5, root=r, lanes=80),
        ]
    if head_dispatch and merged:
        spans += [
            _sp(r + 4, "engine.merge", t + 0.0282, t + 0.0342, parent=r + 3, root=r, phase="pack"),
            _sp(r + 7, "engine.merge", t + 0.0855, t + 0.0865, parent=r + 3, root=r, phase="slice"),
        ]
    return spans


MERGED = {"requests": 2, "lanes": 200, "unique": 100, "dedup": 100, "klass": "blocksync"}
LONE = {"requests": 1, "lanes": 100, "unique": 100, "klass": "blocksync"}
# Two nodes in lock-step: equal request ids, calls that overlap in time, ports of their own.
NODE_A = _with(_node_op(1, 1.0, req=7), port=40001) + _with(_node_op(11, 2.0, req=9), port=40001)
NODE_B = _with(_node_op(1, 1.001, req=7), port=40002) + _with(_node_op(11, 2.001, req=9), port=40002)
SIDECAR = (
    # the first height: both requests queued together, one dispatch, under node A's request
    _request_of(100, 1.0, req=7, conn=40001, head_dispatch=MERGED)
    + _request_of(200, 1.0, req=7, conn=40002)
    # the second: each dispatched alone, node B's a millisecond after node A's
    + _request_of(300, 2.0, req=9, conn=40001, head_dispatch=LONE, merged=False)
    + _request_of(400, 2.001, req=9, conn=40002, head_dispatch=LONE, merged=False)
)
ENGINE0 = {"requests": 10, "dispatches": 10, "dedup_sigs": 0, "max_sigs": 128}
ENGINE1 = {"requests": 410, "dispatches": 210, "dedup_sigs": 2_000_000, "max_sigs": 128}
SERVER0, SERVER1 = {"lanes_in": 100_000}, {"lanes_in": 4_100_000}


@pytest.fixture
def bench(monkeypatch):
    monkeypatch.syspath_prepend(BENCH)
    import harness
    import multinodelib

    from cometbft_tpu.libs import trace

    monkeypatch.setattr(trace, "spans", lambda: SIDECAR)
    monkeypatch.setattr(trace, "dropped", lambda: 0)

    def load(name):
        path = os.path.join(BENCH, "layers", name + ".py")
        return harness.load_by_path(path, "layer_" + name.replace(".", "_")).read

    def obs(nodes=(NODE_A, NODE_B), engine=(ENGINE0, ENGINE1), server=(SERVER0, SERVER1), joined=True):
        o = types.SimpleNamespace(
            window=(0.0, 20.0),
            samples={"nodes_spans": [{"spans": n, "dropped": 0} for n in nodes]} if nodes else {},
            counters_before={"engine": engine[0], **({"server": server[0]} if server else {})},
            counters_after={"engine": engine[1], **({"server": server[1]} if server else {})},
        )
        if joined:
            o.samples["wire_ops"] = multinodelib.join(o)
        return o

    return load, obs


def test_two_nodes_with_equal_request_ids_each_get_their_own_request(bench):
    _, obs = bench
    ops = obs().samples["wire_ops"]
    assert [(e["node_index"], e["op"]["id"]) for e in ops] == [(0, 1), (0, 11), (1, 1), (1, 11)]
    assert all(len(e["requests"]) == 1 for e in ops), "exactly one sidecar.request an operation"
    assert [e["requests"][0]["id"] for e in ops] == [100, 300, 200, 400]
    assert [e["requests"][0]["attrs"]["conn"] for e in ops] == [40001, 40001, 40002, 40002]
    # by time and `req` alone (the one-node rule) node A's call holds node B's request too
    a, b = ops[0], ops[2]
    call = next(s for s in a["node"] if s["name"] == "grpc.call")
    other = b["requests"][0]
    assert other["t0"] >= call["t0"] and other["t1"] <= call["t1"]
    assert other["attrs"]["req"] == call["attrs"]["req"]


def test_a_request_that_rode_anothers_dispatch_is_given_its_spans(bench):
    _, obs = bench
    ops = obs().samples["wire_ops"]
    names = [sorted(s["name"] for s in e["sidecar"]) for e in ops]
    assert names[0] == names[2] == sorted(
        ["sidecar.request", "sidecar.decode", "engine.queue_wait", "sidecar.encode", "engine.dispatch",
         "engine.merge", "engine.merge", "hybrid.call", "device.pack"])
    rode = ops[2]["sidecar"]
    assert {s["root"] for s in rode if s["name"] in ("sidecar.request", "engine.queue_wait")} == {200}
    assert {s["root"] for s in rode if s["name"] in ("engine.dispatch", "hybrid.call")} == {100}
    # a request dispatched alone keeps its own and nothing of the one before it
    assert {s["root"] for s in ops[3]["sidecar"]} == {400}
    assert names[3].count("hybrid.call") == 1 and "engine.merge" not in names[3]


@pytest.mark.parametrize(
    "name, want",
    [
        ("requests_per_dispatch.commit", 2.0),   # 400 requests over 200 dispatches
        ("dedup_lane_share_pct.commit", 50.0),   # 2,000,000 lanes saved of 4,000,000 received
        ("engine_merge_ms.commit", 7.0),         # one merged dispatch: 6 ms pack + 1 ms slice
    ],
)
def test_each_new_reader_finds_its_number(bench, name, want):
    load, obs = bench
    assert load(name)(obs(), None) == pytest.approx(want)


@pytest.mark.parametrize(
    "name, want",
    [
        ("wire_ms.commit", 30.0),               # an 80 ms call and the 50 ms hybrid.call that answered it
        ("wire_encode_ms.commit", 6.5),         # 2 x 3 ms in the node, 0.5 ms in the sidecar
        ("wire_decode_ms.commit", 6.0),         # 5 ms in the sidecar, 1 ms in the node
        ("queue_wait_ms.commit", 11.0),         # both engines: 2 + 9 ms
        ("node_outside_seam_ms.commit", 10.0),  # a 100 ms operation holding a 90 ms dispatch
    ],
)
def test_the_one_node_cells_readers_read_the_joined_entries_unchanged(bench, name, want):
    load, obs = bench
    assert load(name)(obs(), None) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(NEW))
def test_each_new_reader_gives_none_where_the_program_has_nothing_for_it(bench, monkeypatch, name):
    """The parent commit's counters (no `server` group, an engine without
    the keys), counters that did not grow, and an untraced run's empty ring."""
    from cometbft_tpu.libs import trace

    load, obs = bench
    monkeypatch.setattr(trace, "spans", lambda: [])
    assert load(name)(obs(engine=({}, {}), server=None, joined=False), None) is None
    assert load(name)(obs(engine=(ENGINE0, dict(ENGINE0)), server=(SERVER0, dict(SERVER0)),
                          joined=False), None) is None


def test_spans_that_do_not_name_the_connection_join_nothing(bench, monkeypatch):
    """A program from before `port` / `conn`: no operation is given a
    request, so no reader takes another node's spans for its own."""
    import multinodelib

    from cometbft_tpu.libs import trace

    _, obs = bench
    bare = [{**s, "attrs": {k: v for k, v in s["attrs"].items() if k != "port"}} for s in NODE_A]
    ops = obs(nodes=(bare,)).samples["wire_ops"]
    assert [len(e["requests"]) for e in ops] == [0, 0] and all(e["sidecar"] == [] for e in ops)
    assert multinodelib.join(obs(nodes=None, joined=False)) is None
    monkeypatch.setattr(trace, "dropped", lambda: 3)  # the ring wrapped inside the window
    assert multinodelib.join(obs(joined=False)) is None


def test_what_a_traced_run_says_of_its_heights(bench):
    import multinodelib

    _, obs = bench
    o = obs()
    o.samples["heights"] = [{"index": 0, "t_release": 0.99, "t_done": 1.2},
                            {"index": 1, "t_release": 1.99, "t_done": 2.2}]
    lines = multinodelib.height_report(o)
    assert lines[0].startswith("traced heights 2: dispatches a height {1: 1, 2: 1}")
    assert "most unique lanes 100, merged with lanes over the cap 1" in lines[0]
    assert "joined requests an operation [1]" in lines[0]
    assert lines[1].startswith("alone (2 operations") and lines[2].startswith("merged (2 operations")
    assert lines[3].startswith("merged dispatches 1: requests 2.00, lanes offered 200.00, unique 100.00")


@pytest.mark.parametrize("name", sorted(NEW))
def test_each_new_metric_is_in_the_benchmark_once_with_its_cell(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    unit, source, better = NEW[name]
    entries = [m for m in bench["per_layer"] if m["name"] == name]
    assert entries == [{"name": name, "unit": unit, "better": better, "source": source,
                        "layer": "batch seam and engine", "moves": "commit_verify_p50_ms",
                        "workloads": [CELL]}]
    assert os.path.isfile(os.path.join(BENCH, "layers", name + ".py"))


@pytest.mark.parametrize("name", APPENDED)
def test_the_cell_is_appended_to_the_metrics_it_took(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry["workloads"][-1] == CELL and entry["workloads"].count(CELL) == 1


def test_the_cell_and_its_configuration_are_in_the_benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]  # later cells follow it
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "valset-10000-sidecar-4nodes", "cold-commits-4nodes", 1)
    (config,) = [c for c in bench["configs"] if c["name"] == cell["config"]]
    with open(os.path.join(ROOT, config["file"])) as f:
        body = json.load(f)
    assert config["name"] == body["name"] == cell["config"] and config["reduced"] == body["reduced"] == []
    assert body["source"] == config["source"] and len(config["source"]) <= 200
    assert body["validators"] == 10000 and body["nodes"] == 4 and len(body["guarantees"]) == 7
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert traffic["kind"] == "commit_stream_4nodes" and traffic["nodes"] == 4
    assert (traffic["pool_commits"], traffic["arrival_skew_ms"], traffic["trace_seconds"]) == (16, 40, 1.0)
    assert (traffic["warm_min_ops"], traffic["warm_quiet_ops"], traffic["warm_max_ops"]) == (24, 12, 96)
    for m in bench["end_to_end"]:
        if m["name"].startswith("commit_verify_"):
            assert m["workloads"][-1] == CELL


# -- what the benchmark reads of a height answered by one dispatch (ISSUE 33) --------

JOIN_LANES, JOIN_CAP, JOIN_NODES = 100, 128, 4


@pytest.fixture
def joined_height(monkeypatch):
    """One height through a real engine under `trace.capture()`: node 0's
    request is dispatched alone and held in flight while the other three
    nodes' copies are submitted, each inside a `sidecar.request` span of its
    own connection, as the server opens them. Gives (obs, the ring)."""
    monkeypatch.syspath_prepend(BENCH)
    import multinodelib

    from cometbft_tpu.libs import trace
    from cometbft_tpu.sidecar.backend import VerifyBackend
    from cometbft_tpu.sidecar.engine import VerificationEngine

    class Held(VerifyBackend):
        name = "held"
        in_flight, go = threading.Event(), threading.Event()

        def batch_verify(self, pubs, msgs, sigs):
            with trace.span("hybrid.call", n=len(pubs)):
                self.in_flight.set()
                assert self.go.wait(30)
                return True, [True] * len(pubs)

    columns = [bytes([i]) * 32 for i in range(JOIN_LANES)]
    submitted = [threading.Event() for _ in range(JOIN_NODES)]

    def connection(k: int):
        with trace.span("sidecar.request", method="batch_verify", req=7, conn=40001 + k,
                        lanes=JOIN_LANES):
            fut = eng.submit(list(columns), list(columns), list(columns))
            submitted[k].set()
            assert fut.result(30) == (True, [True] * JOIN_LANES)

    trace.clear()
    eng = VerificationEngine(Held(), hold_ms=0.0, max_sigs=JOIN_CAP, starvation_ms=0.0)
    before = eng.counters()
    t_open = time.perf_counter()
    try:
        with trace.capture():
            threads = [threading.Thread(target=connection, args=(k,)) for k in range(JOIN_NODES)]
            threads[0].start()
            assert Held.in_flight.wait(10)
            for k in range(1, JOIN_NODES):
                threads[k].start()
                assert submitted[k].wait(10)
            Held.go.set()
            for t in threads:
                t.join(30)
        after = eng.counters()
    finally:
        Held.go.set()
        eng.close()
    ring = trace.spans()
    # each node's side: an operation whose `grpc.call` holds its connection's request
    nodes = []
    for k in range(JOIN_NODES):
        (r,) = [s for s in ring if s["name"] == "sidecar.request" and s["attrs"]["conn"] == 40001 + k]
        t0, t1 = r["t0"] - 0.003, r["t1"] + 0.003
        nodes.append([
            _sp(1, "validation.verify_commit", t0, t1),
            _sp(2, "batch.dispatch", t0 + 0.001, t1 - 0.001, parent=1, root=1),
            _sp(3, "engine.queue_wait", t0 + 0.001, t0 + 0.0015, parent=2, root=1),
            _sp(4, "grpc.call", t0 + 0.002, t1 - 0.002, parent=2, root=1, req=7, port=40001 + k,
                lanes=JOIN_LANES),
        ])
    lanes_in = JOIN_NODES * JOIN_LANES
    obs = types.SimpleNamespace(
        window=(t_open - 1.0, time.perf_counter() + 1.0),  # the nodes' calls begin before the requests
        samples={"nodes_spans": [{"spans": n, "dropped": 0} for n in nodes]},
        counters_before={"engine": before, "server": {"lanes_in": 0}},
        counters_after={"engine": after, "server": {"lanes_in": lanes_in}},
    )
    obs.samples["wire_ops"] = multinodelib.join(obs)
    yield obs, ring
    trace.clear()


def test_the_ring_of_a_height_answered_by_one_dispatch(joined_height):
    _, ring = joined_height
    (dispatch,) = [s for s in ring if s["name"] == "engine.dispatch"]
    attrs = dispatch["attrs"]
    assert (attrs["requests"], attrs["lanes"], attrs["unique"], attrs["joined"]) == (
        JOIN_NODES, JOIN_NODES * JOIN_LANES, JOIN_LANES, JOIN_NODES - 1)
    merges = [s for s in ring if s["name"] == "engine.merge"]
    assert [(s["attrs"]["phase"], s["parent"]) for s in merges] == [("slice", dispatch["id"])]
    requests = {s["attrs"]["conn"]: s for s in ring if s["name"] == "sidecar.request"}
    for conn, r in requests.items():
        names = sorted(s["name"] for s in ring if s["root"] == r["id"] and s["id"] != r["id"])
        if conn == 40001:  # the one dispatched: the chain's spans hang under its request
            assert names == ["engine.dispatch", "engine.merge", "engine.queue_wait", "hybrid.call"]
        else:
            assert names == ["engine.join"], "it never stood in the queue"


def test_the_cells_readers_read_a_height_answered_by_one_dispatch(joined_height):
    import harness

    obs, _ = joined_height
    ops = obs.samples["wire_ops"]
    assert [e["node_index"] for e in ops] == list(range(JOIN_NODES))
    assert all(len(e["requests"]) == 1 for e in ops), "exactly one sidecar.request an operation"
    assert [e["requests"][0]["attrs"]["conn"] for e in ops] == [40001 + k for k in range(JOIN_NODES)]
    # a joined request is given no other request's dispatch: no `hybrid.call` of its own
    assert [sum(s["name"] == "hybrid.call" for s in e["sidecar"]) for e in ops] == [1, 0, 0, 0]

    def read(name):
        path = os.path.join(BENCH, "layers", name + ".py")
        return harness.load_by_path(path, "layer_" + name.replace(".", "_")).read(obs, None)

    assert read("requests_per_dispatch.commit") == pytest.approx(4.0)
    assert read("dedup_lane_share_pct.commit") == pytest.approx(75.0)
    assert 0 < read("engine_merge_ms.commit") < 1000
    assert read("wire_ms.commit") is not None, "read over the operation that owns a hybrid.call"
    assert read("queue_wait_ms.commit") is not None
    generator = harness.load_by_path(
        os.path.join(BENCH, "generators", "commit_stream_4nodes.py"), "generator_commit_stream_4nodes")
    assert generator._trace_problems(obs) == []
