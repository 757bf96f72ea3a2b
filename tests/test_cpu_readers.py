"""The six per-layer readers ISSUE 34 adds (`benchmarks/layers/`, helpers in
`benchmarks/cpulib.py`): each reads the `cpu` / `pcpu` a span of
`libs/trace.py` now carries, on a span list written by hand, and returns
None on the same spans without them (a program from before this PR: the
parent commit, whose traced runs the driver also makes with these files).
No chip, no process."""

from __future__ import annotations

import json
import os
import types

import pytest

from tests.test_sidecar_cell_readers import _node_op, _server_request, _sp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
CATCHUP = ["qa175-blocksync", "qa175-blocksync-load"]
COMMIT = ["commit10k-cold", "commit10k-cold-x4", "commit10k-sidecar", "commit10k-sidecar-4nodes"]
# name -> (unit, layer, moves, cells)
NEW = {
    "part_set_cpu_ms_per_height.catchup": ("ms", "callers", "catchup_heights_per_s", CATCHUP),
    "sync_cpu_ms_per_height.catchup": ("ms", "callers", "catchup_heights_per_s", CATCHUP),
    "recv_cpu_ms_per_height.catchup": ("ms", "p2p receive", "catchup_heights_per_s", CATCHUP),
    "interp_busy_pct.catchup": ("%", "callers", "catchup_heights_per_s", CATCHUP),
    "pack_cpu_ms.commit": ("ms", "device tier", "commit_verify_p50_ms", COMMIT),
    "wire_cpu_ms.commit": ("ms", "sidecar wire", "commit_verify_p50_ms", COMMIT[2:]),
}


def _cpu(span, cpu, pcpu):
    return {**span, "cpu": cpu, "pcpu": pcpu}


def _height(base_id, t, applied=True):
    """One synced height, 60 ms on the sync thread of which it ran 20 while
    the process used 57: `part_set` stood 30 ms and ran 5."""
    r = base_id
    return [
        _cpu(_sp(r, "blocksync.sync_one", t, t + 0.060, height=r, applied=applied), 0.020, 0.057),
        _cpu(_sp(r + 1, "blocksync.part_set", t + 0.005, t + 0.035, parent=r, root=r), 0.005, 0.029),
        _cpu(_sp(r + 2, "blocksync.apply", t + 0.040, t + 0.058, parent=r, root=r), 0.010, 0.017),
    ]


def _recv(i, t, cpu, thread="p2p-recv:aa"):
    s = _cpu(_sp(i, "p2p.recv_msg", t, t + 0.050, chan=0x40, bytes=311_000, packets=304), cpu, 0.049)
    return {**s, "thread": thread}


CATCHUP_RING = (
    _height(1, 1.0) + _height(11, 2.0) + _height(21, 3.0, applied=False)  # refused: no height
    + [_recv(40, 1.0, 0.009), _recv(41, 1.0, 0.011, "p2p-recv:bb"), _recv(42, 2.0, 0.010),
       _recv(43, 2.1, None)]  # the first of a session on its thread: no mark, no CPU
    + _height(51, 30.0)  # outside the window
)
COMMIT_RING = [
    # a clock that ticks at 10 ms, as the chip's machine has: 7 ms of work read 0, 10, 10
    _cpu(_sp(1, "device.pack", 1.0, 1.0100, lanes=8192), 0.0, 0.0100),
    _cpu(_sp(2, "device.pack", 2.0, 2.0200, lanes=8192), 0.010, 0.0200),
    _cpu(_sp(3, "device.pack", 3.0, 3.0080, lanes=8192), 0.010, 0.0100),
    _cpu(_sp(4, "device.pack", 4.0, 4.0075, lanes=8192), 0.010, 0.0100),
]


def _strip(spans):
    """The same spans as a program without `cpu` / `pcpu` records them."""
    return [{k: v for k, v in s.items() if k not in ("cpu", "pcpu")} for s in spans]


def _wire(cpu_call, cpu_request):
    """Three operations across the wire (tests/test_sidecar_cell_readers.py's)
    whose `grpc.call` and `sidecar.request` carry these CPU seconds."""
    def put(spans, name, cpus):
        seen = iter(cpus)
        return [_cpu(s, next(seen), 0.07) if s["name"] == name else s for s in spans]

    node = _node_op(1, 1.0, req=7) + _node_op(11, 2.0, req=9) + _node_op(21, 3.0, req=11)
    sidecar = (_server_request(1, 1.0, req=7) + _server_request(11, 2.0, req=9)
               + _server_request(21, 3.0, req=11))
    return put(node, "grpc.call", cpu_call), put(sidecar, "sidecar.request", cpu_request)


@pytest.fixture
def bench(monkeypatch):
    """(reader by metric name, obs over a ring) as run.py has them."""
    monkeypatch.syspath_prepend(BENCH)
    import harness
    import sidecarlib

    from cometbft_tpu.libs import trace

    monkeypatch.setattr(trace, "dropped", lambda: 0)

    def load(name):
        path = os.path.join(BENCH, "layers", name + ".py")
        return harness.load_by_path(path, "layer_" + name.replace(".", "_")).read

    def obs(ring, node_spans=None):
        monkeypatch.setattr(trace, "spans", lambda: ring)
        o = types.SimpleNamespace(window=(0.0, 20.0), samples={}, counters_before={},
                                  counters_after={})
        if node_spans is not None:
            o.samples.update(node_spans=node_spans, node_dropped=0)
            o.samples["wire_ops"] = sidecarlib.merge(o)
        return o

    return load, obs


@pytest.mark.parametrize(
    "name, ring, want",
    [
        ("part_set_cpu_ms_per_height.catchup", CATCHUP_RING, 7.5),  # 3 x 5 ms over 2 applied heights
        ("sync_cpu_ms_per_height.catchup", CATCHUP_RING, 20.0),     # the applied roots' own 20 ms
        ("recv_cpu_ms_per_height.catchup", CATCHUP_RING, 15.0),     # 9 + 11 + 10 ms, both threads
        ("interp_busy_pct.catchup", CATCHUP_RING, 95.0),            # 2 x 57 ms of 2 x 60
        ("pack_cpu_ms.commit", COMMIT_RING, 7.5),                   # the mean: a median would say 10
    ],
)
def test_each_reader_finds_its_number_and_none_without_cpu(bench, name, ring, want):
    load, obs = bench
    assert load(name)(obs(ring), None) == pytest.approx(want)
    assert load(name)(obs(_strip(ring)), None) is None, "the parent's spans: nothing to read"
    assert load(name)(obs([]), None) is None, "an untraced run"


def test_wire_cpu_is_the_two_threads_cpu_of_the_operations_that_own_a_hybrid_call(bench):
    load, obs = bench
    read = load("wire_cpu_ms.commit")
    node, sidecar = _wire([0.004, 0.005, 0.006], [0.006, 0.007, 0.008])
    assert read(obs(sidecar, node), None) == pytest.approx(12.0)  # mean of 10, 12, 14
    # an operation that joined another's dispatch owns no hybrid.call: left out
    joined = [s for s in sidecar if not (s["name"] == "hybrid.call" and s["root"] == 21)]
    assert read(obs(joined, node), None) == pytest.approx(11.0)
    # either process from before this PR, or an untraced run
    assert read(obs(_strip(sidecar), node), None) is None
    assert read(obs(sidecar, _strip(node)), None) is None
    assert read(obs(sidecar), None) is None
    # the wall-clock reader beside it reads the same operations, with or without
    assert load("wire_ms.commit")(obs(_strip(sidecar), _strip(node)), None) == pytest.approx(30.0)


def test_a_wrapped_ring_is_not_read(bench, monkeypatch):
    from cometbft_tpu.libs import trace

    load, obs = bench
    monkeypatch.setattr(trace, "dropped", lambda: 3)  # pushed out after the window opened
    for name, ring in (("sync_cpu_ms_per_height.catchup", CATCHUP_RING),
                       ("pack_cpu_ms.commit", COMMIT_RING)):
        assert load(name)(obs(ring), None) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_each_new_metric_is_in_the_benchmark_once_with_its_cells(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    unit, layer, moves, cells = NEW[name]
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry.pop("workloads")[:len(cells)] == cells, "a later cell is appended"
    assert entry == {"name": name, "unit": unit, "better": "lower", "source": "program_span",
                     "layer": layer, "moves": moves}
    assert os.path.isfile(os.path.join(BENCH, "layers", name + ".py"))
