"""The reduction from a trace to numbers, on small recorded traces:
`data/trace_synthetic.json` is written by hand so that every number below
can be checked on paper; `data/trace_tpu_cut.json`, where present, is a cut
of a real `--trace 1` run on a TPU v5e."""

import json
import os

import pytest

import trace_reduce as tr
from conftest import HERE

MS = 1e6  # the trace's clock is in nanoseconds


def load(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)


def test_interval_arithmetic():
    u = tr.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)])
    assert u == [(0, 3), (5, 8)]
    assert tr.total(u) == 6
    assert tr.complement(u, -1, 10) == [(-1, 0), (3, 5), (8, 10)]
    assert tr.intersect(u, [(2, 6)]) == [(2, 3), (5, 6)]
    assert tr.subtract(u, [(2, 6)]) == [(0, 2), (6, 8)]


def test_synthetic_trace():
    planes = load("trace_synthetic.json")
    out = tr.reduce(planes, window_s=0.100)
    # two programs of 20 ms and 10 ms; inside the first, ops cover 15 of the 20 ms
    assert out["chips"] == 1
    assert out["busy_s"] == pytest.approx(0.025)
    assert 1 - out["busy_s"] / out["window_s"] == pytest.approx(0.75)
    mods = out["modules"]
    assert mods["jit_verify_core"]["count"] == 2
    assert mods["jit_verify_core"]["total_s"] == pytest.approx(0.030)
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(0.020)]
    assert out["collective_s"] == pytest.approx(0.002)
    gaps = dict(out["idle_gaps"])
    assert gaps["within a program (between its operations)"] == pytest.approx(0.005)
    assert gaps["seam: pack+dispatch (before the call's program starts)"] == pytest.approx(0.004 + 0.003)
    assert gaps["seam: host_msm tail + collect (after the call's program ends)"] == pytest.approx(0.002 + 0.001)
    assert gaps["seam: call with no device program (host route)"] == pytest.approx(0.006)
    assert gaps["bench:verify_commit"] == pytest.approx(0.003 + 0.003 + 0.002 + 0.002)
    assert gaps["no benchmark span (between operations, fetch wait, reactor loop)"] == pytest.approx(0.016 + 0.004)


def test_module_name_match_is_the_verify_program():
    import layerlib

    class Run:
        trace = tr.reduce(load("trace_synthetic.json"), 0.1)

    assert layerlib.verify_module(Run) == (2, pytest.approx(0.030))
    assert layerlib.verify_device_ms(None, Run) == pytest.approx(15.0)


def test_tpu_cut_if_recorded():
    path = os.path.join(HERE, "data", "trace_tpu_cut.json")
    if not os.path.exists(path):
        pytest.skip("no recorded TPU cut")
    planes = load("trace_tpu_cut.json")
    out = tr.reduce(planes, window_s=1.0)
    assert out["chips"] >= 1 and out["busy_s"] > 0
    assert any(tr_name for tr_name in out["modules"] if "verify_core" in tr_name)
    assert out["device_ops"] and out["idle_gaps"]
