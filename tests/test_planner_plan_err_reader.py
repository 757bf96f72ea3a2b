"""`planner_plan_err_pct.commit`, the benchmark's reading of the hybrid
planner's model error (`benchmarks/layers/planner_plan_err_pct.commit.py`
over `HybridBackend.counters()`), on recorded counters. No JAX, no chip."""

from __future__ import annotations

import json
import os
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
NAME = "planner_plan_err_pct.commit"

# counters() of the hybrid before and after a window, as a run records them
BEFORE = {"split_calls": 24, "share_changes": 1, "plan_abs_err_ms": 212.4, "wall_ms": 1890.0}


@pytest.fixture
def read(monkeypatch):
    monkeypatch.syspath_prepend(BENCH)  # as run.py has it: harness and spanlib lie there
    import harness

    path = os.path.join(BENCH, "layers", NAME + ".py")
    return harness.load_by_path(path, "layer_planner_plan_err_pct_commit").read


def _obs(before, after):
    return types.SimpleNamespace(
        window=(0.0, 20.0),
        counters_before={"hybrid": before},
        counters_after={"hybrid": after},
    )


@pytest.mark.parametrize(
    "before, after, want",
    [
        # 160 calls of ~70 ms, each predicted to within ~1.4 ms
        (BEFORE, {**BEFORE, "plan_abs_err_ms": 436.4, "wall_ms": 13090.0}, 2.0),
        # a tier from before the window's first call: everything is growth
        ({}, {"plan_abs_err_ms": 50.0, "wall_ms": 200.0}, 25.0),
        # no call reached the device in the window: nothing to read
        (BEFORE, dict(BEFORE), None),
        # a program from before the counters (the keys are absent): nothing to read
        ({"split_calls": 24}, {"split_calls": 184}, None),
    ],
    ids=["grows", "from-nothing", "does-not-grow", "key-absent"],
)
def test_plan_err_reader_on_recorded_counters(read, before, after, want):
    got = read(_obs(before, after), None)
    assert got == (None if want is None else pytest.approx(want))


def test_the_metric_is_in_the_benchmark_once_and_names_its_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert entry == {
        "name": NAME, "unit": "%", "better": "lower", "source": "program_counter",
        "layer": "hybrid planner", "moves": "commit_verify_p95_ms",
        "workloads": ["commit10k-cold", "commit10k-cold-x4", "commit10k-sidecar"],
    }
    assert [m["name"] for m in bench["per_layer"]].count(NAME) == 1
