"""`commit10k-sidecar` at the rehearsal's 96 validators on a CPU (`--platform
cpu`): never a result (`correct: false`), but every check of the kind runs:
the node child verifies through the wire and never imports JAX, the lanes it
sent are the lanes the sidecar's hybrid tier counted, a commit reaches the
planner as one call, and the traced line holds the wire's metrics."""

import json
import re

from conftest import result_line, run_bench

SEED = str(2**31 + 5)  # seeds go a little past 32 signed bits
ARGS = ("--workload", "commit10k-sidecar", "--seed", SEED, "--seconds", "2", "--platform", "cpu")
WIRE = {"wire_ms.commit", "wire_encode_ms.commit", "wire_decode_ms.commit",
        "queue_wait_ms.commit", "node_outside_seam_ms.commit", "wire_bytes_per_sig.commit"}


def _problems(lines):
    return [ln[len("NOT CORRECT: "):] for ln in lines if ln.startswith("NOT CORRECT: ")]


def test_the_sidecar_cell_end_to_end():
    rc, lines, err = run_bench(*ARGS, "--trace", "0", env={"CMTPU_VERIFY_CACHE_MAX": "256"})
    assert rc == 0, err
    res = result_line(lines)
    assert res is not None and res["correct"] is False
    assert set(res["metrics"]) == {"commit_verify_p50_ms", "commit_verify_p95_ms", "setup_s"}
    # every check ran and only what a CPU cannot give is held against the run
    assert all("device" in p or "rehearsal" in p for p in _problems(lines)), _problems(lines)
    assert any("flipped commit refused" in ln and "problems 0" in ln for ln in lines)
    window = next(ln for ln in lines if ln.startswith("window: "))
    ops, lanes = map(int, re.search(r"window: (\d+) operations.*lanes dispatched (\d+)", window).groups())
    assert ops > 0 and lanes == ops * 96, "the sidecar's hybrid tier counted the node's lanes"
    served = json.loads(next(ln for ln in lines if ln.startswith("sidecar: stopping, server "))[26:])
    assert served["lanes_in"] >= lanes and served["streams_failed"] == 0
    sup = json.loads(next(ln for ln in lines if ln.startswith("sidecar: stopping, supervisor "))[30:])
    assert sup["chain"] == ["hybrid", "cpu"] and sup["degraded_calls"] == 0
    assert any(ln.startswith("node: chain ['grpc', 'cpu']") for ln in lines)
    assert not any("child" in p for p in _problems(lines)), "the child's JAX-free exit was seen"


def test_the_traced_line_holds_the_wires_metrics():
    rc, lines, err = run_bench(*ARGS, "--trace", "1", env={"CMTPU_VERIFY_CACHE_MAX": "256"})
    assert rc == 0, err
    res = result_line(lines)
    assert res is not None and "breakdown" in res
    assert WIRE <= set(res["metrics"]), sorted(WIRE - set(res["metrics"]))
    assert res["metrics"]["lanes_per_dispatch.commit"]["value"] == 96.0, "one planned call a commit"
    # 32 + ~122 + 64 bytes a triple and their field headers, one way; a byte a lane back
    assert 220 < res["metrics"]["wire_bytes_per_sig.commit"]["value"] < 260
    assert res["metrics"]["wire_ms.commit"]["value"] > res["metrics"]["queue_wait_ms.commit"]["value"] > 0


def test_a_pool_that_fits_the_nodes_cache_is_caught():
    rc, lines, err = run_bench(*ARGS, "--trace", "0")  # the shipped 131,072-triple cache
    assert rc == 0, err
    assert any("hit the cache" in p for p in _problems(lines))
