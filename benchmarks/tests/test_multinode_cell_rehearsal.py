"""`commit10k-sidecar-4nodes` at the rehearsal's 4 nodes of 96 validators on
a CPU (`--platform cpu`, MULTINODE.md's line): never a result (`correct:
false`), but every check of the kind runs: four node children verify the same
commits through four connections and never import JAX, the lanes they sent
are the server's `lanes_in`, copies that queue together share a dispatch
under a cap of 128 lanes, the check height's four bitmaps equal
`answers_alone`, every traced operation is joined to one `sidecar.request` of
its own connection, and the traced line holds the new metrics."""

import json
import re

from conftest import result_line, run_bench

SEED = str(2**31 + 5)  # seeds go a little past 32 signed bits
ARGS = ("--workload", "commit10k-sidecar-4nodes", "--seed", SEED, "--seconds", "3", "--platform", "cpu")
ENV = {"CMTPU_VERIFY_CACHE_MAX": "256", "CMTPU_COALESCE_MAX": "128"}
NEW = {"requests_per_dispatch.commit", "dedup_lane_share_pct.commit", "engine_merge_ms.commit"}
TAKEN = {"wire_ms.commit", "wire_encode_ms.commit", "wire_decode_ms.commit", "queue_wait_ms.commit",
         "node_outside_seam_ms.commit", "wire_bytes_per_sig.commit", "lanes_per_dispatch.commit",
         "device_lane_share_pct.commit", "compiles_in_window.commit"}


def _problems(lines):
    return [ln[len("NOT CORRECT: "):] for ln in lines if ln.startswith("NOT CORRECT: ")]


def test_the_four_node_cell_end_to_end():
    rc, lines, err = run_bench(*ARGS, "--trace", "0", env=ENV)
    assert rc == 0, err
    res = result_line(lines)
    assert res is not None and res["correct"] is False and res["failed"] == 0
    assert set(res["metrics"]) == {"commit_verify_p50_ms", "commit_verify_p95_ms", "setup_s"}
    # every check ran and only what a CPU cannot give is held against the run
    assert all("device" in p or "rehearsal" in p for p in _problems(lines)), _problems(lines)
    assert any(ln.startswith("check height: node 0 refused at lane") and "problems 0" in ln for ln in lines)
    assert any("flipped commit refused" in ln and "problems 0" in ln for ln in lines)
    window = next(ln for ln in lines if ln.startswith("window: "))
    heights, ops, offered, ran = map(int, re.search(
        r"window: (\d+) heights, (\d+) operations.*lanes offered (\d+), the hybrid tier ran (\d+)",
        window).groups())
    assert ops == 4 * heights and offered == ops * 96 and heights * 96 <= ran <= offered
    served = json.loads(next(ln for ln in lines if ln.startswith("sidecar: stopping, server "))[26:])
    assert served["connections_accepted"] == 4 and served["streams_failed"] == 0
    assert served["lanes_in"] >= offered
    sup = json.loads(next(ln for ln in lines if ln.startswith("sidecar: stopping, supervisor "))[30:])
    assert sup["chain"] == ["hybrid", "cpu"] and sup["degraded_calls"] == 0
    assert any(ln.startswith("nodes: chains [['grpc', 'cpu'], ['grpc', 'cpu'], ['grpc', 'cpu'], "
                             "['grpc', 'cpu']]") for ln in lines)
    assert not any("child" in p for p in _problems(lines)), "each child's JAX-free exit was seen"


def test_the_traced_line_holds_the_merges_metrics():
    rc, lines, err = run_bench(*ARGS, "--trace", "1", env=ENV)
    assert rc == 0, err
    res = result_line(lines)
    assert res is not None and "breakdown" in res
    assert NEW | TAKEN <= set(res["metrics"]), sorted((NEW | TAKEN) - set(res["metrics"]))
    assert all("device" in p or "rehearsal" in p for p in _problems(lines)), _problems(lines)
    assert res["metrics"]["requests_per_dispatch.commit"]["value"] > 1
    assert 0 < res["metrics"]["dedup_lane_share_pct.commit"]["value"] < 75
    assert res["metrics"]["lanes_per_dispatch.commit"]["value"] == 96.0, "a merged dispatch runs the commit once"
    report = next(ln for ln in lines if ln.startswith("traced heights "))
    assert "most unique lanes 96," in report and "joined requests an operation [1]" in report
    assert not report.split("merged with lanes over the cap ")[1].startswith("0;")


def test_a_pool_that_fits_the_nodes_caches_is_caught():
    rc, lines, err = run_bench(*ARGS, "--trace", "0", env={"CMTPU_COALESCE_MAX": "128"})
    assert rc == 0, err  # the shipped 131,072-triple cache
    assert any("not whole_miss alone" in p for p in _problems(lines))
