"""Byzantine actor layer on the simnet harness (round 19).

Adversaries attack the gossip surface the node itself exposes
(``set_broadcast`` / transport send-taps) — never forked consensus
logic — so every defence exercised here is the production defence:
VoteSet conflict detection, the evidence pool's detect→pending→commit
pipeline, the stall watchdog, and span catchup.  Quick tests ride
tier-1 under ``-m simnet``; the churn soak and the 100-node acceptance
run carry ``slow``.
"""

import json
import os
import subprocess
import sys

import pytest

from cometbft_tpu.simnet.byzantine import make_actor
from cometbft_tpu.simnet.clock import SimClock
from cometbft_tpu.simnet.scenario import Scenario, default_spec, run_scenario
from cometbft_tpu.simnet.transport import SimConn, SimNetwork

pytestmark = pytest.mark.simnet


def _digest(report):
    """Replay-compare key: per-height hashes + the evidence trail."""
    return {
        "hashes": [report["block_hashes"][h] for h in sorted(report["block_hashes"])],
        "evidence_heights": report["evidence"]["committed_heights"],
        "detections": report["evidence"]["detections"],
    }


# -- equivocation → evidence pipeline ----------------------------------------


def test_equivocator_evidence_detected_and_committed():
    spec = default_spec(
        seed=11,
        validators=4,
        blocks=8,
        zones=2,
        jitter_ms=5.0,
        byzantine=[{"role": "equivocator", "node": 1, "from_s": 5.0, "until_s": 60.0}],
        max_sim_s=600.0,
    )
    scen = Scenario(spec)
    report = scen.run()
    assert report["ok"], report
    assert report["safety_ok"] and not report["conflicting_heights"]
    assert report["counters"].get("byz_equivocations", 0) >= 1
    ev = report["evidence"]
    # Detected by honest VoteSets, committed inside a block, bounded lag.
    assert ev["detections"] >= 1
    assert ev["committed_count"] >= 1 and ev["committed_heights"]
    assert ev["detect_to_commit_s"] is not None
    assert ev["detect_to_commit_s"] < 120.0
    # No false convictions: the committed evidence names the one
    # equivocating validator by address.
    byz_addr = scen.nodes[1].pv.address()
    blk = scen.nodes[0].cs.block_store.load_block(ev["committed_heights"][0])
    assert blk.evidence
    assert all(e.vote_a.validator_address == byz_addr for e in blk.evidence)


def test_equivocator_only_partitioned_invisible_until_heal():
    # Camps = the partition sides; honest nodes inside one side see a
    # single consistent vote stream, so detection can only happen once
    # gossip crosses the healed boundary.
    heal_s = 45.0
    report = run_scenario(
        seed=3,
        validators=10,
        blocks=12,
        zones=2,
        jitter_ms=5.0,
        partitions=[{"at_s": 20.0, "heal_s": heal_s, "fraction": 0.5}],
        byzantine=[{
            "role": "equivocator", "node": 3, "from_s": 10.0,
            "until_s": 50.0, "only_partitioned": True,
        }],
        max_sim_s=900.0,
    )
    assert report["ok"], report
    assert report["safety_ok"]
    assert report["counters"].get("byz_equivocations", 0) >= 1
    ev = report["evidence"]
    assert ev["detections"] >= 1
    assert ev["first_detection"]["sim_s"] >= heal_s
    assert ev["committed_count"] >= 1
    assert ev["detect_to_commit_s"] is not None and ev["detect_to_commit_s"] < 120.0


def test_withholder_slows_but_chain_recovers():
    report = run_scenario(
        seed=5,
        validators=4,
        blocks=10,
        zones=2,
        jitter_ms=5.0,
        byzantine=[{
            "role": "withholder", "node": 2, "from_s": 10.0,
            "until_s": 40.0, "delay_s": 0.0,
        }],
        max_sim_s=900.0,
    )
    assert report["ok"], report
    assert report["safety_ok"]
    assert report["counters"].get("byz_withheld", 0) >= 1
    rec = report["recovery"]
    assert rec["applicable"]
    assert rec["recovered_at_s"] is not None, rec


def test_flooder_is_griefing_not_safety():
    report = run_scenario(
        seed=9,
        validators=4,
        blocks=8,
        zones=2,
        jitter_ms=5.0,
        byzantine=[{
            "role": "flooder", "node": 1, "from_s": 5.0,
            "until_s": 45.0, "rate_hz": 20.0,
        }],
        max_sim_s=600.0,
    )
    assert report["ok"], report
    assert report["safety_ok"] and not report["conflicting_heights"]
    assert report["counters"].get("byz_flooded", 0) >= 1
    # Replayed duplicates must never surface as evidence: same vote twice
    # is idempotent, only CONFLICTING pairs are punishable.
    assert report["evidence"]["committed_count"] == 0


def test_bad_byzantine_specs_rejected():
    scen = Scenario(default_spec(validators=4, blocks=1))
    with pytest.raises(ValueError, match="unknown byzantine role"):
        make_actor(scen, {"role": "time_traveler", "node": 1})
    with pytest.raises(ValueError, match="node 0 is the hash-reference"):
        make_actor(scen, {"role": "equivocator", "node": 0})
    with pytest.raises(ValueError, match="unknown byzantine keys"):
        make_actor(scen, {"role": "withholder", "node": 1, "rate_hz": 5.0})
    with pytest.raises(ValueError, match="cannot also be a late-joiner"):
        Scenario(default_spec(
            validators=4, blocks=1,
            byzantine=[{"role": "equivocator", "node": 2}],
            joins=[{"node": 2, "at_s": 10.0}],
        )).run()


# -- determinism --------------------------------------------------------------


def test_same_seed_byzantine_rerun_bit_identical():
    spec = dict(
        seed=21,
        validators=6,
        blocks=6,
        zones=2,
        jitter_ms=8.0,
        partitions=[{"at_s": 15.0, "heal_s": 30.0, "fraction": 0.5}],
        byzantine=[{"role": "equivocator", "node": 2, "from_s": 5.0, "until_s": 40.0}],
        max_sim_s=600.0,
    )
    a = run_scenario(**spec)
    b = run_scenario(**spec)
    assert a["ok"] and b["ok"]
    assert _digest(a) == _digest(b)
    assert a["evidence"] == b["evidence"]
    assert a["commit_times"] == b["commit_times"]


_XPROC_SCRIPT = """
import json, sys
from cometbft_tpu.simnet.scenario import run_scenario
report = run_scenario(
    seed=7, validators=8, blocks=5, zones=2, jitter_ms=5.0,
    partitions=[{"at_s": 10.0, "heal_s": 25.0, "fraction": 0.5}],
    byzantine=[{"role": "equivocator", "node": 2, "from_s": 5.0,
                "until_s": 40.0, "only_partitioned": True}],
    max_sim_s=600.0,
)
assert report["ok"] and report["safety_ok"], report
print(json.dumps({
    "hashes": [report["block_hashes"][h] for h in sorted(report["block_hashes"])],
    "evidence_heights": report["evidence"]["committed_heights"],
    "first_detection": report["evidence"]["first_detection"],
    "commit_times": report["commit_times"],
}, sort_keys=True))
"""


def test_cross_process_byzantine_determinism():
    # Same seed in two fresh interpreters (fresh hash randomization, fresh
    # import order) must replay the identical chain AND the identical
    # evidence trail — the repro.json contract for byzantine schedules.
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["CMTPU_BACKEND"] = "cpu"
    outs = []
    for _ in range(2):
        proc = subprocess.run(
            [sys.executable, "-c", _XPROC_SCRIPT],
            capture_output=True, text=True, timeout=300, env=env,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        outs.append(json.loads(proc.stdout.strip().splitlines()[-1]))
    assert outs[0] == outs[1]
    assert outs[0]["evidence_heights"], outs[0]


# -- in-sim blocksync join ----------------------------------------------------


def test_blocksync_late_joiner_reaches_head():
    report = run_scenario(
        seed=5,
        validators=6,
        blocks=12,
        zones=2,
        jitter_ms=5.0,
        joins=[{"node": 5, "at_s": 40.0}],
        max_sim_s=900.0,
    )
    assert report["ok"], report
    assert report["stragglers"] == []
    assert report["counters"]["join_completions"] == 1
    assert report["counters"]["blocksync_served"] >= 1
    (jr,) = report["joins"]
    assert jr["node"] == 5
    # The join pulled real wire-framed blocks before consensus handoff.
    assert jr["synced_blocks"] >= 1
    assert jr["joined_s"] > jr["started_s"]


# -- transport send-tap (the adversary's wire hook) ---------------------------


def test_transport_send_tap_drop_dup_delay():
    clock = SimClock()
    net = SimNetwork(clock=clock, seed=1)
    a = SimConn(net, "a", "b", None)
    b = SimConn(net, "b", "a", None)
    a.peer, b.peer = b, a

    def drain():
        while clock.step():
            pass

    a.write(b"clean")
    drain()
    assert bytes(b._buf) == b"clean" and net.stats["tapped"] == 0
    b._buf.clear()

    net.set_send_tap("a", lambda dst, data: [])  # drop everything
    a.write(b"lost")
    drain()
    assert bytes(b._buf) == b"" and net.stats["tapped"] == 1

    # Duplicate with one delayed copy; extra delay rides the link clamp.
    net.set_send_tap("a", lambda dst, data: [(0.0, data), (0.5, data)])
    a.write(b"xx")
    drain()
    assert bytes(b._buf) == b"xxxx" and net.stats["tapped"] == 2
    assert clock.now() >= 0.5
    b._buf.clear()

    net.set_send_tap("a", None)  # tap removed: back to passthrough
    a.write(b"done")
    drain()
    assert bytes(b._buf) == b"done" and net.stats["tapped"] == 2


# -- soak + acceptance (slow) -------------------------------------------------


@pytest.mark.slow
def test_soak_200_blocks_churn_partitions_byzantine_join():
    report = run_scenario(
        seed=19,
        validators=50,
        blocks=200,
        zones=4,
        jitter_ms=10.0,
        partitions=[
            {"at_s": 120.0, "heal_s": 180.0, "fraction": 0.3},
            {"at_s": 700.0, "heal_s": 760.0, "fraction": 0.5},
        ],
        churn=[
            {"at_s": 250.0, "down_s": 60.0, "nodes": 5},
            {"at_s": 500.0, "down_s": 60.0, "nodes": 5},
            {"at_s": 900.0, "down_s": 60.0, "nodes": 5},
        ],
        byzantine=[
            {"role": "equivocator", "node": 7, "from_s": 650.0,
             "until_s": 800.0, "only_partitioned": True},
            {"role": "flooder", "node": 11, "from_s": 300.0,
             "until_s": 400.0, "rate_hz": 10.0},
        ],
        joins=[{"node": 49, "at_s": 400.0}],
        max_sim_s=3600.0,
    )
    assert report["ok"], {k: report[k] for k in (
        "ok", "height_node0", "heights_min", "stragglers", "safety_ok")}
    assert report["safety_ok"] and not report["conflicting_heights"]
    assert report["counters"]["join_completions"] == 1
    assert report["stragglers"] == []
    assert report["evidence"]["committed_count"] >= 1
    assert report["accel"] >= 3.0, report["accel"]


@pytest.mark.slow
def test_acceptance_100_nodes_equivocator_partition_rerun_identical():
    # ISSUE round-19 acceptance: 100-node sim, one equivocating validator
    # under partition+heal — evidence committed in a bounded window, zero
    # conflicting honest commits, and the same seed replays bit-identically.
    spec = dict(
        seed=23,
        validators=100,
        blocks=10,
        zones=4,
        jitter_ms=10.0,
        partitions=[{"at_s": 20.0, "heal_s": 45.0, "fraction": 0.5}],
        byzantine=[{"role": "equivocator", "node": 17, "from_s": 10.0,
                    "until_s": 50.0, "only_partitioned": True}],
        max_sim_s=900.0,
    )
    a = run_scenario(**spec)
    assert a["ok"], a
    assert a["safety_ok"] and not a["conflicting_heights"]
    ev = a["evidence"]
    assert ev["committed_count"] >= 1
    assert ev["detect_to_commit_s"] is not None and ev["detect_to_commit_s"] < 180.0
    b = run_scenario(**spec)
    assert _digest(a) == _digest(b)
