"""Multi-host fan-out tests (sidecar/fanout.py, round 15).

The FanoutBackend makes N sidecars (plus the local tier) look like ONE
wide VerifyBackend: width-weighted contiguous slices, concurrent dispatch,
exact bitmap reassembly, and one redistribution round before the
supervisor sees a failure.  These tests pin:

* the split arithmetic (weighted, contiguous, rounding absorbed);
* bitmap bit-identity against the host CPU backend, shard mix regardless;
* per-shard failure handling — error/wedge redistributes to survivors
  with zero wrong bits, all-dead raises, flips are caught by the
  supervisor's cross-check (never served);
* the width algebra the engine sizes from: fanout SUMS shard widths
  (shards verify concurrently), the supervisor takes the MAX across tiers
  (tiers are alternatives) and never dials a tripped tier for it;
* the real wire path: three shard-server OS processes behind one
  FanoutBackend client (the multi-process JAX mesh rig carries `slow`).
"""

import json
import os
import subprocess
import sys
import time

import pytest

from cometbft_tpu.crypto import ed25519
from cometbft_tpu.crypto.merkle import hash_from_byte_slices
from cometbft_tpu.sidecar.backend import CpuBackend
from cometbft_tpu.sidecar.fanout import FanoutBackend, build_fanout, fanout_peers

pytestmark = pytest.mark.fanout


def _signed_triples(n, tag=b"fanout", corrupt=()):
    pv = ed25519.gen_priv_key_from_secret(tag)
    pub = pv.pub_key().bytes()
    msgs = [b"%s-%d" % (tag, i) for i in range(n)]
    sigs = [pv.sign(m) for m in msgs]
    for i in corrupt:
        sigs[i] = sigs[i][:-1] + bytes([sigs[i][-1] ^ 1])
    return [pub] * n, msgs, sigs


class _StubShard:
    """Scriptable shard: fixed width, optional per-call failure plan."""

    name = "stub"

    def __init__(self, width=1, fail=0, wedge_s=0.0, flip=False):
        self.width = width
        self.fail = fail  # first N batch_verify calls raise
        self.wedge_s = wedge_s
        self.flip = flip
        self.calls = []
        self._cpu = CpuBackend()

    def mesh_width(self):
        return self.width

    def ping(self):
        return True

    def batch_verify(self, pubs, msgs, sigs):
        self.calls.append(len(pubs))
        if self.fail > 0:
            self.fail -= 1
            raise ConnectionError("stub: scripted failure")
        if self.wedge_s:
            time.sleep(self.wedge_s)
        if self.flip:
            return True, [True] * len(pubs)
        return self._cpu.batch_verify(pubs, msgs, sigs)

    def merkle_root(self, leaves):
        return self._cpu.merkle_root(leaves)


# -- split arithmetic --------------------------------------------------------


def test_split_weighted_contiguous():
    fan = FanoutBackend(
        [("a", _StubShard(4)), ("b", _StubShard(2)), ("c", _StubShard(1))],
        deadline_ms=1000,
    )
    fan.refresh_widths(dial=False)
    tasks = fan._split(0, 70, fan.shards)
    # Contiguous cover of [0, 70), in order.
    assert tasks[0][1] == 0 and tasks[-1][2] == 70
    for (_, _, hi), (_, lo2, _) in zip(tasks, tasks[1:]):
        assert hi == lo2
    sizes = {s.name: hi - lo for s, lo, hi in tasks}
    assert sizes == {"a": 40, "b": 20, "c": 10}


def test_split_drops_empty_slices_for_narrow_batches():
    fan = FanoutBackend(
        [("a", _StubShard(8)), ("b", _StubShard(8)), ("c", _StubShard(8))],
        deadline_ms=1000,
    )
    fan.refresh_widths(dial=False)
    tasks = fan._split(0, 2, fan.shards)
    assert sum(hi - lo for _, lo, hi in tasks) == 2
    assert all(hi > lo for _, lo, hi in tasks)  # no zero-lane dispatches


# -- bit-identity ------------------------------------------------------------


def test_bitmap_identical_to_cpu_backend_across_shard_mix():
    n = 97  # deliberately not a multiple of the width total
    pubs, msgs, sigs = _signed_triples(n, corrupt=(0, 17, 50, 96))
    want = CpuBackend().batch_verify(pubs, msgs, sigs)
    fan = FanoutBackend(
        [("a", _StubShard(4)), ("b", _StubShard(2)), ("c", _StubShard(1))],
        deadline_ms=5000,
    )
    got = fan.batch_verify(pubs, msgs, sigs)
    assert got == want
    assert got[0] is False and sum(got[1]) == n - 4
    # Every shard carried a slice.
    assert all(s.backend.calls for s in fan.shards)


# -- failure handling --------------------------------------------------------


def test_erroring_shard_slice_redistributed_to_survivors():
    n = 64
    pubs, msgs, sigs = _signed_triples(n, corrupt=(3,))
    want = CpuBackend().batch_verify(pubs, msgs, sigs)
    sick = _StubShard(2, fail=1)
    fan = FanoutBackend(
        [("ok", _StubShard(2)), ("sick", sick)], deadline_ms=5000
    )
    got = fan.batch_verify(pubs, msgs, sigs)
    assert got == want  # zero wrong bits after redistribution
    cn = fan.counters()
    assert cn["redistributions"] == 1
    assert cn["redistributed_sigs"] == 32  # the sick shard's whole slice
    assert cn["shards"]["sick"]["failures"] == 1
    assert cn["shards"]["sick"]["down"] is True  # cooling down


def test_wedged_shard_abandoned_within_deadline():
    n = 32
    pubs, msgs, sigs = _signed_triples(n)
    fan = FanoutBackend(
        [("ok", _StubShard(1)), ("wedged", _StubShard(1, wedge_s=30.0))],
        deadline_ms=400,
    )
    t0 = time.monotonic()
    ok, bits = fan.batch_verify(pubs, msgs, sigs)
    wall = time.monotonic() - t0
    assert ok is True and len(bits) == n and all(bits)
    # Two rounds (initial + redistribution), each bounded by the deadline;
    # the wedged thread is abandoned, never joined to completion.
    assert wall < 2 * 0.4 + 1.0
    assert fan.counters()["redistributions"] == 1


def test_all_shards_dead_raises_connection_error():
    pubs, msgs, sigs = _signed_triples(8)
    fan = FanoutBackend(
        [("a", _StubShard(1, fail=9)), ("b", _StubShard(1, fail=9))],
        deadline_ms=1000,
    )
    with pytest.raises(ConnectionError, match="unserved after redistribution"):
        fan.batch_verify(pubs, msgs, sigs)
    # Both now cooling down: the next dispatch has no healthy shard.
    with pytest.raises(ConnectionError, match="no healthy shard"):
        fan.batch_verify(pubs, msgs, sigs)


def test_cooled_down_shard_rejoins_after_cooldown():
    pubs, msgs, sigs = _signed_triples(16)
    sick = _StubShard(1, fail=1)
    fan = FanoutBackend(
        [("ok", _StubShard(1)), ("sick", sick)],
        deadline_ms=2000,
        cooldown_ms=400,
    )
    fan.batch_verify(pubs, msgs, sigs)
    assert fan.counters()["shards"]["sick"]["down"] is True
    time.sleep(0.5)
    fan.batch_verify(pubs, msgs, sigs)  # the dispatch IS the probe
    assert fan.counters()["shards"]["sick"]["down"] is False
    assert len(sick.calls) >= 2


def test_merkle_root_fails_over_across_shards():
    leaves = [b"leaf-%d" % i for i in range(9)]
    fan = FanoutBackend(
        [("sick", _StubShard(1)), ("ok", _StubShard(1))], deadline_ms=1000
    )
    fan.shards[0].backend.merkle_root = _raise_oserror
    assert fan.merkle_root(leaves) == hash_from_byte_slices(leaves)
    assert fan.counters()["shards"]["sick"]["failures"] == 1


def _raise_oserror(_leaves):
    raise OSError("stub: merkle down")


# -- chaos on one shard ------------------------------------------------------


def test_chaos_error_on_one_shard_redistributes_with_exact_bits():
    from cometbft_tpu.sidecar.chaos import ChaosBackend

    n = 48
    pubs, msgs, sigs = _signed_triples(n, corrupt=(7, 40))
    want = CpuBackend().batch_verify(pubs, msgs, sigs)
    chaotic = ChaosBackend(_StubShard(1), "error:1.0", seed=5)
    fan = FanoutBackend(
        [("ok", _StubShard(1)), ("chaos", chaotic)], deadline_ms=5000
    )
    # Skip the dial probe: chaos would already fail the ping and bench the
    # shard before its first slice — this test wants the DISPATCH to hit it.
    fan.refresh_widths(dial=False)
    fan._probed = True
    assert fan.batch_verify(pubs, msgs, sigs) == want
    cn = fan.counters()
    assert cn["redistributions"] == 1 and chaotic.injected["error"] >= 1


def test_chaos_flip_is_caught_by_supervisor_crosscheck():
    """A shard that false-accepts poisons the fanout's merged bitmap; the
    supervised chain's cross-check must catch it and serve the anchor's
    answer — a flipped fleet never ships a wrong bit."""
    from cometbft_tpu.sidecar.chaos import ChaosBackend
    from cometbft_tpu.sidecar.supervisor import ResilientBackend

    n = 32
    pubs, msgs, sigs = _signed_triples(n, corrupt=(2, 30))
    want = CpuBackend().batch_verify(pubs, msgs, sigs)
    flipper = ChaosBackend(_StubShard(1), "flip:1.0", seed=1)
    fan = FanoutBackend(
        [("a", _StubShard(1)), ("flip", flipper)], deadline_ms=5000
    )
    sup = ResilientBackend(
        [("fanout", fan), ("cpu", CpuBackend())],
        crosscheck="full",
        retries=0,
        backoff_ms=1,
    )
    try:
        assert sup.batch_verify(pubs, msgs, sigs) == want
        assert sup.counters_["crosscheck_catches"] >= 1
    finally:
        sup.close()


# -- width algebra -----------------------------------------------------------


def test_fanout_width_is_sum_of_shards():
    fan = FanoutBackend(
        [("a", _StubShard(4)), ("b", _StubShard(2)), ("c", _StubShard(1))],
        deadline_ms=1000,
    )
    fan.refresh_widths(dial=False)
    assert fan.mesh_width() == 7
    assert fan.shard_widths() == {"a": 4, "b": 2, "c": 1}


def test_supervisor_width_sums_through_fanout_tier():
    from cometbft_tpu.sidecar.supervisor import ResilientBackend

    fan = FanoutBackend(
        [("a", _StubShard(4)), ("b", _StubShard(4))], deadline_ms=1000
    )
    fan.refresh_widths(dial=False)
    sup = ResilientBackend(
        [("fanout", fan), ("cpu", CpuBackend())], crosscheck="off"
    )
    try:
        # MAX across tiers, and the fanout tier's contribution is the SUM
        # of its shards — the fleet's chips all verify concurrently.
        assert sup.mesh_width() == 8
    finally:
        sup.close()


def test_supervisor_width_caches_reads_and_never_dials_tripped_tier():
    """Satellite lock, both halves: a width-read ERROR on a live tier
    serves the cached width (the tier must not vanish from the estimate),
    while a TRIPPED tier is excluded entirely — and, critically, is never
    dialed just to read its width."""
    from cometbft_tpu.sidecar.supervisor import ResilientBackend

    class _Booby:
        name = "booby"
        width_reads = 0
        width_errors = False

        def mesh_width(self):
            type(self).width_reads += 1
            if type(self).width_errors:
                raise ConnectionError("booby: width read failed")
            return 16

        def batch_verify(self, pubs, msgs, sigs):
            raise ConnectionError("booby: down")

        def merkle_root(self, leaves):
            raise ConnectionError("booby: down")

    sup = ResilientBackend(
        [("booby", _Booby()), ("cpu", CpuBackend())],
        crosscheck="off",
        retries=0,
        backoff_ms=1,
        breaker_threshold=1,
        breaker_cooldown_ms=60000,
    )
    try:
        assert sup.mesh_width() == 16  # healthy: read and cached
        _Booby.width_errors = True
        assert sup.mesh_width() == 16  # read errors: cache serves
        pubs, msgs, sigs = _signed_triples(4)
        sup.batch_verify(pubs, msgs, sigs)  # trips the booby tier
        assert sup.tiers[0].state == "open"
        reads = _Booby.width_reads
        # Tripped: excluded from the estimate AND never dialed for it.
        assert sup.mesh_width() == 1
        assert _Booby.width_reads == reads
    finally:
        sup.close()


def test_engine_cap_and_rate_model_scale_through_fanout(monkeypatch):
    """Acceptance lock: the engine's auto merge cap and dispatch-wall rate
    model must grow through the fleet's COMBINED width, re-reading rates
    when the width moves (refresh_cap invalidates the cached model)."""
    from cometbft_tpu.sidecar.engine import VerificationEngine
    from cometbft_tpu.sidecar.supervisor import ResilientBackend

    monkeypatch.delenv("CMTPU_COALESCE_MAX", raising=False)
    monkeypatch.setenv("CMTPU_DEV_RATE", "10.0")
    monkeypatch.setenv("CMTPU_DEV_OVERHEAD_MS", "3.0")
    a, b = _StubShard(1), _StubShard(1)
    fan = FanoutBackend([("a", a), ("b", b)], deadline_ms=1000)
    fan.refresh_widths(dial=False)
    sup = ResilientBackend(
        [("fanout", fan), ("cpu", CpuBackend())], crosscheck="off"
    )
    eng = VerificationEngine(sup, hold_ms=0)
    try:
        cap0 = eng.refresh_cap()
        assert cap0 >= 16384 * 2
        rate, overhead = eng._rate_model()
        # The fanout tier heads the chain, so it prices the dispatch:
        # per-chip rate x fleet width.
        assert rate == pytest.approx(10.0 * fan.mesh_width())
        assert overhead == pytest.approx(3.0)
        # Two more hosts join the fleet (widths learned from Ping).
        a.width, b.width = 8, 8
        fan.refresh_widths(dial=False)
        assert eng.refresh_cap() == 16384 * sup.mesh_width() >= 16384 * 16
        rate2, _ = eng._rate_model()  # cache invalidated by the growth
        assert rate2 == pytest.approx(10.0 * 16)
    finally:
        eng.close()
        sup.close()


# -- env wiring --------------------------------------------------------------


def test_fanout_peers_parsing(monkeypatch):
    monkeypatch.setenv("CMTPU_FANOUT_PEERS", " 10.0.0.1:7777, 10.0.0.2:7777 ,")
    assert fanout_peers() == ["10.0.0.1:7777", "10.0.0.2:7777"]
    monkeypatch.delenv("CMTPU_FANOUT_PEERS")
    assert fanout_peers() == [] and build_fanout() is None


def test_build_chain_heads_with_fanout_tier(monkeypatch):
    from cometbft_tpu.sidecar import supervisor

    monkeypatch.setenv("CMTPU_FANOUT_PEERS", "127.0.0.1:1,127.0.0.2:1")
    monkeypatch.delenv("CMTPU_FAULTS", raising=False)
    tiers = supervisor.build_chain()
    names = [n for n, _ in tiers]
    assert names[0] == "fanout" and names[-1] == "cpu"
    fan = tiers[0][1]
    assert len(fan.shards) >= 2  # one GrpcBackend shard per peer
    fan.close()


def test_fanout_gauges_sample_the_active_chain():
    """fanout_* node gauges: zero with no fleet, live counters once the
    active backend chain carries a fanout tier — and the sampler never
    constructs or dials anything (it walks `backend_mod._backend` only)."""
    from cometbft_tpu.libs.metrics import Registry
    from cometbft_tpu.node.node import Node
    from cometbft_tpu.sidecar import backend as backend_mod
    from cometbft_tpu.sidecar.supervisor import ResilientBackend

    reg = Registry(namespace="cmt")
    Node._register_fanout_metrics(reg)
    old = backend_mod._backend
    try:
        backend_mod._backend = None
        assert "cmt_fanout_shards 0" in reg.render()

        fan = FanoutBackend(
            [("a", _StubShard(4)), ("b", _StubShard(2, fail=1))],
            deadline_ms=5000,
        )
        sup = ResilientBackend(
            [("fanout", fan), ("cpu", CpuBackend())], crosscheck="off"
        )
        backend_mod._backend = sup
        pubs, msgs, sigs = _signed_triples(16)
        sup.batch_verify(pubs, msgs, sigs)
        text = reg.render()
        assert "cmt_fanout_shards 2" in text
        assert "cmt_fanout_width 6" in text
        assert "cmt_fanout_dispatches 1" in text
        assert "cmt_fanout_redistributions 1" in text
        assert "cmt_fanout_shards_down 1" in text
        sup.close()
    finally:
        backend_mod._backend = old


# -- real processes ----------------------------------------------------------


def _spawn_shard(width: int):
    here = os.path.dirname(os.path.abspath(__file__))
    env = {
        k: v
        for k, v in os.environ.items()
        if k != "XLA_FLAGS"
    }
    return subprocess.Popen(
        [sys.executable, os.path.join(here, "fanout_shard_worker.py"), str(width)],
        stdin=subprocess.PIPE,
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        env=env,
    )


def test_three_process_fleet_end_to_end():
    """Integration: three real shard-server processes behind one
    FanoutBackend client — the v2 chunk-stream wire path, width learning
    via Ping, weighted split, and exact reassembly, all for real."""
    from cometbft_tpu.sidecar.service import GrpcBackend

    procs = [_spawn_shard(w) for w in (4, 2, 2)]
    fan = None
    try:
        addrs = []
        for p in procs:
            line = p.stdout.readline()
            assert line, p.stderr.read().decode(errors="replace")[-2000:]
            addrs.append(json.loads(line)["addr"])
        fan = FanoutBackend(
            [
                (f"proc{i}", GrpcBackend(addr, timeout_s=60))
                for i, addr in enumerate(addrs)
            ],
            deadline_ms=60000,
        )
        n = 96
        pubs, msgs, sigs = _signed_triples(n, corrupt=(1, 47, 95))
        want = CpuBackend().batch_verify(pubs, msgs, sigs)
        got = fan.batch_verify(pubs, msgs, sigs)
        assert got == want
        assert fan.mesh_width() == 8  # 4 + 2 + 2, learned over the wire
        cn = fan.counters()
        assert cn["redistributions"] == 0
        assert {s["width"] for s in cn["shards"].values()} == {4, 2}
        # Kill one server: the next dispatch redistributes and still
        # answers bit-exactly from the two survivors.
        procs[0].kill()
        procs[0].wait()
        got2 = fan.batch_verify(pubs, msgs, sigs)
        assert got2 == want
        assert fan.counters()["redistributions"] >= 1
    finally:
        if fan is not None:
            fan.close()
        for p in procs:
            if p.poll() is None:
                try:
                    p.stdin.close()
                except OSError:
                    pass
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait()


@pytest.mark.slow
def test_multiprocess_jax_mesh_serves_as_one_shard():
    """The tentpole's deepest rig: a TWO-PROCESS JAX mesh (gloo
    coordinator, 4 virtual devices each) serving as ONE fanout shard via
    multihost_worker's serve mode — the fleet client sees an 8-wide shard
    and bit-exact answers verified collectively across both processes."""
    import socket

    from cometbft_tpu.sidecar.service import GrpcBackend

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    coord = free_port()
    here = os.path.dirname(os.path.abspath(__file__))
    worker = os.path.join(here, "multihost_worker.py")
    env = {
        k: v
        for k, v in os.environ.items()
        if k != "XLA_FLAGS"
    }

    def spawn(pid, side):
        return subprocess.Popen(
            [
                sys.executable,
                worker,
                str(pid),
                "2",
                str(coord),
                "serve",
                str(side),
            ],
            stdin=subprocess.PIPE,
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            env=env,
        )

    # The leader binds + announces the follower rendezvous port BEFORE its
    # slow jax init, so the follower is spawned against a live listener
    # (a pre-picked port would race every other port-0 test on the box).
    procs = [spawn(0, 0)]
    fan = None
    try:
        line = procs[0].stdout.readline()
        assert line, procs[0].stderr.read().decode(errors="replace")[-3000:]
        side = json.loads(line)["side_port"]
        procs.append(spawn(1, side))
        line = procs[0].stdout.readline()
        assert line, procs[0].stderr.read().decode(errors="replace")[-3000:]
        rec = json.loads(line)
        assert rec["width"] == 8  # 2 processes x 4 virtual devices
        fan = FanoutBackend(
            [("mesh", GrpcBackend(rec["addr"], timeout_s=540))],
            deadline_ms=540000,
        )
        n = 64
        pubs, msgs, sigs = _signed_triples(n, tag=b"mh-serve", corrupt=(9,))
        want = CpuBackend().batch_verify(pubs, msgs, sigs)
        assert fan.batch_verify(pubs, msgs, sigs) == want
        assert fan.mesh_width() == 8
    finally:
        if fan is not None:
            fan.close()
        for p in procs:
            try:
                p.stdin.close()
            except OSError:
                pass
        for p in procs:
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
