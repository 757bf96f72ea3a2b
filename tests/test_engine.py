"""Continuous-batching verification engine (sidecar/engine.py): strict
priority drain with a starvation escape hatch, deadline-aware dispatch
sizing off the hybrid rate model, seeded mixed-load starvation-freedom
(an ingress flood never delays a consensus triple past its deadline bound,
a poisoned ingress request never fails a consensus caller), class tagging
through the threadlocal, the SigBatcher engine path (the process engine,
or its own over a bare backend), the deadline-derived result timeout, and
the grow-only refresh_cap.
Seeded/deterministic, CPU-only."""

import random
import threading
import time

import pytest

from cometbft_tpu.crypto import ed25519, sigbatch
from cometbft_tpu.sidecar import backend as backend_mod
from cometbft_tpu.sidecar.backend import CpuBackend, VerifyBackend
from cometbft_tpu.sidecar.engine import (
    CLASS_BLOCKSYNC,
    CLASS_CONSENSUS,
    CLASS_INGRESS,
    CLASS_LIGHT,
    VerificationEngine,
    current_class,
    engine_of,
    submission_class,
)

pytestmark = pytest.mark.engine


@pytest.fixture(autouse=True)
def clean_cache():
    ed25519._verified.clear()
    yield
    ed25519._verified.clear()


def _synthetic(n, tag, poison=()):
    """Unique byte triples judged by the sig marker byte (no real crypto):
    \\x01 = valid lane, \\x00 = invalid lane, \\xee = poison (the marker
    backends below raise on it, the shape of a hostile entry that makes a
    tier choke)."""
    pubs = [(b"%s-p-%d" % (tag, i)).ljust(32, b"\x00") for i in range(n)]
    msgs = [b"%s-m-%d" % (tag, i) for i in range(n)]
    sigs = [
        (b"\xee" if i in poison else b"\x01")
        + (b"%s-s-%d" % (tag, i)).ljust(63, b"\x02")
        for i in range(n)
    ]
    return pubs, msgs, sigs


class _MarkerGate(VerifyBackend):
    """First call wedges the dispatcher so followers provably queue;
    verdicts come from the sig marker byte; poison markers raise."""

    name = "marker-gate"

    def __init__(self, wedge_first=True):
        self.release = threading.Event()
        self.calls = []  # batch sizes, in dispatch order
        self._first = wedge_first

    def batch_verify(self, pubs, msgs, sigs):
        self.calls.append(len(pubs))
        if self._first:
            self._first = False
            self.release.wait(10.0)
        if any(s[0] == 0xEE for s in sigs):
            raise ConnectionError("poisoned lane")
        bits = [s[0] == 1 for s in sigs]
        return all(bits), bits

    def merkle_root(self, leaves):
        raise NotImplementedError("verify-only marker backend")


# -- priority classes ---------------------------------------------------------


def test_consensus_class_outranks_queued_bulk():
    """Bulk ingress work queued FIRST must still drain AFTER a consensus
    request once the device frees up — strict priority, not FIFO."""
    gate = _MarkerGate()
    eng = VerificationEngine(gate, hold_ms=0, max_sigs=4, starvation_ms=0)
    try:
        head = eng.submit(*_synthetic(1, b"head"))
        while not gate.calls:
            time.sleep(0.001)
        bulk = [
            eng.submit(*_synthetic(3, b"bulk-%d" % i), klass=CLASS_INGRESS)
            for i in range(3)
        ]
        vote = eng.submit(
            *_synthetic(2, b"vote"), klass=CLASS_CONSENSUS, deadline_ms=0
        )
        gate.release.set()
        assert head.result(10.0) == (True, [True])
        assert vote.result(10.0) == (True, [True, True])
        for f in bulk:
            assert f.result(10.0) == (True, [True] * 3)
        # Dispatch #2 must be the consensus request alone: the 4-sig cap
        # excludes the 3-sig bulk heads once the 2-sig vote is in.
        assert gate.calls[1] == 2, gate.calls
        c = eng.counters()
        assert c["classes"]["consensus"]["admitted"] == 1
        assert c["classes"]["ingress"]["admitted"] == 3
        assert c["classes"]["consensus"]["dispatched_sigs"] == 2
    finally:
        gate.release.set()
        eng.close()


def test_starvation_hatch_promotes_stale_light_work():
    """A light-class request older than starvation_ms jumps ahead of
    fresher consensus work — lowest class, but never parked forever."""
    gate = _MarkerGate()
    eng = VerificationEngine(gate, hold_ms=0, max_sigs=3, starvation_ms=30)
    try:
        head = eng.submit(*_synthetic(1, b"head"))
        while not gate.calls:
            time.sleep(0.001)
        lamp = eng.submit(*_synthetic(3, b"lamp"), klass=CLASS_LIGHT)
        time.sleep(0.05)  # let the light request go stale
        votes = [
            eng.submit(
                *_synthetic(2, b"v-%d" % i),
                klass=CLASS_CONSENSUS,
                deadline_ms=0,
            )
            for i in range(2)
        ]
        gate.release.set()
        assert head.result(10.0) == (True, [True])
        assert lamp.result(10.0) == (True, [True] * 3)
        for f in votes:
            assert f.result(10.0) == (True, [True, True])
        # The stale light request fills dispatch #2 alone (3-sig cap);
        # without promotion the consensus pair would have gone first.
        assert gate.calls[1] == 3, gate.calls
        c = eng.counters()
        assert c["classes"]["light"]["starvation_promotions"] == 1
    finally:
        gate.release.set()
        eng.close()


def test_deadline_caps_merged_dispatch_size():
    """A queued consensus request's deadline caps how much bulk work the
    next dispatch may carry, via the inner backend's rate model; without a
    deadline the same queue merges into one pod-scale dispatch."""
    gate = _MarkerGate()
    gate._dev_rate = 1.0  # 1 sig/ms: a 100-sig dispatch costs ~100 ms
    gate._n_dev = 1
    gate._dev_overhead = 0.0
    eng = VerificationEngine(gate, hold_ms=0, max_sigs=16384, starvation_ms=0)
    try:
        head = eng.submit(*_synthetic(1, b"head"))
        while not gate.calls:
            time.sleep(0.001)
        vote = eng.submit(
            *_synthetic(2, b"vote"), klass=CLASS_CONSENSUS, deadline_ms=20
        )
        bulk = eng.submit(*_synthetic(100, b"bulk"), klass=CLASS_INGRESS)
        gate.release.set()
        assert head.result(10.0) == (True, [True])
        assert vote.result(10.0) == (True, [True, True])
        assert bulk.result(10.0) == (True, [True] * 100)
        # 100 bulk sigs can't fit a <=20 ms budget at 1 sig/ms: the vote
        # dispatches alone, the bulk request right after.
        assert gate.calls[1:] == [2, 100], gate.calls
    finally:
        gate.release.set()
        eng.close()

    # Contrast arm: no deadline -> one merged dispatch carries both.
    gate2 = _MarkerGate()
    gate2._dev_rate = 1.0
    gate2._n_dev = 1
    gate2._dev_overhead = 0.0
    eng2 = VerificationEngine(gate2, hold_ms=0, max_sigs=16384, starvation_ms=0)
    try:
        head = eng2.submit(*_synthetic(1, b"head2"))
        while not gate2.calls:
            time.sleep(0.001)
        vote = eng2.submit(
            *_synthetic(2, b"vote2"), klass=CLASS_CONSENSUS, deadline_ms=0
        )
        bulk = eng2.submit(*_synthetic(100, b"bulk2"), klass=CLASS_INGRESS)
        gate2.release.set()
        assert head.result(10.0)[0]
        assert vote.result(10.0)[0]
        assert bulk.result(10.0)[0]
        assert gate2.calls[1:] == [102], gate2.calls
    finally:
        gate2.release.set()
        eng2.close()


# -- mixed-load property: starvation freedom + cross-class isolation ----------


class _SimDevice(VerifyBackend):
    """Simulated device: fixed dispatch overhead + per-sig cost, verdicts
    from the marker byte, poison markers raise (merged AND solo — the
    guilty caller must error, batchmates must not)."""

    name = "sim-device"

    def __init__(self, overhead_ms=2.0, per_sig_us=10.0):
        self.overhead_ms = overhead_ms
        self.per_sig_us = per_sig_us
        self.calls = []
        self._lock = threading.Lock()

    def batch_verify(self, pubs, msgs, sigs):
        with self._lock:
            self.calls.append(len(pubs))
        time.sleep(self.overhead_ms / 1000.0 + len(pubs) * self.per_sig_us / 1e6)
        if any(s[0] == 0xEE for s in sigs):
            raise ConnectionError("poisoned lane")
        bits = [s[0] == 1 for s in sigs]
        return all(bits), bits

    def merkle_root(self, leaves):
        raise NotImplementedError


def test_mixed_load_starvation_freedom_and_poison_isolation():
    """Seeded property run: under a 4-thread ingress flood (some requests
    poisoned), every consensus submission resolves correctly within its
    deadline bound, and no consensus caller ever sees an ingress poison
    error. The bound is the engine's admission guarantee: one in-flight
    dispatch + the deadline-capped next dispatch, with slack for a loaded
    CI host."""
    rng = random.Random(0xE14)
    sim = _SimDevice(overhead_ms=2.0, per_sig_us=10.0)
    eng = VerificationEngine(sim, hold_ms=0, max_sigs=64, starvation_ms=100)
    deadline_ms = 250.0
    flood_threads = 4
    floods_per_thread = 25
    poisoned = ingress_errors = 0
    plock = threading.Lock()
    stop = threading.Event()

    def flood(tid):
        nonlocal poisoned, ingress_errors
        frng = random.Random(rng.random() * 1e9 + tid)
        for i in range(floods_per_thread):
            poison = {3} if frng.random() < 0.2 else ()
            fut = eng.submit(
                *_synthetic(8, b"fl-%d-%d" % (tid, i), poison=poison),
                klass=CLASS_INGRESS,
            )
            try:
                ok, bits = fut.result(20.0)
                assert not poison
                assert ok and len(bits) == 8
            except ConnectionError:
                assert poison, "clean ingress request got the poison error"
                with plock:
                    ingress_errors += 1
            if poison:
                with plock:
                    poisoned += 1

    threads = [
        threading.Thread(target=flood, args=(t,)) for t in range(flood_threads)
    ]
    try:
        for t in threads:
            t.start()
        latencies = []
        failures = []
        for i in range(30):
            t0 = time.perf_counter()
            fut = eng.submit(
                *_synthetic(2, b"vote-%d" % i),
                klass=CLASS_CONSENSUS,
                deadline_ms=deadline_ms,
            )
            try:
                ok, bits = fut.result(20.0)
            except BaseException as e:  # noqa: BLE001
                failures.append(e)
                continue
            latencies.append((time.perf_counter() - t0) * 1000.0)
            if not (ok and bits == [True, True]):
                failures.append((ok, bits))
            time.sleep(0.002)
        stop.set()
        for t in threads:
            t.join(60.0)
        assert not failures, f"consensus caller failed under flood: {failures[:3]}"
        assert poisoned > 0, "seeded flood never drew a poisoned request"
        assert ingress_errors == poisoned
        # Starvation freedom: every consensus admission within its bound.
        bound_ms = 2 * deadline_ms
        assert max(latencies) < bound_ms, (
            f"consensus admission {max(latencies):.1f} ms "
            f"exceeded {bound_ms:.0f} ms under ingress flood"
        )
        c = eng.counters()
        assert c["classes"]["consensus"]["admitted"] == 30
        assert c["classes"]["ingress"]["admitted"] == flood_threads * floods_per_thread
    finally:
        stop.set()
        eng.close()


# -- class tagging ------------------------------------------------------------


def test_submission_class_threadlocal_scopes_and_restores():
    assert current_class() == CLASS_BLOCKSYNC  # untagged default
    with submission_class(CLASS_INGRESS):
        assert current_class() == CLASS_INGRESS
        with submission_class(CLASS_LIGHT):
            assert current_class() == CLASS_LIGHT
        assert current_class() == CLASS_INGRESS
    assert current_class() == CLASS_BLOCKSYNC

    eng = VerificationEngine(_MarkerGate(wedge_first=False), hold_ms=0)
    try:
        with submission_class(CLASS_LIGHT):
            eng.submit(*_synthetic(2, b"tag")).result(10.0)
        assert eng.counters()["classes"]["light"]["admitted"] == 1
    finally:
        eng.close()


def test_tagging_is_per_thread_not_global():
    seen = {}
    with submission_class(CLASS_INGRESS):
        t = threading.Thread(target=lambda: seen.update(k=current_class()))
        t.start()
        t.join(10.0)
    assert seen["k"] == CLASS_BLOCKSYNC, "threadlocal leaked across threads"


# -- SigBatcher engine path ---------------------------------------------------


def test_sigbatch_rides_engine_consensus_class(monkeypatch):
    """With an engine-backed chain installed, vote admission submits
    consensus-class straight to the engine (no private window thread),
    keeps bit-identical verdicts, populates the verified cache for valid
    triples only, and reports sharing through the engine future."""
    eng = VerificationEngine(CpuBackend(), hold_ms=2)
    old_backend = backend_mod.set_backend(eng)
    old_batcher = sigbatch.set_batcher(None)
    try:
        b = sigbatch.SigBatcher(window_ms=2)
        pvs = [ed25519.gen_priv_key_from_secret(b"eng-sb-%d" % i) for i in range(4)]
        pubs = [pv.pub_key() for pv in pvs]
        msgs = [b"vote-%d" % i for i in range(4)]
        sigs = [pv.sign(m) for pv, m in zip(pvs, msgs)]
        sigs[2] = b"\x07" * 64  # bad signature: a False lane, not an error
        bits = b.verify_many(pubs, msgs, sigs)
        assert bits == [True, True, False, True]
        c = b.counters()
        assert c["dispatches"] == 1 and c["dispatched_sigs"] == 4
        assert engine_of(backend_mod._backend) is eng
        assert eng.counters()["classes"]["consensus"]["admitted"] == 1
        # Valid triples (and only those) are now cache hits.
        assert (pubs[0].bytes(), sigs[0], msgs[0]) in ed25519._verified
        assert (pubs[2].bytes(), sigs[2], msgs[2]) not in ed25519._verified
        # The process engine served it: the batcher built none of its own.
        assert b._own is None
    finally:
        sigbatch.set_batcher(old_batcher)
        backend_mod.set_backend(old_backend)
        eng.close()


def test_sigbatch_legacy_path_serves_bare_backends():
    """A bare (engine-less) backend is served through the batcher's own
    engine over it: its window as the hold, its cap, the same verdicts, and
    concurrent admissions merged into shared dispatches."""
    cpu = CpuBackend()
    calls = []
    orig = cpu.batch_verify
    cpu.batch_verify = lambda p, m, s: calls.append(len(p)) or orig(p, m, s)
    old_backend = backend_mod.set_backend(cpu)
    old_batcher = sigbatch.set_batcher(None)
    try:
        assert engine_of(backend_mod._backend) is None
        b = sigbatch.SigBatcher(window_ms=200)
        pvs = [ed25519.gen_priv_key_from_secret(b"legacy-sb-%d" % i) for i in range(4)]
        msgs = [b"legacy-vote-%d" % i for i in range(4)]
        sigs = [pv.sign(m) for pv, m in zip(pvs, msgs)]
        sigs[3] = b"\x07" * 64  # bad signature: a False lane, not an error
        start = threading.Barrier(4)
        got = [None] * 4

        def admit(i):
            start.wait()
            got[i] = b.verify_one(pvs[i].pub_key(), msgs[i], sigs[i])

        threads = [threading.Thread(target=admit, args=(i,)) for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(10.0)
        assert not any(t.is_alive() for t in threads)
        assert got == [True, True, True, False]
        own = b._own
        assert own is not None and own.inner is cpu
        assert (own.hold_ms, own.max_sigs) == (200, b.max_sigs)
        assert calls == [4], "four admissions, one merged dispatch"
        c = b.counters()
        assert c["dispatches"] == 4 and c["batched"] == 4 and c["fallbacks"] == 0
        assert own.counters()["coalesced_dispatches"] == 1
        # A second admission reuses the batcher's engine.
        msg = b"legacy-vote-late"
        assert b.verify_one(pvs[0].pub_key(), msg, pvs[0].sign(msg)) is True
        assert b._own is own
    finally:
        sigbatch.set_batcher(old_batcher)
        backend_mod.set_backend(old_backend)


# -- satellite: deadline-derived result timeout -------------------------------


def test_sigbatch_result_timeout_derived_from_deadline(monkeypatch):
    monkeypatch.delenv("CMTPU_DEADLINE_MS", raising=False)
    monkeypatch.delenv("CMTPU_RETRIES", raising=False)
    assert sigbatch.SigBatcher(window_ms=2).result_timeout_s == 30.0
    monkeypatch.setenv("CMTPU_DEADLINE_MS", "0")
    assert sigbatch.SigBatcher(window_ms=2).result_timeout_s == 30.0
    # deadline 500 ms x (2 retries + 1) x 3 tiers = 4.5 s, not 30 s.
    monkeypatch.setenv("CMTPU_DEADLINE_MS", "500")
    monkeypatch.setenv("CMTPU_RETRIES", "2")
    assert sigbatch.SigBatcher(window_ms=2).result_timeout_s == 4.5
    # Floor: a tiny deadline still leaves a sane wait.
    monkeypatch.setenv("CMTPU_DEADLINE_MS", "10")
    monkeypatch.setenv("CMTPU_RETRIES", "0")
    assert sigbatch.SigBatcher(window_ms=2).result_timeout_s == 1.0


# -- grow-only refresh_cap -----------------------------------------------------


class _WidthStub(VerifyBackend):
    name = "width-stub"

    def __init__(self, width):
        self.width = width
        self._cpu = CpuBackend()

    def batch_verify(self, pubs, msgs, sigs):
        return self._cpu.batch_verify(pubs, msgs, sigs)

    def merkle_root(self, leaves):
        return self._cpu.merkle_root(leaves)

    def mesh_width(self):
        return self.width


def test_refresh_cap_grows_never_shrinks(monkeypatch):
    """A Ping-advertised wider remote mesh grows the auto cap; a narrower
    reading never shrinks it; caps pinned by argument or by
    `CMTPU_COALESCE_MAX` never move."""
    monkeypatch.delenv("CMTPU_COALESCE_MAX", raising=False)
    stub = _WidthStub(1)
    eng = VerificationEngine(stub, hold_ms=0)
    try:
        initial = eng.max_sigs
        assert initial % 16384 == 0
        stub.width = (initial // 16384) * 4  # the remote pod is wider
        assert eng.refresh_cap() == 16384 * stub.width
        assert eng.max_sigs == 16384 * stub.width
        assert eng.counters()["max_sigs"] == eng.max_sigs
        stub.width = 1  # narrower later reading must not shrink
        grown = eng.max_sigs
        assert eng.refresh_cap() == grown and eng.max_sigs == grown
    finally:
        eng.close()

    pinned = VerificationEngine(_WidthStub(8), hold_ms=0, max_sigs=99)
    try:
        assert pinned.refresh_cap() == 99 and pinned.max_sigs == 99
    finally:
        pinned.close()

    monkeypatch.setenv("CMTPU_COALESCE_MAX", "4096")
    env_pinned = VerificationEngine(_WidthStub(8), hold_ms=0)
    try:
        assert env_pinned.refresh_cap() == 4096 and env_pinned.max_sigs == 4096
    finally:
        env_pinned.close()


# -- counters shape (dashboards read through) ---------------------------------


def test_counters_keep_legacy_keys_and_add_classes():
    eng = VerificationEngine(_MarkerGate(wedge_first=False), hold_ms=0)
    try:
        eng.submit(*_synthetic(2, b"cnt")).result(10.0)
        c = eng.counters()
        for key in (
            "requests", "dispatches", "coalesced_dispatches",
            "batched_requests", "coalesced_sigs", "dedup_sigs",
            "fallback_splits", "queue_depth", "max_sigs", "coalesce_ratio",
            "queue_wait_p50_ms", "queue_wait_p95_ms",
        ):
            assert key in c, key
        for cname in ("consensus", "blocksync", "ingress", "light"):
            cc = c["classes"][cname]
            for key in (
                "admitted", "dispatched_sigs", "starvation_promotions",
                "p95_us",
            ):
                assert key in cc, (cname, key)
        assert c["classes"]["blocksync"]["admitted"] == 1  # untagged default
    finally:
        eng.close()
