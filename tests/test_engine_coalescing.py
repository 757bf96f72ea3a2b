"""The verification engine's coalescing (sidecar/engine.py): concurrent
requests merge into single dispatches with correct per-request bitmap
slicing, a failed coalesced dispatch falls back to per-request retries
(no cross-request poisoning), and chaos faults in one request's lane never
flip a batchmate's verdict.  Seeded/deterministic, CPU-only — part of the
`chaos` tier-1 group."""

import threading
import time

import pytest

from cometbft_tpu.crypto import ed25519
from cometbft_tpu.sidecar import backend as backend_mod
from cometbft_tpu.sidecar.backend import CpuBackend, VerifyBackend
from cometbft_tpu.sidecar.chaos import ChaosBackend
from cometbft_tpu.sidecar.engine import HOLD_MS, VerificationEngine
from cometbft_tpu.sidecar.supervisor import ResilientBackend

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def clean_cache():
    ed25519._verified.clear()
    yield
    ed25519._verified.clear()


def _signed(n, tag=b"sched"):
    pvs = [ed25519.gen_priv_key_from_secret(tag + b"-%d" % i) for i in range(n)]
    pubs = [pv.pub_key().bytes() for pv in pvs]
    msgs = [b"msg-%d" % i for i in range(n)]
    sigs = [pv.sign(m) for pv, m in zip(pvs, msgs)]
    return pubs, msgs, sigs


class _GateBackend(VerifyBackend):
    """CpuBackend whose first call blocks until released — holds the
    dispatcher busy so follow-up requests provably queue and coalesce."""

    name = "gate"

    def __init__(self):
        self._cpu = CpuBackend()
        self.release = threading.Event()
        self.calls = []  # batch sizes, in dispatch order
        self._first = True

    def batch_verify(self, pubs, msgs, sigs):
        self.calls.append(len(pubs))
        if self._first:
            self._first = False
            self.release.wait(10.0)
        return self._cpu.batch_verify(pubs, msgs, sigs)

    def merkle_root(self, leaves):
        return self._cpu.merkle_root(leaves)


def test_single_request_passes_through():
    eng = VerificationEngine(CpuBackend(), hold_ms=0)
    try:
        pubs, msgs, sigs = _signed(4)
        ok, bits = eng.batch_verify(pubs, msgs, sigs)
        assert ok and bits == [True] * 4
        c = eng.counters()
        assert c["requests"] == 1 and c["dispatches"] == 1
        assert c["coalesced_dispatches"] == 0
    finally:
        eng.close()


def test_empty_request_resolves_immediately():
    eng = VerificationEngine(CpuBackend(), hold_ms=0)
    try:
        assert eng.batch_verify([], [], []) == (False, [])
    finally:
        eng.close()


def test_concurrent_requests_coalesce_with_correct_slicing():
    """Requests queued behind an in-flight dispatch merge into ONE backend
    call, and each caller gets exactly its own bitmap back."""
    gate = _GateBackend()
    eng = VerificationEngine(gate, hold_ms=0)
    try:
        p0, m0, s0 = _signed(2, tag=b"first")
        fut0 = eng.submit(p0, m0, s0)
        while not gate.calls:  # dispatcher now wedged inside call #1
            time.sleep(0.001)
        batches = [_signed(3, tag=b"req-%d" % i) for i in range(3)]
        # poison one lane of request 1 only
        batches[1][2][1] = b"\x01" * 64
        futs = [eng.submit(p, m, s) for p, m, s in batches]
        gate.release.set()
        ok0, bits0 = fut0.result(10.0)
        assert ok0 and bits0 == [True, True]
        results = [f.result(10.0) for f in futs]
        assert results[0] == (True, [True, True, True])
        assert results[1] == (False, [True, False, True])
        assert results[2] == (True, [True, True, True])
        assert gate.calls == [2, 9], "queued requests must share one dispatch"
        c = eng.counters()
        assert c["coalesced_dispatches"] == 1
        assert c["batched_requests"] == 3
        assert c["fallback_splits"] == 0
        assert c["coalesce_ratio"] == 2.0
    finally:
        gate.release.set()
        eng.close()


def test_identical_triples_share_lanes():
    gate = _GateBackend()
    eng = VerificationEngine(gate, hold_ms=0)
    try:
        fut0 = eng.submit(*_signed(1, tag=b"warm"))
        while not gate.calls:
            time.sleep(0.001)
        shared = _signed(4, tag=b"dup")
        futs = [eng.submit(*shared) for _ in range(3)]
        gate.release.set()
        assert fut0.result(10.0)[0]
        for f in futs:
            assert f.result(10.0) == (True, [True] * 4)
        assert gate.calls == [1, 4], "3x4 identical triples -> 4 lanes"
        assert eng.counters()["dedup_sigs"] == 8
    finally:
        gate.release.set()
        eng.close()


def test_window_accumulates_burst_into_one_dispatch():
    cpu = CpuBackend()
    calls = []
    orig = cpu.batch_verify
    cpu.batch_verify = lambda p, m, s: calls.append(len(p)) or orig(p, m, s)
    eng = VerificationEngine(cpu, hold_ms=200)
    try:
        batches = [_signed(2, tag=b"burst-%d" % i) for i in range(4)]
        start = threading.Barrier(4)

        def go(b):
            start.wait()
            return eng.batch_verify(*b)

        threads = []
        results = [None] * 4
        for i, b in enumerate(batches):
            t = threading.Thread(
                target=lambda i=i, b=b: results.__setitem__(i, go(b))
            )
            t.start()
            threads.append(t)
        for t in threads:
            t.join(10.0)
        assert all(r == (True, [True, True]) for r in results)
        assert len(calls) == 1 and calls[0] == 8
    finally:
        eng.close()


def test_max_sigs_caps_a_dispatch_without_splitting_requests():
    gate = _GateBackend()
    eng = VerificationEngine(gate, hold_ms=0, max_sigs=5)
    try:
        fut0 = eng.submit(*_signed(1, tag=b"head"))
        while not gate.calls:
            time.sleep(0.001)
        futs = [eng.submit(*_signed(3, tag=b"cap-%d" % i)) for i in range(3)]
        gate.release.set()
        assert fut0.result(10.0)[0]
        for f in futs:
            assert f.result(10.0) == (True, [True] * 3)
        # 3x3 sigs under a 5-sig cap: one pair fits (3+3 > 5 -> actually
        # only one whole request per dispatch once the first is in), and a
        # request is never split across dispatches.
        assert all(c in (1, 3, 6) for c in gate.calls)
        assert sum(gate.calls) == 10
    finally:
        gate.release.set()
        eng.close()


def test_oversized_single_request_is_not_split():
    eng = VerificationEngine(CpuBackend(), hold_ms=0, max_sigs=2)
    try:
        pubs, msgs, sigs = _signed(6, tag=b"big")
        ok, bits = eng.batch_verify(pubs, msgs, sigs)
        assert ok and bits == [True] * 6
    finally:
        eng.close()


def test_submit_after_close_raises():
    eng = VerificationEngine(CpuBackend(), hold_ms=0)
    eng.close()
    with pytest.raises(RuntimeError):
        eng.submit(*_signed(1, tag=b"late"))


# -- chaos: failed coalesced dispatches -----------------------------------


class _FlakyBackend(VerifyBackend):
    """Fails (or wedges, then fails) any MERGED dispatch; serves
    request-sized batches — the shape of a sick tier that chokes on the
    coalesced batch but can still answer its parts."""

    name = "flaky"

    def __init__(self, limit, wedge_ms=0.0):
        self._cpu = CpuBackend()
        self.limit = limit
        self.wedge_ms = wedge_ms
        self.calls = []

    def batch_verify(self, pubs, msgs, sigs):
        self.calls.append(len(pubs))
        if len(pubs) > self.limit:
            if self.wedge_ms:
                time.sleep(self.wedge_ms / 1000.0)
            raise ConnectionError("flaky: coalesced batch rejected")
        return self._cpu.batch_verify(pubs, msgs, sigs)

    def merkle_root(self, leaves):
        return self._cpu.merkle_root(leaves)


@pytest.mark.parametrize("wedge_ms", [0.0, 50.0])
def test_failed_coalesced_dispatch_falls_back_per_request(wedge_ms):
    """Error/wedge on the merged dispatch: every batchmate still gets its
    own correct bitmap via per-request retries; the caller with the bad
    signature is the only one who sees a False lane."""
    flaky = _FlakyBackend(limit=3, wedge_ms=wedge_ms)
    eng = VerificationEngine(flaky, hold_ms=200)
    try:
        batches = [_signed(3, tag=b"fb-%d" % i) for i in range(3)]
        batches[2][2][0] = b"\x02" * 64  # poison request 2, lane 0
        start = threading.Barrier(3)
        results = [None] * 3

        def go(i):
            start.wait()
            results[i] = eng.batch_verify(*batches[i])

        threads = [
            threading.Thread(target=go, args=(i,)) for i in range(3)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(15.0)
        assert results[0] == (True, [True] * 3)
        assert results[1] == (True, [True] * 3)
        assert results[2] == (False, [False, True, True])
        c = eng.counters()
        assert c["fallback_splits"] == 1
        assert c["coalesced_dispatches"] == 1
        # one failed merged call (9 lanes) + 3 per-request retries
        assert flaky.calls[0] == 9 and sorted(flaky.calls[1:]) == [3, 3, 3]
    finally:
        eng.close()


def test_poisoned_request_error_does_not_fail_batchmates():
    """A request whose RETRY also fails (backend rejects even its solo
    batch) errors alone; batchmates still resolve."""

    class _Vetoing(VerifyBackend):
        name = "veto"

        def __init__(self):
            self._cpu = CpuBackend()

        def batch_verify(self, pubs, msgs, sigs):
            if len(pubs) != 2 or any(s == b"\xee" * 64 for s in sigs):
                raise ConnectionError("veto")
            return self._cpu.batch_verify(pubs, msgs, sigs)

        def merkle_root(self, leaves):
            return self._cpu.merkle_root(leaves)

    eng = VerificationEngine(_Vetoing(), hold_ms=200)
    try:
        good = _signed(2, tag=b"ok")
        poisoned = _signed(2, tag=b"poison")
        poisoned[2][0] = b"\xee" * 64
        start = threading.Barrier(2)
        out = {}

        def go(name, batch):
            start.wait()
            try:
                out[name] = eng.batch_verify(*batch)
            except Exception as e:
                out[name] = e

        threads = [
            threading.Thread(target=go, args=("good", good)),
            threading.Thread(target=go, args=("poisoned", poisoned)),
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(15.0)
        assert out["good"] == (True, [True, True])
        assert isinstance(out["poisoned"], ConnectionError)
    finally:
        eng.close()


def test_chaos_error_faults_fall_back_per_request():
    """CMTPU_FAULTS-style seeded chaos under the engine: injected errors
    on merged dispatches degrade to per-request retries, verdicts stay
    honest."""
    chaos = ChaosBackend(CpuBackend(), "error:0.5", seed=7)
    eng = VerificationEngine(chaos, hold_ms=150)
    try:
        for round_i in range(4):
            batches = [
                _signed(2, tag=b"cr-%d-%d" % (round_i, i)) for i in range(3)
            ]
            batches[1][2][1] = b"\x03" * 64
            start = threading.Barrier(3)
            results = [None] * 3

            def go(i):
                start.wait()
                try:
                    results[i] = eng.batch_verify(*batches[i])
                except ConnectionError:
                    results[i] = "error"

            threads = [
                threading.Thread(target=go, args=(i,)) for i in range(3)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(15.0)
            # Whatever the chaos draw did, a RESOLVED verdict is honest.
            if results[0] != "error":
                assert results[0] == (True, [True, True])
            if results[1] != "error":
                assert results[1] == (False, [True, False])
            if results[2] != "error":
                assert results[2] == (True, [True, True])
    finally:
        eng.close()


def test_flip_fault_cannot_cross_request_boundaries():
    """A flip-corrupted tier under the SUPERVISED chain, under the
    engine: the cpu cross-check catches the false-accept, and the one
    request carrying an invalid signature is the only one whose bitmap
    shows it — a flip in its lane never flips a batchmate."""
    flipping = ChaosBackend(CpuBackend(), "flip:1.0", seed=3)
    flipping.name = "chaos-primary"
    chain = ResilientBackend(
        [("chaos-primary", flipping), ("cpu", CpuBackend())],
        deadline_ms=0,
        crosscheck="full",
    )
    eng = VerificationEngine(chain, hold_ms=200)
    try:
        batches = [_signed(2, tag=b"flip-%d" % i) for i in range(3)]
        batches[0][2][0] = b"\x04" * 64  # only request 0 is invalid
        start = threading.Barrier(3)
        results = [None] * 3

        def go(i):
            start.wait()
            results[i] = eng.batch_verify(*batches[i])

        threads = [threading.Thread(target=go, args=(i,)) for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(20.0)
        assert results[0] == (False, [False, True])
        assert results[1] == (True, [True, True]), "batchmate must not flip"
        assert results[2] == (True, [True, True]), "batchmate must not flip"
    finally:
        eng.close()


# -- pod-scale coalescing ------------------------------------------------


def _synthetic(n, tag, poison=()):
    """Unique byte triples without real crypto — signing a pod-scale pack
    host-side would take minutes; the marker backend below judges lanes by
    the signature's first byte instead."""
    pubs = [(b"%s-p-%d" % (tag, i)).ljust(32, b"\x00") for i in range(n)]
    msgs = [b"%s-m-%d" % (tag, i) for i in range(n)]
    sigs = [
        (b"\x00" if i in poison else b"\x01")
        + (b"%s-s-%d" % (tag, i)).ljust(63, b"\x02")
        for i in range(n)
    ]
    return pubs, msgs, sigs


class _MarkerGate(VerifyBackend):
    """_GateBackend at pod scale: first call wedges the dispatcher so
    followers provably queue; verdicts come from the sig marker byte."""

    name = "marker-gate"

    def __init__(self):
        self.release = threading.Event()
        self.calls = []
        self._first = True

    def batch_verify(self, pubs, msgs, sigs):
        self.calls.append(len(pubs))
        if self._first:
            self._first = False
            self.release.wait(10.0)
        bits = [s[0] == 1 for s in sigs]
        return all(bits), bits

    def merkle_root(self, leaves):
        raise NotImplementedError("verify-only marker backend")


@pytest.mark.mesh
def test_default_cap_scales_with_mesh_width(monkeypatch):
    """The default dispatch cap is 16384 x mesh width (one merged dispatch
    can fill every chip); an explicit env or ctor arg always wins."""
    monkeypatch.delenv("CMTPU_COALESCE_MAX", raising=False)
    eng = VerificationEngine(CpuBackend(), hold_ms=0)
    try:
        assert eng.max_sigs == 16384 * 8  # the 8-device conftest mesh
        assert eng.counters()["max_sigs"] == 131072
    finally:
        eng.close()
    monkeypatch.setenv("CMTPU_COALESCE_MAX", "4096")
    eng = VerificationEngine(CpuBackend(), hold_ms=0)
    try:
        assert eng.max_sigs == 4096
    finally:
        eng.close()
    eng = VerificationEngine(CpuBackend(), hold_ms=0, max_sigs=5)
    try:
        assert eng.max_sigs == 5
    finally:
        eng.close()


@pytest.mark.mesh
def test_pod_scale_merged_dispatch_with_per_caller_slicing():
    """8 x 4096-sig requests — above the old single-chip 16384 cap — must
    merge into ONE columnar dispatch under the pod-width default cap, and
    the single poisoned lane must come back to its own caller only."""
    gate = _MarkerGate()
    eng = VerificationEngine(gate, hold_ms=0)
    try:
        assert eng.max_sigs >= 8 * 4096
        head = eng.submit(*_synthetic(1, b"head"))
        while not gate.calls:  # dispatcher wedged inside call #1
            time.sleep(0.001)
        futs = [
            eng.submit(
                *_synthetic(4096, b"req-%d" % i,
                            poison={100} if i == 3 else ())
            )
            for i in range(8)
        ]
        gate.release.set()
        assert head.result(10.0) == (True, [True])
        for i, fut in enumerate(futs):
            ok, bits = fut.result(30.0)
            assert len(bits) == 4096
            if i == 3:
                assert not ok
                assert [j for j, b in enumerate(bits) if not b] == [100]
            else:
                assert ok and all(bits)
        assert gate.calls == [1, 32768], "pod batch must be ONE dispatch"
        c = eng.counters()
        assert c["coalesced_dispatches"] == 1
        assert c["batched_requests"] == 8
        assert c["fallback_splits"] == 0
    finally:
        gate.release.set()
        eng.close()


def test_auto_backend_composes_one_engine(monkeypatch):
    """`auto` and `get_bn254_backend` each put one engine, holding `HOLD_MS`,
    in front of the supervised chain."""
    from cometbft_tpu.crypto import bn254
    from cometbft_tpu.sidecar.engine import engine_of

    monkeypatch.setenv("CMTPU_BACKEND", "auto")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.delenv("CMTPU_FAULTS", raising=False)
    old = backend_mod._backend
    old_bn = bn254._backend
    try:
        backend_mod.set_backend(None)
        bn254._backend = None
        for eng in (backend_mod.get_backend(), bn254.get_bn254_backend()):
            assert isinstance(eng, VerificationEngine)
            assert engine_of(eng) is eng
            assert eng.hold_ms == HOLD_MS == 2.0
            assert isinstance(eng.inner, ResilientBackend)
            assert engine_of(eng.inner) is None, "one engine, not two"
            eng.close()
    finally:
        backend_mod.set_backend(old)
        bn254._backend = old_bn
