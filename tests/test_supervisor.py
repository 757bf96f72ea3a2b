"""Resilient verification-backend supervisor (sidecar/supervisor.py) +
chaos fault injection (sidecar/chaos.py): deadlines, circuit breaker,
degradation chain, half-open recovery, and the cpu cross-check catching an
injected false-accept.  All seeded/deterministic, all CPU-only — the
`chaos` tier-1 group."""

import threading
import time

import pytest

from cometbft_tpu.crypto import ed25519
from cometbft_tpu.crypto.merkle import hash_from_byte_slices
from cometbft_tpu.sidecar import backend as backend_mod
from cometbft_tpu.sidecar.backend import CpuBackend, VerifyBackend
from cometbft_tpu.sidecar.chaos import ChaosBackend, FaultSpecError, parse_faults
from cometbft_tpu.sidecar.supervisor import (
    ChainExhausted,
    ResilientBackend,
    build_chain,
)

pytestmark = pytest.mark.chaos


def _signed(n, tag=b"sup"):
    pvs = [ed25519.gen_priv_key_from_secret(tag + b"-%d" % i) for i in range(n)]
    pubs = [pv.pub_key().bytes() for pv in pvs]
    msgs = [b"msg-%d" % i for i in range(n)]
    sigs = [pv.sign(m) for pv, m in zip(pvs, msgs)]
    return pubs, msgs, sigs


class _ScriptedBackend(VerifyBackend):
    """A tier that fails on command: raises `exc` while `failing`, else
    delegates to CpuBackend.  Counts calls and pings."""

    name = "scripted"

    def __init__(self, exc=ConnectionError("scripted failure")):
        self._cpu = CpuBackend()
        self.exc = exc
        self.failing = True
        self.calls = 0
        self.pings = 0
        self.ping_ok = True

    def batch_verify(self, pubs, msgs, sigs):
        self.calls += 1
        if self.failing:
            raise self.exc
        return self._cpu.batch_verify(pubs, msgs, sigs)

    def merkle_root(self, leaves):
        self.calls += 1
        if self.failing:
            raise self.exc
        return self._cpu.merkle_root(leaves)

    def ping(self):
        self.pings += 1
        if not self.ping_ok:
            raise ConnectionError("ping failed")
        return True


def _supervisor(primary, **kw):
    kw.setdefault("deadline_ms", 500)
    kw.setdefault("retries", 0)
    kw.setdefault("backoff_ms", 1)
    kw.setdefault("breaker_threshold", 2)
    kw.setdefault("breaker_cooldown_ms", 100)
    kw.setdefault("crosscheck", "off")
    return ResilientBackend([("primary", primary), ("cpu", CpuBackend())], **kw)


# -- fault spec ----------------------------------------------------------------


def test_parse_faults():
    f = parse_faults("latency:0.5:20,error:0.1,wedge:0.2:1000,flip:1")
    assert f["latency"] == (0.5, 20.0)
    assert f["error"][0] == 0.1
    assert f["wedge"] == (0.2, 1000.0)
    assert f["flip"][0] == 1.0
    assert parse_faults("wedge:1")["wedge"][1] > 0  # default duration


def test_parse_faults_rejects_bad_specs():
    for bad in ("jitter:0.5", "error:2", "latency:0.5", "error:0.5:100",
                "latency:0.1:5:9", "error"):
        with pytest.raises(FaultSpecError):
            parse_faults(bad)


def test_chaos_is_deterministic_per_seed():
    pubs, msgs, sigs = _signed(4)

    def run(seed):
        b = ChaosBackend(CpuBackend(), "error:0.5", seed=seed)
        outcomes = []
        for _ in range(20):
            try:
                b.batch_verify(pubs, msgs, sigs)
                outcomes.append("ok")
            except ConnectionError:
                outcomes.append("err")
        return outcomes

    assert run(7) == run(7)
    assert run(7) != run(8), "different seeds must explore different faults"


def test_chaos_flip_is_a_false_accept():
    pubs, msgs, sigs = _signed(4)
    sigs[2] = bytes(64)  # garbage signature
    b = ChaosBackend(CpuBackend(), "flip:1", seed=0)
    ok, bits = b.batch_verify(pubs, msgs, sigs)
    assert ok and all(bits), "flip must corrupt the result into all-valid"


# -- degradation chain + breaker ----------------------------------------------


def test_degradation_chain_serves_correct_result():
    pubs, msgs, sigs = _signed(6)
    primary = _ScriptedBackend()
    sup = _supervisor(primary)
    ok, bits = sup.batch_verify(pubs, msgs, sigs)
    assert ok and bits == [True] * 6
    c = sup.counters()
    assert c["degraded_calls"] == 1
    assert c["active_tier"] == "primary"  # one failure: not tripped yet


def test_breaker_opens_after_threshold_and_fails_fast():
    pubs, msgs, sigs = _signed(4)
    primary = _ScriptedBackend()
    sup = _supervisor(primary, breaker_threshold=3, breaker_cooldown_ms=60_000)
    for _ in range(5):
        ok, _ = sup.batch_verify(pubs, msgs, sigs)
        assert ok
    c = sup.counters()
    assert c["trips"] == 1
    assert c["tiers"]["primary"]["state"] == "open"
    assert c["active_tier"] == "cpu"
    # Once open, the primary is not called at all.
    assert primary.calls == 3


def test_half_open_probe_repromotes_healed_tier():
    pubs, msgs, sigs = _signed(4)
    primary = _ScriptedBackend()
    sup = _supervisor(primary, breaker_threshold=1, breaker_cooldown_ms=50)
    sup.batch_verify(pubs, msgs, sigs)  # trips immediately (threshold 1)
    assert sup.counters()["tiers"]["primary"]["state"] == "open"
    primary.failing = False  # tier heals while open
    time.sleep(0.08)  # cooldown elapses -> next call half-opens
    ok, bits = sup.batch_verify(pubs, msgs, sigs)
    assert ok and all(bits)
    c = sup.counters()
    assert c["tiers"]["primary"]["state"] == "closed"
    assert c["active_tier"] == "primary"
    assert primary.pings >= 1, "half-open recovery must probe via Ping"


def test_half_open_failed_probe_reopens():
    pubs, msgs, sigs = _signed(4)
    primary = _ScriptedBackend()
    primary.ping_ok = False
    sup = _supervisor(primary, breaker_threshold=1, breaker_cooldown_ms=50)
    sup.batch_verify(pubs, msgs, sigs)
    time.sleep(0.08)
    calls_before = primary.calls
    ok, _ = sup.batch_verify(pubs, msgs, sigs)  # probe fails; cpu serves
    assert ok
    assert primary.calls == calls_before, "failed probe must not admit the call"
    assert sup.counters()["tiers"]["primary"]["state"] == "open"


def test_retries_with_backoff_then_success():
    pubs, msgs, sigs = _signed(4)

    class FlakyOnce(_ScriptedBackend):
        def batch_verify(self, pubs, msgs, sigs):
            self.calls += 1
            if self.calls == 1:
                raise ConnectionError("transient")
            return self._cpu.batch_verify(pubs, msgs, sigs)

    primary = FlakyOnce()
    sup = _supervisor(primary, retries=2)
    ok, _ = sup.batch_verify(pubs, msgs, sigs)
    assert ok
    assert primary.calls == 2
    c = sup.counters()
    assert c["retries"] == 1
    assert c["degraded_calls"] == 0, "retry succeeded on the SAME tier"


def test_chain_exhausted_raises():
    pubs, msgs, sigs = _signed(2)
    bad = _ScriptedBackend()
    sup = ResilientBackend(
        [("a", bad), ("b", _ScriptedBackend())],
        deadline_ms=0, retries=0, breaker_threshold=3,
        breaker_cooldown_ms=100, crosscheck="off",
    )
    with pytest.raises(ChainExhausted):
        sup.batch_verify(pubs, msgs, sigs)


def test_merkle_root_degrades_too():
    leaves = [b"leaf-%d" % i for i in range(33)]
    sup = _supervisor(_ScriptedBackend())
    assert sup.merkle_root(leaves) == hash_from_byte_slices(leaves)


# -- deadlines -----------------------------------------------------------------


def test_wedged_tier_costs_one_deadline_not_liveness():
    """The acceptance shape: a wedged primary + a 10,240-signature batch
    must return a CORRECT result via the chain in < 2x CMTPU_DEADLINE_MS,
    and subsequent calls fail over fast (the worker stays wedged)."""
    n = 10_240
    pv = ed25519.gen_priv_key_from_secret(b"wedge-acceptance")
    pub, msg = pv.pub_key().bytes(), b"the-commit-vote"
    sig = pv.sign(msg)
    # One real verification, repeated to commit scale: the anchor's cost is
    # the verified-triple cache, so the measured wall is supervisor+wedge.
    pubs, msgs, sigs = [pub] * n, [msg] * n, [sig] * n
    CpuBackend().batch_verify([pub], [msg], [sig])  # warm the cache

    deadline_ms = 400.0
    wedged = ChaosBackend(CpuBackend(), "wedge:1:30000", seed=3)
    sup = ResilientBackend(
        [("tpu", wedged), ("cpu", CpuBackend())],
        deadline_ms=deadline_ms, retries=0, breaker_threshold=2,
        breaker_cooldown_ms=60_000, crosscheck="off",
    )
    t0 = time.perf_counter()
    ok, bits = sup.batch_verify(pubs, msgs, sigs)
    wall_ms = (time.perf_counter() - t0) * 1000
    assert ok and len(bits) == n and all(bits)
    assert wall_ms < 2 * deadline_ms, f"degradation cost {wall_ms:.0f} ms"
    c = sup.counters()
    assert c["deadline_exceeded"] == 1 and c["degraded_calls"] == 1

    # Second call: the wedged worker is still busy -> fail fast, trip.
    # "Fast" here means NO deadline wait happened (TierWedged short-circuits
    # before the worker), not a tight wall bound: on this 1-core CI host the
    # 10,240-signature anchor pass alone can take ~200 ms under suite load,
    # so the wall assertion only rules out another full deadline spent
    # waiting on the wedge (the counters are the primary signal).
    t0 = time.perf_counter()
    ok, _ = sup.batch_verify(pubs, msgs, sigs)
    fast_ms = (time.perf_counter() - t0) * 1000
    assert ok
    assert fast_ms < deadline_ms, f"post-wedge call took {fast_ms:.0f} ms"
    c = sup.counters()
    assert c["deadline_exceeded"] == 1  # still just the first call's
    assert c["trips"] == 1 and c["active_tier"] == "cpu"


def test_no_deadline_means_inline_calls():
    pubs, msgs, sigs = _signed(4)
    primary = _ScriptedBackend()
    primary.failing = False
    sup = _supervisor(primary, deadline_ms=0)
    # Delta, not absolute: the full suite leaks daemon threads from other
    # modules (indexer pumps, sidecar servers), so an absolute
    # active_count() bound flakes by test ordering. The claim under test
    # is only that deadline_ms=0 spawns NO tier workers.
    before = threading.active_count()
    ok, _ = sup.batch_verify(pubs, msgs, sigs)
    assert ok
    assert threading.active_count() - before == 0  # inline: no tier workers
    assert all(t.worker._thread is None for t in sup.tiers)


# -- cross-check ---------------------------------------------------------------


def test_crosscheck_catches_injected_false_accept():
    """A degraded tier's bit-flip false-accept (one INVALID signature
    reported all-valid) must be caught by the cpu cross-check and the
    anchor's honest result served instead."""
    pubs, msgs, sigs = _signed(8, tag=b"flip")
    sigs[5] = bytes(64)  # invalid: the honest bitmap has a False at 5
    flipping = ChaosBackend(CpuBackend(), "flip:1", seed=0)
    sup = ResilientBackend(
        [("tpu", flipping), ("cpu", CpuBackend())],
        deadline_ms=0, retries=0, breaker_threshold=1,
        breaker_cooldown_ms=60_000, crosscheck="full",
    )
    ok, bits = sup.batch_verify(pubs, msgs, sigs)
    assert not ok and bits[5] is False and sum(bits) == 7
    c = sup.counters()
    assert c["crosscheck_catches"] == 1
    assert c["tiers"]["tpu"]["state"] == "open", "false-accept must trip"


def test_crosscheck_sample_is_deterministic_and_cheap():
    pubs, msgs, sigs = _signed(64, tag=b"sample")
    clean = ChaosBackend(CpuBackend(), "error:0", seed=0)
    sup = ResilientBackend(
        [("tpu", clean), ("cpu", CpuBackend())],
        deadline_ms=0, retries=0, breaker_threshold=3,
        breaker_cooldown_ms=100, crosscheck="sample",
    )
    ok, bits = sup.batch_verify(pubs, msgs, sigs)
    assert ok and all(bits)
    assert sup.counters()["crosscheck_catches"] == 0


# -- chain assembly + env selection -------------------------------------------


def test_build_chain_cpu_only(monkeypatch):
    monkeypatch.delenv("CMTPU_SIDECAR_ADDR", raising=False)
    monkeypatch.delenv("CMTPU_FAULTS", raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    chain = build_chain()
    assert [name for name, _ in chain] == ["cpu"]


def test_build_chain_inserts_chaos_tier_under_faults(monkeypatch):
    monkeypatch.delenv("CMTPU_SIDECAR_ADDR", raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("CMTPU_FAULTS", "error:0.5")
    monkeypatch.setenv("CMTPU_FAULTS_SEED", "11")
    chain = build_chain()
    assert [name for name, _ in chain] == ["chaos", "cpu"]
    assert isinstance(chain[0][1], ChaosBackend)
    assert isinstance(chain[1][1], CpuBackend), "the anchor stays clean"


def test_auto_backend_is_supervised(monkeypatch):
    monkeypatch.setenv("CMTPU_BACKEND", "auto")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.delenv("CMTPU_SIDECAR_ADDR", raising=False)
    monkeypatch.delenv("CMTPU_FAULTS", raising=False)
    old = backend_mod._backend
    backend_mod.set_backend(None)
    try:
        b = backend_mod.get_backend()
        # auto composes engine -> supervisor; the supervised chain is the
        # engine's inner tier.
        from cometbft_tpu.sidecar.engine import VerificationEngine

        assert isinstance(b, VerificationEngine)
        assert isinstance(b.inner, ResilientBackend)
        pubs, msgs, sigs = _signed(3, tag=b"auto")
        ok, bits = b.batch_verify(pubs, msgs, sigs)
        assert ok and bits == [True] * 3
        assert b.counters()["inner"]["active_tier"] == "cpu"
        b.close()
    finally:
        backend_mod.set_backend(old)


def test_supervised_chain_under_faults_stays_correct(monkeypatch):
    """The e2e backend_faults environment in miniature: supervised auto
    chain, chaotic primary, seeded errors + latency — every call must
    still return the honest verdict."""
    monkeypatch.setenv("CMTPU_BACKEND", "auto")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("CMTPU_FAULTS", "latency:0.3:5,error:0.4")
    monkeypatch.setenv("CMTPU_FAULTS_SEED", "42")
    monkeypatch.setenv("CMTPU_BACKOFF_MS", "1")
    monkeypatch.delenv("CMTPU_SIDECAR_ADDR", raising=False)
    old = backend_mod._backend
    backend_mod.set_backend(None)
    try:
        b = backend_mod.get_backend()
        pubs, msgs, sigs = _signed(5, tag=b"fault-env")
        bad = list(sigs)
        bad[1] = bytes(64)
        for _ in range(12):
            ok, bits = b.batch_verify(pubs, msgs, bad)
            assert not ok and bits[1] is False and sum(bits) == 4
        leaves = [b"l%d" % i for i in range(9)]
        for _ in range(4):
            assert b.merkle_root(leaves) == hash_from_byte_slices(leaves)
    finally:
        backend_mod.set_backend(old)


def test_batch_verifier_survives_chain_exhaustion():
    """The crypto caller's last resort: when every supervised tier is down,
    BatchVerifier.verify falls back to scalar ZIP-215 — liveness over speed."""

    class Down(VerifyBackend):
        name = "down"

        def batch_verify(self, pubs, msgs, sigs):
            raise ChainExhausted("all tiers down")

        def merkle_root(self, leaves):
            raise ChainExhausted("all tiers down")

    old = backend_mod._backend
    backend_mod.set_backend(Down())
    try:
        v = ed25519.BatchVerifier()
        pv = ed25519.gen_priv_key_from_secret(b"exhausted")
        v.add(pv.pub_key(), b"good", pv.sign(b"good"))
        pv2 = ed25519.gen_priv_key_from_secret(b"exhausted2")
        v.add(pv2.pub_key(), b"bad", bytes(64))
        ok, bits = v.verify()
        assert not ok and bits == [True, False]
    finally:
        backend_mod.set_backend(old)


def test_metrics_gauges_render(monkeypatch):
    from cometbft_tpu.libs.metrics import Registry

    primary = _ScriptedBackend()
    sup = _supervisor(primary, breaker_threshold=1, breaker_cooldown_ms=60_000)
    pubs, msgs, sigs = _signed(2, tag=b"gauge")
    sup.batch_verify(pubs, msgs, sigs)
    reg = Registry(namespace="cmt")
    sup.register_metrics(reg)
    out = reg.render()
    assert "cmt_backend_trips 1" in out
    assert "cmt_backend_deadline_exceeded 0" in out
    assert "cmt_backend_retries 0" in out
    assert "cmt_backend_active_tier 1" in out  # degraded to the anchor
