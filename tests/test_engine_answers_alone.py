"""Requests that share a dispatch (ISSUE 32): N overlapping requests through
one engine give each request exactly the answer it would get alone.

The yardstick is that sentence in code, `benchmarks/reference/answers_alone.py`
(each lane by the scalar ZIP-215 reference; no engine, no dedup, no
batching). The engine under test runs over the host tier (`CpuBackend`) with
a small cap; the requests are signed by OpenSSL and queued together behind a
wedged dispatch, so what merges is decided by the cap alone: a merged
dispatch is sized by the lanes it will run, and requests that carry the same
columns cost the cap their distinct lanes once.

ISSUE 33 adds the in-flight join: a request submitted while a dispatch that
carries the same columns is in flight is answered by that dispatch; what
differs in a byte, in order or in length is queued as before. Here the
dispatch in flight is held at a stop in the host tier (`_Stops`) while the
later requests are submitted. CPU only, small sizes."""

from __future__ import annotations

import os
import random
import sys
import threading
import time

import pytest
from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey

from cometbft_tpu.crypto import ed25519
from cometbft_tpu.libs import trace
from cometbft_tpu.sidecar.backend import CpuBackend, VerifyBackend
from cometbft_tpu.sidecar.engine import VerificationEngine, columns_fingerprint

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

from reference.answers_alone import answers_alone  # noqa: E402

pytestmark = pytest.mark.engine

LANES = 20  # a request
CAP = 32    # the engine's: two requests offer more than it holds


@pytest.fixture(autouse=True)
def clean_cache():
    ed25519._verified.clear()
    yield
    ed25519._verified.clear()


@pytest.fixture
def profiler_ring():
    trace.clear()
    with trace.capture():
        yield
    trace.clear()


def _signed(seed: int, n: int = LANES):
    """n triples signed by OpenSSL, a key a lane, from `seed`."""
    rng = random.Random(seed)
    pubs, msgs, sigs = [], [], []
    for i in range(n):
        key = Ed25519PrivateKey.from_private_bytes(rng.randbytes(32))
        msg = b"precommit/%d/%d/" % (seed, i) + rng.randbytes(90 + i % 7)
        pubs.append(key.public_key().public_bytes_raw())
        msgs.append(msg)
        sigs.append(key.sign(msg))
    return pubs, msgs, sigs


def _fresh(req):
    """The same columns as new objects, as each connection's decoder makes them."""
    return tuple([bytes(bytearray(x)) for x in col] for col in req)


def _flipped(req, seed: int, k: int = 3):
    pubs, msgs, sigs = _fresh(req)
    for i in random.Random(seed).sample(range(len(sigs)), k):
        sigs[i] = sigs[i][:7] + bytes([sigs[i][7] ^ 0x10]) + sigs[i][8:]
    return pubs, msgs, sigs


def _reordered(req, seed: int):
    order = list(range(len(req[0])))
    random.Random(seed).shuffle(order)
    return tuple([col[i] for i in order] for col in _fresh(req))


def _cases():
    a, b, c = (_signed(s) for s in (1, 2, 3))
    half = LANES // 2
    overlap = tuple(x[half:] + y[:half] for x, y in zip(a, b))  # a's tail, then b's head
    small = [_signed(s, 8) for s in (5, 6, 7, 8)]
    return {
        # name: (requests, the lanes of each dispatch that carries them)
        "identical-x4": ([_fresh(a) for _ in range(4)], [LANES]),
        "identical-x3-and-a-flipped-copy": (
            [_fresh(a), _flipped(a, 32), _fresh(a), _fresh(a)], [LANES, LANES]),
        "disjoint": (small, [32]),
        "partly-overlapping": ([tuple(col[:12] for col in _fresh(a)),
                                tuple(col[6:18] for col in _fresh(a))], [18]),
        "same-triples-another-order": ([_fresh(a), _reordered(a, 5)], [LANES, LANES]),
        "offered-over-the-cap-distinct-under-it": (
            [_fresh(a), _fresh(a), _fresh(a), tuple(col[:10] for col in _fresh(b))], [30]),
        "distinct-over-the-cap": ([_fresh(a), _fresh(b), _fresh(c)], [LANES, LANES, LANES]),
        # a copy of what is already in costs nothing, so it may pass a request that did not fit
        "two-chains-interleaved": ([_fresh(a), _fresh(b), _fresh(a), _fresh(b)], [LANES, LANES]),
        "an-overlap-is-sized-by-what-it-offered": ([_fresh(a), overlap], [LANES, LANES]),
    }


CASES = _cases()


class _Gate(VerifyBackend):
    """The host tier behind a gate: the first call (a blocker's) waits until
    the test has queued every request, so they are collected together; every
    call's lanes are recorded."""

    name = "gate"

    def __init__(self):
        self.cpu = CpuBackend()
        self.release = threading.Event()
        self.entered = threading.Event()
        self.calls: list[int] = []

    def batch_verify(self, pubs, msgs, sigs):
        if not self.entered.is_set():
            self.entered.set()
            assert self.release.wait(30)
            return True, [True] * len(pubs)
        self.calls.append(len(pubs))
        return self.cpu.batch_verify(pubs, msgs, sigs)


def _through_one_engine(requests, cap=CAP, gate=None):
    """Every request's answer with all of them queued together, the lanes of
    the dispatches that carried them, and the engine's counters."""
    gate = gate or _Gate()
    eng = VerificationEngine(gate, hold_ms=0.0, max_sigs=cap, starvation_ms=0.0)
    try:
        blocker = eng.submit([b"b" * 32], [b"blocker"], [b"s" * 64])
        assert gate.entered.wait(10)
        futs = [eng.submit(*r) for r in requests]
        gate.release.set()
        blocker.result(30)
        return [f.result(60) for f in futs], gate.calls, eng.counters()
    finally:
        gate.release.set()
        eng.close()


@pytest.mark.parametrize("name", sorted(CASES))
def test_every_request_gets_the_answer_it_would_get_alone(name):
    requests, want_calls = CASES[name]
    answers, calls, counters = _through_one_engine(requests)
    assert answers == answers_alone(requests)
    assert calls == want_calls, "what merged is decided by the distinct lanes and the cap"
    assert all(n <= CAP for n in calls)
    assert counters["dedup_sigs"] == sum(len(r[0]) for r in requests) - sum(calls)
    assert counters["dispatches"] == 1 + len(calls)  # the blocker's first


def test_four_copies_over_the_cap_leave_collect_as_one_batch_and_one_call(profiler_ring):
    """The deployment's case at a small size: 4 x 20 lanes offered against a
    cap of 32, 20 distinct: one dispatch, its span saying so."""
    requests, _ = CASES["identical-x4"]
    answers, calls, counters = _through_one_engine(requests)
    assert calls == [LANES] and answers == answers_alone(requests)
    assert counters["dispatches"] == 2 and counters["coalesced_dispatches"] == 1  # the blocker's, then one
    assert counters["batched_requests"] == 4 and counters["dedup_sigs"] == 3 * LANES
    merged = [s for s in trace.spans() if s["name"] == "engine.dispatch" and s["attrs"]["requests"] > 1]
    assert [(s["attrs"]["requests"], s["attrs"]["lanes"], s["attrs"]["unique"], s["attrs"]["dedup"])
            for s in merged] == [(4, 4 * LANES, LANES, 3 * LANES)]
    lone = [s for s in trace.spans() if s["name"] == "engine.dispatch" and s["attrs"]["requests"] == 1]
    assert [s["attrs"]["unique"] for s in lone] == [1], "a lone request runs what it offered"
    assert merged[0]["attrs"]["fingerprint_ms"] > 0 and "fingerprint_ms" not in lone[0]["attrs"]
    phases = sorted(s["attrs"]["phase"] for s in trace.spans() if s["name"] == "engine.merge")
    assert phases == ["pack", "slice"]


def test_a_copy_is_one_only_if_its_columns_compare_equal(monkeypatch):
    """The fingerprint finds the candidate, the comparison decides: with
    every fingerprint made equal, requests that differ are still walked
    triple by triple and every answer is still the one it would get alone;
    true copies are compared once each and skip the walk."""
    import cometbft_tpu.sidecar.engine as engine_mod

    compared = []
    real = engine_mod._same_columns
    monkeypatch.setattr(engine_mod, "_same_columns", lambda a, b: compared.append(1) or real(a, b))
    requests, _ = CASES["identical-x4"]
    answers, calls, _ = _through_one_engine(requests)
    assert calls == [LANES] and answers == answers_alone(requests) and len(compared) == 3
    monkeypatch.setattr(engine_mod, "columns_fingerprint", lambda *cols: 7)
    requests, _ = CASES["offered-over-the-cap-distinct-under-it"]
    del compared[:]
    answers, calls, counters = _through_one_engine(requests)
    assert answers == answers_alone(requests)
    assert calls == [30] and len(compared) == 3, "two copies, and one request that only hashes alike"
    requests = [CASES["identical-x4"][0][0], _flipped(CASES["identical-x4"][0][0], 32)]
    answers, calls, _ = _through_one_engine(requests, cap=64)  # they fit as offered: no fingerprint
    assert answers == answers_alone(requests) and calls == [LANES + 3]
    answers, calls, _ = _through_one_engine(requests)  # over the cap, and they hash alike
    assert answers == answers_alone(requests) and calls == [LANES + 3], "sized wrong, answered right"


def test_no_fingerprint_is_taken_where_the_queue_fits_the_cap(monkeypatch):
    """A lone request, and requests that fit the cap as offered, pay nothing:
    the fingerprint is taken only where the queue offers more than the cap."""
    import cometbft_tpu.sidecar.engine as engine_mod

    taken = []
    real = engine_mod.columns_fingerprint
    monkeypatch.setattr(engine_mod, "columns_fingerprint",
                        lambda *cols: taken.append(len(cols[0])) or real(*cols))
    small = [_signed(s, 8) for s in (5, 6)]
    answers, calls, _ = _through_one_engine(small)
    assert calls == [16] and taken == [] and answers == answers_alone(small)
    requests, _ = CASES["identical-x4"]
    _through_one_engine(requests)
    assert taken == [LANES] * 4, "once a request, outside the lock"


def test_a_failed_merged_dispatch_still_falls_back_to_each_request_alone():
    """The guarantee that does not move: a merged dispatch that fails is
    retried request by request, and each still gets its own answer."""
    requests = [_fresh(CASES["identical-x4"][0][0]), _flipped(CASES["identical-x4"][0][0], 9),
                _fresh(CASES["identical-x4"][0][0])]

    class FailsMerged(_Gate):
        def batch_verify(self, pubs, msgs, sigs):
            if self.entered.is_set() and not self.calls:
                self.calls.append(-len(pubs))
                raise RuntimeError("the merged call fails")
            return super().batch_verify(pubs, msgs, sigs)

    answers, calls, counters = _through_one_engine(requests, cap=64, gate=FailsMerged())
    assert answers == answers_alone(requests)
    assert calls == [-(LANES + 3), LANES, LANES, LANES]
    assert counters["fallback_splits"] == 1


def test_the_fingerprint_is_of_the_whole_columns_in_their_order():
    a = _signed(1)
    assert columns_fingerprint(*a) == columns_fingerprint(*_fresh(a))
    assert columns_fingerprint(*a) != columns_fingerprint(*_reordered(a, 5))
    assert columns_fingerprint(*a) != columns_fingerprint(*_flipped(a, 32, k=1))
    assert columns_fingerprint(*a) != columns_fingerprint(*(col[:-1] for col in a))


# -- a lone request over the cap (ISSUE 36) ------------------------------------------

BIG = 2 * CAP + 8  # a blocksync prefetch window against the cap: 31,744 lanes against 16,384


class _Columns(_Gate):
    """`_Gate` that also keeps the columns of every call after the blocker's."""

    def __init__(self):
        super().__init__()
        self.columns: list[tuple] = []

    def batch_verify(self, pubs, msgs, sigs):
        if self.entered.is_set():
            self.columns.append((list(pubs), list(msgs), list(sigs)))
        return super().batch_verify(pubs, msgs, sigs)


def _over_the_cap():
    big, small = _signed(11, BIG), _signed(12, 8)
    return {
        # name: (requests, the lanes of each dispatch, which request each dispatch carries whole)
        "alone": ([big], [BIG], [0]),
        "with-flipped-lanes": ([_flipped(big, 36)], [BIG], [0]),
        "behind-a-small-request": ([small, big], [8, BIG], [0, 1]),
        "before-a-small-request": ([big, small], [BIG, 8], [0, 1]),
        "and-a-copy-of-it": ([big, _fresh(big)], [BIG], [0]),
        "and-a-flipped-copy": ([big, _flipped(big, 37)], [BIG, BIG], [0, 1]),
    }


OVER_THE_CAP = _over_the_cap()


@pytest.mark.parametrize("name", sorted(OVER_THE_CAP))
def test_a_lone_request_over_the_cap_is_one_dispatch_in_order_answered_whole(name):
    """The cap bounds what is merged, never a request: one that alone offers
    more than the cap runs as ONE dispatch of its own columns, in the order
    it was queued, and gets the answer it would get alone; only a copy of it
    rides along."""
    requests, want_calls, carried = OVER_THE_CAP[name]
    gate = _Columns()
    answers, calls, counters = _through_one_engine(requests, gate=gate)
    assert answers == answers_alone(requests)
    assert all(len(bits) == len(r[0]) for (_, bits), r in zip(answers, requests)), "answered whole"
    assert calls == want_calls
    assert gate.columns == [tuple(list(col) for col in requests[i]) for i in carried], (
        "each dispatch is one request's own columns, nothing cut, nothing reordered")
    assert counters["dispatches"] == 1 + len(calls) and counters["max_sigs"] == CAP
    assert counters["dedup_sigs"] == sum(len(r[0]) for r in requests) - sum(calls)


# -- a request that arrives while the same columns are in flight (ISSUE 33) ---------


class _Stops(VerifyBackend):
    """The host tier with stops: a call whose number (from 0) is in `stops`
    says that it is in flight and waits there until the test lets it go;
    `spoil` then makes of call 0 a dispatch that fails. Every call's lanes
    are recorded."""

    name = "stops"

    def __init__(self, stops=(0,), spoil=None, failing_calls=()):
        self.cpu = CpuBackend()
        self.in_flight = {i: threading.Event() for i in stops}
        self.go = {i: threading.Event() for i in stops}
        self.spoil = spoil
        self.failing_calls = failing_calls
        self.calls: list[int] = []

    def batch_verify(self, pubs, msgs, sigs):
        i = len(self.calls)
        self.calls.append(len(pubs))
        if i in self.go:
            self.in_flight[i].set()
            assert self.go[i].wait(30)
        if i in self.failing_calls or (i == 0 and self.spoil == "raises"):
            raise RuntimeError(f"call {i} fails")
        ok, bits = self.cpu.batch_verify(pubs, msgs, sigs)
        if i == 0 and self.spoil == "wrong-shape":
            return ok, bits[:-1]
        return ok, bits

    def let_go(self):
        for event in self.go.values():
            event.set()


def _engine(backend, cap=CAP):
    return VerificationEngine(backend, hold_ms=0.0, max_sigs=cap, starvation_ms=0.0)


def _while_in_flight(first, later, backend=None):
    """`first` dispatched alone and held in flight while `later` are
    submitted; then it is let go. Every answer (first's first; an error in
    place of an answer that raised), the lanes of every call of the chain,
    the engine's counters."""
    backend = backend or _Stops()
    eng = _engine(backend)
    try:
        futs = [eng.submit(*first)]
        assert backend.in_flight[0].wait(10)
        futs += [eng.submit(*r) for r in later]
        backend.let_go()
        answers = []
        for f in futs:
            try:
                answers.append(f.result(60))
            except RuntimeError as e:
                answers.append(e)
        return answers, backend.calls, eng.counters()
    finally:
        backend.let_go()
        eng.close()


def _flipped_at(req, lane: int):
    pubs, msgs, sigs = _fresh(req)
    sigs[lane] = sigs[lane][:7] + bytes([sigs[lane][7] ^ 0x10]) + sigs[lane][8:]
    return pubs, msgs, sigs


def _arrivals():
    a, b = CASES["distinct-over-the-cap"][0][:2]
    shorter = tuple(col[:-1] for col in _fresh(a))
    return {
        # name: (what is submitted while `a` is in flight, the lanes of the calls after a's, how
        #        many of them are compared entry for entry: those alike in the first and last triple)
        "three-copies": ([_fresh(a) for _ in range(3)], [], 3),
        "a-copy-flipped-in-the-middle": ([_flipped_at(a, 7)], [LANES], 1),
        "a-copy-flipped-at-its-first-lane": ([_flipped_at(a, 0)], [LANES], 0),
        "a-copy-flipped-at-its-last-lane": ([_flipped_at(a, LANES - 1)], [LANES], 0),
        "a-reordered-copy-with-the-ends-in-place": (
            [tuple([col[0]] + col[1:-1][::-1] + [col[-1]] for col in _fresh(a))], [LANES], 1),
        "a-reordered-copy": ([_reordered(a, 5)], [LANES], 0),
        "a-copy-one-triple-shorter": ([shorter], [LANES - 1], 0),
        "the-same-length-other-content": ([_fresh(b)], [LANES], 0),
        "two-copies-about-a-flipped-one": ([_fresh(a), _flipped_at(a, 7), _fresh(a)], [LANES], 3),
    }


ARRIVALS = _arrivals()


@pytest.mark.parametrize("name", sorted(ARRIVALS))
def test_a_copy_of_the_request_in_flight_takes_its_answer_and_nothing_else_does(name, monkeypatch):
    """Copies are answered by the dispatch in flight (the chain is not called
    for them); whatever differs in a byte, in order or in length is queued
    and run as before; `_same_columns` is never called where the O(1) check
    fails; every answer is the one the request would get alone."""
    import cometbft_tpu.sidecar.engine as engine_mod

    compared = []
    real = engine_mod._same_columns
    monkeypatch.setattr(engine_mod, "_same_columns", lambda a, b: compared.append(1) or real(a, b))
    first = _fresh(CASES["identical-x4"][0][0])
    later, want_calls, want_compared = ARRIVALS[name]
    answers, calls, counters = _while_in_flight(first, later)
    assert answers == answers_alone([first] + later)
    assert calls == [LANES] + want_calls and len(compared) == want_compared
    joined = sum(r == first for r in later)
    assert joined == len(later) - len(want_calls)
    assert counters["requests"] == 1 + len(later)
    assert counters["dispatches"] == len(calls)
    assert (counters["joined_requests"], counters["joined_sigs"]) == (joined, joined * LANES)
    assert counters["dedup_sigs"] == joined * LANES, "lanes offered that the chain did not run again"
    assert counters["batched_requests"] == (1 + joined if joined else 0)
    assert counters["coalesced_dispatches"] == (1 if joined else 0)
    assert counters["fallback_splits"] == 0 and counters["queue_depth"] == 0


def test_a_copy_joins_its_kind_of_a_merged_dispatch_in_flight():
    """Two requests that overlap in part are in flight as one merged dispatch
    of their 18 distinct lanes: a copy of either joins it and is given that
    request's lanes, in its own order; a flipped copy is queued."""
    a = CASES["identical-x4"][0][0]
    x, y = (tuple(col[:12] for col in a), tuple(col[6:18] for col in a))
    backend = _Stops(stops=(0, 1))
    eng = _engine(backend, cap=64)
    try:
        blocker = eng.submit([b"b" * 32], [b"blocker"], [b"s" * 64])
        assert backend.in_flight[0].wait(10)
        requests = [_fresh(x), _fresh(y)]
        futs = [eng.submit(*r) for r in requests]
        backend.go[0].set()
        assert backend.in_flight[1].wait(10)
        requests += [_fresh(y), _flipped_at(y, 4), _fresh(x), _fresh(y)]
        futs += [eng.submit(*r) for r in requests[2:]]
        backend.let_go()
        blocker.result(30)
        assert [f.result(60) for f in futs] == answers_alone(requests)
        assert [f.shared for f in futs] == [True] * 3 + [False] + [True] * 2
    finally:
        backend.let_go()
        eng.close()
    assert backend.calls == [1, 18, 12]
    counters = eng.counters()
    assert (counters["joined_requests"], counters["joined_sigs"]) == (3, 36)
    assert counters["dedup_sigs"] == (24 - 18) + 36
    assert counters["batched_requests"] == 5 and counters["coalesced_dispatches"] == 1


@pytest.mark.parametrize("spoil, failing_calls", [
    ("raises", ()), ("wrong-shape", ()), ("raises", (2,)),
], ids=["the-dispatch-raises", "the-dispatch-answers-with-the-wrong-shape",
        "the-dispatch-raises-and-so-does-one-retry"])
def test_a_failed_dispatch_leaves_each_request_that_joined_it_its_own_answer(spoil, failing_calls):
    """The requests that joined go through `_fallback` with the batch: each
    is retried alone and gets its own answer or its own error; none inherits
    another request's."""
    a = CASES["identical-x4"][0][0]
    first, later = _fresh(a), [_fresh(a), _fresh(a)]
    answers, calls, counters = _while_in_flight(
        first, later, backend=_Stops(spoil=spoil, failing_calls=failing_calls))
    want = answers_alone([first] + later)
    for i, (got, alone) in enumerate(zip(answers, want)):
        if 1 + i in failing_calls:  # call 0 is the dispatch, call 1 + i the retry of request i
            assert isinstance(got, RuntimeError) and str(got) == f"call {1 + i} fails"
        else:
            assert got == alone
    assert calls == [LANES] * 4
    assert counters["fallback_splits"] == 1 and counters["joined_requests"] == 2


def test_a_lone_dispatch_that_nobody_joined_still_fails_to_its_caller_alone():
    answers, calls, counters = _while_in_flight(
        _fresh(CASES["identical-x4"][0][0]), [], backend=_Stops(spoil="raises"))
    assert isinstance(answers[0], RuntimeError) and calls == [LANES]
    assert counters["fallback_splits"] == 0


def test_a_comparison_that_ends_after_the_dispatch_returned_is_queued_not_lost(monkeypatch):
    """The join is decided under the lock, on the identity of what is in
    flight: a submitter still comparing when the dispatch returns runs as a
    dispatch of its own."""
    import cometbft_tpu.sidecar.engine as engine_mod

    a = CASES["identical-x4"][0][0]
    backend = _Stops()
    eng = _engine(backend)
    real = engine_mod._same_columns
    futs = []

    def slow(x, y):
        backend.let_go()
        futs[0].result(30)  # the dispatch has returned and is answered
        return real(x, y)

    monkeypatch.setattr(engine_mod, "_same_columns", slow)
    try:
        futs.append(eng.submit(*_fresh(a)))
        assert backend.in_flight[0].wait(10)
        futs.append(eng.submit(*_fresh(a)))
        assert [f.result(60) for f in futs] == answers_alone([a, a])
    finally:
        backend.let_go()
        eng.close()
    assert backend.calls == [LANES, LANES]
    counters = eng.counters()
    assert counters["joined_requests"] == 0 and counters["dispatches"] == 2 == counters["requests"]


def test_what_the_ring_says_of_a_joined_dispatch(profiler_ring):
    """The dispatch's span closes with what it ended up answering; a request
    that joined has an `engine.join` and no `engine.queue_wait`; the late
    slice is an `engine.merge` under the dispatch."""
    a = CASES["identical-x4"][0][0]
    t0 = time.perf_counter()
    answers, calls, _ = _while_in_flight(_fresh(a), [_fresh(a) for _ in range(3)])
    t1 = time.perf_counter()
    assert calls == [LANES] and answers == answers_alone([a] * 4)
    spans = trace.spans()
    (dispatch,) = [s for s in spans if s["name"] == "engine.dispatch"]
    attrs = dispatch["attrs"]
    assert (attrs["requests"], attrs["lanes"], attrs["unique"], attrs["joined"], attrs["dedup"]) == (
        4, 4 * LANES, LANES, 3, 3 * LANES)
    merges = [s for s in spans if s["name"] == "engine.merge"]
    assert [(s["attrs"]["phase"], s["parent"]) for s in merges] == [("slice", dispatch["id"])]
    assert len([s for s in spans if s["name"] == "engine.queue_wait"]) == 1, "the first request's"
    joins = [s for s in spans if s["name"] == "engine.join"]
    assert [s["attrs"]["lanes"] for s in joins] == [LANES] * 3
    assert all(s["attrs"]["compare_ms"] >= 0 and t0 <= s["t0"] <= s["t1"] <= t1 for s in joins)
    assert all(dispatch["t0"] <= s["t0"] and s["t1"] <= dispatch["t1"] for s in joins)
