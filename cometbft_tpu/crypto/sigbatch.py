"""Micro-batched scalar signature verification for the consensus hot path.

`VoteSet.add_vote` (and evidence duplicate-vote checks) verify ONE signature
at a time, but under gossip many admissions run concurrently — one per peer
connection, across every in-process node in devnet. This module gives those
scalar callers consensus-class admission into the continuous-batching
verification engine (round 14, `sidecar/engine.py`): each caller submits
its pending triples tagged CLASS_CONSENSUS and the engine merges everything
queued — across vote sets, peers AND the other traffic classes — into the
next device dispatch, draining votes ahead of bulk work under a deadline
bound. Cache semantics are unchanged: pending triples are filtered against
the verified-triple cache here and only VALID dispatched triples populate
it afterward.

When no engine is active (`CMTPU_COALESCE=0`, or a bare backend installed
by tests) the round-12 private window dispatcher runs instead:
callers block on a shared window (`CMTPU_VOTE_BATCH_WINDOW_MS`, default
2 ms from the first waiter) and a dispatcher merges everything queued into
ONE `ed25519.BatchVerifier` call.

Failure containment is identical on both paths: a bad signature is just a
False lane (never poisons the window), and any dispatch-level error —
including a result not arriving within the deadline-derived timeout —
degrades each request independently to the scalar `verify_signature` path.
Window 0 (the env off switch) keeps the inline scalar behavior exactly.
"""

from __future__ import annotations

import os
import threading
import time

_DEFAULT_WINDOW_MS = 2.0
# A caller never waits forever on the dispatcher: consensus liveness
# outranks batching, so a wedged dispatch degrades to scalar verification.
# Used verbatim only when no supervisor deadline is configured — see
# _result_timeout_s().
_RESULT_TIMEOUT_S = 30.0


def _result_timeout_s() -> float:
    """How long a caller waits on a dispatch result before degrading to
    scalar verification. With a supervised per-call deadline configured
    (`CMTPU_DEADLINE_MS`), the worst honest wall is every tier of the
    chain burning its retries under that deadline — wait that long, not a
    hard-coded 30 s, so a wedge degrades in one supervised exhaustion.
    Deadline 0/unset keeps the legacy 30 s backstop."""
    try:
        deadline_ms = float(os.environ.get("CMTPU_DEADLINE_MS", "") or 0.0)
    except ValueError:
        deadline_ms = 0.0
    if deadline_ms <= 0:
        return _RESULT_TIMEOUT_S
    try:
        retries = int(os.environ.get("CMTPU_RETRIES", "") or 2)
    except ValueError:
        retries = 2
    # <= 3 tiers (grpc|tpu -> hybrid -> cpu), each (retries+1) attempts.
    return max(1.0, deadline_ms / 1000.0 * (retries + 1) * 3)


class _Req:
    __slots__ = ("pubs", "msgs", "sigs", "event", "bits")

    def __init__(self, pubs, msgs, sigs):
        self.pubs = pubs
        self.msgs = msgs
        self.sigs = sigs
        self.event = threading.Event()
        self.bits: list[bool] | None = None


class SigBatcher:
    """Window-from-first-waiter batcher over `ed25519.BatchVerifier`.

    `inline` (test hook) dispatches each request through the batch
    verifier immediately with no window and no dispatcher thread — the
    "one device dispatch per vote" arm of an A/B comparison.
    """

    def __init__(self, window_ms: float | None = None, max_sigs: int = 4096,
                 inline: bool = False):
        if window_ms is None:
            window_ms = float(
                os.environ.get("CMTPU_VOTE_BATCH_WINDOW_MS", "") or _DEFAULT_WINDOW_MS
            )
        self.window_ms = window_ms
        self.max_sigs = max_sigs
        self.inline = inline
        self.result_timeout_s = _result_timeout_s()
        self._cond = threading.Condition()
        self._queue: list[_Req] = []
        self._thread: threading.Thread | None = None
        self._closed = False
        # Counters (read by the lazy node gauges; mutate under _cond).
        self.requests = 0
        self.batched = 0  # requests that rode a shared dispatch
        self.dispatches = 0
        self.dispatched_sigs = 0
        self.cache_hits = 0
        self.scalar_direct = 0
        self.fallbacks = 0
        self.max_batch = 0

    # -- public API -----------------------------------------------------------

    def verify_one(self, pub_key, msg: bytes, sig: bytes) -> bool:
        return self.verify_many([pub_key], [msg], [sig])[0]

    def verify_many(self, pub_keys, msgs, sigs) -> list[bool]:
        from cometbft_tpu.crypto import ed25519 as _ed

        n = len(pub_keys)
        bits: list[bool | None] = [None] * n
        pend: list[int] = []
        cache_hits = scalar = 0
        for i in range(n):
            pk = pub_keys[i]
            if not isinstance(pk, _ed.PubKey):
                # Only ed25519 has a batch backend; exotic key types keep
                # their own scalar verify.
                bits[i] = bool(pk.verify_signature(msgs[i], sigs[i]))
                scalar += 1
            elif (
                len(sigs[i]) != _ed.SIGNATURE_SIZE
                or len(pk.bytes()) != _ed.PUB_KEY_SIZE
            ):
                # Structurally impossible — reject without letting it poison
                # a batch (BatchVerifier.add raises on bad sizes).
                bits[i] = False
            elif (pk.bytes(), bytes(sigs[i]), bytes(msgs[i])) in _ed._verified:
                # Gossip re-delivery and own-vote echo land here: free.
                bits[i] = True
                cache_hits += 1
            else:
                pend.append(i)
        with self._cond:
            self.requests += 1
            self.cache_hits += cache_hits
            self.scalar_direct += scalar
        if not pend:
            return bits  # type: ignore[return-value]
        if self.window_ms <= 0 and not self.inline:
            # Off switch: today's inline scalar path, verbatim.
            for i in pend:
                bits[i] = bool(pub_keys[i].verify_signature(msgs[i], sigs[i]))
            with self._cond:
                self.scalar_direct += len(pend)
            return bits  # type: ignore[return-value]
        if not self.inline:
            eng = self._engine()
            if eng is not None:
                # Continuous-batching path: no private window thread — the
                # engine merges concurrent admissions (and the other
                # traffic classes) itself, votes first.
                pbits = self._engine_dispatch(eng, pub_keys, msgs, sigs, pend)
                for j, i in enumerate(pend):
                    bits[i] = pbits[j]
                return bits  # type: ignore[return-value]
        req = _Req(
            [pub_keys[i] for i in pend],
            [msgs[i] for i in pend],
            [sigs[i] for i in pend],
        )
        if self.inline:
            self._dispatch([req])
        else:
            with self._cond:
                self._queue.append(req)
                if self._thread is None or not self._thread.is_alive():
                    self._thread = threading.Thread(
                        target=self._run, name="sigbatch", daemon=True
                    )
                    self._thread.start()
                self._cond.notify_all()
            if not req.event.wait(self.result_timeout_s):
                req.bits = [
                    bool(pk.verify_signature(m, s))
                    for pk, m, s in zip(req.pubs, req.msgs, req.sigs)
                ]
        for j, i in enumerate(pend):
            bits[i] = bool(req.bits[j])
        return bits  # type: ignore[return-value]

    # -- engine path ----------------------------------------------------------

    @staticmethod
    def _engine():
        """The active continuous-batching engine, or None when the backend
        chain runs bare (`CMTPU_COALESCE=0`, or a test-installed backend) —
        the legacy private-window dispatcher serves those."""
        from cometbft_tpu.sidecar import backend as _be
        from cometbft_tpu.sidecar import engine as _engine

        try:
            return _engine.engine_of(_be.get_backend())
        except Exception:
            return None

    def _engine_dispatch(self, eng, pub_keys, msgs, sigs, pend) -> list[bool]:
        """Submit the pending triples consensus-class and wait. Decision
        path matches the legacy dispatcher bit for bit: only VALID
        dispatched triples populate the verified cache, and any failure —
        engine error, chain exhaustion surfacing as an exception, or the
        deadline-derived timeout — degrades THIS request alone to the
        scalar anchor."""
        from cometbft_tpu.crypto import ed25519 as _ed
        from cometbft_tpu.sidecar.engine import CLASS_CONSENSUS

        pubs = [pub_keys[i].bytes() for i in pend]
        ms = [bytes(msgs[i]) for i in pend]
        ss = [bytes(sigs[i]) for i in pend]
        try:
            fut = eng.submit(pubs, ms, ss, klass=CLASS_CONSENSUS)
            _, rbits = fut.result(self.result_timeout_s)
            rbits = [bool(b) for b in rbits]
        except Exception:
            with self._cond:
                self.fallbacks += 1
            return [
                bool(pub_keys[i].verify_signature(msgs[i], sigs[i]))
                for i in pend
            ]
        _ed._verified_put_many(
            [(p, s, m) for p, m, s, b in zip(pubs, ms, ss, rbits) if b]
        )
        with self._cond:
            self.dispatches += 1
            self.dispatched_sigs += len(pend)
            if fut.shared:
                self.batched += 1
            self.max_batch = max(self.max_batch, len(pend))
        return rbits

    def close(self) -> None:
        with self._cond:
            self._closed = True
            self._cond.notify_all()

    def counters(self) -> dict:
        with self._cond:
            return {
                "requests": self.requests,
                "batched": self.batched,
                "dispatches": self.dispatches,
                "dispatched_sigs": self.dispatched_sigs,
                "cache_hits": self.cache_hits,
                "scalar_direct": self.scalar_direct,
                "fallbacks": self.fallbacks,
                "max_batch": self.max_batch,
            }

    # -- dispatcher -----------------------------------------------------------

    def _run(self) -> None:
        while True:
            with self._cond:
                while not self._queue and not self._closed:
                    self._cond.wait()
                if self._closed and not self._queue:
                    return
            # Window from the FIRST waiter (scheduler/ingress idiom): the
            # request that opened the window pays it once; everything that
            # arrives inside rides free.
            if self.window_ms > 0:
                time.sleep(self.window_ms / 1000.0)
            with self._cond:
                batch: list[_Req] = []
                total = 0
                while self._queue:
                    nxt = len(self._queue[0].pubs)
                    if batch and total + nxt > self.max_sigs:
                        break  # whole requests only; rest opens a new window
                    total += nxt
                    batch.append(self._queue.pop(0))
            if batch:
                self._dispatch(batch)

    def _dispatch(self, reqs: list[_Req]) -> None:
        from cometbft_tpu.crypto import ed25519 as _ed

        total = sum(len(r.pubs) for r in reqs)
        try:
            bv = _ed.BatchVerifier()
            for r in reqs:
                for pk, m, s in zip(r.pubs, r.msgs, r.sigs):
                    bv.add(pk, m, s)
            # BatchVerifier.verify(): cache filter + dedup, scheduler →
            # supervised chain, ZIP-215 scalar fallback on ChainExhausted.
            _, bits = bv.verify()
        except Exception:
            # Per-request isolation: degrade each request to the scalar
            # anchor independently — one hostile entry or a backend crash
            # must never reject a whole window of valid votes.
            with self._cond:
                self.fallbacks += len(reqs)
            for r in reqs:
                try:
                    r.bits = [
                        bool(pk.verify_signature(m, s))
                        for pk, m, s in zip(r.pubs, r.msgs, r.sigs)
                    ]
                except Exception:
                    r.bits = [False] * len(r.pubs)
                r.event.set()
            return
        with self._cond:
            self.dispatches += 1
            self.dispatched_sigs += total
            if len(reqs) > 1:
                self.batched += len(reqs)
            self.max_batch = max(self.max_batch, total)
        i = 0
        for r in reqs:
            n = len(r.pubs)
            r.bits = [bool(b) for b in bits[i : i + n]]
            i += n
            r.event.set()


# -- module singleton ---------------------------------------------------------

_batcher: SigBatcher | None = None
_lock = threading.Lock()


def get_batcher() -> SigBatcher:
    """The process-wide batcher (constructed lazily from env)."""
    global _batcher
    b = _batcher
    if b is None:
        with _lock:
            if _batcher is None:
                _batcher = SigBatcher()
            b = _batcher
    return b


def set_batcher(b: SigBatcher | None) -> SigBatcher | None:
    """Install a batcher (tests); returns the previous one."""
    global _batcher
    with _lock:
        old, _batcher = _batcher, b
    return old


def reset() -> None:
    """Drop the singleton so the next use re-reads env knobs."""
    set_batcher(None)


def verify_vote_signature(pub_key, msg: bytes, sig: bytes) -> bool:
    return get_batcher().verify_one(pub_key, msg, sig)


def verify_triples(pub_keys, msgs, sigs) -> list[bool]:
    return get_batcher().verify_many(pub_keys, msgs, sigs)


def counters() -> dict:
    """Counters WITHOUT constructing a batcher (lazy metric scrapes)."""
    b = _batcher
    if b is None:
        return {
            "requests": 0, "batched": 0, "dispatches": 0, "dispatched_sigs": 0,
            "cache_hits": 0, "scalar_direct": 0, "fallbacks": 0, "max_batch": 0,
        }
    return b.counters()
