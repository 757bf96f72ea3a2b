"""Micro-batched scalar signature verification for the consensus hot path.

`VoteSet.add_vote` (and evidence duplicate-vote checks) verify ONE signature
at a time, but under gossip many admissions run concurrently — one per peer
connection, across every in-process node in devnet. This module gives those
scalar callers consensus-class admission into the continuous-batching
verification engine (`sidecar/engine.py`): each caller submits its pending
triples tagged CLASS_CONSENSUS and the engine merges everything queued —
across vote sets, peers AND the other traffic classes — into the next
device dispatch, draining votes ahead of bulk work under a deadline bound.
Cache semantics: pending triples are filtered against the verified-triple
cache here and only VALID dispatched triples populate it afterward.

The engine is the process backend's own (`engine_of(get_backend())`, what
`CMTPU_BACKEND=auto` builds). A backend with no engine in front (an
explicit `cpu`/`tpu`/`hybrid`/`grpc` choice, or one a test installs) gets
one of this batcher's own over it, built on first use with the batcher's
window (`CMTPU_VOTE_BATCH_WINDOW_MS`, default 2 ms from the first waiter)
as its hold and `max_sigs` as its cap.

A bad signature is just a False lane (never poisons the dispatch), and any
dispatch-level error — including a result not arriving within the
deadline-derived timeout — degrades that request alone to the scalar
`verify_signature` path. Window 0 (the env off switch) keeps the inline
scalar behavior exactly.
"""

from __future__ import annotations

import os
import threading

_DEFAULT_WINDOW_MS = 2.0
# A caller never waits forever on the engine: consensus liveness outranks
# batching, so a wedged dispatch degrades to scalar verification. Used
# verbatim only when no supervisor deadline is configured — see
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


class SigBatcher:
    """Consensus-class admission of scalar checks into the verification
    engine (module docstring)."""

    def __init__(self, window_ms: float | None = None, max_sigs: int = 4096):
        if window_ms is None:
            window_ms = float(
                os.environ.get("CMTPU_VOTE_BATCH_WINDOW_MS", "") or _DEFAULT_WINDOW_MS
            )
        self.window_ms = window_ms
        self.max_sigs = max_sigs
        self.result_timeout_s = _result_timeout_s()
        self._lock = threading.Lock()
        self._own = None  # the engine over a bare process backend, once needed
        # Counters (read by the lazy node gauges; mutate under _lock).
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
                # a batch.
                bits[i] = False
            elif (pk.bytes(), bytes(sigs[i]), bytes(msgs[i])) in _ed._verified:
                # Gossip re-delivery and own-vote echo land here: free.
                bits[i] = True
                cache_hits += 1
            else:
                pend.append(i)
        with self._lock:
            self.requests += 1
            self.cache_hits += cache_hits
            self.scalar_direct += scalar
        if not pend:
            return bits  # type: ignore[return-value]
        if self.window_ms <= 0:
            # Off switch: today's inline scalar path, verbatim.
            for i in pend:
                bits[i] = bool(pub_keys[i].verify_signature(msgs[i], sigs[i]))
            with self._lock:
                self.scalar_direct += len(pend)
            return bits  # type: ignore[return-value]
        pbits = self._engine_dispatch(pub_keys, msgs, sigs, pend)
        for j, i in enumerate(pend):
            bits[i] = pbits[j]
        return bits  # type: ignore[return-value]

    # -- engine path ----------------------------------------------------------

    def _engine(self):
        """The process backend's engine, else this batcher's own over the
        backend (built again if the process backend was replaced)."""
        from cometbft_tpu.sidecar import backend as _be
        from cometbft_tpu.sidecar.engine import VerificationEngine, engine_of

        backend = _be.get_backend()
        eng = engine_of(backend)
        if eng is not None:
            return eng
        with self._lock:
            if self._own is None or self._own.inner is not backend:
                self._own = VerificationEngine(
                    backend, hold_ms=self.window_ms, max_sigs=self.max_sigs
                )
            return self._own

    def _engine_dispatch(self, pub_keys, msgs, sigs, pend) -> list[bool]:
        """Submit the pending triples consensus-class and wait. Only VALID
        dispatched triples populate the verified cache, and any failure —
        no backend, engine error, chain exhaustion surfacing as an
        exception, or the deadline-derived timeout — degrades THIS request
        alone to the scalar anchor."""
        from cometbft_tpu.crypto import ed25519 as _ed
        from cometbft_tpu.sidecar.engine import CLASS_CONSENSUS

        pubs = [pub_keys[i].bytes() for i in pend]
        ms = [bytes(msgs[i]) for i in pend]
        ss = [bytes(sigs[i]) for i in pend]
        try:
            fut = self._engine().submit(pubs, ms, ss, klass=CLASS_CONSENSUS)
            _, rbits = fut.result(self.result_timeout_s)
            rbits = [bool(b) for b in rbits]
        except Exception:
            with self._lock:
                self.fallbacks += 1
            return [
                bool(pub_keys[i].verify_signature(msgs[i], sigs[i]))
                for i in pend
            ]
        _ed._verified_put_many(
            [(p, s, m) for p, m, s, b in zip(pubs, ms, ss, rbits) if b]
        )
        with self._lock:
            self.dispatches += 1
            self.dispatched_sigs += len(pend)
            if fut.shared:
                self.batched += 1
            self.max_batch = max(self.max_batch, len(pend))
        return rbits

    def counters(self) -> dict:
        with self._lock:
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
