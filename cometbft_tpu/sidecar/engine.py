"""Continuous-batching verification engine (round 14).

Until this round FOUR independent micro-batch windows fed the device tier —
the coalescer (sidecar/scheduler.py), vote admission (crypto/sigbatch.py),
ingress preverify (mempool/ingress.py) and the gateway prewarm
(light/gateway.py) — each with its own window knob, queue, dispatcher
thread and fallback path, each holding work the others could ride with.
This module is the one engine they all feed, run the way inference servers
run their device (vLLM/Orca continuous batching):

* There is no window-then-dispatch. The dispatcher sizes the next dispatch
  from whatever is queued THE MOMENT the device frees up — a burst's first
  request pays only the in-flight dispatch, never a fixed window. (A
  compat hold, `hold_ms`, reproduces the old window-from-first-waiter
  behavior for the CoalescingScheduler shim and its tests; the engine
  default is 0.)
* Requests carry a PRIORITY CLASS — consensus votes > blocksync > ingress
  preverify > light clients — drained strict-priority with a starvation
  escape hatch: any request older than `CMTPU_ENGINE_STARVATION_MS` is
  promoted ahead of fresher higher-class work, so a consensus flood can
  delay a light client but never park it forever.
* Dispatch sizing is DEADLINE-AWARE: a queued consensus request caps how
  large the merged dispatch may grow, using the hybrid planner's rate
  model (sigs/ms x chips + fixed overhead) to predict the dispatch wall —
  bulk work never drags a vote past its admission deadline.
* Fallback/crosscheck/degradation remain ONE story: the engine dispatches
  through whatever chain it wraps (normally `build_resilient()`'s
  supervisor), keeps the columnar pack + within-batch dedup + per-request
  bitmap slicing of the round-6 coalescer verbatim, and splits a failed
  merged dispatch into per-request retries so a poisoned request errors
  alone.
* The merge cap is on the lanes a dispatch will RUN, not on the lanes its
  requests offered (PR 32): queued requests that carry the same triples
  (N nodes of one chain sent the same commit on N connections) cost the cap
  their distinct lanes once. Sameness is read off a fingerprint of a
  request's whole columns (`columns_fingerprint`), taken by the dispatcher
  outside the lock and only when the queue offers more than the cap holds;
  alone it sizes the dispatch and nothing else: in the pack a request is a
  copy of an earlier one only if its columns compare equal entry for entry
  (then it takes that one's lanes and the per-triple walk, which decides
  every other shared lane, sees each distinct request once). So four
  queued copies of one 10,000-signature commit are one 10,000-lane call on
  a 16,384-lane chip, with no walk at all;
  requests that overlap only in part are sized by what they offered (an
  upper bound of their distinct lanes), two different 10,000-lane commits
  still take two dispatches (a copy of what is already in costs nothing
  and rides along, even past a request that did not fit; nothing that
  adds lanes passes one). The cap bounds what is MERGED, never a request: a
  lone request that offers more than the cap (a blocksync prefetch window
  of 31 x 1,024 = 31,744 lanes against 16,384 a chip) is the first of its
  dispatch whatever its size, so it runs as one dispatch of its own columns
  in their order and is answered whole; only a copy of it rides along.
* A request that arrives while the same columns are IN FLIGHT takes that
  dispatch's answer (PR 33, in-flight join): while the chain runs a
  dispatch the engine publishes what it carries (its distinct requests and
  each one's lanes in the dispatched columns); `submit()` holds a new
  request against each in O(1) (lane count, first and last triple) and only
  one that passes is compared entry for entry, outside the lock, on the
  submitter's thread. Equal, and the dispatch still in flight: the request
  is attached to it and answered from its bitmap when it returns, never
  queued for a second run of the same lanes. Whatever differs in a byte, in
  order or in length is queued as before; nothing is kept past the
  dispatch. A dispatch that fails retries the requests that joined it with
  its batch, each alone. So four nodes of one chain cost the chip one run a
  height, whenever they arrive. No answer depends on who shared the
  dispatch.

Callers tag their class either explicitly (`engine.submit(..., klass=...)`)
or ambiently via `submission_class(...)` — a threadlocal the engine reads
for traffic that reaches it through `ed25519.BatchVerifier` and the
backend chain without any API change (ingress preverify, gateway prewarm,
blocksync windows). Untagged traffic is blocksync-class: the middle of the
ladder, below votes, above opportunistic prewarm.

Knobs: `CMTPU_ENGINE_HOLD_MS` (compat hold, default 0 = continuous),
`CMTPU_ENGINE_MAX` (merge cap on a dispatch's distinct lanes, default
16384 x mesh width, auto caps grow-only via refresh_cap),
`CMTPU_ENGINE_STARVATION_MS` (promotion age, default 100),
`CMTPU_ENGINE_DEADLINE_MS` (consensus admission deadline,
default `CMTPU_DEADLINE_MS` else 50), `CMTPU_ENGINE_RATE` /
`CMTPU_ENGINE_OVERHEAD_MS` (fallback dispatch-wall model when no hybrid
tier is present to read rates from).
"""

from __future__ import annotations

import contextlib
import os
import sys
import threading
import time

from cometbft_tpu.libs import trace
from cometbft_tpu.sidecar.backend import VerifyBackend

# Priority classes, drained strict-priority (lower value wins).
CLASS_CONSENSUS = 0  # vote admission / commit verification on the hot path
CLASS_BLOCKSYNC = 1  # block-window pre-verify, untagged legacy callers
CLASS_INGRESS = 2    # mempool envelope preverify
CLASS_LIGHT = 3      # light-client speculative prewarm

CLASS_NAMES = ("consensus", "blocksync", "ingress", "light")
_N_CLASSES = len(CLASS_NAMES)

_WAIT_SAMPLES = 512  # admission-wait ring buffer (p50/p95 source)


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _mesh_width_for_cap() -> int:
    """Device count behind the default dispatch cap (16384 x width), read
    WITHOUT this constructor being what starts the device backend: use the
    kernel's already-probed width when available (the auto chain constructs
    its device tier — which probes — before this layer), and only probe
    ourselves when JAX is pinned to the local CPU backend with a forced
    virtual device count (the test/dryrun mesh). Everywhere else the probe
    would put backend start-up on a node's start path, and a cpu-only
    deployment shouldn't pay a jax import for a cap it can't use."""
    ek = sys.modules.get("cometbft_tpu.ops.ed25519_kernel")
    if ek is not None and ek.known_mesh_width():
        return ek.known_mesh_width()
    if (
        os.environ.get("JAX_PLATFORMS", "") == "cpu"
        and "xla_force_host_platform_device_count"
        in os.environ.get("XLA_FLAGS", "")
    ):
        try:
            from cometbft_tpu.ops import ed25519_kernel as ek2

            return ek2.mesh_width()
        except Exception:
            return 1
    return 1


# -- ambient class tagging ----------------------------------------------------

_tls = threading.local()


@contextlib.contextmanager
def submission_class(klass: int):
    """Tag every engine submission made on this thread inside the block.

    This is how surfaces that reach the engine through BatchVerifier and
    the backend chain (ingress, gateway, blocksync) declare their class
    without threading a parameter through crypto-layer APIs."""
    prev = getattr(_tls, "klass", None)
    _tls.klass = klass
    try:
        yield
    finally:
        _tls.klass = prev


def current_class() -> int:
    k = getattr(_tls, "klass", None)
    return CLASS_BLOCKSYNC if k is None else k


def columns_fingerprint(pubs, msgs, sigs) -> int:
    """What two requests that carry the same triples in the same order have
    in common: a hash of their whole columns. It sizes a merged dispatch
    (`_collect`) and, in the pack, names the earlier request a later one is
    then compared with entry for entry; alone it decides no answer, so the
    interpreter's keyed 64-bit hash is enough. It also leaves every entry's
    hash cached for the pack's per-triple dedup."""
    return hash((tuple(pubs), tuple(msgs), tuple(sigs)))


def _same_columns(a: "_Request", b: "_Request") -> bool:
    """The exact test the fingerprint only suggests: the same triples in the
    same order, entry for entry."""
    return a.pubs == b.pubs and a.msgs == b.msgs and a.sigs == b.sigs


def _may_be_copy(a: "_Request", b: "_Request") -> bool:
    """What a copy has for certain, in O(1): the lane count, the first and
    the last triple. Decides no answer: it spares `_same_columns` where the
    columns plainly differ."""
    return len(a.pubs) == len(b.pubs) and all(
        x[:1] == y[:1] and x[-1:] == y[-1:]
        for x, y in ((a.sigs, b.sigs), (a.pubs, b.pubs), (a.msgs, b.msgs))
    )


class _InFlight:
    """What the dispatcher publishes while the chain runs a dispatch: the
    distinct requests it carries, each one's lanes in the dispatched
    columns, and the requests that joined it since, each as (request, its
    kind). `kinds` and `lanes` never change; `joined` only under `_cond`
    and only while the record is the one published."""

    __slots__ = ("kinds", "lanes", "joined")

    def __init__(self, kinds, lanes):
        self.kinds = kinds
        self.lanes = lanes
        self.joined: list[tuple[_Request, int]] = []

    def kind_of_copy(self, req: "_Request") -> int | None:
        """The place among `kinds` of the request `req` is a copy of."""
        for k, kind in enumerate(self.kinds):
            if _may_be_copy(req, kind) and _same_columns(req, kind):
                return k
        return None


def engine_of(backend) -> "VerificationEngine | None":
    """The engine behind a backend, if one is active: the backend itself,
    or the one the CoalescingScheduler shim embeds. None for a bare chain
    (`CMTPU_COALESCE=0`) or a test-installed backend — callers keep their
    legacy private-dispatcher paths in that case."""
    if isinstance(backend, VerificationEngine):
        return backend
    eng = getattr(backend, "engine", None)
    return eng if isinstance(eng, VerificationEngine) else None


class VerifyFuture:
    """Result slot a submitter blocks on; filled by the dispatcher.

    `shared` reports (after resolution) whether the request rode a merged
    dispatch — surfaces use it for their legacy "batched" counters."""

    __slots__ = ("_event", "_result", "_error", "t_submit", "n_sigs", "shared")

    def __init__(self, n_sigs: int):
        self._event = threading.Event()
        self._result: tuple[bool, list[bool]] | None = None
        self._error: BaseException | None = None
        self.t_submit = time.perf_counter()
        self.n_sigs = n_sigs
        self.shared = False

    def _set_result(self, result: tuple[bool, list[bool]]) -> None:
        self._result = result
        self._event.set()

    def _set_error(self, err: BaseException) -> None:
        self._error = err
        self._event.set()

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: float | None = None) -> tuple[bool, list[bool]]:
        if not self._event.wait(timeout):
            raise TimeoutError("verification future not resolved in time")
        if self._error is not None:
            raise self._error
        return self._result


class _Request:
    __slots__ = (
        "pubs", "msgs", "sigs", "future", "klass", "deadline", "t_start", "span",
        "fingerprint", "compare_ms",
    )

    def __init__(self, pubs, msgs, sigs, future, klass, deadline):
        self.pubs = pubs
        self.msgs = msgs
        self.sigs = sigs
        self.future = future
        self.klass = klass
        self.deadline = deadline  # absolute perf_counter deadline or None
        self.t_start = 0.0  # set when the dispatcher picks it up
        # The submitter's open span: what the dispatcher thread does for
        # this request is traced as its child (libs/trace.py).
        self.span = trace.current()
        # `columns_fingerprint` of the columns, once the dispatcher needed it.
        self.fingerprint: int | None = None
        # What comparing it with the request in flight took, where it joined
        # that dispatch (then it never stood in the queue).
        self.compare_ms: float | None = None

    def answer(self, result=None, error: BaseException | None = None) -> None:
        """Resolves the future. A request that joined a dispatch in flight
        first records its `engine.join`, submission to answer."""
        if self.compare_ms is not None:
            trace.record(
                "engine.join", self.future.t_submit, time.perf_counter(),
                parent=self.span, lanes=len(self.pubs),
                compare_ms=round(self.compare_ms, 3),
            )
        if error is not None:
            self.future._set_error(error)
        else:
            self.future._set_result(result)


class VerificationEngine(VerifyBackend):
    """Continuous-batching front of the verification chain (module docstring)."""

    name = "engine"

    def __init__(
        self,
        inner: VerifyBackend,
        hold_ms: float | None = None,
        max_sigs: int | None = None,
        starvation_ms: float | None = None,
        deadline_ms: float | None = None,
    ):
        self.inner = inner
        # Compat hold: the round-6 window-from-first-waiter, kept for the
        # CoalescingScheduler shim. 0 = true continuous batching.
        self.hold_ms = (
            _env_float("CMTPU_ENGINE_HOLD_MS", 0.0)
            if hold_ms is None
            else hold_ms
        )
        self.starvation_ms = (
            _env_float("CMTPU_ENGINE_STARVATION_MS", 100.0)
            if starvation_ms is None
            else starvation_ms
        )
        # Consensus admission deadline: a queued vote must be RESOLVED
        # within this budget, so it caps merged-dispatch growth. Derived
        # from the supervisor's per-call deadline when one is configured.
        if deadline_ms is None:
            deadline_ms = _env_float(
                "CMTPU_ENGINE_DEADLINE_MS",
                _env_float("CMTPU_DEADLINE_MS", 0.0) or 50.0,
            )
        self.consensus_deadline_ms = deadline_ms
        self._cap_auto = False
        if max_sigs is not None:
            self.max_sigs = max_sigs
        elif os.environ.get("CMTPU_ENGINE_MAX", ""):
            self.max_sigs = int(_env_float("CMTPU_ENGINE_MAX", 16384))
        else:
            # Pod-width default: one merged dispatch can fill every chip
            # (16384 lanes each). An explicit env or arg always wins. The
            # auto cap re-reads the chain's width periodically
            # (refresh_cap) because the width a grpc tier serves is only
            # learned from the sidecar's Ping capability reply AFTER the
            # first connect.
            self._cap_auto = True
            self.max_sigs = 16384 * max(1, _mesh_width_for_cap())
        self._queues: list[list[_Request]] = [[] for _ in range(_N_CLASSES)]
        self._cond = threading.Condition()
        self._closed = False
        self._thread: threading.Thread | None = None
        self._wait_ms: list[float] = []  # aggregate admission-wait ring
        self._wait_i = 0
        self._class_wait: list[list[float]] = [[] for _ in range(_N_CLASSES)]
        self._class_wait_i = [0] * _N_CLASSES
        self._rate_cache: tuple[float, float] | None = None
        self._fingerprint_ms = 0.0
        self._in_flight: _InFlight | None = None  # published while the chain runs
        self.counters_ = {
            "requests": 0,
            "dispatches": 0,
            "coalesced_dispatches": 0,  # dispatches carrying >1 request
            "batched_requests": 0,      # requests that shared a dispatch
            "coalesced_sigs": 0,        # sigs that rode a shared dispatch
            "dedup_sigs": 0,            # lanes offered that no dispatch ran again
            "joined_requests": 0,       # answered by a dispatch already in flight
            "joined_sigs": 0,           # their lanes
            "fallback_splits": 0,       # coalesced dispatches split on error
        }
        self.class_counters_ = [
            {"admitted": 0, "dispatched_sigs": 0, "starvation_promotions": 0}
            for _ in range(_N_CLASSES)
        ]

    # -- submission surface ------------------------------------------------

    def submit(
        self,
        pubs,
        msgs,
        sigs,
        klass: int | None = None,
        deadline_ms: float | None = None,
    ) -> VerifyFuture:
        """Enqueue one verification request; returns the future its caller
        blocks on.  Raises after close() — an engine with no dispatcher
        must fail loudly, not hang the submitter forever."""
        if klass is None:
            klass = current_class()
        klass = min(max(int(klass), 0), _N_CLASSES - 1)
        fut = VerifyFuture(len(pubs))
        if not pubs:
            fut._set_result((False, []))
            return fut
        if deadline_ms is None and klass == CLASS_CONSENSUS:
            deadline_ms = self.consensus_deadline_ms
        deadline = (
            fut.t_submit + deadline_ms / 1000.0
            if deadline_ms and deadline_ms > 0
            else None
        )
        req = _Request(list(pubs), list(msgs), list(sigs), fut, klass, deadline)
        # Read without the lock: a record that has ended, or one published
        # a moment later, only means the request is queued as ever; the
        # join itself is decided under the lock, on the record's identity.
        flying = self._in_flight
        kind = None
        if flying is not None:
            t0 = time.perf_counter()
            kind = flying.kind_of_copy(req)
            compare_ms = (time.perf_counter() - t0) * 1000.0
        with self._cond:
            if self._closed:
                raise RuntimeError("engine is closed")
            self.counters_["requests"] += 1
            self.class_counters_[klass]["admitted"] += 1
            if kind is not None and self._in_flight is flying:
                req.compare_ms = compare_ms
                flying.joined.append((req, kind))
                return fut
            self._queues[klass].append(req)
            self._ensure_thread()
            self._cond.notify_all()
        return fut

    def batch_verify(self, pubs, msgs, sigs):
        return self.submit(pubs, msgs, sigs).result()

    def aggregate_verify(self, pubs, msgs, agg_sig):
        # One boolean per whole commit: nothing to slice across callers;
        # pass straight through to the supervised chain.
        return self.inner.aggregate_verify(pubs, msgs, agg_sig)

    def merkle_root(self, leaves):
        # Roots carry no cross-caller coalescing opportunity (one tree per
        # call); pass straight through to the chain.
        return self.inner.merkle_root(leaves)

    def mesh_width(self) -> int:
        mw = getattr(self.inner, "mesh_width", None)
        return int(mw()) if mw is not None else 1

    def refresh_cap(self) -> int:
        """Re-derive the auto merge cap from the chain's CURRENT width
        (local chips, or a remote pod's once the sidecar Ping capability
        reply has been seen). Grow-only; pinned caps (arg/env) never move."""
        if self._cap_auto:
            try:
                width = max(1, self.mesh_width())
            except Exception:
                return self.max_sigs
            new_cap = 16384 * width
            if new_cap > self.max_sigs:
                self.max_sigs = new_cap
                # The width grew (a sidecar Ping reply arrived, a fanout
                # fleet came up): the dispatch-wall model must re-read
                # rates at the new device count or deadline sizing keeps
                # pricing the old, narrower chain.
                self._rate_cache = None
        return self.max_sigs

    def ping(self):
        inner_ping = getattr(self.inner, "ping", None)
        return inner_ping() if inner_ping is not None else True

    # -- dispatch-wall model -----------------------------------------------

    def _rate_model(self) -> tuple[float, float]:
        """(sigs/ms, fixed overhead ms) for one dispatch through the chain,
        read from the hybrid planner's EMA-calibrated rates when a hybrid
        tier is present (duck-typed walk — chain shapes vary by backend
        knob), else the env/default model."""
        cached = self._rate_cache
        if cached is not None:
            return cached
        rate = _env_float("CMTPU_ENGINE_RATE", 100.0)
        overhead = _env_float("CMTPU_ENGINE_OVERHEAD_MS", 8.0)
        stack = [self.inner]
        seen: set[int] = set()
        while stack:
            b = stack.pop()
            if b is None or id(b) in seen:
                continue
            seen.add(id(b))
            if hasattr(b, "_dev_rate") and hasattr(b, "_n_dev"):
                rate = float(b._dev_rate) * max(1, int(b._n_dev))
                overhead = float(getattr(b, "_dev_overhead", overhead))
                break
            # LIFO stack: push tiers reversed so the CHAIN-ORDER head pops
            # first — a fanout fleet tier must price the dispatch, not the
            # narrower hybrid tier sitting below it in the chain.
            for t in reversed(getattr(b, "tiers", ()) or ()):
                stack.append(getattr(t, "backend", None))
            stack.append(getattr(b, "inner", None))
        model = (max(rate, 1e-6), max(overhead, 0.0))
        self._rate_cache = model
        return model

    def _deadline_cap(self, now: float) -> int:
        """How many signatures the NEXT dispatch may carry without driving
        a queued consensus request past its admission deadline: predicted
        wall(overhead + n/rate) must fit the tightest remaining budget.
        Queued consensus work itself always fits (it IS the deadline's
        beneficiary; shrinking below it would only delay it further)."""
        cons = self._queues[CLASS_CONSENSUS]
        deadlines = [r.deadline for r in cons if r.deadline is not None]
        if not deadlines:
            return self.max_sigs
        budget_ms = (min(deadlines) - now) * 1000.0
        rate, overhead = self._rate_model()
        fit = int(rate * max(0.0, budget_ms - overhead))
        cons_sigs = sum(len(r.pubs) for r in cons)
        return min(self.max_sigs, max(fit, cons_sigs, 1))

    # -- dispatcher --------------------------------------------------------

    def _ensure_thread(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._loop, daemon=True, name="verify-engine"
            )
            self._thread.start()

    def _queued_sigs(self) -> int:
        return sum(len(r.pubs) for q in self._queues for r in q)

    def _have_work(self) -> bool:
        return any(self._queues)

    def _collect(self) -> list[_Request]:
        """Block until work exists; in compat-hold mode keep the window
        open for batchmates; then assemble the next dispatch: starvation
        promotions first (oldest first), then strict class priority, whole
        requests only while the lanes the dispatch will run fit the
        deadline-aware cap (first always taken). Requests that carry the
        same columns cost the cap once; where the queue offers more than
        the cap, the requests not yet fingerprinted are, outside the lock
        (nothing per-lane runs under it), and the queue is read again."""
        fingerprinted = False
        self._fingerprint_ms = 0.0  # the dispatcher's own: what sizing this dispatch cost
        while True:
            with self._cond:
                while not self._have_work() and not self._closed:
                    self._cond.wait()
                if not self._have_work():
                    return []
                hold_s = self.hold_ms / 1000.0
                first_t = min(q[0].future.t_submit for q in self._queues if q)
                while hold_s > 0 and not self._closed:
                    if self._queued_sigs() >= self.max_sigs:
                        break
                    remaining = first_t + hold_s - time.perf_counter()
                    if remaining <= 0:
                        break
                    self._cond.wait(remaining)
                now = time.perf_counter()
                cap = self._deadline_cap(now)
                order, promoted_ids = self._drain_order(now)
                blank = [r for r in order if r.fingerprint is None]
                crowded = len(order) > 1 and self._queued_sigs() > cap
                if fingerprinted or not (crowded and blank):
                    return self._take(order, promoted_ids, cap)
            t0 = time.perf_counter()
            for req in blank:
                req.fingerprint = columns_fingerprint(req.pubs, req.msgs, req.sigs)
            self._fingerprint_ms = (time.perf_counter() - t0) * 1000.0
            fingerprinted = True  # later arrivals count whole this time round

    def _drain_order(self, now: float) -> tuple[list[_Request], set[int]]:
        """The queued requests in the order a dispatch takes them, and the
        ids of those the starvation escape hatch promoted: requests older
        than starvation_ms jump the class ladder (oldest first). Ages are
        monotone within a FIFO queue, so only each queue's stale prefix
        needs checking."""
        starv_s = self.starvation_ms / 1000.0
        promoted: list[_Request] = []
        if self.starvation_ms > 0:
            for q in self._queues:
                for r in q:
                    if now - r.future.t_submit >= starv_s:
                        promoted.append(r)
                    else:
                        break
            promoted.sort(key=lambda r: r.future.t_submit)
        promoted_ids = {id(r) for r in promoted}
        order = promoted + [
            r
            for klass in range(_N_CLASSES)
            for r in self._queues[klass]
            if id(r) not in promoted_ids
        ]
        return order, promoted_ids

    def _take(
        self, order: list[_Request], promoted_ids: set[int], cap: int
    ) -> list[_Request]:
        """Unqueue the head of `order` that fits `cap`. A fingerprint seen
        earlier in the batch costs nothing (its lanes are already in), so
        such a request rides along even past one that did not fit; nothing
        that adds lanes passes a request that did not fit."""
        batch: list[_Request] = []
        seen: set[int] = set()
        total = 0
        full = False
        for req in order:
            again = req.fingerprint is not None and req.fingerprint in seen
            n = 0 if again else len(req.pubs)
            if not again and (full or (batch and total + n > cap)):
                full = True
                continue
            if id(req) in promoted_ids and any(
                self._queues[k] for k in range(req.klass)
            ):
                # Promotion only counts when the escape hatch actually
                # bypassed fresher higher-class work.
                self.class_counters_[req.klass]["starvation_promotions"] += 1
            self._queues[req.klass].remove(req)
            total += n
            if req.fingerprint is not None:
                seen.add(req.fingerprint)
            batch.append(req)
        return batch

    def _loop(self) -> None:
        while True:
            batch = self._collect()
            if not batch:
                return  # closed and drained
            now = time.perf_counter()
            for req in batch:
                req.t_start = now
                self._record_wait(req.klass, (now - req.future.t_submit) * 1000.0)
                trace.record(
                    "engine.queue_wait", req.future.t_submit, now,
                    parent=req.span, klass=CLASS_NAMES[req.klass],
                )
            try:
                self._dispatch(batch)
            except BaseException as e:  # never kill the dispatcher
                for req in batch:
                    if not req.future.done():
                        req.future._set_error(e)

    def _dispatch(self, batch: list[_Request]) -> None:
        with trace.span(
            "engine.dispatch", parent=batch[0].span, requests=len(batch),
            lanes=sum(len(r.pubs) for r in batch),
            klass=CLASS_NAMES[batch[0].klass],
        ) as call:
            if self._fingerprint_ms:
                call.set(fingerprint_ms=round(self._fingerprint_ms, 3))
            self._dispatch_traced(batch, call)

    def _dispatch_traced(self, batch: list[_Request], call) -> None:
        with self._cond:
            self.counters_["dispatches"] += 1
            refresh = self._cap_auto and self.counters_["dispatches"] % 64 == 1
        if refresh:
            # Cheap cached-width read (no dial): pick up a remote pod's
            # width once the grpc tier has seen a Ping capability reply.
            try:
                self.refresh_cap()
            except Exception:
                pass
        if len(batch) > 1:
            kinds, kind_of, (pubs, msgs, sigs), lanes = self._pack(batch)
        else:
            # Nothing to pack: the lone request's columns as they stand.
            req = batch[0]
            kinds, kind_of = [req], [0]
            pubs, msgs, sigs = req.pubs, req.msgs, req.sigs
            lanes = [range(len(pubs))]
        call.set(unique=len(pubs))
        # While the chain runs, a copy of a request this dispatch carries
        # joins it (`submit`) and is answered with the batch below.
        flying = _InFlight(kinds, lanes)
        with self._cond:
            self._in_flight = flying
        answer = bits = error = None
        try:
            answer = self.inner.batch_verify(pubs, msgs, sigs)
            bits = answer[1]
        except BaseException as e:
            error = e
        with self._cond:
            self._in_flight = None  # nothing joins from here
            # Those that did are the batch's now: sliced with it, retried
            # with it, and `_loop`'s last resort finds them in it.
            joined = len(flying.joined)
            batch += [req for req, _ in flying.joined]
            kind_of += [k for _, k in flying.joined]
            shared = len(batch) > 1
            offered = 0
            for req in batch:
                req.future.shared = shared
                offered += len(req.pubs)
                self.class_counters_[req.klass]["dispatched_sigs"] += len(
                    req.pubs
                )
            dedup = offered - len(pubs)  # lanes offered that this dispatch ran once
            if shared:
                self.counters_["coalesced_dispatches"] += 1
                self.counters_["batched_requests"] += len(batch)
                self.counters_["coalesced_sigs"] += offered
                self.counters_["dedup_sigs"] += dedup
                self.counters_["joined_requests"] += joined
                self.counters_["joined_sigs"] += sum(
                    len(req.pubs) for req, _ in flying.joined
                )
        if not shared:
            # Nothing to slice or protect: the lone request gets the
            # chain's answer as it is (errors propagate to its caller alone).
            if error is not None:
                batch[0].future._set_error(error)
            else:
                batch[0].future._set_result(answer)
            return
        call.set(dedup=dedup)
        if joined:
            call.set(requests=len(batch), lanes=offered, joined=joined)
        if error is not None or len(bits) != len(pubs):
            # A sick tier answering with the wrong shape is a failed
            # dispatch, not something to mis-slice.
            self._fallback(batch)
            return
        with trace.span("engine.merge", phase="slice"):
            kind_bits: dict[int, list[bool]] = {}  # copies of a request share one walk
            for req, k in zip(batch, kind_of):
                if k not in kind_bits:
                    kind_bits[k] = [bits[lane] for lane in lanes[k]]
                req_bits = list(kind_bits[k])
                req.answer((all(req_bits), req_bits))

    def _pack(self, batch: list[_Request]):
        """Columnar pack with within-batch dedup: identical triples from
        concurrent requests (N light clients walking the same descent)
        share one lane. A request with the fingerprint AND the columns of
        one before it in the batch is its copy (the fingerprint finds the
        candidate, the comparison decides) and takes that one's lanes, so
        the per-triple walk sees each distinct request once, and copies
        of one request alone (N nodes of one chain) skip it. Gives the
        batch's distinct requests in order, each request's place among
        them, the columns to dispatch and each distinct request's lanes in
        them."""
        with trace.span("engine.merge", phase="pack"):
            kinds: list[_Request] = []
            kind_of: list[int] = []
            first_with: dict[int, int] = {}
            for req in batch:
                k = None
                if req.fingerprint is not None:
                    k = first_with.setdefault(req.fingerprint, len(kinds))
                if k is None or k == len(kinds) or not _same_columns(req, kinds[k]):
                    k = len(kinds)
                    kinds.append(req)
                kind_of.append(k)
            if len(kinds) == 1:
                one = kinds[0]
                return kinds, kind_of, (one.pubs, one.msgs, one.sigs), [range(len(one.pubs))]
            lane_of: dict[tuple, int] = {}
            pubs, msgs, sigs = [], [], []
            lanes = []
            for req in kinds:
                req_lanes = []
                for p, m, s in zip(req.pubs, req.msgs, req.sigs):
                    key = (p, s, m)
                    lane = lane_of.get(key)
                    if lane is None:
                        lane = len(pubs)
                        lane_of[key] = lane
                        pubs.append(p)
                        msgs.append(m)
                        sigs.append(s)
                    req_lanes.append(lane)
                lanes.append(req_lanes)
            return kinds, kind_of, (pubs, msgs, sigs), lanes

    def _fallback(self, batch: list[_Request]) -> None:
        """The merged dispatch failed: retry each request alone so one
        poisoned request cannot fail its batchmates.  Per-request errors go
        to that request's caller only."""
        with self._cond:
            self.counters_["fallback_splits"] += 1
        for req in batch:
            try:
                req.answer(self.inner.batch_verify(req.pubs, req.msgs, req.sigs))
            except BaseException as e:
                req.answer(error=e)

    # -- observability -----------------------------------------------------

    def _record_wait(self, klass: int, ms: float) -> None:
        with self._cond:
            if len(self._wait_ms) < _WAIT_SAMPLES:
                self._wait_ms.append(ms)
            else:
                self._wait_ms[self._wait_i % _WAIT_SAMPLES] = ms
            self._wait_i += 1
            ring = self._class_wait[klass]
            if len(ring) < _WAIT_SAMPLES:
                ring.append(ms)
            else:
                ring[self._class_wait_i[klass] % _WAIT_SAMPLES] = ms
            self._class_wait_i[klass] += 1

    @staticmethod
    def _percentile(data: list[float], q: float) -> float:
        if not data:
            return 0.0
        data = sorted(data)
        idx = min(len(data) - 1, int(q * (len(data) - 1) + 0.5))
        return data[idx]

    def _wait_percentile(self, q: float) -> float:
        with self._cond:
            data = list(self._wait_ms)
        return self._percentile(data, q)

    def class_wait_p95_ms(self, klass: int) -> float:
        with self._cond:
            data = list(self._class_wait[klass])
        return self._percentile(data, 0.95)

    def counters(self) -> dict:
        with self._cond:
            out = dict(self.counters_)
            out["queue_depth"] = sum(len(q) for q in self._queues)
            classes = {
                CLASS_NAMES[k]: dict(self.class_counters_[k])
                for k in range(_N_CLASSES)
            }
        out["max_sigs"] = self.max_sigs
        d = max(1, out["dispatches"])
        out["coalesce_ratio"] = round(out["requests"] / d, 3)
        out["queue_wait_p50_ms"] = round(self._wait_percentile(0.50), 3)
        out["queue_wait_p95_ms"] = round(self._wait_percentile(0.95), 3)
        for k in range(_N_CLASSES):
            classes[CLASS_NAMES[k]]["p95_us"] = int(
                self.class_wait_p95_ms(k) * 1000
            )
        out["classes"] = classes
        inner_counters = getattr(self.inner, "counters", None)
        if inner_counters is not None:
            out["inner"] = inner_counters()
        return out

    def close(self) -> None:
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        thread = self._thread
        if thread is not None and thread.is_alive():
            thread.join(timeout=5.0)
        inner_close = getattr(self.inner, "close", None)
        if inner_close is not None:
            inner_close()
