"""The program's one tracer: spans at layer boundaries, on the profiler's clock.

    with trace.span("batch.verify", entries=n) as sp:
        ...
        sp.set(hits=hits)

A span records `(id, parent, root, name, t0, t1, thread, attrs)` into one
bounded in-memory ring on `time.perf_counter()` and, for the same interval,
enters `jax.profiler.TraceAnnotation("seam:" + name)`, so it also lies in
the profiler's xplane beside the device's operations. `parent` is the
innermost open span of the thread (or the one handed over with `parent=`
when the work crossed a thread); `root` is the id of the outermost one, so
the spans of one request — one `verify_commit`, one synced height, one
prefetch job — share it.

The switch is the profiler session, not a knob: a site first asks whether
JAX has been imported at all and then whether a profiler session is on.
Where either says no, `span()` hands back one shared no-op and records
nothing, so a process that never imports JAX (serving peers, the chain
builder, a cpu-only node) never does, and nothing constructs a
`TraceAnnotation`. `jax.profiler.start_trace` turns every site on — the
benchmark's `--trace 1`, or an operator's `/debug/jax/trace`, which also
writes `spans()` beside the xplane (libs/pprof.py).

A process that never imports JAX (the node beside a sidecar) has no
profiler to start. `capture()` is the session it can hold: while one is
open every site writes the ring and constructs no `TraceAnnotation`;
with neither a capture nor a profiler session every site is the no-op.
`time.perf_counter()` is CLOCK_MONOTONIC on Linux, one clock for every
process of a host, so the spans of a node and of the sidecar it calls
merge by their times.

A site sits at a layer boundary, once per call, dispatch, request or
height — never inside a loop over lanes, signatures or messages. `NAMES`
is every name the program emits (tests/test_trace.py holds the code and
PERF.md's table to it). This module imports nothing of JAX.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import sys
import threading
import time

RING = 65536
PREFIX = "seam:"  # the prefix benchmarks/trace_reduce.py reads for program spans

NAMES = (
    # callers
    "validation.verify_commit",
    "validation.basic",
    "validation.key_type",
    "validation.sign_bytes",
    "validation.tally",
    "blocksync.sync_one",
    "blocksync.fetch_wait",
    "blocksync.make_requests",
    "blocksync.verify_wait",
    "blocksync.part_set",
    "blocksync.verify_light",
    "blocksync.validate",
    "blocksync.save",
    "blocksync.pipeline_submit",
    "blocksync.apply",
    "blocksync.prefetch",
    "blocksync.decode",
    "types.data_hash",
    "types.part_set_proofs",
    # state and stores
    "state.validate",
    "state.exec_abci",
    "state.save_responses",
    "state.update",
    "state.results_hash",
    "state.commit",
    "state.save_state",
    "store.save_block",
    # batch seam and engine
    "batch.verify",
    "batch.cache_filter",
    "batch.dispatch",
    "batch.cache_insert",
    "engine.queue_wait",
    "engine.join",
    "engine.dispatch",
    "engine.merge",
    # supervisor
    "supervisor.tier_call",
    # hybrid planner
    "hybrid.call",
    "hybrid.plan",
    # host tier
    "hybrid.host_msm",
    # device tier
    "device.pack",
    "device.run",
    "device.wait",
    "device.unpack",
    # sidecar wire: the node's side, then the server's
    "grpc.call",
    "grpc.encode",
    "grpc.wait",
    "grpc.decode",
    "sidecar.request",
    "sidecar.decode",
    "sidecar.encode",
)

_ring: collections.deque = collections.deque(maxlen=RING)
_dropped = 0
_ring_lock = threading.Lock()
_ids = itertools.count(1)
_tls = threading.local()
_annotation = None  # jax.profiler.TraceAnnotation, once JAX is there to ask
_captures = 0  # open capture()s: ring-only sessions


def _session():
    """`TraceAnnotation` if a profiler session is on, else None."""
    global _annotation
    ann = _annotation
    if ann is None:
        if "jax" not in sys.modules:
            return None
        # a `jax` still being imported on another thread has no profiler yet
        prof = getattr(sys.modules["jax"], "profiler", None)
        if prof is None:
            return None
        ann = _annotation = prof.TraceAnnotation
    return ann if ann.is_enabled() else None


@contextlib.contextmanager
def capture():
    """A ring-only session, for a process with no profiler to start: while
    it is open `span()` and `record()` write the ring. Nothing of JAX is
    imported or asked; a profiler session that is on besides still gets
    its annotations."""
    global _captures
    with _ring_lock:
        _captures += 1
    try:
        yield
    finally:
        with _ring_lock:
            _captures -= 1


def _stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def _put(rec: dict) -> None:
    global _dropped
    with _ring_lock:
        if len(_ring) == RING:
            _dropped += 1
        _ring.append(rec)


class _Off:
    """What `span()` hands back with no session of either kind: nothing happens."""

    __slots__ = ()
    id = root = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass

    def backdate(self, t0: float) -> None:
        pass


_OFF = _Off()


class _Span:
    __slots__ = ("id", "parent", "root", "name", "t0", "attrs", "_ann")

    def __init__(self, name: str, parent, attrs: dict, ann):
        self.id = next(_ids)
        self.name = name
        self.parent = parent
        self.attrs = attrs
        self._ann = None if ann is None else ann(PREFIX + name)  # None: ring only

    def set(self, **attrs) -> None:
        """Attributes known only once the work is done (hits, applied, ...)."""
        self.attrs.update(attrs)

    def backdate(self, t0: float) -> None:
        """Moves the start of an open span back to `t0`, for a span whose
        need shows only in its first piece of work (a streamed request is
        found by decoding its first chunk)."""
        self.t0 = t0

    def __enter__(self):
        stack = _stack()
        if self.parent is None and stack:
            self.parent = stack[-1]
        parent = self.parent
        self.root = self.id if parent is None else parent.root
        stack.append(self)
        if self._ann is not None:
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        stack = _stack()
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:  # left out of order: drop it and what it held open
            del stack[stack.index(self):]
        parent = self.parent
        _put({
            "id": self.id, "parent": None if parent is None else parent.id,
            "root": self.root, "name": self.name, "t0": self.t0, "t1": t1,
            "thread": threading.current_thread().name, "attrs": self.attrs,
        })
        return False


def span(name: str, parent=None, **attrs):
    """Context manager for one interval of work at a layer boundary.
    `parent` hands over a span of another thread (from `current()`); left
    out, the parent is the innermost span open on this thread."""
    ann = _annotation  # _session(), inlined: this is every site's off path
    if ann is None:
        if "jax" in sys.modules:
            ann = _session()
    elif not ann.is_enabled():
        ann = None
    if ann is None and not _captures:
        return _OFF
    return _Span(name, parent, attrs, ann)


def current():
    """The innermost span open on this thread, to hand to another thread
    as `parent=`; None with no session or no open span."""
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else None


def record(name: str, t0: float, t1: float, parent=None, **attrs) -> None:
    """Ring only, for an interval known after the fact or one that crosses
    threads (the engine's queue wait). Times are `time.perf_counter()`."""
    if not _captures and _session() is None:
        return
    sid = next(_ids)
    _put({
        "id": sid, "parent": None if parent is None else parent.id,
        "root": sid if parent is None else parent.root, "name": name,
        "t0": t0, "t1": t1, "thread": threading.current_thread().name,
        "attrs": attrs,
    })


def spans() -> list[dict]:
    """A copy of the ring, oldest first."""
    with _ring_lock:
        return [dict(r) for r in _ring]


def dropped() -> int:
    """Spans the ring has pushed out since the process started."""
    return _dropped


def clear() -> None:
    """Empties the ring (tests, and a capture that wants only its own spans)."""
    global _dropped
    with _ring_lock:
        _ring.clear()
        _dropped = 0
