"""The program's one tracer: spans at layer boundaries, on the profiler's clock.

    with trace.span("batch.verify", entries=n) as sp:
        ...
        sp.set(hits=hits)

A span records `(id, parent, root, name, t0, t1, cpu, pcpu, thread, attrs)`
into one bounded in-memory ring on `time.perf_counter()` and, for the same
interval, enters `jax.profiler.TraceAnnotation("seam:" + name)`, so it also
lies in the profiler's xplane beside the device's operations. `parent` is
the innermost open span of the thread (or the one handed over with
`parent=` when the work crossed a thread); `root` is the id of the
outermost one, so the spans of one request — one `verify_commit`, one
synced height, one prefetch job — share it.

`t1 - t0` says where the thread stood; `cpu` and `pcpu` say what ran. `cpu`
is the seconds this thread was on a CPU inside the span
(`time.thread_time()`), `pcpu` the seconds of CPU the whole process used
meanwhile, all its threads (`time.process_time()`). `t1 - t0 - cpu` is the
time the thread did not run: it waited for the interpreter lock, a queue,
a socket or the device. `pcpu / (t1 - t0)` near 1 says the interpreter was
saturated meanwhile (the wait was for the lock), near 0 that the process
slept (a real wait); in between the two cannot be told apart, and native
threads that hold no lock (XLA's, the host MSM's) count in `pcpu` too. The
clocks are read inside one another (`t0`, `pcpu`, `cpu` ... `cpu`, `pcpu`,
`t1`), so `cpu <= pcpu` and `cpu <= t1 - t0` up to the clocks' resolution.
`cpu` is None where a span is closed on another thread than opened it, and
in a `record()` whose caller gave none. On plain Linux the two clocks are
exact and cost ~0.3 us a read. Under gVisor (the chip tool's machine,
PERF.md section 6) they tick at 10 ms, cost 6 us a read and charge part of
a lock's wait as running: a span's `cpu` is then a whole number of ticks
and an upper bound; read sums over many spans, as a sampling profiler's.

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

A site sits at a layer boundary, once per call, dispatch, request, height
or received p2p message — never inside a loop over lanes, signatures or
packets. `NAMES` is every name the program emits (tests/test_trace.py
holds the code and PERF.md's table to it). This module imports nothing of
JAX.

The ring's `thread` is the thread's name, and every thread the hot paths
start is named `<role>` or `<role>:<which>` (`p2p-recv:<peer>`,
`blocksync-pool`, `sidecar-conn:<port>`, `verify-engine`, `cmtpu-dev`):
`role()` is the part before the colon, and `thread_cpu()` the CPU each
role's live threads have used, for `/metrics` and `/debug/jax/trace`.
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
    "blocksync.prefetch_collect",
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
    "device.table_build",
    # sidecar wire: the node's side, then the server's
    "grpc.call",
    "grpc.encode",
    "grpc.wait",
    "grpc.decode",
    "sidecar.request",
    "sidecar.decode",
    "sidecar.encode",
    # p2p receive
    "p2p.recv_msg",
    # light client
    "light.sync",
    "light.fetch",
    "light.verify",
    "light.speculate",
    "light.speculate_collect",
    "light.detect",
    "light.save",
)

# Every role the hot paths name a thread by (module text).
ROLES = (
    "p2p-recv", "p2p-send", "p2p-drain", "blocksync-pool", "blocksync-prefetch",
    "verify-engine", "cmtpu-dev", "sidecar-conn", "sidecar-reader",
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
    __slots__ = ("id", "parent", "root", "name", "t0", "attrs", "_ann",
                 "_tid", "_cpu0", "_pcpu0")

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
        self._tid = threading.get_ident()
        self.t0 = time.perf_counter()
        self._pcpu0 = time.process_time()
        self._cpu0 = time.thread_time()
        return self

    def __exit__(self, *exc):
        cpu1 = time.thread_time()
        pcpu1 = time.process_time()
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
            "cpu": cpu1 - self._cpu0 if threading.get_ident() == self._tid else None,
            "pcpu": pcpu1 - self._pcpu0,
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


def on() -> bool:
    """Whether a session of either kind is open. A site that has to read a
    clock now for a `record()` later asks this first, so that with no
    session it reads none."""
    return bool(_captures) or _session() is not None


def mark():
    """`(perf_counter, process_time, thread_time)` now, the start of an
    interval this thread will `record()`; None, and no clock read, with no
    session."""
    if not on():
        return None
    return time.perf_counter(), time.process_time(), time.thread_time()


def since(mark) -> dict:
    """`t0`, `t1`, `cpu` and `pcpu` of the interval from `mark()` to now,
    as `record()` takes them. Same thread as the mark."""
    cpu1 = time.thread_time()
    pcpu1 = time.process_time()
    t0, pcpu0, cpu0 = mark
    return {"t0": t0, "t1": time.perf_counter(), "cpu": cpu1 - cpu0, "pcpu": pcpu1 - pcpu0}


def record(name: str, t0: float, t1: float, parent=None, cpu=None, pcpu=None, **attrs) -> None:
    """Ring only, for an interval known after the fact or one that crosses
    threads (the engine's queue wait). Times are `time.perf_counter()`;
    `cpu` / `pcpu` are the caller's own readings (`since()`), None where it
    has none: an interval that crossed threads has no thread's CPU."""
    if not on():
        return
    sid = next(_ids)
    _put({
        "id": sid, "parent": None if parent is None else parent.id,
        "root": sid if parent is None else parent.root, "name": name,
        "t0": t0, "t1": t1, "cpu": cpu, "pcpu": pcpu,
        "thread": threading.current_thread().name, "attrs": attrs,
    })


def role(thread_name: str) -> str:
    """`<role>` of a thread named `<role>` or `<role>:<which>`."""
    return thread_name.partition(":")[0]


def thread_cpu() -> dict[str, float]:
    """CPU seconds each role's LIVE threads have used since they started,
    `process` (all threads, ended ones too) and `ended_or_native` = `process`
    minus the live threads' sum, so the roles close on the process: threads
    that have ended (a prefetch job a height) and native threads Python does
    not list (XLA's, the host MSM's). A caller that wants an interval reads
    it at both edges. Where the platform cannot read another thread's clock
    (no `pthread_getcpuclockid`) it is `{"process": ...}` alone."""
    clock_of = getattr(time, "pthread_getcpuclockid", None)
    if clock_of is None:
        return {"process": time.process_time()}
    out: dict[str, float] = {}
    live = 0.0
    for t in threading.enumerate():
        try:
            used = time.clock_gettime(clock_of(t.ident))
        except (OSError, TypeError):  # ended since it was listed, or not started yet
            continue
        who = role(t.name)
        out[who] = out.get(who, 0.0) + used
        live += used
    process = time.process_time()  # read last: never under the sum of its parts
    out["process"] = process
    out["ended_or_native"] = process - live
    return out


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
