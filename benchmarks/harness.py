"""What every cell shares: the device gate, the backend under
`CMTPU_BACKEND=auto`, the counters of its chain, the compile-log reader,
the profiler trace around part of the window, and the result line.

Nothing here knows a cell, a configuration or a traffic mix by name: a
generator (`generators/<kind>.py`) is handed a `Run` and gives back
`Observations`; `run.py` turns those into the metrics `BENCHMARK.json`
lists for the cell.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import logging
import os
import re
import shutil
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(HERE, ".cache")

SUPERVISOR_EVENTS = ("trips", "degraded_calls", "deadline_exceeded", "crosscheck_catches")


class BenchFailure(Exception):
    """The run cannot produce a result line (no chip, broken set-up)."""


def say(msg: str) -> None:
    """An earlier line of standard output; the result line comes last."""
    print(msg, flush=True)


def load_by_path(path: str, name: str):
    """Import one file of the benchmark by path (metric names hold dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise BenchFailure(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- JAX's compile log -----------------------------------------------------------
# The patterns are chip_smoke.py's (PR 21), read here from the logger itself
# instead of from a child's stderr: the process that holds the chip is the
# one that measures.

_HIT = re.compile(r"Persistent compilation cache hit for '([^']+)'")
_MISS = re.compile(r"PERSISTENT COMPILATION CACHE MISS for '([^']+)'")
_TOOK = re.compile(r"'([^']+)' took at least [\d.]+ seconds to compile \(([\d.]+)s\)")


class CompileLog(logging.Handler):
    """Counts the programs JAX lowered and looked up in (hit) or compiled
    into (miss) the persistent cache, with the seconds a miss took. A
    program already loaded in this process writes no line, so a count of 0
    over an interval means nothing was traced, lowered or compiled in it."""

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.events: list[dict] = []
        self._lock = threading.Lock()

    def install(self) -> "CompileLog":
        for name in ("jax._src.compiler", "jax._src.compilation_cache"):
            lg = logging.getLogger(name)
            lg.setLevel(logging.DEBUG)
            lg.addHandler(self)
            lg.propagate = False  # thousands of debug lines are not output
        return self

    def emit(self, record: logging.LogRecord) -> None:
        try:
            line = record.getMessage()
        except Exception:  # a malformed log call must not stop a verify
            return
        if record.levelno >= logging.WARNING:
            print(f"{record.name}: {line}", file=sys.stderr, flush=True)
        now = time.perf_counter()
        with self._lock:
            for pat, how in ((_HIT, "hit"), (_MISS, "miss")):
                m = pat.search(line)
                if m:
                    self.events.append({"t": now, "name": m.group(1), "cache": how, "compile_s": 0.0})
                    return
            m = _TOOK.search(line)
            if m:
                for ev in reversed(self.events):
                    if ev["name"] == m.group(1) and ev["cache"] == "miss":
                        ev["compile_s"] = float(m.group(2))
                        break

    @property
    def count(self) -> int:
        with self._lock:
            return len(self.events)

    def between(self, t0: float, t1: float) -> list[dict]:
        with self._lock:
            return [dict(e) for e in self.events if t0 <= e["t"] <= t1]

    def summary(self) -> dict:
        with self._lock:
            evs = list(self.events)
        return {
            "programs": len(evs),
            "hits": sum(e["cache"] == "hit" for e in evs),
            "misses": sum(e["cache"] == "miss" for e in evs),
            "compile_s": round(sum(e["compile_s"] for e in evs), 2),
        }


# -- the run -----------------------------------------------------------------------


@dataclasses.dataclass
class Observations:
    """What a generator hands back. `correct_problems` empty means correct."""

    attempted: int
    failed: int
    end_to_end: dict  # metric name -> value, measured by the generator's host clock
    setup_s: float
    window: tuple[float, float]  # perf_counter at window open and close
    counters_before: dict
    counters_after: dict
    correct_problems: list
    samples: dict = dataclasses.field(default_factory=dict)  # per-layer raw material


class Run:
    """One run of one cell: arguments, the cell's files, and the tools a
    generator uses to reach the device path."""

    def __init__(self, args, cell: dict, config: dict, traffic: dict, t_start: float):
        self.cell = cell
        self.config = config
        self.traffic = traffic
        self.t_start = t_start
        self.seed = args.seed
        self.seconds = float(args.seconds)
        self.traced = bool(args.trace)
        self.platform = args.platform
        self.chips = int(cell["chips"])
        self.compile_log = CompileLog()
        self.backend = None
        self.device: dict = {}
        self.peaks: dict | None = None  # this device's row of peaks.json
        self.trace: dict | None = None  # trace_reduce's output, traced runs
        self.trace_counters: tuple[dict, dict] | None = None  # at trace start and stop
        self._trace_dir: str | None = None
        self._trace_t0 = 0.0
        self._trace_window_s: float | None = None
        os.makedirs(CACHE_DIR, exist_ok=True)

    def cache_path(self, *parts: str) -> str:
        name = f"{self.cell['config']}-{self.cell['traffic']}-{self.seed}"
        return os.path.join(CACHE_DIR, name, *parts)

    # -- device and backend -------------------------------------------------------

    def start_backend(self):
        """Imports JAX, refuses any device but the one the cell asks for,
        and returns `get_backend()` under CMTPU_BACKEND=auto: the node's own
        chain (engine -> supervisor -> hybrid -> cpu)."""
        os.environ["CMTPU_BACKEND"] = "auto"
        self.compile_log.install()
        import jax

        import cometbft_tpu.ops  # noqa: F401  (places the compile cache)
        from cometbft_tpu import native
        from cometbft_tpu.sidecar import backend as backend_mod

        devs = jax.devices()
        self.device = {
            "platform": str(devs[0].platform),
            "kind": str(devs[0].device_kind),
            "count": len(devs),
        }
        say(f"device {json.dumps(self.device)} jax {jax.__version__} cpus {os.cpu_count()} "
            f"cache {jax.config.jax_compilation_cache_dir}")
        if self.device["platform"] != self.platform:
            raise BenchFailure(
                f"wanted platform {self.platform!r}, JAX found {self.device['platform']!r}"
            )
        if self.platform == "tpu" and self.device["count"] != self.chips:
            raise BenchFailure(
                f"the cell asks for {self.chips} chip(s), JAX found {self.device['count']}"
            )
        with open(os.path.join(HERE, "peaks.json")) as f:
            peaks = json.load(f)
        if self.platform == "tpu" and self.device["kind"] not in peaks:
            raise BenchFailure(f"device kind {self.device['kind']!r} is not in peaks.json")
        self.peaks = peaks.get(self.device["kind"])
        native.require()  # a failed gcc stops the run here, with gcc's message
        backend_mod.set_backend(None)
        if self.platform == "tpu":
            backend = backend_mod.get_backend()
        else:
            # Rehearsal only: `auto` never puts a device tier on XLA:CPU, so
            # the selection alone is answered for it (as chip_smoke.py does);
            # the chain is still assembled by the program.
            from unittest import mock

            from cometbft_tpu.sidecar import supervisor

            with mock.patch.object(
                supervisor, "device_backend", lambda choice: backend_mod.HybridBackend()
            ):
                backend = backend_mod.get_backend()
        if self.traced:
            backend = SeamSpans(backend)
            backend_mod.set_backend(backend)
        self.backend = backend
        return backend

    def counters(self) -> dict:
        """The chain's counters, flattened to what the metrics read:
        `engine`, `supervisor`, `hybrid` (each the tier's own dict)."""
        c = self.backend.counters()
        sup = c.get("inner", {})
        hybrid = sup.get("tiers", {}).get("hybrid", {}).get("backend", {})
        engine = {k: v for k, v in c.items() if k != "inner"}
        return {
            "engine": engine,
            "supervisor": {k: v for k, v in sup.items() if k != "tiers"},
            "hybrid_tier": {
                k: v for k, v in sup.get("tiers", {}).get("hybrid", {}).items() if k != "backend"
            },
            "hybrid": hybrid,
        }

    def health_problems(self, before: dict, after: dict) -> list[str]:
        """A run answered by the cpu anchor is not a slow run: it is wrong."""
        out = []
        sup, hyb = after["supervisor"], after["hybrid"]
        if (sup.get("chain") or [None])[0] != "hybrid":
            out.append(f"chain is {sup.get('chain')}, not hybrid-first")
        if sup.get("active_tier") != "hybrid":
            out.append(f"active tier is {sup.get('active_tier')}")
        for key in SUPERVISOR_EVENTS:
            if sup.get(key, 0) != 0:
                out.append(f"supervisor counted {key} = {sup.get(key)}")
        if after["hybrid_tier"].get("failures", 0) != 0:
            out.append(f"hybrid tier failed {after['hybrid_tier']['failures']} calls")
        if hyb.get("platform") != self.platform:
            out.append(f"hybrid tier runs on {hyb.get('platform')}")
        if hyb.get("native") != "ready":
            out.append(f"native library: {hyb.get('native')}")
        if hyb.get("device_lanes", 0) <= before["hybrid"].get("device_lanes", 0):
            out.append("no lane of the window ran on the device")
        return out

    def setup_done(self) -> float:
        return time.time() - self.t_start

    # -- profiler trace -----------------------------------------------------------

    def trace_start(self) -> None:
        import jax

        self._trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the Python tracer slows the host it observes
        jax.profiler.start_trace(self._trace_dir, profiler_options=options)
        self._trace_c0 = self.counters()
        self._trace_t0 = time.perf_counter()

    def trace_stop(self) -> None:
        """Stops the trace; the window goes on, and `trace_finish` reads it after."""
        import jax

        if self._trace_dir is None or self._trace_window_s is not None:
            return
        self._trace_window_s = time.perf_counter() - self._trace_t0
        self.trace_counters = (self._trace_c0, self.counters())
        jax.profiler.stop_trace()

    def trace_finish(self) -> None:
        """Reduces the stopped trace to `self.trace` and removes its files."""
        import trace_reduce

        if self._trace_dir is None:
            return
        self.trace_stop()
        try:
            planes = trace_reduce.load_xplane_dir(self._trace_dir)
            self.trace = trace_reduce.reduce(planes, self._trace_window_s)
        finally:
            shutil.rmtree(self._trace_dir, ignore_errors=True)
            self._trace_dir = None

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span in the profiler's own trace (traced runs only)."""
        if not self.traced:
            yield
            return
        import jax

        with jax.profiler.TraceAnnotation(name):
            yield

    def memory_peak_bytes(self) -> int:
        import jax

        peak = 0
        for d in jax.local_devices():
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        return peak


class SeamSpans:
    """Timing pass-through at the one public seam between callers and
    backends (`sidecar.backend.set_backend`), installed in traced runs
    only: a span in the profiler's trace round every `batch_verify`."""

    def __init__(self, inner):
        self._inner = inner
        self.calls: list[tuple[int, float, float]] = []  # lanes, t0, t1

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def batch_verify(self, pubs, msgs, sigs):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("seam:batch_verify"):
            out = self._inner.batch_verify(pubs, msgs, sigs)
        self.calls.append((len(pubs), t0, time.perf_counter()))
        return out


def stop_children(children, timeout: float = 10.0) -> list[str]:
    """Tells every (process, pipe) child to stop and waits until each has
    ended. Returns what went wrong: a child that did not answer, or one
    that says it imported JAX while the parent held the chip."""
    problems = []
    for _, conn in children:
        try:
            conn.send("stop")
        except (OSError, ValueError):
            pass
    for proc, conn in children:
        try:
            bye = conn.recv() if conn.poll(timeout) else None
        except (EOFError, OSError):
            bye = None
        if not isinstance(bye, dict) or bye.get("jax_imported") is not False:
            problems.append(f"child {proc.pid} ended with {bye!r}")
        proc.join(timeout)
        if proc.is_alive():
            proc.kill()
            proc.join()
        conn.close()
    return problems


sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)
