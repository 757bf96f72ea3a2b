"""Runtime profiling endpoints — the net/http/pprof analog
(reference: node/node.go:379-383 wiring config.RPC.PprofListenAddress,
DESIGN: SURVEY §5.1).

Python-native equivalents of the Go profiles, plus the device tier's:

  /debug/pprof/            index
  /debug/pprof/goroutine   every thread's current stack (threads are the
                           goroutine analog here)
  /debug/pprof/heap        tracemalloc top allocations (started on demand)
  /debug/pprof/profile     wall-clock sampling profile over ?seconds=N
                           (default 5): samples sys._current_frames and
                           aggregates frame stacks, text output
  /debug/jax/memory        per-device HBM stats (jax memory_stats)
  /debug/jax/trace         capture a JAX profiler trace for ?seconds=N into
                           ?dir= (default <home>/jax-trace) — loadable in
                           TensorBoard/Perfetto; the XLA-level view of the
                           verify/merkle kernels. The session turns the
                           program's spans on (libs/trace.py): they lie in
                           the xplane as `seam:<name>` and are written as
                           spans.json beside it
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
import traceback
from collections import Counter
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlparse


def thread_stacks() -> str:
    """All live thread stacks (the goroutine dump analog)."""
    frames = sys._current_frames()
    by_ident = {t.ident: t for t in threading.enumerate()}
    out = []
    for ident, frame in frames.items():
        t = by_ident.get(ident)
        name = t.name if t else f"thread-{ident}"
        daemon = " daemon" if (t and t.daemon) else ""
        out.append(f"--- {name} (ident {ident}{daemon}) ---")
        out.append("".join(traceback.format_stack(frame)))
    return "\n".join(out)


def sample_profile(seconds: float = 5.0, hz: int = 100) -> str:
    """Wall-clock sampling profiler: aggregate stack samples across all
    threads for `seconds`, report hottest stacks (pprof 'profile' analog
    without a C agent)."""
    counts: Counter = Counter()
    interval = 1.0 / hz
    deadline = time.monotonic() + seconds
    n = 0
    while time.monotonic() < deadline:
        for frame in sys._current_frames().values():
            stack = []
            f = frame
            while f is not None and len(stack) < 24:
                # co_qualname is 3.11+; co_name keeps 3.10 serving samples.
                qn = getattr(f.f_code, "co_qualname", f.f_code.co_name)
                stack.append(f"{f.f_code.co_filename}:{f.f_lineno}:{qn}")
                f = f.f_back
            counts[tuple(reversed(stack))] += 1
        n += 1
        time.sleep(interval)
    out = [f"# wall-clock samples: {n} over {seconds}s at ~{hz}Hz"]
    for stack, c in counts.most_common(40):
        out.append(f"\n{c} samples:")
        out.extend(f"  {line}" for line in stack[-12:])
    return "\n".join(out)


def heap_profile(top: int = 50) -> str:
    import tracemalloc

    if not tracemalloc.is_tracing():
        tracemalloc.start()
        return (
            "tracemalloc just started — allocations are tracked from NOW; "
            "re-request this endpoint after exercising the node."
        )
    snap = tracemalloc.take_snapshot()
    stats = snap.statistics("lineno")[:top]
    total = sum(s.size for s in snap.statistics("filename"))
    out = [f"# tracemalloc: {total / 1e6:.1f} MB tracked"]
    out.extend(str(s) for s in stats)
    return "\n".join(out)


def jax_memory() -> str:
    try:
        import jax

        out = []
        for d in jax.local_devices():
            stats = d.memory_stats() or {}
            out.append(f"{d}: " + ", ".join(f"{k}={v}" for k, v in sorted(stats.items())))
        return "\n".join(out) or "no devices"
    except Exception as e:
        return f"jax unavailable: {e}"


def jax_trace(seconds: float, trace_dir: str) -> str:
    """A profiler session of `seconds`: it is also what turns the program's
    spans on (libs/trace.py), and the capture's spans are written as
    `spans.json` beside the xplane, on `time.perf_counter()`, with `threads`:
    the CPU seconds each role's threads used over the session
    (`trace.thread_cpu()` at its two edges)."""
    import jax

    from cometbft_tpu.libs import trace

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # the Python tracer slows the host it observes
    t0 = time.perf_counter()
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    before = trace.thread_cpu()
    time.sleep(seconds)
    after = trace.thread_cpu()
    jax.profiler.stop_trace()
    captured = [s for s in trace.spans() if s["t0"] >= t0]
    # Over both edges' roles, so the differences still close on `process`. A
    # role whose threads come and go (a prefetch job a height) reads only what
    # its live ones held at the edges: its work is in `ended_or_native`.
    threads = {role: after.get(role, 0.0) - before.get(role, 0.0)
               for role in {**before, **after}}
    with open(os.path.join(trace_dir, "spans.json"), "w") as f:
        json.dump({"t0": t0, "t1": time.perf_counter(), "dropped": trace.dropped(),
                   "spans": captured, "threads": threads}, f)
    return (
        f"trace written to {trace_dir} (open with TensorBoard/Perfetto); "
        f"{len(captured)} program spans in spans.json"
    )


class PprofServer:
    """The /debug HTTP listener (config.rpc.pprof_laddr)."""

    def __init__(self, host: str = "127.0.0.1", port: int = 6060, trace_dir: str = "jax-trace"):
        self.host, self.port = host, port
        self.trace_dir = trace_dir
        self._httpd = None

    def start(self) -> None:
        server = self

        class Handler(BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802
                u = urlparse(self.path)
                q = parse_qs(u.query)
                try:
                    if u.path in ("/debug/pprof", "/debug/pprof/"):
                        body = (
                            "profiles:\n  goroutine\n  heap\n  profile?seconds=N\n"
                            "device:\n  /debug/jax/memory\n  /debug/jax/trace?seconds=N\n"
                        )
                    elif u.path == "/debug/pprof/goroutine":
                        body = thread_stacks()
                    elif u.path == "/debug/pprof/heap":
                        body = heap_profile()
                    elif u.path == "/debug/pprof/profile":
                        secs = float(q.get("seconds", ["5"])[0])
                        body = sample_profile(min(secs, 60.0))
                    elif u.path == "/debug/jax/memory":
                        body = jax_memory()
                    elif u.path == "/debug/jax/trace":
                        secs = float(q.get("seconds", ["3"])[0])
                        tdir = q.get("dir", [server.trace_dir])[0]
                        body = jax_trace(min(secs, 60.0), tdir)
                    else:
                        self.send_response(404)
                        self.end_headers()
                        return
                except Exception as e:
                    self.send_response(500)
                    self.end_headers()
                    self.wfile.write(str(e).encode())
                    return
                raw = body.encode()
                self.send_response(200)
                self.send_header("Content-Type", "text/plain")
                self.send_header("Content-Length", str(len(raw)))
                self.end_headers()
                self.wfile.write(raw)

            def log_message(self, *a):
                pass

        self._httpd = ThreadingHTTPServer((self.host, self.port), Handler)
        if self.port == 0:
            self.port = self._httpd.server_address[1]
        threading.Thread(target=self._httpd.serve_forever, daemon=True).start()

    def stop(self) -> None:
        if self._httpd:
            self._httpd.shutdown()
            self._httpd.server_close()
