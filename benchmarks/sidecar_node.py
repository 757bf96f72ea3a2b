"""The node of traffic kind `commit_stream_sidecar`: a child process that
never imports JAX, with `CMTPU_BACKEND=auto` and `CMTPU_SIDECAR_ADDR` set, so
its chain is engine -> `ResilientBackend` (`grpc` -> `cpu`) and every
verification crosses the wire to the process that holds the chip (the one
that runs `run.py`: see SIDECAR.md).

It runs `commit_stream`'s own `run()`: the fixtures, the operation
(`fresh_commit`, then `vals.verify_commit`, timed by this process's
`perf_counter` around that call alone), the warm-up rule, the window, p50 /
p95 and the answer check. What that code asks of its `Run` and this process
does not hold (the sidecar chain's counters, JAX's compile log, the profiler,
the health check) it asks the parent for over the pipe; what only this
process holds (its own chain's counters, its spans) it adds.
"""

from __future__ import annotations

import contextlib
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
KEPT = ("CMTPU_VERIFY_CACHE_MAX",)  # the node's own cache (the rehearsal makes it tiny)


class _Stopped(Exception):
    """The parent told this child to stop while it was asking for something."""


class _CompileLog:
    """The parent's compile log, asked over the pipe."""

    def __init__(self, ask):
        self._ask = ask

    @property
    def count(self) -> int:
        return self._ask("compile_count")

    def summary(self) -> dict:
        return self._ask("compile_summary")


class NodeRun:
    """What `commit_stream.run` is handed in place of `harness.Run`."""

    def __init__(self, conn, spec: dict):
        self._conn = conn
        self.cell, self.config, self.traffic = spec["cell"], spec["config"], spec["traffic"]
        self.seed, self.seconds = spec["seed"], spec["seconds"]
        self.traced, self.t_start = spec["traced"], spec["t_start"]
        self.compile_log = _CompileLog(self._ask)
        self.backend = None
        self._capture = contextlib.ExitStack()

    def _ask(self, what: str, *args):
        self._conn.send((what, *args))
        answer = self._conn.recv()
        if answer == "stop":  # the parent gave up (no chip, a failed start)
            raise _Stopped
        return answer[0]

    def start_backend(self):
        """Waits until the parent serves, then assembles this node's chain."""
        from cometbft_tpu.sidecar import backend as backend_mod

        os.environ["CMTPU_SIDECAR_ADDR"] = self._ask("addr")
        backend_mod.set_backend(None)
        self.backend = backend_mod.get_backend()
        return self.backend

    def setup_done(self) -> float:
        return time.time() - self.t_start

    def span(self, name: str):
        return contextlib.nullcontext()  # the profiler's annotations are the parent's

    def node_counters(self) -> dict:
        """This node's chain, flattened as `harness.Run.counters` flattens the
        sidecar's: `engine`, `supervisor`, and the `grpc` tier with its client."""
        c = self.backend.counters()
        sup = c.get("inner", {})
        tier = sup.get("tiers", {}).get("grpc", {})
        return {
            "engine": {k: v for k, v in c.items() if k != "inner"},
            "supervisor": {k: v for k, v in sup.items() if k != "tiers"},
            "grpc_tier": {k: v for k, v in tier.items() if k != "backend"},
            "grpc": tier.get("backend", {}),
        }

    def counters(self) -> dict:
        return {**self._ask("counters"), "node": self.node_counters()}

    def health_problems(self, before: dict, after: dict) -> list[str]:
        return self._ask("health_problems", before, after)

    def trace_start(self) -> None:
        from cometbft_tpu.libs import trace

        self._ask("trace_start")
        capture = getattr(trace, "capture", None)  # a program from before it: no node spans
        if capture is not None:
            self._capture.enter_context(capture())

    def trace_stop(self) -> None:
        self._capture.close()
        self._ask("trace_stop")


def node_main(conn, spec: dict) -> None:
    """The child's whole life: scrub the environment, run, hand the
    observations up, wait to be told to stop."""
    for k in [k for k in os.environ if k.startswith("CMTPU_") and k not in KEPT]:
        del os.environ[k]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["CMTPU_BACKEND"] = "auto"
    stopped = False
    try:
        import harness

        base = harness.load_by_path(
            os.path.join(HERE, "generators", "commit_stream.py"), "generator_commit_stream"
        )
        run = NodeRun(conn, spec)
        obs = base.run(run)
        if run.traced:
            from cometbft_tpu.libs import trace

            obs.samples["node_spans"] = trace.spans()
            obs.samples["node_dropped"] = trace.dropped()
        conn.send(("result", obs))
    except _Stopped:
        stopped = True
    except Exception:
        conn.send(("failed", traceback.format_exc()))
    try:
        if not stopped:
            conn.recv()  # "stop", or EOF when the parent goes away
        conn.send({"jax_imported": "jax" in sys.modules})
    except (EOFError, OSError):
        pass
    conn.close()
