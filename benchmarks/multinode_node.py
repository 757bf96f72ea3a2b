"""A node of traffic kind `commit_stream_4nodes`: one of N child processes
that never import JAX, each with `CMTPU_BACKEND=auto` and `CMTPU_SIDECAR_ADDR`
set, so its chain is engine -> `ResilientBackend` (`grpc` -> `cpu`), its own
verified-triple cache and its own connection to the one sidecar (the process
that runs `run.py`: MULTINODE.md).

The parent drives it over a pipe, one command at a time: it is handed the
pool's signatures (signed once, by the parent's workers) and rebuilds the
validator set and the commits from `--seed`; at each height it is told which
commit and the instant (`time.perf_counter()`, one clock for every process of
the host) at which to start its `vals.verify_commit`, which it times by its
own clock around that call alone. Everything the parent needs of it comes
back as the command's answer: its chain's counters and its cache's, its
spans, the answer check of `commit_stream` run through this node alone.
"""

from __future__ import annotations

import contextlib
import gc
import hashlib
import os
import sys
import time
import traceback

import sidecar_node

HERE = os.path.dirname(os.path.abspath(__file__))


def columns_digest(pubs, msgs, sigs) -> str:
    """What names a request's triples across processes: SHA-256 over the
    three columns, each entry behind its length."""
    h = hashlib.sha256()
    for col in (pubs, msgs, sigs):
        h.update(b"%d:" % len(col))
        for x in col:
            h.update(b"%d:" % len(x))
            h.update(x)
    return h.hexdigest()


def rebuild_commits(seed: int, vals, blobs: dict[int, bytes]):
    """[(block_id, commit)] by height from the signature blobs (64 bytes a
    validator, in set order), as `fixtures.make_commits_async` builds them."""
    import fixtures

    from cometbft_tpu.types import Commit
    from cometbft_tpu.types.block import CommitSig

    addresses = [v.address for v in vals.validators]
    out = []
    for h in sorted(blobs):
        blob = blobs[h]
        bid = fixtures.block_id_for(seed, h)
        sigs = [
            CommitSig(2, addr, fixtures.vote_time(seed, h, j), blob[64 * j : 64 * j + 64])
            for j, addr in enumerate(addresses)
        ]
        out.append((bid, Commit(height=h, round=0, block_id=bid, signatures=sigs)))
    return out


def flipped_lanes(seed: int, n_vals: int, k: int) -> list[int]:
    """The lanes `commit_stream`'s answer check flips, by the same draw."""
    import random

    return sorted(random.Random(f"{seed}/flip").sample(range(n_vals * 2 // 3), k))


class _Recorder:
    """Pass-through at the seam (`sidecar.backend.set_backend`) for one
    height: what this node sent and the bitmap it was given."""

    def __init__(self, inner):
        self._inner = inner
        self.calls: list[dict] = []

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def batch_verify(self, pubs, msgs, sigs):
        ok, bits = self._inner.batch_verify(pubs, msgs, sigs)
        self.calls.append({"digest": columns_digest(pubs, msgs, sigs), "lanes": len(pubs),
                           "ok": bool(ok), "bits": bytes(bits)})
        return ok, bits


class Node:
    def __init__(self, conn, spec: dict):
        import fixtures

        self.conn = conn
        self.seed, self.traffic = spec["seed"], spec["traffic"]
        self.n_vals = int(spec["config"]["validators"])
        tag = spec["cell"]["config"]
        self.chain_id = fixtures.chain_id_for(self.seed, tag)
        self.vals, _ = fixtures.make_validator_set(self.seed, tag, self.n_vals)
        self.commits: list = []
        self.backend = None
        self._capture = contextlib.ExitStack()

    # -- what the parent asks for ---------------------------------------------------

    def fixtures(self, blobs: dict[int, bytes]) -> dict:
        self.commits = rebuild_commits(self.seed, self.vals, blobs)
        # The pool is the benchmark's, not the node's: keep the collector from
        # walking its signature objects inside an operation.
        gc.collect()
        gc.freeze()
        return {"commits": len(self.commits)}

    def connect(self, addr: str) -> dict:
        from cometbft_tpu.sidecar import backend as backend_mod

        os.environ["CMTPU_SIDECAR_ADDR"] = addr
        backend_mod.set_backend(None)
        self.backend = backend_mod.get_backend()
        return {"backend": self.backend.name}

    def height(self, index: int, t_go: float, flip: bool = False, record: bool = False) -> dict:
        """One operation: the commit at `index` as a fresh object (with the
        seeded flipped signatures if `flip`), `verify_commit` started at
        `t_go` and timed round the call alone."""
        import fixtures

        from cometbft_tpu.sidecar import backend as backend_mod

        bid, commit = self.commits[index]
        if flip:
            lanes = flipped_lanes(self.seed, self.n_vals, int(self.traffic["flipped_lanes"]))
            commit = fixtures.flip_signatures(commit, lanes)
        else:
            commit = fixtures.fresh_commit(commit)  # as decoded: nothing memoized on it
        rec = None
        if record:
            rec = _Recorder(backend_mod.get_backend())
            backend_mod.set_backend(rec)
        out = {"error": None}
        try:
            wait = t_go - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            t0 = time.perf_counter()
            try:
                self.vals.verify_commit(self.chain_id, bid, commit.height, commit)
            except Exception as e:  # a refusal or an error is a failed operation
                out["error"] = f"{type(e).__name__}: {e}"
            out["t0"], out["dt"] = t0, time.perf_counter() - t0
            out["late"] = t0 - t_go
        finally:
            if rec is not None:
                backend_mod.set_backend(rec._inner)
                out["calls"] = rec.calls
        return out

    def counters(self) -> dict:
        """This node's chain, flattened as the one-node kind's child flattens
        its own (`self.backend` is all that reads), and its cache's counters."""
        from cometbft_tpu.crypto import ed25519

        return {**sidecar_node.NodeRun.node_counters(self),
                "cache": ed25519.verified_cache_counters()}

    def capture(self, on: bool) -> dict:
        from cometbft_tpu.libs import trace

        if on:
            self._capture.enter_context(trace.capture())
        else:
            self._capture.close()
        return {}

    def spans(self) -> dict:
        from cometbft_tpu.libs import trace

        return {"spans": trace.spans(), "dropped": trace.dropped()}

    def check_answers(self, next_i: int) -> dict:
        """`commit_stream`'s answer check, through this node alone; the
        sidecar chain's lane counters it reads are the parent's."""
        import harness

        base = harness.load_by_path(
            os.path.join(HERE, "generators", "commit_stream.py"), "generator_commit_stream"
        )
        pool = self.commits[: int(self.traffic["pool_commits"])]
        problems = base._check_answers(_AnswerRun(self), self.chain_id, self.vals, pool,
                                       self.n_vals, next_i)
        return {"problems": problems}

    def ask(self, what: str, *args):
        """A question to the parent in the middle of a command."""
        self.conn.send(("ask", what, *args))
        answer = self.conn.recv()
        if answer == "stop":
            raise sidecar_node._Stopped
        return answer[0]


class _AnswerRun:
    """What `commit_stream._check_answers` reads of its `Run`: the traffic
    file, the seed, and the sidecar chain's counters, which are the parent's."""

    def __init__(self, node: Node):
        self.traffic, self.seed = node.traffic, node.seed
        self.counters = lambda: node.ask("counters")


def node_main(conn, spec: dict) -> None:
    """The child's whole life: scrub the environment, answer the parent's
    commands until told to stop, say whether JAX was ever imported."""
    for k in [k for k in os.environ if k.startswith("CMTPU_") and k not in sidecar_node.KEPT]:
        del os.environ[k]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["CMTPU_BACKEND"] = "auto"
    try:
        node = Node(conn, spec)
        conn.send(("done", {"validators": node.n_vals}))
        while True:
            msg = conn.recv()
            if msg == "stop":
                break
            what, *args = msg
            conn.send(("done", getattr(node, what)(*args)))
    except sidecar_node._Stopped:
        pass
    except (EOFError, OSError):
        return
    except Exception:
        try:
            conn.send(("failed", traceback.format_exc()))
            conn.recv()  # "stop", or EOF when the parent goes away
        except (EOFError, OSError):
            return
    try:
        conn.send({"jax_imported": "jax" in sys.modules})
    except (EOFError, OSError):
        pass
    conn.close()
