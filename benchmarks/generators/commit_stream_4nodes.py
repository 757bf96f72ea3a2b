"""Traffic kind `commit_stream_4nodes`: N nodes of ONE chain behind one
sidecar (`configs/valset-10000-sidecar-4nodes.json`; MULTINODE.md). N node
processes that never import JAX (`multinode_node.py`; each `CMTPU_BACKEND=auto`
+ `CMTPU_SIDECAR_ADDR`, chain `grpc` -> `cpu`, its own verified-triple cache
and its own connection) verify the SAME fully signed commit at each height,
each starting at a seeded offset inside `arrival_skew_ms`; height h+1 is
released to all when all have answered height h. The process that runs
`run.py` is the sidecar, as in `commit_stream_sidecar`: it starts the backend
as every cell does, serves it by the program's own `open_sidecar`, releases the
heights and otherwise only answers.

An operation is one node's `vals.verify_commit`, timed by that node's
`perf_counter` around the call alone (the offset is outside it);
`commit_verify_p50_ms` / `commit_verify_p95_ms` are over all nodes' operations
of the window. The pool is signed once, by this process's fixture workers, and
handed to the nodes as signatures; one commit more than the pool is signed
for the answer check and is never in any cache.

`correct` = `commit_stream_sidecar`'s for every node (first tier answered
every call, no supervisor event in any process, no child imported JAX, lanes
the nodes sent = the server's `lanes_in`) and: every node's cache counted
only `whole_miss` over the window; heights x validators <= lanes the hybrid
tier ran over the window <= lanes offered; `connections_accepted` = N; outside
the window `commit_stream`'s answer check through node 0 alone, and one
height, zero skew, at which node 0 is handed the check commit with its seeded
flipped signatures and the others the good one: node 0 is refused at exactly
the first flipped lane, the others accept, and all N bitmaps equal
`reference/answers_alone.py` (computed in set-up by the fixture workers). A
traced run also holds the ring to it: every traced operation joined to
exactly one `sidecar.request` of its own connection, no `engine.dispatch`
over the cap in `unique` lanes, one at least with two requests and `lanes`
over the cap.

A program whose engine sizes a merged dispatch by the lanes its requests
offered (before PR 32) cannot run the kind: `_program` finds out on a stub
backend, before any process or JAX is started, and the run ends with exit
code 3 and a line that says so.

Parameters (the traffic file): nodes, pool_commits, arrival_skew_ms,
warm_min_ops, warm_quiet_ops, warm_max_ops (heights), flipped_lanes,
sample_lanes, trace_seconds.
"""

from __future__ import annotations

import gc
import multiprocessing
import multiprocessing.connection
import os
import random
import statistics
import time

import fixtures
import harness
import multinode_node
import multinodelib
import sidecarlib

LEAD_S = 0.002  # a height's release lies this far ahead of the messages that announce it

# The one-node kind: its program check and its pipe timeout are this kind's too.
single = harness.load_by_path(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "commit_stream_sidecar.py"),
    "generator_commit_stream_sidecar",
)


def _program():
    """The program's server function (as the one-node kind asks), and an
    engine in which queued copies of one request cost the merge cap once:
    two 8-lane copies against a cap of 12 must be one dispatch."""
    service = single._program()
    from cometbft_tpu.sidecar.engine import VerificationEngine

    class Stub:
        name = "stub"

        def __init__(self):
            self.calls: list[int] = []

        def batch_verify(self, pubs, msgs, sigs):
            self.calls.append(len(pubs))
            return True, [True] * len(pubs)

    stub = Stub()
    eng = VerificationEngine(stub, hold_ms=250.0, max_sigs=12)
    try:
        cols = [bytes([i]) * 32 for i in range(8)]
        futs = [eng.submit(list(cols), list(cols), list(cols)) for _ in range(2)]
        for f in futs:
            f.result(30)
    finally:
        eng.close()
    if stub.calls != [8]:
        raise harness.BenchFailure(
            "this program cannot run kind commit_stream_4nodes: its engine sizes a merged dispatch "
            f"by the lanes its requests offered (two 8-lane copies against a cap of 12 made calls "
            f"of {stub.calls} lanes, not one of 8), so whole commits from several nodes never merge"
        )
    return service


class _Nodes:
    """The node children and the pipe protocol: a command to each, the
    answers of all; a node's question in between is answered at once."""

    def __init__(self, run: harness.Run, n: int):
        ctx = multiprocessing.get_context("spawn")
        self.children = []
        for k in range(n):
            conn, child_conn = ctx.Pipe()
            spec = {"cell": run.cell, "config": run.config, "traffic": run.traffic,
                    "seed": run.seed, "index": k}
            proc = ctx.Process(target=multinode_node.node_main, args=(child_conn, spec))
            proc.start()
            child_conn.close()
            self.children.append((proc, conn))
        self.answers = {"counters": run.counters}

    def gather(self, only=None) -> list:
        """The `done` of each node asked (all, or those in `only`), by index."""
        waiting = {conn: k for k, (_, conn) in enumerate(self.children)
                   if only is None or k in only}
        out: dict[int, object] = {}
        while waiting:
            ready = multiprocessing.connection.wait(list(waiting), single.RPC_TIMEOUT_S)
            if not ready:
                raise harness.BenchFailure(
                    f"nodes {sorted(waiting.values())} said nothing for {single.RPC_TIMEOUT_S:.0f} s")
            for conn in ready:
                try:
                    what, *args = conn.recv()
                except EOFError:
                    raise harness.BenchFailure(f"node {waiting[conn]} ended without an answer")
                if what == "ask":
                    conn.send((self.answers[args[0]](*args[1:]),))
                elif what == "failed":
                    raise harness.BenchFailure(f"node {waiting[conn]} failed:\n{args[0]}")
                else:
                    out[waiting.pop(conn)] = args[0]
        return [out[k] for k in sorted(out)]

    def all(self, what: str, *args) -> list:
        for _, conn in self.children:
            conn.send((what, *args))
        return self.gather()

    def one(self, k: int, what: str, *args):
        self.children[k][1].send((what, *args))
        return self.gather(only={k})[0]

    def height(self, index: int, offsets, flip=(), record: bool = False) -> list[dict]:
        """Releases one height: node k starts at the release + offsets[k]."""
        t_release = time.perf_counter() + LEAD_S
        for k, (_, conn) in enumerate(self.children):
            conn.send(("height", index, t_release + offsets[k], k in flip, record))
        done = self.gather()
        for d in done:
            d["t_release"] = t_release
        return done


def _reference(pool, requests):
    """Starts `answers_alone` of `requests` in the fixture workers, a slice
    of lanes a job; the returned function waits and gives each request's
    (ok, bitmap)."""
    step = max(1, -(-len(requests[0][0]) // (4 * fixtures.worker_count())))
    jobs, spans = [], []
    for r, (pubs, msgs, sigs) in enumerate(requests):
        for lo in range(0, len(pubs), step):
            jobs.append((pubs[lo:lo + step], msgs[lo:lo + step], sigs[lo:lo + step]))
            spans.append(r)
    pending = pool.map_async(multinodelib.reference_slice, jobs)

    def finish():
        bits: list[list[bool]] = [[] for _ in requests]
        for r, part in zip(spans, pending.get()):
            bits[r] += part
        return [(bool(b) and all(b), b) for b in bits]

    return finish


def _check_requests(chain_id, vals, commit, flipped):
    """The triples each node's `verify_commit` of the check commit sends, in
    validator-set order: (the flipped copy's, the good one's)."""
    out = []
    for c in (fixtures.flip_signatures(commit, flipped), commit):
        sbs = c.vote_sign_bytes_all(chain_id)
        out.append(([v.pub_key.bytes() for v in vals.validators], [bytes(sb) for sb in sbs],
                    [s.signature for s in c.signatures]))
    return out


def _sum_counters(per_node: list[dict], tier: str) -> dict:
    keys = {k for c in per_node for k, v in c[tier].items() if isinstance(v, (int, float))
            and not isinstance(v, bool)}
    return {k: sum(c[tier].get(k, 0) for c in per_node) for k in keys}


def _med_ms(xs) -> str:
    xs = list(xs)
    return f"{statistics.median(xs) * 1000:.1f}" if xs else "-"


def _quartiles_ms(xs) -> str:
    xs = list(xs)
    if len(xs) < 4:
        return "-"
    q = statistics.quantiles(xs, n=4)
    return f"{q[0] * 1000:.1f} / {q[2] * 1000:.1f}"


def _node_problems(k: int, before: dict, after: dict, ops: int) -> list[str]:
    """The one-node kind's checks of its node, for node k (its lanes are held
    to the server's with the other nodes', not here), and its cache's."""
    sent = sidecarlib.grown(before["grpc"], after["grpc"], "lanes_sent")
    out = [f"node {k}: {p}" for p in single._node_problems(before, after, sent)]
    cache = {p: sidecarlib.grown(before["cache"], after["cache"], p)
             for p in ("whole_miss", "whole_hit", "mixed")}
    if cache != {"whole_miss": ops, "whole_hit": 0, "mixed": 0}:
        out.append(f"node {k}: its cache counted {cache} over {ops} operations, not whole_miss alone")
    return out


def _check_height(nodes: _Nodes, n_nodes: int, index: int, flipped, want, expected) -> list[str]:
    """The height at which node 0 is handed the flipped copy, zero skew."""
    done = nodes.height(index, [0.0] * n_nodes, flip={0}, record=True)
    problems = []
    for k, d in enumerate(done):
        digest, (ok, bits) = expected[0 if k == 0 else 1], want[0 if k == 0 else 1]
        if k == 0 and f"wrong signature (#{flipped[0]})" not in (d["error"] or ""):
            problems.append(f"node 0 was not refused at the first flipped lane {flipped[0]}: {d['error']}")
        if k > 0 and d["error"] is not None:
            problems.append(f"node {k} refused the good commit: {d['error']}")
        calls = d.get("calls", [])
        if len(calls) != 1 or calls[0]["digest"] != digest or calls[0]["lanes"] != len(bits):
            problems.append(f"node {k} did not send the check commit's triples whole, once: "
                            f"{[(c['lanes'], c['digest'][:12]) for c in calls]}")
        elif (calls[0]["ok"], [bool(b) for b in calls[0]["bits"]]) != (ok, bits):
            got = [j for j, b in enumerate(calls[0]["bits"]) if not b]
            problems.append(f"node {k}: bitmap false at {got[:12]}, answers_alone at "
                            f"{[j for j, b in enumerate(bits) if not b][:12]}")
    harness.say(f"check height: node 0 refused at lane {flipped[0]} of flipped {flipped}, "
                f"nodes 1-{n_nodes - 1} accepted, {n_nodes} bitmaps of {len(want[0][1])} lanes against "
                f"answers_alone; problems {len(problems)}")
    return problems


def _trace_problems(obs) -> list[str]:
    """What a traced run's ring must show (module text)."""
    import spanlib

    ops = obs.samples.get("wire_ops")
    mine = spanlib.window_spans(obs)
    if not ops or not mine:
        return ["a traced run with no joined operations: the spans do not name the connection"]
    out = []
    unjoined = [e["node_index"] for e in ops if len(e["requests"]) != 1]
    if unjoined:
        out.append(f"{len(unjoined)} of {len(ops)} traced operations are not joined to exactly one "
                   f"sidecar.request of their own connection (nodes {sorted(set(unjoined))})")
    cap = obs.counters_after["engine"].get("max_sigs", 0)
    dispatches = spanlib.named(mine, "engine.dispatch")
    over = [d["attrs"].get("unique") for d in dispatches if d["attrs"].get("unique", 0) > cap]
    if over or any("unique" not in d["attrs"] for d in dispatches):
        out.append(f"engine.dispatch spans over the cap of {cap} unique lanes (or without `unique`): {over}")
    if not any(d["attrs"].get("requests", 1) >= 2 and d["attrs"].get("lanes", 0) > cap
               for d in dispatches):
        out.append(f"no traced dispatch merged requests that offered more than the cap of {cap} lanes")
    return out


def run(run: harness.Run) -> harness.Observations:
    service = _program()
    cfg, tr = run.config, run.traffic
    n_vals, n_nodes, pool_n = int(cfg["validators"]), int(tr["nodes"]), int(tr["pool_commits"])
    tag = run.cell["config"]
    flipped = multinode_node.flipped_lanes(run.seed, n_vals, int(tr["flipped_lanes"]))
    pool = fixtures.start_pool()
    nodes = server = None
    child_problems: list[str] = []
    try:
        # Signing starts before JAX does, and the nodes derive the validator
        # set meanwhile; the pool's workers then compute the plain reference
        # of the check height while the backend starts.
        pending = fixtures.make_commits_async(pool, run.seed, tag, n_vals, pool_n + 1)
        nodes = _Nodes(run, n_nodes)
        chain_id, vals, commits = pending()
        harness.say(f"fixtures: {pool_n} + 1 commits x {n_vals} validators signed once and checked by "
                    f"OpenSSL after {run.setup_done():.1f} s")
        blobs = {c.height: b"".join(s.signature for s in c.signatures) for _, c in commits}
        requests = _check_requests(chain_id, vals, commits[pool_n][1], flipped)
        expected = [multinode_node.columns_digest(*r) for r in requests]
        reference = _reference(pool, [requests[0]] + [requests[1]] * (n_nodes - 1))
        nodes.gather()  # each has its validator set
        nodes.all("fixtures", blobs)
        del commits, blobs, requests, vals
        run.start_backend()
        server = service.open_sidecar("127.0.0.1:0", run.backend, say=harness.say).start()
        nodes.all("connect", server.bound_addr)
        t_ref = time.time()
        alone = reference()
        want = [alone[0], alone[1]]
        if any(a != alone[1] for a in alone[2:]):
            raise harness.BenchFailure("answers_alone gave equal requests different answers")
        harness.say(f"reference: answers_alone of {n_nodes} x {n_vals} lanes, waited "
                    f"{time.time() - t_ref:.1f} s for it after the backend was up")
    except BaseException:
        if nodes is not None:
            harness.stop_children(nodes.children)
        if server is not None:
            service.close_sidecar(server, say=harness.say)
        raise
    finally:
        pool.terminate()  # done, or not wanted any more (no chip)
        pool.join()
    try:
        obs = _measure(run, nodes, server, n_vals, n_nodes, pool_n, flipped, want, expected)
    finally:
        child_problems = harness.stop_children(nodes.children)
        service.close_sidecar(server, say=harness.say)
    obs.correct_problems += child_problems
    return obs


def _measure(run, nodes: _Nodes, server, n_vals, n_nodes, pool_n, flipped, want, expected):
    tr = run.traffic
    skew_s = float(tr["arrival_skew_ms"]) / 1000.0
    rng = random.Random(f"{run.seed}/skew")
    # The sidecar's own objects are few; keep the collector off its threads.
    gc.collect()
    gc.freeze()

    def counters() -> dict:
        per_node = nodes.all("counters")
        return {**run.counters(), "server": server.counters(), "nodes": per_node,
                "node": {t: _sum_counters(per_node, t) for t in ("grpc", "cache")}}

    def height(i: int) -> list[dict]:
        return nodes.height(i % pool_n, [rng.uniform(0.0, skew_s) for _ in range(n_nodes)])

    # Warm phase: `commit_stream`'s rule, counted in heights.
    i = quiet = 0
    shares: set = set()
    while i < int(tr["warm_max_ops"]):
        compiles = run.compile_log.count
        done = height(i)
        i += 1
        share = run.counters()["hybrid"].get("last_share")
        same = run.compile_log.count == compiles and share in shares
        quiet = quiet + 1 if same else 0
        shares.add(share)
        if not same:
            harness.say(f"warm height {i}: {[round(d['dt'] * 1000, 1) for d in done]} ms, share {share}, "
                        f"compile events {run.compile_log.count - compiles}")
        failed = [d["error"] for d in done if d["error"]]
        if failed:
            raise harness.BenchFailure(f"a warm-up operation failed: {failed[0]}")
        if i >= int(tr["warm_min_ops"]) and quiet >= int(tr["warm_quiet_ops"]):
            break
    harness.say(f"warm-up: {i} heights, last {quiet} quiet, shares {sorted(shares)}, "
                f"compile log {run.compile_log.summary()}")

    before = counters()
    setup_s = run.setup_done()
    lat, late, failed, heights = [], [], 0, []
    if run.traced:
        nodes.all("capture", True)
        run.trace_start()
    t_open = time.perf_counter()
    trace_until = t_open + float(tr["trace_seconds"])
    t_end = t_open + run.seconds
    tracing = run.traced
    while time.perf_counter() < t_end:
        done = height(i)
        ends = [d["t0"] + d["dt"] - done[0]["t_release"] for d in done]
        heights.append({"index": i, "t_release": done[0]["t_release"], "t_done": time.perf_counter(),
                        "first_s": min(ends), "last_s": max(ends)})
        i += 1
        for k, d in enumerate(done):
            if d["error"] is None:
                lat.append(d["dt"])
            else:
                failed += 1
                harness.say(f"height {i}, node {k} failed: {d['error']}")
            late.append(d["late"])
        if tracing and time.perf_counter() >= trace_until:
            run.trace_stop()  # the window goes on untraced
            nodes.all("capture", False)
            tracing = False
    t_close = time.perf_counter()
    if tracing:
        run.trace_stop()
        nodes.all("capture", False)
    after = counters()
    attempted = len(lat) + failed

    problems = run.health_problems(before, after)
    for k in range(n_nodes):
        problems += _node_problems(k, before["nodes"][k], after["nodes"][k], len(heights))
    offered = attempted * n_vals
    ran = sum(sidecarlib.grown(before["hybrid"], after["hybrid"], key)
              for key in ("device_lanes", "host_lanes"))
    if not len(heights) * n_vals <= ran <= offered:
        problems.append(f"the hybrid tier ran {ran} lanes over {len(heights)} heights of {n_vals} "
                        f"({offered} offered): a height answered without a verification, or more "
                        f"verified than sent")
    sent = sidecarlib.grown(before["node"]["grpc"], after["node"]["grpc"], "lanes_sent")
    received = sidecarlib.grown(before["server"], after["server"], "lanes_in")
    if sent != received or sent != offered:
        problems.append(f"the nodes sent {sent} lanes over the window for {offered} offered, the "
                        f"server counted {received}")
    if after["server"].get("connections_accepted") != n_nodes:
        problems.append(f"the server accepted {after['server'].get('connections_accepted')} "
                        f"connections for {n_nodes} nodes")
    problems += _check_height(nodes, n_nodes, pool_n, flipped, want, expected)
    problems += nodes.one(0, "check_answers", i)["problems"]

    e2e = {}
    if len(lat) >= 2:
        e2e["commit_verify_p50_ms"] = statistics.median(lat) * 1000
        e2e["commit_verify_p95_ms"] = statistics.quantiles(lat, n=20, method="inclusive")[-1] * 1000
    eng = {k: sidecarlib.grown(before["engine"], after["engine"], k)
           for k in ("requests", "dispatches", "coalesced_dispatches", "batched_requests", "dedup_sigs")}
    harness.say(f"window: {len(heights)} heights, {attempted} operations in {t_close - t_open:.2f} s, "
                f"{failed} failed; lanes offered {offered}, the hybrid tier ran {ran} (device "
                f"{sidecarlib.grown(before['hybrid'], after['hybrid'], 'device_lanes')}); the sidecar's "
                f"engine {eng}; a height's first answer {_med_ms(h['first_s'] for h in heights)} ms after "
                f"its release, its last {_med_ms(h['last_s'] for h in heights)} ms (medians; quartiles of the "
                f"last {_quartiles_ms(h['last_s'] for h in heights)}); starts late by "
                f"{statistics.median(late) * 1e6:.0f} us (median), {max(late) * 1e6:.0f} us at most")
    obs = harness.Observations(
        attempted=attempted, failed=failed, end_to_end=e2e, setup_s=setup_s,
        window=(t_open, t_close), counters_before=before, counters_after=after,
        correct_problems=problems,
        samples={"op_s": lat, "lanes_offered": offered, "heights": heights},
    )
    harness.say(f"nodes: chains {[c['supervisor'].get('chain') for c in after['nodes']]}, wire bytes "
                f"a signature {sidecarlib.wire_bytes_per_sig(obs)}")
    if run.traced:
        obs.samples["nodes_spans"] = nodes.all("spans")
        obs.samples["wire_ops"] = multinodelib.join(obs)
        obs.correct_problems += _trace_problems(obs)
        if obs.samples["wire_ops"]:
            harness.say(sidecarlib.breakdown(obs.samples["wire_ops"]))
            for line in multinodelib.height_report(obs):
                harness.say(line)
    return obs
