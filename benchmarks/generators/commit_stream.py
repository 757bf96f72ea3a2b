"""Traffic kind `commit_stream`: one caller verifying fully signed commits
it has not seen, one at a time (a closed loop: a node verifies one commit
at a time).

Each operation is `vals.verify_commit(chain_id, block_id, height, commit)`
through the node's normal path. The fixtures are a pool of `pool_commits`
seeded commits of one validator set, cycled in order; the pool holds more
triples than the program's verified-triple cache, which evicts its oldest
quarter, so every revisit finds its triples gone without the benchmark
reaching into the program. The run proves that: lanes dispatched over the
window must be no fewer than lanes offered.

Parameters (the traffic file): pool_commits, warm_min_ops, warm_quiet_ops,
warm_max_ops, flipped_lanes, sample_lanes, trace_seconds.
"""

from __future__ import annotations

import gc
import random
import statistics
import time

import fixtures
import harness
from reference import ed25519_zip215 as ref


def run(run: harness.Run) -> harness.Observations:
    cfg, tr = run.config, run.traffic
    n_vals = int(cfg["validators"])
    pool_n = int(tr["pool_commits"])
    pool = fixtures.start_pool()
    try:
        # Signing starts before JAX does: the workers sign while the backend starts.
        pending = fixtures.make_commits_async(pool, run.seed, run.cell["config"], n_vals, pool_n)
        run.start_backend()
        chain_id, vals, commits = pending()
    finally:
        pool.terminate()  # done, or not wanted any more (no chip)
        pool.join()
    harness.say(f"fixtures: {pool_n} commits x {n_vals} validators signed and checked by OpenSSL "
                f"after {run.setup_done():.1f} s")
    # The pool is the benchmark's, not the node's: keep the collector from
    # walking its 160,000 signature objects inside an operation.
    gc.collect()
    gc.freeze()

    def op(i: int):
        bid, commit = commits[i % pool_n]
        commit = fixtures.fresh_commit(commit)  # as decoded: nothing memoized on it
        t0 = time.perf_counter()
        with run.span("bench:verify_commit"):
            vals.verify_commit(chain_id, bid, commit.height, commit)
        return time.perf_counter() - t0

    # Warm phase: the cell's own operations, until `warm_quiet_ops` in a row
    # caused no compile and took the planner to no share it had not yet
    # used. (That the share stops moving cannot be asked: on the chip it
    # alternates between two buckets for as long as the run lasts.)
    i = quiet = 0
    shares: set = set()
    while i < int(tr["warm_max_ops"]):
        compiles = run.compile_log.count
        dt = op(i)
        i += 1
        share = run.counters()["hybrid"].get("last_share")
        same = run.compile_log.count == compiles and share in shares
        quiet = quiet + 1 if same else 0
        shares.add(share)
        if not same:
            harness.say(f"warm op {i}: {dt * 1000:.1f} ms, share {share}, "
                        f"compile events {run.compile_log.count - compiles}")
        if i >= int(tr["warm_min_ops"]) and quiet >= int(tr["warm_quiet_ops"]):
            break
    harness.say(f"warm-up: {i} operations, last {quiet} quiet, shares {sorted(shares)}, "
                f"compile log {run.compile_log.summary()}")

    before = run.counters()
    setup_s = run.setup_done()
    lat, failed, timing = [], 0, []
    if run.traced:
        run.trace_start()
    t_open = time.perf_counter()
    trace_until = t_open + float(tr["trace_seconds"])
    t_end = t_open + run.seconds
    while time.perf_counter() < t_end:
        try:
            lat.append(op(i))
        except Exception as e:  # a refusal or an error is a failed operation
            failed += 1
            harness.say(f"operation {i} failed: {type(e).__name__}: {e}")
        i += 1
        if run.traced:
            # One caller in a closed loop: the hybrid's last call is this one.
            timing.append(dict(run.counters()["hybrid"].get("last_timing") or {}))
            if time.perf_counter() >= trace_until:
                run.trace_stop()  # the window goes on untraced
    t_close = time.perf_counter()
    run.trace_stop()
    after = run.counters()
    attempted = len(lat) + failed

    problems = run.health_problems(before, after)
    offered = attempted * n_vals
    dispatched = _lanes(after) - _lanes(before)
    if dispatched < offered:
        problems.append(f"{dispatched} lanes dispatched for {offered} offered: the pool hit the cache")
    problems += _check_answers(run, chain_id, vals, commits, n_vals, i)
    e2e = {}
    if len(lat) >= 2:
        e2e["commit_verify_p50_ms"] = statistics.median(lat) * 1000
        e2e["commit_verify_p95_ms"] = statistics.quantiles(lat, n=20, method="inclusive")[-1] * 1000
    harness.say(f"window: {attempted} operations in {t_close - t_open:.2f} s, {failed} failed, "
                f"lanes dispatched {dispatched} (device {after['hybrid'].get('device_lanes', 0) - before['hybrid'].get('device_lanes', 0)})")
    return harness.Observations(
        attempted=attempted, failed=failed, end_to_end=e2e, setup_s=setup_s,
        window=(t_open, t_close), counters_before=before, counters_after=after,
        correct_problems=problems,
        samples={"op_s": lat, "hybrid_timing": timing, "lanes_offered": offered},
    )


def _lanes(c: dict) -> int:
    return c["hybrid"].get("device_lanes", 0) + c["hybrid"].get("host_lanes", 0)


def _check_answers(run, chain_id, vals, commits, n_vals, next_i) -> list[str]:
    """Outside the window: one dispatch of a commit with seeded flipped lanes
    plus the ZIP-215 edge vectors must give a bitmap false at exactly the
    lanes the scalar reference rejects, a seeded sample of lanes must equal
    the reference, and `verify_commit` must refuse a flipped commit. Both
    use the pool's next commits in cycle order, whose triples the cache has
    evicted, so both are whole dispatches."""
    from cometbft_tpu.crypto import ed25519

    tr = run.traffic
    problems = []
    rng = random.Random(f"{run.seed}/flip")
    flipped = sorted(rng.sample(range(n_vals * 2 // 3), int(tr["flipped_lanes"])))
    edges = [c for c in ref.zip215_edge_cases() if len(c[1]) == 32 and len(c[3]) == 64]
    keep = n_vals - len(edges)
    if flipped[-1] >= keep:
        raise harness.BenchFailure("validator count too small for the edge vectors")
    _, commit = commits[next_i % len(commits)]
    bad = fixtures.flip_signatures(commit, flipped)
    sbs = bad.vote_sign_bytes_all(chain_id)
    triples = [
        (vals.validators[j].pub_key.bytes(), bytes(sbs[j]), bad.signatures[j].signature)
        for j in range(keep)
    ] + [(p, m, s) for _, p, m, s in edges]
    bv = ed25519.BatchVerifier()
    for p, m, s in triples:
        bv.add(ed25519.PubKey(p), m, s)
    lanes0 = _lanes(run.counters())
    ok, bits = bv.verify()
    sent = _lanes(run.counters()) - lanes0
    if ok or len(bits) != n_vals:
        problems.append(f"bitmap call returned ok={ok} with {len(bits)} lanes")
        return problems
    if sent < n_vals:
        problems.append(f"the answer check dispatched {sent} of {n_vals} lanes")
    edge_lanes = list(range(keep, n_vals))
    sample = fixtures.sample_lanes(run.seed, n_vals, int(tr["sample_lanes"]), flipped + edge_lanes)
    for j in sample:
        want = ref.verify_zip215(*triples[j])
        if bits[j] != want:
            problems.append(f"lane {j}: bitmap {bits[j]}, scalar ZIP-215 reference {want}")
        if j < keep and j not in flipped and not fixtures.openssl_verify(*triples[j]):
            problems.append(f"lane {j}: OpenSSL refuses a fixture signature")
    want_false = sorted(set(flipped) | {j for j in edge_lanes if not ref.verify_zip215(*triples[j])})
    got_false = [j for j, b in enumerate(bits) if not b]
    if got_false != want_false:
        problems.append(f"bitmap false at {got_false[:12]}, the reference rejects exactly {want_false}")
    bid2, commit2 = commits[(next_i + 1) % len(commits)]
    bad2 = fixtures.flip_signatures(commit2, flipped)
    try:
        vals.verify_commit(chain_id, bid2, bad2.height, bad2)
        problems.append("verify_commit accepted a commit with flipped signatures")
    except ValueError as e:
        if f"wrong signature (#{flipped[0]})" not in str(e):
            problems.append(f"verify_commit refused the flipped commit for another reason: {e}")
    harness.say(f"answers: bitmap false at exactly flipped {flipped} and "
                f"{len(want_false) - len(flipped)} invalid edge vectors; {len(sample)} sampled lanes "
                f"equal the scalar reference; flipped commit refused; problems {len(problems)}")
    return problems
