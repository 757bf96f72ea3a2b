"""Traffic kind `light_skipping`: cold light clients skip a trace of headers
of a chain whose validator set turns over, one sync at a time (a closed loop
with one client).

An operation is a cold sync: a fresh `light.Client` with a fresh
`LightStore(MemDB())` trusts root r (its hash, from the chain) and asks for
height r + headers - 1 in skipping mode, with one witness that serves the
same chain. Roots go 1..roots in order, cycled; each trace touches heights
no other root's does, so a revisit finds its triples gone from the
verified-triple cache when the roots' triples outnumber it. The provider
and the witness are in-process and decode every light block afresh from its
stored encoding (`light_chain.make_provider`). The chain is built by
`light_chain.py` in processes that never import JAX, cached under
`benchmarks/.cache/`, while the backend starts.

Set-up:

- the check dispatch: root 1's first prewarm column (the light prefixes of
  its first pivot's commit and its target's, in the client's order) through
  the chain `auto` returned with three seeded lanes flipped, one in each
  third: refused, and the bitmap equals the scalar ZIP-215 reference's lane
  for lane; then unflipped, in reverse lane order (the same triples, and not
  the same key column: a column seen twice would have its window tables
  built), accepted and equal to the reference;
- the plain reference (`reference/light_skipping.py`) syncs every root in
  the fixture workers, each signature it reaches answered by the check
  dispatch's known answers or by the scalar reference, beside the warm-up;
- the tampered sync: root 1 against a copy of the chain whose target
  commit has one seeded signature flipped, of a validator no hop that is
  refused there knows and inside the light check's prefix: the client must
  refuse at the hop where the plain reference does, and its store must hold
  what the reference's does (the root alone);
- the warm-up: roots 2..roots (the check dispatch's columns are forgotten
  meanwhile), the tampered sync, then whole passes from root 1, until the
  reference is done and two roots in a row load no program and repeat the
  planner's shares of their previous sync; at `warmup_max_passes` the
  window opens regardless, and the compile count says so. The window opens
  at root 1 and closes at the first sync's end after `--seconds`.

`correct`, beside the harness's health check: every sync's stored heights
equal the reference's trace for its root and its target's hash the chain's;
the seam dispatched at least 90% of the reference's distinct triples for
the window's syncs (revisits miss); no prewarm raised; `resident_builds`
did not grow over the window.

End to end: `catchup_heights_per_s` = the heights the window's syncs
covered (target - root each) over the first one's start to the last one's
end, on the host's clock.

Parameters (the traffic file): roots, headers, warmup_max_passes,
trace_seconds. The configuration gives validators, rotate, light_blocks,
block_seconds, trusting_period_s, trust_level, max_clock_drift_s.
"""

from __future__ import annotations

import glob
import multiprocessing
import os
import random
import shutil
import time

import fixtures
import harness
import light_chain
import multinodelib
from reference import ed25519_zip215

FLIPPED_LANES = 3  # one in each third of the check dispatch
DISPATCHED_SHARE = 0.9  # of the reference's distinct triples, over the window's syncs


class Chain:
    """The stored chain and how a client of the cell meets it."""

    def __init__(self, run, marker: dict, encoded: dict[int, bytes]):
        from cometbft_tpu.types.cmttime import Time
        from cometbft_tpu.types.validation import Fraction

        cfg, tr = run.config, run.traffic
        self.chain_id = marker["chain_id"]
        self.encoded = encoded
        self.hashes = {int(h): bytes.fromhex(x) for h, x in marker["header_hashes"].items()}
        self.headers = int(tr["headers"])
        self.roots = list(range(1, int(tr["roots"]) + 1))
        self.period_ns = int(cfg["trusting_period_s"]) * 10**9
        self.drift_ns = int(cfg["max_clock_drift_s"]) * 10**9
        self.trust = tuple(cfg["trust_level"])
        self.trust_level = Fraction(*self.trust)
        tip = light_chain.header_time(run.seed, int(cfg["light_blocks"]), int(cfg["block_seconds"]))
        self.now = Time(tip.seconds + 60, 0)  # a minute after the tip was made
        self.primary = light_chain.make_provider(self.chain_id, encoded)
        self.witness = light_chain.make_provider(self.chain_id, encoded)

    def target(self, root: int) -> int:
        return root + self.headers - 1

    def sync(self, root: int, primary=None):
        """One cold sync: (stored heights, target block, the client's counters)."""
        from cometbft_tpu.libs.db import MemDB
        from cometbft_tpu.light import Client, LightStore, TrustOptions

        store = LightStore(MemDB())
        client = Client(
            self.chain_id, TrustOptions(self.period_ns, root, self.hashes[root]),
            primary or self.primary, [self.witness], store, trust_level=self.trust_level,
        )
        try:
            lb = client.verify_light_block_at_height(self.target(root), self.now)
        finally:
            self.last_counters = client.counters()
            self.last_stored = [h for h in range(root, self.target(root) + 1) if store.light_block(h) is not None]
        return self.last_stored, lb, self.last_counters


def _drop_other_seeds(run) -> None:
    mine = run.cache_path()
    for path in glob.glob(mine.rsplit("-", 1)[0] + "-*"):
        if path != mine:
            shutil.rmtree(path, ignore_errors=True)


def _light_prefix(chain, lb) -> list[tuple]:
    """The triples a light check of `lb`'s commit reaches, in commit order:
    signatures FOR the block until their power passes 2/3 of the set's."""
    commit, vals = lb.signed_header.commit, lb.validator_set.validators
    sign_bytes = commit.vote_sign_bytes_all(chain.chain_id)
    needed = sum(v.voting_power for v in vals) * 2 // 3
    out, tallied = [], 0
    for idx, cs in enumerate(commit.signatures):
        if not cs.for_block_flag():
            continue
        out.append((vals[idx].pub_key.bytes(), bytes(sign_bytes[idx]), cs.signature))
        tallied += vals[idx].voting_power
        if tallied > needed:
            break
    return out


def _flip(sig: bytes) -> bytes:
    return sig[:7] + bytes([sig[7] ^ 0x10]) + sig[8:]


def _reference_lanes(pool, triples):
    """Starts the scalar reference over every lane in the fixture workers;
    the returned function waits for the bitmap."""
    pubs, msgs, sigs = (list(c) for c in zip(*triples))
    step = max(1, -(-len(pubs) // (4 * fixtures.worker_count())))
    jobs = [(pubs[lo:lo + step], msgs[lo:lo + step], sigs[lo:lo + step]) for lo in range(0, len(pubs), step)]
    pending = pool.map_async(multinodelib.reference_slice, jobs)
    return lambda: [bit for part in pending.get() for bit in part]


def _check_dispatch(run, pool, chain):
    """Set-up's check (module text). Returns (problems, the known answers)."""
    root = chain.roots[0]
    target = chain.target(root)
    pivot = (root + target) // 2
    triples = [t for h in (pivot, target) for t in _light_prefix(chain, chain.primary.light_block(h))]
    n = len(triples)
    reference = _reference_lanes(pool, triples)
    rng = random.Random(f"{run.seed}/check-dispatch")
    third = n // FLIPPED_LANES
    flipped = [rng.randrange(k * third, (k + 1) * third) for k in range(FLIPPED_LANES)]
    bad = list(triples)
    for lane in flipped:
        p, m, s = triples[lane]
        bad[lane] = (p, m, _flip(s))
    calls = []
    for name, lanes in (("flipped", bad), ("unflipped", triples[::-1])):
        programs = run.compile_log.count
        t0 = time.perf_counter()
        ok, bits = run.backend.batch_verify(*(list(c) for c in zip(*lanes)))
        hybrid = run.counters()["hybrid"]
        calls.append((ok, bits if name == "flipped" else bits[::-1]))
        harness.say(f"check dispatch ({name}): {n} lanes of heights {pivot} and {target} in "
                    f"{(time.perf_counter() - t0) * 1000:.1f} ms, share {hybrid.get('last_share')}, "
                    f"programs loaded {run.compile_log.count - programs}")
    want = reference()
    want_bad = list(want)
    for lane in flipped:
        want_bad[lane] = ed25519_zip215.verify_zip215(*bad[lane])
    problems = []
    (ok_bad, bits_bad), (ok, bits) = calls
    if ok_bad or any(want_bad[lane] for lane in flipped):
        problems.append(f"the check dispatch with lanes {flipped} flipped was not refused")
    for name, got, want_ in (("flipped", bits_bad, want_bad), ("unflipped", bits, want)):
        wrong = [lane for lane in range(n) if lane >= len(got) or got[lane] != want_[lane]]
        if wrong or len(got) != n:
            problems.append(f"the {name} check dispatch's bitmap differs from the scalar ZIP-215 "
                            f"reference at lanes {wrong[:8]} ({len(wrong)} of {n})")
    if not ok or not all(want):
        problems.append("the unflipped check dispatch was not accepted")
    harness.say(f"check dispatch: flipped {flipped}, reference false at "
                f"{[i for i, b in enumerate(want_bad) if not b]}; problems {len(problems)} "
                f"after {run.setup_done():.1f} s")
    known = dict(zip(triples, want))
    known.update((bad[lane], want_bad[lane]) for lane in flipped)
    return problems, known


def _tamper(run, chain):
    """(index, encoded block): root 1's target with one signature flipped
    (`light_chain.tamper_index`)."""
    root = chain.roots[0]
    target = chain.target(root)
    index = light_chain.tamper_index(chain.chain_id, chain.primary, root, target, (chain.now.seconds, chain.now.nanos),
                                     chain.period_ns, chain.drift_ns, chain.trust, run.seed)
    if index is None:
        raise harness.BenchFailure(f"no signature of the commit for height {target} lies on the verified path alone")
    return index, light_chain.flip_commit_signature(chain.encoded[target], index)


def _tampered_sync(chain, tamper):
    """The client's side: (the hop it refused at, its stored heights, why)."""
    from cometbft_tpu.libs import trace

    root = chain.roots[0]
    primary = light_chain.make_provider(chain.chain_id, {**chain.encoded, chain.target(root): tamper[1]})
    with trace.capture():
        t0 = time.perf_counter()
        try:
            chain.sync(root, primary)
            why = "the sync verified"
        except Exception as e:  # the refusal this check wants
            why = f"{type(e).__name__}: {e}"
        hops = [s for s in trace.spans() if s["name"] == "light.verify" and s["t0"] >= t0
                and s["attrs"].get("result") == "invalid"]
    hop = (hops[-1]["attrs"]["from"], hops[-1]["attrs"]["to"]) if hops else None
    return hop, chain.last_stored, why


def _shares(before: dict, after: dict) -> list:
    """The planner's device shares of the calls made between two readings."""
    d = after["engine"].get("dispatches", 0) - before["engine"].get("dispatches", 0)
    routes = after["hybrid"].get("routes", [])
    return [r["device"] for r in routes[-d:]] if 0 < d <= len(routes) else [None] * max(d, 0)


def run(run: harness.Run) -> harness.Observations:
    from cometbft_tpu.light.client import Client

    if not hasattr(Client, "counters"):
        raise harness.BenchFailure("the program's light client keeps no counters(): it cannot run this cell")
    cfg, tr = run.config, run.traffic
    n_vals, rotate, heights = int(cfg["validators"]), int(cfg["rotate"]), int(cfg["light_blocks"])
    if int(tr["roots"]) + int(tr["headers"]) - 1 != heights:
        raise harness.BenchFailure(f"{tr['roots']} roots of {tr['headers']} headers need {heights} light blocks")
    chain_dir = run.cache_path()
    shape = (run.seed, n_vals, rotate, heights)
    ctx = multiprocessing.get_context("spawn")
    builder = None
    if light_chain.load_marker(chain_dir, *shape) is None:
        _drop_other_seeds(run)
        builder = ctx.Process(target=light_chain.build_chain, args=(
            run.seed, run.cell["config"], n_vals, rotate, heights, int(cfg["block_seconds"]), chain_dir))
        builder.start()
    try:
        run.start_backend()  # while the chain is built
    except BaseException:
        if builder is not None:
            builder.kill()
            builder.join()
        raise
    if builder is not None:
        builder.join()
        if builder.exitcode != 0:
            raise harness.BenchFailure(f"the light chain builder exited {builder.exitcode}")
    marker = light_chain.load_marker(chain_dir, *shape)
    if marker is None:
        raise harness.BenchFailure(f"no whole light chain in {chain_dir}")
    harness.say(f"chain: {heights} light blocks x {n_vals} validators ({rotate} replaced a height), "
                f"{marker['openssl_checked_signatures']} signatures checked by OpenSSL, built in "
                f"{marker['build_s']} s; after {run.setup_done():.1f} s")
    chain = Chain(run, marker, light_chain.load_blocks(chain_dir, heights))
    pool = fixtures.start_pool()
    try:
        return _measure(run, pool, chain)
    finally:
        pool.terminate()
        pool.join()


def _measure(run, pool, chain) -> harness.Observations:
    from cometbft_tpu.crypto import ed25519

    tr = run.traffic
    problems, known = _check_dispatch(run, pool, chain)
    tamper = _tamper(run, chain)
    now = (chain.now.seconds, chain.now.nanos)
    jobs = {r: pool.apply_async(light_chain.reference_sync, ((
        run.cache_path(), chain.chain_id, r, chain.target(r), now, chain.period_ns, chain.drift_ns, chain.trust,
        known, (chain.target(r), tamper[1]) if r == chain.roots[0] else None),))
        for r in chain.roots}
    syncs = []  # (root, stored, target hash, counters, t0, t1) of every clean sync

    def op(root):
        t0 = time.perf_counter()
        stored, lb, counters = chain.sync(root)
        t1 = time.perf_counter()
        syncs.append((root, stored, lb.hash(), counters, t0, t1))
        return t1 - t0

    # Warm-up (module text).
    last_shares: dict = {}
    log = run.compile_log

    def warm(root):
        programs, before = log.count, run.counters()
        dt = op(root)
        shares = _shares(before, run.counters())
        quiet = log.count == programs and last_shares.get(root) == shares
        last_shares[root] = shares
        harness.say(f"warm sync of root {root}: {dt * 1000:.0f} ms, shares {shares}, "
                    f"programs loaded {log.count - programs}")
        return quiet

    for root in chain.roots[1:]:
        warm(root)
    hop, tampered_stored, tampered_why = _tampered_sync(chain, tamper)
    harness.say(f"tampered sync: signature {tamper[0]} of height {chain.target(chain.roots[0])} flipped; "
                f"refused at {hop}, stored {tampered_stored}: {tampered_why[:160]}")
    quiet = passes = 0
    while passes < int(tr["warmup_max_passes"]):
        passes += 1
        for root in chain.roots:
            done = all(j.ready() for j in jobs.values())
            quiet = quiet + 1 if warm(root) and done else 0
        if quiet >= 2:
            break
    harness.say(f"warm-up: {passes} passes, last {quiet} syncs quiet, compile log {log.summary()} "
                f"after {run.setup_done():.1f} s")
    references = {r: j.get() for r, j in jobs.items()}  # ready by now, unless the passes ran out
    warm_syncs = len(syncs)

    before = run.counters()
    cache0 = ed25519.verified_cache_counters()
    setup_s = run.setup_done()
    if run.traced:
        run.trace_start()
    t_open = time.perf_counter()
    trace_until = t_open + float(tr["trace_seconds"])
    i = failed = 0
    stop_s = None  # how long stopping the profiler held the window
    while True:
        root = chain.roots[i % len(chain.roots)]
        i += 1
        try:
            op(root)
        except Exception as e:  # a refusal or an error is a failed operation
            failed += 1
            harness.say(f"sync of root {root} failed: {type(e).__name__}: {e}")
        now_ = time.perf_counter()
        if now_ >= trace_until and stop_s is None:
            run.trace_stop()  # the window goes on untraced
            stop_s = time.perf_counter() - now_
        if now_ >= t_open + run.seconds:
            break
    run.trace_stop()
    after = run.counters()
    cache1 = ed25519.verified_cache_counters()
    window = syncs[warm_syncs:]
    t_close = window[-1][5] if window else time.perf_counter()
    covered = sum(chain.target(r) - r for r, *_ in window)
    rate = covered / (t_close - t_open) if window else 0.0
    dispatched = cache1["dispatched"] - cache0["dispatched"]
    harness.say(f"window: {len(window)} syncs ({failed} failed) covering {covered} heights in "
                f"{t_close - t_open:.3f} s = {rate:.2f} heights/s; lanes through the seam {dispatched}")
    if run.traced:
        harness.say(f"traced syncs: stopping the profiler took {stop_s} s; (spans, ms) a sync by name "
                    f"{_per_sync((t_open, t_close))}")

    problems += run.health_problems(before, after)
    problems += _check_syncs(chain, syncs, references)
    want = sum(references[r][1] for r, *_ in window)
    if dispatched < DISPATCHED_SHARE * want:
        problems.append(f"{dispatched} lanes dispatched through the batch seam for the window's syncs, whose "
                        f"reference verified {want} distinct triples: a revisit hit the cache")
    errors = sum(c["speculation_errors"] for _, _, _, c, _, _ in syncs)
    if errors:
        problems.append(f"{errors} speculative prewarms raised")
    built = after["hybrid"].get("resident_builds", 0) - before["hybrid"].get("resident_builds", 0)
    if built:
        problems.append(f"{built} window tables were built in the window: a key column recurred")
    problems += _check_tampered(chain, references[chain.roots[0]][2], hop, tampered_stored)
    harness.say(f"references: {[(r, references[r][1]) for r in chain.roots]} distinct triples a root; "
                f"the window's reference {want}; speculation errors {errors}; tables built {built}")
    return harness.Observations(
        attempted=len(window) + failed, failed=failed,
        end_to_end={"catchup_heights_per_s": rate}, setup_s=setup_s,
        window=(t_open, t_close), counters_before=before, counters_after=after,
        correct_problems=problems,
        samples={"syncs": [(r, t0, t1) for r, _, _, _, t0, t1 in window], "dispatched": dispatched},
    )


def _per_sync(window) -> dict:
    """Spans of each name under the window's traced `light.sync` spans, and
    their summed milliseconds, over those syncs (nested names each count)."""
    from cometbft_tpu.libs import trace

    spans = [s for s in trace.spans() if s["t0"] >= window[0] and s["t1"] <= window[1]]
    syncs = {s["id"] for s in spans if s["name"] == "light.sync"}
    sums: dict = {}
    for s in spans:
        if s["root"] in syncs:
            n, ms = sums.get(s["name"], (0, 0.0))
            sums[s["name"]] = (n + 1, ms + (s["t1"] - s["t0"]) * 1000.0)
    return {k: (round(n / len(syncs), 1), round(ms / len(syncs), 1)) for k, (n, ms) in sorted(sums.items())}


def _check_syncs(chain, syncs, references) -> list[str]:
    """Every clean sync of the run against the plain reference and the chain."""
    problems = []
    for root, stored, target_hash, *_ in syncs:
        sync = references[root][0]
        if sync.refused is not None:
            problems.append(f"the plain reference refuses root {root} at hop {sync.refused}: {sync.why}")
        elif stored != sync.stored:
            problems.append(f"root {root}: the client stored {stored}, the reference {sync.stored}")
        if target_hash != chain.hashes[chain.target(root)]:
            problems.append(f"root {root}: the target's hash is not the chain's")
        if len(problems) > 5:
            break
    harness.say(f"syncs: {len(syncs)} compared with the plain reference "
                f"({[(r, s[0].stored) for r, s in sorted(references.items())][:1]}), {len(problems)} problems")
    return problems


def _check_tampered(chain, sync, hop, stored) -> list[str]:
    problems = []
    if sync is None or sync.refused is None:
        problems.append("the plain reference verified the tampered chain")
    elif hop != sync.refused:
        problems.append(f"the client refused the tampered chain at hop {hop}, the reference at {sync.refused}")
    if sync is not None and stored != sync.stored:
        problems.append(f"after the tampered sync the client stored {stored}, the reference {sync.stored}")
    harness.say(f"tampered chain: the reference refuses at {sync and sync.refused} "
                f"({sync and sync.why}); problems {len(problems)}")
    return problems
