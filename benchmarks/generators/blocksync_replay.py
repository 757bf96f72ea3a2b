"""Traffic kind `blocksync_replay`: `blocksync_join` over a chain whose
validator set is wide enough that one prefetch window fills the device
tier's largest bucket (1,024 validators: 31 commits x 1,024 = 31,744 lanes).

The joiner, the serving peers, the warm-up and the window are
`blocksync_join`'s own, and the recorder that keeps the reactor's counters
height by height is `blocksync_join_loaded`'s (both files are loaded by
path and their pieces called). What this kind adds:

- the check window, in set-up (`_check_window`): the triples of the chain's
  first prefetch window, gathered as `_prefetch_verify_window` gathers them,
  go through the chain `auto` returned with three seeded lanes flipped (one
  in each third of the window): refused, and the bitmap equals the scalar
  ZIP-215 reference's lane for lane (every lane computed by the fixture
  workers); then unflipped, accepted, again and again until the planner
  rests, so that every device program a prefetch reaches is loaded before
  the first height;
- the plain replay (`reference/commit_replay.py`) over the check window's
  heights and over `tamper_height` of the tampered chain says which heights
  a joiner may apply and where a tampered sync must stop; the joiner's
  heights and the tampered sync are held to it;
- over the window, from `verified_cache_counters()` after the height that
  opened it and after the last height inside it: the lanes dispatched
  through the batch seam stay within 1.25 x validators x heights applied
  (each triple once, by the prefetch: a cache that gave up a triple before
  the serial path read it would verify it again and pass this), and the
  device's lanes grew by at least a third of validators x heights applied;
- before it builds, it removes its own cell's chains of other seeds (a
  chain of 1,000 blocks x 1,024 signatures is ~320 MB).

Parameters (the traffic file): those of `blocksync_join`.
"""

from __future__ import annotations

import glob
import multiprocessing
import os
import random
import shutil

import chain
import fixtures
import harness
import multinodelib
from reference import commit_replay, ed25519_zip215

loaded = harness.load_by_path(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "blocksync_join_loaded.py"),
    "generator_blocksync_join_loaded",
)
base = loaded.base
FLIPPED_LANES = 3  # one in each third of the check window


class CacheRecorder(loaded.CountingRecorder):
    """`CountingRecorder` that also keeps the verified-triple cache's
    counters as they stood after each applied height."""

    def __init__(self, executor, run):
        super().__init__(executor, run)
        self.cache: list[dict] = []

    def apply_block(self, state, block_id, block):
        from cometbft_tpu.crypto import ed25519

        out = super().apply_block(state, block_id, block)
        self.cache.append(ed25519.verified_cache_counters())
        return out


def _joiner(run, gen, addrs):
    from cometbft_tpu.blocksync.reactor import BlocksyncReactor

    state, store, executor = chain.fresh_node(gen)
    rec = CacheRecorder(executor, run)
    if run.traced:
        store = base._SpannedStore(store, run)
    reactor = BlocksyncReactor(state=state, block_exec=rec, block_store=store, block_sync=True)
    rec.reactor = reactor
    _, sw = chain.new_switch(gen.chain_id, "joiner")
    sw.add_reactor("BLOCKSYNC", reactor)
    sw.start("")
    for addr in addrs:
        if sw.dial_peer(addr) is None:
            raise harness.BenchFailure(f"could not dial serving peer {addr}")
    return rec, store, reactor, sw


def _drop_other_seeds(run) -> None:
    mine = run.cache_path()
    for path in glob.glob(mine.rsplit("-", 1)[0] + "-*"):
        if path != mine:
            shutil.rmtree(path, ignore_errors=True)


def _window_triples(gen, serving):
    """The first prefetch window of the chain, as the reactor gathers it:
    the commits for heights 1 to W - 1, a lane a signature, in order."""
    from cometbft_tpu.blocksync.reactor import BlocksyncReactor
    from cometbft_tpu.state import make_genesis_state

    vals = make_genesis_state(gen).validators.validators
    blocks = min(BlocksyncReactor.PREFETCH_WINDOW, BlocksyncReactor.PREFETCH_MAX_SIGS // len(vals))
    pubs, msgs, sigs = [], [], []
    for h in range(1, blocks):
        commit = serving.load_block(h + 1).last_commit
        sign_bytes = commit.vote_sign_bytes_all(gen.chain_id)
        for idx, cs in enumerate(commit.signatures):
            pubs.append(vals[idx].pub_key.bytes())
            msgs.append(bytes(sign_bytes[idx]))
            sigs.append(cs.signature)
    return blocks - 1, (pubs, msgs, sigs)


def _reference_lanes(pool, triples):
    """Starts the scalar reference over every lane in the fixture workers;
    the returned function waits for the bitmap."""
    pubs, msgs, sigs = triples
    step = max(1, -(-len(pubs) // (8 * fixtures.worker_count())))
    jobs = [(pubs[lo:lo + step], msgs[lo:lo + step], sigs[lo:lo + step])
            for lo in range(0, len(pubs), step)]
    pending = pool.map_async(multinodelib.reference_slice, jobs)
    return lambda: [bit for part in pending.get() for bit in part]


def _flip(sig: bytes) -> bytes:
    return sig[:7] + bytes([sig[7] ^ 0x10]) + sig[8:]


def _tampered_pair(serving, height: int, index: int) -> list[dict]:
    """Blocks `height` and `height` + 1 as the tampered peer serves them
    (`chain._TamperedStore`), as plain values."""
    first, second = (loaded._plain_values(serving.load_block(h)) for h in (height, height + 1))
    flag, address, at, sig = second["last_commit"]["signatures"][index]
    second["last_commit"]["signatures"][index] = (flag, address, at, _flip(sig))
    return [first, second]


def _check_window(run, pool, gen, serving, tamper_height: int, tamper_index: int):
    """Set-up's check (module text). Returns the problems it found and the
    two plain replays, still running in the workers."""
    n_heights, (pubs, msgs, sigs) = _window_triples(gen, serving)
    n = len(pubs)
    reference = _reference_lanes(pool, (pubs, msgs, sigs))
    validators = [(v.pub_key.bytes(), v.power) for v in gen.validators]
    tampered = pool.apply_async(
        commit_replay.replay,
        (gen.chain_id, validators, _tampered_pair(serving, tamper_height, tamper_index)),
    )
    rng = random.Random(f"{run.seed}/check-window")
    third = n // FLIPPED_LANES
    flipped = [rng.randrange(k * third, (k + 1) * third) for k in range(FLIPPED_LANES)]
    bad_sigs = list(sigs)
    for lane in flipped:
        bad_sigs[lane] = _flip(sigs[lane])
    problems = []
    shares, walls = [], []

    def walked():  # the call just made: its share, and its walls by the hybrid tier's own clock
        hybrid = run.counters()["hybrid"]
        timing = hybrid.get("last_timing") or {}
        shares.append(hybrid.get("last_share"))
        walls.append(tuple(timing.get(k) for k in ("total_ms", "dev_wall_ms", "host_msm_ms")))

    ok_bad, bits_bad = run.backend.batch_verify(pubs, msgs, bad_sigs)
    walked()
    ok, bits = run.backend.batch_verify(pubs, msgs, sigs)
    walked()
    # The planner's walk to rest, as `blocksync_join_loaded._warm_prefetch`
    # makes it: until two calls in a row repeat the share before them and
    # load nothing (a program's first use teaches the planner nothing).
    at_rest = 0
    while at_rest < 2 and len(shares) < loaded.WARM_MAX_CALLS:
        programs = run.compile_log.count
        if not run.backend.batch_verify(pubs, msgs, sigs)[0]:
            problems.append("the check window did not verify again during the planner's walk")
        walked()
        same = run.compile_log.count == programs and shares[-2] == shares[-1]
        at_rest = at_rest + 1 if same else 0
    harness.say(f"check window: {n} lanes of {n_heights} heights, flipped {flipped}; shares {shares}, "
                f"compile log {run.compile_log.summary()} after {run.setup_done():.1f} s")
    harness.say(f"check window: each call's (total, device wall, host MSM) ms {walls}")

    want = reference()
    want_bad = list(want)
    for lane in flipped:
        want_bad[lane] = ed25519_zip215.verify_zip215(pubs[lane], msgs[lane], bad_sigs[lane])
    if ok_bad or any(want_bad[lane] for lane in flipped):
        problems.append(f"the check window with lanes {flipped} flipped was not refused")
    for name, got, ref in (("flipped", bits_bad, want_bad), ("unflipped", bits, want)):
        wrong = [lane for lane in range(n) if lane >= len(got) or got[lane] != ref[lane]]
        if wrong or len(got) != n:
            problems.append(f"the {name} check window's bitmap differs from the scalar ZIP-215 "
                            f"reference at lanes {wrong[:8]} ({len(wrong)} of {n})")
    if not ok or not all(want):
        problems.append("the unflipped check window was not accepted")
    # The plain replay of the same heights, by the reference's own answers
    # for the same triples (computed above, lane for lane).
    known = dict(zip(zip(pubs, msgs, sigs), want))

    def verify(pub, msg, sig):  # a triple the window lacks (other sign bytes) is computed here
        held = known.get((pub, msg, sig))
        return ed25519_zip215.verify_zip215(pub, msg, sig) if held is None else held

    plain = [loaded._plain_values(serving.load_block(h)) for h in range(1, n_heights + 2)]
    clean = commit_replay.replay(gen.chain_id, validators, plain, verify=verify)
    harness.say(f"check window: reference bitmap false at {[i for i, b in enumerate(want_bad) if not b]}, "
                f"the plain replay applies heights {clean.applied[:1]}-{clean.applied[-1:]}, "
                f"stops at {clean.stopped_at}; problems {len(problems)} after {run.setup_done():.1f} s")
    return problems, clean, tampered


def _check_counters(run, rec, obs, n_vals: int) -> list[str]:
    """The window's lanes, at the seam and on the device (module text)."""
    at = rec.times.index(obs.window[0])  # the height that opened the window
    closed = sum(t <= obs.window[1] for t in rec.times)
    obs.samples["reactor_counters"] = (
        rec.counters[at], rec.counters[closed - 1], rec.times[closed - 1] - rec.times[at]
    )
    first, last = rec.cache[at], rec.cache[closed - 1]
    applied = closed - 1 - at
    dispatched = last["dispatched"] - first["dispatched"]
    device = obs.counters_after["hybrid"].get("device_lanes", 0) - obs.counters_before["hybrid"].get("device_lanes", 0)
    problems = []
    if dispatched > 1.25 * n_vals * applied:
        problems.append(f"{dispatched} lanes dispatched through the batch seam for {applied} heights of "
                        f"{n_vals} signatures: a triple was verified again")
    if device * 3 < n_vals * applied:
        problems.append(f"{device} lanes on the device for {applied} heights of {n_vals} signatures")
    grown = {k: last[k] - first[k] for k in last if k != "size"}
    harness.say(f"window's lanes: {applied} heights x {n_vals}; through the seam {dispatched}, on the device "
                f"{device}; the cache's counters grew by {grown}; problems {len(problems)}")
    return problems


def run(run: harness.Run) -> harness.Observations:
    cfg, tr = run.config, run.traffic
    n_vals = int(cfg["validators"])
    tag = run.cell["config"]
    warm_h, quiet_h = int(tr["warmup_heights"]), int(tr["quiet_heights"])
    warm_max, measured = int(tr["warmup_max_heights"]), int(tr["measured_heights"])
    tamper_height = int(tr["tamper_height"])
    heights = warm_max + measured + 2  # the tip cannot be verified: no next block
    chain_dir = run.cache_path()
    ctx = multiprocessing.get_context("spawn")
    builder = None
    if not chain.have_chain(chain_dir, run.seed, n_vals, heights):
        _drop_other_seeds(run)
        builder = ctx.Process(
            target=chain.build_chain, args=(run.seed, tag, n_vals, heights, chain_dir)
        )
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
        if builder.exitcode != 0 or not chain.have_chain(chain_dir, run.seed, n_vals, heights):
            raise harness.BenchFailure(f"the chain builder exited {builder.exitcode}")
        harness.say(f"chain: built {heights} heights x {n_vals} validators "
                    f"after {run.setup_done():.1f} s")
    else:
        harness.say(f"chain: {heights} heights found in {chain_dir}")

    gen, _ = chain.genesis_for(run.seed, tag, n_vals)
    # As `blocksync_join._check_tampered` draws it: the same flipped signature.
    tamper_index = random.Random(f"{run.seed}/tamper").randrange(n_vals * 2 // 3)
    pool = fixtures.start_pool()
    try:
        problems, clean, tampered = _check_window(
            run, pool, gen, chain.open_store(chain_dir), tamper_height, tamper_index
        )
        tampered = tampered.get()
    finally:
        pool.terminate()
        pool.join()
    children = [base._spawn_peer(ctx, chain_dir, run.seed, tag, n_vals) for _ in range(int(tr["peers"]))]
    try:
        hello = [conn.recv() for _, conn in children]
        rec, store, reactor, sw = _joiner(run, gen, [h["addr"] for h in hello])
        try:
            obs = base._measure(run, rec, reactor, sw, warm_h, quiet_h, warm_max, measured)
        finally:
            reactor.stop()
            sw.stop()
        loaded._settle(rec)
        obs.correct_problems += problems
        obs.correct_problems += _check_counters(run, rec, obs, n_vals)
        obs.correct_problems += base._check_hashes(rec, store, chain_dir)
        if clean.stopped_at is not None or rec.heights[:len(clean.applied)] != clean.applied:
            obs.correct_problems.append(
                f"the plain replay applies heights {clean.applied[:1]}-{clean.applied[-1:]} and stops at "
                f"{clean.stopped_at} ({clean.why}); the joiner applied {rec.heights[:len(clean.applied) + 1][-3:]}"
            )
    finally:
        left = harness.stop_children(children)
    obs.correct_problems += left
    if (tampered.applied, tampered.stopped_at) != ([], tamper_height):
        obs.correct_problems.append(
            f"the plain replay of the tampered chain applies {tampered.applied} and stops at "
            f"{tampered.stopped_at}, not at {tamper_height}"
        )
    harness.say(f"plain replay of the tampered chain: stops at {tampered.stopped_at}: {tampered.why}")
    # The joiner's side of the same sentence: stopped below tamper_height, peer dropped.
    obs.correct_problems += base._check_tampered(run, ctx, gen, chain_dir, tag, n_vals, tamper_height)
    return obs
