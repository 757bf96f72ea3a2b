"""Traffic kind `blocksync_join`: one fresh node joins a seeded chain of
empty blocks through the real blocksync reactor, fetching over loopback TCP
from serving peers that hold the chain.

The joiner (new stores, new `BlocksyncReactor`, `block_sync=True`) lives in
the process that holds the chip; each serving peer is a child process that
never imports JAX. The window opens at the first applied height after the
warm-up (`warmup_heights` applied and `quiet_heights` in a row applied with
no compile, which is two prefetch dispatches; at `warmup_max_heights` it
opens regardless and the compile count says so) and closes after
`measured_heights` or `--seconds`, whichever is first: a fixed amount of
work repeats better than a fixed time.

Parameters (the traffic file): peers, warmup_heights, quiet_heights,
warmup_max_heights, measured_heights, tamper_height, trace_seconds.
"""

from __future__ import annotations

import multiprocessing
import random
import time

import chain
import harness


class ApplyRecorder:
    """The joiner's executor, handed to the reactor in place of the bare
    one: the same calls, with the host clock read after every applied block
    (the window is read from these) and, in a traced run, a span round each
    call the reactor makes into it."""

    def __init__(self, executor, run: harness.Run):
        self._executor = executor
        self._run = run
        self.times: list[float] = []  # perf_counter after apply_block of each height
        self.heights: list[int] = []
        self.app_hashes: list[bytes] = []

    def __getattr__(self, name):
        return getattr(self._executor, name)

    def validate_block(self, state, block):
        with self._run.span("bench:validate_block"):
            return self._executor.validate_block(state, block)

    def apply_block(self, state, block_id, block):
        with self._run.span("bench:apply_block"):
            out = self._executor.apply_block(state, block_id, block)
        self.times.append(time.perf_counter())
        self.heights.append(block.header.height)
        self.app_hashes.append(out[0].app_hash)
        return out


class _SpannedStore:
    """The joiner's block store with a span round save_block (traced runs)."""

    def __init__(self, store, run):
        self._store = store
        self._run = run

    def __getattr__(self, name):
        return getattr(self._store, name)

    def save_block(self, block, parts, seen_commit):
        with self._run.span("bench:save_block"):
            return self._store.save_block(block, parts, seen_commit)


def _spawn_peer(ctx, chain_dir, seed, tag, n_vals, tamper=None):
    parent, child = ctx.Pipe()
    proc = ctx.Process(
        target=chain.serve_peer, args=(child, chain_dir, seed, tag, n_vals, tamper), daemon=True
    )
    proc.start()
    child.close()
    return proc, parent


def _joiner(run, gen, addrs):
    from cometbft_tpu.blocksync.reactor import BlocksyncReactor

    state, store, executor = chain.fresh_node(gen)
    rec = ApplyRecorder(executor, run)
    if run.traced:
        store = _SpannedStore(store, run)
    reactor = BlocksyncReactor(
        state=state, block_exec=rec, block_store=store, block_sync=True
    )
    _, sw = chain.new_switch(gen.chain_id, "joiner")
    sw.add_reactor("BLOCKSYNC", reactor)
    sw.start("")
    for addr in addrs:
        if sw.dial_peer(addr) is None:
            raise harness.BenchFailure(f"could not dial serving peer {addr}")
    return rec, store, reactor, sw


def _recv_bytes(sw) -> int:
    return sum(p.mconn.recv_monitor.bytes_total for p in sw.peers())


def run(run: harness.Run) -> harness.Observations:
    cfg, tr = run.config, run.traffic
    n_vals = int(cfg["validators"])
    tag = run.cell["config"]
    warm_h, quiet_h = int(tr["warmup_heights"]), int(tr["quiet_heights"])
    warm_max, measured = int(tr["warmup_max_heights"]), int(tr["measured_heights"])
    heights = warm_max + measured + 2  # the tip cannot be verified: no next block
    chain_dir = run.cache_path()
    ctx = multiprocessing.get_context("spawn")
    builder = None
    if not chain.have_chain(chain_dir, run.seed, n_vals, heights):
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

    children = [_spawn_peer(ctx, chain_dir, run.seed, tag, n_vals) for _ in range(int(tr["peers"]))]
    try:
        hello = [conn.recv() for _, conn in children]
        gen, _ = chain.genesis_for(run.seed, tag, n_vals)
        rec, store, reactor, sw = _joiner(run, gen, [h["addr"] for h in hello])
        try:
            obs = _measure(run, rec, reactor, sw, warm_h, quiet_h, warm_max, measured)
        finally:
            reactor.stop()
            sw.stop()
        obs.correct_problems += _check_hashes(rec, store, chain_dir)
    finally:
        left = harness.stop_children(children)
    obs.correct_problems += left
    obs.correct_problems += _check_tampered(run, ctx, gen, chain_dir, tag, n_vals, int(tr["tamper_height"]))
    return obs


def _measure(run, rec, reactor, sw, warm_h, quiet_h, warm_max, measured):
    log = run.compile_log
    open_at = None  # index into rec.times of the height that opens the window
    before = recv0 = None
    overlap0 = 0.0
    setup_s = 0.0
    compiles_seen, quiet_from = log.count, 0
    last_progress = (0, time.perf_counter())
    trace_until = float("inf")
    while True:
        time.sleep(0.01)
        n = len(rec.times)
        now = time.perf_counter()
        if n != last_progress[0]:
            last_progress = (n, now)
        elif now - last_progress[1] > 60:
            raise harness.BenchFailure(f"the sync made no progress for 60 s at height {n}")
        if open_at is None:
            if log.count != compiles_seen:
                compiles_seen, quiet_from = log.count, n
            if (n >= warm_h and n - quiet_from >= quiet_h) or n >= warm_max:
                before = run.counters()
                recv0 = _recv_bytes(sw)
                overlap0 = reactor.pipeline_overlap_ms
                setup_s = run.setup_done()
                if run.traced:
                    run.trace_start()
                    trace_until = time.perf_counter() + float(run.traffic["trace_seconds"])
                open_at = len(rec.times)
                harness.say(f"warm-up: {open_at} heights, quiet since height {quiet_from}, "
                            f"compile log {log.summary()}")
            continue
        if now >= trace_until:
            run.trace_stop()  # the window goes on untraced
        if n <= open_at:
            continue
        t_open = rec.times[open_at]
        if n - 1 - open_at >= measured:
            t_close = rec.times[open_at + measured]
            break
        if now >= t_open + run.seconds:
            t_close = t_open + run.seconds
            break
    run.trace_stop()
    after = run.counters()
    recv1 = _recv_bytes(sw)
    done = [t for t in rec.times[open_at + 1:] if t <= t_close]
    applied = len(done)
    elapsed = t_close - t_open
    rate = applied / elapsed
    cap = len(sw.peers()) * int(run.config["p2p"]["recv_rate"])
    harness.say(
        f"window: {applied} heights in {elapsed:.3f} s = {rate:.2f} heights/s; "
        f"received {(recv1 - recv0) / max(now - rec.times[open_at], 1e-9):.0f} B/s over "
        f"{len(sw.peers())} connections (cap {cap} B/s as shipped); "
        f"overlap {reactor.pipeline_overlap_ms:.0f} ms"
    )
    problems = run.health_problems(before, after)
    return harness.Observations(
        attempted=applied, failed=0,
        end_to_end={"catchup_heights_per_s": rate}, setup_s=setup_s,
        window=(t_open, t_close), counters_before=before, counters_after=after,
        correct_problems=problems,
        samples={
            "pipeline_overlap_ms": reactor.pipeline_overlap_ms - overlap0,
            "heights_since_open": len(rec.times) - open_at,
        },
    )


def _check_hashes(rec, store, chain_dir) -> list[str]:
    """Block hash and app hash of every synced height against the serving chain."""
    serving = chain.open_store(chain_dir)
    problems = []
    for h, app_hash in zip(rec.heights, rec.app_hashes):
        mine, theirs = store.load_block_meta(h), serving.load_block_meta(h)
        nxt = serving.load_block_meta(h + 1)
        if mine is None or mine.block_id.hash != theirs.block_id.hash:
            problems.append(f"height {h}: block hash differs from the serving chain")
        elif nxt is not None and app_hash != nxt.header.app_hash:
            problems.append(f"height {h}: app hash differs from the serving chain")
        if len(problems) > 5:
            break
    if rec.heights != list(range(1, len(rec.heights) + 1)):
        problems.append("heights were not applied in order from 1")
    harness.say(f"hashes: {len(rec.heights)} synced heights compared with the serving chain, "
                f"{len(problems)} problems")
    return problems


def _check_tampered(run, ctx, gen, chain_dir, tag, n_vals, tamper_height) -> list[str]:
    """A short second sync from a peer whose chain has one flipped signature
    in the commit for `tamper_height` must stop below it, with the peer dropped."""
    index = random.Random(f"{run.seed}/tamper").randrange(n_vals * 2 // 3)
    child = _spawn_peer(ctx, chain_dir, run.seed, tag, n_vals,
                        {"height": tamper_height, "index": index})
    problems = []
    try:
        hello = child[1].recv()
        rec, store, reactor, sw = _joiner(run, gen, [hello["addr"]])
        try:
            deadline = time.perf_counter() + 60
            while time.perf_counter() < deadline:
                time.sleep(0.02)
                if sw.num_peers() == 0:
                    break
            time.sleep(0.2)  # anything still in flight would land now
            if sw.num_peers() != 0:
                problems.append("the peer serving a bad commit was not dropped")
            if store.height() != tamper_height - 1:
                problems.append(
                    f"the sync from a tampered chain stopped at {store.height()}, "
                    f"not below {tamper_height}"
                )
            harness.say(f"tampered chain: signature {index} of the commit for height "
                        f"{tamper_height} flipped; joiner stopped at {store.height()}, "
                        f"peers left {sw.num_peers()}")
        finally:
            reactor.stop()
            sw.stop()
    finally:
        problems += harness.stop_children([child])
    return problems
