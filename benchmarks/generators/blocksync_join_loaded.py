"""Traffic kind `blocksync_join_loaded`: `blocksync_join` over a chain whose
blocks carry the configuration's transactions (`txs_per_block` of
`tx_bytes` each, `key=value` with key and padding from `--seed`).

The joiner, the serving peers, the warm-up and the window are
`blocksync_join`'s own (its file is loaded by path and its pieces called:
the two cells differ only in what a block carries). What this kind adds:

- the chain comes from `loaded_chain.py` (a child that never imports JAX);
- set-up warms the shapes a prefetch uses before the joiner exists
  (`_warm_prefetch`): the lanes of the chain's first full prefetch window
  go through the backend's seam until the planner rests;
- the reactor's counters, and the pool's requests to each peer, are read
  after every applied height, so a reader has their growth over the window
  (`samples["reactor_counters"]`);
- after the window every synced height is held to the plain references
  (`reference/rfc6962.py`, `reference/kvstore_replay.py`): the txs the
  joiner stored are the seeded ones, the header's data hash is their plain
  RFC-6962 root, the stored parts are the plain protobuf encoding of the
  block (`reference/block_proto.py`) cut in 64 KiB, the part-set header is
  the plain root over them, the next header's results hash and app hash are the
  plain replay's, and the joiner's application reads back the replay's map;
- the tampered peer serves block `tamper_height` with one bit of one
  transaction flipped; the joiner must stop below it and drop the peer.

Parameters (the traffic file): those of `blocksync_join`.
"""

from __future__ import annotations

import multiprocessing
import os
import random
import time

import chain
import harness
import loaded_chain
from reference import block_proto, kvstore_replay, rfc6962

base = harness.load_by_path(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "blocksync_join.py"),
    "generator_blocksync_join",
)
PART_BYTES = 65536  # types/params.go BlockPartSizeBytes
WARM_MAX_CALLS = 12  # the planner rests after five: two shares, each loaded, then measured


class CountingRecorder(base.ApplyRecorder):
    """`ApplyRecorder` that also keeps the reactor's counters as they stood
    after each applied height (`counters[i]` belongs to `times[i]`)."""

    def __init__(self, executor, run):
        super().__init__(executor, run)
        self.reactor = None
        self.counters: list[dict] = []

    def apply_block(self, state, block_id, block):
        out = super().apply_block(state, block_id, block)
        counters = self.reactor.counters()
        by_peer = getattr(self.reactor.pool, "requests_by_peer", None)
        if by_peer is not None:  # a program from before the pool counted
            counters["requests_by_peer"] = by_peer()
        self.counters.append(counters)
        return out


def _joiner(run, gen, addrs):
    from cometbft_tpu.blocksync.reactor import BlocksyncReactor

    state, store, executor = chain.fresh_node(gen)
    rec = CountingRecorder(executor, run)
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


def _warm_prefetch(run, chain_dir, gen) -> None:
    """Loads every device program a prefetch reaches, inside set-up. The
    hybrid planner walks to its share over its first calls (its priors'
    bucket, then the one the measured walls choose), and each new bucket is
    a program to compile or load: 10-30 s in which no height is applied. So
    the lanes of one full prefetch window (the chain's own commits for
    heights 1 to PREFETCH_WINDOW - 1, as `_prefetch_verify_window` gathers
    them) go through `batch_verify` of the chain `auto` returned until two
    calls in a row repeat the share before them and load nothing. The
    verified-triple cache lies above this seam and sees none of it."""
    from cometbft_tpu.blocksync.reactor import BlocksyncReactor
    from cometbft_tpu.state import make_genesis_state

    vals = make_genesis_state(gen).validators.validators
    serving = chain.open_store(chain_dir)
    pubs, msgs, sigs = [], [], []
    for h in range(1, BlocksyncReactor.PREFETCH_WINDOW):
        commit = serving.load_block(h + 1).last_commit
        sign_bytes = commit.vote_sign_bytes_all(gen.chain_id)
        for idx, cs in enumerate(commit.signatures):
            pubs.append(vals[idx].pub_key.bytes())
            msgs.append(sign_bytes[idx])
            sigs.append(cs.signature)
    shares, at_rest = [], 0
    while at_rest < 2 and len(shares) < WARM_MAX_CALLS:
        loaded = run.compile_log.count
        ok, _ = run.backend.batch_verify(pubs, msgs, sigs)
        if not ok:
            raise harness.BenchFailure("a commit of the serving chain did not verify in set-up")
        shares.append(run.counters()["hybrid"].get("last_share"))
        # a program's first use teaches the planner nothing: the call after it does
        same = run.compile_log.count == loaded and shares[-2:-1] == shares[-1:]
        at_rest = at_rest + 1 if same else 0
    harness.say(f"prefetch warm-up: {len(pubs)} lanes, shares {shares}, "
                f"compile log {run.compile_log.summary()} after {run.setup_done():.1f} s")


def _settle(rec, timeout: float = 10.0) -> None:
    """The reactor stops between heights: wait until the sync thread has
    applied nothing for a while, so the application is read at a block's end."""
    n, since = len(rec.times), time.perf_counter()
    deadline = since + timeout
    while time.perf_counter() < deadline:
        time.sleep(0.05)
        if len(rec.times) != n:
            n, since = len(rec.times), time.perf_counter()
        elif time.perf_counter() - since >= 0.5:
            return


def run(run: harness.Run) -> harness.Observations:
    cfg, tr = run.config, run.traffic
    n_vals, n_txs, tx_bytes = int(cfg["validators"]), int(cfg["txs_per_block"]), int(cfg["tx_bytes"])
    tag = run.cell["config"]
    warm_h, quiet_h = int(tr["warmup_heights"]), int(tr["quiet_heights"])
    warm_max, measured = int(tr["warmup_max_heights"]), int(tr["measured_heights"])
    heights = warm_max + measured + 2  # the tip cannot be verified: no next block
    shape = (run.seed, n_vals, heights, n_txs, tx_bytes)
    chain_dir = run.cache_path()
    ctx = multiprocessing.get_context("spawn")
    builder = None
    if not loaded_chain.have_chain(chain_dir, *shape):
        builder = ctx.Process(
            target=loaded_chain.build_chain,
            args=(run.seed, tag, n_vals, heights, n_txs, tx_bytes, chain_dir),
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
        if builder.exitcode != 0 or not loaded_chain.have_chain(chain_dir, *shape):
            raise harness.BenchFailure(f"the chain builder exited {builder.exitcode}")
        harness.say(f"chain: built {heights} heights x {n_vals} validators x {n_txs} txs of "
                    f"{tx_bytes} B after {run.setup_done():.1f} s")
    else:
        harness.say(f"chain: {heights} heights found in {chain_dir}")

    children = [
        base._spawn_peer(ctx, chain_dir, run.seed, tag, n_vals) for _ in range(int(tr["peers"]))
    ]
    try:
        hello = [conn.recv() for _, conn in children]
        gen, _ = chain.genesis_for(run.seed, tag, n_vals)
        _warm_prefetch(run, chain_dir, gen)
        rec, store, reactor, sw = _joiner(run, gen, [h["addr"] for h in hello])
        try:
            obs = base._measure(run, rec, reactor, sw, warm_h, quiet_h, warm_max, measured)
        finally:
            reactor.stop()
            sw.stop()
        _settle(rec)
        at = rec.times.index(obs.window[0])  # the height that opened the window
        closed = sum(t <= obs.window[1] for t in rec.times)
        obs.samples["reactor_counters"] = (
            rec.counters[at], rec.counters[closed - 1], rec.times[closed - 1] - rec.times[at]
        )
        obs.correct_problems += base._check_hashes(rec, store, chain_dir)
        obs.correct_problems += _check_plain(run, rec, store, n_txs, tx_bytes)
    finally:
        left = harness.stop_children(children)
    obs.correct_problems += left
    obs.correct_problems += _check_altered(
        run, ctx, gen, chain_dir, tag, n_vals, int(tr["tamper_height"]), n_txs, tx_bytes
    )
    return obs


def _plain_values(block) -> dict:
    """A block of the program as `reference/block_proto.py` takes it."""
    if block.evidence:
        raise harness.BenchFailure("a block with evidence: the plain encoding carries none")

    def block_id(b):
        return b.hash, b.part_set_header.total, b.part_set_header.hash

    def at(t):
        return t.seconds, t.nanos

    h, c = block.header, block.last_commit
    return {
        "header": {
            "version_block": h.version.block, "version_app": h.version.app,
            "chain_id": h.chain_id, "height": h.height, "time": at(h.time),
            "last_block_id": block_id(h.last_block_id),
            "last_commit_hash": h.last_commit_hash, "data_hash": h.data_hash,
            "validators_hash": h.validators_hash, "next_validators_hash": h.next_validators_hash,
            "consensus_hash": h.consensus_hash, "app_hash": h.app_hash,
            "last_results_hash": h.last_results_hash, "evidence_hash": h.evidence_hash,
            "proposer_address": h.proposer_address,
        },
        "txs": list(block.data.txs),
        "last_commit": c and {
            "height": c.height, "round": c.round, "block_id": block_id(c.block_id),
            "signatures": [
                (s.block_id_flag, s.validator_address, at(s.timestamp), s.signature)
                for s in c.signatures
            ],
        },
    }


def _check_plain(run, rec, store, n_txs, tx_bytes) -> list[str]:
    """Every synced height against the plain references."""
    from cometbft_tpu.abci import types as abci

    problems: list[str] = []

    def bad(h, what):
        if len(problems) < 6:
            problems.append(f"height {h}: {what}")

    synced = len(rec.heights)
    blocks = [loaded_chain.block_txs(run.seed, h, n_txs, tx_bytes) for h in range(1, synced + 1)]
    app_hashes, kv = kvstore_replay.replay(blocks)
    results_root = rfc6962.root([kvstore_replay.result_leaf()] * n_txs)
    for h, txs, app_hash, got_app_hash in zip(rec.heights, blocks, app_hashes, rec.app_hashes):
        block, meta = store.load_block(h), store.load_block_meta(h)
        if block is None or list(block.data.txs) != txs:
            bad(h, "the stored txs are not the seeded ones")
            continue
        if block.header.data_hash != rfc6962.root(txs):
            bad(h, "data hash is not the plain RFC-6962 root of the txs it carried")
        header = meta.block_id.part_set_header
        stored = [store.load_block_part(h, i).bytes for i in range(header.total)]
        parts = block_proto.parts(block_proto.block(_plain_values(block)), PART_BYTES)
        if stored != parts:
            bad(h, "the stored parts are not the plain encoding of the block, cut in 64 KiB")
        if (header.total, header.hash) != (len(parts), rfc6962.root(parts)):
            bad(h, "part-set header is not the plain root over the block's parts")
        if got_app_hash != app_hash:
            bad(h, "app hash is not the plain replay's")
        nxt = store.load_block_meta(h + 1)
        if nxt is not None and (nxt.header.app_hash, nxt.header.last_results_hash) != (app_hash, results_root):
            bad(h, "the next header's app hash or results hash is not the plain replay's")
    info = rec.proxy_app.info(abci.RequestInfo())
    if info.last_block_height != synced or info.last_block_app_hash != (app_hashes or [b""])[-1]:
        problems.append(f"the application stands at height {info.last_block_height}, synced {synced}")
    wrong = sum(
        rec.proxy_app.query(abci.RequestQuery(data=k)).value != v for k, v in kv.items()
    )
    if wrong:
        problems.append(f"{wrong} of {len(kv)} keys do not read back the replay's value")
    harness.say(f"plain references: {synced} heights x {n_txs} txs (tx root, part-set root, results "
                f"hash, app hash), {len(kv)} keys read back; {len(problems)} problems")
    return problems


def _check_altered(run, ctx, gen, chain_dir, tag, n_vals, tamper_height, n_txs, tx_bytes) -> list[str]:
    """A short second sync from a peer that serves block `tamper_height`
    with one bit of one transaction flipped must stop below it, with the
    peer dropped and the block never saved."""
    rng = random.Random(f"{run.seed}/alter")
    flip = (rng.randrange(n_txs), rng.randrange(tx_bytes))
    parent, child = ctx.Pipe()
    proc = ctx.Process(
        target=loaded_chain.serve_altered_peer,
        args=(child, chain_dir, run.seed, tag, n_vals, {"height": tamper_height, "flip": flip}),
        daemon=True,
    )
    proc.start()
    child.close()
    problems = []
    try:
        hello = parent.recv()
        rec, store, reactor, sw = _joiner(run, gen, [hello["addr"]])
        try:
            deadline = time.perf_counter() + 60
            while time.perf_counter() < deadline and sw.num_peers() != 0:
                time.sleep(0.02)
            time.sleep(0.2)  # anything still in flight would land now
            if sw.num_peers() != 0:
                problems.append("the peer serving an altered block was not dropped")
            if store.height() != tamper_height - 1:
                problems.append(
                    f"the sync from an altered chain stopped at {store.height()}, "
                    f"not below {tamper_height}"
                )
            harness.say(f"altered chain: byte {flip[1]} of tx {flip[0]} of block {tamper_height} "
                        f"flipped; joiner stopped at {store.height()}, peers left {sw.num_peers()}")
        finally:
            reactor.stop()
            sw.stop()
    finally:
        problems += harness.stop_children([(proc, parent)])
    return problems
