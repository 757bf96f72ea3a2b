"""A chain whose blocks carry transactions, synced through the real
blocksync reactor from several in-process peers over loopback TCP, and held
to the plain replay (`benchmarks/reference/`); a peer that serves an altered
block is refused and dropped. Small sizes, no chip."""

from __future__ import annotations

import os
import sys
import time
import types

import pytest

from cometbft_tpu.blocksync.reactor import BlocksyncReactor
from cometbft_tpu.libs.db import MemDB
from cometbft_tpu.state import make_genesis_state

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
SEED, TAG = 23, "sync-test"


@pytest.fixture(scope="module")
def bench():
    sys.path.insert(0, BENCH)  # as run.py has it: chain, fixtures, reference lie there
    try:
        import chain
        import loaded_chain
        from reference import kvstore_replay, rfc6962

        yield types.SimpleNamespace(
            chain=chain, loaded_chain=loaded_chain, rfc6962=rfc6962, kvstore_replay=kvstore_replay
        )
    finally:
        sys.path.remove(BENCH)


def _serve(bench, gen, store):
    nk, sw = bench.chain.new_switch(gen.chain_id, "serving-peer")
    sw.add_reactor("BLOCKSYNC", BlocksyncReactor(
        state=make_genesis_state(gen), block_exec=None, block_store=store, block_sync=False,
    ))
    return sw, f"{nk.id}@{sw.start('127.0.0.1:0')}"


def _join(bench, gen, addrs):
    """A fresh node that starts to sync once every peer has told its height
    (as a node does after state sync), so that the pool's choice among them
    does not hang on which status arrived first."""
    state, store, executor = bench.chain.fresh_node(gen)
    reactor = BlocksyncReactor(state=state, block_exec=executor, block_store=store, block_sync=False)
    _, sw = bench.chain.new_switch(gen.chain_id, "joiner")
    sw.add_reactor("BLOCKSYNC", reactor)
    sw.start("")
    for addr in addrs:
        assert sw.dial_peer(addr) is not None
    assert _wait(lambda: len(reactor.pool.pending_by_peer()) == len(addrs)), "a peer never told its height"
    reactor.switch_to_block_sync(state)
    return store, executor, reactor, sw


def _wait(cond, seconds=45.0):
    deadline = time.time() + seconds
    while time.time() < deadline and not cond():
        time.sleep(0.02)
    return cond()


@pytest.mark.parametrize(
    "heights, n_vals, n_txs, tx_bytes, n_peers",
    [(24, 8, 16, 64, 3), (9, 4, 37, 4096, 2), (12, 5, 1, 100, 4)],
    ids=["24x8x16-3peers", "9x4x37-multipart-2peers", "12x5x1-4peers"],
)
def test_a_loaded_chain_synced_from_several_peers_equals_the_plain_replay(
    bench, heights, n_vals, n_txs, tx_bytes, n_peers
):
    from cometbft_tpu.abci import types as abci

    gen, served = bench.loaded_chain.make_chain(SEED, TAG, n_vals, heights, n_txs, tx_bytes, MemDB())
    peers = [_serve(bench, gen, served) for _ in range(n_peers)]
    store, executor, reactor, sw = _join(bench, gen, [addr for _, addr in peers])
    try:
        # a height is saved before it is applied: wait for the application
        assert _wait(lambda: reactor.heights_applied >= heights - 1), (
            f"applied only {reactor.heights_applied} of {heights - 1}"
        )
        c = reactor.counters()  # while the peers are there: two of them count the live peers
        reactor.stop()
    finally:
        sw.stop()
        for peer_sw, _ in peers:
            peer_sw.stop()
    synced = heights - 1  # the tip has no next block to verify it
    blocks = [bench.loaded_chain.block_txs(SEED, h, n_txs, tx_bytes) for h in range(1, synced + 1)]
    app_hashes, kv = bench.kvstore_replay.replay(blocks)
    for h, txs in enumerate(blocks, start=1):
        mine, theirs = store.load_block(h), served.load_block(h)
        assert mine.hash() == theirs.hash() == store.load_block_meta(h).block_id.hash
        assert list(mine.data.txs) == txs and mine.header.data_hash == bench.rfc6962.root(txs)
        if h > 1:
            assert mine.header.app_hash == app_hashes[h - 2]
    assert reactor.state.app_hash == app_hashes[-1]
    info = executor.proxy_app.info(abci.RequestInfo())
    assert (info.last_block_height, info.last_block_app_hash) == (synced, app_hashes[-1])
    assert all(executor.proxy_app.query(abci.RequestQuery(data=k)).value == v for k, v in kv.items())
    assert c["heights_applied"] == synced and c["redo_requests"] == 0
    assert c["peers_asked"] == min(n_peers, heights) and c["requests_sent"] >= synced
    assert c["requests_to_busiest_peer"] < c["requests_sent"], "no one peer carried the catch-up"
    assert c["block_bytes_received"] >= sum(len(b.encode()) for b in map(served.load_block, range(1, synced + 1)))


def _two_txs_in_different_parts(block):
    """Indices of two txs whose bytes lie in different 64 KiB parts."""
    raw = block.encode()
    first, last = block.data.txs[0], block.data.txs[-1]
    assert raw.find(first) // 65536 != raw.find(last) // 65536
    return 0, len(block.data.txs) - 1


@pytest.mark.parametrize("how", ["tx-byte-flipped", "parts-swapped"])
def test_an_altered_block_is_refused_and_its_peer_dropped(bench, how):
    """One bit of one tx flipped, or two txs that lie in different parts
    exchanged (which exchanges what the parts hold): the block is never
    saved, the sync stops below it, the serving peer is dropped."""
    heights, bad_height = 8, 5
    gen, served = bench.loaded_chain.make_chain(SEED, TAG, 4, heights, 37, 4096, MemDB())
    if how == "tx-byte-flipped":
        altered = bench.loaded_chain.AlteredStore(served, bad_height, flip=(17, 1000))
    else:
        pair = _two_txs_in_different_parts(served.load_block(bad_height))
        altered = bench.loaded_chain.AlteredStore(served, bad_height, swap=pair)
    good, bad = served.load_block(bad_height), altered.load_block(bad_height)
    assert bad.header == good.header and list(bad.data.txs) != list(good.data.txs)
    assert bad.make_part_set().header() != good.make_part_set().header()
    peer_sw, addr = _serve(bench, gen, altered)
    store, _, reactor, sw = _join(bench, gen, [addr])
    try:
        assert _wait(lambda: sw.num_peers() == 0), "the peer serving an altered block was not dropped"
        time.sleep(0.2)  # anything still in flight would land now
        assert store.height() == bad_height - 1
        assert store.load_block(bad_height) is None, "the altered block was saved"
        assert reactor.counters()["redo_requests"] >= 1
        # an honest peer arrives: the same joiner asks it for both heights and goes on
        honest_sw, honest = _serve(bench, gen, served)
        try:
            assert sw.dial_peer(honest) is not None
            assert _wait(lambda: reactor.heights_applied >= heights - 1), f"stuck at {store.height()}"
            assert store.load_block(bad_height).hash() == good.hash()
            assert list(store.load_block(bad_height).data.txs) == list(good.data.txs)
        finally:
            honest_sw.stop()
    finally:
        reactor.stop()
        sw.stop()
        peer_sw.stop()
