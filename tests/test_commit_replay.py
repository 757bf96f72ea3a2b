"""The plain replay (`benchmarks/reference/commit_replay.py`, ISSUE 36)
against the system: its pieces equal the program's bytes, a joiner applies
exactly the heights it allows on a clean chain and stops where it says on a
tampered one, and the prefetch window's bitmap through the hybrid tier
equals the scalar ZIP-215 reference lane for lane with flipped lanes. The
chains are built in this process (empty blocks, every validator signing),
the peers are in-process switches over loopback TCP. Small sizes, no chip."""

from __future__ import annotations

import os
import random
import sys
import threading
import time
import types

import pytest

from cometbft_tpu import native
from cometbft_tpu.blocksync.reactor import BlocksyncReactor
from cometbft_tpu.crypto import ed25519
from cometbft_tpu.libs.db import MemDB
from cometbft_tpu.sidecar import backend as be
from cometbft_tpu.state import make_genesis_state

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
SEED, TAG = 36, "replay-test"


@pytest.fixture(scope="module")
def bench():
    sys.path.insert(0, BENCH)  # as run.py has it: chain, fixtures, harness, reference lie there
    try:
        import chain
        import harness
        import loaded_chain
        from reference import commit_replay, ed25519_zip215

        generator = harness.load_by_path(
            os.path.join(BENCH, "generators", "blocksync_replay.py"), "generator_blocksync_replay"
        )
        yield types.SimpleNamespace(
            chain=chain, loaded_chain=loaded_chain, replay=commit_replay, zip215=ed25519_zip215,
            generator=generator, plain=generator.loaded._plain_values,
        )
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(autouse=True)
def clean_cache():
    ed25519._verified.clear()
    yield
    ed25519._verified.clear()


@pytest.fixture(autouse=True)
def no_thread_left_behind():
    """A stopped switch's receive and send threads end a moment after
    `stop()` returns; a test of thread roles that follows in the same
    process (tests/test_trace.py) must not find them."""
    before = set(threading.enumerate())
    yield
    deadline = time.time() + 5.0
    while time.time() < deadline and any(
        t.is_alive() and t.name.startswith(("p2p-", "blocksync-"))
        for t in set(threading.enumerate()) - before
    ):
        time.sleep(0.02)


def _chain(bench, n_vals: int, heights: int):
    """(genesis, block store) of `heights` empty blocks, every validator
    signing. The builder's executor verified every commit in this process:
    what it left in the verified-triple cache goes, a joiner starts cold."""
    made = bench.loaded_chain.make_chain(SEED, TAG, n_vals, heights, 0, 17, MemDB())
    ed25519._verified.clear()
    return made


def _validators(gen):
    return [(v.pub_key.bytes(), v.power) for v in gen.validators]


def _plain_blocks(bench, store, lo: int, hi: int):
    return [bench.plain(store.load_block(h)) for h in range(lo, hi + 1)]


def _serve(bench, gen, store):
    nk, sw = bench.chain.new_switch(gen.chain_id, "serving-peer")
    sw.add_reactor("BLOCKSYNC", BlocksyncReactor(
        state=make_genesis_state(gen), block_exec=None, block_store=store, block_sync=False,
    ))
    return sw, f"{nk.id}@{sw.start('127.0.0.1:0')}"


def _join(bench, gen, addr):
    state, store, executor = bench.chain.fresh_node(gen)
    reactor = BlocksyncReactor(state=state, block_exec=executor, block_store=store, block_sync=True)
    _, sw = bench.chain.new_switch(gen.chain_id, "joiner")
    sw.add_reactor("BLOCKSYNC", reactor)
    sw.start("")
    assert sw.dial_peer(addr) is not None
    return store, reactor, sw


def _wait(cond, seconds=60.0):
    deadline = time.time() + seconds
    while time.time() < deadline and not cond():
        time.sleep(0.02)
    return cond()


@pytest.fixture(scope="module")
def small(bench):
    """12 heights x 8 validators, and the program's own objects beside them."""
    gen, store = _chain(bench, 8, 12)
    return gen, store, make_genesis_state(gen).validators


@pytest.mark.parametrize("piece", ["header-hash", "block-id", "sign-bytes", "set-order", "address"])
def test_the_reference_computes_the_programs_bytes(bench, small, piece):
    """Written from the .proto files and the Go sources; held here to what
    the program computes for the same chain."""
    gen, store, vals = small
    for h in range(1, 12):
        block, nxt = store.load_block(h), store.load_block(h + 1)
        plain = bench.plain(block)
        if piece == "header-hash":
            assert bench.replay.header_hash(plain["header"]) == block.hash()
        elif piece == "block-id":
            meta = store.load_block_meta(h).block_id
            want = (meta.hash, meta.part_set_header.total, meta.part_set_header.hash)
            assert bench.replay.block_id(plain) == want
            assert tuple(bench.plain(nxt)["last_commit"]["block_id"]) == want
        elif piece == "sign-bytes":
            commit = nxt.last_commit
            bid = bench.replay.block_id(plain)
            for idx, cs in enumerate(commit.signatures):
                at = (cs.timestamp.seconds, cs.timestamp.nanos)
                got = bench.replay.sign_bytes(gen.chain_id, h, commit.round, bid, at)
                assert got == bytes(commit.vote_sign_bytes(gen.chain_id, idx))
        elif piece == "set-order":
            assert bench.replay.set_order(_validators(gen)) == [
                (v.pub_key.bytes(), v.voting_power) for v in vals.validators]
        else:
            assert [bench.replay.address(v.pub_key.bytes()) for v in vals.validators] == [
                v.address for v in vals.validators]


@pytest.mark.parametrize("n_vals, heights", [(8, 40), (16, 48)], ids=["8x40", "16x48"])
def test_a_joiner_applies_exactly_the_heights_the_reference_allows(bench, n_vals, heights):
    gen, served = _chain(bench, n_vals, heights)
    plain = bench.replay.replay(gen.chain_id, _validators(gen), _plain_blocks(bench, served, 1, heights))
    assert plain == (list(range(1, heights)), None, None), "the tip has no next block to commit it"
    peer_sw, addr = _serve(bench, gen, served)
    store, reactor, sw = _join(bench, gen, addr)
    try:
        assert _wait(lambda: reactor.heights_applied >= heights - 1), f"stuck at {store.height()}"
        time.sleep(0.2)
        assert reactor.heights_applied == len(plain.applied) and store.height() == plain.applied[-1]
        for h in plain.applied:
            assert store.load_block_meta(h).block_id.hash == served.load_block_meta(h).block_id.hash
        c = reactor.counters()
        assert c["redo_requests"] == 0 and c["prefetch_windows"] >= 1
        assert c["prefetch_lanes"] >= n_vals * 2 and c["prefetch_ms"] > 0
    finally:
        reactor.stop()
        sw.stop()
        peer_sw.stop()


@pytest.mark.parametrize("n_vals, bad_height, index", [(8, 6, 2), (16, 33, 9), (12, 1, 0)],
                         ids=["8-vals-h6", "16-vals-h33-second-window", "12-vals-h1"])
def test_a_tampered_chain_stops_where_the_reference_says(bench, n_vals, bad_height, index):
    """One bit of one signature of the commit for `bad_height` flipped, in
    the first two thirds of the set (where VerifyCommitLight looks): the
    reference stops there, and so does the joiner, one height below."""
    heights = bad_height + 6
    gen, served = _chain(bench, n_vals, heights)
    pair = bench.generator._tampered_pair(served, bad_height, index)
    assert bench.replay.replay(gen.chain_id, _validators(gen), pair) == (
        [], bad_height, f"wrong signature (#{index})")
    blocks = _plain_blocks(bench, served, 1, bad_height - 1) + pair
    plain = bench.replay.replay(gen.chain_id, _validators(gen), blocks)
    assert (plain.applied, plain.stopped_at) == (list(range(1, bad_height)), bad_height)
    peer_sw, addr = _serve(bench, gen, bench.chain._TamperedStore(served, bad_height, index))
    store, reactor, sw = _join(bench, gen, addr)
    try:
        assert _wait(lambda: sw.num_peers() == 0), "the peer serving a bad commit was not dropped"
        time.sleep(0.2)  # anything still in flight would land now
        assert store.height() == plain.stopped_at - 1 == reactor.heights_applied
        assert reactor.counters()["redo_requests"] >= 1
    finally:
        reactor.stop()
        sw.stop()
        peer_sw.stop()


def test_what_verify_commit_light_does_not_look_at(bench, small):
    """Power is summed until it passes 2/3, in the set's order: a signature
    after that point is not checked, a nil or absent vote before it is
    passed over, and too few votes for the block refuse the commit."""
    gen, store, _ = small
    validators = bench.replay.set_order(_validators(gen))
    block, commit = bench.plain(store.load_block(3)), bench.plain(store.load_block(4))["last_commit"]

    def with_sig(idx, **change):
        flag, address, at, sig = commit["signatures"][idx]
        new = {"flag": flag, "sig": sig, **change}
        sigs = list(commit["signatures"])
        sigs[idx] = (new["flag"], address, at, new["sig"])
        return {**commit, "signatures": sigs}

    flip = bench.generator._flip
    refusal = bench.replay.refusal
    assert refusal(gen.chain_id, validators, block, commit) is None
    # 8 x 10 of power: 2/3 is 53, so the sixth signature passes it
    assert refusal(gen.chain_id, validators, block, with_sig(5, sig=flip(commit["signatures"][5][3]))) == "wrong signature (#5)"
    assert refusal(gen.chain_id, validators, block, with_sig(6, sig=flip(commit["signatures"][6][3]))) is None
    assert refusal(gen.chain_id, validators, block, with_sig(1, flag=bench.replay.NIL)) is None
    absent = commit
    for idx in (0, 1, 2):
        absent = {**absent, "signatures": [
            (bench.replay.ABSENT, b"", (0, 0), b"") if i == idx else s
            for i, s in enumerate(absent["signatures"])]}
    assert refusal(gen.chain_id, validators, block, absent) == "voting power 50 does not pass 53"
    assert "block id" in refusal(gen.chain_id, validators, bench.plain(store.load_block(2)), {**commit, "height": 2})
    assert refusal(gen.chain_id, validators, block, {**commit, "signatures": commit["signatures"][:-1]}).startswith("7 signatures")
    assert refusal(gen.chain_id, validators, block, None) == "no commit"


needs_native = pytest.mark.skipif(not native.available(), reason="native tier unavailable")


@needs_native
@pytest.mark.parametrize("n_vals", [4, 8], ids=["31x4-lanes", "31x8-lanes"])
def test_the_prefetch_windows_bitmap_is_the_scalar_references(bench, monkeypatch, n_vals):
    """The chain's first prefetch window, gathered as the reactor gathers
    it, through the hybrid tier (split between the XLA:CPU device and the
    host MSM) with a lane flipped in each third: refused, and the bitmap is
    the scalar reference's lane for lane; unflipped, accepted."""
    for k, v in {"CMTPU_HYBRID_MIN": "8", "CMTPU_DEV_RATE": "1000", "CMTPU_HOST_RATE": "1000",
                 "CMTPU_DEV_OVERHEAD_MS": "0"}.items():
        monkeypatch.setenv(k, v)
    gen, served = _chain(bench, n_vals, 33)
    n_heights, (pubs, msgs, sigs) = bench.generator._window_triples(gen, served)
    assert (n_heights, len(pubs)) == (31, 31 * n_vals)
    rng = random.Random(n_vals)
    third = len(pubs) // 3
    flipped = [rng.randrange(k * third, (k + 1) * third) for k in range(3)]
    bad = list(sigs)
    for lane in flipped:
        bad[lane] = bench.generator._flip(sigs[lane])
    hybrid = be.HybridBackend()
    hybrid._n_dev = 1
    before = hybrid.counters()
    ok, bits = hybrid.batch_verify(pubs, msgs, bad)
    want = [bench.zip215.verify_zip215(p, m, s) for p, m, s in zip(pubs, msgs, bad)]
    assert not ok and bits == want and [i for i, b in enumerate(bits) if not b] == flipped
    assert hybrid.batch_verify(pubs, msgs, sigs) == (True, [True] * len(pubs))
    after = hybrid.counters()
    assert after["device_lanes"] > before["device_lanes"], "the window reached the device tier"
    # the window repeats the set's keys a commit: counted, never given tables
    assert after["resident_repeat_sightings"] - before["resident_repeat_sightings"] == 2
    assert after["resident_repeat_lanes"] - before["resident_repeat_lanes"] == 2 * len(pubs)
    assert after["resident_builds"] == before["resident_builds"]


@needs_native
def test_the_prefetchs_span_and_counters_over_a_window_whose_keys_repeat_and_one_whose_do_not(
    bench, monkeypatch
):
    """A joiner over the hybrid tier, its spans in the ring: every
    `blocksync.prefetch_collect` is the child of a `blocksync.prefetch` with
    the same blocks and lanes and ends before the seam is entered; the
    reactor's `prefetch_*` counters are the spans' sums; a window's column
    repeats the set's keys, so its `hybrid.plan` says how many are distinct
    and `resident_repeat_*` count it, where a column of distinct keys moves
    neither."""
    from cometbft_tpu.libs import trace

    for k, v in {"CMTPU_HYBRID_MIN": "8", "CMTPU_DEV_RATE": "1000", "CMTPU_HOST_RATE": "1000",
                 "CMTPU_DEV_OVERHEAD_MS": "0"}.items():
        monkeypatch.setenv(k, v)
    n_vals, heights = 8, 40
    gen, served = _chain(bench, n_vals, heights)
    hybrid = be.HybridBackend()
    hybrid._n_dev = 1
    be.set_backend(hybrid)
    peer_sw, addr = _serve(bench, gen, served)
    trace.clear()
    try:
        with trace.capture():
            before = hybrid.counters()
            store, reactor, sw = _join(bench, gen, addr)
            try:
                assert _wait(lambda: reactor.heights_applied >= heights - 1), f"stuck at {store.height()}"
            finally:
                reactor.stop()
                sw.stop()
            windows = hybrid.counters()
            # a column whose keys do not repeat: one commit's worth, as verify_commit sends it
            commit = served.load_block(2).last_commit
            vals = make_genesis_state(gen).validators.validators
            ok, _ = hybrid.batch_verify(
                [v.pub_key.bytes() for v in vals],
                [bytes(m) for m in commit.vote_sign_bytes_all(gen.chain_id)],
                [cs.signature for cs in commit.signatures],
            )
            assert ok
            after = hybrid.counters()
            spans = trace.spans()
    finally:
        be.set_backend(None)
        peer_sw.stop()
        trace.clear()
    by_id = {s["id"]: s for s in spans}
    collects = [s for s in spans if s["name"] == "blocksync.prefetch_collect"]
    prefetches = [s for s in spans if s["name"] == "blocksync.prefetch"]
    assert collects and len(collects) == len(prefetches)
    for c in collects:
        parent = by_id[c["parent"]]
        assert parent["name"] == "blocksync.prefetch" and parent["thread"] == "blocksync-prefetch"
        assert (c["attrs"]["blocks"], c["attrs"]["lanes"]) == (parent["attrs"]["blocks"], parent["attrs"]["lanes"])
        assert c["attrs"]["lanes"] == c["attrs"]["blocks"] * n_vals
        inside = [s for s in spans if s["root"] == c["root"] and s["name"] == "batch.verify"]
        assert all(s["t0"] >= c["t1"] for s in inside), "everything before bv.verify()"
    counters = reactor.counters()
    verified = [p for p in prefetches if p["attrs"]["blocks"] >= 2]
    assert counters["prefetch_windows"] == len(verified)
    assert counters["prefetch_lanes"] == sum(p["attrs"]["lanes"] for p in verified)
    assert counters["prefetch_ms"] >= sum((p["t1"] - p["t0"]) * 1000 for p in prefetches) * 0.99
    # the windows' columns repeat 8 keys; the planner's span and the counters say so
    plans = [s for s in spans if s["name"] == "hybrid.plan"]
    window_plans = [s for s in plans if by_id[s["root"]]["name"] == "blocksync.prefetch"]
    assert window_plans and all(s["attrs"]["distinct"] == n_vals for s in window_plans)
    sighted = windows["resident_repeat_sightings"] - before["resident_repeat_sightings"]
    assert sighted == len(window_plans)
    assert windows["resident_repeat_lanes"] - before["resident_repeat_lanes"] == sum(
        by_id[s["parent"]]["attrs"]["n"] for s in window_plans)
    lone = [s for s in plans if s["parent"] is not None and by_id[s["parent"]]["attrs"]["n"] == n_vals
            and by_id[s["root"]]["name"] == "hybrid.call"]
    assert len(lone) == 1 and "distinct" not in lone[0]["attrs"]
    assert after["resident_repeat_sightings"] == windows["resident_repeat_sightings"]
    assert after["resident_repeat_lanes"] == windows["resident_repeat_lanes"]
    assert after["resident_first_sightings"] == windows["resident_first_sightings"] + 1
