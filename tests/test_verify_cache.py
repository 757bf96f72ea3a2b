"""Verified-triple cache + blocksync window prefetch: many consecutive
blocks' commit signatures verify in ONE backend call, and the per-commit
protocol checks (trySync light verify, ApplyBlock full verify) become
cache hits. Invalid signatures must never be cached."""

import pytest

from cometbft_tpu.crypto import ed25519


@pytest.fixture(autouse=True)
def clean_cache():
    ed25519._verified.clear()
    yield
    ed25519._verified.clear()


class CountingBackend:
    """Wraps the real cpu backend, counting batch_verify calls."""

    def __init__(self):
        from cometbft_tpu.sidecar.backend import CpuBackend

        self.inner = CpuBackend()
        self.calls = 0
        self.sigs = 0

    def batch_verify(self, pubs, msgs, sigs):
        self.calls += 1
        self.sigs += len(pubs)
        return self.inner.batch_verify(pubs, msgs, sigs)


@pytest.fixture
def counting_backend(monkeypatch):
    be = CountingBackend()
    import cometbft_tpu.sidecar.backend as backend_mod

    monkeypatch.setattr(backend_mod, "get_backend", lambda: be)
    return be


def _bv(entries):
    bv = ed25519.BatchVerifier()
    for pub, msg, sig in entries:
        bv.add(ed25519.PubKey(pub), msg, sig)
    return bv


def test_cache_skips_backend_on_full_hit(counting_backend):
    priv = ed25519.gen_priv_key_from_secret(b"cache")
    entries = [
        (priv.pub_key().bytes(), b"m%d" % i, priv.sign(b"m%d" % i)) for i in range(8)
    ]
    ok, bits = _bv(entries).verify()
    assert ok and all(bits)
    assert counting_backend.calls == 1
    ok, bits = _bv(entries).verify()
    assert ok and all(bits)
    assert counting_backend.calls == 1, "full cache hit must skip the backend"
    # subset of a verified batch is also a full hit
    ok, _ = _bv(entries[2:5]).verify()
    assert ok
    assert counting_backend.calls == 1


def test_invalid_sig_is_never_cached(counting_backend):
    priv = ed25519.gen_priv_key_from_secret(b"bad")
    good = (priv.pub_key().bytes(), b"good", priv.sign(b"good"))
    bad = (priv.pub_key().bytes(), b"bad", b"\x01" * 64)
    ok, bits = _bv([good, bad]).verify()
    assert not ok and bits == [True, False]
    assert counting_backend.calls == 1
    # the bad triple forces a backend call every time; the good one is cached
    ok, bits = _bv([bad]).verify()
    assert not ok and bits == [False]
    assert counting_backend.calls == 2
    ok, _ = _bv([good]).verify()
    assert ok
    assert counting_backend.calls == 2


def test_single_verify_populates_and_consults_cache():
    priv = ed25519.gen_priv_key_from_secret(b"single")
    pub = priv.pub_key()
    msg, sig = b"one-shot", priv.sign(b"one-shot")
    key = (pub.bytes(), sig, msg)
    assert key not in ed25519._verified
    assert pub.verify_signature(msg, sig)
    assert key in ed25519._verified, "valid single verify must cache"
    # a cached triple short-circuits (observable: even a poisoned pubkey
    # handle cache cannot make it fail)
    assert pub.verify_signature(msg, sig)
    # invalid never lands in the cache
    bad = b"\x01" * 64
    assert not pub.verify_signature(msg, bad)
    assert (pub.bytes(), bad, msg) not in ed25519._verified


def test_consensus_prebatch_warms_cache(counting_backend):
    """_prebatch_vote_signatures on a drained queue of vote messages puts
    every valid signature in the cache with one backend call; the serial
    _try_add_vote verification then runs cache-hot."""
    from cometbft_tpu.consensus import messages as cmsg
    from cometbft_tpu.types import BlockID, GenesisDoc, GenesisValidator, Time, Vote
    from cometbft_tpu.types.block import PRECOMMIT_TYPE
    from cometbft_tpu.types.part_set import PartSetHeader
    from cometbft_tpu.types.priv_validator import MockPV
    from cometbft_tpu.state import make_genesis_state

    pvs = [MockPV() for _ in range(16)]
    gen = GenesisDoc(
        chain_id="prebatch-chain",
        genesis_time=Time(1700000000, 0),
        validators=[
            GenesisValidator(pv.address(), pv.get_pub_key(), 10, "") for pv in pvs
        ],
    )
    gen.validate_and_complete()
    state = make_genesis_state(gen)

    class FakeCS:
        pass

    cs = FakeCS()
    cs.state = state
    cs.logger = None
    cs._failed_triples = {}
    from cometbft_tpu.consensus.state import ConsensusState

    bid = BlockID(b"\x07" * 32, PartSetHeader(1, b"\x07" * 32))
    pv_by_addr = {pv.address(): pv for pv in pvs}
    items = []
    # indices must follow the SORTED validator-set order, not genesis order
    for idx, val in enumerate(state.validators.validators):
        pv = pv_by_addr[val.address]
        v = Vote(
            type=PRECOMMIT_TYPE, height=1, round=0, block_id=bid,
            timestamp=Time(1700000001, idx),
            validator_address=pv.address(), validator_index=idx,
        )
        v = pv.sign_vote("prebatch-chain", v)
        items.append(("peer", cmsg.VoteMessage(v), "p"))
    ConsensusState._prebatch_vote_signatures(cs, items)
    assert counting_backend.calls == 1
    assert counting_backend.sigs == 16
    # every vote now verifies without further backend traffic
    for _, m, _ in items:
        val = state.validators.validators[m.vote.validator_index]
        assert val.pub_key.verify_signature(
            m.vote.sign_bytes("prebatch-chain"), m.vote.signature
        )
    assert counting_backend.calls == 1


def test_blocksync_prefetch_batches_window(counting_backend):
    """Build a 12-block chain for a 4-validator set, feed it to a blocksync
    reactor's pool, and sync: the window prefetch must cover many commits
    per backend call (trySync light verify AND ApplyBlock's full LastCommit
    verify both become cache hits) instead of two calls per block."""
    from cometbft_tpu.blocksync.pool import _Requester
    from cometbft_tpu.blocksync.reactor import BlocksyncReactor
    from cometbft_tpu.types import GenesisDoc, GenesisValidator, Time
    from cometbft_tpu.types.priv_validator import MockPV
    from tests.test_blocksync import CHAIN_ID, _fresh_node, _populated_chain

    pvs = [MockPV() for _ in range(4)]
    gen = GenesisDoc(
        chain_id=CHAIN_ID,
        genesis_time=Time(1700000000, 0),
        validators=[
            GenesisValidator(pv.address(), pv.get_pub_key(), 10, "") for pv in pvs
        ],
    )
    gen.validate_and_complete()
    _, server_store, _ = _populated_chain(pvs, gen, 12)
    client_state, client_store, client_exec = _fresh_node(gen)
    reactor = BlocksyncReactor(
        state=client_state,
        block_exec=client_exec,
        block_store=client_store,
        block_sync=True,
    )
    for h in range(1, 13):
        req = _Requester(h)
        req.block = server_store.load_block(h)
        req.peer_id = "p1"
        reactor.pool._requesters[h] = req
    counting_backend.calls = 0
    counting_backend.sigs = 0
    applied = 0
    while reactor._try_sync_one():
        applied += 1
    assert applied == 11, f"applied {applied} of 11 possible blocks"
    # Without the prefetch this costs ~2 backend calls per block (22+);
    # with it the whole sync fits in a few window-sized dispatches.
    assert counting_backend.calls <= 3, (
        f"{counting_backend.calls} backend calls for {applied} blocks "
        f"({counting_backend.sigs} sigs)"
    )


def test_prebatch_memoizes_failed_triples(counting_backend):
    """An invalid-vote storm replayed across drains costs ONE dispatch for
    the unique bad triples, not one per drain (advisor r4: attacker-
    controlled double-verification amplification)."""
    from cometbft_tpu.consensus import messages as cmsg
    from cometbft_tpu.consensus.state import ConsensusState
    from cometbft_tpu.state import make_genesis_state
    from cometbft_tpu.types import BlockID, GenesisDoc, GenesisValidator, Time, Vote
    from cometbft_tpu.types.block import PRECOMMIT_TYPE
    from cometbft_tpu.types.part_set import PartSetHeader
    from cometbft_tpu.types.priv_validator import MockPV

    pvs = [MockPV() for _ in range(16)]
    gen = GenesisDoc(
        chain_id="memo-chain",
        genesis_time=Time(1700000000, 0),
        validators=[
            GenesisValidator(pv.address(), pv.get_pub_key(), 10, "") for pv in pvs
        ],
    )
    gen.validate_and_complete()
    state = make_genesis_state(gen)

    class FakeCS:
        pass

    cs = FakeCS()
    cs.state = state
    cs.logger = None
    cs._failed_triples = {}
    cs._FAILED_TRIPLES_MAX = ConsensusState._FAILED_TRIPLES_MAX

    bid = BlockID(b"\x07" * 32, PartSetHeader(1, b"\x07" * 32))
    pv_by_addr = {pv.address(): pv for pv in pvs}
    items = []
    for idx, val in enumerate(state.validators.validators):
        pv = pv_by_addr[val.address]
        v = Vote(
            type=PRECOMMIT_TYPE, height=1, round=0, block_id=bid,
            timestamp=Time(1700000001, idx),
            validator_address=pv.address(), validator_index=idx,
        )
        v = pv.sign_vote("memo-chain", v)
        import dataclasses

        v = dataclasses.replace(v, signature=bytes(64))  # garbage signature
        items.append(("peer", cmsg.VoteMessage(v), "p"))

    ConsensusState._prebatch_vote_signatures(cs, items)
    assert counting_backend.calls == 1
    assert len(cs._failed_triples) == 16
    # replayed storm: all triples memoized bad -> no new dispatch
    ConsensusState._prebatch_vote_signatures(cs, items)
    assert counting_backend.calls == 1


# -- CMTPU_VERIFY_CACHE_MAX: bounded LRU on the verified-triple cache -----


def test_cache_cap_evicts_oldest_first(counting_backend, monkeypatch):
    """Mirrors the _CACHE_SIZE pubkey-cache pattern: overflow evicts from
    the OLD end of insertion order, the newest entries survive."""
    monkeypatch.setattr(ed25519, "_VERIFIED_MAX", 8)
    priv = ed25519.gen_priv_key_from_secret(b"cap")
    entries = [
        (priv.pub_key().bytes(), b"cap-%d" % i, priv.sign(b"cap-%d" % i))
        for i in range(12)
    ]
    for e in entries[:8]:
        _bv([e]).verify()
    assert len(ed25519._verified) == 8
    # Entry 9 overflows: the oldest quarter (entries 0-1) is swept first.
    _bv([entries[8]]).verify()
    keys = set(ed25519._verified)
    assert (entries[0][0], entries[0][2], entries[0][1]) not in keys
    assert (entries[8][0], entries[8][2], entries[8][1]) in keys
    assert (entries[7][0], entries[7][2], entries[7][1]) in keys
    assert len(ed25519._verified) <= 8


def test_cache_refresh_on_reverify_moves_to_young_end(
    counting_backend, monkeypatch
):
    monkeypatch.setattr(ed25519, "_VERIFIED_MAX", 4)
    priv = ed25519.gen_priv_key_from_secret(b"lru")
    entries = [
        (priv.pub_key().bytes(), b"lru-%d" % i, priv.sign(b"lru-%d" % i))
        for i in range(6)
    ]
    for e in entries[:4]:
        _bv([e]).verify()
    # Re-verify entry 0 through the backend path (cache bypassed via a
    # direct put — BatchVerifier would short-circuit on the hit).
    ed25519._verified_put((entries[0][0], entries[0][2], entries[0][1]))
    assert list(ed25519._verified)[-1] == (
        entries[0][0], entries[0][2], entries[0][1]
    ), "refreshed triple must move to the young end"
    # Overflow now: entry 1 (the true oldest) goes, entry 0 survives.
    _bv([entries[4]]).verify()
    keys = set(ed25519._verified)
    assert (entries[0][0], entries[0][2], entries[0][1]) in keys
    assert (entries[1][0], entries[1][2], entries[1][1]) not in keys


def test_cache_max_env_knob(monkeypatch):
    import importlib
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, "-c",
         "from cometbft_tpu.crypto import ed25519; print(ed25519._VERIFIED_MAX)"],
        env={**__import__('os').environ,
             "CMTPU_VERIFY_CACHE_MAX": "4096", "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120,
    )
    assert out.stdout.strip() == "4096", out.stderr


def test_partial_cache_hit_dispatches_only_uncached(counting_backend):
    """A batch mixing cached and new triples dispatches ONLY the new ones
    (with within-batch dedup), and merges bitmaps correctly."""
    priv = ed25519.gen_priv_key_from_secret(b"partial")
    entries = [
        (priv.pub_key().bytes(), b"p-%d" % i, priv.sign(b"p-%d" % i))
        for i in range(6)
    ]
    ok, _ = _bv(entries[:3]).verify()
    assert ok and counting_backend.sigs == 3
    # 3 cached + 3 new + 1 duplicate of a new one -> 3 lanes dispatched
    mixed = entries[:3] + entries[3:] + [entries[3]]
    ok, bits = _bv(mixed).verify()
    assert ok and bits == [True] * 7
    assert counting_backend.calls == 2
    assert counting_backend.sigs == 6, "only uncached unique triples dispatch"
    # invalid lane merges back into the right slot
    bad = (priv.pub_key().bytes(), b"p-bad", b"\x05" * 64)
    ok, bits = _bv([entries[0], bad, entries[4]]).verify()
    assert not ok and bits == [True, False, True]


# -- ISSUE 29: the seam decides a batch as whole columns ------------------------
#
# verify() takes a batch that is all unseen and distinct (`whole_miss`) or all
# cached (`whole_hit`) by the dict's own loops and walks only a `mixed` one
# triple by triple. `_walk_reference` is verify() as it stood before, the
# walk for every batch, word for word but for the spans: each case below must
# leave the same answer, the same columns at the backend and the same cache,
# entry for entry in the same order.


class RecordingBackend(CountingBackend):
    """CountingBackend that keeps the columns of every dispatch, and can run
    a writer of its own while the caller waits for the dispatch."""

    def __init__(self):
        super().__init__()
        self.columns = []
        self.meanwhile = None

    def batch_verify(self, pubs, msgs, sigs):
        self.columns.append((list(pubs), list(msgs), list(sigs)))
        if self.meanwhile is not None:
            self.meanwhile()
        return super().batch_verify(pubs, msgs, sigs)


@pytest.fixture
def recording_backend(monkeypatch):
    be = RecordingBackend()
    import cometbft_tpu.sidecar.backend as backend_mod

    monkeypatch.setattr(backend_mod, "get_backend", lambda: be)
    return be


def _walk_reference(entries, backend):
    keys = [(pub, sig, msg) for pub, msg, sig in entries]
    lane_of, lanes = {}, []
    sub_pubs, sub_msgs, sub_sigs = [], [], []
    for key in keys:
        if key in ed25519._verified:
            lanes.append(-1)
            continue
        lane = lane_of.get(key)
        if lane is None:
            lane = len(sub_pubs)
            lane_of[key] = lane
            sub_pubs.append(key[0])
            sub_msgs.append(key[2])
            sub_sigs.append(key[1])
        lanes.append(lane)
    if not sub_pubs:
        return True, [True] * len(keys)
    _, sub_bits = backend.batch_verify(sub_pubs, sub_msgs, sub_sigs)
    bits = [True if lane < 0 else sub_bits[lane] for lane in lanes]
    ed25519._verified_put_many(
        [k for k, lane in zip(keys, lanes) if lane >= 0 and sub_bits[lane]]
    )
    return all(bits), bits


_PRIV = ed25519.gen_priv_key_from_secret(b"issue-29")
_PUB = _PRIV.pub_key().bytes()


def _signed(tag: str, n: int):
    msgs = [b"%s-%d" % (tag.encode(), i) for i in range(n)]
    return [(_PUB, m, _PRIV.sign(m)) for m in msgs]


def _forged(entry):
    pub, msg, _ = entry
    return pub, msg, b"\x07" * 64


def _cases():
    """name -> (cache cap, triples cached beforehand, the batch, the case it is)"""
    old, new = _signed("old", 6), _signed("new", 11)
    return {
        "whole_miss": (64, old, new[:5], "whole_miss"),
        "whole_hit": (64, old, old[1:5], "whole_hit"),
        "whole_hit_with_repeats": (64, old, old[1:4] + old[2:3], "whole_hit"),
        "part_hit": (64, old, old[:2] + new[:3] + old[4:5], "mixed"),
        "repeats_of_a_miss": (64, old, new[:3] + new[1:2] + new[3:4], "mixed"),
        "part_hit_with_repeats": (64, old, new[:2] + old[:1] + new[1:3], "mixed"),
        "failed_lane_in_a_whole_miss": (
            64, old, new[:2] + [_forged(new[2])] + new[3:5], "whole_miss"),
        "failed_lane_in_a_mixed_batch": (
            64, old, old[:1] + new[:2] + [_forged(new[2])], "mixed"),
        "only_failed_lanes": (64, old, [_forged(new[0]), _forged(new[1])], "whole_miss"),
        # cap 8, so a quarter is 2: six cached and a batch of 3, 5 and 11
        "more_than_a_quarter": (8, old, new[:3], "whole_miss"),
        "fills_the_cap_exactly": (8, old, new[:2], "whole_miss"),
        "two_sweeps": (8, old, new[:5], "whole_miss"),
        "more_than_the_whole_cache": (8, old, new, "whole_miss"),
        "mixed_and_more_than_the_whole_cache": (8, old, old[4:] + new, "mixed"),
        "more_than_an_empty_cache": (8, [], new, "whole_miss"),
    }


CASES = _cases()


def _prepare(monkeypatch, cap, cached):
    monkeypatch.setattr(ed25519, "_VERIFIED_MAX", cap)
    ed25519._verified.clear()
    ed25519._verified_put_many([(p, s, m) for p, m, s in cached])


@pytest.mark.parametrize("name", CASES)
def test_every_case_leaves_what_the_walk_leaves(name, recording_backend, monkeypatch):
    cap, cached, batch, path = CASES[name]
    _prepare(monkeypatch, cap, cached)
    before = ed25519.verified_cache_counters()
    got = _bv(batch).verify()
    after = ed25519.verified_cache_counters()
    got_columns, got_cache = recording_backend.columns, list(ed25519._verified)
    recording_backend.columns = []
    _prepare(monkeypatch, cap, cached)
    want = _walk_reference(batch, recording_backend)
    assert got == want
    assert got_columns == recording_backend.columns
    assert got_cache == list(ed25519._verified)
    assert len(got_cache) <= cap
    grew = {k: after[k] - before[k] for k in before}
    assert [p for p in ("whole_miss", "whole_hit", "mixed") if grew[p]] == [path]
    assert grew[path] == 1
    assert grew["entries"] == len(batch) == grew["hits"] + grew["dups"] + grew["dispatched"]
    assert grew["dispatched"] == sum(len(c[0]) for c in got_columns)
    assert not {(p, s, m) for (p, m, s), ok in zip(batch, got[1]) if not ok} & set(got_cache)


def test_a_whole_miss_is_dispatched_as_the_columns_stand(recording_backend):
    batch = _signed("as-they-stand", 4)
    bv = _bv(batch)
    assert bv.verify() == (True, [True] * 4)
    assert recording_backend.columns == [(bv._pubs, bv._msgs, bv._sigs)]


@pytest.mark.parametrize("case", ["whole_miss", "mixed"])
def test_a_writer_during_the_dispatch_changes_nothing_and_its_triples_end_young(
    case, recording_backend
):
    """Membership is the filter's snapshot: triples another thread inserts
    while the batch is at the backend were still dispatched and answered by
    it, and the insert moves them to the young end like every other one."""
    old, new = _signed("w-old", 3), _signed("w-new", 6)
    ed25519._verified_put_many([(p, s, m) for p, m, s in old])
    batch = (old[:1] if case == "mixed" else []) + new
    other = _signed("w-other", 1)[0]
    raced = [new[4], other, new[1]]
    recording_backend.meanwhile = lambda: ed25519._verified_put_many(
        [(p, s, m) for p, m, s in raced])
    before = ed25519.verified_cache_counters()
    assert _bv(batch).verify() == (True, [True] * len(batch))
    grew = {k: v - before[k] for k, v in ed25519.verified_cache_counters().items()}
    assert recording_backend.columns == [tuple(list(c) for c in zip(*new))]
    assert grew[case] == 1 and grew["dispatched"] == 6 and grew["hits"] == len(batch) - 6
    assert grew["inserted"] == 7, "the batch's six and the writer's other one, each once"
    cache = list(ed25519._verified)
    assert cache[-6:] == [(p, s, m) for p, m, s in new], "batch order, at the young end"
    assert cache.index((other[0], other[2], other[1])) < len(cache) - 6
    assert len(cache) == 3 + 6 + 1


def test_a_racing_writer_of_other_triples_leaves_the_bound_and_the_order(
    recording_backend, monkeypatch
):
    monkeypatch.setattr(ed25519, "_VERIFIED_MAX", 8)
    old, new, others = _signed("r-old", 6), _signed("r-new", 3), _signed("r-other", 2)
    ed25519._verified_put_many([(p, s, m) for p, m, s in old])
    recording_backend.meanwhile = lambda: ed25519._verified_put_many(
        [(p, s, m) for p, m, s in others])
    assert _bv(new).verify() == (True, [True] * 3)
    cache = list(ed25519._verified)
    assert len(cache) <= 8 and cache[-3:] == [(p, s, m) for p, m, s in new]
    assert (old[0][0], old[0][2], old[0][1]) not in cache, "oldest first"


def test_the_span_names_the_case_that_ran(recording_backend, tmp_path):
    import jax

    from cometbft_tpu.libs import trace

    old, new = _signed("span-old", 3), _signed("span-new", 3)
    ed25519._verified_put_many([(p, s, m) for p, m, s in old])
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    trace.clear()
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for batch in (new, old, old[:1] + new[:1] + _signed("span-more", 1)):
            assert _bv(batch).verify()[0]
    finally:
        jax.profiler.stop_trace()
    got = [s["attrs"] for s in trace.spans() if s["name"] == "batch.verify"]
    trace.clear()
    assert got == [
        {"entries": 3, "hits": 0, "dups": 0, "dispatched": 3, "evicted": 0, "path": "whole_miss"},
        {"entries": 3, "hits": 3, "dups": 0, "dispatched": 0, "evicted": 0, "path": "whole_hit"},
        {"entries": 3, "hits": 2, "dups": 0, "dispatched": 1, "evicted": 0, "path": "mixed"},
    ]


def test_racing_batches_keep_the_bound_and_the_counts(monkeypatch):
    """More threads than cores put whole-miss, whole-hit and mixed batches
    through a small cache at once: the bound holds whenever anyone looks, and
    every insert and eviction is counted once (size = inserted - evicted)."""
    import sys
    import threading
    import time

    import cometbft_tpu.sidecar.backend as backend_mod

    class AllValid:
        def batch_verify(self, pubs, msgs, sigs):
            time.sleep(0.0005)  # lets another thread write between filter and insert
            return True, [True] * len(pubs)

    monkeypatch.setattr(backend_mod, "get_backend", AllValid)
    monkeypatch.setattr(ed25519, "_VERIFIED_MAX", 64)
    before = ed25519.verified_cache_counters()
    shared = [(_PUB, b"shared-%d" % i, b"\x01" * 64) for i in range(24)]
    stop = time.monotonic() + 1.5
    over, errors, calls = [], [], []

    def worker(w: int):
        try:
            i = 0
            while time.monotonic() < stop:
                i += 1
                own = [(_PUB, b"own-%d-%d-%d" % (w, i, j), b"\x02" * 64) for j in range(20)]
                for batch in (own, shared[w % 4:][:12], own[:5] + shared[:6], own[:9]):
                    ok, bits = _bv(batch).verify()
                    calls.append(w)
                    if not ok or bits != [True] * len(batch):
                        errors.append((w, i, "answer"))
                    size = len(ed25519._verified)
                    if size > 64:
                        over.append(size)
        except Exception as e:  # reported below: a thread's exception is otherwise lost
            errors.append((w, repr(e)))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(w,)) for w in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors and not over
    grew = {k: v - before[k] for k, v in ed25519.verified_cache_counters().items()}
    assert grew["size"] == grew["inserted"] - grew["evicted"]
    assert grew["entries"] == grew["hits"] + grew["dups"] + grew["dispatched"]
    assert grew["whole_miss"] and grew["whole_hit"] and grew["mixed"]
    assert grew["whole_miss"] + grew["whole_hit"] + grew["mixed"] == len(calls)
