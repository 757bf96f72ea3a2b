"""A validator set's and a commit's wire bytes, built once and shared by
copies, against an encoder that is not the program's.

The reference below is written field by field from the .proto files
(tendermint.types.Validator, ValidatorSet, SimpleValidator, CommitSig,
Commit, google.protobuf.Timestamp) and imports nothing of cometbft_tpu's
encoders; `benchmarks/reference/block_proto.py` and `rfc6962.py`, which
import nothing of the program either, are a second yardstick for a commit
and the Merkle roots. The memo tests check that what copies share is only
served while it is still true."""

from __future__ import annotations

import copy
import os
import random
import sys

import pytest

from cometbft_tpu.crypto import ed25519, secp256k1
from cometbft_tpu.types import validator_set as vs_mod
from cometbft_tpu.types.block import (
    BLOCK_ID_FLAG_ABSENT,
    BLOCK_ID_FLAG_COMMIT,
    BLOCK_ID_FLAG_NIL,
    BlockID,
    Commit,
    CommitSig,
    PartSetHeader,
)
from cometbft_tpu.types.cmttime import GO_ZERO_SECONDS, Time
from cometbft_tpu.types.validator import Validator
from cometbft_tpu.types.validator_set import (
    INT64_MAX,
    INT64_MIN,
    MAX_TOTAL_VOTING_POWER,
    ValidatorSet,
)

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")

# -- the reference: plain protobuf, field by field ---------------------------

_KEY_FIELD = {"ed25519": 1, "secp256k1": 2, "bn254": 3, "sr25519": 4}


def _uvarint(n: int) -> bytes:
    out = bytearray()
    while n > 0x7F:
        out.append(n & 0x7F | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def _int64(field: int, n: int) -> bytes:
    """int64 scalar: absent when 0, two's complement on 64 bits."""
    return _uvarint(field << 3) + _uvarint(n & (2**64 - 1)) if n else b""


def _msg(field: int, body: bytes) -> bytes:
    """Length-delimited and always there (non-nullable, repeated)."""
    return _uvarint(field << 3 | 2) + _uvarint(len(body)) + body


def _bytes(field: int, value: bytes) -> bytes:
    return _msg(field, value) if value else b""


def ref_pub_key(pk) -> bytes:
    return _msg(_KEY_FIELD[pk.type()], pk.bytes())


def ref_validator(v) -> bytes:
    return (
        _bytes(1, v.address)
        + _msg(2, ref_pub_key(v.pub_key))
        + _int64(3, v.voting_power)
        + _int64(4, v.proposer_priority)
    )


def ref_set(vals: ValidatorSet, total: int | None = None) -> bytes:
    out = b"".join(_msg(1, ref_validator(v)) for v in vals.validators)
    if vals.proposer is not None:
        out += _msg(2, ref_validator(vals.proposer))
    if total is None:
        total = sum(v.voting_power for v in vals.validators)
    return out + _int64(3, total)


def ref_set_leaves(vals: ValidatorSet) -> list:
    """SimpleValidator{pub_key=1, voting_power=2}: the Merkle leaves."""
    return [_msg(1, ref_pub_key(v.pub_key)) + _int64(2, v.voting_power) for v in vals.validators]


def ref_commit_sig(cs) -> bytes:
    t = cs.timestamp
    return (
        _int64(1, cs.block_id_flag)
        + _bytes(2, cs.validator_address)
        + _msg(3, _int64(1, t.seconds) + _int64(2, t.nanos))
        + _bytes(4, cs.signature)
    )


def ref_commit(c) -> bytes:
    bid = c.block_id
    psh = bid.part_set_header
    out = (
        _int64(1, c.height)
        + _int64(2, c.round)
        + _msg(3, _bytes(1, bid.hash) + _msg(2, _int64(1, psh.total) + _bytes(2, psh.hash)))
        + b"".join(_msg(4, ref_commit_sig(cs)) for cs in c.signatures)
    )
    if c.agg_signature:
        out += _bytes(5, c.agg_signature) + _bytes(6, c.agg_bitmap)
    return out


@pytest.fixture(scope="module")
def bench_ref():
    sys.path.insert(0, BENCH)
    try:
        from reference import block_proto, rfc6962

        yield block_proto, rfc6962
    finally:
        sys.path.remove(BENCH)


# -- data --------------------------------------------------------------------

PRIORITIES = (0, 5, -7, 300, -(2**40), INT64_MIN, INT64_MAX)


def _key(rnd: random.Random):
    return ed25519.PubKey(rnd.randbytes(32))


def make_set(n: int, seed: int = 7, powers=None) -> ValidatorSet:
    rnd = random.Random(seed)
    powers = powers or [rnd.choice((1, 1, 10, 1000, 2**30)) for _ in range(n)]
    return ValidatorSet([Validator.new(_key(rnd), p) for p in powers])


def spread_priorities(vals: ValidatorSet) -> None:
    """Zero, positive and negative priorities (ten-byte varints) in one set:
    priority is the one field a copy may change without a new holder."""
    for i, v in enumerate(vals.validators):
        v.proposer_priority = PRIORITIES[i % len(PRIORITIES)]


def make_commit(vals: ValidatorSet, seed: int = 3, height: int = 9, round_: int = 1) -> Commit:
    rnd = random.Random(seed)
    sigs = []
    for i, v in enumerate(vals.validators):
        kind = i % 5
        if kind == 3:
            sigs.append(CommitSig.absent())
        elif kind == 4:
            sigs.append(CommitSig(BLOCK_ID_FLAG_NIL, v.address, Time(1_700_000_000 + i, 0), rnd.randbytes(64)))
        else:
            at = Time(1_700_000_000 + i % 2, rnd.randrange(10**9))
            sigs.append(CommitSig.for_block(v.address, at, rnd.randbytes(64)))
    bid = BlockID(rnd.randbytes(32), PartSetHeader(rnd.randrange(1, 300), rnd.randbytes(32)))
    return Commit(height=height, round=round_, block_id=bid, signatures=sigs)


def _plain_commit(c) -> dict:
    bid = c.block_id
    return {
        "height": c.height,
        "round": c.round,
        "block_id": (bid.hash, bid.part_set_header.total, bid.part_set_header.hash),
        "signatures": [
            (cs.block_id_flag, cs.validator_address, (cs.timestamp.seconds, cs.timestamp.nanos), cs.signature)
            for cs in c.signatures
        ],
    }


# -- byte identity -----------------------------------------------------------


@pytest.mark.parametrize("power", [1, 10**6, MAX_TOTAL_VOTING_POWER, INT64_MAX])
@pytest.mark.parametrize("priority", PRIORITIES)
def test_validator_encode_is_the_reference(power, priority):
    pk = _key(random.Random(power ^ priority))
    v = Validator(pk.address(), pk, power, priority)
    assert v.encode() == ref_validator(v)
    assert Validator.decode(v.encode()) == v


def test_validator_encode_with_an_empty_address_and_other_key_types():
    pk = secp256k1.gen_priv_key().pub_key()
    for v in (Validator(b"", pk, 3, -1), Validator(pk.address(), pk, 0, 0)):
        assert v.encode() == ref_validator(v)


@pytest.mark.parametrize("n", [1, 175, 1024])
def test_set_encode_and_root_are_the_reference(n, bench_ref):
    _, rfc6962 = bench_ref
    vals = make_set(n)
    assert vals.encode() == ref_set(vals)
    spread_priorities(vals)
    assert vals.encode() == ref_set(vals)
    assert vals.hash() == rfc6962.root(ref_set_leaves(vals))
    twin = vals.copy()
    assert twin.encode() == ref_set(twin) == vals.encode()
    assert twin.hash() == vals.hash()
    back = ValidatorSet.decode(vals.encode())
    assert back.encode() == vals.encode() and back.hash() == vals.hash()


def test_set_at_the_maximum_power_and_without_a_proposer(bench_ref):
    _, rfc6962 = bench_ref
    one = make_set(1, powers=[MAX_TOTAL_VOTING_POWER])
    assert one.encode() == ref_set(one)
    assert one.hash() == rfc6962.root(ref_set_leaves(one))
    vals = make_set(175)
    vals.proposer = None
    assert vals.encode() == ref_set(vals)
    assert ValidatorSet().encode() == b""
    mixed = ValidatorSet(
        [Validator.new(secp256k1.gen_priv_key().pub_key(), 4)] + make_set(6).validators
    )
    assert mixed.encode() == ref_set(mixed)
    assert mixed.hash() == rfc6962.root(ref_set_leaves(mixed))


@pytest.mark.parametrize(
    "cs",
    [
        CommitSig.absent(),
        CommitSig(BLOCK_ID_FLAG_ABSENT, b"", Time(0, 0), b""),
        CommitSig(BLOCK_ID_FLAG_NIL, b"\x01" * 20, Time(1_700_000_000, 5), b"\x02" * 64),
        CommitSig(BLOCK_ID_FLAG_COMMIT, b"\x03" * 20, Time(1_700_000_000, 999_999_999), b"\x04" * 64),
        CommitSig(BLOCK_ID_FLAG_COMMIT, b"\x05" * 20, Time(-5, 7), b""),
        CommitSig(BLOCK_ID_FLAG_COMMIT, b"\x06" * 20, Time(GO_ZERO_SECONDS, 1), b"\x07" * 128),
        CommitSig(0, b"", Time(1, 0), b"\x08" * 200),
    ],
    ids=["absent", "absent-unix-epoch", "nil", "commit", "commit-no-sig-negative-time",
         "zero-time-128-byte-sig", "flag-zero-long-sig"],
)
def test_commit_sig_encode_is_the_reference(cs):
    assert cs.encode() == ref_commit_sig(cs)
    assert CommitSig.decode(cs.encode()) == cs


@pytest.mark.parametrize("n", [1, 175, 1024])
def test_commit_encode_and_hash_are_the_reference(n, bench_ref):
    block_proto, rfc6962 = bench_ref
    c = make_commit(make_set(n))
    if n > 1:
        flags = {cs.block_id_flag for cs in c.signatures}
        assert flags == {BLOCK_ID_FLAG_ABSENT, BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_NIL}
    want = ref_commit(c)
    assert want == block_proto.commit(_plain_commit(c))
    assert c.encode() == want
    assert c.encode() == want  # the kept bytes
    assert c.hash() == rfc6962.root([ref_commit_sig(cs) for cs in c.signatures])
    fresh = Commit.decode(want)
    assert fresh.hash() == c.hash()  # leaves first, then the encoding
    assert fresh.encode() == want


def test_aggregate_commit_fields_five_and_six_are_the_reference(bench_ref):
    _, rfc6962 = bench_ref
    c = make_commit(make_set(13))
    stripped = [
        cs if cs.is_absent() else CommitSig(cs.block_id_flag, cs.validator_address, cs.timestamp, b"")
        for cs in c.signatures
    ]
    agg = Commit(c.height, c.round, c.block_id, stripped, agg_signature=b"\x09" * 64, agg_bitmap=b"\xf7\x1b")
    assert agg.encode() == ref_commit(agg)
    assert agg.hash() == rfc6962.root([ref_commit_sig(cs) for cs in stripped])
    assert Commit.decode(agg.encode()) == agg
    no_bitmap = Commit(c.height, c.round, c.block_id, stripped, agg_signature=b"\x0a" * 128)
    assert no_bitmap.encode() == ref_commit(no_bitmap)
    empty = Commit()
    assert empty.encode() == ref_commit(empty) and empty.hash() == rfc6962.root([])


# -- memo validity -------------------------------------------------------------


def test_a_copy_shares_heads_and_root_and_encodes_like_a_fresh_set():
    vals = make_set(175)
    vals.encode()
    root = vals.hash()
    before = vs_mod.wire_counters()
    nxt = vals.copy()
    nxt.increment_proposer_priority(3)
    assert nxt._memo is vals._memo
    assert nxt.hash() is root
    fresh = ValidatorSet.decode(nxt.encode())  # a holder of its own
    assert fresh._memo is not nxt._memo
    assert nxt.encode() == ref_set(nxt) == fresh.encode()
    assert nxt.encode() != vals.encode()  # the priorities moved
    after = vs_mod.wire_counters()
    assert after["heads_built"] - before["heads_built"] == 1  # the decoded set's
    assert after["roots_built"] == before["roots_built"]


@pytest.mark.parametrize("change", ["power", "addition", "removal"])
def test_a_change_set_gives_new_heads_and_a_new_root(change, bench_ref):
    _, rfc6962 = bench_ref
    vals = make_set(40)
    vals.encode()
    old_root = vals.hash()
    nxt = vals.copy()
    target = nxt.validators[5]
    if change == "power":
        update = Validator(target.address, target.pub_key, target.voting_power + 17, 0)
    elif change == "addition":
        update = Validator.new(_key(random.Random(99)), 50)
    else:
        update = Validator(target.address, target.pub_key, 0, 0)
    before = vs_mod.wire_counters()
    nxt.update_with_change_set([update])
    assert nxt._memo is not vals._memo
    assert nxt.encode() == ref_set(nxt)
    assert nxt.hash() == rfc6962.root(ref_set_leaves(nxt)) != old_root
    assert vals.hash() is old_root and vals.encode() == ref_set(vals)
    after = vs_mod.wire_counters()
    assert after["heads_built"] - before["heads_built"] == 1
    assert after["roots_built"] - before["roots_built"] == 1


@pytest.mark.parametrize("field", ["voting_power", "pub_key", "address"])
def test_an_entry_changed_in_place_is_encoded_and_hashed_afresh(field, bench_ref):
    _, rfc6962 = bench_ref
    vals = make_set(30)
    twin = vals.copy()
    assert vals.encode() == twin.encode()
    root = vals.hash()
    v = twin.validators[4]
    if field == "voting_power":
        v.voting_power += 1
    elif field == "pub_key":
        v.pub_key = _key(random.Random(5))
    else:
        v.address = b"\xee" * 20
    assert twin._memo is vals._memo
    # the total stays the set's own count until a change set recounts it
    assert twin.encode() == ref_set(twin, vals.total_voting_power()) != vals.encode()
    assert twin.hash() == rfc6962.root(ref_set_leaves(twin))
    assert (twin.hash() != root) == (field != "address")  # the address is no leaf's
    assert vals.encode() == ref_set(vals) and vals.hash() is root


def test_a_commit_from_non_canonical_bytes_encodes_canonically():
    c = make_commit(make_set(9))
    canonical = c.encode()

    def rev_sig(cs):
        # fields in reverse order, and a zero nanos sent where proto3 omits it
        t = cs.timestamp
        ts = _int64(2, t.nanos) + _int64(1, t.seconds) + (b"\x10\x00" if not t.nanos else b"")
        return _bytes(4, cs.signature) + _msg(3, ts) + _bytes(2, cs.validator_address) + _int64(1, cs.block_id_flag)

    bid = c.block_id
    odd = (
        b"".join(_msg(4, rev_sig(cs)) for cs in c.signatures)
        + _int64(2, c.round)
        + _msg(3, _msg(2, _bytes(2, bid.part_set_header.hash) + _int64(1, bid.part_set_header.total)) + _bytes(1, bid.hash))
        + _int64(1, c.height)
    )
    assert odd != canonical
    back = Commit.decode(odd)
    assert back == c
    assert back.encode() == canonical
    assert back.signatures[0].encode() == ref_commit_sig(c.signatures[0])


def test_a_commit_given_another_list_or_field_encodes_afresh():
    c = make_commit(make_set(12))
    first = c.encode()
    twin = copy.deepcopy(c)  # carries the memo
    twin.agg_signature, twin.agg_bitmap = b"\x01" * 64, b"\xff\x0f"
    assert twin.encode() == ref_commit(twin) != first
    other = copy.deepcopy(c)
    other.signatures = other.signatures[:-1]
    assert other.encode() == ref_commit(other)
    assert other.hash() == Commit.decode(other.encode()).hash()
    import dataclasses

    moved = dataclasses.replace(c, height=c.height + 1)
    assert moved.encode() == ref_commit(moved)
    assert c.encode() is first


def test_the_counters_count_one_build_and_the_reuses_of_a_commit():
    c = make_commit(make_set(64))
    before = vs_mod.wire_counters()
    a = c.encode()
    c.hash()
    b = c.encode()
    c.encode()
    after = vs_mod.wire_counters()
    assert a is b
    assert after["commit_records_built"] - before["commit_records_built"] == 1
    assert after["commit_encodes_reused"] - before["commit_encodes_reused"] == 2
    assert set(after) == {
        "heads_built", "heads_reused", "roots_built", "roots_reused",
        "commit_records_built", "commit_encodes_reused",
        "sig_records_walked", "sig_records_generic",
        "validator_records_walked", "validator_records_generic",
    }


def test_the_counters_count_heads_and_roots_of_a_chain_of_copies():
    vals = make_set(50)
    before = vs_mod.wire_counters()
    cur = vals
    for _ in range(5):  # as a node's state copies its sets every height
        nxt = cur.copy()
        nxt.increment_proposer_priority(1)
        assert nxt.encode() == ref_set(nxt)
        nxt.hash()
        cur = nxt
    after = vs_mod.wire_counters()
    delta = {k: after[k] - before[k] for k in ("heads_built", "heads_reused", "roots_built", "roots_reused")}
    assert delta == {"heads_built": 1, "heads_reused": 4, "roots_built": 1, "roots_reused": 4}


def test_a_blocks_part_set_and_the_stores_share_one_commit_encoding():
    from cometbft_tpu.types.block import Block, Data, Header

    vals = make_set(20)
    c = make_commit(vals)
    block = Block(Header(chain_id="wire", height=c.height + 1), Data(), [], c)
    before = vs_mod.wire_counters()
    parts = block.make_part_set()
    c.encode()  # the store's last commit
    c.encode()  # the seen commit
    after = vs_mod.wire_counters()
    assert after["commit_records_built"] - before["commit_records_built"] == 1
    assert after["commit_encodes_reused"] - before["commit_encodes_reused"] == 2
    assert ref_commit(c) in b"".join(parts.get_part(i).bytes for i in range(parts.total))


def test_the_wire_counters_reach_metrics():
    import types

    from cometbft_tpu.libs.metrics import Registry
    from cometbft_tpu.node.node import Node

    reg = Registry(namespace="cmt")
    Node._register_hotpath_metrics(types.SimpleNamespace(blocksync_reactor=None), reg)
    make_commit(make_set(8)).encode()
    out = reg.render()
    counts = vs_mod.wire_counters()
    for key in ("heads_built", "heads_reused", "roots_built", "roots_reused",
                "commit_records_built", "commit_encodes_reused"):
        assert f"cmt_wire_{key} {counts[key]}\n" in out, key
