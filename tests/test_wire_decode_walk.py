"""Commit.decode and ValidatorSet.decode read each signature and validator
record in one walk where the record is in the form the program's encoder
writes, and hand any other record to CommitSig.decode / Validator.decode.

The walk may only ever return what the generic decoder returns for the same
bytes: these tests decode each input both ways and ask for equal objects or
the same exception class, and read the counters that say which path each
record took (`wire_counters()`, `/metrics` `wire_*`)."""

from __future__ import annotations

import hashlib
import random

import pytest

from cometbft_tpu.crypto import bn254, ed25519, secp256k1
from cometbft_tpu.types import validator_set as vs_mod
from cometbft_tpu.types.block import (
    BLOCK_ID_FLAG_COMMIT,
    BLOCK_ID_FLAG_NIL,
    BlockID,
    Commit,
    CommitSig,
    PartSetHeader,
    canonical_commit_sig,
)
from cometbft_tpu.types.cmttime import GO_ZERO_SECONDS, Time
from cometbft_tpu.types.validator import Validator, canonical_validator
from cometbft_tpu.types.validator_set import INT64_MAX, INT64_MIN, ValidatorSet
from cometbft_tpu.wire import proto as wire

WALK_KEYS = (
    "sig_records_walked", "sig_records_generic",
    "validator_records_walked", "validator_records_generic",
)


# -- the generic decoders, record by record ------------------------------------


def generic_commit(data: bytes) -> Commit:
    f = wire.decode_fields(data)
    return Commit(
        height=wire.get_varint(f, 1),
        round=wire.get_varint(f, 2),
        block_id=BlockID.decode(wire.get_bytes(f, 3)),
        signatures=[CommitSig.decode(r) for r in wire.get_repeated_bytes(f, 4)],
        agg_signature=wire.get_bytes(f, 5),
        agg_bitmap=wire.get_bytes(f, 6),
    )


def generic_set(data: bytes) -> tuple:
    f = wire.decode_fields(data)
    vals = [Validator.decode(r) for r in wire.get_repeated_bytes(f, 1)]
    proposer = Validator.decode(wire.get_bytes(f, 2)) if 2 in f else None
    return vals, proposer


def walked_set(data: bytes) -> tuple:
    vs = ValidatorSet.decode(data)
    return vs.validators, vs.proposer


def outcome(decode, data):
    """What `decode` gives for `data`: its result, or its exception's class."""
    try:
        return decode(data)
    except Exception as e:  # noqa: BLE001 - the class is what is compared
        return type(e)


def counts() -> dict:
    c = vs_mod.wire_counters()
    return {k: c[k] for k in WALK_KEYS}


def grown(before: dict) -> dict:
    after = counts()
    return {k: after[k] - before[k] for k in WALK_KEYS}


# -- data ----------------------------------------------------------------------


def make_set(n: int, seed: int = 11) -> ValidatorSet:
    rnd = random.Random(seed)
    return ValidatorSet([Validator.new(ed25519.PubKey(rnd.randbytes(32)), rnd.choice((1, 10, 2**30))) for _ in range(n)])


def make_commit(n: int, seed: int = 5, times=None) -> Commit:
    """n signatures of every flag kind in turn: commit, nil, absent."""
    rnd = random.Random(seed)
    sigs = []
    for i in range(n):
        at = times[i % len(times)] if times else Time(1_700_000_000 + i % 3, rnd.randrange(10**9))
        kind = i % 3
        if kind == 2:
            sigs.append(CommitSig.absent())
        else:
            flag = BLOCK_ID_FLAG_COMMIT if kind == 0 else BLOCK_ID_FLAG_NIL
            sigs.append(CommitSig(flag, rnd.randbytes(20), at, rnd.randbytes(64)))
    bid = BlockID(rnd.randbytes(32), PartSetHeader(rnd.randrange(1, 9), rnd.randbytes(32)))
    return Commit(height=7, round=2, block_id=bid, signatures=sigs)


# -- canonical input: every record walks ---------------------------------------


def test_a_canonical_commit_of_every_flag_kind_walks_every_record():
    c = make_commit(1024)
    data = c.encode()
    before = counts()
    back = Commit.decode(data)
    assert grown(before) == dict.fromkeys(WALK_KEYS, 0) | {"sig_records_walked": 1024}
    assert back == c == generic_commit(data)
    assert {cs.block_id_flag for cs in back.signatures} == {1, 2, 3}
    assert back.encode() == data


TIMES = [
    Time(0, 0),  # an empty Timestamp
    Time(GO_ZERO_SECONDS, 0),  # an absent signature's time: ten bytes of seconds
    Time(-1, 0),
    Time(1_700_000_000, 0),
    Time(1_700_000_000, 999_999_999),
    Time(0, 999_999_999),
    Time(GO_ZERO_SECONDS, 999_999_999),
    Time(INT64_MAX, 1),
    Time(INT64_MIN, 0),
    Time(5, -1),  # nanos read as int64, as Time.decode reads them
]


@pytest.mark.parametrize("at", TIMES, ids=lambda t: f"{t.seconds}s{t.nanos}n")
def test_a_time_is_read_as_the_generic_decoder_reads_it(at):
    c = make_commit(6, times=[at])
    data = c.encode()
    before = counts()
    back = Commit.decode(data)
    assert grown(before)["sig_records_walked"] == 6
    assert back == c == generic_commit(data)
    assert all(cs.timestamp == at for cs in back.signatures if not cs.is_absent())
    for record in c.sig_records()[1::2]:
        assert canonical_commit_sig(record) == CommitSig.decode(record)


def _keys():
    ed = ed25519.gen_priv_key_from_secret(b"walk-ed").pub_key()
    secp = secp256k1.gen_priv_key_from_secret(b"walk-secp").pub_key()
    bn = bn254.PrivKey(hashlib.sha256(b"walk-bn254").digest()).pub_key()
    return {"ed25519": ed, "secp256k1": secp, "bn254": bn}


@pytest.mark.parametrize("proposer", [True, False], ids=["proposer", "no-proposer"])
@pytest.mark.parametrize("priority", [0, -7, INT64_MIN, 2**40, INT64_MAX])
def test_a_set_of_every_key_type_is_read_as_the_generic_decoder_reads_it(priority, proposer):
    keys = _keys()
    vals = ValidatorSet([Validator.new(k, 10 + i) for i, k in enumerate(keys.values())] + make_set(5).validators)
    for v in vals.validators:
        v.proposer_priority = priority
    if not proposer:
        vals.proposer = None
    data = vals.encode()
    before = counts()
    back = walked_set(data)
    assert back == generic_set(data)
    assert back[0] == vals.validators and back[1] == vals.proposer
    assert {v.pub_key.type() for v in back[0]} == set(keys)
    # the two keys of other types go to Validator.decode, each record alone
    ed_proposer = proposer and vals.proposer.pub_key.type() == "ed25519"
    assert grown(before) == dict.fromkeys(WALK_KEYS, 0) | {
        "validator_records_walked": 6 + ed_proposer,
        "validator_records_generic": 2 + (proposer and not ed_proposer),
    }
    assert ValidatorSet.decode(data).encode() == data


def test_the_walk_builds_the_key_that_the_generic_decoder_builds():
    v = make_set(1).validators[0]
    v.proposer_priority = -3
    record = v.encode()
    walked, generic = canonical_validator(record), Validator.decode(record)
    assert walked == generic == v
    assert type(walked.pub_key) is ed25519.PubKey and walked.pub_key.bytes() == v.pub_key.bytes()
    assert walked.pub_key.address() == walked.address


# -- the counters --------------------------------------------------------------


def test_the_counters_count_every_record_and_one_mutated_record_moves_one_count():
    c = make_commit(1024)
    vals = make_set(4096)
    commit_data, set_data = c.encode(), vals.encode()
    before = counts()
    Commit.decode(commit_data)
    ValidatorSet.decode(set_data)
    # a set's records are its 4,096 entries and its proposer
    assert grown(before) == {
        "sig_records_walked": 1024, "sig_records_generic": 0,
        "validator_records_walked": 4096 + 1, "validator_records_generic": 0,
    }
    # one signature record whose flag is written in two bytes (0x82 0x00)
    records = list(c.sig_records())
    body = records[1]
    assert body[:2] == b"\x08\x02"
    odd = b"\x08\x82\x00" + body[2:]
    records[0:2] = [b"\x22" + wire.encode_uvarint(len(odd)), odd]
    head = commit_data[: len(commit_data) - len(b"".join(c.sig_records()))]
    before = counts()
    assert Commit.decode(head + b"".join(records)) == c
    assert grown(before) == dict.fromkeys(WALK_KEYS, 0) | {"sig_records_walked": 1023, "sig_records_generic": 1}


def test_the_walk_counters_reach_metrics():
    import types

    from cometbft_tpu.libs.metrics import Registry
    from cometbft_tpu.node.node import Node

    reg = Registry(namespace="cmt")
    Node._register_hotpath_metrics(types.SimpleNamespace(blocksync_reactor=None), reg)
    Commit.decode(make_commit(9).encode())
    ValidatorSet.decode(make_set(9).encode())
    out = reg.render()
    now = counts()
    for key in WALK_KEYS:
        assert f"cmt_wire_{key} {now[key]}\n" in out, key
    assert now["sig_records_walked"] >= 9 and now["validator_records_walked"] >= 10
