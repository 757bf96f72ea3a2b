"""Decoder robustness: adversarial bytes into every wire-facing decode path
must raise a clean error (ValueError/KeyError/struct.error family), never
crash the process, hang, or succeed silently. The reference fuzzes its
decoders continuously (test/fuzz/); this is the deterministic analog —
seeded random corpora plus structured mutations of valid encodings."""

import random

import pytest

from cometbft_tpu.abci import types as abci
from cometbft_tpu.abci import wire as abci_wire
from cometbft_tpu.types.block import Block, BlockID, Commit, CommitSig, Header, PartSetHeader, canonical_commit_sig
from cometbft_tpu.types.validator import Validator, canonical_validator
from cometbft_tpu.types.validator_set import ValidatorSet
from cometbft_tpu.types.vote import Vote
from cometbft_tpu.wire import proto as wire
from tests.test_wire_decode_walk import (
    counts,
    generic_commit,
    generic_set,
    grown,
    make_commit,
    make_set,
    outcome,
    walked_set,
)
from tests.test_wire_encode_once import PRIORITIES, _bytes, _int64, _msg

# Deliberately NOT TypeError: raw type confusion (indexing an int where
# bytes were expected) is exactly the crash class the wire getters guard
# against; a decoder raising TypeError on adversarial input is a bug.
_DecodeError = (ValueError, KeyError, IndexError, OverflowError)


def _corpus(seed: int, n: int = 300):
    rng = random.Random(seed)
    out = []
    for _ in range(n):
        size = rng.choice([0, 1, 2, 7, 33, 120, 1000])
        out.append(bytes(rng.getrandbits(8) for _ in range(size)))
    return out


def _mutations(valid: bytes, seed: int, n: int = 200):
    rng = random.Random(seed)
    out = []
    for _ in range(n):
        b = bytearray(valid)
        op = rng.randrange(3)
        if op == 0 and b:  # flip a byte
            i = rng.randrange(len(b))
            b[i] ^= 1 << rng.randrange(8)
        elif op == 1 and b:  # truncate
            del b[rng.randrange(len(b)) :]
        else:  # splice garbage
            i = rng.randrange(len(b) + 1)
            b[i:i] = bytes(rng.getrandbits(8) for _ in range(rng.randrange(1, 9)))
        out.append(bytes(b))
    return out


def _must_not_crash(decode, blobs):
    for blob in blobs:
        try:
            decode(blob)
        except _DecodeError:
            pass  # clean rejection (or clean partial parse) is the contract


def test_uvarint_decoder_rejects_garbage():
    for blob in _corpus(1):
        try:
            v, pos = wire.decode_uvarint(blob)
            assert 0 <= v < 1 << 64 and pos <= len(blob)
        except _DecodeError:
            pass


def test_decode_fields_never_crashes():
    _must_not_crash(wire.decode_fields, _corpus(2))


def test_vote_decode_fuzz():
    bid = BlockID(hash=b"\x01" * 32, part_set_header=PartSetHeader(total=2, hash=b"\x02" * 32))
    valid = Vote(type=2, height=9, round=1, block_id=bid,
                 validator_address=b"\x03" * 20, validator_index=4,
                 signature=b"s" * 64).encode()
    Vote.decode(valid)  # sanity: the seed input itself parses
    _must_not_crash(Vote.decode, _corpus(3))
    _must_not_crash(Vote.decode, _mutations(valid, 4))


def test_header_and_commit_decode_fuzz():
    h = Header(height=3, chain_id="fuzz")
    _must_not_crash(Header.decode, _corpus(5))
    _must_not_crash(Header.decode, _mutations(h.encode(), 6))
    c = Commit(height=3, round=0,
               block_id=BlockID(hash=b"\x01" * 32,
                                part_set_header=PartSetHeader(total=1, hash=b"\x02" * 32)))
    _must_not_crash(Commit.decode, _corpus(7))
    _must_not_crash(Commit.decode, _mutations(c.encode(), 8))


def test_abci_request_decode_fuzz():
    valid = abci_wire.encode_request(abci.RequestCheckTx(tx=b"k=v"))
    abci_wire.decode_request(valid)
    _must_not_crash(abci_wire.decode_request, _corpus(9))
    _must_not_crash(abci_wire.decode_request, _mutations(valid, 10))


def test_abci_response_decode_fuzz():
    valid = abci_wire.encode_response(
        abci.ResponseCheckTx(code=1, data=b"d", log="l")
    )
    abci_wire.decode_response(valid)
    _must_not_crash(abci_wire.decode_response, _corpus(11))
    _must_not_crash(abci_wire.decode_response, _mutations(valid, 12))


def test_block_decode_fuzz():
    blk = Block(header=Header(height=1, chain_id="fz"))
    _must_not_crash(Block.decode, _corpus(13))
    _must_not_crash(Block.decode, _mutations(blk.encode(), 14))


def test_genesis_json_fuzz():
    from cometbft_tpu.crypto import ed25519
    from cometbft_tpu.types.genesis import GenesisDoc, GenesisValidator
    from cometbft_tpu.types import cmttime

    # sanity: a valid doc round-trips (so the fuzz below exercises the real
    # parser, not a missing attribute)
    pv = ed25519.gen_priv_key_from_secret(b"genesis-fuzz")
    doc = GenesisDoc(
        chain_id="fz", genesis_time=cmttime.now(),
        validators=[GenesisValidator(pv.pub_key().address(), pv.pub_key(), 1, "v")],
    )
    doc.validate_and_complete()
    assert GenesisDoc.from_json(doc.to_json()).chain_id == "fz"

    corpora = _corpus(15, 150)
    # structured junk: valid JSON with wrong shapes
    corpora += [b"{}", b"[]", b"null", b'{"validators": 3}',
                b'{"chain_id": "x", "validators": [{"pub_key": {"type": "nope", "value": "!!"}}]}']
    for blob in corpora:
        try:
            GenesisDoc.from_json(blob.decode("utf-8", "replace"))
        except _DecodeError + (AttributeError,):
            # AttributeError only for JSON whose shape is wrong at the top
            # level (e.g. a list where a dict is expected)
            pass


def test_fuzz_decoders_do_not_accept_bitflipped_signatures():
    """A flipped byte anywhere in an encoded vote must either fail decode or
    produce a vote whose signature check fails — never verify."""
    from cometbft_tpu.crypto import ed25519

    priv = ed25519.gen_priv_key_from_secret(b"fuzz-vote")
    bid = BlockID(hash=b"\x01" * 32, part_set_header=PartSetHeader(total=2, hash=b"\x02" * 32))
    v = Vote(type=2, height=9, round=1, block_id=bid,
             validator_address=priv.pub_key().address(), validator_index=0)
    v = v.with_signature(priv.sign(v.sign_bytes("fuzz-chain")))
    valid = v.encode()
    rng = random.Random(16)
    for _ in range(150):
        b = bytearray(valid)
        i = rng.randrange(len(b))
        bit = 1 << rng.randrange(8)
        b[i] ^= bit
        try:
            mutated = Vote.decode(bytes(b))
        except _DecodeError:
            continue
        if mutated == v:  # flip landed in unparsed padding; irrelevant
            continue
        verified = True
        try:
            mutated.verify("fuzz-chain", priv.pub_key())
        except Exception:
            verified = False
        assert not verified, f"bit flip at byte {i} still verifies"


# -- the canonical walk (Commit.decode, ValidatorSet.decode) -------------------
#
# A record in the encoder's own form is read by one walk, any other by the
# generic decoder; whatever the bytes, the result must be the generic
# decoder's: equal objects, or the same exception class.

_WALKS = {
    "commit_sig": (canonical_commit_sig, CommitSig.decode),
    "validator": (canonical_validator, Validator.decode),
}


@pytest.fixture(scope="module")
def walk_inputs():
    """A 256-signature commit of every flag kind and a 256-validator set
    whose priorities are zero, positive and negative (ten-byte varints)."""
    vals = make_set(256)
    for i, v in enumerate(vals.validators):
        v.proposer_priority = PRIORITIES[i % len(PRIORITIES)]
    return make_commit(256), vals


def _walk_records(kind, walk_inputs) -> list:
    c, vals = walk_inputs
    if kind == "commit_sig":
        return c.sig_records()[1::2]
    return [v.encode() for v in vals.validators] + [vals.proposer.encode()]


@pytest.mark.parametrize("kind", sorted(_WALKS))
def test_a_mutated_record_decodes_as_the_generic_decoder_reads_it(kind, walk_inputs):
    walk, decode = _WALKS[kind]
    records = _walk_records(kind, walk_inputs)
    blobs = _corpus(17, 150) + [m for i, r in enumerate(records) for m in _mutations(r, 1000 + i, 6)]
    walked = 0
    for blob in blobs:
        want = outcome(decode, blob)
        assert outcome(lambda b: wire.decode_records([b], walk, decode)[0][0], blob) == want
        try:
            got = walk(blob)
        except (IndexError, ValueError):
            continue
        # the walk takes nothing but the encoder's own bytes
        assert got == want and got.encode() == blob
        walked += 1
    assert 0 < walked < len(blobs)


@pytest.mark.parametrize("kind", ["commit", "set"])
def test_a_mutated_commit_or_set_decodes_as_the_generic_decoder_reads_it(kind, walk_inputs):
    c, vals = walk_inputs
    data, new, generic = (
        (c.encode(), Commit.decode, generic_commit) if kind == "commit" else (vals.encode(), walked_set, generic_set)
    )
    assert outcome(new, data) == outcome(generic, data)
    for blob in _corpus(18, 100) + _mutations(data, 19, 40):
        assert outcome(new, blob) == outcome(generic, blob)


_FORMS = ("reordered", "repeated", "unknown_field", "non_minimal_varint", "non_minimal_length")


def _sig_forms(cs) -> dict:
    """One CommitSig's record in forms the encoder never writes; the generic
    decoder reads each as `cs`."""
    t = cs.timestamp
    flag, ts = _int64(1, cs.block_id_flag), _msg(3, _int64(1, t.seconds) + _int64(2, t.nanos))
    addr, sig = _bytes(2, cs.validator_address), _bytes(4, cs.signature)
    return {
        "reordered": sig + ts + addr + flag,
        "repeated": flag + addr + addr + ts + sig,
        "unknown_field": flag + addr + ts + sig + b"\x48\x05",
        "non_minimal_varint": b"\x08" + bytes([cs.block_id_flag | 0x80, 0]) + addr + ts + sig,
        "non_minimal_length": flag + b"\x12\x94\x00" + cs.validator_address + ts + sig,
        "time_reordered": flag + addr + _msg(3, _int64(2, t.nanos) + _int64(1, t.seconds)) + sig,
    }


def _validator_forms(v) -> dict:
    """One Validator's record in forms the encoder never writes; the generic
    decoder reads each as `v`."""
    key = _msg(2, _msg(1, v.pub_key.bytes()))
    addr, power, prio = _bytes(1, v.address), _int64(3, v.voting_power), _int64(4, v.proposer_priority)
    return {
        "reordered": prio + power + key + addr,
        "repeated": addr + key + power + power + prio,
        "unknown_field": addr + key + power + prio + b"\x48\x05",
        "non_minimal_varint": addr + key + b"\x18" + bytes([v.voting_power | 0x80, 0]) + prio,
        "non_minimal_length": b"\x0a\x94\x00" + v.address + key + power + prio,
        "key_field_other_form": addr + _msg(2, _msg(1, v.pub_key.bytes()) + b"\x48\x05") + power + prio,
    }


@pytest.mark.parametrize("form", _FORMS + ("time_reordered",))
def test_a_commit_sig_in_another_form_goes_to_the_generic_decoder(form, walk_inputs):
    c, _ = walk_inputs
    records = list(c.sig_records())
    body = _sig_forms(c.signatures[0])[form]
    assert body != records[1]
    records[0:2] = [b"\x22" + wire.encode_uvarint(len(body)), body]
    data = c.encode()
    odd = data[: len(data) - len(b"".join(c.sig_records()))] + b"".join(records)
    before = counts()
    assert Commit.decode(odd) == c == generic_commit(odd)
    assert grown(before) == {
        "sig_records_walked": 255, "sig_records_generic": 1,
        "validator_records_walked": 0, "validator_records_generic": 0,
    }


@pytest.mark.parametrize("form", _FORMS + ("key_field_other_form",))
def test_a_validator_in_another_form_goes_to_the_generic_decoder(form, walk_inputs):
    _, vals = walk_inputs
    target = next(v for v in vals.validators if 0 < v.voting_power < 0x80 and v.proposer_priority)
    body = _validator_forms(target)[form]
    assert body != target.encode()
    data = b"".join(_msg(1, body if v is target else v.encode()) for v in vals.validators)
    data += _msg(2, vals.proposer.encode()) + _int64(3, vals.total_voting_power())
    before = counts()
    assert walked_set(data) == (vals.validators, vals.proposer) == generic_set(data)
    assert grown(before) == {
        "sig_records_walked": 0, "sig_records_generic": 0,
        "validator_records_walked": 256, "validator_records_generic": 1,
    }
    assert ValidatorSet.decode(data).encode() == vals.encode()
