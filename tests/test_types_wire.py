"""Wire/hash compatibility tests for the types layer.

Golden vectors lifted from the reference's own test expectations
(types/vote_test.go TestVoteSignBytesTestVectors, types/block_test.go
TestHeaderHash) prove bit-for-bit sign-bytes and hash compatibility.
"""

import calendar
import hashlib

from cometbft_tpu.types.block import (
    PRECOMMIT_TYPE,
    PREVOTE_TYPE,
    BlockID,
    Commit,
    CommitSig,
    Consensus,
    Header,
    PartSetHeader,
)
from cometbft_tpu.types.cmttime import Time
from cometbft_tpu.types.proposal import Proposal
from cometbft_tpu.types.vote import Vote


def _ts(s: bytes) -> bytes:
    return hashlib.sha256(s).digest()


GO_ZERO_TS = bytes(
    [0x2A, 0xB, 0x8, 0x80, 0x92, 0xB8, 0xC3, 0x98, 0xFE, 0xFF, 0xFF, 0xFF, 0x1]
)


class TestVoteSignBytesGoldenVectors:
    """types/vote_test.go:60-135."""

    def test_zero_vote(self):
        assert Vote().sign_bytes("") == bytes([0xD]) + GO_ZERO_TS

    def test_precommit(self):
        want = (
            bytes([0x21, 0x8, 0x2, 0x11]) + (1).to_bytes(8, "little")
            + bytes([0x19]) + (1).to_bytes(8, "little") + GO_ZERO_TS
        )
        assert Vote(height=1, round=1, type=PRECOMMIT_TYPE).sign_bytes("") == want

    def test_prevote(self):
        want = (
            bytes([0x21, 0x8, 0x1, 0x11]) + (1).to_bytes(8, "little")
            + bytes([0x19]) + (1).to_bytes(8, "little") + GO_ZERO_TS
        )
        assert Vote(height=1, round=1, type=PREVOTE_TYPE).sign_bytes("") == want

    def test_no_type(self):
        want = (
            bytes([0x1F, 0x11]) + (1).to_bytes(8, "little")
            + bytes([0x19]) + (1).to_bytes(8, "little") + GO_ZERO_TS
        )
        assert Vote(height=1, round=1).sign_bytes("") == want

    def test_with_chain_id(self):
        want = (
            bytes([0x2E, 0x11]) + (1).to_bytes(8, "little")
            + bytes([0x19]) + (1).to_bytes(8, "little") + GO_ZERO_TS
            + bytes([0x32, 0xD]) + b"test_chain_id"
        )
        assert Vote(height=1, round=1).sign_bytes("test_chain_id") == want


class TestHeaderHashGoldenVector:
    """types/block_test.go TestHeaderHash."""

    def _header(self) -> Header:
        return Header(
            version=Consensus(block=1, app=2),
            chain_id="chainId",
            height=3,
            time=Time(calendar.timegm((2019, 10, 13, 16, 14, 44, 0, 0, 0)), 0),
            last_block_id=BlockID(b"\x00" * 32, PartSetHeader(6, b"\x00" * 32)),
            last_commit_hash=_ts(b"last_commit_hash"),
            data_hash=_ts(b"data_hash"),
            validators_hash=_ts(b"validators_hash"),
            next_validators_hash=_ts(b"next_validators_hash"),
            consensus_hash=_ts(b"consensus_hash"),
            app_hash=_ts(b"app_hash"),
            last_results_hash=_ts(b"last_results_hash"),
            evidence_hash=_ts(b"evidence_hash"),
            proposer_address=_ts(b"proposer_address")[:20],
        )

    def test_expected_hash(self):
        assert (
            self._header().hash().hex().upper()
            == "F740121F553B5418C3EFBD343C2DBFE9E007BB67B0D020A0741374BAB65242A4"
        )

    def test_nil_validators_hash_yields_nil(self):
        import dataclasses

        h = dataclasses.replace(self._header(), validators_hash=b"")
        assert h.hash() is None

    def test_roundtrip(self):
        h = self._header()
        assert Header.decode(h.encode()) == h


class TestZeroBlockIDWire:
    """gogoproto non-nullable part_set_header: a zero BlockID marshals as
    b'\\x12\\x00' (types.pb.go BlockID.MarshalToSizedBuffer emits tag 0x12
    unconditionally) — this shapes every chain's height-1 header hash."""

    def test_zero_block_id_bytes(self):
        assert BlockID().encode() == b"\x12\x00"

    def test_zero_block_id_roundtrip(self):
        assert BlockID.decode(BlockID().encode()) == BlockID()

    def test_height1_header_encodes_zero_last_block_id(self):
        import dataclasses

        h = dataclasses.replace(
            TestHeaderHashGoldenVector()._header(), last_block_id=BlockID()
        )
        # field 5 must be present with the 2-byte zero BlockID payload
        assert b"\x2a\x02\x12\x00" in h.encode()
        assert Header.decode(h.encode()) == h
        assert h.hash() is not None


class TestRoundTrips:
    def test_vote(self):
        bid = BlockID(b"\x12" * 32, PartSetHeader(5, b"\x34" * 32))
        v = Vote(
            type=1,
            height=7,
            round=2,
            block_id=bid,
            timestamp=Time(123, 456),
            validator_address=b"\xaa" * 20,
            validator_index=3,
            signature=b"\x55" * 64,
        )
        assert Vote.decode(v.encode()) == v

    def test_commit(self):
        bid = BlockID(b"\x12" * 32, PartSetHeader(5, b"\x34" * 32))
        c = Commit(
            height=9,
            round=1,
            block_id=bid,
            signatures=[
                CommitSig(2, b"\xaa" * 20, Time(5, 6), b"\x01" * 64),
                CommitSig.absent(),
            ],
        )
        d = Commit.decode(c.encode())
        assert (d.height, d.round, d.block_id, d.signatures) == (
            c.height,
            c.round,
            c.block_id,
            c.signatures,
        )

    def test_proposal(self):
        bid = BlockID(b"\x12" * 32, PartSetHeader(5, b"\x34" * 32))
        p = Proposal(
            height=3, round=1, pol_round=-1, block_id=bid,
            timestamp=Time(100, 5), signature=b"\x11" * 64,
        )
        assert Proposal.decode(p.encode()) == p

    def test_vote_sign_bytes_all_matches_scalar_large_commit(self):
        """The vectorized n >= 64 path must stay byte-identical to the
        scalar splice across flags and varint-width extremes — it feeds
        batch signature verification for every real-size commit."""
        import random

        from cometbft_tpu.types.block import BlockID, Commit, CommitSig, PartSetHeader
        from cometbft_tpu.types.cmttime import GO_ZERO_SECONDS, Time

        rng = random.Random(11)
        bid = BlockID(
            hash=b"\x01" * 32,
            part_set_header=PartSetHeader(total=3, hash=b"\x02" * 32),
        )
        sigs = []
        for i in range(200):
            ts = rng.choice(
                [
                    Time(1700000000 + rng.randrange(10**6), rng.randrange(10**9)),
                    Time(0, 0),
                    Time(GO_ZERO_SECONDS, 0),
                    Time(-5, 7),
                    Time(2**62, 999999999),
                    Time(0, rng.randrange(1, 128)),
                ]
            )
            flag = rng.choice([1, 2, 3])
            if flag == 1:
                sigs.append(CommitSig.absent())
            else:
                sigs.append(
                    CommitSig(
                        block_id_flag=flag,
                        validator_address=bytes([i % 250]) * 20,
                        timestamp=ts,
                        signature=b"s" * 64,
                    )
                )
        c = Commit(height=42, round=1, block_id=bid, signatures=sigs)
        got = c.vote_sign_bytes_all("vec-chain")
        assert len(got) == 200
        for i in range(200):
            assert got[i] == c.vote_sign_bytes("vec-chain", i), i

    def test_commit_sig_validate(self):
        CommitSig.absent().validate_basic()
        CommitSig(2, b"\xaa" * 20, Time(5, 6), b"\x01" * 64).validate_basic()
        try:
            CommitSig(2, b"\xaa" * 19, Time(5, 6), b"\x01" * 64).validate_basic()
            raise AssertionError("should reject short address")
        except ValueError:
            pass


import pytest  # noqa: E402


@pytest.mark.parametrize("width", range(1, 10))
def test_vote_sign_bytes_all_matches_scalar_at_each_varint_width(width):
    """The vectorized path encodes no more varint bytes than the widest
    timestamp of the commit needs (ISSUE 27): both sides of each 7-bit
    boundary, under a chain id that ends in NUL bytes (the rows are cut
    out of one matrix), byte-identical to the scalar splice and the same
    whether or not the commit's columns were taken first."""
    from cometbft_tpu.types.block import BlockID, Commit, CommitSig, PartSetHeader

    top = 2 ** (7 * width)
    values = [top - 1, top // 2, 1, 0] if width > 1 else [127, 64, 1, 0]
    bid = BlockID(hash=b"\x01" * 32, part_set_header=PartSetHeader(total=3, hash=b"\x02" * 32))
    sigs = [
        CommitSig(2 + (i % 5 == 0), bytes([i]) * 20,
                  Time(values[i % 4], min(values[(i + 1) % 4], 999_999_999)), bytes([i]) * 64)
        for i in range(80)
    ]
    chain_id = "nul-tail\x00\x00"
    c = Commit(height=9, round=0, block_id=bid, signatures=sigs)
    got = c.vote_sign_bytes_all(chain_id)
    assert all(type(b) is bytes for b in got)
    assert got == [c.vote_sign_bytes(chain_id, i) for i in range(80)]
    flags, secs, nanos, signatures = c.sig_columns()
    assert (flags, secs, nanos, signatures) == (
        [cs.block_id_flag for cs in sigs], [cs.timestamp.seconds for cs in sigs],
        [cs.timestamp.nanos for cs in sigs], [cs.signature for cs in sigs])
