"""The benchmark's yardstick for a block's bytes: the protobuf encoding of
`tendermint.types.Block` in plain Python, from plain values.

Written from the .proto files (proto/tendermint/types/block.proto,
types.proto, version/types.proto, google/protobuf/timestamp.proto) and
gogoproto's rule for them, not copied from `cometbft_tpu/types` or
`cometbft_tpu/wire`: nothing here imports the program, so a block encoding
that changed, or was wrong on both its ways, does not pass as its own
reference. The part set of a block is cut from these bytes.

    scalar (varint)             absent when 0; int64 as 64-bit two's complement
    bytes, string               absent when empty
    repeated bytes, messages    every element, empty ones too
    non-nullable sub-message    always there (Header.version, .time,
                                .last_block_id, BlockID.part_set_header,
                                Block.header, .data, .evidence, .last_commit,
                                Commit.block_id, CommitSig.timestamp)

A block is a dict: `header` (a dict of the fields below), `txs`, and
`last_commit` (`height`, `round`, `block_id`, `signatures` as tuples of
flag, validator address, time, signature; None where a block has none:
the one field of a block that is a pointer). A block id is (hash, parts
total, parts hash); a time is (seconds, nanos). Evidence is not carried:
the chains of this benchmark have none, and a block with some is refused.
"""

from __future__ import annotations

LEN = 2  # wire type of bytes, strings and sub-messages


def _uvarint(n: int) -> bytes:
    out = bytearray()
    while n > 0x7F:
        out.append(n & 0x7F | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def _scalar(field: int, n: int) -> bytes:
    return _uvarint(field << 3) + _uvarint(n & 0xFFFFFFFFFFFFFFFF) if n else b""


def _bytes(field: int, value: bytes) -> bytes:
    return _every(field, value) if value else b""


def _every(field: int, value: bytes) -> bytes:
    return _uvarint(field << 3 | LEN) + _uvarint(len(value)) + value


def _time(t) -> bytes:
    return _scalar(1, t[0]) + _scalar(2, t[1])


def _block_id(b) -> bytes:
    return _bytes(1, b[0]) + _every(2, _scalar(1, b[1]) + _bytes(2, b[2]))


def header(h: dict) -> bytes:
    return b"".join([
        _every(1, _scalar(1, h["version_block"]) + _scalar(2, h["version_app"])),
        _bytes(2, h["chain_id"].encode()),
        _scalar(3, h["height"]),
        _every(4, _time(h["time"])),
        _every(5, _block_id(h["last_block_id"])),
        _bytes(6, h["last_commit_hash"]),
        _bytes(7, h["data_hash"]),
        _bytes(8, h["validators_hash"]),
        _bytes(9, h["next_validators_hash"]),
        _bytes(10, h["consensus_hash"]),
        _bytes(11, h["app_hash"]),
        _bytes(12, h["last_results_hash"]),
        _bytes(13, h["evidence_hash"]),
        _bytes(14, h["proposer_address"]),
    ])


def commit(c: dict) -> bytes:
    sigs = [
        _every(4, _scalar(1, flag) + _bytes(2, address) + _every(3, _time(at)) + _bytes(4, sig))
        for flag, address, at, sig in c["signatures"]
    ]
    return _scalar(1, c["height"]) + _scalar(2, c["round"]) + _every(3, _block_id(c["block_id"])) + b"".join(sigs)


def block(b: dict) -> bytes:
    data = b"".join(_every(1, tx) for tx in b["txs"])
    return b"".join([
        _every(1, header(b["header"])),
        _every(2, data),
        _every(3, b""),  # EvidenceList, empty
        _every(4, commit(b["last_commit"])) if b["last_commit"] is not None else b"",
    ])


def parts(encoded: bytes, part_bytes: int) -> list[bytes]:
    """A block's part set: its bytes in pieces of `part_bytes`, one empty
    piece for no bytes."""
    return [encoded[i : i + part_bytes] for i in range(0, len(encoded), part_bytes)] or [b""]
