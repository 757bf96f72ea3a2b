"""The benchmark's yardstick for a replay by blocksync: which heights of a
chain of stored blocks a joiner may apply, by the semantics of
`VerifyCommitLight` (CometBFT types/validation.go:59, called by
blocksync/reactor.go:355-400 `trySync`) in plain Python.

Block h is applied only when block h+1 is there and its `LastCommit`
commits block h under the validator set:

    the commit's height is h and it carries one signature a validator;
    its block id is block h's: the RFC-6962 root over the header's 14
        encoded fields (types/block.go Header.Hash), and the part-set
        header of the block's protobuf bytes cut in 64 KiB;
    in the set's order, each signature FOR the block (absent and nil votes
        are passed over) is checked by the scalar ZIP-215 reference against
        the validator at its index, over the canonical precommit's sign
        bytes (types/canonical.go, length-delimited), and its power is
        added, until the sum passes 2/3 of the set's: a signature that does
        not verify before then refuses the commit;
    the sum passed 2/3.

A replay walks the heights in order and stops at the first it may not
apply: that is where a sync from a tampered chain stops. Written from the
.proto files and the Go sources named above, not from `cometbft_tpu/types`;
the encoders are `block_proto.py`'s, the tree is `rfc6962.py`'s. Nothing
here imports the program.

A validator is (public key, voting power); a block is `block_proto.py`'s
dict. `verify` may be handed in by a caller that already holds the scalar
reference's answers for the same triples (31,744 of them take minutes on
one core); whatever is handed in must be those answers.
"""

from __future__ import annotations

import hashlib
import struct
from typing import NamedTuple

from . import block_proto as bp
from . import rfc6962
from .ed25519_zip215 import verify_zip215

PART_BYTES = 65536  # types/params.go BlockPartSizeBytes
PRECOMMIT = 2       # SignedMsgType
ABSENT, COMMIT, NIL = 1, 2, 3  # BlockIDFlag


def address(pub: bytes) -> bytes:
    """crypto/ed25519 Address: the first 20 bytes of SHA-256(public key)."""
    return hashlib.sha256(pub).digest()[:20]


def set_order(validators) -> list[tuple[bytes, int]]:
    """(public key, power) as a ValidatorSet holds them: by power, the
    larger first, then by address (types/validator_set.go ValidatorsByVotingPower)."""
    return sorted(validators, key=lambda v: (-v[1], address(v[0])))


def _wrapped(value: bytes) -> bytes:
    """cdcEncode of a string, bytes or int64 field: the gogotypes wrapper
    message (field 1), and no bytes at all for an empty value."""
    return value and bp._every(1, value)


def header_hash(h: dict) -> bytes:
    """Header.Hash: the root over the fields in their declared order."""
    return rfc6962.root([
        bp._scalar(1, h["version_block"]) + bp._scalar(2, h["version_app"]),
        _wrapped(h["chain_id"].encode()),
        bp._scalar(1, h["height"]),  # Int64Value{1: height}
        bp._time(h["time"]),
        bp._block_id(h["last_block_id"]),
        _wrapped(h["last_commit_hash"]),
        _wrapped(h["data_hash"]),
        _wrapped(h["validators_hash"]),
        _wrapped(h["next_validators_hash"]),
        _wrapped(h["consensus_hash"]),
        _wrapped(h["app_hash"]),
        _wrapped(h["last_results_hash"]),
        _wrapped(h["evidence_hash"]),
        _wrapped(h["proposer_address"]),
    ])


def block_id(block: dict) -> tuple[bytes, int, bytes]:
    """(hash, parts total, parts hash) of a block, from its plain bytes."""
    parts = bp.parts(bp.block(block), PART_BYTES)
    return header_hash(block["header"]), len(parts), rfc6962.root(parts)


def _sfixed64(field: int, n: int) -> bytes:
    return bp._uvarint(field << 3 | 1) + struct.pack("<q", n) if n else b""


def sign_bytes(chain_id: str, height: int, round_: int, bid, at) -> bytes:
    """What a validator signed to commit `bid` at `height`: CanonicalVote
    (proto/tendermint/types/canonical.proto), length-delimited. The block
    id's part-set header and the timestamp are non-nullable: always there."""
    canonical_bid = bp._bytes(1, bid[0]) + bp._every(2, bp._scalar(1, bid[1]) + bp._bytes(2, bid[2]))
    vote = b"".join([
        bp._scalar(1, PRECOMMIT),
        _sfixed64(2, height),
        _sfixed64(3, round_),
        bp._every(4, canonical_bid),
        bp._every(5, bp._time(at)),
        bp._bytes(6, chain_id.encode()),
    ])
    return bp._uvarint(len(vote)) + vote


def refusal(chain_id: str, validators, block: dict, commit, verify=verify_zip215) -> str | None:
    """Why `commit` (block h+1's LastCommit) does not commit `block` under
    `validators` (in the set's order); None where it does."""
    height = block["header"]["height"]
    if commit is None:
        return "no commit"
    if commit["height"] != height:
        return f"the commit is for height {commit['height']}"
    if len(commit["signatures"]) != len(validators):
        return f"{len(commit['signatures'])} signatures for {len(validators)} validators"
    bid = block_id(block)
    if tuple(commit["block_id"]) != bid:
        return "the commit's block id is not the block's"
    needed = sum(power for _, power in validators) * 2 // 3
    tallied = 0
    for idx, ((pub, power), (flag, _, at, sig)) in enumerate(zip(validators, commit["signatures"])):
        if flag != COMMIT:
            continue
        if not verify(pub, sign_bytes(chain_id, height, commit["round"], bid, at), sig):
            return f"wrong signature (#{idx})"
        tallied += power
        if tallied > needed:
            return None
    return f"voting power {tallied} does not pass {needed}"


class Replay(NamedTuple):
    applied: list  # the heights a joiner may apply, in order
    stopped_at: int | None  # the first height it may not: the sync stops below it
    why: str | None


def replay(chain_id: str, validators, blocks, verify=verify_zip215) -> Replay:
    """The joiner's walk over `blocks` (consecutive heights, in order): the
    last one only carries the commit of the one before it, so it is never
    applied."""
    validators = set_order(validators)
    applied = []
    for block, nxt in zip(blocks, blocks[1:]):
        height = block["header"]["height"]
        if nxt["header"]["height"] != height + 1:
            return Replay(applied, height, "the next block is not the next height")
        why = refusal(chain_id, validators, block, nxt["last_commit"], verify)
        if why is not None:
            return Replay(applied, height, why)
        applied.append(height)
    return Replay(applied, None, None)
