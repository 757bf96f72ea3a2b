"""Validator (reference: types/validator.go)."""

from __future__ import annotations

from dataclasses import dataclass, field as dfield

from cometbft_tpu.crypto import ed25519
from cometbft_tpu.crypto import encoding as key_encoding
from cometbft_tpu.wire import proto as wire

# Field 2 of a Validator record holding an Ed25519 key: PublicKey{ed25519 =
# 1} of 32 bytes, 34 bytes in all.
_ED25519_KEY_FIELD = b"\x12\x22\x0a\x20"


def wire_head(address: bytes, pub_key, voting_power: int) -> bytes:
    """Fields 1-3 of tendermint.types.Validator (address, key, power): the
    part of a validator's wire form that proposer rotation leaves as it is.
    Field 4, the priority, follows it (Validator.encode)."""
    return (
        wire.field_bytes(1, address)
        + wire.field_message(2, key_encoding.pub_key_to_proto(pub_key), emit_empty=True)
        + wire.field_varint(3, voting_power)
    )


def canonical_validator(record: bytes) -> "Validator":
    """A Validator record read in one walk, taken only in the form
    Validator.encode writes with an Ed25519 key: a 20-byte address, the
    key, the power and the priority each where not 0, every varint minimal.
    Raises ValueError (IndexError where the record ends early) on any other
    form or key type, which Validator.decode then reads."""
    if record[0] != 0x0A or record[1] != 20 or record[22:26] != _ED25519_KEY_FIELD:
        raise ValueError("not a canonical Ed25519 validator")
    varint = wire.canonical_varint
    end = len(record)
    p = 58
    power = priority = 0
    if p < end and record[p] == 0x18:
        power, p = varint(record, p + 1)
    if p < end and record[p] == 0x20:
        priority, p = varint(record, p + 1)
    if p != end:
        raise ValueError("not a canonical validator")
    return Validator(record[2:22], ed25519.PubKey(record[26:58]), power, priority)


@dataclass
class Validator:
    """types/validator.go:17-35. Mutable: priority changes every round."""

    address: bytes
    pub_key: object
    voting_power: int
    proposer_priority: int = 0

    @classmethod
    def new(cls, pub_key, voting_power: int) -> "Validator":
        return cls(pub_key.address(), pub_key, voting_power, 0)

    def copy(self) -> "Validator":
        return Validator(
            self.address, self.pub_key, self.voting_power, self.proposer_priority
        )

    def compare_proposer_priority(self, other: "Validator") -> "Validator":
        """types/validator.go:64-84: higher priority wins; ties break to the
        smaller address."""
        if other is None:
            return self
        if self.proposer_priority > other.proposer_priority:
            return self
        if self.proposer_priority < other.proposer_priority:
            return other
        if self.address < other.address:
            return self
        if self.address > other.address:
            return other
        raise ValueError("Cannot compare identical validators")

    def bytes(self) -> bytes:
        """SimpleValidator proto bytes — the Merkle leaf of ValidatorSet.Hash
        (types/validator.go:117-133)."""
        pk = key_encoding.pub_key_to_proto(self.pub_key)
        return wire.field_message(1, pk, emit_empty=True) + wire.field_varint(
            2, self.voting_power
        )

    def validate_basic(self) -> None:
        """types/validator.go ValidateBasic."""
        if self.pub_key is None:
            raise ValueError("validator does not have a public key")
        if self.voting_power < 0:
            raise ValueError("validator has negative voting power")
        if len(self.address) != 20:
            raise ValueError("validator address is the wrong size")
        if self.address != self.pub_key.address():
            raise ValueError("validator address does not match its pubkey")

    def encode(self) -> bytes:
        """tendermint.types.Validator wire form."""
        return wire_head(
            self.address, self.pub_key, self.voting_power
        ) + wire.field_varint(4, self.proposer_priority)

    @classmethod
    def decode(cls, data: bytes) -> "Validator":
        f = wire.decode_fields(data)
        return cls(
            address=wire.get_bytes(f, 1),
            pub_key=key_encoding.pub_key_from_proto(wire.get_bytes(f, 2)),
            voting_power=wire.get_varint(f, 3),
            proposer_priority=wire.get_varint(f, 4),
        )
