"""Crypto interfaces and address derivation.

Mirrors the reference's `crypto` package contract (crypto/crypto.go:22-54):
`PubKey`/`PrivKey` duck-typed interfaces, `BatchVerifier` — the seam through
which the TPU sidecar is selected — and `address = SHA256-20(pubkey bytes)`
(crypto/crypto.go:18-20).
"""

from __future__ import annotations

import abc
import hashlib
import os

from cometbft_tpu.crypto import tmhash

ADDRESS_SIZE = tmhash.TRUNCATED_SIZE  # crypto/crypto.go:10-12


def address_hash(bz: bytes) -> bytes:
    """SHA256-20 address of arbitrary bytes (crypto/crypto.go:18)."""
    return tmhash.sum_truncated(bz)


def sha256(bz: bytes) -> bytes:
    """crypto.Sha256 (crypto/hash.go)."""
    return hashlib.sha256(bz).digest()


def c_random(n: int) -> bytes:
    """Cryptographically secure random bytes (crypto.CReader, crypto/random.go)."""
    return os.urandom(n)


class PubKey(abc.ABC):
    """crypto.PubKey (crypto/crypto.go:27-33)."""

    @abc.abstractmethod
    def address(self) -> bytes: ...

    @abc.abstractmethod
    def bytes(self) -> bytes: ...

    @abc.abstractmethod
    def verify_signature(self, msg: bytes, sig: bytes) -> bool: ...

    @abc.abstractmethod
    def type(self) -> str: ...

    def equals(self, other: "PubKey") -> bool:
        return type(self) is type(other) and self.bytes() == other.bytes()

    def __eq__(self, other) -> bool:
        return isinstance(other, PubKey) and self.equals(other)

    def __hash__(self) -> int:
        return hash((self.type(), self.bytes()))


class PrivKey(abc.ABC):
    """crypto.PrivKey (crypto/crypto.go:35-41)."""

    @abc.abstractmethod
    def bytes(self) -> bytes: ...

    @abc.abstractmethod
    def sign(self, msg: bytes) -> bytes: ...

    @abc.abstractmethod
    def pub_key(self) -> PubKey: ...

    @abc.abstractmethod
    def type(self) -> str: ...

    def equals(self, other: "PrivKey") -> bool:
        return type(self) is type(other) and self.bytes() == other.bytes()


class BatchVerifier(abc.ABC):
    """crypto.BatchVerifier (crypto/crypto.go:46-54).

    `add()` appends an entry; `verify()` returns (all_valid, per_entry_valid)
    in insertion order. The TPU device tier plugs in at this seam.
    """

    @abc.abstractmethod
    def add(self, key: PubKey, message: bytes, signature: bytes) -> None: ...

    @staticmethod
    def key_bytes(keys) -> tuple | None:
        """The raw bytes of a column of keys, where this engine's add_many()
        takes them and add() would refuse none of the keys; else None (the
        default: an engine whose add_many() is the loop below). A validator
        set asks once and keeps the answer as a column (SetColumns)."""
        return None

    def add_many(self, keys, messages, signatures, key_bytes=None) -> None:
        """add() for whole columns: the same entries in the same order, and
        the error add() would raise at the first entry it refuses.
        `key_bytes`, where given, is what key_bytes() made of a column that
        `keys` was selected from, selected alike: an engine that takes raw
        bytes reads those and leaves the key objects alone."""
        for key, message, signature in zip(keys, messages, signatures, strict=True):
            self.add(key, message, signature)

    @abc.abstractmethod
    def verify(self) -> tuple[bool, list[bool]]: ...
