"""The benchmark's Merkle yardstick: RFC 6962 tree hashing in plain hashlib.

Written from the RFC (section 2.1) and CometBFT's `crypto/merkle` notes, not
copied from `cometbft_tpu/crypto/merkle`: recursive where the program is
iterative or native, kept under the benchmark's own directory so that no
later PR can move what `correct` is decided against. Nothing here imports
the program.

    leaf hash   SHA-256(0x00 || leaf)
    inner hash  SHA-256(0x01 || left || right)
    empty tree  SHA-256("")
    split       the largest power of two strictly below n

The tx root of a block (`Header.DataHash`) is `root(txs)` over the raw
transactions; a part set's root is `root(parts)` over the raw 64 KiB parts;
`LastResultsHash` is `root` over the deterministic DeliverTx encodings.
"""

from __future__ import annotations

import hashlib


def _leaf(data: bytes) -> bytes:
    return hashlib.sha256(b"\x00" + data).digest()


def _inner(left: bytes, right: bytes) -> bytes:
    return hashlib.sha256(b"\x01" + left + right).digest()


def _split(n: int) -> int:
    k = 1
    while k * 2 < n:
        k *= 2
    return k


def root(leaves: list[bytes]) -> bytes:
    """MTH(D[n]) of RFC 6962 section 2.1."""
    n = len(leaves)
    if n == 0:
        return hashlib.sha256(b"").digest()
    if n == 1:
        return _leaf(leaves[0])
    k = _split(n)
    return _inner(root(leaves[:k]), root(leaves[k:]))


def audit_path(leaves: list[bytes], index: int) -> list[bytes]:
    """PATH(m, D[n]) of RFC 6962 section 2.1.1: the sibling hashes from the
    leaf up to the root's child (CometBFT calls them aunts)."""
    n = len(leaves)
    if n <= 1:
        return []
    k = _split(n)
    if index < k:
        return audit_path(leaves[:k], index) + [root(leaves[k:])]
    return audit_path(leaves[k:], index - k) + [root(leaves[:k])]


def root_from_path(leaf: bytes, index: int, total: int, path: list[bytes]) -> bytes | None:
    """The root that `path` gives `leaf` at `index` of `total`; None where
    the path's length does not fit the tree's shape."""
    if not 0 <= index < total:
        return None
    if total == 1:
        return _leaf(leaf) if not path else None
    if not path:
        return None
    k = _split(total)
    if index < k:
        below = root_from_path(leaf, index, k, path[:-1])
        return None if below is None else _inner(below, path[-1])
    below = root_from_path(leaf, index - k, total - k, path[:-1])
    return None if below is None else _inner(path[-1], below)


def includes(root_hash: bytes, leaf: bytes, index: int, total: int, path: list[bytes]) -> bool:
    return root_from_path(leaf, index, total, path) == root_hash
