"""Merkle inclusion proofs (reference: crypto/merkle/proof.go).

Proof = {total, index, leaf_hash, aunts}: leaf hashes included, root excluded,
aunts ordered from the leaf's sibling up to the root's child. MaxAunts=100
bounds proof size against DoS (proof.go:12-16).
"""

from __future__ import annotations

from collections.abc import Sequence
from dataclasses import dataclass, field

from cometbft_tpu.crypto import tmhash
from cometbft_tpu.crypto.merkle.hash import inner_hash, leaf_hash
from cometbft_tpu.crypto.merkle.tree import get_split_point

MAX_AUNTS = 100


@dataclass
class Proof:
    """crypto/merkle/proof.go:26-31."""

    total: int = 0
    index: int = 0
    leaf_hash: bytes = b""
    aunts: list[bytes] = field(default_factory=list)

    def verify(self, root_hash: bytes, leaf: bytes) -> None:
        """Raises ValueError unless this proof links `leaf` to `root_hash`
        (crypto/merkle/proof.go:52-69)."""
        if self.total < 0:
            raise ValueError("proof total must be positive")
        if self.index < 0:
            raise ValueError("proof index cannot be negative")
        lh = leaf_hash(leaf)
        if self.leaf_hash != lh:
            raise ValueError(
                f"invalid leaf hash: wanted {lh.hex()} got {self.leaf_hash.hex()}"
            )
        computed = self.compute_root_hash()
        if computed != root_hash:
            raise ValueError(
                f"invalid root hash: wanted {root_hash.hex()} got "
                f"{computed.hex() if computed else None}"
            )

    def compute_root_hash(self) -> bytes | None:
        """crypto/merkle/proof.go:72-79."""
        return compute_hash_from_aunts(self.index, self.total, self.leaf_hash, self.aunts)

    def validate_basic(self) -> None:
        """crypto/merkle/proof.go:97-118."""
        if self.total < 0:
            raise ValueError("negative Total")
        if self.index < 0:
            raise ValueError("negative Index")
        if len(self.leaf_hash) != tmhash.SIZE:
            raise ValueError(
                f"expected LeafHash size to be {tmhash.SIZE}, got {len(self.leaf_hash)}"
            )
        if len(self.aunts) > MAX_AUNTS:
            raise ValueError(f"expected no more than {MAX_AUNTS} aunts, got {len(self.aunts)}")
        for i, aunt in enumerate(self.aunts):
            if len(aunt) != tmhash.SIZE:
                raise ValueError(f"expected Aunts#{i} size to be {tmhash.SIZE}, got {len(aunt)}")

    def to_proto(self) -> dict:
        return {
            "total": self.total,
            "index": self.index,
            "leaf_hash": self.leaf_hash,
            "aunts": list(self.aunts),
        }

    @classmethod
    def from_proto(cls, pb: dict) -> "Proof":
        p = cls(
            total=pb.get("total", 0),
            index=pb.get("index", 0),
            leaf_hash=pb.get("leaf_hash", b""),
            aunts=list(pb.get("aunts", [])),
        )
        p.validate_basic()
        return p


def compute_hash_from_aunts(
    index: int, total: int, leaf_hash_: bytes, inner_hashes: list[bytes]
) -> bytes | None:
    """Fold aunts into a root; None if the shape is wrong
    (crypto/merkle/proof.go:151-181). Iterative to handle 64k-leaf proofs."""
    if index >= total or index < 0 or total <= 0:
        return None
    # Walk the split-point recursion iteratively, recording left/right turns
    # top-down, then fold bottom-up over the aunts.
    turns: list[bool] = []  # True = we're in the left subtree at this step
    lo_total, lo_index = total, index
    depth = 0
    while lo_total > 1:
        if depth >= len(inner_hashes):
            return None
        k = get_split_point(lo_total)
        if lo_index < k:
            turns.append(True)
            lo_total = k
        else:
            turns.append(False)
            lo_index -= k
            lo_total -= k
        depth += 1
    if depth != len(inner_hashes):
        return None
    h = leaf_hash_
    for i, left in enumerate(reversed(turns)):
        aunt = inner_hashes[i]
        h = inner_hash(h, aunt) if left else inner_hash(aunt, h)
    return h


class _LazyProofs(Sequence):
    """Sequence of Proof over the native packed-aunts buffer.

    All hashing (every tree level) and aunt gathering already happened in
    one C pass; this materializes the per-leaf Proof object — 32-byte aunt
    slices included — only when indexed, because a 64k-leaf block would
    otherwise allocate ~1M small bytes objects up front that consumers
    (tx proof RPC, part-set gossip) touch one leaf at a time.
    """

    __slots__ = ("_n", "_leaf_hashes", "_packed", "_stride", "_counts")

    def __init__(self, n, leaf_hashes, packed, stride, counts):
        self._n = n
        self._leaf_hashes = leaf_hashes
        self._packed = packed
        self._stride = stride
        self._counts = counts

    def __len__(self) -> int:
        return self._n

    def __getitem__(self, i: int) -> Proof:
        if isinstance(i, slice):
            return [self[j] for j in range(*i.indices(self._n))]
        if i < 0:
            i += self._n
        if not 0 <= i < self._n:
            raise IndexError(i)
        base = i * self._stride
        return Proof(
            total=self._n,
            index=i,
            leaf_hash=self._leaf_hashes[i],
            aunts=[
                self._packed[base + 32 * k : base + 32 * (k + 1)]
                for k in range(self._counts[i])
            ],
        )

    def __iter__(self):
        for i in range(self._n):
            yield self[i]


def proofs_from_byte_slices(items: list[bytes]) -> tuple[bytes, Sequence[Proof]]:
    """Root + one inclusion proof per item (crypto/merkle/proof.go:35-49).

    Level-synchronous construction: at each level node i's aunt is its
    neighbor i^1; an odd trailing node is promoted with no aunt. Identical
    aunt lists to the reference's trailsFromByteSlices recursion.

    Built on the host: from 32 items up one pass of the native C library
    (every level and every aunt; proofs materialize when indexed), else,
    and until that library is built, the pure-Python construction below.
    """
    n = len(items)
    if n == 0:
        from cometbft_tpu.crypto.merkle.hash import empty_hash

        return empty_hash(), []
    if n >= 32:
        from cometbft_tpu import native

        if native.ready() is not None:
            root, leaf_hashes, packed, stride, counts = (
                native.merkle_proof_parts(items)
            )
            return root, _LazyProofs(n, leaf_hashes, packed, stride, counts)
        native.ensure_built_async()
    level = [leaf_hash(item) for item in items]
    leaf_hashes = list(level)
    aunts_per_leaf: list[list[bytes]] = [[] for _ in range(n)]
    # index of each original leaf within the current level (or -1 once merged)
    pos = list(range(n))
    while len(level) > 1:
        size = len(level)
        for leaf_i in range(n):
            idx = pos[leaf_i]
            sib = idx ^ 1
            if sib < size:
                aunts_per_leaf[leaf_i].append(level[sib])
            pos[leaf_i] = idx // 2
        nxt = []
        for i in range(0, size - 1, 2):
            nxt.append(inner_hash(level[i], level[i + 1]))
        if size % 2 == 1:
            nxt.append(level[-1])
        level = nxt
    root = level[0]
    proofs = [
        Proof(total=n, index=i, leaf_hash=leaf_hashes[i], aunts=aunts_per_leaf[i])
        for i in range(n)
    ]
    return root, proofs
