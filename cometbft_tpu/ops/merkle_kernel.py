"""Level-synchronous RFC-6962 Merkle hashing on TPU (crypto/merkle device tier).

The reference builds trees by recursive splitting at the largest power of two
(crypto/merkle/tree.go:11-27); pairing adjacent nodes level-by-level with odd
promotion yields the identical tree (tree.go:68-98). The level-synchronous
form is the TPU-native one: each level is a single batched SHA-256 call over
all sibling pairs (full lane width), and a 64k-leaf tree is 17 device calls
instead of 131k sequential host hashes.

Domain separation per RFC 6962 (crypto/merkle/hash.go:11-13):
  leaf  = SHA-256(0x00 || leaf bytes)
  inner = SHA-256(0x01 || left(32) || right(32))   [65 bytes -> 2 blocks]
"""

from __future__ import annotations

import functools
import hashlib
import os

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from cometbft_tpu.ops import sha256_kernel as sha


def _inner_core(left, right):
    """Batched inner-node hash. left/right: uint32[8, N] digests."""
    n = left.shape[1]
    # Block 1: 0x01 || left || right[:31]  (big-endian byte stream -> words)
    w = [None] * 16
    w[0] = jnp.uint32(0x01 << 24) | (left[0] >> 8)
    for i in range(1, 8):
        w[i] = (left[i - 1] << 24) | (left[i] >> 8)
    w[8] = (left[7] << 24) | (right[0] >> 8)
    for i in range(9, 16):
        w[i] = (right[i - 9] << 24) | (right[i - 8] >> 8)
    st = sha.compress(sha.iv_state(n), jnp.stack(w))
    # Block 2: last byte of right || 0x80 pad || bit length (65*8 = 520)
    zero = jnp.zeros((n,), jnp.uint32)
    w2 = [zero] * 16
    w2[0] = (right[7] << 24) | jnp.uint32(0x80 << 16)
    w2[15] = jnp.broadcast_to(jnp.uint32(520), (n,))
    return sha.compress(st, jnp.stack(w2))


@functools.lru_cache(maxsize=None)
def _inner_jit(n: int):
    return jax.jit(_inner_core)


def _leaf_core(blocks, nblocks):
    """Hash N variable-length pre-padded messages: blocks uint32[B, 16, N],
    nblocks int32[N]. Lanes stop updating once their block count is reached."""
    n = blocks.shape[2]
    init = sha.iv_state(n)

    def body(i, st):
        new = sha.compress(st, blocks[i])
        active = (i < nblocks)[None, :]
        return jnp.where(active, new, st)

    return lax.fori_loop(0, blocks.shape[0], body, init)


@functools.lru_cache(maxsize=None)
def _leaf_jit(bmax: int, n: int):
    return jax.jit(_leaf_core)


def _pow2_pad(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


def hash_leaves_device(items: list[bytes]) -> np.ndarray:
    """RFC-6962 leaf hashes of all items in one device program: uint32[8, n]."""
    n = len(items)
    msgs = [b"\x00" + it for it in items]
    blocks, nblocks = sha.pack_messages(msgs)
    npad = _pow2_pad(n)
    if npad != n:
        blocks = np.pad(blocks, ((0, 0), (0, 0), (0, npad - n)))
        nblocks = np.pad(nblocks, (0, npad - n), constant_values=1)
    out = _leaf_jit(blocks.shape[0], npad)(blocks, nblocks)
    return np.asarray(out)[:, :n]


def tree_levels(leaf_digests: np.ndarray) -> list[np.ndarray]:
    """All tree levels bottom-up from uint32[8, n] leaf digests; each level is
    one batched device call over its sibling pairs (odd node promoted)."""
    levels = [leaf_digests]
    cur = leaf_digests
    while cur.shape[1] > 1:
        m = cur.shape[1]
        pairs = m // 2
        left = cur[:, 0 : 2 * pairs : 2]
        right = cur[:, 1 : 2 * pairs : 2]
        ppad = _pow2_pad(pairs)
        if ppad != pairs:
            left = np.pad(left, ((0, 0), (0, ppad - pairs)))
            right = np.pad(right, ((0, 0), (0, ppad - pairs)))
        nxt = np.asarray(_inner_jit(ppad)(jnp.asarray(left), jnp.asarray(right)))
        nxt = nxt[:, :pairs]
        if m % 2 == 1:
            nxt = np.concatenate([nxt, cur[:, -1:]], axis=1)
        levels.append(nxt)
        cur = nxt
    return levels


def merkle_root(leaves: list[bytes]) -> bytes:
    """Root of the RFC-6962 tree over `leaves` (crypto/merkle/tree.go:11),
    computed level-parallel on device. Empty tree = SHA-256 of empty string
    (crypto/merkle/hash.go empty hash)."""
    if len(leaves) == 0:
        return hashlib.sha256(b"").digest()
    digests = hash_leaves_device(leaves)
    if len(leaves) == 1:
        return sha.digest_words_to_bytes(digests)[0]
    root = tree_levels(digests)[-1]
    return sha.digest_words_to_bytes(root)[0]


def leaves_to_root_core(blocks, nblocks):
    """ONE jittable program: leaf-hash all padded messages AND reduce the
    full tree to the root. blocks uint32[B, 16, n] (n a power of two),
    nblocks int32[n] -> uint32[8, 1]. Fusing the leaf pass and the log2(n)
    inner levels into a single dispatch saves one host round-trip per
    level."""
    with jax.named_scope("merkle"):  # HLO metadata only: a profile names the stage
        cur = _leaf_core(blocks, nblocks)
        while cur.shape[1] > 1:
            cur = _inner_core(cur[:, 0::2], cur[:, 1::2])
        return cur


@functools.lru_cache(maxsize=None)
def _leaves_to_root_jit(bmax: int, n: int):
    return jax.jit(leaves_to_root_core)


@functools.lru_cache(maxsize=1)
def _sharded_root():
    """(mesh width, sharded fused leaves->root fn) when this process owns
    multiple chips and the width is a power of two (the subtree-roots top
    reduction pairs level-synchronously), else None. Lazy import: merkle
    callers on single-chip hosts never pull the ed25519 kernel graph."""
    from cometbft_tpu.ops import ed25519_kernel as ek

    w = ek.mesh_width()
    if w <= 1 or w & (w - 1):
        return None
    from cometbft_tpu.ops import sharded

    return w, sharded.sharded_leaves_to_root_fn(
        sharded.make_mesh(jax.local_devices())
    )


def _mesh_merkle_floor() -> int:
    """Leaf count from which the fused root routes to the subtree-parallel
    mesh program. On a single chip the fused program already wins; sharding
    only pays once the leaf pass dominates the collective + top reduction."""
    try:
        return max(1, int(os.environ.get("CMTPU_MESH_MERKLE_FLOOR", "16384")))
    except ValueError:
        return 16384


def merkle_root_fused(leaves: list[bytes]) -> bytes:
    """RFC-6962 root in one device dispatch (power-of-two leaf counts; the
    general path pads via duplicate-free promotion in merkle_root). Forests
    at/above CMTPU_MESH_MERKLE_FLOOR route to ops/sharded's subtree-parallel
    program when this process owns a power-of-two mesh — each chip leaf-
    hashes and reduces its own subtree, still one dispatch end to end."""
    n = len(leaves)
    if n == 0:
        return hashlib.sha256(b"").digest()
    if n & (n - 1):
        return merkle_root(leaves)
    msgs = [b"\x00" + it for it in leaves]
    blocks, nblocks = sha.pack_messages(msgs)
    if n >= _mesh_merkle_floor():
        sh = _sharded_root()
        # n and width are both pow2 here, so divisibility of the shard
        # size follows whenever the mesh isn't wider than the forest.
        if sh is not None and n % sh[0] == 0:
            from cometbft_tpu.ops import ed25519_kernel as ek

            ek._mesh_count("merkle_sharded_dispatches")
            out = sh[1](jnp.asarray(blocks), jnp.asarray(nblocks))
            return sha.digest_words_to_bytes(np.asarray(out))[0]
    out = _leaves_to_root_jit(blocks.shape[0], n)(blocks, nblocks)
    return sha.digest_words_to_bytes(np.asarray(out))[0]


@functools.lru_cache(maxsize=None)
def _tree_root_jit(n: int):
    """ONE compiled program reducing uint32[8, n] (n a power of two) leaf
    digests to the root: the level loop unrolls inside jit (log2(n) levels,
    ~100 ops each), so a 64k-leaf tree costs one compile + one dispatch."""

    def root(leaves):
        cur = leaves
        while cur.shape[1] > 1:
            cur = _inner_core(cur[:, 0::2], cur[:, 1::2])
        return cur

    return jax.jit(root)


def merkle_root_pow2(leaf_digests: np.ndarray) -> bytes:
    """Root from uint32[8, n] leaf digests, n a power of two."""
    n = leaf_digests.shape[1]
    if n & (n - 1):
        raise ValueError("merkle_root_pow2 requires a power-of-two leaf count")
    if n == 1:
        return sha.digest_words_to_bytes(leaf_digests)[0]
    out = _tree_root_jit(n)(jnp.asarray(leaf_digests))
    return sha.digest_words_to_bytes(np.asarray(out))[0]
