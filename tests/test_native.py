"""Native C tier: MSM batch ed25519 + SHA-NI merkle, bit-exact against the
pure-Python anchors (ed25519_pure ZIP-215, crypto/merkle).

The native library is what CpuBackend ships on device-less hosts, so its
bitmap must match ed25519_pure.verify_zip215 exactly — including the
adversarial edge encodings the reference accepts/rejects via
curve25519-voi's VerifyOptionsZIP_215 (crypto/ed25519/ed25519.go:27-29).
"""

import hashlib
import os
import random

import pytest

from cometbft_tpu import native
from cometbft_tpu.crypto import ed25519, ed25519_pure as pure
from cometbft_tpu.crypto.merkle import hash_from_byte_slices_iterative

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native library unavailable (no gcc?)"
)


def _signed(n, seed=b"native"):
    pvs = [
        ed25519.gen_priv_key_from_secret(seed + b"%d" % i) for i in range(n)
    ]
    msgs = [b"msg-%04d-" % i + bytes([i % 251]) * (i % 37) for i in range(n)]
    sigs = [pv.sign(m) for pv, m in zip(pvs, msgs)]
    pubs = [pv.pub_key().bytes() for pv in pvs]
    return pubs, msgs, sigs


def test_all_valid_batch():
    pubs, msgs, sigs = _signed(100)
    ok, bits = native.batch_verify(pubs, msgs, sigs)
    assert ok and all(bits) and len(bits) == 100


def test_mixed_batch_bitmap_attribution():
    pubs, msgs, sigs = _signed(64)
    bad = {0, 17, 33, 63}
    sigs = [
        s if i not in bad else s[:20] + bytes([s[20] ^ 0xFF]) + s[21:]
        for i, s in enumerate(sigs)
    ]
    ok, bits = native.batch_verify(pubs, msgs, sigs)
    assert not ok
    assert all(bits[i] == (i not in bad) for i in range(64))


def test_zip215_edge_vectors_match_pure():
    """The exact edge-vector set the device kernel is held to
    (tests/test_ops_kernel.py): non-canonical encodings, small-order
    points, s-range boundaries, malformed lengths."""
    cases = pure.zip215_edge_cases()
    pubs = [c[1] for c in cases]
    msgs = [c[2] for c in cases]
    sigs = [c[3] for c in cases]
    _, got = native.batch_verify(pubs, msgs, sigs)
    for (name, p_, m_, s_), bit in zip(cases, got):
        if len(p_) != 32 or len(s_) != 64:
            want = False
        else:
            want = pure.verify_zip215(p_, m_, s_)
        assert bit == want, f"{name}: native={bit} pure={want}"
    assert got[0] is True
    assert got[5] is True, "s=0 with identity A satisfies the cofactored eq"
    assert got[9] is True, "noncanonical identity alias must decode (rule 1)"


def test_randomized_bitmap_vs_pure_fuzz():
    rng = random.Random(1234)
    pubs, msgs, sigs = _signed(48)
    pubs, msgs, sigs = list(pubs), list(msgs), list(sigs)
    for i in range(48):
        roll = rng.random()
        if roll < 0.3:
            j = rng.randrange(64)
            sigs[i] = sigs[i][:j] + bytes([sigs[i][j] ^ (1 << rng.randrange(8))]) + sigs[i][j + 1:]
        elif roll < 0.4:
            msgs[i] = msgs[i] + b"x"
        elif roll < 0.5:
            j = rng.randrange(32)
            pubs[i] = pubs[i][:j] + bytes([pubs[i][j] ^ 1]) + pubs[i][j + 1:]
    ok, bits = native.batch_verify(pubs, msgs, sigs)
    want = [pure.verify_zip215(p, m, s) for p, m, s in zip(pubs, msgs, sigs)]
    assert bits == want
    assert ok == all(want)


def test_empty_and_single():
    ok, bits = native.batch_verify([], [], [])
    assert not ok and bits == []
    pubs, msgs, sigs = _signed(1)
    ok, bits = native.batch_verify(pubs, msgs, sigs)
    assert ok and bits == [True]
    ok, bits = native.batch_verify(pubs, [b"other"], sigs)
    assert not ok and bits == [False]


def test_merkle_root_matches_pure():
    rng = random.Random(99)
    for n in (1, 2, 3, 4, 5, 7, 8, 9, 31, 32, 33, 100, 1000):
        leaves = [rng.randbytes(rng.randrange(0, 150)) for _ in range(n)]
        assert native.merkle_root(leaves) == hash_from_byte_slices_iterative(
            leaves
        ), n
    assert native.merkle_root([]) == hashlib.sha256(b"").digest()


def test_merkle_large_leaves():
    # >64-byte and >1024-byte leaves take the copy and streaming paths
    leaves = [os.urandom(n) for n in (0, 1, 64, 65, 100, 1024, 1025, 5000)]
    assert native.merkle_root(leaves) == hash_from_byte_slices_iterative(leaves)


def test_sha256_batch_matches_hashlib():
    msgs = [os.urandom(n) for n in (0, 1, 55, 56, 63, 64, 65, 119, 120, 200)]
    got = native.sha256_batch(msgs)
    assert got == [hashlib.sha256(m).digest() for m in msgs]


def test_cpu_backend_uses_native_with_exact_bitmap():
    """The shipped seam: CpuBackend.batch_verify over the native threshold
    returns the same bitmap as per-signature host verification."""
    from cometbft_tpu.sidecar.backend import CpuBackend

    pubs, msgs, sigs = _signed(32)
    sigs[5] = b"\x00" * 64
    ok, bits = CpuBackend().batch_verify(pubs, msgs, sigs)
    assert not ok
    assert bits == [i != 5 for i in range(32)]


def test_sha256_pack_matches_numpy():
    """The C leaf packer (cmtpu_sha256_pack) is bit-exact with the numpy
    path across block-boundary lengths, zero-length messages, and tile
    edges (the C pass transposes in 64-lane tiles)."""
    import numpy as np

    from cometbft_tpu.ops import sha256_kernel as sha

    rng = random.Random(7)
    boundary = [0, 1, 55, 56, 63, 64, 65, 119, 120, 128, 200]
    cases = [
        [b""],
        [os.urandom(n) for n in boundary],
        # 3 tiles + a ragged tail, mixed lengths crossing block counts
        [os.urandom(rng.choice(boundary)) for _ in range(64 * 3 + 17)],
    ]
    for msgs in cases:
        lens = np.fromiter((len(m) for m in msgs), np.int64, len(msgs))
        want_blocks, want_nb = sha._pack_messages_np(msgs, lens)
        got_blocks, got_nb = sha.pack_messages(msgs)
        assert np.array_equal(want_nb, got_nb)
        assert np.array_equal(want_blocks, got_blocks)
