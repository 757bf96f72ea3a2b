"""Native host-tier crypto: C batch ed25519 verification + C Merkle trees.

The reference's CPU story rests on curve25519-voi's batch verifier
(crypto/ed25519/ed25519.go:196-228): one random-linear-combination equation
evaluated as a multi-scalar multiplication, ~an order of magnitude fewer
field multiplications than per-signature verification.  This package is
that tier for the TPU framework's device-less hosts: `ed25519_msm.c`
(radix-51 field arithmetic, ZIP-215 decompression, Pippenger MSM) and
`sha256_merkle.c` (RFC-6962 tree with the whole level loop in C), built
on first use with gcc into `_build/libcmtpu_native-<hash>.so` — the hash
covers the three sources and the compiler flags, so a binary copied along
with a checkout can only load if it was built from exactly these bytes —
and driven via ctypes.  Without a compiler available() is False and the
callers keep their slower paths; require() raises what stopped the build
for paths that must not run without it.  Semantics are anchored by
cometbft_tpu/crypto/ed25519_pure.py and the pure merkle tree, tested
bit-exact in tests/test_native.py.

Soundness: the batch equation uses independent 128-bit random nonzero
coefficients, so a batch that verifies without being valid has probability
~2^-128 (same construction as the reference's verifier).  On batch failure
the wrapper bisects; with z_i != 0 the randomized single-signature check
is EXACTLY the cofactored ZIP-215 check ([8][z](sB - R - hA) == id iff
[8](sB - R - hA) == id for 0 < z < L), so the recovered bitmap is exact.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_SOURCES = ("ed25519_msm.c", "sha256_merkle.c", "fe_ifma.c")
_CFLAGS = ("-O3", "-fPIC", "-shared")
_BUILD_DIR = os.path.join(_HERE, "_build")

L = 2**252 + 27742317777372353535851937790883648493

_lock = threading.Lock()
# The C MSM uses a static bucket table; serialize calls into it.
_msm_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False
_error = ""  # why _lib is None once _tried


def _so_path() -> str:
    """`_build/libcmtpu_native-<hash>.so`, the hash over the compiler
    flags and every source byte."""
    h = hashlib.sha256(" ".join(_CFLAGS).encode())
    for name in _SOURCES:
        with open(os.path.join(_HERE, name), "rb") as f:
            h.update(b"\0" + name.encode() + b"\0" + f.read())
    return os.path.join(_BUILD_DIR, f"libcmtpu_native-{h.hexdigest()[:16]}.so")


def _build() -> str:
    """Path of the library for the current sources, compiling it if no
    file of that name exists yet. Raises what gcc or the filesystem did."""
    path = _so_path()
    if os.path.exists(path):
        return path
    os.makedirs(_BUILD_DIR, exist_ok=True)
    tmp = path + f".tmp.{os.getpid()}"
    try:
        subprocess.run(
            ["gcc", *_CFLAGS, "-o", tmp]
            + [os.path.join(_HERE, s) for s in _SOURCES],
            check=True,
            capture_output=True,
            timeout=120,
        )
    except subprocess.CalledProcessError as e:
        raise OSError(
            f"gcc exited {e.returncode}: {e.stderr.decode(errors='replace')[-2000:]}"
        ) from e
    os.replace(tmp, path)
    return path


def _load() -> ctypes.CDLL | None:
    global _lib, _tried, _error
    if _tried:
        return _lib
    with _lock:
        if _tried:
            return _lib
        if os.environ.get("CMTPU_NATIVE", "1") == "0":
            _error = "disabled by CMTPU_NATIVE=0"
            _tried = True
            return None
        try:
            path = _build()
        except (OSError, subprocess.TimeoutExpired) as e:
            # No compiler / failed compile / read-only checkout: the host
            # tier keeps its slower paths, and require() reports this.
            path = None
            _error = f"{type(e).__name__}: {e}"
        if path is not None:
            try:
                lib = ctypes.CDLL(path)
                lib.cmtpu_ed25519_precheck.restype = ctypes.c_long
                lib.cmtpu_ed25519_precheck.argtypes = [
                    ctypes.c_long, ctypes.c_char_p, ctypes.c_char_p,
                    ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ]
                lib.cmtpu_ed25519_check_subset.restype = ctypes.c_int
                lib.cmtpu_ed25519_check_subset.argtypes = [
                    ctypes.c_void_p, ctypes.c_void_p,
                    ctypes.c_void_p, ctypes.c_long,
                    ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
                ]
                lib.cmtpu_ge_size.restype = ctypes.c_long
                lib.cmtpu_merkle_root.restype = None
                lib.cmtpu_merkle_root.argtypes = [
                    ctypes.c_long, ctypes.c_char_p, ctypes.c_void_p,
                    ctypes.c_void_p, ctypes.c_void_p,
                ]
                lib.cmtpu_sha256_batch.restype = None
                lib.cmtpu_sha256_batch.argtypes = [
                    ctypes.c_long, ctypes.c_char_p, ctypes.c_void_p,
                    ctypes.c_void_p,
                ]
                lib.cmtpu_merkle_levels.restype = None
                lib.cmtpu_merkle_levels.argtypes = [
                    ctypes.c_long, ctypes.c_char_p, ctypes.c_void_p,
                    ctypes.c_void_p,
                ]
                lib.cmtpu_merkle_aunts.restype = None
                lib.cmtpu_merkle_aunts.argtypes = [
                    ctypes.c_long, ctypes.c_void_p, ctypes.c_long,
                    ctypes.c_void_p, ctypes.c_void_p,
                ]
                lib.cmtpu_sha512_batch.restype = None
                lib.cmtpu_sha512_batch.argtypes = [
                    ctypes.c_long, ctypes.c_char_p, ctypes.c_void_p,
                    ctypes.c_void_p,
                ]
                lib.cmtpu_ed25519_scalar_prep.restype = None
                lib.cmtpu_ed25519_scalar_prep.argtypes = [
                    ctypes.c_long, ctypes.c_void_p, ctypes.c_char_p,
                    ctypes.c_char_p, ctypes.c_void_p, ctypes.c_void_p,
                    ctypes.c_void_p, ctypes.c_void_p,
                ]
                lib.cmtpu_sha256_pack.restype = None
                lib.cmtpu_sha256_pack.argtypes = [
                    ctypes.c_long, ctypes.c_char_p, ctypes.c_void_p,
                    ctypes.c_long, ctypes.c_void_p, ctypes.c_void_p,
                ]
                _lib = lib
            except OSError as e:
                _lib = None
                _error = f"{type(e).__name__}: {e}"
        _tried = True
        return _lib


def available() -> bool:
    """Blocking: builds the library on first call if needed (seconds of gcc).
    Latency-sensitive callers should use ready() + ensure_built_async()."""
    return _load() is not None


def require() -> ctypes.CDLL:
    """Blocking like available(), but a missing library is an error that
    says why (gcc's own message, a load failure, CMTPU_NATIVE=0)."""
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native library unavailable: {_error}")
    return lib


def status() -> str:
    """Non-blocking one-word state for counters and logs: `ready`,
    `building` (not tried yet or in progress) or `failed: <why>`."""
    if not _tried:
        return "building"
    return "ready" if _lib is not None else f"failed: {_error}"


def ready():
    """Non-blocking: the loaded library, or None if not (yet) built.  Never
    triggers a compile — pair with ensure_built_async() from hot paths."""
    return _lib if _tried else None


def ensure_built_async() -> None:
    """Kick the build/load off a daemon thread so first-use verification
    paths never stall behind gcc (the same first-call-stall discipline as
    sidecar/backend.py's jax probing)."""
    if _tried:
        return
    threading.Thread(target=_load, name="cmtpu-native-build", daemon=True).start()


def batch_verify(
    pubs: list[bytes], msgs: list[bytes], sigs: list[bytes]
) -> tuple[bool, list[bool]]:
    """ZIP-215 batch verification with an exact per-signature bitmap.

    One MSM when everything is valid (the overwhelmingly common case);
    bisection recovers per-signature attribution on failure.
    """
    lib = require()
    n = len(pubs)
    bits = [False] * n
    if n == 0:
        return False, bits

    # Length gate (the kernel seam accepts raw triples).
    cand = [
        i for i in range(n) if len(pubs[i]) == 32 and len(sigs[i]) == 64
    ]
    m = len(cand)
    if m == 0:
        return False, bits

    pub_buf = b"".join(pubs[i] for i in cand)
    sig_buf = b"".join(sigs[i] for i in cand)
    ge_size = lib.cmtpu_ge_size()
    a_neg = ctypes.create_string_buffer(m * ge_size)
    r_neg = ctypes.create_string_buffer(m * ge_size)
    dec_ok = ctypes.create_string_buffer(m)
    lib.cmtpu_ed25519_precheck(m, pub_buf, sig_buf, a_neg, r_neg, dec_ok)

    # Challenges h = SHA512(R||A||M), then all scalar work (s<L check,
    # h mod L, z odd, zh = z*h, ssum accumulation) in one C pass.
    chal_buf = b"".join(
        sigs[i][:32] + pubs[i] + msgs[i] for i in cand
    )
    offs = _offsets((64 + len(msgs[i]) for i in cand), m)
    digests = ctypes.create_string_buffer(64 * m)
    lib.cmtpu_sha512_batch(m, chal_buf, offs, digests)

    rand = os.urandom(16 * m)
    z_buf = ctypes.create_string_buffer(32 * m)
    zh_buf = ctypes.create_string_buffer(32 * m)
    ssum_buf = ctypes.create_string_buffer(32)
    lib.cmtpu_ed25519_scalar_prep(
        m, digests, sig_buf, rand, z_buf, zh_buf, ssum_buf, dec_ok
    )
    okflags = dec_ok.raw  # decompress AND s-range survivors
    eligible = [j for j in range(m) if okflags[j]]
    if not eligible:
        return False, bits

    zb = z_buf.raw
    zhb = zh_buf.raw

    def check(subset: list[int], ssum: bytes) -> bool:
        idx = (ctypes.c_int64 * len(subset))(*subset)
        with _msm_lock:
            return bool(
                lib.cmtpu_ed25519_check_subset(
                    a_neg, r_neg, idx, len(subset), ssum, zb, zhb,
                )
            )

    if check(eligible, ssum_buf.raw):
        for j in eligible:
            bits[cand[j]] = True
        return all(bits), bits

    # Batch failed: bisect.  Subset ssums need the integers — parse them
    # once, only on this (rare, adversarial) path.
    z_int = {
        j: int.from_bytes(zb[32 * j : 32 * j + 32], "little") for j in eligible
    }
    s_int = {
        j: int.from_bytes(sigs[cand[j]][32:], "little") for j in eligible
    }

    def settle(subset: list[int]) -> None:
        ssum = 0
        for j in subset:
            ssum += z_int[j] * s_int[j]
        if check(subset, (ssum % L).to_bytes(32, "little")):
            for j in subset:
                bits[cand[j]] = True
            return
        if len(subset) == 1:
            return  # exact: randomized single == cofactored ZIP-215 check
        mid = len(subset) // 2
        settle(subset[:mid])
        settle(subset[mid:])

    mid = len(eligible) // 2
    if eligible[:mid]:
        settle(eligible[:mid])
    settle(eligible[mid:])
    return all(bits), bits


def _offsets(lengths, n: int):
    """uint64[n+1] cumulative offsets as a ctypes array from an iterable of
    n lengths — vectorized; the obvious python accumulation loop costs
    ~10 ms at 64k entries on a small host, which was a visible slice of
    the hybrid tier's merkle overlap."""
    import numpy as np

    offs = (ctypes.c_uint64 * (n + 1))()
    view = np.frombuffer(offs, np.uint64)
    np.cumsum(np.fromiter(lengths, np.uint64, n), out=view[1:])
    return offs


def _leaf_offsets(leaves: list[bytes]):
    return _offsets((len(v) for v in leaves), len(leaves))


def merkle_root(leaves: list[bytes]) -> bytes:
    """RFC-6962 root, identical to crypto/merkle hash_from_byte_slices."""
    lib = require()
    n = len(leaves)
    if n == 0:
        return hashlib.sha256(b"").digest()
    buf = b"".join(leaves)
    offs = _leaf_offsets(leaves)
    scratch = ctypes.create_string_buffer(32 * n)
    out = ctypes.create_string_buffer(32)
    lib.cmtpu_merkle_root(n, buf, offs, scratch, out)
    return out.raw


def merkle_proof_parts(
    leaves: list[bytes],
) -> tuple[bytes, list[bytes], bytes, int, "list[int]"]:
    """Everything proofs_from_byte_slices needs, hashed in one C pass:
    (root, leaf_hashes, packed_aunts, stride, counts) where leaf i's aunts
    are packed_aunts[i*stride : i*stride + 32*counts[i]] in 32-byte nodes,
    ordered sibling-first (crypto/merkle/proof.go:35-49 shape)."""
    lib = require()
    n = len(leaves)
    if n == 0:
        return hashlib.sha256(b"").digest(), [], b"", 0, []
    buf = b"".join(leaves)
    offs = _leaf_offsets(leaves)

    total_nodes = 0
    size = n
    depth = 0
    while True:
        total_nodes += size
        if size == 1:
            break
        size = (size + 1) // 2
        depth += 1
    levels = ctypes.create_string_buffer(32 * total_nodes)
    lib.cmtpu_merkle_levels(n, buf, offs, levels)
    lraw = levels.raw  # one copy out of ctypes; .raw re-copies per access
    root = lraw[32 * (total_nodes - 1) : 32 * total_nodes]
    leaf_hashes = [lraw[32 * i : 32 * i + 32] for i in range(n)]
    stride = 32 * max(depth, 1)
    aunts = ctypes.create_string_buffer(n * stride)
    counts = (ctypes.c_int32 * n)()
    lib.cmtpu_merkle_aunts(n, levels, max(depth, 1), aunts, counts)
    return root, leaf_hashes, aunts.raw, stride, list(counts)


def sha256_batch(msgs: list[bytes]) -> list[bytes]:
    """Batch SHA-256 without per-call interpreter dispatch."""
    lib = require()
    n = len(msgs)
    if n == 0:
        return []
    buf = b"".join(msgs)
    offs = (ctypes.c_uint64 * (n + 1))()
    acc = 0
    for i, msg in enumerate(msgs):
        offs[i] = acc
        acc += len(msg)
    offs[n] = acc
    out = ctypes.create_string_buffer(32 * n)
    lib.cmtpu_sha256_batch(n, buf, offs, out)
    return [out.raw[32 * i : 32 * i + 32] for i in range(n)]
