"""Batched SHA-512 on TPU (device tier of the ed25519 challenge hash).

The verify equation's k = SHA-512(R || A || M) mod L was the last host-side
crypto in the batch path (hashlib, ~12 ms per 10k batch). This kernel hashes
all lanes' messages in SPMD lockstep: 64-bit words are emulated as
(hi, lo) uint32 pairs — TPU has no int64 — with ~5 int32 ops per 64-bit add
(sum + carry-compare) and ~6 per rotation, so one 80-round compression is a
few thousand [N]-wide VPU ops, traced once inside a lax.fori_loop over the
message's 128-byte blocks with per-lane active masking (same pattern as
sha256_kernel._leaf_core).

Host side packs variable-length messages into padded blocks
(pack_messages512, the SHA-512 analog of sha256_kernel.pack_messages).
"""

from __future__ import annotations

import functools
import struct

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

# -- constants (FIPS 180-4) --------------------------------------------------

_K = [
    0x428A2F98D728AE22, 0x7137449123EF65CD, 0xB5C0FBCFEC4D3B2F, 0xE9B5DBA58189DBBC,
    0x3956C25BF348B538, 0x59F111F1B605D019, 0x923F82A4AF194F9B, 0xAB1C5ED5DA6D8118,
    0xD807AA98A3030242, 0x12835B0145706FBE, 0x243185BE4EE4B28C, 0x550C7DC3D5FFB4E2,
    0x72BE5D74F27B896F, 0x80DEB1FE3B1696B1, 0x9BDC06A725C71235, 0xC19BF174CF692694,
    0xE49B69C19EF14AD2, 0xEFBE4786384F25E3, 0x0FC19DC68B8CD5B5, 0x240CA1CC77AC9C65,
    0x2DE92C6F592B0275, 0x4A7484AA6EA6E483, 0x5CB0A9DCBD41FBD4, 0x76F988DA831153B5,
    0x983E5152EE66DFAB, 0xA831C66D2DB43210, 0xB00327C898FB213F, 0xBF597FC7BEEF0EE4,
    0xC6E00BF33DA88FC2, 0xD5A79147930AA725, 0x06CA6351E003826F, 0x142929670A0E6E70,
    0x27B70A8546D22FFC, 0x2E1B21385C26C926, 0x4D2C6DFC5AC42AED, 0x53380D139D95B3DF,
    0x650A73548BAF63DE, 0x766A0ABB3C77B2A8, 0x81C2C92E47EDAEE6, 0x92722C851482353B,
    0xA2BFE8A14CF10364, 0xA81A664BBC423001, 0xC24B8B70D0F89791, 0xC76C51A30654BE30,
    0xD192E819D6EF5218, 0xD69906245565A910, 0xF40E35855771202A, 0x106AA07032BBD1B8,
    0x19A4C116B8D2D0C8, 0x1E376C085141AB53, 0x2748774CDF8EEB99, 0x34B0BCB5E19B48A8,
    0x391C0CB3C5C95A63, 0x4ED8AA4AE3418ACB, 0x5B9CCA4F7763E373, 0x682E6FF3D6B2B8A3,
    0x748F82EE5DEFB2FC, 0x78A5636F43172F60, 0x84C87814A1F0AB72, 0x8CC702081A6439EC,
    0x90BEFFFA23631E28, 0xA4506CEBDE82BDE9, 0xBEF9A3F7B2C67915, 0xC67178F2E372532B,
    0xCA273ECEEA26619C, 0xD186B8C721C0C207, 0xEADA7DD6CDE0EB1E, 0xF57D4F7FEE6ED178,
    0x06F067AA72176FBA, 0x0A637DC5A2C898A6, 0x113F9804BEF90DAE, 0x1B710B35131C471B,
    0x28DB77F523047D84, 0x32CAAB7B40C72493, 0x3C9EBE0A15C9BEBC, 0x431D67C49C100D4C,
    0x4CC5D4BECB3E42B6, 0x597F299CFC657E2A, 0x5FCB6FAB3AD6FAEC, 0x6C44198C4A475817,
]
_IV = [
    0x6A09E667F3BCC908, 0xBB67AE8584CAA73B, 0x3C6EF372FE94F82B, 0xA54FF53A5F1D36F1,
    0x510E527FADE682D1, 0x9B05688C2B3E6C1F, 0x1F83D9ABFB41BD6B, 0x5BE0CD19137E2179,
]
_K_HI = jnp.asarray(np.array([k >> 32 for k in _K], np.uint32))
_K_LO = jnp.asarray(np.array([k & 0xFFFFFFFF for k in _K], np.uint32))


def _add2(a, b):
    """64-bit add of (hi, lo) uint32 pairs."""
    lo = a[1] + b[1]
    carry = (lo < a[1]).astype(jnp.uint32)
    return a[0] + b[0] + carry, lo


def _add_many(*vals):
    acc = vals[0]
    for v in vals[1:]:
        acc = _add2(acc, v)
    return acc


def _rotr(x, n: int):
    hi, lo = x
    if n == 0:
        return x
    if n < 32:
        return (
            (hi >> n) | (lo << (32 - n)),
            (lo >> n) | (hi << (32 - n)),
        )
    if n == 32:
        return lo, hi
    n -= 32
    return (
        (lo >> n) | (hi << (32 - n)),
        (hi >> n) | (lo << (32 - n)),
    )


def _shr(x, n: int):
    hi, lo = x
    if n < 32:
        return hi >> n, (lo >> n) | (hi << (32 - n))
    return jnp.zeros_like(hi), hi >> (n - 32)


def _xor(a, b):
    return a[0] ^ b[0], a[1] ^ b[1]


def _xor3(a, b, c):
    return a[0] ^ b[0] ^ c[0], a[1] ^ b[1] ^ c[1]


def _big_sigma0(x):
    return _xor3(_rotr(x, 28), _rotr(x, 34), _rotr(x, 39))


def _big_sigma1(x):
    return _xor3(_rotr(x, 14), _rotr(x, 18), _rotr(x, 41))


def _small_sigma0(x):
    return _xor3(_rotr(x, 1), _rotr(x, 8), _shr(x, 7))


def _small_sigma1(x):
    return _xor3(_rotr(x, 19), _rotr(x, 61), _shr(x, 6))


def _ch(x, y, z):
    return (
        (x[0] & y[0]) ^ (~x[0] & z[0]),
        (x[1] & y[1]) ^ (~x[1] & z[1]),
    )


def _maj(x, y, z):
    return (
        (x[0] & y[0]) ^ (x[0] & z[0]) ^ (y[0] & z[0]),
        (x[1] & y[1]) ^ (x[1] & z[1]) ^ (y[1] & z[1]),
    )


def iv_state(n: int):
    """uint32[2, 8, N]: (hi/lo, word, lane)."""
    hi = np.array([v >> 32 for v in _IV], np.uint32)
    lo = np.array([v & 0xFFFFFFFF for v in _IV], np.uint32)
    st = np.stack([hi, lo])[:, :, None]  # [2, 8, 1]
    return jnp.broadcast_to(jnp.asarray(st), (2, 8, n))


def compress(state, block):
    """One SHA-512 compression: state uint32[2, 8, N], block uint32[2, 16, N]
    (big-endian 64-bit message words as hi/lo pairs). The 80 rounds run in a
    lax.fori_loop with the 16-word message schedule as a circular window —
    an unrolled form is ~8k ops per block and XLA:CPU's compile time is
    superlinear in fusion size (same lesson as field25519's lowerings)."""
    n = state.shape[2]

    def w_at(w_arr, j):
        sl = lax.dynamic_slice(w_arr, (0, j, 0), (2, 1, n))
        return sl[0, 0], sl[1, 0]

    def body(t, carry):
        a, b, c, d, e, f, g, h, w_arr = carry
        idx = t % 16
        scheduled = _add_many(
            _small_sigma1(w_at(w_arr, (t - 2) % 16)),
            w_at(w_arr, (t - 7) % 16),
            _small_sigma0(w_at(w_arr, (t - 15) % 16)),
            w_at(w_arr, idx),
        )
        cur = w_at(w_arr, idx)
        in_first16 = t < 16
        wt = (
            jnp.where(in_first16, cur[0], scheduled[0]),
            jnp.where(in_first16, cur[1], scheduled[1]),
        )
        w_arr = lax.dynamic_update_slice(
            w_arr, jnp.stack([wt[0], wt[1]])[:, None, :], (0, idx, 0)
        )
        k = (_K_HI[t], _K_LO[t])
        t1 = _add_many(h, _big_sigma1(e), _ch(e, f, g), k, wt)
        t2 = _add2(_big_sigma0(a), _maj(a, b, c))
        return (_add2(t1, t2), a, b, c, _add2(d, t1), e, f, g, w_arr)

    init = tuple((state[0, i], state[1, i]) for i in range(8))
    carry = (*init, block)
    a, b, c, d, e, f, g, h, _ = lax.fori_loop(0, 80, body, carry)
    out = [a, b, c, d, e, f, g, h]
    hi = jnp.stack([_add2(out[i], (state[0, i], state[1, i]))[0] for i in range(8)])
    lo = jnp.stack([_add2(out[i], (state[0, i], state[1, i]))[1] for i in range(8)])
    return jnp.stack([hi, lo])


def hash_blocks_core(blocks, nblocks):
    """Hash N variable-length pre-padded messages: blocks uint32[B, 2, 16, N]
    (B = max block count), nblocks int32[N]. Lanes stop updating once their
    block count is reached. Returns uint32[2, 8, N]."""
    n = blocks.shape[3]
    init = iv_state(n)

    def body(i, st):
        new = compress(st, blocks[i])
        active = (i < nblocks)[None, None, :]
        return jnp.where(active, new, st)

    return lax.fori_loop(0, blocks.shape[0], body, init)


@functools.lru_cache(maxsize=None)
def _hash_jit(bmax: int, n: int):
    return jax.jit(hash_blocks_core)


def blocks_for(lens: np.ndarray) -> np.ndarray:
    """Message byte lengths -> SHA-512 block counts (0x80 + 16-byte len)."""
    return ((lens + 17 + 127) // 128).astype(np.int32)


def rows_by_length(lens: np.ndarray) -> list:
    """[(length, rows)] over the distinct lengths, ascending: `rows` is
    slice(None) where every row has the one length, else that length's row
    indices. One stable sort and one split, so no pass a length and no
    Python-level step a row."""
    if len(lens) == 0:
        return []
    if lens.min() == lens.max():
        return [(lens[0], slice(None))]
    order = np.argsort(lens, kind="stable")
    ordered = lens[order]
    cuts = np.nonzero(np.diff(ordered))[0] + 1
    return list(zip(ordered[np.r_[0, cuts]], np.split(order, cuts)))


def write_padding(buf: np.ndarray, by_length: list) -> None:
    """Write the FIPS 180-4 pad into buf uint8[n, B*128] rows holding
    messages whose byte lengths rows_by_length() grouped: for each length
    the 0x80 terminator and the 128-bit big-endian bit length at the end of
    its last block are constant columns, written into that length's rows
    (messages here are < 2^53 bits so the low 64 bits suffice). Shared by
    the generic packer and the ed25519 challenge packer so the padding
    rules live once."""
    for ln, rows in by_length:
        end = int(blocks_for(ln)) * 128
        buf[rows, ln] = 0x80
        buf[rows, end - 8 : end] = np.frombuffer((int(ln) * 8).to_bytes(8, "big"), np.uint8)


def pack_messages512(msgs: list[bytes]):
    """Pad + pack variable-length messages into SHA-512 blocks:
    (uint32[B, 2, 16, N], int32[N]). Vectorized where it counts: one
    big byte buffer, length-grouped padding writes."""
    n = len(msgs)
    lens = np.fromiter(map(len, msgs), np.int64, n)
    nblocks = blocks_for(lens)
    bmax = int(nblocks.max()) if n else 1
    buf = np.zeros((n, bmax * 128), np.uint8)
    for i, m in enumerate(msgs):
        buf[i, : lens[i]] = np.frombuffer(m, np.uint8)
    write_padding(buf, rows_by_length(lens))
    words = buf.view(">u4").reshape(n, bmax, 32).astype(np.uint32)
    # -> [B, 2(hi/lo), 16, N]: 64-bit word t is words[.., 2t](hi), 2t+1(lo)
    hi = words[:, :, 0::2]
    lo = words[:, :, 1::2]
    out = np.stack([hi, lo], axis=1).transpose(2, 1, 3, 0)
    return np.ascontiguousarray(out), nblocks


def bswap32(x):
    """Device-side 32-bit byte swap (uint32 arrays)."""
    return (
        ((x & jnp.uint32(0xFF)) << 24)
        | ((x & jnp.uint32(0xFF00)) << 8)
        | ((x >> 8) & jnp.uint32(0xFF00))
        | (x >> 24)
    )


def digest_to_le_words(state):
    """Device-side uint32[2, 8, N] SHA-512 state -> int32[16, N] little-endian
    uint32 words of the 64-byte digest stream (the layout
    unpack.digest_words_to_digits consumes). Word 2t is the byte-swapped hi
    half of 64-bit word t, word 2t+1 the byte-swapped lo half."""
    hi = bswap32(state[0])  # [8, N]
    lo = bswap32(state[1])
    out = jnp.stack([hi, lo], axis=1).reshape(16, -1)  # interleave hi/lo
    return out.astype(jnp.int32)


def digest_words_to_arr(state: np.ndarray) -> np.ndarray:
    """uint32[2, 8, N] -> uint8[N, 64] big-endian digests."""
    st = np.asarray(state)
    inter = np.empty((st.shape[2], 16), np.uint32)
    inter[:, 0::2] = st[0].T
    inter[:, 1::2] = st[1].T
    return np.ascontiguousarray(inter.astype(">u4")).view(np.uint8).reshape(-1, 64)


def sha512_batch(msgs: list[bytes]) -> list[bytes]:
    """Hash a batch of messages on device; returns 64-byte digests."""
    if not msgs:
        return []
    blocks, nblocks = pack_messages512(msgs)
    st = _hash_jit(blocks.shape[0], blocks.shape[3])(blocks, nblocks)
    return [bytes(r) for r in digest_words_to_arr(np.asarray(st))]
