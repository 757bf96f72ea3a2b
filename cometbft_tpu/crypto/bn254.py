"""BLS signatures on BN254 (reference fork addition: crypto/bn254/bn254.go).

The fork adds a zk-friendly BLS key type: pubkey = compressed G1 point
(32 bytes), signature = uncompressed G2 point (128 bytes), hash-to-field via
Keccak-256 (bn254.go:120-151), sign = [sk]·H(m) on G2 (bn254.go:46-53), verify
= pairing check e(pk, H(m)) == e(G1, sig). No batch verification — bn254 is
deliberately absent from crypto/batch (crypto/batch/batch.go:12-17).

Pure-Python BN254: Fp/Fp2/Fp6/Fp12 towers, optimal ate pairing. Verification
is not in the consensus hot path (bn254 validators verify per-vote, like
secp256k1 would), so Python-int speed is acceptable on the host tier.
"""

from __future__ import annotations

import hashlib
import os

from cometbft_tpu import crypto
from cometbft_tpu.crypto import tmhash

KEY_TYPE = "bn254"
PUB_KEY_SIZE = 32
PRIV_KEY_SIZE = 64  # fr scalar (32) || compressed pubkey (32), mirrors sizePrivateKey
SIGNATURE_SIZE = 128
# Compressed G2 (gnark-style: x only, 2-bit flag selecting the y root).
# Per-vote signatures stay uncompressed on the hot path; the 64-byte form is
# the wire encoding of the per-block aggregate under CMTPU_AGG_COMMITS.
SIGNATURE_SIZE_COMPRESSED = 64

PRIV_KEY_NAME = "tendermint/PrivKeyBn254"
PUB_KEY_NAME = "tendermint/PubKeyBn254"

# BN254 (alt_bn128) parameters
P = 21888242871839275222246405745257275088696311157297823662689037894645226208583
R = 21888242871839275222246405745257275088548364400416034343698204186575808495617

# G1 generator
G1 = (1, 2)

# G2 generator (from EIP-197 / gnark-crypto); Fp2 elements as (a0, a1) = a0 + a1*u
G2 = (
    (
        10857046999023057135944570762232829481370756359578518086990519993285655852781,
        11559732032986387107991004021392285783925812861821192530917403151452391805634,
    ),
    (
        8495653923123431417604973247489272438418190587263600148770280649306958101930,
        4082367875863433681332203403145435568316851327593401208105741076214120093531,
    ),
)

# ---------------------------------------------------------------------------
# Fp2 arithmetic: elements (a, b) = a + b*u with u^2 = -1


def f2_add(x, y):
    return ((x[0] + y[0]) % P, (x[1] + y[1]) % P)


def f2_sub(x, y):
    return ((x[0] - y[0]) % P, (x[1] - y[1]) % P)


def f2_neg(x):
    return ((-x[0]) % P, (-x[1]) % P)


def f2_mul(x, y):
    a = x[0] * y[0] % P
    b = x[1] * y[1] % P
    c = (x[0] + x[1]) * (y[0] + y[1]) % P
    return ((a - b) % P, (c - a - b) % P)


def f2_sqr(x):
    return f2_mul(x, x)


def f2_inv(x):
    t = pow((x[0] * x[0] + x[1] * x[1]) % P, P - 2, P)
    return (x[0] * t % P, (-x[1] * t) % P)


def f2_scalar(x, k):
    return (x[0] * k % P, x[1] * k % P)


F2_ONE = (1, 0)
F2_ZERO = (0, 0)

# twist curve G2: y^2 = x^3 + b', b' = b / xi where xi = 9 + u
B = 3
XI = (9, 1)
B2 = f2_mul((B, 0), f2_inv(XI))

# ---------------------------------------------------------------------------
# Curve arithmetic (affine, generic over the field ops)


def _g1_add(p, q):
    if p is None:
        return q
    if q is None:
        return p
    x1, y1 = p
    x2, y2 = q
    if x1 == x2:
        if (y1 + y2) % P == 0:
            return None
        lam = 3 * x1 * x1 * pow(2 * y1, P - 2, P) % P
    else:
        lam = (y2 - y1) * pow(x2 - x1, P - 2, P) % P
    x3 = (lam * lam - x1 - x2) % P
    return (x3, (lam * (x1 - x3) - y1) % P)


def _g1_mul(k, p):
    r = None
    while k > 0:
        if k & 1:
            r = _g1_add(r, p)
        p = _g1_add(p, p)
        k >>= 1
    return r


def _g2_add(p, q):
    if p is None:
        return q
    if q is None:
        return p
    x1, y1 = p
    x2, y2 = q
    if x1 == x2:
        if f2_add(y1, y2) == F2_ZERO:
            return None
        lam = f2_mul(f2_scalar(f2_sqr(x1), 3), f2_inv(f2_scalar(y1, 2)))
    else:
        lam = f2_mul(f2_sub(y2, y1), f2_inv(f2_sub(x2, x1)))
    x3 = f2_sub(f2_sub(f2_sqr(lam), x1), x2)
    return (x3, f2_sub(f2_mul(lam, f2_sub(x1, x3)), y1))


def _g2_mul(k, p):
    r = None
    while k > 0:
        if k & 1:
            r = _g2_add(r, p)
        p = _g2_add(p, p)
        k >>= 1
    return r


def _g2_neg(p):
    if p is None:
        return None
    return (p[0], f2_neg(p[1]))


# ---------------------------------------------------------------------------
# Fp12 tower for pairing: Fp12 = Fp2[w] / (w^6 - xi), elements as 6-tuples of
# Fp2 coefficients (c0..c5) for c0 + c1 w + ... + c5 w^5.


def f12_mul(a, b):
    res = [F2_ZERO] * 12
    for i in range(6):
        if a[i] == F2_ZERO:
            continue
        for j in range(6):
            if b[j] == F2_ZERO:
                continue
            t = f2_mul(a[i], b[j])
            res[i + j] = f2_add(res[i + j], t)
    out = list(res[:6])
    for k in range(6, 12):
        if res[k] != F2_ZERO:
            out[k - 6] = f2_add(out[k - 6], f2_mul(res[k], XI))
    return tuple(out)


F12_ONE = (F2_ONE,) + (F2_ZERO,) * 5


def f12_conj_like_inv(a):
    """Generic Fp12 inversion via linear algebra is costly; use
    exponentiation: a^(p^12 - 2) is overkill. Instead solve with the tower:
    treat Fp12 as Fp6[w]/(w^2 - v) — here we just use Gaussian elimination on
    the 12x12 multiplication matrix over Fp (simple, runs rarely)."""
    # Build matrix M where M @ x = e1 represents a * x = 1.
    # Basis: (1, w, ..., w^5) over Fp2 → 12 Fp coordinates (re, im per coeff).
    import itertools

    def to_vec(el12):
        v = []
        for c in el12:
            v.extend([c[0], c[1]])
        return v

    # column j of M = a * basis_j
    cols = []
    for j in range(6):
        for im in range(2):
            basis = [F2_ZERO] * 6
            basis[j] = (0, 1) if im else (1, 0)
            cols.append(to_vec(f12_mul(a, tuple(basis))))
    n = 12
    M = [[cols[j][i] % P for j in range(n)] for i in range(n)]
    rhs = [1] + [0] * (n - 1)
    # Gaussian elimination mod P
    for col in range(n):
        piv = next(r for r in range(col, n) if M[r][col] != 0)
        M[col], M[piv] = M[piv], M[col]
        rhs[col], rhs[piv] = rhs[piv], rhs[col]
        inv = pow(M[col][col], P - 2, P)
        M[col] = [x * inv % P for x in M[col]]
        rhs[col] = rhs[col] * inv % P
        for r in range(n):
            if r != col and M[r][col]:
                f = M[r][col]
                M[r] = [(M[r][c] - f * M[col][c]) % P for c in range(n)]
                rhs[r] = (rhs[r] - f * rhs[col]) % P
    out = tuple((rhs[2 * j], rhs[2 * j + 1]) for j in range(6))
    return out


def f12_pow(a, e):
    r = F12_ONE
    while e > 0:
        if e & 1:
            r = f12_mul(r, a)
        a = f12_mul(a, a)
        e >>= 1
    return r


# Line evaluations for the Miller loop. G2 points are on the twist; we map the
# G1 point into the Fp12 embedding: for the D-twist with w^6 = xi,
# x' = x_t / w^2, y' = y_t / w^3 — equivalently multiply line coefficients by
# powers of w. We use the standard "untwist" evaluation:
#   line(P=(xp, yp)) for tangent/chord at Q=(xq, yq) in Fp2:
#   l = yp * 1 - lam * xp * w - (yq - lam*xq) * w^3  ... using the mapping
# below (coefficients placed so that all arithmetic stays in the tower).


def _line(q1, q2, p_pt):
    """Evaluate the line through q1,q2 (or tangent if equal) at G1 point p.
    Returns an Fp12 element. Embedding: G2 (x,y) ↦ (x/w^2, y/w^3)."""
    xp, yp = p_pt
    x1, y1 = q1
    x2, y2 = q2
    if x1 == x2 and y1 == y2:
        lam_num = f2_scalar(f2_sqr(x1), 3)
        lam_den = f2_scalar(y1, 2)
    elif x1 == x2:
        # Vertical line x = x1; under the untwist (x_t ↦ x_t·w^2) evaluated at
        # P: l = xp - x1·w^2. The lost constant factors are killed by the
        # final exponentiation.
        coeffs = [F2_ZERO] * 6
        coeffs[0] = (xp % P, 0)
        coeffs[2] = f2_neg(x1)
        return tuple(coeffs)
    else:
        lam_num = f2_sub(y2, y1)
        lam_den = f2_sub(x2, x1)
    # Untwist Q ↦ (x·w^2, y·w^3) so the slope is λ'·w with λ' = lam_num/lam_den
    # in Fp2. Line at P, scaled by lam_den (removed by final exp):
    #   l = yp·lam_den − lam_num·xp·w + (lam_num·x1 − y1·lam_den)·w^3
    coeffs = [F2_ZERO] * 6
    coeffs[0] = f2_scalar(lam_den, yp)
    coeffs[1] = f2_neg(f2_scalar(lam_num, xp))
    coeffs[3] = f2_sub(f2_mul(lam_num, x1), f2_mul(y1, lam_den))
    return tuple(coeffs)


# BN parameter for BN254
_T = 4965661367192848881
_ATE_LOOP = 6 * _T + 2


def miller_loop(q, p_pt):
    """Miller loop f_{6t+2,Q}(P) with the final Frobenius adjustment lines."""
    if q is None or p_pt is None:
        return F12_ONE
    f = F12_ONE
    t_pt = q
    bits = bin(_ATE_LOOP)[3:]  # skip MSB
    for bit in bits:
        f = f12_mul(f12_mul(f, f), _line(t_pt, t_pt, p_pt))
        t_pt = _g2_add(t_pt, t_pt)
        if bit == "1":
            f = f12_mul(f, _line(t_pt, q, p_pt))
            t_pt = _g2_add(t_pt, q)
    # Frobenius adjustment: Q1 = pi_p(Q), Q2 = -pi_p^2(Q)
    q1 = _g2_frobenius(q)
    q2 = _g2_neg(_g2_frobenius(q1))
    f = f12_mul(f, _line(t_pt, q1, p_pt))
    t_pt = _g2_add(t_pt, q1)
    f = f12_mul(f, _line(t_pt, q2, p_pt))
    return f


# Frobenius on the twist: (x, y) → (x^p * gamma12, y^p * gamma13)
_GAMMA12 = None
_GAMMA13 = None


def _f2_conj(x):
    return (x[0], (-x[1]) % P)


def _f2_pow(x, e):
    r = F2_ONE
    while e > 0:
        if e & 1:
            r = f2_mul(r, x)
        x = f2_sqr(x)
        e >>= 1
    return r


def _init_frobenius():
    global _GAMMA12, _GAMMA13
    _GAMMA12 = _f2_pow(XI, (P - 1) // 3)
    _GAMMA13 = _f2_pow(XI, (P - 1) // 2)


_init_frobenius()


def _g2_frobenius(q):
    if q is None:
        return None
    x, y = q
    return (f2_mul(_f2_conj(x), _GAMMA12), f2_mul(_f2_conj(y), _GAMMA13))


def final_exponentiation(f):
    """f^((p^12-1)/r) — plain big-exponent form (slow but simple & correct)."""
    e = (P**12 - 1) // R
    return f12_pow(f, e)


def pairing(p_pt, q) -> tuple:
    """e(P, Q) for P in G1, Q in G2 (on the twist)."""
    return final_exponentiation(miller_loop(q, p_pt))


def pairing_check(pairs) -> bool:
    """prod e(P_i, Q_i) == 1."""
    f = F12_ONE
    for p_pt, q in pairs:
        f = f12_mul(f, miller_loop(q, p_pt))
    return final_exponentiation(f) == F12_ONE


# ---------------------------------------------------------------------------
# Hash-to-curve. The reference hashes to the curve via gnark's MapToG2
# (bn254.go:120-151 hashedMessage); scalar·generator constructions are
# forgeable (the dlog of H(m) would be public), so we hash to an x-coordinate
# by try-and-increment, then clear the twist cofactor c2 = 2p − r to land in
# the r-torsion. Unknown-dlog and deterministic.

_G2_COFACTOR = 2 * P - R


def _hash_to_g2(msg: bytes):
    base = hashlib.sha3_256(msg).digest()
    ctr = 0
    while True:
        h0 = hashlib.sha3_256(base + b"\x00" + ctr.to_bytes(4, "big")).digest()
        h1 = hashlib.sha3_256(base + b"\x01" + ctr.to_bytes(4, "big")).digest()
        x = (int.from_bytes(h0, "big") % P, int.from_bytes(h1, "big") % P)
        y2 = f2_add(f2_mul(f2_sqr(x), x), B2)
        y = _f2_sqrt(y2)
        if y is not None:
            # choose the lexicographically smaller root for determinism
            if (y[1], y[0]) > ((P - y[1]) % P, (P - y[0]) % P):
                y = f2_neg(y)
            q = _g2_mul(_G2_COFACTOR, (x, y))
            if q is not None:
                return q
        ctr += 1


def _f2_sqrt(a):
    """Square root in Fp2 (p ≡ 3 mod 4): complex method; None if non-residue."""
    if a == F2_ZERO:
        return F2_ZERO
    a0, a1 = a
    if a1 == 0:
        r = pow(a0, (P + 1) // 4, P)
        if r * r % P == a0:
            return (r, 0)
        # sqrt(a0) = sqrt(-a0) * sqrt(-1); -1 is a non-residue so a0 non-residue
        # means -a0 is a residue: root is purely imaginary.
        r = pow((-a0) % P, (P + 1) // 4, P)
        if r * r % P == (-a0) % P:
            return (0, r)
        return None
    # norm = a0^2 + a1^2 must be a residue
    norm = (a0 * a0 + a1 * a1) % P
    n = pow(norm, (P + 1) // 4, P)
    if n * n % P != norm:
        return None
    for sign in (1, -1):
        alpha = (a0 + sign * n) % P * pow(2, P - 2, P) % P
        x0 = pow(alpha, (P + 1) // 4, P)
        if x0 * x0 % P != alpha:
            continue
        x1 = a1 * pow(2 * x0 % P, P - 2, P) % P
        cand = (x0, x1)
        if f2_sqr(cand) == a:
            return cand
    return None


# ---------------------------------------------------------------------------
# Point serialization: gnark-style compressed G1 (32 bytes, big-endian x with
# 2-bit flag in the top bits) and uncompressed G2 (128 bytes).

_MASK = 0b11 << 6
_COMPRESSED_SMALLEST = 0b10 << 6
_COMPRESSED_LARGEST = 0b11 << 6
_COMPRESSED_INFINITY = 0b01 << 6


def g1_compress(p) -> bytes:
    if p is None:
        out = bytearray(32)
        out[0] = _COMPRESSED_INFINITY
        return bytes(out)
    x, y = p
    out = bytearray(x.to_bytes(32, "big"))
    neg_y = (P - y) % P
    flag = _COMPRESSED_LARGEST if y > neg_y else _COMPRESSED_SMALLEST
    out[0] |= flag
    return bytes(out)


def g1_decompress(b: bytes):
    if len(b) != 32:
        raise ValueError("bad G1 compressed length")
    flag = b[0] & _MASK
    if flag == _COMPRESSED_INFINITY:
        return None
    x_bytes = bytes([b[0] & ~_MASK]) + b[1:]
    x = int.from_bytes(x_bytes, "big")
    if x >= P:
        raise ValueError("G1 x out of range")
    y2 = (pow(x, 3, P) + B) % P
    y = pow(y2, (P + 1) // 4, P)
    if y * y % P != y2:
        raise ValueError("not on curve")
    if flag == _COMPRESSED_LARGEST:
        if y < (P - y) % P:
            y = (P - y) % P
    else:
        if y > (P - y) % P:
            y = (P - y) % P
    return (x, y)


def g2_marshal(q) -> bytes:
    """Uncompressed G2: x.a1 || x.a0 || y.a1 || y.a0 big-endian (gnark order)."""
    if q is None:
        return b"\x00" * 128
    (x0, x1), (y0, y1) = q[0], q[1]
    return (
        x1.to_bytes(32, "big")
        + x0.to_bytes(32, "big")
        + y1.to_bytes(32, "big")
        + y0.to_bytes(32, "big")
    )


def g2_compress(q) -> bytes:
    """Compressed G2: x.a1 || x.a0 big-endian (64 bytes) with the gnark
    2-bit flag in the top bits of the first byte selecting which square
    root of y² the point carries (lexicographically larger = (y1, y0) >
    (-y1, -y0), matching gnark's Fp2 ordering)."""
    if q is None:
        out = bytearray(64)
        out[0] = _COMPRESSED_INFINITY
        return bytes(out)
    (x0, x1), (y0, y1) = q[0], q[1]
    out = bytearray(x1.to_bytes(32, "big") + x0.to_bytes(32, "big"))
    neg = ((P - y1) % P, (P - y0) % P)
    flag = _COMPRESSED_LARGEST if (y1, y0) > neg else _COMPRESSED_SMALLEST
    out[0] |= flag
    return bytes(out)


def g2_decompress(b: bytes):
    if len(b) != 64:
        raise ValueError("bad G2 compressed length")
    flag = b[0] & _MASK
    if flag == _COMPRESSED_INFINITY:
        if (b[0] & ~_MASK) or any(b[1:]):
            raise ValueError("bad G2 infinity encoding")
        return None
    if flag not in (_COMPRESSED_SMALLEST, _COMPRESSED_LARGEST):
        raise ValueError("bad G2 compression flag")
    x1 = int.from_bytes(bytes([b[0] & ~_MASK]) + b[1:32], "big")
    x0 = int.from_bytes(b[32:64], "big")
    if x0 >= P or x1 >= P:
        raise ValueError("G2 coordinate out of range")
    x = (x0, x1)
    y2 = f2_add(f2_mul(f2_sqr(x), x), B2)
    y = _f2_sqrt(y2)
    if y is None:
        raise ValueError("G2 x not on curve")
    larger = (y[1], y[0]) > ((P - y[1]) % P, (P - y[0]) % P)
    if (flag == _COMPRESSED_LARGEST) != larger:
        y = f2_neg(y)
    q = (x, y)
    if _g2_mul(R, q) is not None:
        raise ValueError("G2 point not in r-torsion subgroup")
    return q


def g2_unmarshal(b: bytes):
    if len(b) == SIGNATURE_SIZE_COMPRESSED:
        return g2_decompress(b)
    if len(b) != 128:
        raise ValueError("bad G2 length")
    if b == b"\x00" * 128:
        return None
    x1 = int.from_bytes(b[0:32], "big")
    x0 = int.from_bytes(b[32:64], "big")
    y1 = int.from_bytes(b[64:96], "big")
    y0 = int.from_bytes(b[96:128], "big")
    if any(v >= P for v in (x0, x1, y0, y1)):
        raise ValueError("G2 coordinate out of range")
    q = ((x0, x1), (y0, y1))
    # on-curve check
    lhs = f2_sqr(q[1])
    rhs = f2_add(f2_mul(f2_sqr(q[0]), q[0]), B2)
    if lhs != rhs:
        raise ValueError("G2 point not on curve")
    # subgroup check: the twist has cofactor 2p − r, so on-curve points outside
    # the r-torsion exist; reject them (gnark's SetBytes does the same).
    if _g2_mul(R, q) is not None:
        raise ValueError("G2 point not in r-torsion subgroup")
    return q


# ---------------------------------------------------------------------------


class PubKey(crypto.PubKey):
    def __init__(self, data: bytes):
        if len(data) != PUB_KEY_SIZE:
            raise ValueError(f"bn254 pubkey must be {PUB_KEY_SIZE} bytes")
        self._bytes = bytes(data)

    def address(self) -> bytes:
        return tmhash.sum_truncated(self._bytes)

    def bytes(self) -> bytes:
        return self._bytes

    def verify_signature(self, msg: bytes, sig: bytes) -> bool:
        """Pairing check e(pk, H(m)) == e(G1, sig) ⇔
        e(-pk, H(m)) · e(G1, sig) == 1."""
        if len(sig) != SIGNATURE_SIZE:
            return False
        try:
            pk = g1_decompress(self._bytes)
            s = g2_unmarshal(sig)
            if pk is None or s is None:
                return False
            hm = _hash_to_g2(msg)
            neg_pk = (pk[0], (P - pk[1]) % P)
            return pairing_check([(neg_pk, hm), (G1, s)])
        except (ValueError, TypeError):
            return False

    def type(self) -> str:
        return KEY_TYPE


class PrivKey(crypto.PrivKey):
    def __init__(self, data: bytes):
        if len(data) not in (32, PRIV_KEY_SIZE):
            raise ValueError("bn254 privkey must be 32 or 64 bytes")
        self._scalar_bytes = bytes(data[:32])
        self._scalar = int.from_bytes(self._scalar_bytes, "big") % R
        if self._scalar == 0:
            raise ValueError("invalid bn254 scalar")
        self._pub = PubKey(g1_compress(_g1_mul(self._scalar, G1)))

    def bytes(self) -> bytes:
        return self._scalar_bytes + self._pub.bytes()

    def sign(self, msg: bytes) -> bytes:
        """[sk]·H(m) on G2, uncompressed (bn254.go:46-53)."""
        hm = _hash_to_g2(msg)
        return g2_marshal(_g2_mul(self._scalar, hm))

    def pub_key(self) -> PubKey:
        return self._pub

    def type(self) -> str:
        return KEY_TYPE


def gen_priv_key() -> PrivKey:
    while True:
        raw = os.urandom(32)
        if int.from_bytes(raw, "big") % R != 0:
            return PrivKey(raw)


# ===========================================================================
# Fast host-tier pairing path (ISSUE 9).
#
# Everything above is the reference-faithful slow form and stays untouched —
# `verify_signature_slow` below preserves it verbatim as the ground truth
# the fast path is tested against. The fast path
# changes the arithmetic, never the decision:
#
#  * `final_exponentiation_fast` — easy part by conjugation/Frobenius + one
#    Fp12 inversion, hard part by the Scott et al. addition chain in the BN
#    parameter t (3 exponentiations by t instead of one 2790-bit ladder).
#    Computes exactly f^((p^12-1)/r), asserted value-identical in tests.
#  * `multi_miller_loop` — one shared Fp12 squaring per iteration across
#    every pair of a whole commit (the squaring of a product is the product
#    of squarings, so n Miller loops share their doubling schedule).
#  * One shared final exponentiation per CHECK, not per signature — the
#    aggregate-BLS shape from arXiv:2302.00418.
#
# Line-function scalings live in Fp2, a proper subfield, so they are killed
# by the final exponentiation: check results are bit-identical to the slow
# engine (tested over valid, corrupted, and wrong-key signatures).


def f12_sqr(a):
    return f12_mul(a, a)


def f12_inv(a):
    return f12_conj_like_inv(a)


def _f12_conj6(a):
    """a^(p^6): the nontrivial automorphism fixing Fp6 = Fp2[w^2] — negates
    the odd-power-of-w coefficients. Equals a^-1 inside the cyclotomic
    subgroup (post-easy-part), which is what the hard part exploits."""
    return tuple(c if i % 2 == 0 else f2_neg(c) for i, c in enumerate(a))


# gamma[k][i] = xi^(i * (p^k - 1) / 6): the twist constants of the Fp12
# Frobenius x -> x^(p^k) on the w^i basis.
_F12_GAMMA = {
    k: tuple(_f2_pow(XI, i * (P**k - 1) // 6) for i in range(6)) for k in (1, 2, 3)
}


def _f12_frobenius(a, k):
    """a^(p^k) for k in {1,2,3}: coefficient-wise Fp2 Frobenius (conjugation
    when k is odd) times the basis twist gamma[k][i]."""
    g = _F12_GAMMA[k]
    if k % 2:
        return tuple(f2_mul(_f2_conj(c), g[i]) for i, c in enumerate(a))
    return tuple(f2_mul(c, g[i]) for i, c in enumerate(a))


def final_exponentiation_fast(f):
    """f^((p^12-1)/r), value-identical to `final_exponentiation`.

    Easy part (p^6-1)(p^2+1) via conjugation + one Fp12 inversion; hard
    part (p^4-p^2+1)/r via the Scott-Benger-Charlemagne-Perez-Kachisa
    addition chain in t (exact exponent, not a multiple)."""
    # easy part: m = f^((p^6-1)(p^2+1))
    t = f12_mul(_f12_conj6(f), f12_inv(f))  # f^(p^6-1)
    m = f12_mul(_f12_frobenius(t, 2), t)  # ^(p^2+1)
    # hard part: m^((p^4-p^2+1)/r); conj6 = inverse in the cyclotomic group
    fu = f12_pow(m, _T)
    fu2 = f12_pow(fu, _T)
    fu3 = f12_pow(fu2, _T)
    y0 = f12_mul(
        f12_mul(_f12_frobenius(m, 1), _f12_frobenius(m, 2)), _f12_frobenius(m, 3)
    )
    y1 = _f12_conj6(m)
    y2 = _f12_frobenius(fu2, 2)
    y3 = _f12_conj6(_f12_frobenius(fu, 1))
    y4 = _f12_conj6(f12_mul(fu, _f12_frobenius(fu2, 1)))
    y5 = _f12_conj6(fu2)
    y6 = _f12_conj6(f12_mul(fu3, _f12_frobenius(fu3, 1)))
    t0 = f12_mul(f12_mul(f12_sqr(y6), y4), y5)
    t1 = f12_mul(f12_mul(y3, y5), t0)
    t0 = f12_mul(t0, y2)
    t1 = f12_mul(f12_sqr(t1), t0)
    t1 = f12_sqr(t1)
    t0 = f12_mul(t1, y1)
    t1 = f12_mul(t1, y0)
    t0 = f12_mul(f12_sqr(t0), t1)
    return t0


def multi_miller_loop(pairs):
    """prod_i f_{6t+2,Q_i}(P_i) with ONE shared Fp12 squaring per iteration.

    Bit-for-bit the same doubling/addition schedule as `miller_loop` run per
    pair, but the accumulator is the product, so the per-iteration squaring
    (the only O(n)-independent cost) is paid once for the whole batch."""
    live = [(p_pt, q) for p_pt, q in pairs if p_pt is not None and q is not None]
    if not live:
        return F12_ONE
    f = F12_ONE
    ts = [q for _, q in live]
    bits = bin(_ATE_LOOP)[3:]
    for bit in bits:
        f = f12_sqr(f)
        for i, (p_pt, q) in enumerate(live):
            f = f12_mul(f, _line(ts[i], ts[i], p_pt))
            ts[i] = _g2_add(ts[i], ts[i])
            if bit == "1":
                f = f12_mul(f, _line(ts[i], q, p_pt))
                ts[i] = _g2_add(ts[i], q)
    for i, (p_pt, q) in enumerate(live):
        q1 = _g2_frobenius(q)
        q2 = _g2_neg(_g2_frobenius(q1))
        f = f12_mul(f, _line(ts[i], q1, p_pt))
        ts[i] = _g2_add(ts[i], q1)
        f = f12_mul(f, _line(ts[i], q2, p_pt))
    return f


def pairing_check_fast(pairs) -> bool:
    """prod e(P_i, Q_i) == 1 via the shared-squaring Miller loop and fast
    final exponentiation. Decision-identical to `pairing_check`."""
    return final_exponentiation_fast(multi_miller_loop(pairs)) == F12_ONE


# -- hash-to-G2 cache --------------------------------------------------------
# Vote sign bytes recur across engines (vote admission, commit verify, light
# client, crosscheck); try-and-increment + cofactor clearing is ~5 ms, so a
# small LRU removes the dominant per-message cost of re-verification.

_HM_CACHE: dict[bytes, tuple] = {}
_HM_CACHE_MAX = 8192


def _hash_to_g2_cached(msg: bytes):
    key = bytes(msg)
    hit = _HM_CACHE.get(key)
    if hit is not None:
        return hit
    q = _hash_to_g2(key)
    if len(_HM_CACHE) >= _HM_CACHE_MAX:
        for k in list(_HM_CACHE)[: _HM_CACHE_MAX // 4]:
            _HM_CACHE.pop(k, None)
    _HM_CACHE[key] = q
    return q


def verify_signature_slow(pub: bytes, msg: bytes, sig: bytes) -> bool:
    """Today's scalar pairing, verbatim (pre-ISSUE-9 PubKey.verify_signature
    body): plain Miller loops, 2790-bit final-exponentiation ladder, uncached
    hash-to-G2. The fast-path equivalence tests check against THIS."""
    if len(sig) != SIGNATURE_SIZE:
        return False
    try:
        pk = g1_decompress(pub)
        s = g2_unmarshal(sig)
        if pk is None or s is None:
            return False
        hm = _hash_to_g2(msg)
        neg_pk = (pk[0], (P - pk[1]) % P)
        return pairing_check([(neg_pk, hm), (G1, s)])
    except (ValueError, TypeError):
        return False


# ---------------------------------------------------------------------------
# Aggregate BLS (ISSUE 9 tentpole): one G2 point for a whole commit.
#
# Trust model: sound for DISTINCT per-signer messages under proof-of-
# possession of the registered validator keys (the standard BLS deployment
# assumption; a rogue pubkey registered as pk' = [x]G1 - pk_victim could
# otherwise claim the victim co-signed an identical message). Documented in
# ops/DESIGN.md; CMTPU_AGG_COMMITS stays default-off.


def aggregate_signatures(sigs) -> bytes:
    """G2 sum of BLS signatures -> one 128-byte uncompressed point.

    Every input is fully validated (on-curve + r-torsion) by g2_unmarshal;
    a malformed signature raises ValueError rather than silently poisoning
    the aggregate."""
    total = None
    for s in sigs:
        total = _g2_add(total, g2_unmarshal(bytes(s)))
    return g2_marshal(total)


def aggregate_signatures_compressed(sigs) -> bytes:
    """Same G2 sum, emitted in the 64-byte compressed wire form the block
    commit carries under CMTPU_AGG_COMMITS."""
    total = None
    for s in sigs:
        total = _g2_add(total, g2_unmarshal(bytes(s)))
    return g2_compress(total)


def verify_aggregate(pub_keys, msgs, agg_sig: bytes) -> bool:
    """e(G1, agg) == prod_i e(pk_i, H(m_i)) as n+1 Miller loops sharing one
    final exponentiation. pub_keys are compressed G1 bytes, msgs the
    per-signer (distinct) messages."""
    if len(pub_keys) != len(msgs) or not pub_keys:
        return False
    try:
        s = g2_unmarshal(bytes(agg_sig))
    except (ValueError, TypeError):
        return False
    pairs = []
    for pb, m in zip(pub_keys, msgs):
        try:
            pk = g1_decompress(bytes(pb))
        except (ValueError, TypeError):
            return False
        if pk is None:
            return False
        pairs.append(((pk[0], (P - pk[1]) % P), _hash_to_g2_cached(m)))
    pairs.append((G1, s))
    return pairing_check_fast(pairs)


def verify_aggregate_slow(pub_keys, msgs, agg_sig: bytes) -> bool:
    """Decision-identical slow-arithmetic form of verify_aggregate (plain
    per-pair Miller loops + the 2790-bit final-exp ladder) — the anchor the
    equivalence tests compare against."""
    if len(pub_keys) != len(msgs) or not pub_keys:
        return False
    try:
        s = g2_unmarshal(bytes(agg_sig))
        pairs = []
        for pb, m in zip(pub_keys, msgs):
            pk = g1_decompress(bytes(pb))
            if pk is None:
                return False
            pairs.append(((pk[0], (P - pk[1]) % P), _hash_to_g2(m)))
        pairs.append((G1, s))
        return pairing_check(pairs)
    except (ValueError, TypeError):
        return False


# ---------------------------------------------------------------------------
# Proof of possession (round 10). Plain BLS aggregation is vulnerable to the
# rogue-key attack: a registrant who publishes pk' = pk_rogue − Σ pk_honest
# can forge an aggregate "signed" by the whole set. The standard defence
# (Ristenpart–Yilek; draft-irtf-cfrg-bls-signature §3.3) is to demand, at
# KEY REGISTRATION time, a signature over the key's own serialization under
# a domain-separation tag no consensus message can collide with — consensus
# sign-bytes are length-prefixed protobuf of SignedMsgType ≥ 1, so this
# ASCII prefix is unreachable from any vote or proposal.

POP_DST = b"CMTPU-BN254-POP-V1|"


def pop_sign_bytes(pub_key_bytes: bytes) -> bytes:
    return POP_DST + bytes(pub_key_bytes)


def prove_possession(priv: "PrivKey") -> bytes:
    """64-byte compressed G2 proof that the holder knows the secret scalar
    behind their published pubkey — required in genesis for bn254 keys."""
    sig = priv.sign(pop_sign_bytes(priv.pub_key().bytes()))
    return g2_compress(g2_unmarshal(sig))


def verify_possession(pub_key_bytes: bytes, pop: bytes) -> bool:
    """One fast pairing check; accepts either G2 wire form. Never raises —
    malformed input is simply an invalid proof."""
    if len(pop) not in (SIGNATURE_SIZE, SIGNATURE_SIZE_COMPRESSED):
        return False
    try:
        pk = g1_decompress(bytes(pub_key_bytes))
        s = g2_unmarshal(bytes(pop))
        if pk is None or s is None:
            return False
        hm = _hash_to_g2_cached(pop_sign_bytes(pub_key_bytes))
        neg_pk = (pk[0], (P - pk[1]) % P)
        return pairing_check_fast([(neg_pk, hm), (G1, s)])
    except (ValueError, TypeError):
        return False


# ---------------------------------------------------------------------------
# Batched per-signature verification with a bitmap (the BatchVerifier
# protocol). A naive product check is UNSOUND for bitmap semantics — two bad
# signatures can cancel (e(G1, s+d) * e(G1, s'-d) preserves the product) —
# so each signature is weighted by an unpredictable 64-bit scalar derived
# Fiat-Shamir-style from the whole batch:
#     prod_i e([w_i](-pk_i), H(m_i)) * e(G1, sum_i [w_i] s_i) == 1
# A cancellation would need the adversary to predict w_i before fixing the
# signatures that determine them. On failure the check bisects to the exact
# bad lanes (the per-sig bitmap the verify_commit error path needs).


def _batch_weights(pubs, msgs, sigs):
    h = hashlib.sha256()
    for col in (pubs, msgs, sigs):
        for x in col:
            h.update(len(x).to_bytes(4, "big"))
            h.update(x)
    seed = h.digest()
    return [
        int.from_bytes(
            hashlib.sha256(seed + i.to_bytes(4, "big")).digest()[:8], "big"
        )
        | 1
        for i in range(len(pubs))
    ]


def batch_verify_signatures(pubs, msgs, sigs) -> tuple[bool, list]:
    """(all_ok, per-sig bitmap) over raw byte columns — the host multi-
    pairing engine behind Bn254HostBackend. Structurally invalid entries are
    False lanes and never poison the rest."""
    n = len(pubs)
    bits = [False] * n
    parsed: dict[int, tuple] = {}
    for i in range(n):
        try:
            pk = g1_decompress(bytes(pubs[i]))
            s = g2_unmarshal(bytes(sigs[i]))
            if pk is None or s is None:
                continue
        except (ValueError, TypeError):
            continue
        parsed[i] = (
            (pk[0], (P - pk[1]) % P),
            _hash_to_g2_cached(bytes(msgs[i])),
            s,
        )
    ws = _batch_weights(
        [bytes(p) for p in pubs], [bytes(m) for m in msgs], [bytes(s) for s in sigs]
    )

    def check(idxs) -> bool:
        pairs = []
        agg = None
        for i in idxs:
            neg_pk, hm, s = parsed[i]
            pairs.append((_g1_mul(ws[i], neg_pk), hm))
            agg = _g2_add(agg, _g2_mul(ws[i], s))
        pairs.append((G1, agg))
        return pairing_check_fast(pairs)

    stack = [sorted(parsed)] if parsed else []
    while stack:
        idxs = stack.pop()
        if not idxs:
            continue
        if check(idxs):
            for i in idxs:
                bits[i] = True
        elif len(idxs) == 1:
            bits[idxs[0]] = False
        else:
            mid = len(idxs) // 2
            stack.append(idxs[:mid])
            stack.append(idxs[mid:])
    return (n > 0 and all(bits)), bits


# ---------------------------------------------------------------------------
# Verification backends: the same VerifyBackend shape the ed25519 chain
# speaks ((pubs, msgs, sigs) byte columns -> (ok, bitmap)), so the generic
# CoalescingScheduler / ResilientBackend / ChaosBackend stack applies
# unchanged. The bn254 chain is its OWN instance — the ed25519 singleton
# cannot verify bn254 triples — with the same env knobs.


class Bn254HostBackend:
    """Randomized-weight multi-pairing with shared final exponentiation."""

    name = "bn254-host"

    def batch_verify(self, pubs, msgs, sigs):
        return batch_verify_signatures(pubs, msgs, sigs)

    def aggregate_verify(self, pubs, msgs, agg_sig) -> bool:
        return verify_aggregate(pubs, msgs, agg_sig)

    def merkle_root(self, leaves):
        from cometbft_tpu.crypto import merkle

        return merkle.hash_from_byte_slices(list(leaves))

    def ping(self) -> bool:
        return True


class Bn254ScalarBackend:
    """The chain anchor: independent scalar pairing checks, one per
    signature — no shared state with the batched engines, so it is valid
    crosscheck ground truth for them."""

    name = "bn254-cpu"

    def batch_verify(self, pubs, msgs, sigs):
        bits = []
        for p, m, s in zip(pubs, msgs, sigs):
            bits.append(_scalar_verify(bytes(p), bytes(m), bytes(s)))
        return (len(bits) > 0 and all(bits)), bits

    def aggregate_verify(self, pubs, msgs, agg_sig) -> bool:
        # The aggregate has no per-sig form; the anchor's check is the
        # exact-integer host multi-pairing (same decision as the slow
        # reference ladder, asserted by the equivalence tests).
        return verify_aggregate(pubs, msgs, agg_sig)

    def merkle_root(self, leaves):
        from cometbft_tpu.crypto import merkle

        return merkle.hash_from_byte_slices(list(leaves))

    def ping(self) -> bool:
        return True


def _scalar_verify(pub: bytes, msg: bytes, sig: bytes) -> bool:
    """One pairing check (fast arithmetic, scalar semantics): the per-sig
    anchor. Decision-identical to verify_signature_slow."""
    if len(sig) != SIGNATURE_SIZE:
        return False
    try:
        pk = g1_decompress(pub)
        s = g2_unmarshal(sig)
        if pk is None or s is None:
            return False
        hm = _hash_to_g2_cached(msg)
        neg_pk = (pk[0], (P - pk[1]) % P)
        return pairing_check_fast([(neg_pk, hm), (G1, s)])
    except (ValueError, TypeError):
        return False


def build_bn254_chain():
    """bn254-device -> bn254-host -> scalar-cpu anchor, with the same
    CMTPU_FAULTS chaos wrapping rules as supervisor.build_chain (non-anchor
    tiers only; cpu-only + faults inserts a chaos-wrapped host primary)."""
    from cometbft_tpu.sidecar.chaos import ChaosBackend, faults_from_env

    tiers = []
    try:
        from cometbft_tpu.ops import bn254_kernel as _bk

        if _bk.device_available():
            tiers.append(("bn254-device", _bk.Bn254DeviceBackend()))
    except Exception:
        pass  # no jax / kernel import failure: host tiers still serve
    tiers.append(("bn254-host", Bn254HostBackend()))
    faults = faults_from_env()
    if faults:
        seed = int(os.environ.get("CMTPU_FAULTS_SEED", "0") or 0)
        tiers = [
            (name, ChaosBackend(b, faults, seed=seed + i))
            for i, (name, b) in enumerate(tiers)
        ]
    tiers.append(("cpu", Bn254ScalarBackend()))
    return tiers


_backend = None
_backend_lock = None


def get_bn254_backend():
    """Process singleton mirroring sidecar.backend.get_backend(): under
    CMTPU_BACKEND=auto the supervised chain behind the coalescer; any other
    choice serves the bare host multi-pairing engine (always CPU-capable,
    fails loudly — never a silent downgrade to per-sig verification)."""
    global _backend, _backend_lock
    if _backend is not None:
        return _backend
    import threading

    if _backend_lock is None:
        _backend_lock = threading.Lock()
    with _backend_lock:
        if _backend is not None:
            return _backend
        choice = os.environ.get("CMTPU_BACKEND", "auto").strip() or "auto"
        if choice == "auto":
            from cometbft_tpu.sidecar.scheduler import CoalescingScheduler
            from cometbft_tpu.sidecar.supervisor import ResilientBackend

            chain = ResilientBackend(build_bn254_chain())
            if os.environ.get("CMTPU_COALESCE", "1") != "0":
                _backend = CoalescingScheduler(chain)
            else:
                _backend = chain
        else:
            _backend = Bn254HostBackend()
    return _backend


def set_bn254_backend(b) -> None:
    """Test hook (None re-resolves lazily on next use)."""
    global _backend
    old = _backend
    _backend = b
    if old is not None and hasattr(old, "close") and old is not b:
        try:
            old.close()
        except Exception:
            pass


# -- verified-triple cache (same contract as ed25519._verified) --------------

_VERIFIED_MAX = int(os.environ.get("CMTPU_VERIFY_CACHE_MAX", "") or 131072)
_verified: dict[tuple, None] = {}


def _verified_put(key: tuple) -> None:
    if key in _verified:
        del _verified[key]
    elif len(_verified) >= _VERIFIED_MAX:
        for k in list(_verified)[: max(1, _VERIFIED_MAX // 4)]:
            _verified.pop(k, None)
    _verified[key] = None


class BatchVerifier(crypto.BatchVerifier):
    """crypto.BatchVerifier over bn254 triples: verified-triple LRU filter,
    within-batch dedup, the supervised bn254 chain, per-sig scalar fallback
    on ChainExhausted — the same lifecycle ed25519.BatchVerifier has."""

    def __init__(self):
        self._pubs: list[bytes] = []
        self._msgs: list[bytes] = []
        self._sigs: list[bytes] = []

    def add(self, key, msg: bytes, sig: bytes) -> None:
        if not isinstance(key, PubKey):
            raise TypeError("bn254.BatchVerifier requires bn254 public keys")
        if len(sig) != SIGNATURE_SIZE:
            raise ValueError(f"bn254 signature must be {SIGNATURE_SIZE} bytes")
        self._pubs.append(key.bytes())
        self._msgs.append(bytes(msg))
        self._sigs.append(bytes(sig))

    def count(self) -> int:
        return len(self._pubs)

    def verify(self) -> tuple[bool, list]:
        n = len(self._pubs)
        if n == 0:
            return False, []
        bits: list = [None] * n
        first_at: dict[tuple, int] = {}
        sub_idx: list[int] = []
        for i in range(n):
            key = (self._pubs[i], self._sigs[i], self._msgs[i])
            if key in _verified:
                bits[i] = True
            elif key in first_at:
                bits[i] = first_at[key]  # lane alias, resolved below
            else:
                first_at[key] = i
                sub_idx.append(i)
        if sub_idx:
            sub_pubs = [self._pubs[i] for i in sub_idx]
            sub_msgs = [self._msgs[i] for i in sub_idx]
            sub_sigs = [self._sigs[i] for i in sub_idx]
            from cometbft_tpu.sidecar.supervisor import ChainExhausted

            try:
                _, sub_bits = get_bn254_backend().batch_verify(
                    sub_pubs, sub_msgs, sub_sigs
                )
                if len(sub_bits) != len(sub_idx):
                    raise ValueError("backend returned wrong-shaped bitmap")
            except ChainExhausted:
                sub_bits = [
                    _scalar_verify(p, m, s)
                    for p, m, s in zip(sub_pubs, sub_msgs, sub_sigs)
                ]
            for j, i in enumerate(sub_idx):
                bits[i] = bool(sub_bits[j])
                if bits[i]:
                    _verified_put((self._pubs[i], self._sigs[i], self._msgs[i]))
        out = []
        for b in bits:
            if isinstance(b, bool):
                out.append(b)
            else:  # alias lane: int index of the first occurrence
                out.append(bool(bits[b]))
        return all(out), out

# The name commit verification uses via crypto.batch's registry.
Bn254BatchVerifier = BatchVerifier
