"""GF(2^255-19) arithmetic vectorized for TPU (device tier of crypto/ed25519).

Representation: 17 little-endian limbs of radix 2^15, stacked int32[17, N]
with the batch N in the TPU lane dimension. 17*15 = 255 exactly, so the
wrap-around factor is just 19 (2^255 = 19 mod p) — no oversized fold
constants. Limbs carry a LOOSE invariant: every public op returns limbs in
[0, 2^15 + 95], which keeps all intermediates exact:

  - products:         (2^15+95)^2          < 2^30.2  (int32, no overflow)
  - split halves:     lo < 2^15, hi < 2^15.2
  - column sums:      <= 17 * 2^15.2       < 2^19.3  (int32)
  - 19-fold:          < 2^23.7             (int32)

The multiply has TWO lowerings, chosen from the platform at trace time:

  - STACKED (accelerators): the schoolbook convolution as ~35 chunky HLO ops
    — pad x to 33 limbs, stack 17 rolls into a Toeplitz band [17, 33, N],
    broadcast-multiply by y, 15-bit-split, reduce over the j axis, 19-fold,
    stacked carries. 289 limb products in a graph small enough that the
    full verify ladder compiles in seconds (one [N]-wide op per limb
    product, the form this replaced, took XLA:TPU >8 MINUTES: pass time is
    superlinear in a ~75k-op loop body).
  - COMPACT (CPU): the [17,17,N] product tensor + one-hot f32 accumulation
    matmul (~15 HLO ops per multiply). XLA:CPU's compile time is quadratic
    in elementwise-fusion size, so the CPU backend (tests, the
    8-virtual-device dryrun, the host fallback) gets the small-graph form.

Carries are one shift-mask pass per call, ~4 array ops on the stacked
[17, N] form (_carry). This is the TPU-native replacement for
curve25519-voi's assembly field element (reference backend of
crypto/ed25519/ed25519.go:27-29).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

LIMBS = 17
LIMB_BITS = 15
MASK = 0x7FFF

P_INT = 2**255 - 19
D_INT = (-121665 * pow(121666, P_INT - 2, P_INT)) % P_INT
TWO_D_INT = (2 * D_INT) % P_INT
SQRT_M1_INT = pow(2, (P_INT - 1) // 4, P_INT)


def int_to_limbs(v: int) -> np.ndarray:
    """Python int -> int32[17] little-endian 15-bit limbs (host)."""
    return np.array([(v >> (LIMB_BITS * i)) & MASK for i in range(LIMBS)], np.int32)


def limbs_to_int(a) -> int:
    """int32[17] (or [17,1]) -> Python int (host, for tests)."""
    a = np.asarray(a).reshape(LIMBS)
    return sum(int(a[i]) << (LIMB_BITS * i) for i in range(LIMBS))


_P_LIMBS = [int(x) for x in int_to_limbs(P_INT)]
# 4p per-limb: every limb >= 4*(2^15-19) > 2^15+95, so a - b + 4p stays
# non-negative limb-wise under the loose invariant.
_FOUR_P = np.array([4 * x for x in _P_LIMBS], np.int32).reshape(LIMBS, 1)


def const_fe(v: int) -> np.ndarray:
    """Field constant as int32[17, 1] (broadcasts over the batch). A numpy
    literal: jnp consumers convert on use."""
    return int_to_limbs(v).reshape(LIMBS, 1)


def fe_from_bytes_le(b: np.ndarray) -> np.ndarray:
    """uint8[N, 32] little-endian -> int32[17, N] limbs, using bits 0..254
    (bit 255 — the point-compression sign — is dropped; extract it first)."""
    b = np.ascontiguousarray(b, dtype=np.uint8)
    bits = np.unpackbits(b, axis=1, bitorder="little")[:, :255]  # [N, 255]
    pows = (1 << np.arange(LIMB_BITS, dtype=np.int32)).astype(np.int32)
    limbs = bits.reshape(-1, LIMBS, LIMB_BITS).astype(np.int32) @ pows  # [N, 17]
    return np.ascontiguousarray(limbs.T)


def fe_to_bytes_le(x) -> np.ndarray:
    """int32[17, N] canonical limbs -> uint8[N, 32] (host)."""
    a = np.asarray(x).T  # [N, 17]
    bits = np.zeros((a.shape[0], 256), np.uint8)
    for l in range(LIMBS):
        for i in range(LIMB_BITS):
            bits[:, l * LIMB_BITS + i] = (a[:, l] >> i) & 1
    return np.packbits(bits, axis=1, bitorder="little")


def _carry(x: jnp.ndarray) -> jnp.ndarray:
    """One parallel carry pass as ~4 array ops on the stacked [17, N] form:
    split at 15 bits, carry up one limb, top carry wraps to limb 0 with
    factor 19."""
    hi = x >> LIMB_BITS
    lo = x & MASK
    wrap = jnp.concatenate([19 * hi[LIMBS - 1 :], hi[: LIMBS - 1]], axis=0)
    return lo + wrap


# -- stacked (Toeplitz-band) multiply: the accelerators' lowering ------------


def _mul_stacked(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """Schoolbook multiply as ~35 chunky HLO ops: z_col[c] = sum_j
    x[c-j] * y[j] via a rolled Toeplitz band. Products are split at 15 bits
    BEFORE the j-reduction (raw column sums of 2^30.2 products would
    overflow int32), the high halves land one column up, and columns 17..33
    fold back with factor 19 (2^255 = 19 mod p). Bounds: split sums
    < 2^19.3, folded columns < 2^24.5, two carry passes restore the loose
    invariant."""
    n = x.shape[1]
    xp = jnp.concatenate([x, jnp.zeros((LIMBS - 1, n), jnp.int32)], axis=0)
    band = jnp.stack([jnp.roll(xp, j, axis=0) for j in range(LIMBS)])
    p = band * y[:, None, :]  # [17 (j), 33 (col), N], each < 2^30.2
    lo = (p & MASK).sum(axis=0)  # [33, N], < 17 * 2^15
    hi = (p >> LIMB_BITS).sum(axis=0)  # [33, N], < 17 * 2^15.2
    zrow = jnp.zeros((1, n), jnp.int32)
    cols = jnp.concatenate([lo, zrow], axis=0) + jnp.concatenate([zrow, hi], axis=0)
    folded = cols[:LIMBS] + 19 * cols[LIMBS:]
    return _carry(_carry(folded))


# -- compact (matmul-accumulation) multiply for the CPU backend --------------

# One-hot accumulation matrix: entry [k, j*17+i] = 1 where the low half of
# product x_i*y_j lands in column i+j, and [k, 289 + j*17+i] = 1 where the
# high half lands in column i+j+1. One f32 matmul replaces ~580 adds; exact
# because every UNWEIGHTED column sum stays under 2^21 (f32 integer-exact
# range) — the 19-fold happens afterwards in int32, where a folded column
# can exceed 2^24 and would NOT be f32-exact.
_ACC = np.zeros((2 * LIMBS, 2 * LIMBS * LIMBS), np.float32)
for _j in range(LIMBS):
    for _i in range(LIMBS):
        _ACC[_i + _j, _j * LIMBS + _i] = 1.0
        _ACC[_i + _j + 1, LIMBS * LIMBS + _j * LIMBS + _i] = 1.0


def _mul_compact(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    n = x.shape[1]
    p = x[None, :, :] * y[:, None, :]  # [j, i, N] int32, < 2^30.2
    lo = (p & MASK).astype(jnp.float32).reshape(LIMBS * LIMBS, n)
    hi = (p >> LIMB_BITS).astype(jnp.float32).reshape(LIMBS * LIMBS, n)
    flat = jnp.concatenate([lo, hi], axis=0)  # [578, N]
    cols = lax.dot_general(
        jnp.asarray(_ACC),
        flat,
        (((1,), (0,)), ((), ())),
        precision=lax.Precision.HIGHEST,
    ).astype(jnp.int32)  # [34, N]
    folded = cols[:LIMBS] + 19 * cols[LIMBS:]
    return _carry(_carry(folded))


_ACCEL: bool | None = None


def _is_accel() -> bool:
    """True on non-CPU backends (matched by exclusion, so any accelerator
    platform takes the stacked lowering). The backend is sampled once per
    process — mixed-backend processes would need per-trace plumbing this
    framework doesn't require."""
    global _ACCEL
    if _ACCEL is None:
        _ACCEL = jax.default_backend() != "cpu"
    return _ACCEL


def _mode() -> str:
    """Lowering for the current trace (see module docstring)."""
    return "stacked" if _is_accel() else "compact"


def fe_mul(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """z = x*y mod p under the loose invariant."""
    if _mode() == "stacked":
        return _mul_stacked(x, y)
    return _mul_compact(x, y)


def fe_sq(x: jnp.ndarray) -> jnp.ndarray:
    return fe_mul(x, x)


def fe_add(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    return _carry(x + y)


def fe_sub(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    return _carry(x + jnp.asarray(_FOUR_P) - y)


def fe_neg(x: jnp.ndarray) -> jnp.ndarray:
    return _carry(jnp.asarray(_FOUR_P) - x)


def _seq_carry(x: jnp.ndarray) -> jnp.ndarray:
    """Sequential carry chain with wrap — tightens limbs to < 2^15 except a
    tiny residue in limb 0; used only inside freeze."""
    cols = [x[k] for k in range(LIMBS)]
    out = []
    c = None
    for k in range(LIMBS):
        t = cols[k] if c is None else cols[k] + c
        out.append(t & MASK)
        c = t >> LIMB_BITS
    out[0] = out[0] + 19 * c
    return jnp.stack(out)


def fe_freeze(x: jnp.ndarray) -> jnp.ndarray:
    """Canonical residue in [0, p). Two sequential passes bring the value
    below 2^255 + 19; two conditional subtractions of p finish."""
    x = _seq_carry(_seq_carry(x))
    for _ in range(2):
        cols = [x[k] - _P_LIMBS[k] for k in range(LIMBS)]
        out = []
        b = None
        for k in range(LIMBS):
            t = cols[k] if b is None else cols[k] + b
            out.append(t & MASK)
            b = t >> LIMB_BITS  # arithmetic shift: 0 or -1 (borrow)
        ge = b == 0  # no final borrow -> x >= p -> keep subtracted form
        x = jnp.stack([jnp.where(ge, out[k], x[k]) for k in range(LIMBS)])
    return x


def fe_is_zero(x: jnp.ndarray) -> jnp.ndarray:
    """bool[N]: x == 0 mod p (freezes internally)."""
    f = fe_freeze(x)
    acc = f[0]
    for k in range(1, LIMBS):
        acc = acc | f[k]
    return acc == 0


def fe_eq(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    return fe_is_zero(fe_sub(x, y))


def fe_parity(x: jnp.ndarray) -> jnp.ndarray:
    """bool[N]: least significant bit of the canonical residue."""
    return (fe_freeze(x)[0] & 1) == 1


def fe_select(mask: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """where(mask, a, b) with mask [N] broadcast over limbs."""
    return jnp.where(mask[None, :], a, b)


def _sq_n(x: jnp.ndarray, n: int) -> jnp.ndarray:
    """n repeated squarings; rolled into fori_loop to bound program size."""
    if n <= 4:
        for _ in range(n):
            x = fe_sq(x)
        return x
    return lax.fori_loop(0, n, lambda _, t: fe_sq(t), x)


def fe_pow2523(z: jnp.ndarray) -> jnp.ndarray:
    """z^((p-5)/8) = z^(2^252 - 3), the square-root exponent for point
    decompression (crypto/ed25519 decoding). Standard 2^k-1 ladder chain."""
    t0 = fe_sq(z)                      # z^2
    t1 = fe_mul(z, _sq_n(t0, 2))       # z^9
    t0 = fe_mul(t0, t1)                # z^11
    t0 = fe_mul(t1, fe_sq(t0))         # z^31   = z^(2^5 - 1)
    t0 = fe_mul(_sq_n(t0, 5), t0)      # 2^10 - 1
    t1 = fe_mul(_sq_n(t0, 10), t0)     # 2^20 - 1
    t2 = fe_mul(_sq_n(t1, 20), t1)     # 2^40 - 1
    t1 = fe_mul(_sq_n(t2, 10), t0)     # 2^50 - 1
    t2 = fe_mul(_sq_n(t1, 50), t1)     # 2^100 - 1
    t2 = fe_mul(_sq_n(t2, 100), t2)    # 2^200 - 1
    t1 = fe_mul(_sq_n(t2, 50), t1)     # 2^250 - 1
    return fe_mul(_sq_n(t1, 2), z)     # 2^252 - 3


def fe_invert(z: jnp.ndarray) -> jnp.ndarray:
    """z^(p-2) = z^(2^255 - 21) via the same ladder (for point compression)."""
    t0 = fe_sq(z)                      # z^2
    t1 = fe_mul(z, _sq_n(t0, 2))       # z^9
    t1b = fe_mul(t0, t1)               # z^11
    t0 = fe_mul(t1, fe_sq(t1b))        # z^31
    t0 = fe_mul(_sq_n(t0, 5), t0)      # 2^10 - 1
    t1 = fe_mul(_sq_n(t0, 10), t0)     # 2^20 - 1
    t2 = fe_mul(_sq_n(t1, 20), t1)     # 2^40 - 1
    t1 = fe_mul(_sq_n(t2, 10), t0)     # 2^50 - 1
    t2 = fe_mul(_sq_n(t1, 50), t1)     # 2^100 - 1
    t2 = fe_mul(_sq_n(t2, 100), t2)    # 2^200 - 1
    t1 = fe_mul(_sq_n(t2, 50), t1)     # 2^250 - 1
    return fe_mul(_sq_n(t1, 5), t1b)   # 2^255 - 21
