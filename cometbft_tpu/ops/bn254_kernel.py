"""Device multi-pairing for BN254 BLS commits.

The Miller loop is the batchable part of a pairing: every (G1, G2) lane of a
commit walks the same 65-bit ate ladder, so one `lax.scan` body — traced once
— runs all lanes in lockstep, data-parallel over the lane axis and shardable
over the local mesh exactly like the ed25519 bucket programs. Per-lane Miller
values come back to the host, which multiplies the *real* lanes (padding is
simply skipped — no device masking), runs ONE shared fast final
exponentiation, and compares against F12_ONE.

Field representation: Fp elements are 13 limbs of 21 bits in float64
(13*21 = 273 bits > 254). All arithmetic is exact: products of |limb| < 2^26
inputs stay under 2^52; reduction is outer-product columns -> hi/lo split ->
one-hot einsum scatter to 26 columns -> sequential signed carry -> high-column
fold against precomputed 2^(21k) mod P rows -> three carry+fold rounds whose
top carries shrink 2^25 -> 2^6 -> <=1, leaving |limb| < 2^22. Every multi-term
sum is condensed back under the 2^26 mul bound before feeding another
multiply. Host reconstruction sum(l_i * 2^21i) mod P is exact for loose and
negative limbs alike.

G2 runs Jacobian (no inversions); line coefficients are the standard sparse
(c0, c1*w, c3*w^3) untwist form scaled by Z^6 (doubling) / Z^3 (addition) —
Fp2 scalar factors are killed by the final exponentiation, asserted
decision-identical to crypto.bn254.pairing_check by the agg tests.

float64 is exact on XLA:CPU (and the virtual-mesh tests pin CPU); real TPU
f64 is emulated and slow, which is why `device_available()` is opt-in via
CMTPU_BN254_DEVICE=1.
Keccak/SHA hash-to-field stays host-side; this kernel has a single
stacked-limb lowering (field25519's two are ed25519-field concerns).
"""

from __future__ import annotations

import os
import threading
from functools import lru_cache

from cometbft_tpu.crypto import bn254 as _b

BASE = 1 << 21
NLIMB = 13
NCOL = 2 * NLIMB
P = _b.P

# Ate-loop bits, MSB skipped — the same constant ladder the host loop walks.
_BITS = [1 if c == "1" else 0 for c in bin(_b._ATE_LOOP)[3:]]

_LADDER = [8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096, 8192]
MAX_LANES = _LADDER[-1]

_counters = {"dispatches": 0, "lanes": 0, "sharded_dispatches": 0}
_counters_lock = threading.Lock()


def to_limbs(x: int) -> list:
    """254-bit int -> 13 limbs of 21 bits (little-endian)."""
    out = []
    for _ in range(NLIMB):
        out.append(float(x & (BASE - 1)))
        x >>= 21
    return out


def from_limbs(limbs) -> int:
    """Loose (possibly negative) limbs -> exact int mod P."""
    acc = 0
    for i, v in enumerate(limbs):
        acc += int(round(float(v))) << (21 * i)
    return acc % P


# Fold tables (plain python — device copies built lazily in _tables()).
_M_ROWS = [to_limbs(pow(2, 21 * (NLIMB + k), P)) for k in range(NLIMB)]
_C26 = to_limbs(pow(2, 21 * NCOL, P))
_K13 = to_limbs(pow(2, 21 * NLIMB, P))


def device_available() -> bool:
    """Opt-in only: the Miller scan is a heavy compile and must never be
    probed at node start. Bench/tests set CMTPU_BN254_DEVICE=1 for the
    device arm."""
    return os.environ.get("CMTPU_BN254_DEVICE", "") == "1"


def mesh_width() -> int:
    try:
        from cometbft_tpu.ops import ed25519_kernel as _ek

        return max(1, int(_ek.mesh_width()))
    except Exception:
        return 1


def _mesh_floor() -> int:
    try:
        from cometbft_tpu.ops import ed25519_kernel as _ek

        return max(1, int(_ek.mesh_floor()))
    except Exception:
        return 1


def bucket_for(n: int) -> int:
    """Pow2-ish ladder rounded up to mesh-width multiples at/above the mesh
    floor — the same shape as ed25519_kernel.bucket_for."""
    n = max(1, int(n))
    b = next((x for x in _LADDER if x >= n), MAX_LANES)
    w = mesh_width()
    if w > 1 and b >= _mesh_floor():
        b = ((b + w - 1) // w) * w
    return b


def counters() -> dict:
    with _counters_lock:
        return dict(_counters)


class _Tables:
    pass


@lru_cache(maxsize=1)
def _tables():
    import jax
    import jax.numpy as jnp
    import numpy as np

    t = _Tables()
    t.jax, t.jnp, t.np = jax, jnp, np
    with _x64(jax):
        f64 = np.float64
        e0 = np.zeros((NLIMB, NLIMB, NCOL), dtype=f64)
        e1 = np.zeros((NLIMB, NLIMB, NCOL), dtype=f64)
        for i in range(NLIMB):
            for j in range(NLIMB):
                e0[i, j, i + j] = 1.0
                e1[i, j, i + j + 1] = 1.0
        t.e0 = jnp.asarray(e0)
        t.e1 = jnp.asarray(e1)
        t.m = jnp.asarray(np.array(_M_ROWS, dtype=f64))
        t.c26 = jnp.asarray(np.array(_C26, dtype=f64))
        t.k13 = jnp.asarray(np.array(_K13, dtype=f64))
        t.bits = jnp.asarray(np.array(_BITS, dtype=f64))
        # f12 squaring: 21 symmetric (i, j) products, cross terms weight 2
        pairs21 = [(i, j) for i in range(6) for j in range(i, 6)]
        s21 = np.zeros((len(pairs21), 12), dtype=f64)
        for k, (i, j) in enumerate(pairs21):
            s21[k, i + j] = 2.0 if i != j else 1.0
        t.i21 = jnp.asarray(np.array([i for i, _ in pairs21]))
        t.j21 = jnp.asarray(np.array([j for _, j in pairs21]))
        t.s21 = jnp.asarray(s21)
        # sparse line mul: f[i] * c_j for the line's w^0, w^1, w^3 slots
        slots = (0, 1, 3)
        trip18 = [(i, jj) for i in range(6) for jj in range(3)]
        s18 = np.zeros((len(trip18), 12), dtype=f64)
        for k, (i, jj) in enumerate(trip18):
            s18[k, i + slots[jj]] = 1.0
        t.i18 = jnp.asarray(np.array([i for i, _ in trip18]))
        t.jsel18 = jnp.asarray(np.array([jj for _, jj in trip18]))
        t.s18 = jnp.asarray(s18)
    return t


def _x64(jax):
    """Confine float64 to this kernel's traces — the rest of the process
    keeps jax's default x32 promotion rules."""
    return jax.enable_x64(True)


# ---------------------------------------------------------------------------
# Fp (13x21-bit f64 limbs)


def _carry_round(x, t, fold=None):
    """One parallel carry round: every limb drops its multiple of BASE into
    its neighbor simultaneously (floor carries handle negatives; exact for
    |value| < 2^52). With `fold`, the top limb's carry re-enters at 2^273
    mod P; without, it is returned for the caller to fold."""
    jnp = t.jnp
    c = jnp.floor(x * (1.0 / BASE))
    low = x - c * BASE
    y = low + jnp.concatenate(
        [jnp.zeros_like(c[..., :1]), c[..., :-1]], axis=-1
    )
    if fold is not None:
        return y + c[..., -1:] * fold
    return y, c[..., -1]


def _fp_condense(x, t):
    """|limb| < 2^46 -> |limb| < 2^23 via four parallel carry+fold rounds.
    The top column's fold contribution is tiny (K13's top limb is < 4), so
    successive top carries shrink 2^25 -> 2^6 -> 2^4 -> <=1 and the lateral
    carries collapse with them."""
    for _ in range(4):
        x = _carry_round(x, t, fold=t.k13)
    return x


def _fp_mul(a, b, t):
    """Exact modular multiply, |input limb| < 2^26 -> |output limb| < 2^23."""
    jnp = t.jnp
    prod = a[..., :, None] * b[..., None, :]  # < 2^52, exact
    hi = jnp.floor(prod * (1.0 / BASE))
    lo = prod - hi * BASE
    cols = jnp.einsum("...ij,ijk->...k", lo, t.e0) + jnp.einsum(
        "...ij,ijk->...k", hi, t.e1
    )
    # One parallel round takes the 26 columns from < 2^38.5 to < 2^21.1 —
    # small enough that the high-half fold stays under 2^46.
    limbs, top = _carry_round(cols, t)
    low, high = limbs[..., :NLIMB], limbs[..., NLIMB:]
    red = high @ t.m + top[..., None] * t.c26
    return _fp_condense(low + red, t)


# ---------------------------------------------------------------------------
# Packed Fp2: arrays (..., 2, 13), u^2 = -1. Every multiply in a stage is
# stacked into ONE batched _fp_mul: a Miller bit is ~200 field muls, and
# issuing them as individual subgraphs made XLA chew minutes of compile —
# batched, the body is a handful of wide einsums.


def _f2_mul_many(xs, ys, t):
    """Karatsuba Fp2 multiply for k independent pairs in one _fp_mul call.
    An Fp operand rides as (re, 0) — one wasted lane beats a second path."""
    jnp = t.jnp
    k = len(xs)
    X = jnp.stack(xs, axis=1)  # (n, k, 2, 13)
    Y = jnp.stack(ys, axis=1)
    L = jnp.concatenate(
        [X[:, :, 0], X[:, :, 1], X[:, :, 0] + X[:, :, 1]], axis=1
    )
    R = jnp.concatenate(
        [Y[:, :, 0], Y[:, :, 1], Y[:, :, 0] + Y[:, :, 1]], axis=1
    )
    prod = _fp_mul(L, R, t)
    a, b, c = prod[:, :k], prod[:, k : 2 * k], prod[:, 2 * k :]
    out = jnp.stack([a - b, c - a - b], axis=2)
    return [out[:, i] for i in range(k)]


def _f2_cond_many(xs, t):
    jnp = t.jnp
    v = _fp_condense(jnp.stack(xs, axis=1), t)
    return [v[:, i] for i in range(len(xs))]


# ---------------------------------------------------------------------------
# Fp12 = Fp2[w]/(w^6 - xi): packed (n, 6, 2, 13), same basis as crypto.bn254


def _fold_cond(re, im, t):
    """Scatter residues 6..11 back through w^6 = xi = 9 + u, then condense.
    re/im: (n, 12, 13)."""
    jnp = t.jnp
    r6 = re[:, :6] + 9 * re[:, 6:] - im[:, 6:]
    i6 = im[:, :6] + re[:, 6:] + 9 * im[:, 6:]
    return _fp_condense(jnp.stack([r6, i6], axis=2), t)


def _f12_sqr(F, t):
    """Schoolbook squaring with symmetry: 21 Fp2 products (cross terms
    carry weight 2 in the scatter matrix), one batched mul."""
    jnp = t.jnp
    aL, aR = F[:, t.i21], F[:, t.j21]  # (n, 21, 2, 13)
    L = jnp.concatenate(
        [aL[:, :, 0], aL[:, :, 1], aL[:, :, 0] + aL[:, :, 1]], axis=1
    )
    R = jnp.concatenate(
        [aR[:, :, 0], aR[:, :, 1], aR[:, :, 0] + aR[:, :, 1]], axis=1
    )
    prod = _fp_mul(L, R, t)
    a, b, c = prod[:, :21], prod[:, 21:42], prod[:, 42:]
    re = jnp.einsum("nkl,km->nml", a - b, t.s21)
    im = jnp.einsum("nkl,km->nml", c - a - b, t.s21)
    return _fold_cond(re, im, t)


def _f12_sparse(F, line, t):
    """F * line for a line sparse at w^0, w^1, w^3: 18 Fp2 products, one
    batched mul."""
    jnp = t.jnp
    C = jnp.stack(line, axis=1)  # (n, 3, 2, 13)
    aL, aR = F[:, t.i18], C[:, t.jsel18]
    L = jnp.concatenate(
        [aL[:, :, 0], aL[:, :, 1], aL[:, :, 0] + aL[:, :, 1]], axis=1
    )
    R = jnp.concatenate(
        [aR[:, :, 0], aR[:, :, 1], aR[:, :, 0] + aR[:, :, 1]], axis=1
    )
    prod = _fp_mul(L, R, t)
    a, b, c = prod[:, :18], prod[:, 18:36], prod[:, 36:]
    re = jnp.einsum("nkl,km->nml", a - b, t.s18)
    im = jnp.einsum("nkl,km->nml", c - a - b, t.s18)
    return _fold_cond(re, im, t)


# ---------------------------------------------------------------------------
# G2 Jacobian steps with scaled sparse lines (Fp2 scalings die in the final
# exponentiation; asserted against the host affine loop by the agg tests).
# Stages batch every multiply whose operands are already available.


def _dbl_and_line(X, Y, Z, xp2, yp2, t):
    """Double T=(X,Y,Z) and evaluate the tangent at (xp, yp), scaled Z^6:
    c0 = 2*Y*Z^3*yp, c1 = -3*X^2*Z^2*xp, c3 = 3*X^3 - 2*Y^2."""
    A, Bv, Z2 = _f2_mul_many([X, Y, Z], [X, Y, Z], t)
    Cv, XB, Z3p, YZ = _f2_mul_many(
        [Bv, X + Bv, Z2, Y], [Bv, X + Bv, Z, Z], t
    )
    D, E = _f2_cond_many([2 * (XB - A - Cv), 3 * A], t)
    F2, EZ2, AX, YZ3 = _f2_mul_many([E, E, A, Y], [E, Z2, X, Z3p], t)
    X3, c3, Z3 = _f2_cond_many([F2 - 2 * D, 3 * AX - 2 * Bv, 2 * YZ], t)
    EDX, c0h, c1h = _f2_mul_many([E, YZ3, EZ2], [D - X3, yp2, xp2], t)
    Y3 = _f2_cond_many([EDX - 8 * Cv], t)[0]
    return X3, Y3, Z3, (2 * c0h, -c1h, c3)


def _add_and_line(X, Y, Z, xq, yq, xp2, yp2, t):
    """Mixed add T + Q (Q affine) and the chord line through Q, scaled Z^3:
    c0 = H*Z*yp, c1 = -r*xp, c3 = r*xq - yq*H*Z."""
    Z2 = _f2_mul_many([Z], [Z], t)[0]
    Z3p, U2 = _f2_mul_many([Z2, xq], [Z, Z2], t)
    S2 = _f2_mul_many([yq], [Z3p], t)[0]
    H, r = _f2_cond_many([U2 - X, S2 - Y], t)
    H2, rsq, ZH = _f2_mul_many([H, r, Z], [H, r, H], t)
    H3, V, rxq, yqZH, c0, c1h = _f2_mul_many(
        [H2, X, r, yq, ZH, r], [H, H2, xq, ZH, yp2, xp2], t
    )
    X3, Z3 = _f2_cond_many([rsq - H3 - 2 * V, ZH], t)
    rVX3, YH3 = _f2_mul_many([r, Y], [V - X3, H3], t)
    Y3 = _f2_cond_many([rVX3 - YH3], t)[0]
    return X3, Y3, Z3, (c0, -c1h, rxq - yqZH)


def _build_program(t):
    """One traced body for every bucket size: the scan is over the constant
    ate bits, the add branch always computed and where-selected."""
    jnp = t.jnp

    def run(p1, q, q1, q2):
        n = p1.shape[0]
        zero = jnp.zeros((n, NLIMB), dtype=p1.dtype)
        xp2 = jnp.stack([p1[:, 0], zero], axis=1)  # Fp as (re, 0)
        yp2 = jnp.stack([p1[:, 1], zero], axis=1)
        xq, yq = q[:, 0], q[:, 1]  # (n, 2, 13)
        F = jnp.zeros((n, 6, 2, NLIMB), dtype=p1.dtype).at[:, 0, 0, 0].set(1.0)
        Z1 = jnp.zeros((n, 2, NLIMB), dtype=p1.dtype).at[:, 0, 0].set(1.0)
        X, Y, Z = xq, yq, Z1

        def body(carry, bit):
            F, X, Y, Z = carry
            F = _f12_sqr(F, t)
            Xd, Yd, Zd, ldbl = _dbl_and_line(X, Y, Z, xp2, yp2, t)
            F = _f12_sparse(F, ldbl, t)
            Xa, Ya, Za, ladd = _add_and_line(Xd, Yd, Zd, xq, yq, xp2, yp2, t)
            Fa = _f12_sparse(F, ladd, t)
            take = bit > 0.5

            def sel(a, b):
                return jnp.where(take, a, b)

            return (sel(Fa, F), sel(Xa, Xd), sel(Ya, Yd), sel(Za, Zd)), None

        (F, X, Y, Z), _ = t.jax.lax.scan(body, (F, X, Y, Z), t.bits)
        # Frobenius adjustment: Q1 = pi(Q), Q2 = -pi^2(Q), host-precomputed.
        Xn, Yn, Zn, l1 = _add_and_line(X, Y, Z, q1[:, 0], q1[:, 1], xp2, yp2, t)
        F = _f12_sparse(F, l1, t)
        _, _, _, l2 = _add_and_line(Xn, Yn, Zn, q2[:, 0], q2[:, 1], xp2, yp2, t)
        F = _f12_sparse(F, l2, t)
        return F  # (n, 6, 2, 13)

    return run


@lru_cache(maxsize=8)
def _program(n):
    t = _tables()
    return t.jax.jit(_build_program(t))


# ---------------------------------------------------------------------------
# Host packing / dispatch


def _pack(pairs, bucket, np):
    p1 = np.zeros((bucket, 2, NLIMB), dtype=np.float64)
    qa = np.zeros((bucket, 2, 2, NLIMB), dtype=np.float64)
    q1a = np.zeros_like(qa)
    q2a = np.zeros_like(qa)
    padded = list(pairs) + [(_b.G1, _b.G2)] * (bucket - len(pairs))
    for lane, (p_pt, q) in enumerate(padded):
        p1[lane, 0] = to_limbs(p_pt[0] % P)
        p1[lane, 1] = to_limbs(p_pt[1] % P)
        q1 = _b._g2_frobenius(q)
        q2 = _b._g2_neg(_b._g2_frobenius(q1))
        for arr, pt in ((qa, q), (q1a, q1), (q2a, q2)):
            for ci, comp in enumerate(pt):  # x, y
                arr[lane, ci, 0] = to_limbs(comp[0] % P)
                arr[lane, ci, 1] = to_limbs(comp[1] % P)
    return p1, qa, q1a, q2a


def _unpack_lane(out, lane) -> tuple:
    return tuple(
        (from_limbs(out[lane, k, 0]), from_limbs(out[lane, k, 1]))
        for k in range(6)
    )


def _dispatch(pairs) -> list:
    """Run one chunk of (G1, G2-affine) lanes on device; exact per-lane f12
    Miller values back as host ints."""
    t = _tables()
    bucket = bucket_for(len(pairs))
    with _x64(t.jax):
        arrays = _pack(pairs, bucket, t.np)
        w = mesh_width()
        sharded = w > 1 and bucket % w == 0 and bucket >= _mesh_floor()
        if sharded:
            from jax.sharding import Mesh, NamedSharding, PartitionSpec

            mesh = Mesh(t.np.array(t.jax.devices()[:w]), ("lane",))
            sh = NamedSharding(mesh, PartitionSpec("lane"))
            arrays = tuple(t.jax.device_put(a, sh) for a in arrays)
        out = t.np.asarray(_program(bucket)(*arrays))
    with _counters_lock:
        _counters["dispatches"] += 1
        _counters["lanes"] += len(pairs)
        if sharded:
            _counters["sharded_dispatches"] += 1
    return [_unpack_lane(out, lane) for lane in range(len(pairs))]


def multi_miller_values(pairs) -> list:
    """Per-lane f_{6t+2,Q}(P) (Jacobian-scaled; valid under final exp).
    None lanes (point at infinity) come back as F12_ONE, matching the host
    multi_miller_loop's filtering, so indices stay 1:1."""
    live = [
        (i, pr)
        for i, pr in enumerate(pairs)
        if pr[0] is not None and pr[1] is not None
    ]
    vals = [_b.F12_ONE] * len(pairs)
    for start in range(0, len(live), MAX_LANES):
        chunk = live[start : start + MAX_LANES]
        outs = _dispatch([pr for _, pr in chunk])
        for (i, _), v in zip(chunk, outs):
            vals[i] = v
    return vals


def multi_pairing_check(pairs) -> bool:
    """prod e(P_i, Q_i) == 1 with device Miller loops and one shared host
    final exponentiation."""
    if not pairs:
        return True
    f = _b.F12_ONE
    for v in multi_miller_values(pairs):
        f = _b.f12_mul(f, v)
    return _b.final_exponentiation_fast(f) == _b.F12_ONE


def warmup(n: int = 8) -> None:
    """Precompile the bucket for n lanes (the scan body is size-independent
    but each bucket is its own XLA executable)."""
    _dispatch([(_b.G1, _b.G2)] * min(n, MAX_LANES))


def clear_compiled_caches() -> None:
    _program.cache_clear()


# ---------------------------------------------------------------------------
# Chain tier


class Bn254DeviceBackend:
    """Device tier of the bn254 chain: same (pubs, msgs, sigs) byte-column
    protocol as Bn254HostBackend, Miller loops on device, parse + weights +
    final exponentiation on host."""

    name = "bn254-device"

    def aggregate_verify(self, pubs, msgs, agg_sig) -> bool:
        if len(pubs) != len(msgs) or not pubs:
            return False
        if len(agg_sig) not in (
            _b.SIGNATURE_SIZE,
            _b.SIGNATURE_SIZE_COMPRESSED,
        ):
            return False
        try:
            s = _b.g2_unmarshal(bytes(agg_sig))
            pairs = []
            for pk_b, m in zip(pubs, msgs):
                pk = _b.g1_decompress(bytes(pk_b))
                if pk is None:
                    return False
                hm = _b._hash_to_g2_cached(bytes(m))
                pairs.append(((pk[0], (P - pk[1]) % P), hm))
            pairs.append((_b.G1, s))
        except (ValueError, TypeError):
            return False
        return multi_pairing_check(pairs)

    def batch_verify(self, pubs, msgs, sigs):
        n = len(pubs)
        bits = [False] * n
        parsed: dict[int, tuple] = {}
        for i in range(n):
            try:
                pk = _b.g1_decompress(bytes(pubs[i]))
                s = _b.g2_unmarshal(bytes(sigs[i]))
                if pk is None or s is None:
                    continue
            except (ValueError, TypeError):
                continue
            parsed[i] = (
                (pk[0], (P - pk[1]) % P),
                _b._hash_to_g2_cached(bytes(msgs[i])),
                s,
            )
        if not parsed:
            return False, bits
        ws = _b._batch_weights(
            [bytes(p) for p in pubs],
            [bytes(m) for m in msgs],
            [bytes(s) for s in sigs],
        )
        # Two lanes per signature — e([w](-pk), H(m)) and e(G1, [w]s) — so a
        # failed product attributes per-sig with one extra final exp each,
        # no re-dispatch. Host scalar mults are ~ms-scale: fine at vote
        # batch sizes, and the 10k commit path uses the aggregate form.
        order = sorted(parsed)
        lanes = []
        for i in order:
            neg_pk, hm, s = parsed[i]
            lanes.append((_b._g1_mul(ws[i], neg_pk), hm))
            lanes.append((_b.G1, _b._g2_mul(ws[i], s)))
        vals = multi_miller_values(lanes)
        f = _b.F12_ONE
        for v in vals:
            f = _b.f12_mul(f, v)
        if _b.final_exponentiation_fast(f) == _b.F12_ONE:
            for i in order:
                bits[i] = True
        else:
            for k, i in enumerate(order):
                v = _b.f12_mul(vals[2 * k], vals[2 * k + 1])
                bits[i] = (
                    _b.final_exponentiation_fast(v) == _b.F12_ONE
                )
        return (n > 0 and all(bits)), bits

    def merkle_root(self, leaves):
        from cometbft_tpu.crypto import merkle

        return merkle.hash_from_byte_slices(list(leaves))

    def mesh_width(self) -> int:
        return mesh_width()

    def ping(self) -> bool:
        if not device_available():
            return False
        try:
            _tables()
            return True
        except Exception:
            return False
