"""Pallas ladder probe (ops/pallas_ladder.py, CMTPU_LADDER=pallas).

What CAN be validated off-device: the kernel traces to a jaxpr (no
captured-constant rejections — Pallas refuses closures over arrays, which
is why the kernel reimplements the point ops over python-int constants),
the row arithmetic primitives match field25519's planar semantics
bit-for-bit, and the precomp-form point algebra matches ed25519_pure.

What CANNOT: executing the full kernel on CPU.  The ~28k-op body is
exactly the planar graph XLA:CPU compiles quadratically (the reason
CMTPU_FE_MODE=compact exists), and Pallas interpret-mode emulation of a
body this size is slower still.  On device the kernel stays opt-in until
a chip benchmark's commit-verify cells, which assert correct bitmaps,
show it winning (ROADMAP D2).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cometbft_tpu.crypto import ed25519_pure as pure
from cometbft_tpu.ops import edwards as ed
from cometbft_tpu.ops import field25519 as fe
from cometbft_tpu.ops import pallas_ladder as plad


def _rows_from_int(v, n=4):
    limbs = fe.int_to_limbs(v)
    return [jnp.full((n,), int(x), jnp.int32) for x in limbs]


def _rows_to_int(rows, lane=0):
    arr = np.stack([np.asarray(r) for r in rows])
    return fe.limbs_to_int(arr[:, lane]) % pure.P


def test_row_arithmetic_matches_bigints():
    import random

    rng = random.Random(11)
    for _ in range(20):
        a, b = rng.randrange(pure.P), rng.randrange(pure.P)
        ra, rb = _rows_from_int(a), _rows_from_int(b)
        assert _rows_to_int(plad._mulr(ra, rb)) == a * b % pure.P
        assert _rows_to_int(plad._addr(ra, rb)) == (a + b) % pure.P
        assert _rows_to_int(plad._subr(ra, rb)) == (a - b) % pure.P
        assert _rows_to_int(plad._negr(ra)) == (-a) % pure.P
        assert _rows_to_int(plad._sqr(ra)) == a * a % pure.P
        assert (
            _rows_to_int(plad._mul_intconst(ra, plad._TWO_D))
            == a * fe.TWO_D_INT % pure.P
        )


def _ext_rows(p):
    return tuple(_rows_from_int(c) for c in p)


def test_point_algebra_matches_pure():
    import random

    rng = random.Random(12)
    for _ in range(6):
        p = pure.scalar_mult(rng.randrange(1, pure.L), pure.BASE)
        q = pure.scalar_mult(rng.randrange(1, pure.L), pure.BASE)
        want_add = pure.point_add(p, q)
        want_dbl = pure.point_double(p)
        got_add = plad._add_precomp(
            _ext_rows(p), plad._to_precomp(_ext_rows(q)), z2_is_one=False
        )
        got_dbl = plad._pdbl(_ext_rows(p))
        for got, want in ((got_add, want_add), (got_dbl, want_dbl)):
            zi = pow(want[2], pure.P - 2, pure.P)
            gz = _rows_to_int(got[2])
            gzi = pow(gz, pure.P - 2, pure.P)
            assert _rows_to_int(got[0]) * gzi % pure.P == want[0] * zi % pure.P
            assert _rows_to_int(got[1]) * gzi % pure.P == want[1] * zi % pure.P


def test_signed_table_selects():
    """_select_b against the pure-python multiples of B, every digit in
    [-8, 8] — covers identity, negation (swap + 2dT negate), and |8|."""
    digits = jnp.asarray(np.arange(-8, 9, dtype=np.int32))
    ymx, ypx, td2, z = plad._select_b(digits)
    n = 17
    for lane, d in enumerate(range(-8, 9)):
        mult = pure.scalar_mult(abs(d), pure.BASE)
        if d < 0:
            mult = pure.point_neg(mult)
        x, y, zz, t = mult
        zi = pow(zz, pure.P - 2, pure.P)
        ax, ay, at = x * zi % pure.P, y * zi % pure.P, t * zi % pure.P
        gymx = fe.limbs_to_int(
            np.stack([np.asarray(r) for r in ymx])[:, lane]
        ) % pure.P
        gypx = fe.limbs_to_int(
            np.stack([np.asarray(r) for r in ypx])[:, lane]
        ) % pure.P
        gtd2 = fe.limbs_to_int(
            np.stack([np.asarray(r) for r in td2])[:, lane]
        ) % pure.P
        gzl = fe.limbs_to_int(
            np.stack([np.asarray(r) for r in z])[:, lane]
        ) % pure.P
        # entries are affine (Z == 1): compare directly
        assert gzl == 1, d
        assert gymx == (ay - ax) % pure.P, d
        assert gypx == (ay + ax) % pure.P, d
        assert gtd2 == fe.TWO_D_INT * at % pure.P, d


def test_kernel_traces_without_captures():
    """pallas_call tracing must succeed: any array constant leaking into
    the kernel closure raises at trace time (the failure mode this kernel
    is structured around)."""
    s = jnp.zeros((ed.DIGITS, plad.TILE), jnp.int32)
    k = jnp.zeros((ed.DIGITS, plad.TILE), jnp.int32)
    a = tuple(jnp.zeros((fe.LIMBS, plad.TILE), jnp.int32) for _ in range(4))
    # lower() raising (e.g. the captured-constant rejection) is the failure
    # mode; reaching HLO text at all is the invariant
    jax.jit(
        lambda *args: plad._ladder_call(*args, interpret=True)
    ).lower(s, k, *a).as_text()
