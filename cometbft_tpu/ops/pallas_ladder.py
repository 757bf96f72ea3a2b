"""Pallas lowering of the windowed double-scalar ladder (weak-#5 probe).

The XLA lowerings trade arithmetic shape against GRAPH SIZE: the planar
row form is the minimal-arithmetic program but its ~75k-op full-ladder
graph never finished compiling on the device, so the stacked Toeplitz
band (same products, ~45x smaller graph) became the accelerator default
(ops/DESIGN.md).  Pallas dissolves that trade: the whole ladder runs as
ONE kernel whose body Mosaic compiles once — accumulator, the per-lane
[1..8]A table, and every intermediate live in VMEM across all 252
doublings instead of streaming through HBM between XLA fusions — and the
body is the planar row arithmetic (reusing field25519's closure-free
_mul_rows/_sq_rows/_carry_rows), because inside a kernel the graph-size
concern is gone.

Pallas rejects kernels that close over ARRAY constants, so every field
constant here (4p, 2d, the [0..8]B table) is plain python ints that
broadcast into the lanes; the algorithms mirror ops/edwards.py exactly
(same precomp form, same signed-window schedule) and are held to it by
tests/test_pallas_ladder.py in interpret mode.

Routed by CMTPU_LADDER=pallas (ed25519_kernel); opt-in, off every
shipped path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from cometbft_tpu.ops import edwards as ed
from cometbft_tpu.ops import field25519 as fe

TILE = 128

# Constants as python ints (closure-safe in Pallas kernels).
_F4 = [int(v) for v in np.asarray(fe._FOUR_P).reshape(-1)]
_TWO_D = [int(v) for v in fe.int_to_limbs(fe.TWO_D_INT)]
# [0..8]B in precomp form (ymx, ypx, 2dT, Z), [9][4][17] ints.
_TB = [
    [[int(v) for v in np.asarray(ed.TABLE_B_PRE)[e, c, :, 0]] for c in range(4)]
    for e in range(9)
]

_mulr = fe._mul_rows
_sqr = fe._sq_rows
_carryr = fe._carry_rows


def _addr(a, b):
    return _carryr([x + y for x, y in zip(a, b)])


def _subr(a, b):
    return _carryr([x + p4 - y for x, y, p4 in zip(a, b, _F4)])


def _negr(a):
    return _carryr([p4 - x for x, p4 in zip(a, _F4)])


def _mul_intconst(a, climbs):
    return _mulr(a, climbs)


def _to_precomp(p):
    """(X:Y:Z:T) -> (Y-X, Y+X, 2d*T, Z), mirroring edwards.to_precomp."""
    x, y, z, t = p
    return (_subr(y, x), _addr(y, x), _mul_intconst(t, _TWO_D), z)


def _add_precomp(p, q_pre, z2_is_one):
    """edwards._add_precomp_core: complete hwcd addition against a
    precomputed point; z2_is_one skips the Z1*Z2 multiply."""
    x1, y1, z1, t1 = p
    ymx, ypx, td2, z2 = q_pre
    a = _mulr(_subr(y1, x1), ymx)
    b = _mulr(_addr(y1, x1), ypx)
    c = _mulr(t1, td2)
    zz = z1 if z2_is_one else _mulr(z1, z2)
    d = _carryr([2 * v for v in zz])
    e = _subr(b, a)
    f = _subr(d, c)
    g = _addr(d, c)
    h = _addr(b, a)
    return (_mulr(e, f), _mulr(g, h), _mulr(f, g), _mulr(e, h))


def _pdbl(p):
    """edwards.point_double (dbl-2008-hwcd for a = -1)."""
    x1, y1, z1, _ = p
    a = _sqr(x1)
    b = _sqr(y1)
    zz = _sqr(z1)
    c = _carryr([2 * v for v in zz])
    e = _subr(_subr(_sqr(_addr(x1, y1)), a), b)
    g = _subr(b, a)
    f = _subr(g, c)
    h = _negr(_addr(a, b))
    return (_mulr(e, f), _mulr(g, h), _mulr(f, g), _mulr(e, h))


def _select_a(table, digits):
    """Signed lookup from the per-lane A table (list of 8 precomp entries
    for [1..8]A): |d| selects, d<0 negates (swap ymx/ypx, negate 2dT),
    d==0 yields the precomp identity (1, 1, 0, 1)."""
    idx = jnp.abs(digits)
    neg = digits < 0
    one = jnp.ones_like(digits)
    zero = jnp.zeros_like(digits)
    out = []
    for coord in range(4):
        rows = []
        for limb in range(fe.LIMBS):
            # identity entry: ymx=ypx=z=1 (limb0), 2dT=0
            init = (
                one if (coord in (0, 1, 3) and limb == 0) else zero
            )
            acc = init
            for e in range(1, 9):
                acc = jnp.where(idx == e, table[e - 1][coord][limb], acc)
            rows.append(acc)
        out.append(rows)
    ymx, ypx, td2, z = out
    sel_ymx = [jnp.where(neg, b, a) for a, b in zip(ymx, ypx)]
    sel_ypx = [jnp.where(neg, a, b) for a, b in zip(ymx, ypx)]
    ntd2 = _negr(td2)
    sel_td2 = [jnp.where(neg, b, a) for a, b in zip(td2, ntd2)]
    return (sel_ymx, sel_ypx, sel_td2, z)


def _select_b(digits):
    """Signed lookup from the constant [0..8]B table (python ints)."""
    idx = jnp.abs(digits)
    neg = digits < 0
    out = []
    for coord in range(4):
        rows = []
        for limb in range(fe.LIMBS):
            acc = jnp.full_like(digits, _TB[0][coord][limb])
            for e in range(1, 9):
                acc = jnp.where(idx == e, _TB[e][coord][limb], acc)
            rows.append(acc)
        out.append(rows)
    ymx, ypx, td2, z = out
    sel_ymx = [jnp.where(neg, b, a) for a, b in zip(ymx, ypx)]
    sel_ypx = [jnp.where(neg, a, b) for a, b in zip(ymx, ypx)]
    ntd2 = _negr(td2)
    sel_td2 = [jnp.where(neg, b, a) for a, b in zip(td2, ntd2)]
    return (sel_ymx, sel_ypx, sel_td2, z)


def _ladder_math(s_dig, k_dig, ax, ay, az, at, n_windows=None):
    """The closure-free ladder over stacked [.., T] arrays — the kernel
    body's math, also directly jit-testable on CPU without Pallas emulation
    (tests/test_pallas_ladder.py).  n_windows < DIGITS truncates to the top
    windows (the cheap interpret-mode plumbing smoke)."""
    if n_windows is None:
        n_windows = ed.DIGITS
    a_point = tuple(
        [r[i] for i in range(fe.LIMBS)] for r in (ax, ay, az, at)
    )

    # per-lane [1..8]A table in precomp form. The chain is UNROLLED in
    # python: the rolled fori_loop form needed `tbl.at[i].set(...)` with a
    # traced index, which jnp lowers to `scatter` — a primitive Mosaic's TC
    # kernel lowering does not implement (measured on device, round
    # 5). Seven inlined point adds cost trace size, but inside ONE
    # Mosaic kernel the XLA whole-graph compile ceiling that forced the
    # rolled form on the stacked path does not apply.
    pp = _to_precomp(a_point)
    table = [pp]
    cur = a_point
    for _ in range(7):
        cur = _add_precomp(cur, pp, z2_is_one=False)
        table.append(_to_precomp(cur))

    t = s_dig.shape[1]
    zero = jnp.zeros((t,), jnp.int32)
    one = jnp.ones((t,), jnp.int32)
    ident = (
        [zero] * fe.LIMBS,
        [one] + [zero] * (fe.LIMBS - 1),
        [one] + [zero] * (fe.LIMBS - 1),
        [zero] * fe.LIMBS,
    )

    def body(w, acc):
        row = ed.DIGITS - 1 - w
        # rolled doublings (same compile-size control as the XLA ladder)
        acc = lax.fori_loop(
            0, ed.WINDOW_BITS,
            lambda _, a: tuple(tuple(c) for c in _pdbl(a)), acc,
        )
        # Digit-row fetch as a one-hot masked reduction: Mosaic's TC
        # lowering implements neither `scatter` nor `dynamic_slice`
        # (both measured on device, round-5 A/B), and a [DIGITS, T]
        # mask-multiply-sum per window is noise next to the point math.
        sel = (
            lax.broadcasted_iota(jnp.int32, (ed.DIGITS, 1), 0) == row
        ).astype(jnp.int32)
        kd = jnp.sum(k_dig * sel, axis=0)
        sd = jnp.sum(s_dig * sel, axis=0)
        acc = _add_precomp(acc, _select_a(table, kd), z2_is_one=False)
        acc = _add_precomp(acc, _select_b(sd), z2_is_one=True)
        # normalize to the carry treedef (tuples, not the lists the row
        # helpers produce)
        return tuple(tuple(c) for c in acc)

    acc = lax.fori_loop(0, n_windows, body, tuple(tuple(c) for c in ident))
    return tuple(jnp.stack(list(c)) for c in acc)


def _ladder_kernel(s_ref, k_ref, ax_ref, ay_ref, az_ref, at_ref,
                   ox_ref, oy_ref, oz_ref, ot_ref, *, n_windows):
    outs = _ladder_math(
        s_ref[...], k_ref[...], ax_ref[...], ay_ref[...], az_ref[...],
        at_ref[...], n_windows=n_windows,
    )
    ox_ref[...] = outs[0]
    oy_ref[...] = outs[1]
    oz_ref[...] = outs[2]
    ot_ref[...] = outs[3]


@functools.partial(
    jax.jit, static_argnames=("interpret", "tile", "n_windows")
)
def _ladder_call(s_digits, k_digits, ax, ay, az, at, interpret=False,
                 tile=TILE, n_windows=None):
    n = s_digits.shape[1]
    assert n % tile == 0, n
    grid = (n // tile,)
    dig_spec = pl.BlockSpec((ed.DIGITS, tile), lambda i: (0, i))
    fe_spec = pl.BlockSpec((fe.LIMBS, tile), lambda i: (0, i))
    out_shape = [
        jax.ShapeDtypeStruct((fe.LIMBS, n), jnp.int32) for _ in range(4)
    ]
    return pl.pallas_call(
        functools.partial(_ladder_kernel, n_windows=n_windows),
        grid=grid,
        in_specs=[dig_spec, dig_spec, fe_spec, fe_spec, fe_spec, fe_spec],
        out_specs=[fe_spec, fe_spec, fe_spec, fe_spec],
        out_shape=out_shape,
        interpret=interpret,
    )(s_digits, k_digits, ax, ay, az, at)


def windowed_double_base_mult(
    s_digits: jnp.ndarray,
    k_digits: jnp.ndarray,
    a_point,
    interpret: bool = False,
    tile: int = TILE,
    n_windows: int | None = None,
):
    """Drop-in for edwards.windowed_double_base_mult via one Pallas kernel.

    Lanes are padded to a tile multiple (callers are shape-bucketed exactly
    like the XLA path, so padding cost is bounded).  `tile`/`n_windows` are
    overridable for interpret-mode tests, where small shapes keep the
    emulation cheap."""
    n = s_digits.shape[1]
    pad = (-n) % tile
    if pad:
        s_digits = jnp.pad(s_digits, ((0, 0), (0, pad)))
        k_digits = jnp.pad(k_digits, ((0, 0), (0, pad)))
        a_point = tuple(jnp.pad(c, ((0, 0), (0, pad))) for c in a_point)
    outs = _ladder_call(
        s_digits, k_digits, *a_point, interpret=interpret, tile=tile,
        n_windows=n_windows,
    )
    if pad:
        outs = [o[:, :n] for o in outs]
    return tuple(outs)
