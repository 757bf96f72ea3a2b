"""ops/field25519 below a whole verification: every public arithmetic op
against Python integers mod p, under both lowerings the platform test
chooses between (stacked on an accelerator, compact on XLA:CPU), on random
values and on the edges of the representation — 0, 1, p-1, and every limb at
the loose bound 2^15 + 95 that the ops promise to accept and to return."""

import random

import numpy as np
import pytest

pytest.importorskip("jax")

import jax.numpy as jnp

from cometbft_tpu.ops import field25519 as fe

P = fe.P_INT
LOOSE = (1 << fe.LIMB_BITS) + 95


def _operands():
    """Two int32[17, N] operands whose lanes pair every edge value with every
    other, followed by random canonical pairs and random loose-limb pairs."""
    rng = random.Random(25519)
    edges = [fe.int_to_limbs(v) for v in (0, 1, 2, 19, P - 19, P - 2, P - 1)]
    edges.append(np.full(fe.LIMBS, LOOSE, np.int32))
    edges.append(np.full(fe.LIMBS, fe.MASK, np.int32))  # 2^255 - 1 = p + 18
    xs = [a for a in edges for _ in edges]
    ys = [b for _ in edges for b in edges]
    for _ in range(24):
        xs.append(fe.int_to_limbs(rng.randrange(P)))
        ys.append(fe.int_to_limbs(rng.randrange(P)))
    for _ in range(24):
        xs.append(np.array([rng.randrange(LOOSE + 1) for _ in range(fe.LIMBS)], np.int32))
        ys.append(np.array([rng.randrange(LOOSE + 1) for _ in range(fe.LIMBS)], np.int32))
    return np.stack(xs, axis=1), np.stack(ys, axis=1)


OPS = {
    "mul": (lambda x, y: fe.fe_mul(x, y), lambda a, b: a * b),
    "sq": (lambda x, y: fe.fe_sq(x), lambda a, b: a * a),
    "add": (lambda x, y: fe.fe_add(x, y), lambda a, b: a + b),
    "sub": (lambda x, y: fe.fe_sub(x, y), lambda a, b: a - b),
    "neg": (lambda x, y: fe.fe_neg(x), lambda a, b: -a),
}


@pytest.mark.parametrize("op", sorted(OPS))
@pytest.mark.parametrize("lowering", ["compact", "stacked"])
def test_field_op_matches_integers_mod_p(monkeypatch, lowering, op):
    monkeypatch.setattr(fe, "_ACCEL", lowering == "stacked")
    assert fe._mode() == lowering
    device_op, int_op = OPS[op]
    x, y = _operands()
    out = np.asarray(device_op(jnp.asarray(x), jnp.asarray(y)))
    assert out.shape == x.shape
    assert out.min() >= 0 and out.max() <= LOOSE, "loose limb invariant broken"
    for lane in range(x.shape[1]):
        a, b = fe.limbs_to_int(x[:, lane]), fe.limbs_to_int(y[:, lane])
        assert fe.limbs_to_int(out[:, lane]) % P == int_op(a, b) % P, (lowering, op, lane)
    # the canonical form agrees too: freeze brings every lane into [0, p)
    frozen = np.asarray(fe.fe_freeze(jnp.asarray(out)))
    for lane in range(0, x.shape[1], 7):
        a, b = fe.limbs_to_int(x[:, lane]), fe.limbs_to_int(y[:, lane])
        assert fe.limbs_to_int(frozen[:, lane]) == int_op(a, b) % P
