"""Device-kernel correctness: the smallest bucket of the batched ZIP-215
verifier (ops/ed25519_kernel) against host-signed vectors. One fixed-shape
compile (~15s on the 1-core CI box) — kept to a single bucket so the suite
doesn't recompile per test."""

import numpy as np
import pytest

from cometbft_tpu.crypto import ed25519
from cometbft_tpu.ops import ed25519_kernel as ek


@pytest.fixture(scope="module")
def batch8():
    pubs, msgs, sigs = [], [], []
    for i in range(8):
        priv = ed25519.gen_priv_key_from_secret(b"kernel-test-%d" % i)
        msg = b"vote-bytes-%d" % i
        pubs.append(priv.pub_key().bytes())
        msgs.append(msg)
        sigs.append(priv.sign(msg))
    return pubs, msgs, sigs


def test_all_valid(batch8):
    pubs, msgs, sigs = batch8
    ok, res = ek.batch_verify(pubs, msgs, sigs)
    assert ok is True and all(res)


def test_bad_sig_localized(batch8):
    pubs, msgs, sigs = batch8
    sigs = list(sigs)
    sigs[5] = sigs[5][:20] + bytes([sigs[5][20] ^ 0x40]) + sigs[5][21:]
    ok, res = ek.batch_verify(pubs, msgs, sigs)
    assert ok is False
    assert res[5] is False and sum(res) == 7


def test_wrong_message_localized(batch8):
    pubs, msgs, sigs = batch8
    msgs = list(msgs)
    msgs[0] = b"tampered"
    ok, res = ek.batch_verify(pubs, msgs, sigs)
    assert ok is False and res[0] is False and sum(res) == 7


def test_s_out_of_range_rejected_host_side(batch8):
    pubs, msgs, sigs = batch8
    sigs = list(sigs)
    bad_s = (ek.L + 5).to_bytes(32, "little")
    sigs[2] = sigs[2][:32] + bad_s
    ok, res = ek.batch_verify(pubs, msgs, sigs)
    assert ok is False and res[2] is False


def test_precomp_add_matches_generic_add():
    """add_precomp (cached-point form) agrees with the generic hwcd add."""
    import jax.numpy as jnp

    from cometbft_tpu.ops import edwards as ed
    from cometbft_tpu.ops import field25519 as fe

    pubs = [
        ed25519.gen_priv_key_from_secret(b"p%d" % i).pub_key().bytes()
        for i in range(4)
    ]
    enc = np.stack([np.frombuffer(p, np.uint8) for p in pubs])
    y = jnp.asarray(fe.fe_from_bytes_le(enc))
    sign = jnp.asarray((enc[:, 31] >> 7).astype(bool))
    pt, ok = ed.decompress(y, sign)
    assert np.asarray(ok).all()

    d1 = ed.point_double(pt)
    s1 = ed.point_add(pt, d1)
    s2 = ed.add_precomp(pt, ed.to_precomp(d1))
    for a, b in zip(s1, s2):
        assert np.asarray(fe.fe_eq(a, b)).all()


def test_windowed_ladder_matches_pure_python():
    """[s]B + [k]A from the signed-window ladder equals the pure-python
    reference scalar arithmetic, including digit sign/carry edge scalars."""
    import jax.numpy as jnp

    from cometbft_tpu.crypto import ed25519_pure as pure
    from cometbft_tpu.ops import edwards as ed
    from cometbft_tpu.ops import field25519 as fe

    rng = np.random.default_rng(7)
    scal = [
        (1, 1),
        (0, 0),
        (ek.L - 1, ek.L - 1),
        (8, 2**252),
        (0x8888888888888888, 15),  # all-8 nibbles: worst-case carry chain
        (int(rng.integers(1, 1 << 62)) * 3 + 1, int(rng.integers(1, 1 << 62))),
    ]
    n = len(scal)
    apub = ed25519.gen_priv_key_from_secret(b"window-A").pub_key().bytes()
    a_int = pure.point_decompress_zip215(apub)
    enc = np.stack([np.frombuffer(apub, np.uint8)] * n)
    y = jnp.asarray(fe.fe_from_bytes_le(enc))
    sign = jnp.asarray((enc[:, 31] >> 7).astype(bool))
    a_pt, ok = ed.decompress(y, sign)
    assert np.asarray(ok).all()

    s_le = np.stack(
        [np.frombuffer(int(s).to_bytes(32, "little"), np.uint8) for s, _ in scal]
    )
    k_le = np.stack(
        [np.frombuffer(int(k).to_bytes(32, "little"), np.uint8) for _, k in scal]
    )
    s_digits = jnp.asarray(ed.scalars_to_digits(s_le))
    k_digits = jnp.asarray(ed.scalars_to_digits(k_le))
    acc = ed.windowed_double_base_mult(s_digits, k_digits, a_pt)
    ya, sgn = ed.point_compress(acc)
    got = np.asarray(ya)
    got_sign = np.asarray(sgn)

    B = pure.BASE
    for c, (s, k) in enumerate(scal):
        want = pure.point_add(pure.scalar_mult(s, B), pure.scalar_mult(k, a_int))
        want_bytes = pure.point_compress(want)
        y_int = fe.limbs_to_int(got[:, c]) | (int(got_sign[c]) << 255)
        assert y_int.to_bytes(32, "little") == want_bytes


def test_kernel_bitmap_matches_pure_on_zip215_edge_vectors():
    """VERDICT r3 #1 done-criterion: the device kernel's per-signature bitmap
    must agree with ed25519_pure's ZIP-215 semantics on the edge vectors —
    non-canonical A/R encodings, small-order components, s-range boundaries,
    malformed inputs, and plain corruption — in one mixed batch."""
    from cometbft_tpu.crypto import ed25519_pure as pure

    cases = pure.zip215_edge_cases()
    assert pure.point_decompress_zip215(cases[9][1]) is not None
    pubs = [c[1] for c in cases]
    msgs = [c[2] for c in cases]
    sigs = [c[3] for c in cases]

    _, got = ek.batch_verify(pubs, msgs, sigs)

    for (name, p_, m_, s_), bit in zip(cases, got):
        if len(p_) != 32 or len(s_) != 64:
            want = False
        else:
            want = pure.verify_zip215(p_, m_, s_)
        assert bit == want, f"{name}: kernel={bit} pure={want}"
    # sanity on the interesting ones
    assert got[0] is True
    assert got[5] is True, "s=0 with identity A satisfies the cofactored eq"
    assert got[9] is True, "noncanonical identity alias must decode (rule 1)"
    assert got[1] is False and got[3] is False


def test_stacked_lowering_full_verify_on_cpu():
    """The accelerators' (stacked) lowering through the whole verify program,
    forced on XLA:CPU through the platform test's own seam (fe._ACCEL) —
    small graphs, so this runs in the normal suite. The multiply the trace
    reaches is counted, so a trace cached under the other lowering cannot
    pass for this one."""
    from cometbft_tpu.ops import field25519 as fe

    prev_accel, mul_stacked = fe._ACCEL, fe._mul_stacked
    traced = []

    def counting(x, y):
        traced.append(1)
        return mul_stacked(x, y)

    fe._ACCEL, fe._mul_stacked = True, counting
    try:
        ek.clear_compiled_caches()
        pubs, msgs, sigs = [], [], []
        for i in range(8):
            priv = ed25519.gen_priv_key_from_secret(b"stacked-%d" % i)
            msg = b"stacked-vote-%d" % i
            pubs.append(priv.pub_key().bytes())
            msgs.append(msg)
            sigs.append(priv.sign(msg))
        sigs[3] = sigs[3][:8] + bytes([sigs[3][8] ^ 1]) + sigs[3][9:]
        ok, res = ek.batch_verify(pubs, msgs, sigs)
        assert res == [True, True, True, False, True, True, True, True]
        assert traced, "the verify program was not traced under the stacked lowering"
    finally:
        fe._ACCEL, fe._mul_stacked = prev_accel, mul_stacked
        ek.clear_compiled_caches()
