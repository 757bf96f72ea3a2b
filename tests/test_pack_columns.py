"""The device tier's pack and unpack work on whole columns
(ops/ed25519_kernel `_host_checks`, `pack_batch`, `collect()`;
ops/sha512_kernel `write_padding`). Held here: the operands and the host's
mask are byte for byte those of the per-lane pack this replaced, which is
copied below as the plain reference; a batch with a malformed entry takes
the per-lane walk and says so (`walk`, `pack_walk_calls`); `collect()`
answers with n Python bools; the padding rule both packers share gives
hashlib's digests. The pack tests run no device program."""

import hashlib

import numpy as np
import pytest

from cometbft_tpu.crypto import ed25519
from cometbft_tpu.crypto import ed25519_pure as pure
from cometbft_tpu.libs import trace
from cometbft_tpu.ops import ed25519_kernel as ek
from cometbft_tpu.ops import sha512_kernel as s5
from cometbft_tpu.ops import unpack

L = ek.L


# -- the plain reference: the pack as it stood before, lane by lane ------------------


def _reference_write_padding(buf, lens, nblocks):
    n = buf.shape[0]
    idx = np.arange(n)
    buf[idx, lens] = 0x80
    ends = nblocks.astype(np.int64) * 128
    bl_bytes = (lens * 8).astype(">u8").view(np.uint8).reshape(n, 8)
    for k in range(8):
        buf[idx, ends - 8 + k] = bl_bytes[:, k]


def _reference_host_checks(pubs, sigs):
    n = len(pubs)
    nb = ek.bucket_for(n)
    zero_pub, zero_sig = b"\x00" * 32, b"\x00" * 64
    shape_ok = [len(pubs[i]) == 32 and len(sigs[i]) == 64 for i in range(n)]
    pubs_c = [pubs[i] if shape_ok[i] else zero_pub for i in range(n)]
    sigs_c = [sigs[i] if shape_ok[i] else zero_sig for i in range(n)]
    a_enc = np.zeros((nb, 32), np.uint8)
    r_enc = np.zeros((nb, 32), np.uint8)
    s_le = np.zeros((nb, 32), np.uint8)
    s_in_range = np.zeros(n, bool)
    if n:
        a_enc[:n] = np.frombuffer(b"".join(pubs_c), np.uint8).reshape(n, 32)
        sig_arr = np.frombuffer(b"".join(sigs_c), np.uint8).reshape(n, 64)
        r_enc[:n] = sig_arr[:, :32]
        s_le[:n] = sig_arr[:, 32:]
        s_words = s_le[:n].view("<u8")
        l_words = np.frombuffer(L.to_bytes(32, "little"), dtype="<u8")
        decided = np.zeros(n, bool)
        for w in (3, 2, 1, 0):
            lt = ~decided & (s_words[:, w] < l_words[w])
            gt = ~decided & (s_words[:, w] > l_words[w])
            s_in_range |= lt
            decided |= lt | gt
        s_le[:n][~s_in_range] = 0
    return a_enc, r_enc, s_le, pubs_c, sigs_c, shape_ok, s_in_range


def _reference_pack(pubs, msgs, sigs):
    n = len(pubs)
    nb = ek.bucket_for(n)
    a_enc, r_enc, s_le, pubs_c, sigs_c, shape_ok, s_in_range = _reference_host_checks(pubs, sigs)
    host_ok = np.zeros(nb, bool)
    if n:
        mlens = np.fromiter((len(msgs[i]) if shape_ok[i] else 0 for i in range(n)), np.int64, n)
    else:
        mlens = np.zeros(0, np.int64)
    oversized = n > 0 and int(mlens.max()) + 64 > ek.BLOCK_BUCKETS[-1] * 128 - 17
    if oversized:
        k_le = np.zeros((nb, 64), np.uint8)
        digest_rows = bytearray(64 * n)
        for i in range(n):
            if not shape_ok[i] or not s_in_range[i]:
                continue
            h = hashlib.sha512(sigs_c[i][:32])
            h.update(pubs_c[i])
            h.update(msgs[i])
            digest_rows[64 * i : 64 * (i + 1)] = h.digest()
            host_ok[i] = True
        if n:
            k_le[:n] = np.frombuffer(bytes(digest_rows), np.uint8).reshape(n, 64)
        return tuple(unpack.bytes_to_words(x) for x in (a_enc, r_enc, s_le, k_le)), host_ok
    # the one word that is not the parent's: `bool`, without which its n = 0 raised
    host_ok[:n] = np.asarray(shape_ok, bool) & s_in_range
    tot = mlens + 64
    nblocks = s5.blocks_for(tot)
    bmax = ek.block_bucket_for(int(nblocks.max()) if n else 1)
    buf = np.zeros((nb, bmax * 128), np.uint8)
    if n:
        buf[:n, 0:32] = r_enc[:n]
        buf[:n, 32:64] = a_enc[:n]
        for ln in np.unique(mlens):
            if ln == 0:
                continue
            rows = np.nonzero(mlens == ln)[0]
            joined = b"".join(msgs[i] for i in rows)
            buf[rows, 64 : 64 + ln] = np.frombuffer(joined, np.uint8).reshape(len(rows), ln)
        _reference_write_padding(buf[:n], tot, nblocks)
    pnb = np.zeros(nb, np.int32)
    pnb[:n] = nblocks
    words = tuple(unpack.bytes_to_words(x) for x in (a_enc, r_enc, s_le))
    return (*words, buf.view("<u4"), pnb), host_ok


# -- the batches ----------------------------------------------------------------------


def _batch(n: int, seed: int, lens=(122,)):
    """n seeded well-formed lanes (random keys, random R, s < 2^248), the
    message lengths dealt round-robin from `lens`."""
    rng = np.random.default_rng(seed)
    pubs = [rng.bytes(32) for _ in range(n)]
    msgs = [rng.bytes(lens[i % len(lens)]) for i in range(n)]
    sigs = [rng.bytes(63) + b"\x00" for _ in range(n)]
    return pubs, msgs, sigs


def _with(batch, column: int, lane: int, value):
    columns = [list(c) for c in batch]
    columns[column][lane] = value
    return tuple(columns)


def _sig(s: int) -> bytes:
    return b"\x17" * 32 + s.to_bytes(32, "little")


PUBS, MSGS, SIGS = range(3)
# name -> (batch, whether it takes the per-lane walk)
CASES = {
    "one message length": (_batch(11, 1), False),
    "three lengths interleaved": (_batch(23, 2, (110, 122, 113)), False),
    "a 31-byte key": (_with(_batch(11, 3), PUBS, 4, b"\x01" * 31), True),
    "a 65-byte signature": (_with(_batch(11, 4, (90, 122)), SIGS, 7, b"\x02" * 65), True),
    "an empty key and a 33-byte key": (
        _with(_with(_batch(11, 5), PUBS, 0, b""), PUBS, 10, b"\x03" * 33), True),
    "a bytearray entry in each column": (
        _with(_with(_with(_batch(11, 6), PUBS, 1, bytearray(b"\x04" * 32)),
                    MSGS, 2, bytearray(b"\x05" * 122)), SIGS, 3, bytearray(b"\x06" * 63 + b"\x00")),
        False),
    "a memoryview entry in each column": (
        _with(_with(_with(_batch(11, 7), PUBS, 1, memoryview(b"\x04" * 32)),
                    MSGS, 2, memoryview(b"\x05" * 122)), SIGS, 3, memoryview(b"\x06" * 63 + b"\x00")),
        False),
    "s = L": (_with(_batch(11, 8), SIGS, 5, _sig(L)), False),
    "s = L - 1": (_with(_batch(11, 9), SIGS, 5, _sig(L - 1)), False),
    "s with the top word above L's": (_with(_batch(11, 10), SIGS, 5, _sig(L + (1 << 200))), False),
    "s = 2^256 - 1 beside a malformed lane": (
        _with(_with(_batch(11, 11), SIGS, 5, _sig(2**256 - 1)), PUBS, 6, b"\x07" * 30), True),
    "an empty message": (_with(_batch(11, 12), MSGS, 3, b""), False),
    "every message empty": (_batch(9, 13, (0,)), False),
    "a message that crosses a block bucket": (_with(_batch(11, 14), MSGS, 8, b"\x08" * 200), False),
    "lengths at the pad's edges": (_batch(40, 15, (46, 47, 48, 174, 175, 176)), False),
    "an oversized message": (_with(_batch(11, 16), MSGS, 2, b"\x09" * 4100), False),
    "an oversized message, s = L and a malformed lane": (
        _with(_with(_with(_batch(11, 17), MSGS, 2, b"\x09" * 4100), SIGS, 4, _sig(L)),
              SIGS, 9, b"\x0a" * 63), True),
    "n = 0": (_batch(0, 18), False),
    "n = 1": (_batch(1, 19), False),
    "a full bucket": (_batch(8, 20, (122, 123)), False),
    "a bucket + 1": (_batch(9, 21, (122, 123)), False),
    "every lane a length of its own": (_batch(33, 22, tuple(range(60, 93))), False),
}


@pytest.fixture(params=[1, 8, 5], ids=lambda w: f"mesh{w}")
def width(request, monkeypatch):
    """Mesh width 1, the conftest's 8 virtual devices as they are (every
    standard bucket divides them), and 5, which pads a bucket to the mesh
    width (8 -> 10, 32 -> 35)."""
    if request.param != 8:
        monkeypatch.setattr(ek, "mesh_width", lambda: request.param)
    assert ek.mesh_width() == request.param
    return request.param


def _assert_same(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.flags.c_contiguous and g.tobytes() == w.tobytes()


@pytest.mark.parametrize("case", CASES)
def test_pack_equals_the_per_lane_reference(width, case):
    (pubs, msgs, sigs), walks = CASES[case]
    want, want_ok = _reference_pack(pubs, msgs, sigs)
    got, got_ok, walk = ek._pack(pubs, msgs, sigs, ek._NOTHING)
    _assert_same(got, want)
    _assert_same([got_ok], [want_ok])
    assert walk is walks
    assert got[0].shape[1] == ek.bucket_for(len(pubs)) and got[0].shape[1] % width == 0
    public, public_ok = ek.pack_batch(pubs, msgs, sigs)
    _assert_same(public, want)
    _assert_same([public_ok], [want_ok])


@pytest.mark.parametrize("msgs_as", [list, tuple])
@pytest.mark.parametrize(
    "sighting", ["none", "keys only", "keys of a longer column", "resident", "resident, malformed signature"]
)
def test_pack_with_a_sighting(width, sighting, msgs_as):
    """A sighting's key rows stand for the key column's join; where its
    tables serve the dispatch the keys' words are not built at all, and
    everything else is the reference's."""
    pubs, msgs, sigs = _batch(21, 30, (122, 110))
    msgs = msgs_as(msgs)
    column = pubs + _batch(6, 31)[0] if sighting == "keys of a longer column" else pubs
    rows = np.frombuffer(b"".join(column), np.uint8).reshape(len(column), 32)
    if sighting == "resident, malformed signature":
        sigs[3] = sigs[3][:40]
    tables = (None, np.zeros(ek.bucket_for(len(column)), bool))
    given = {
        "none": ek._NOTHING,
        "keys only": ek.Sighting(None, None, 0, rows),
        "keys of a longer column": ek.Sighting(None, None, 0, rows),
    }.get(sighting, ek.Sighting(tables, None, 0, rows))
    want, want_ok = _reference_pack(pubs, msgs, sigs)
    got, got_ok = ek.pack_batch(pubs, msgs, sigs, given)
    if sighting.startswith("resident"):
        assert got[0] is None
        assert ek._bucket_key(got) == ek._bucket_key(want)
        got, want = got[1:], want[1:]
    _assert_same(got, want)
    _assert_same([got_ok], [want_ok])


# -- the engagement counter -----------------------------------------------------------


def test_pack_counters_and_the_walk_attribute(monkeypatch):
    """`pack_calls` / `pack_walk_calls` move as a well-formed and a
    malformed batch are dispatched, `device.pack` says which it was, and
    both reach the tier's counters(). No device program runs."""
    from cometbft_tpu.sidecar import backend as be

    monkeypatch.setattr(ek, "_route_for", lambda operands, tables=None: (
        lambda *ops: np.ones(ops[1].shape[1], bool), False))
    tier = be.TpuBackend()
    before = tier.counters()
    assert {"pack_calls", "pack_walk_calls"} <= set(before)
    good = _batch(11, 40)
    bad = _with(good, PUBS, 4, b"\x01" * 31)
    with trace.capture():
        assert tier.batch_verify(*good) == (True, [True] * 11)
        ok, bits = tier.batch_verify(*bad)
        assert not ok and bits == [True] * 4 + [False] + [True] * 6
        tier.batch_verify(*good)
        packs = [s["attrs"] for s in trace.spans() if s["name"] == "device.pack"]
    assert [a["walk"] for a in packs] == [False, True, False]
    assert [a["lanes"] for a in packs] == [11, 11, 11]
    after = tier.counters()
    assert after["pack_calls"] - before["pack_calls"] == 3
    assert after["pack_walk_calls"] - before["pack_walk_calls"] == 1
    assert ek.pack_counters() == {k: after[k] for k in ("pack_calls", "pack_walk_calls")}


# -- collect() ------------------------------------------------------------------------


@pytest.fixture
def one_device(monkeypatch):
    monkeypatch.setattr(ek, "mesh_width", lambda: 1)
    monkeypatch.setattr(ek, "_sharded_verify", lambda: None)


@pytest.mark.parametrize("n, bucket", [(6, 8), (100, 128)])
def test_collect_answers_with_n_python_bools(one_device, monkeypatch, n, bucket):
    """On the CPU backend: the bitmap is a list of n Python bools equal to
    host_ok[:n] & dev_ok[:n] and to the scalar reference, a flipped lane
    and a shape-vetoed lane are refused where they stand, and the bucket's
    padded lanes, which the device evaluates to true, never appear."""
    pvs = [ed25519.gen_priv_key_from_secret(b"collect-%d" % i) for i in range(n)]
    pubs = [pv.pub_key().bytes() for pv in pvs]
    msgs = [b"collect-vote-%d" % (i % 3) * (1 + i % 2) for i in range(n)]
    sigs = [pv.sign(m) for pv, m in zip(pvs, msgs)]
    flipped, vetoed = 1, n - 2
    sigs[flipped] = sigs[flipped][:20] + bytes([sigs[flipped][20] ^ 0x40]) + sigs[flipped][21:]
    sigs[vetoed] = sigs[vetoed] + b"\x00"
    seen = {}
    route_for = ek._route_for

    def spy(operands, tables=None):
        fn, sharded = route_for(operands, tables)

        def run(*ops):
            seen["dev_ok"] = np.asarray(fn(*ops))
            return seen["dev_ok"]

        return run, sharded

    monkeypatch.setattr(ek, "_route_for", spy)
    collect = ek.batch_verify_submit(pubs, msgs, sigs)
    assert collect.program_key == (bucket, 2, 0)
    ok, bits = collect()
    _, host_ok = ek.pack_batch(pubs, msgs, sigs)
    dev_ok = seen["dev_ok"]
    assert dev_ok.shape == host_ok.shape == (bucket,)
    assert dev_ok[n:].all() and not host_ok[n:].any(), "padded lanes: true on the device, vetoed"
    assert type(bits) is list and len(bits) == n and {type(b) for b in bits} == {bool}
    assert bits == (host_ok[:n] & dev_ok[:n]).tolist()
    for i in {0, flipped, 2, n // 2, vetoed, n - 1}:  # the scalar reference is ~0.15 s a lane
        assert bits[i] == (len(sigs[i]) == 64 and pure.verify_zip215(pubs[i], msgs[i], sigs[i]))
    assert ok is False and [i for i, b in enumerate(bits) if not b] == [flipped, vetoed]
    assert dev_ok[vetoed], "a zero-packed lane verifies on the device: only the mask refuses it"
    clean = ek.batch_verify_submit(pubs[:flipped], msgs[:flipped], sigs[:flipped])()
    assert clean == (True, [True] * flipped) and type(clean[0]) is bool


# -- the padding rule, held once for both packers ------------------------------------


def test_write_padding_by_length_equals_hashlib_on_every_length():
    """Seeded messages of every length 0-300, shuffled so that no length's
    rows are contiguous, through pack_messages512 (which shares
    write_padding with the ed25519 challenge packer): the padded blocks
    are FIPS 180-4's, by hashlib's digest of each block stream."""
    rng = np.random.default_rng(2026)
    lens = np.concatenate([np.arange(301), rng.integers(0, 301, 99)])
    rng.shuffle(lens)
    msgs = [rng.bytes(int(ln)) for ln in lens]
    blocks, nblocks = s5.pack_messages512(msgs)
    assert nblocks.tolist() == [(len(m) + 17 + 127) // 128 for m in msgs]
    # undo the [B, 2, 16, N] hi/lo layout into each row's padded byte stream
    words = blocks.transpose(3, 0, 2, 1).reshape(len(msgs), -1)
    stream = words.astype(">u4").view(np.uint8)
    for i, m in enumerate(msgs):
        padded = stream[i, : nblocks[i] * 128].tobytes()
        assert padded[: len(m)] == m and padded[len(m)] == 0x80
        assert not any(padded[len(m) + 1 : -8]) and not stream[i, nblocks[i] * 128 :].any()
        assert int.from_bytes(padded[-8:], "big") == 8 * len(m)
    assert s5.sha512_batch(msgs[:64]) == [hashlib.sha512(m).digest() for m in msgs[:64]]


def test_rows_by_length_groups_every_row_once():
    assert s5.rows_by_length(np.zeros(0, np.int64)) == []
    (ln, rows), = s5.rows_by_length(np.full(5, 186))
    assert ln == 186 and rows == slice(None)
    lens = np.array([70, 64, 70, 300, 64, 70])
    groups = s5.rows_by_length(lens)
    assert [int(ln) for ln, _ in groups] == [64, 70, 300]
    assert [rows.tolist() for _, rows in groups] == [[1, 4], [0, 2, 5], [3]]
