"""Resident window tables (ops/ed25519_kernel `_ResidentColumns`,
`verify_core_resident`): a key column seen twice gets its fixed-window
tables built on the device, and from then on its commits run 128 table
additions a lane and no doubling. Held here: the resident program's bitmap
is the ladder's and the scalar ZIP-215 reference's lane for lane, and the
policy that decides which program a call rides, from the call's key bytes
alone. One bucket (32) on one device, so the file compiles three small
programs; the mesh has two more."""

import time

import numpy as np
import pytest

import jax

from cometbft_tpu.crypto import ed25519
from cometbft_tpu.crypto import ed25519_pure as pure
from cometbft_tpu.libs import trace
from cometbft_tpu.ops import ed25519_kernel as ek

LANES = 24  # bucket 32 on one device


@pytest.fixture
def one_device(monkeypatch):
    """The routing of a one-chip host (the conftest mesh has 8 devices),
    with CMTPU_HYBRID_MIN small enough for a 24-key column."""
    monkeypatch.setattr(ek, "mesh_width", lambda: 1)
    monkeypatch.setattr(ek, "_sharded_verify", lambda: None)
    monkeypatch.setattr(ek, "BUCKETS", (8, 16, 32, 128))  # a bucket between half and whole
    monkeypatch.setenv("CMTPU_HYBRID_MIN", "8")
    programs = ek._resident_programs  # a later fixture may stand in for it
    programs.cache_clear()
    yield
    programs.cache_clear()


def _enc(y: int, sign: int = 0) -> bytes:
    return (y | (sign << 255)).to_bytes(32, "little")


# lane -> what it holds; every other lane is a valid key with its signature
KINDS = {
    3: "flipped signature",
    6: "wrong message",
    9: "s == L",
    12: "s = L - 1, garbage R",
    16: "small-order key (the identity), s = 0",
    17: "non-canonical key (y = 1 + p), s = 0",
    18: "key with y >= p (reduces mod p)",
    19: "key that does not decode (x = 0, sign 1)",
    20: "key y = p - 1",
}


def _column(tag: bytes = b"resident", msg_tag: bytes = b"h1"):
    """24 DISTINCT well-formed keys, the ZIP-215 edge keys among them, and
    one commit's worth of messages and signatures over them."""
    pvs = [ed25519.gen_priv_key_from_secret(tag + b"-%d" % i) for i in range(LANES)]
    pubs = [pv.pub_key().bytes() for pv in pvs]
    msgs = [b"%s-vote-%d" % (msg_tag, i) for i in range(LANES)]
    sigs = [pv.sign(m) for pv, m in zip(pvs, msgs)]
    sigs[3] = sigs[3][:20] + bytes([sigs[3][20] ^ 0x40]) + sigs[3][21:]
    msgs[6] = b"tampered"
    sigs[9] = sigs[9][:32] + pure.L.to_bytes(32, "little")
    sigs[12] = b"\x11" * 32 + (pure.L - 1).to_bytes(32, "little")
    identity, s0 = _enc(1), (0).to_bytes(32, "little")
    pubs[16], sigs[16] = identity, identity + s0
    pubs[17], sigs[17] = _enc(1 + pure.P), identity + s0
    pubs[18] = _enc((1 << 255) - 1)
    pubs[19] = _enc(0, 1)
    pubs[20] = _enc(pure.P - 1)
    assert len(set(pubs)) == LANES
    return pubs, msgs, sigs


def _wait_for_builds(n: int, timeout: float = 300.0) -> None:
    deadline = time.monotonic() + timeout
    while ek.resident_counters()["resident_builds"] < n:
        assert time.monotonic() < deadline, "the table build did not land"
        time.sleep(0.02)


def _make_resident(pubs, msgs, sigs):
    """Two sightings and the build's landing; returns the ladder's bitmap."""
    builds = ek.resident_counters()["resident_builds"]
    ek.batch_verify(pubs, msgs, sigs)
    _, bits = ek.batch_verify(pubs, msgs, sigs)
    _wait_for_builds(builds + 1)
    return bits


@pytest.fixture
def bitmaps(one_device):
    """(ladder, resident, resident prefix share of 12, scalar reference)."""
    pubs, msgs, sigs = _column()
    ladder = _make_resident(pubs, msgs, sigs)
    calls = ek.resident_counters()["resident_calls"]
    _, resident = ek.batch_verify(pubs, msgs, sigs)
    # the hybrid's device share: a prefix, looked up as part of the whole column
    # (12 lanes, bucket 16: widened to the tables' 32, their one program's shape)
    share = ek.batch_verify_submit(pubs[:12], msgs[:12], sigs[:12], ek.sight_column(pubs))
    assert share.program_key == (32, 2, 32)
    _, prefix = share()
    assert ek.resident_counters()["resident_calls"] == calls + 2
    want = [pure.verify_zip215(p, m, s) for p, m, s in zip(pubs, msgs, sigs)]
    return ladder, resident, prefix, want


@pytest.mark.parametrize("lane", sorted(KINDS), ids=lambda j: KINDS[j].split(",")[0].split(" (")[0])
def test_resident_bitmap_is_the_ladders_and_the_references(bitmaps, lane):
    ladder, resident, _, want = bitmaps
    assert resident[lane] == ladder[lane] == want[lane], KINDS[lane]


def test_resident_bitmap_whole_column_and_prefix_share(bitmaps):
    ladder, resident, prefix, want = bitmaps
    assert resident == ladder == want
    assert prefix == want[:12] and not all(prefix)
    assert [j for j, ok in enumerate(want) if ok] == sorted(
        set(range(LANES)) - set(KINDS) | {16, 17}
    )


def test_resident_program_refuses_a_flipped_commit_of_known_keys(one_device):
    """The same keys, another height, bad signatures: the tables are the
    keys', the verdict is the signatures'."""
    pubs, msgs, sigs = _column()
    _make_resident(pubs, msgs, sigs)
    _, msgs2, sigs2 = _column(msg_tag=b"h2")
    sigs2[0] = sigs2[0][:40] + bytes([sigs2[0][40] ^ 1]) + sigs2[0][41:]
    sigs2[23] = sigs2[22]
    calls = ek.resident_counters()["resident_calls"]
    ok, bits = ek.batch_verify(pubs, msgs2, sigs2)
    assert ek.resident_counters()["resident_calls"] == calls + 1
    want = [pure.verify_zip215(p, m, s) for p, m, s in zip(pubs, msgs2, sigs2)]
    assert not ok and bits == want and not bits[0] and not bits[23] and bits[1]


# -- the policy ------------------------------------------------------------------------


def _runs_and_builds():
    spans = trace.spans()
    return (
        [s for s in spans if s["name"] == "device.run"],
        [s for s in spans if s["name"] == "device.table_build"],
    )


def test_first_sighting_builds_nothing_second_builds_once_behind_its_dispatch(one_device):
    pubs, msgs, sigs = _column()
    before = ek.resident_counters()
    trace.clear()
    with trace.capture():
        ek.batch_verify(pubs, msgs, sigs)
        c1 = ek.resident_counters()
        assert c1["resident_first_sightings"] == before["resident_first_sightings"] + 1
        assert c1["resident_builds"] == before["resident_builds"]
        runs, builds = _runs_and_builds()
        assert [r["attrs"]["resident"] for r in runs] == [False] and not builds

        ek.batch_verify(pubs, msgs, sigs)
        _wait_for_builds(before["resident_builds"] + 1)
        runs, builds = _runs_and_builds()
        assert [r["attrs"]["resident"] for r in runs] == [False, False]
        (build,) = builds
        # on the owner thread, after the dispatch that asked, inside nobody's operation
        assert build["thread"] == runs[1]["thread"] == "cmtpu-dev"
        assert build["t0"] >= runs[1]["t1"]
        assert build["parent"] is None and build["root"] not in {r["root"] for r in runs}
        assert build["attrs"] == {"lanes": 32, "bytes": 32 * ek.TABLE_BYTES_PER_LANE}

        ek.batch_verify(pubs, msgs, sigs)
        ek.batch_verify(pubs, msgs, sigs)
        runs, builds = _runs_and_builds()
        assert [r["attrs"]["resident"] for r in runs] == [False, False, True, True]
        assert len(builds) == 1, "a resident column is not built again"
    after = ek.resident_counters()
    assert after["resident_builds"] == before["resident_builds"] + 1
    assert after["resident_calls"] == before["resident_calls"] + 2
    assert after["resident_lanes"] == before["resident_lanes"] + 2 * LANES
    assert after["resident_bytes"] == before["resident_bytes"] + 32 * ek.TABLE_BYTES_PER_LANE
    assert after["resident_build_ms"] > before["resident_build_ms"]
    pack = [s for s in trace.spans() if s["name"] == "device.pack"]
    assert [s["attrs"]["resident"] for s in pack] == [False, False, True, True]


@pytest.fixture
def stub_programs(one_device, monkeypatch):
    """The policy alone: a build program that allocates nothing."""
    built = []

    def build(a_words):
        built.append(a_words.shape[1])
        return np.zeros((1,), np.int32), np.zeros((a_words.shape[1],), bool)

    monkeypatch.setattr(ek, "_resident_programs", lambda sharded: (build, None))
    return built


def _see_twice(cols, pubs):
    cols.sight(pubs)
    s = cols.sight(pubs)
    if s.build is not None:
        cols.queue_build(s.build)
        deadline = time.monotonic() + 30
        while cols.sight(pubs).tables is None:
            assert time.monotonic() < deadline
            time.sleep(0.005)
    return cols.sight(pubs)


def _keys(n: int, tag: bytes):
    import hashlib

    return [hashlib.sha256(tag + b"%d" % i).digest() for i in range(n)]


@pytest.mark.parametrize(
    "change",
    ["one key changed", "one key repeated", "under the minimum", "a malformed key", "a longer column"],
)
def test_columns_that_are_never_resident(stub_programs, change):
    cols = ek._ResidentColumns()
    pubs = _keys(LANES, b"known")
    assert _see_twice(cols, pubs).tables is not None
    builds = cols.counters()["resident_builds"]
    other = list(pubs)
    if change == "one key changed":
        other[11] = _keys(1, b"new")[0]
    elif change == "one key repeated":
        other[11] = other[10]
    elif change == "under the minimum":
        other = other[:7]  # CMTPU_HYBRID_MIN is 8 here: even a prefix is not looked up
    elif change == "a malformed key":
        other[11] = other[11][:31]
    else:
        other = other + _keys(1, b"joined")
    repeats = cols.counters()
    first = cols.sight(other)
    # a column refused for repeating a key says how many keys it holds, and is counted
    repeated = change == "one key repeated"
    assert first[:3] == (None, None, LANES - 1 if repeated else 0)
    again = cols.sight(other)
    assert again.tables is None
    grown = {k: cols.counters()[k] - repeats[k] for k in ("resident_repeat_sightings", "resident_repeat_lanes")}
    assert grown == {"resident_repeat_sightings": 2 * repeated, "resident_repeat_lanes": 2 * LANES * repeated}
    # only a whole, distinct, well-formed column of its own is on its way to tables
    assert (again.build is not None) == (change in ("one key changed", "a longer column"))
    assert cols.counters()["resident_builds"] == builds
    assert cols.sight(pubs).tables is not None, "the known column stays resident"


def test_a_prefix_of_a_resident_column_rides_its_tables(stub_programs):
    cols = ek._ResidentColumns()
    pubs = _keys(LANES, b"known")
    whole = _see_twice(cols, pubs)
    assert cols.sight(pubs[:16]).tables is whole.tables
    assert cols.sight(pubs[1:17]).tables is None


def test_the_bytes_bound_evicts_the_oldest_column(stub_programs, monkeypatch):
    per_column = 32 * ek.TABLE_BYTES_PER_LANE
    monkeypatch.setattr(ek, "RESIDENT_MAX_BYTES", 2 * per_column)
    cols = ek._ResidentColumns()
    a, b, c = (_keys(LANES, t) for t in (b"a", b"b", b"c"))
    assert _see_twice(cols, a).tables is not None
    assert _see_twice(cols, b).tables is not None
    assert cols.counters()["resident_bytes"] == 2 * per_column
    assert cols.sight(a).tables is not None  # a is now the newer of the two
    assert _see_twice(cols, c).tables is not None
    counters = cols.counters()
    assert counters["resident_evictions"] == 1 and counters["resident_bytes"] == 2 * per_column
    assert cols.sight(b).tables is None, "the oldest went"
    assert cols.sight(a).tables is not None and cols.sight(c).tables is not None
    # a column larger than the bound is never remembered at all
    monkeypatch.setattr(ek, "RESIDENT_MAX_BYTES", per_column - 1)
    big = _keys(LANES, b"big")
    cols.sight(big)
    assert cols.sight(big)[:3] == (None, None, 0)


def test_concurrent_sightings_build_each_column_once(stub_programs):
    """More callers than cores see four columns at once: every column is
    built exactly once, and the bytes and the builds add up."""
    import sys
    import threading

    cols = ek._ResidentColumns()
    columns = [_keys(LANES, b"stress-%d" % i) for i in range(4)]
    errors = []

    def caller(k):
        try:
            for i in range(40):
                pubs = columns[(k + i) % 4]
                s = cols.sight(pubs)
                if s.build is not None:
                    cols.queue_build(s.build)
        except Exception as e:  # surfaced below
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=caller, args=(k,)) for k in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not any(t.is_alive() for t in threads) and not errors, errors
    finally:
        sys.setswitchinterval(interval)
    deadline = time.monotonic() + 30
    while cols.counters()["resident_builds"] < 4:
        assert time.monotonic() < deadline
        time.sleep(0.005)
    c = cols.counters()
    assert c["resident_builds"] == 4 and c["resident_first_sightings"] == 4
    assert c["resident_bytes"] == 4 * 32 * ek.TABLE_BYTES_PER_LANE
    assert sorted(stub_programs) == [32] * 4
    assert all(cols.sight(pubs).tables is not None for pubs in columns)


def test_resident_and_other_lanes_add_up_to_the_lanes_run(one_device):
    from cometbft_tpu.sidecar import backend as be

    tier = be.TpuBackend()
    before = tier.counters()
    pubs, msgs, sigs = _column()
    for _ in range(2):
        tier.batch_verify(pubs, msgs, sigs)
    _wait_for_builds(before["resident_builds"] + 1)
    other = _column(tag=b"another")
    tier.batch_verify(pubs, msgs, sigs)  # resident
    tier.batch_verify(pubs[:12], msgs[:12], sigs[:12])  # a long prefix: resident too
    tier.batch_verify(*other)  # a first sighting: the ladder
    c = {k: v - before[k] for k, v in tier.counters().items() if k.endswith(("_lanes", "_calls", "_builds", "_sightings"))}
    assert c["device_lanes"] == 3 * LANES + 12 + LANES
    assert c["resident_lanes"] == LANES + 12 and c["resident_calls"] == 2
    assert c["resident_builds"] == 1 and c["resident_first_sightings"] == 2
    assert c["device_lanes"] - c["resident_lanes"] == 3 * LANES  # the ladder's


def test_which_dispatches_a_columns_tables_serve():
    """The device-hash program only; the column's own lanes or a prefix of
    at least half its bucket, widened to the tables' one shape; a short
    prefix and the host-hash program (a message past the largest block
    bucket) keep the ladder — on one chip and on the mesh alike."""
    tables = (None, np.zeros(32, bool))
    assert ek._tables_serve((32, 2), tables) and ek._tables_serve((16, 2), tables)
    assert not ek._tables_serve((8, 2), tables), "under half: the ladder is cheaper"
    assert not ek._tables_serve((32, 0), tables), "the host-hash program"
    assert not ek._tables_serve((128, 2), tables), "more lanes than the tables hold"
    assert not ek._tables_serve((32, 2), None)
    r, s_, m, nb = ek._widen(
        (np.ones((8, 16), np.int32), np.ones((8, 16), np.int32),
         np.ones((16, 64), np.uint32), np.ones(16, np.int32)), 32)
    assert r.shape == s_.shape == (8, 32) and m.shape == (32, 64) and nb.shape == (32,)
    assert not r[:, 16:].any() and not m[16:].any() and not nb[16:].any() and nb[:16].all()


# -- the mesh ----------------------------------------------------------------------------


@pytest.mark.mesh
def test_sharded_resident_program_equals_the_single_device_one():
    """Four virtual devices: tables built and added against with the lane
    axis sharded give the single-device programs' tables and bitmap."""
    from cometbft_tpu.ops import sharded

    pubs, msgs, sigs = _column()
    operands, host_ok = ek.pack_batch(pubs, msgs, sigs)
    assert operands[0].shape[1] == 32
    tables1 = jax.jit(ek.build_key_tables)(operands[0])
    single = np.asarray(jax.jit(ek.verify_core_resident)(*tables1, *operands[1:]))
    mesh = sharded.make_mesh(jax.local_devices()[:4])
    tables4 = sharded.sharded_build_fn(mesh)(operands[0])
    assert len(tables4[0].sharding.device_set) == 4
    assert np.array_equal(np.asarray(tables1[0]), np.asarray(tables4[0]))
    assert np.array_equal(np.asarray(tables1[1]), np.asarray(tables4[1]))
    meshed = np.asarray(sharded.sharded_resident_fn(mesh)(*tables4, *operands[1:]))
    assert np.array_equal(single, meshed)
    want = [pure.verify_zip215(p, m, s) for p, m, s in zip(pubs, msgs, sigs)]
    assert [bool(a and b) for a, b in zip(single[:LANES], host_ok)] == want


@pytest.mark.mesh
def test_on_the_mesh_a_resident_column_rides_the_sharded_programs(monkeypatch):
    """The shipped seam on the conftest's 8 virtual devices: the build and
    the resident program take the mesh route of the column's bucket, and
    the bitmap is the ladder's and the reference's."""
    monkeypatch.setenv("CMTPU_HYBRID_MIN", "8")
    assert ek._sharded_verify() is not None and ek.bucket_for(LANES) == 32
    pubs, msgs, sigs = _column()
    ladder = _make_resident(pubs, msgs, sigs)
    tables = ek.sight_column(pubs).tables
    assert len(tables[0].sharding.device_set) == 8
    before = ek.mesh_counters()["sharded_dispatches"]
    collect = ek.batch_verify_submit(pubs, msgs, sigs)
    assert collect.program_key == (32, 2, 32)
    _, resident = collect()
    assert ek.mesh_counters()["sharded_dispatches"] == before + 1
    want = [pure.verify_zip215(p, m, s) for p, m, s in zip(pubs, msgs, sigs)]
    assert resident == ladder == want
