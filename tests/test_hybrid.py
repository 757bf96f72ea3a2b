"""HybridBackend: concurrent device+host split of one verification batch.

The hybrid tier is this framework's answer to owning both an accelerator
and host SIMD at once — the reference's batch verifier is single-tier
(crypto/ed25519/ed25519.go:196-228). These tests run the real split on the
XLA:CPU "device" + the native C MSM: the bitmap contract must hold exactly
across the split boundary, small batches must route host-side, and a
missing native tier must fall back to the device path.
"""

from __future__ import annotations

import pytest

from cometbft_tpu import native
from cometbft_tpu.crypto import ed25519
from cometbft_tpu.sidecar import backend as be


def _batch(n, tag=b"hyb"):
    pvs = [ed25519.gen_priv_key_from_secret(tag + b"-%d" % i) for i in range(n)]
    pubs = [pv.pub_key().bytes() for pv in pvs]
    msgs = [b"hybrid-msg-%d" % i for i in range(n)]
    sigs = [pv.sign(m) for pv, m in zip(pvs, msgs)]
    return pubs, msgs, sigs


def _hybrid(monkeypatch, min_split=8, dev_rate=1000.0, host_rate=1000.0):
    monkeypatch.setenv("CMTPU_HYBRID_MIN", str(min_split))
    monkeypatch.setenv("CMTPU_DEV_RATE", str(dev_rate))
    monkeypatch.setenv("CMTPU_HOST_RATE", str(host_rate))
    monkeypatch.setenv("CMTPU_DEV_OVERHEAD_MS", "0")
    hb = be.HybridBackend()
    # Pin the planner's mesh pricing to one chip so the synthetic-rate
    # arithmetic these tests assert stays readable (the conftest mesh has 8
    # virtual devices); mesh pricing has its own tests below.
    hb._n_dev = 1
    return hb


needs_native = pytest.mark.skipif(
    not native.available(), reason="native tier unavailable"
)


@needs_native
def test_plan_picks_interior_bucket(monkeypatch):
    hb = _hybrid(monkeypatch)
    # Equal rates, no overhead: n=48 should split at bucket 32 (host 16),
    # not pad the whole batch to the 128 bucket or go all-host.
    assert hb._plan(48) == 32


@needs_native
def test_split_batch_all_valid(monkeypatch):
    hb = _hybrid(monkeypatch)
    pubs, msgs, sigs = _batch(48)
    ok, bits = hb.batch_verify(pubs, msgs, sigs)
    assert ok and bits == [True] * 48


@needs_native
def test_split_batch_bitmap_exact_across_boundary(monkeypatch):
    hb = _hybrid(monkeypatch)
    pubs, msgs, sigs = _batch(48)
    # Corrupt one signature inside the device share, one in the host share,
    # and one message right at the split boundary (index 32).
    bad = {3, 32, 45}
    sigs[3] = sigs[3][:-1] + bytes([sigs[3][-1] ^ 1])
    msgs[32] = msgs[32] + b"!"
    sigs[45] = b"\x00" * 64
    ok, bits = hb.batch_verify(pubs, msgs, sigs)
    assert not ok
    assert [i for i, b in enumerate(bits) if not b] == sorted(bad)


@needs_native
def test_small_batch_routes_host(monkeypatch):
    hb = _hybrid(monkeypatch, min_split=64)
    hb._tpu.batch_verify = lambda *a: pytest.fail("device tier must not run")
    pubs, msgs, sigs = _batch(24)
    ok, bits = hb.batch_verify(pubs, msgs, sigs)
    assert ok and all(bits)


def test_native_missing_falls_back_to_device(monkeypatch):
    hb = _hybrid(monkeypatch)

    class _NoNative:
        @staticmethod
        def ready():
            return None

        @staticmethod
        def ensure_built_async():
            pass

    hb._native = _NoNative()
    called = {}

    def _fake_dev(p, m, s):
        called["n"] = len(p)
        return True, [True] * len(p)

    hb._tpu.batch_verify = _fake_dev
    pubs, msgs, sigs = _batch(12)
    ok, _ = hb.batch_verify(pubs, msgs, sigs)
    assert ok and called["n"] == 12


@needs_native
def test_verify_and_root_overlap(monkeypatch):
    from cometbft_tpu.crypto.merkle import hash_from_byte_slices

    hb = _hybrid(monkeypatch)
    pubs, msgs, sigs = _batch(48)
    leaves = [b"leaf-%d" % i for i in range(100)]
    (ok, bits), root = hb.verify_and_root(pubs, msgs, sigs, leaves)
    assert ok and all(bits)
    assert root == hash_from_byte_slices(leaves)


@needs_native
def test_rate_ema_stays_clamped(monkeypatch):
    hb = _hybrid(monkeypatch)
    pubs, msgs, sigs = _batch(48)
    for _ in range(3):
        hb.batch_verify(pubs, msgs, sigs)
    assert 5.0 <= hb._dev_rate <= 5000.0
    assert 5.0 <= hb._host_rate <= 5000.0


def test_backend_env_selects_hybrid(monkeypatch):
    monkeypatch.setenv("CMTPU_BACKEND", "hybrid")
    be.set_backend(None)
    try:
        assert be.get_backend().name == "hybrid"
    finally:
        be.set_backend(None)


@needs_native
def test_all_device_call_feeds_the_buckets_wall(monkeypatch):
    """An all-device call keeps feeding the model: the bucket it ran gets a
    wall from the second call on (the first is the program's warm-up), and
    the wall is the device's own, not longer than the call."""
    from cometbft_tpu.ops import ed25519_kernel as ek

    hb = _hybrid(monkeypatch, dev_rate=5000.0, host_rate=5.0)
    pubs, msgs, sigs = _batch(48)
    assert hb._plan(48) >= 48  # model says all-device
    ok, bits = hb.batch_verify(pubs, msgs, sigs)
    assert ok and all(bits)
    assert hb.last_share == 48
    assert hb.last_timing["first_use"] and hb._dev_wall == {}
    hb.batch_verify(pubs, msgs, sigs)
    wall = hb._dev_wall[(ek.bucket_for(48), hb._n_dev, False)]
    assert 0 < wall <= hb.last_timing["total_ms"]
    assert wall == pytest.approx(hb.last_timing["dev_wall_ms"], abs=0.01)


@needs_native
def test_small_batches_touch_no_wall(monkeypatch):
    """A call under CMTPU_HYBRID_MIN goes to the host and leaves the model
    learned on commit-sized calls alone: no wall, no rate, no split count."""
    hb = _hybrid(monkeypatch, min_split=64)
    with hb._rate_lock:
        hb._dev_wall[(128, 1, False)] = 7.0
    rates = (hb._dev_rate, hb._host_rate)
    pubs, msgs, sigs = _batch(16)
    ok, bits = hb.batch_verify(pubs, msgs, sigs)
    assert ok and all(bits)
    assert hb._dev_wall == {(128, 1, False): 7.0}
    assert (hb._dev_rate, hb._host_rate) == rates
    assert hb.counters()["split_calls"] == 0 and hb.last_timing == {}


# -- the planner on a simulated chip: no device, no sleeps ---------------------------

# The chip's affine costs in ms (PERF.md section 5, chip runs of PR 24): the
# device program, the pack before it, the unpack inside collect(), the host MSM.
DEV_MS = (9.0, 6.35e-3)
# The resident program (ISSUE 35's count: ~0.35 of the ladder's per-lane part).
RESIDENT_DEV_MS = (9.0, 2.2e-3)
PACK_MS_PER_LANE = 0.8e-3
UNPACK_MS = 1.3
HOST_MS = (12.4, 20.7e-3)


class _SimulatedTiers:
    """Stands in for both tiers on a clock that only the tiers' own costs
    move: `submit` packs and starts the device program, `batch_verify` is
    the native MSM, collect() waits for the program if it still runs and
    unpacks. Lanes are placeholders; every bitmap is all true. The key
    column's look-up answers `resident_lanes` (0: no tables; else the lanes
    of the tables every call's column is a prefix of)."""

    def __init__(self, monkeypatch, hb):
        from cometbft_tpu.ops import ed25519_kernel as ek

        self.now = 0.0
        self.stall_ms = 0.0  # a host stall inside the next pack, once
        self.resident_lanes = 0
        self.compile_ms = 0.0  # a program's first use, once per program key
        self._seen_keys: set = set()
        self._ek = ek
        monkeypatch.setattr(be, "time", self)
        monkeypatch.setattr(ek, "batch_verify_submit", self.submit)
        monkeypatch.setattr(ek, "sight_column", self.sight)
        hb._native = self

    def perf_counter(self):
        return self.now

    @staticmethod
    def ready():
        return object()

    @staticmethod
    def status():
        return "simulated"

    def sight(self, pubs):
        import numpy as np

        tables = (None, np.zeros(self.resident_lanes, bool)) if self.resident_lanes else None
        return self._ek.Sighting(tables, None)

    def submit(self, pubs, msgs, sigs, sighting=None):
        n = len(pubs)
        resident = sighting is not None and sighting.tables is not None
        # the resident program has one shape a column: the tables' own bucket
        bucket = len(sighting.tables[1]) if resident else self._ek.bucket_for(n)
        key = (bucket, 2, bucket if resident else 0)
        self.now += (PACK_MS_PER_LANE * n + self.stall_ms) / 1000
        self.stall_ms = 0.0
        started = self.now
        fixed, per_lane = RESIDENT_DEV_MS if resident else DEV_MS
        returned = started + (fixed + per_lane * bucket) / 1000
        if key not in self._seen_keys:
            self._seen_keys.add(key)
            returned += self.compile_ms / 1000

        def collect():
            self.now = max(self.now, returned) + UNPACK_MS / 1000
            collect.run_times = (started, returned)
            return True, [True] * n

        collect.program_key = key
        collect.run_times = None
        return collect

    def batch_verify(self, pubs, msgs, sigs):
        self.now += (HOST_MS[0] + HOST_MS[1] * len(pubs)) / 1000
        return True, [True] * len(pubs)


def _simulated(monkeypatch):
    hb = _hybrid(monkeypatch, min_split=2048, dev_rate=100.0, host_rate=70.0)
    hb._dev_overhead = 8.0  # with it, the shipped priors, all four
    return hb, _SimulatedTiers(monkeypatch, hb)


def _start_fresh(hb, lanes):
    pass


def _start_poisoned(hb, lanes):
    # the stuck run of PR 24: the host's ~100 ms booked as the 6,144 wall
    hb._warmed.add((6144, 2, 0, 1))
    hb._dev_wall[(6144, 1, False)] = 100.0
    hb._host_rate = 42.0


def _start_forced(hb, lanes):
    hb._routed_call(*lanes, 6144)


@pytest.mark.parametrize("start", [_start_fresh, _start_poisoned, _start_forced])
def test_planner_reaches_the_fast_share_and_holds_it(monkeypatch, start):
    """10,000 lanes on the chip's costs: 8,192 is the share (~70 ms a call
    against ~84 all-device and ~98 at 6,144). From the priors, from the
    mis-learned wall that held a whole run at 6,144, and after a first call
    at 6,144, the planner is there within 8 calls and never leaves."""
    hb, sim = _simulated(monkeypatch)
    lanes = ([None] * 10000,) * 3
    start(hb, lanes)
    shares = []
    for _ in range(8):
        ok, bits = hb.batch_verify(*lanes)
        assert ok and len(bits) == 10000
        shares.append(hb.last_share)
    assert shares[-1] == 8192, shares
    assert 4096 not in shares and 0 not in shares, shares
    changes = hb.counters()["share_changes"]
    t0 = sim.now
    for _ in range(50):
        hb.batch_verify(*lanes)
        assert hb.last_share == 8192
    c = hb.counters()
    assert c["share_changes"] == changes
    assert (sim.now - t0) * 1000 / 50 == pytest.approx(69.6, abs=1.0)  # max(6.55+61.02, 6.55+49.83)+1.3
    # the model behind the share is right: the last calls are predicted within 5%
    assert hb.last_timing["dev_wall_ms"] == pytest.approx(67.57, abs=0.5)
    assert abs(hb._plan_cost(10000)[1] - hb.last_timing["total_ms"]) < 0.05 * hb.last_timing["total_ms"]


def test_one_stalled_call_does_not_strand_the_planner(monkeypatch):
    """The chip's machine stalls the host now and then (116 ms inside one
    pack in 815 calls, and a 20 s run left at 6,144 for good by a larger
    one: chip runs of PR 25). The stall is booked as that call's device
    wall, but a bucket the planner leaves is never measured again, so one
    such call must not move the bucket's wall: the share holds."""
    hb, sim = _simulated(monkeypatch)
    lanes = ([None] * 10000,) * 3
    for _ in range(12):
        hb.batch_verify(*lanes)
    assert hb.last_share == 8192
    changes, wall = hb.counters()["share_changes"], hb._dev_wall[(8192, 1, False)]
    sim.stall_ms = 150.0
    hb.batch_verify(*lanes)
    assert hb.last_timing["dev_wall_ms"] == pytest.approx(wall + 150.0, abs=0.1)
    assert hb._dev_wall[(8192, 1, False)] == pytest.approx(wall, abs=0.1)
    for _ in range(20):
        hb.batch_verify(*lanes)
        assert hb.last_share == 8192
    assert hb.counters()["share_changes"] == changes


def test_device_wall_learned_when_the_host_is_late(monkeypatch):
    """A split call whose collect() returns at once (the device finished
    long before the host) still books the bucket's wall, from the owner
    thread's stamps, far below the call's wall: the 6,144 share must read
    as ~53 ms of device beside ~92 ms of host, not as a ~100 ms device."""
    hb, sim = _simulated(monkeypatch)
    lanes = ([None] * 10000,) * 3
    hb._routed_call(*lanes, 6144)  # the program's first use: nothing booked
    assert hb._dev_wall == {}
    hb._routed_call(*lanes, 6144)
    t = hb.last_timing
    assert t["dev_wait_ms"] == pytest.approx(UNPACK_MS, abs=0.01)  # collect() did not block
    assert t["host_msm_ms"] == pytest.approx(92.22, abs=0.1) and t["total_ms"] > 98
    assert hb._dev_wall == {(6144, 1, False): pytest.approx(52.93, abs=0.1)}  # 4.92 pack + 48.01 run
    assert t["dev_wall_ms"] == pytest.approx(52.93, abs=0.1)
    assert t["dev_run_ms"] == pytest.approx(48.01, abs=0.1)


# -- two programs a bucket: the ladder's walls and the resident tables' ---------------


def test_walls_are_booked_by_kind(monkeypatch):
    """One bucket, two programs: a call over a resident column books the
    resident wall of the bucket it ran (the tables' own, whatever the
    share) and leaves the ladder's alone, and the planner prices each kind
    of call with its own kind's walls."""
    hb, sim = _simulated(monkeypatch)
    lanes = ([None] * 10000,) * 3
    hb._routed_call(*lanes, 10000)
    hb._routed_call(*lanes, 10000)
    ladder = hb._dev_wall[(10240, 1, False)]
    assert ladder == pytest.approx(8.0 + 9.0 + 6.35e-3 * 10240, abs=0.1) and len(hb._dev_wall) == 1
    sim.resident_lanes = 10240
    sighting = sim.sight(lanes[0])
    hb._routed_call(*lanes, 8192, sighting=sighting)  # the resident program's first use
    assert hb.last_timing["resident"] and hb.last_timing["first_use"]
    assert hb._dev_wall == {(10240, 1, False): ladder}
    hb._routed_call(*lanes, 8192, sighting=sighting)
    resident = 6.55 + 9.0 + 2.2e-3 * 10240  # an 8,192-lane share, widened to the tables' 10,240
    assert hb._dev_wall == {
        (10240, 1, False): ladder,
        (10240, 1, True): pytest.approx(resident, abs=0.1),
    }
    # each kind of call is priced with its own kind's walls
    assert hb._plan_cost(10000)[1] > 49  # the ladder: a split the host's 1,808 lanes pace, or worse
    assert hb._plan_cost(10000, 10240) == (10000, pytest.approx(resident, abs=0.1))


def test_resident_programs_first_use_is_left_out_of_the_model(monkeypatch):
    """The resident program of a bucket the ladder has long warmed is a new
    program: its first dispatch (an XLA compile, 30 s here) books no wall,
    moves no rate and counts no planning error."""
    hb, sim = _simulated(monkeypatch)
    lanes = ([None] * 10000,) * 3
    for _ in range(6):
        hb.batch_verify(*lanes)
    walls, rate = dict(hb._dev_wall), hb._dev_rate
    err = hb.counters()["plan_abs_err_ms"]
    sim.resident_lanes, sim.compile_ms = 10240, 30000.0
    hb.batch_verify(*lanes)
    assert hb.last_timing["resident"] and hb.last_timing["first_use"]
    assert hb.last_timing["dev_wall_ms"] > 30000
    assert hb._dev_wall == walls and hb._dev_rate == rate
    assert hb.counters()["plan_abs_err_ms"] == err
    hb.batch_verify(*lanes)
    assert not hb.last_timing["first_use"]
    assert (10240, 1, True) in hb._dev_wall


def test_planner_with_a_resident_wall_goes_all_device(monkeypatch):
    """10,000 lanes, the ladder's split at 8,192 learned (host 1,808 lanes
    ~49 ms). Once the column is resident and its program's wall is booked,
    all-device prices lower than any split the host share paces, and the
    planner goes there and stays: no prior, no constant, the walls alone.
    One resident program is loaded on the way, not one a share."""
    hb, sim = _simulated(monkeypatch)
    lanes = ([None] * 10000,) * 3
    for _ in range(12):
        hb.batch_verify(*lanes)
    assert hb.last_share == 8192
    assert hb.last_timing["host_msm_ms"] == pytest.approx(49.8, abs=0.5)
    sim.resident_lanes, sim.compile_ms = 10240, 20000.0
    seen = set(sim._seen_keys)
    shares = []
    for _ in range(6):
        ok, bits = hb.batch_verify(*lanes)
        assert ok and len(bits) == 10000
        shares.append(hb.last_share)
    assert (10240, 1, True) in hb._dev_wall
    assert shares[2:] == [10000] * 4, shares  # first use, the wall's booking, then there
    assert 0 not in shares
    assert sim._seen_keys - seen == {(10240, 2, 10240)}
    t0 = sim.now
    for _ in range(20):
        hb.batch_verify(*lanes)
        assert hb.last_share == 10000 and hb.last_timing["n_host"] == 0
    # pack 8.0 + run 9.0 + 2.2e-3 * 10,240 + unpack 1.3
    assert (sim.now - t0) * 1000 / 20 == pytest.approx(40.8, abs=0.5)
    # a column that is not resident is still priced, and split, as before
    sim.resident_lanes = 0
    hb.batch_verify(*lanes)
    assert hb.last_share == 8192 and not hb.last_timing["resident"]


def test_run_stamps_taken_with_tracing_off():
    """The device-owner thread stamps its start and return on every
    dispatch, profiler session or none: the planner's reading cannot
    depend on the device.run span, which exists only while traced."""
    import time

    from cometbft_tpu.libs import trace
    from cometbft_tpu.ops import ed25519_kernel as ek

    trace.clear()
    assert not trace.spans()
    pubs, msgs, sigs = _batch(12, tag=b"stamps")
    t0 = time.perf_counter()
    collect = ek.batch_verify_submit(pubs, msgs, sigs)
    assert collect.run_times is None  # nothing to read before collect()
    ok, bits = collect()
    t1 = time.perf_counter()
    assert ok and bits == [True] * 12
    started, returned = collect.run_times
    assert t0 <= started <= returned <= t1
    assert not trace.spans(), "no session is open: no span was recorded"


def test_multi_device_routing_shards_the_shipped_seam(monkeypatch):
    """With >1 local device (the 8-device virtual mesh the conftest pins),
    the device tier's batch_verify must route over the sharded sig mesh —
    all chips working the batch — with the exact per-signature bitmap.
    A spy proves the sharded program actually executed."""
    from cometbft_tpu.ops import ed25519_kernel as ek

    sh = ek._sharded_verify()
    assert sh is not None and sh[0] == 8
    called = {}

    def spy(*ops):
        called["sharded"] = True
        return sh[1](*ops)

    monkeypatch.setattr(ek, "_sharded_verify", lambda: (sh[0], spy))
    pubs, msgs, sigs = _batch(48, tag=b"mdev")
    sigs[7] = b"\x00" * 64
    msgs[40] = msgs[40] + b"x"
    ok, bits = ek.batch_verify(pubs, msgs, sigs)
    assert not ok
    assert [i for i, b in enumerate(bits) if not b] == [7, 40]
    assert called.get("sharded"), "batch_verify did not route via the mesh"


def test_plan_snapshots_dev_wall_under_rate_lock(monkeypatch):
    """_plan races _update_rates: straggler-collect threads insert
    first-observation bucket keys into _dev_wall under _rate_lock while
    _plan iterates the model.  The plan must work from a locked snapshot —
    regression for RuntimeError('dictionary changed size during iteration')
    escaping batch_verify into consensus/blocksync callers."""
    import threading

    hb = _hybrid(monkeypatch)
    stop = threading.Event()
    failures = []

    def writer():
        # Same access pattern as _update_rates: mutate only under the lock,
        # churning keys so an unlocked iteration over the live dict would
        # observe size changes.
        k = 0
        while not stop.is_set():
            k += 1
            with hb._rate_lock:
                hb._dev_wall[(128 * (k % 64 + 1), 1, False)] = 1.0 + (k % 7)
                if k % 5 == 0:
                    hb._dev_wall.pop((128 * ((k * 31) % 64 + 1), 1, False), None)

    t = threading.Thread(target=writer, daemon=True)
    t.start()
    try:
        for _ in range(2000):
            try:
                share = hb._plan(4096)
            except RuntimeError as e:  # the exact pre-fix failure mode
                failures.append(e)
                break
            assert share >= 0
    finally:
        stop.set()
        t.join(timeout=2)
    assert not failures, f"_plan raced the rate model: {failures[0]}"


@pytest.mark.mesh
def test_plan_prices_mesh_as_one_large_device(monkeypatch):
    """With symmetric per-chip rates the single-chip planner splits a batch
    evenly; an 8-chip mesh must be priced as one 8x-faster device (per-chip
    rate x width over one shared dispatch overhead) and take ~8/9 of it."""
    hb = _hybrid(monkeypatch, dev_rate=100.0, host_rate=100.0)
    hb._n_dev = 1
    assert hb._plan(9216) == 4096
    hb._n_dev = 8
    assert hb._plan(9216) == 8192


@pytest.mark.mesh
def test_dev_walls_keyed_by_mesh_width(monkeypatch):
    """A wall observed at one mesh width must be invisible at another —
    a stale single-chip wall would make the planner starve the mesh."""
    hb = _hybrid(monkeypatch, dev_rate=100.0, host_rate=100.0)
    with hb._rate_lock:
        hb._dev_wall[(8192, 1, False)] = 1e9  # poisoned single-chip observation
    hb._n_dev = 8
    assert hb._plan(9216) == 8192  # the width-1 wall does not apply
    hb._n_dev = 1
    assert hb._plan(9216) == 0  # ...but at width 1 it routes all-host


@pytest.mark.mesh
def test_warm_keys_include_mesh_width(monkeypatch):
    """First dispatch at a NEW mesh width must count as first_use (a fresh
    sharded program compiles) even when the same (batch, block) program was
    already warm at another width."""
    hb = _hybrid(monkeypatch)
    ts = (0.0, 0.001, 0.002, 0.002, 0.050, (0.001, 0.049))
    hb._n_dev = 1
    hb._update_rates((128, 2, 0), 128, 0, *ts)
    assert hb.last_timing["first_use"]
    hb._update_rates((128, 2, 0), 128, 0, *ts)
    assert not hb.last_timing["first_use"]
    hb._n_dev = 8
    hb._update_rates((128, 2, 0), 128, 0, *ts)
    assert hb.last_timing["first_use"], "width change must re-warm"
