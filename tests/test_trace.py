"""libs/trace.py (ISSUE 24): the program's one tracer. Off without a profiler
session (and without JAX); on, every layer boundary from `verify_commit`
and the blocksync reactor down to the device-owner thread leaves a span in
the ring and a `seam:` event in the profiler's xplane; the counters beside
them are always on."""

import glob
import os
import re
import subprocess
import sys
import threading
import time

import jax
import pytest

import chip_smoke
from cometbft_tpu import native
from cometbft_tpu.crypto import ed25519
from cometbft_tpu.libs import trace
from cometbft_tpu.sidecar import backend as be
from cometbft_tpu.sidecar import engine as engine_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_VALS = 48

needs_native = pytest.mark.skipif(
    not native.available(), reason="native tier unavailable"
)

# One verify_commit through the auto chain with the hybrid splitting: every
# span of the callers / batch / engine / supervisor / hybrid / host / device
# rows of PERF.md's table (engine.merge needs two requests in one dispatch
# and has its own test).
COMMIT_SPANS = {
    "validation.verify_commit", "validation.basic", "validation.key_type",
    "validation.sign_bytes", "validation.tally",
    "batch.verify", "batch.cache_filter", "batch.dispatch", "batch.cache_insert",
    "engine.queue_wait", "engine.dispatch", "supervisor.tier_call",
    "hybrid.call", "hybrid.plan", "hybrid.host_msm",
    "device.pack", "device.run", "device.wait", "device.unpack",
}
SYNC_CHILDREN = {
    "blocksync.verify_wait", "blocksync.part_set", "blocksync.verify_light",
    "blocksync.validate", "blocksync.save", "blocksync.pipeline_submit",
    "blocksync.apply",
}


@pytest.fixture(autouse=True)
def clean():
    trace.clear()
    chip_smoke.clear_verified_cache()
    yield
    trace.clear()
    chip_smoke.clear_verified_cache()


@pytest.fixture
def profiler(tmp_path):
    """A profiler session as the harness starts one; yields the capture's directory."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        yield str(tmp_path)
    finally:
        if jax.profiler.TraceAnnotation.is_enabled():
            jax.profiler.stop_trace()


@pytest.fixture
def auto_chain(monkeypatch):
    """The node's chain (engine -> supervisor -> hybrid -> cpu) on XLA:CPU at
    the rehearsal's settings, so a 48-signature commit still splits."""
    for k, v in {"CMTPU_HYBRID_MIN": "8", "CMTPU_DEV_RATE": "1000",
                 "CMTPU_HOST_RATE": "1000", "CMTPU_DEV_OVERHEAD_MS": "0"}.items():
        monkeypatch.setenv(k, v)
    monkeypatch.delenv("CMTPU_DEADLINE_MS", raising=False)
    backend = chip_smoke.open_auto_chain("cpu")
    native.available()  # the split needs the host tier built
    # price the mesh as one chip (conftest gives 8 virtual devices), as
    # tests/test_hybrid.py does: the planner then splits 48 lanes
    backend.inner.tiers[0].backend._n_dev = 1
    try:
        yield backend
    finally:
        backend.close()
        be.set_backend(None)
        os.environ.pop("CMTPU_BACKEND", None)


def _verify_one(height=1):
    vals, commits = chip_smoke.make_commits(24, N_VALS, 2, "trace")
    bid, commit = commits[height - 1]
    vals.verify_commit(chip_smoke.CHAIN_ID, bid, commit.height, commit)


def _by_id(spans):
    return {s["id"]: s for s in spans}


def _ancestors(span, by_id):
    out = []
    while span["parent"] is not None and span["parent"] in by_id:
        span = by_id[span["parent"]]
        out.append(span["name"])
    return out


# -- (a) off ---------------------------------------------------------------------


def test_off_without_a_profiler_session(auto_chain):
    assert not jax.profiler.TraceAnnotation.is_enabled()
    _verify_one()
    assert trace.spans() == [] and trace.dropped() == 0
    assert trace.span("batch.verify") is trace.span("hybrid.call")  # the shared no-op
    assert trace.current() is None


def test_off_never_imports_jax():
    code = (
        "import sys, chip_smoke\n"
        "from cometbft_tpu.libs import trace\n"
        "vals, commits = chip_smoke.make_commits(3, 8, 1, 'nojax')\n"
        "bid, commit = commits[0]\n"
        "vals.verify_commit(chip_smoke.CHAIN_ID, bid, commit.height, commit)\n"
        "assert trace.spans() == []\n"
        "print('jax' in sys.modules)\n"
    )
    env = {k: v for k, v in os.environ.items() if not k.startswith("CMTPU_")}
    env.update(CMTPU_BACKEND="cpu", JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == "False"


def test_a_capture_is_a_session_a_process_without_jax_can_hold():
    """Ring-only: under `trace.capture()` the sites record, nothing of JAX
    is imported and no annotation is made; outside it they are off again."""
    code = (
        "import sys, chip_smoke\n"
        "from cometbft_tpu.libs import trace\n"
        "vals, commits = chip_smoke.make_commits(3, 8, 1, 'nojax')\n"
        "bid, commit = commits[0]\n"
        "with trace.capture():\n"
        "    with trace.capture():\n"
        "        pass\n"
        "    vals.verify_commit(chip_smoke.CHAIN_ID, bid, commit.height, commit)\n"
        "    trace.record('engine.queue_wait', 1.0, 2.0)\n"
        "names = [s['name'] for s in trace.spans()]\n"
        "assert names.count('validation.verify_commit') == 1 and 'batch.verify' in names\n"
        "assert names[-1] == 'engine.queue_wait' and trace._annotation is None\n"
        "chip_smoke.clear_verified_cache()\n"
        "vals.verify_commit(chip_smoke.CHAIN_ID, bid, commit.height, commit)\n"
        "trace.record('engine.queue_wait', 1.0, 2.0)\n"
        "assert len(trace.spans()) == len(names)\n"
        "assert trace.span('batch.verify') is trace.span('hybrid.call')\n"
        "print('jax' in sys.modules)\n"
    )
    env = {k: v for k, v in os.environ.items() if not k.startswith("CMTPU_")}
    env.update(CMTPU_BACKEND="cpu", JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == "False"


def test_a_capture_beside_jax_writes_the_ring_and_makes_no_annotation():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    with trace.capture():
        with trace.span("hybrid.call", n=8) as call:
            assert call._ann is None and trace.current() is call
            call.backdate(call.t0 - 1.0)
    assert trace.span("hybrid.call") is trace.span("batch.verify")  # closed: the no-op again
    (one,) = trace.spans()
    assert one["name"] == "hybrid.call" and one["t1"] - one["t0"] >= 1.0


def test_perf_counter_is_one_clock_for_two_processes():
    """What lets a node's spans and its sidecar's be merged by their times:
    a child's reading, handed over a pipe, lies between two of the parent's."""
    before = time.perf_counter()
    out = subprocess.run([sys.executable, "-c", "import time; print(repr(time.perf_counter()))"],
                         capture_output=True, text=True, timeout=60, check=True)
    after = time.perf_counter()
    assert before < float(out.stdout) < after


# -- (b) on: one verify_commit ----------------------------------------------------


@needs_native
def test_one_verify_commit_gives_every_span_under_one_root(auto_chain, profiler):
    _verify_one(1)  # first use of the share's program
    trace.clear()
    chip_smoke.clear_verified_cache()
    _verify_one(2)
    jax.profiler.stop_trace()
    spans = trace.spans()
    # The keys' second sighting: their tables are built BEHIND this call's
    # dispatch, on the owner thread, inside nobody's operation.
    builds = [s for s in spans if s["name"] == "device.table_build"]
    assert all(b["parent"] is None and b["thread"] == "cmtpu-dev" for b in builds)
    spans = [s for s in spans if s["name"] != "device.table_build"]
    names = {s["name"] for s in spans}
    assert COMMIT_SPANS <= names, sorted(COMMIT_SPANS - names)
    assert names <= set(trace.NAMES)
    by_id = _by_id(spans)
    top = [s for s in spans if s["name"] == "validation.verify_commit"]
    assert len(top) == 1 and top[0]["parent"] is None
    assert top[0]["attrs"] == {"kind": "full", "sigs": N_VALS}
    assert {s["root"] for s in spans} == {top[0]["id"]}, "one root for the whole request"
    for s in spans:
        assert s["t1"] >= s["t0"]
        if s["parent"] is not None and s["name"] not in (
            "engine.queue_wait", "engine.dispatch", "device.run",  # end on another thread
        ):
            p = by_id[s["parent"]]
            assert p["t0"] <= s["t0"] and s["t1"] <= p["t1"], (s["name"], p["name"])
    one = {s["name"]: s for s in spans}
    assert by_id[one["engine.queue_wait"]["parent"]]["name"] == "batch.dispatch"
    assert by_id[one["engine.dispatch"]["parent"]]["name"] == "batch.dispatch"
    assert one["engine.dispatch"]["thread"] == "verify-engine"
    assert one["device.run"]["thread"] == "cmtpu-dev"
    assert "hybrid.call" in _ancestors(one["device.run"], by_id)
    run = one["device.run"]["attrs"]  # the conftest's 8 virtual devices shard the call
    assert set(run) == {"bucket", "sharded", "resident"} and not run["resident"]
    assert run["bucket"] >= one["hybrid.call"]["attrs"]["share"]
    plan = one["hybrid.plan"]["attrs"]
    assert plan["resident"] is False
    assert plan["share"] == one["hybrid.call"]["attrs"]["share"] and plan["predicted_ms"] > 0
    call = one["hybrid.call"]["attrs"]
    assert call["route"] == "split" and 0 < call["share"] < N_VALS and call["n"] == N_VALS
    assert one["hybrid.host_msm"]["attrs"]["lanes"] == N_VALS - call["share"]
    assert one["device.pack"]["attrs"]["lanes"] == call["share"]
    assert one["batch.verify"]["attrs"] == {
        "entries": N_VALS, "hits": 0, "dups": 0, "dispatched": N_VALS, "evicted": 0,
        "path": "whole_miss",
    }
    assert one["validation.tally"]["attrs"] == {"added": N_VALS}
    # the second call on one set (make_commits memoizes it) finds its columns
    assert one["validation.key_type"]["attrs"] == {"cols": "reused"}
    assert one["supervisor.tier_call"]["attrs"] == {
        "tier": "hybrid", "attempt": 0, "anchored": False,
    }
    # children of verify_commit cover it (the accounting PERF.md relies on)
    kids = [s for s in spans if s["parent"] == top[0]["id"]]
    assert {k["name"] for k in kids} == {
        "validation.basic", "validation.key_type", "validation.sign_bytes",
        "validation.tally", "batch.verify",
    }
    assert sum(k["t1"] - k["t0"] for k in kids) <= top[0]["t1"] - top[0]["t0"]


def test_key_type_says_whether_the_sets_columns_were_reused(profiler, monkeypatch):
    from cometbft_tpu.types import validator_set

    monkeypatch.setattr(ed25519.BatchVerifier, "verify",
                        lambda self: (True, [True] * len(self)))  # the callers' layer alone
    memoized, commits = chip_smoke.make_commits(24, N_VALS, 3, "cols")
    vals = validator_set.ValidatorSet(memoized.validators)  # never verified against
    before = validator_set.columns_counters()
    for bid, commit in commits:
        vals.verify_commit(chip_smoke.CHAIN_ID, bid, commit.height, commit)
    bid, commit = commits[0]
    vals.copy_increment_proposer_priority(1).verify_commit_light(
        chip_smoke.CHAIN_ID, bid, commit.height, commit)
    jax.profiler.stop_trace()
    got = [s["attrs"] for s in trace.spans() if s["name"] == "validation.key_type"]
    assert got == [{"cols": "built"}] + [{"cols": "reused"}] * 3
    after = validator_set.columns_counters()
    assert (after["built"] - before["built"], after["reused"] - before["reused"]) == (1, 3)


# -- (c) the xplane ----------------------------------------------------------------


@needs_native
def test_the_xplane_holds_the_same_spans_as_seam_events(auto_chain, profiler):
    _verify_one()
    jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(profiler, "**", "*.xplane.pb"), recursive=True)
    assert paths
    data = jax.profiler.ProfileData.from_file(sorted(paths)[-1])
    events = {
        e.name for pl in data.planes if pl.name.startswith("/host:")
        for ln in pl.lines for e in ln.events if e.name.startswith("seam:")
    }
    in_ring = {s["name"] for s in trace.spans()}
    # engine.queue_wait is a record(): ring only
    assert events == {"seam:" + n for n in in_ring - {"engine.queue_wait"}}
    assert "seam:batch_verify" not in events, "that name is the benchmark's"
    assert "seam:device.run" in events and "seam:validation.verify_commit" in events


# -- (d) a short blocksync ----------------------------------------------------------


def test_blocksync_heights_are_roots_with_their_children(profiler):
    from test_blocksync import CHAIN_ID, _fresh_node, _populated_chain
    from cometbft_tpu.blocksync.reactor import BlocksyncReactor
    from cometbft_tpu.p2p.key import NodeKey
    from cometbft_tpu.p2p.node_info import NodeInfo
    from cometbft_tpu.p2p.switch import Switch
    from cometbft_tpu.p2p.transport import MultiplexTransport
    from cometbft_tpu.types import GenesisDoc, GenesisValidator, Time
    from cometbft_tpu.types.priv_validator import MockPV

    pvs = [MockPV() for _ in range(3)]
    gen = GenesisDoc(
        chain_id=CHAIN_ID, genesis_time=Time(1700000000, 0),
        validators=[GenesisValidator(pv.address(), pv.get_pub_key(), 10, "") for pv in pvs],
    )
    gen.validate_and_complete()
    _, server_store, _ = _populated_chain(pvs, gen, 8)

    def switch(moniker):
        nk = NodeKey()
        ni = NodeInfo(node_id=nk.id, network=CHAIN_ID, moniker=moniker)
        return nk, Switch(ni, MultiplexTransport(ni, nk))

    nk_s, sw_s = switch("server")
    sw_s.add_reactor("BLOCKSYNC", BlocksyncReactor(
        state=_fresh_node(gen)[0], block_exec=None, block_store=server_store,
        block_sync=False,
    ))
    addr_s = sw_s.start("127.0.0.1:0")
    caught = threading.Event()
    state, store, executor = _fresh_node(gen)
    reactor = BlocksyncReactor(
        state=state, block_exec=executor, block_store=store, block_sync=True,
        on_caught_up=lambda st: caught.set(),
    )
    _, sw_c = switch("client")
    sw_c.add_reactor("BLOCKSYNC", reactor)
    sw_c.start("")
    try:
        sw_c.dial_peer(f"{nk_s.id}@{addr_s}")
        assert caught.wait(45), "never reported caught up"
        live = reactor.counters()  # two of the pool's counters count the peers still there
    finally:
        sw_c.stop()
        sw_s.stop()
    jax.profiler.stop_trace()
    applied = store.height()
    assert applied >= 7
    spans = trace.spans()
    assert {s["name"] for s in spans} <= set(trace.NAMES)
    roots = [s for s in spans if s["name"] == "blocksync.sync_one" and s["attrs"]["applied"]]
    assert [s["attrs"]["height"] for s in roots] == list(range(1, applied + 1))
    for one in roots:
        assert one["parent"] is None and one["root"] == one["id"]
        kids = [s for s in spans if s["parent"] == one["id"]]
        assert {k["name"] for k in kids} == SYNC_CHILDREN
        assert sum(k["t1"] - k["t0"] for k in kids) <= one["t1"] - one["t0"]
        mine = [s["name"] for s in spans if s["root"] == one["id"]]
        assert mine.count("state.validate") == 2, "validate_block, then apply_block's own"
        for name in ("state.exec_abci", "state.save_responses", "state.update",
                     "state.commit", "state.save_state", "store.save_block",
                     "types.data_hash", "types.part_set_proofs", "state.results_hash"):
            assert mine.count(name) == 1, name
        assert mine.count("validation.verify_commit") >= 1
    # the state copies its sets every height: the copies carry the columns
    cols = [s["attrs"]["cols"] for s in sorted(spans, key=lambda s: s["t0"])
            if s["name"] == "validation.key_type" and s["root"] in {r["id"] for r in roots[1:]}]
    assert cols and set(cols) == {"reused"}, cols
    assert any(s["name"] == "blocksync.decode" and s["attrs"]["bytes"] > 0 for s in spans)
    for name, attrs in (("types.data_hash", {"txs", "bytes"}), ("types.part_set_proofs", {"parts"}),
                        ("state.results_hash", {"txs"})):
        assert all(set(s["attrs"]) == attrs for s in spans if s["name"] == name), name
    by_id = {s["id"]: s for s in spans}
    synced = {one["id"] for one in roots}  # the chain's builder hashed too, under no height
    for name, parent in (("types.part_set_proofs", "blocksync.part_set"),
                         ("state.results_hash", "state.update")):
        assert all(by_id[s["parent"]]["name"] == parent
                   for s in spans if s["name"] == name and s["root"] in synced), name
    assert any(s["name"] == "blocksync.make_requests" for s in spans)
    waits = [s for s in spans if s["name"] == "blocksync.fetch_wait"]
    assert waits and all(s["parent"] is None for s in waits)
    # (e) the reactor's counters
    c = reactor.counters()
    assert c["heights_applied"] == applied and c["redo_requests"] == 0
    assert c["idle_sleeps"] >= sum(s["attrs"]["sleeps"] for s in waits) >= 1
    assert c["fetch_wait_ms"] > 0 and c["verify_wait_ms"] >= 0
    assert set(c) == {"heights_applied", "fetch_wait_ms", "verify_wait_ms",
                      "idle_sleeps", "redo_requests", "pipeline_overlap_ms",
                      "block_bytes_received", "requests_sent",
                      "requests_to_busiest_peer", "peers_asked",
                      "prefetch_windows", "prefetch_lanes", "prefetch_ms"}
    assert c["requests_sent"] >= applied and live["peers_asked"] == 1
    assert live["requests_to_busiest_peer"] == live["requests_sent"] >= applied
    assert c["block_bytes_received"] >= sum(
        s["attrs"]["bytes"] for s in spans if s["name"] == "blocksync.decode")


# -- (e) counters --------------------------------------------------------------------


def test_share_changes_over_a_forced_sequence_of_shares(monkeypatch):
    hb = be.HybridBackend()
    # t0, t_disp, t_host, t_wait, t_dev, t_run (the owner thread's start, return)
    ts = (0.0, 0.001, 0.010, 0.010, 0.020, (0.001, 0.019))
    hb._update_rates((32, 2, 0), 32, 16, *ts)  # a program's first use
    for share, predicted in ((32, 25.0), (32, 20.0), (8, 20.0), (32, None), (48, 20.0), (8, 20.0)):
        hb._update_rates((share, 2, 0), share, 48 - share, *ts, predicted)
    c = hb.counters()
    assert c["split_calls"] == 6, "the all-device call (48 of 48) is no split"
    assert c["share_changes"] == 3  # 32 -> 8 -> 32 -> (48: not a split) -> 8
    # first uses (32, 8 and 48 once each) and the call with no prediction are left out
    assert c["wall_ms"] == 60.0 and c["plan_abs_err_ms"] == 5.0  # |25-20| + 0 + 0
    for key in ("last_timing", "last_share", "routes", "device_lanes", "host_lanes"):
        assert key in c


def test_the_planner_hands_its_prediction_back_with_the_share(monkeypatch):
    for key, value in (("DEV_RATE", "1000"), ("HOST_RATE", "1000"), ("DEV_OVERHEAD_MS", "0")):
        monkeypatch.setenv("CMTPU_" + key, value)
    hb = be.HybridBackend()
    hb._n_dev = 1
    # equal rates, no overhead: 48 lanes split at the 32 bucket, host 16
    assert hb._plan_cost(48) == (32, 32 / 1000.0)
    assert hb._plan(48) == 32
    assert not hasattr(hb, "_predicted_ms"), "nothing of one call is left for the next"


def test_cache_counters_add_up():
    class Cpu:
        def batch_verify(self, pubs, msgs, sigs):
            return be.CpuBackend().batch_verify(pubs, msgs, sigs)

    be.set_backend(Cpu())
    try:
        priv = ed25519.gen_priv_key_from_secret(b"trace-cache")
        pub = priv.pub_key()
        triples = [(pub, b"m%d" % i, priv.sign(b"m%d" % i)) for i in range(6)]
        before = ed25519.verified_cache_counters()
        for batch in (triples[:4], triples[2:] + triples[4:5], triples):
            bv = ed25519.BatchVerifier()
            for t in batch:
                bv.add(*t)
            assert bv.verify()[0]
        after = ed25519.verified_cache_counters()
    finally:
        be.set_backend(None)
    d = {k: after[k] - before[k] for k in before}
    assert d["entries"] == 4 + 5 + 6
    assert d["hits"] == 0 + 2 + 6 and d["dups"] == 1 and d["dispatched"] == 4 + 2
    assert d["hits"] + d["dups"] + d["dispatched"] == d["entries"]
    assert d["inserted"] == 6 and d["evicted"] == 0 and d["size"] == 6


def test_engine_counters_keep_the_wait_percentile():
    class Inner:
        def batch_verify(self, pubs, msgs, sigs):
            return True, [True] * len(pubs)

    eng = engine_mod.VerificationEngine(Inner())
    try:
        assert eng.batch_verify([b"p"], [b"m"], [b"s"]) == (True, [True])
        assert "queue_wait_p95_ms" in eng.counters()
    finally:
        eng.close()
    assert not hasattr(eng, "register_metrics")


def test_two_requests_in_one_dispatch_leave_engine_merge(profiler):
    class Inner:
        def batch_verify(self, pubs, msgs, sigs):
            return True, [True] * len(pubs)

    eng = engine_mod.VerificationEngine(Inner(), hold_ms=200.0, max_sigs=4)
    try:
        with trace.span("batch.dispatch") as mine:
            futs = [eng.submit([b"p%d" % i] * 2, [b"m"] * 2, [b"s"] * 2) for i in range(2)]
            assert all(f.result(10) == (True, [True, True]) for f in futs)
    finally:
        eng.close()
    jax.profiler.stop_trace()
    spans = trace.spans()
    merges = [s for s in spans if s["name"] == "engine.merge"]
    assert sorted(s["attrs"]["phase"] for s in merges) == ["pack", "slice"]
    dispatch = next(s for s in spans if s["name"] == "engine.dispatch")
    assert dispatch["attrs"] == {"requests": 2, "lanes": 4, "klass": "blocksync", "dedup": 2,
                                 "unique": 2}
    assert dispatch["parent"] == mine.id and all(s["parent"] == dispatch["id"] for s in merges)
    waits = [s for s in spans if s["name"] == "engine.queue_wait"]
    assert len(waits) == 2 and all(s["parent"] == mine.id for s in waits)


# -- (f) the ring ---------------------------------------------------------------------


def test_the_ring_is_bounded_and_counts_what_it_drops(profiler):
    for i in range(trace.RING + 10):
        trace.record("engine.queue_wait", float(i), float(i) + 0.5)
    jax.profiler.stop_trace()
    spans = trace.spans()
    assert len(spans) == trace.RING and trace.dropped() == 10
    assert spans[0]["t0"] == 10.0 and spans[-1]["t0"] == float(trace.RING + 9)
    trace.record("engine.queue_wait", 0.0, 1.0)  # no session: not recorded
    assert len(trace.spans()) == trace.RING


def test_a_span_handed_to_another_thread_keeps_the_root(profiler):
    got = {}

    def worker(parent):
        with trace.span("device.run", parent=parent, bucket=8) as run:
            got["root"] = run.root

    with trace.span("hybrid.call", n=8) as call:
        t = threading.Thread(target=worker, args=(trace.current(),))
        t.start()
        t.join(10)
        assert not t.is_alive()
    assert got["root"] == call.id
    run = next(s for s in trace.spans() if s["name"] == "device.run")
    assert run["parent"] == call.id and run["thread"] != threading.current_thread().name


# -- (f2) what ran: thread and process CPU ----------------------------------------------


def _spin(seconds):
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


def _readings(attempts, measure, good):
    """Readings of `measure()` up to the first that `good` accepts: the
    sandbox shares its cores, and a spin that the host took off its CPU
    reads as a wait."""
    seen = []
    for _ in range(attempts):
        seen.append(measure())
        if good(seen[-1]):
            break
    return seen


def _span_of(work):
    trace.clear()
    with trace.capture(), trace.span("hybrid.call"):
        work()
    (s,) = trace.spans()
    return s["t1"] - s["t0"], s["cpu"], s["pcpu"]


def _alone_20ms():
    return _readings(8, lambda: _span_of(lambda: _spin(0.02)),
                     lambda r: abs(r[1] - r[0]) <= 0.2 * r[0])


def _host_is_quiet():
    """Whether three lone spins in a row each ran for most of their wall:
    where they do not, the host takes this process off its cores and no
    claim about a wall clock can be held against the program."""
    return all(c >= 0.95 * w for w, c, _ in (_span_of(lambda: _spin(0.02)) for _ in range(3)))


def test_a_span_that_spins_alone_ran_for_about_its_wall():
    seen = _alone_20ms()
    assert all(c <= w + 1e-4 and p >= c - 1e-4 for w, c, p in seen)
    wall, cpu, pcpu = seen[-1]
    if abs(cpu - wall) > 0.2 * wall and not _host_is_quiet():
        pytest.skip(f"the host is too busy to time a lone spin: {seen}")
    assert abs(cpu - wall) <= 0.2 * wall, seen


def test_a_span_beside_three_spinning_threads_stood_more_than_it_ran():
    """One interpreter lock, four threads that want it: the span's thread
    gets its share of the CPU the process uses, whatever the host gives the
    process; and where the host is quiet, the wall is mostly the others'
    work while `pcpu` says the lock was never idle."""
    stop = threading.Event()

    def spin_until_stopped():
        while not stop.is_set():
            pass

    quiet = _host_is_quiet()
    others = [threading.Thread(target=spin_until_stopped, name=f"p2p-recv:{i}") for i in range(3)]
    for t in others:
        t.start()
    try:
        seen = _readings(8, lambda: _span_of(lambda: _spin(0.15)),
                         lambda r: r[1] < 0.6 * r[0] and r[2] >= 0.8 * r[0])
    finally:
        stop.set()
        for t in others:
            t.join(10)
    assert not any(t.is_alive() for t in others)
    for wall, cpu, pcpu in seen:
        assert cpu < 0.6 * pcpu, seen  # a thread's clock, not the process's
        assert cpu <= wall + 1e-4 and pcpu >= cpu - 1e-4
    wall, cpu, pcpu = seen[-1]
    if quiet and _host_is_quiet():  # before and after: else the walls are the host's
        assert cpu < 0.6 * wall and pcpu >= 0.8 * wall, seen


def test_a_span_that_sleeps_did_not_run():
    wall, cpu, pcpu = _span_of(lambda: time.sleep(0.03))
    assert wall >= 0.03 and cpu < 0.001 and pcpu >= cpu - 1e-4


def test_off_no_cpu_clock_is_read(monkeypatch):
    """With no session of either kind neither `span()`, `record()`, `mark()`
    nor the receive path reaches a CPU clock (tests/test_p2p_recv_trace.py
    holds `_recv_routine` to the same counters)."""
    reads = []
    monkeypatch.setattr(time, "thread_time", lambda: reads.append("thread") or 0.0)
    monkeypatch.setattr(time, "process_time", lambda: reads.append("process") or 0.0)
    assert not trace.on() and trace.mark() is None
    with trace.span("hybrid.call") as sp:
        assert sp is trace._OFF
    trace.record("engine.queue_wait", 1.0, 2.0)
    assert reads == [] and trace.spans() == []
    with trace.capture():
        with trace.span("hybrid.call"):
            pass
    assert sorted(reads) == ["process"] * 2 + ["thread"] * 2, "on: two of each a span"


def test_a_span_closed_on_another_thread_has_no_cpu():
    with trace.capture():
        sp = trace.span("sidecar.request").__enter__()
        t = threading.Thread(target=sp.__exit__, args=(None, None, None))
        t.start()
        t.join(10)
        assert not t.is_alive()
    (s,) = trace.spans()
    assert s["cpu"] is None and s["pcpu"] >= 0.0 and s["thread"] == t.name


def test_record_keeps_the_cpu_it_is_given_and_none_where_given_none():
    with trace.capture():
        trace.record("engine.queue_wait", 1.0, 2.0, klass="consensus")
        trace.record("sidecar.decode", 1.0, 2.0, cpu=0.25, pcpu=0.75, seq=3)
        began = trace.mark()
        _spin(0.005)
        trace.record("p2p.recv_msg", chan=64, **trace.since(began))
    crossed, given, timed = trace.spans()
    assert (crossed["cpu"], crossed["pcpu"], crossed["attrs"]) == (None, None, {"klass": "consensus"})
    assert (given["cpu"], given["pcpu"], given["attrs"]) == (0.25, 0.75, {"seq": 3})
    assert (given["t0"], given["t1"]) == (1.0, 2.0)
    assert 0.0 < timed["cpu"] <= timed["pcpu"] + 1e-4
    assert timed["cpu"] <= timed["t1"] - timed["t0"] + 1e-4 and timed["attrs"] == {"chan": 64}


def test_thread_cpu_closes_on_the_process_and_groups_threads_by_role():
    assert trace.role("p2p-recv:0a1b2c3d4e") == "p2p-recv" and trace.role("verify-engine") == "verify-engine"
    stop = threading.Event()

    def spin_until_stopped():
        while not stop.is_set():
            pass

    recv = [threading.Thread(target=spin_until_stopped, name=f"p2p-recv:{peer}")
            for peer in ("aa", "bb")]
    for t in recv:
        t.start()
    try:
        _spin(0.05)
        got = trace.thread_cpu()
    finally:
        stop.set()
        for t in recv:
            t.join(10)
    assert not any(t.is_alive() for t in recv)
    assert "p2p-recv:aa" not in got and got["p2p-recv"] > 0.0, "two threads, one role"
    assert got[trace.role(threading.current_thread().name)] > 0.0
    roles = sum(v for k, v in got.items() if k not in ("process", "ended_or_native"))
    assert got["process"] == pytest.approx(roles + got["ended_or_native"], abs=1e-9)
    assert got["ended_or_native"] >= -1e-3
    # ended threads leave their role and stay in the process
    later = trace.thread_cpu()
    assert "p2p-recv" not in later and later["ended_or_native"] >= got["p2p-recv"] - 1e-3


@pytest.mark.parametrize("name", trace.ROLES)
def test_every_role_is_a_name_some_thread_start_gives(name):
    """`ROLES` is what `/metrics` lists: a role no site starts a thread under
    would read 0 for ever, and one missing from it would hide in `other`."""
    sites = []
    for path in glob.glob(os.path.join(ROOT, "cometbft_tpu", "**", "*.py"), recursive=True):
        if not path.endswith(os.path.join("libs", "trace.py")):
            with open(path) as f:
                sites += re.findall(r'name\s*=\s*f?"([a-z0-9-]+)[":]|\("([a-z0-9-]+)", self\._\w+_routine\)',
                                    f.read())
    assert name in {a or b for a, b in sites}


def test_thread_cpu_without_the_platforms_clock_is_the_process_alone(monkeypatch):
    monkeypatch.delattr(time, "pthread_getcpuclockid")
    assert set(trace.thread_cpu()) == {"process"}


# -- (g) kernel scopes ----------------------------------------------------------------


def test_the_lowered_verify_program_names_its_five_stages():
    import numpy as np

    from cometbft_tpu.ops import ed25519_kernel as ek
    from cometbft_tpu.ops import merkle_kernel as mk

    operands, _ = ek.pack_batch([b"\x00" * 32] * 8, [b"\x00" * 120] * 8, [b"\x00" * 64] * 8)
    text = jax.jit(ek.verify_core).lower(*operands).as_text(debug_info=True)
    for scope in ek.KERNEL_SCOPES:
        assert re.search(rf"jit\(verify_core\)/(jit\(main\)/)?{scope}/", text), scope
    assert ek.KERNEL_SCOPES == ("sha512", "unpack", "decompress", "ladder", "finish")
    blocks, nblocks = np.zeros((1, 16, 8), np.uint32), np.ones(8, np.int32)
    text = jax.jit(mk.leaves_to_root_core).lower(blocks, nblocks).as_text(debug_info=True)
    assert "/merkle/" in text


# -- (h) the names ---------------------------------------------------------------------


def _names_in_code():
    found = set()
    for path in glob.glob(os.path.join(ROOT, "cometbft_tpu", "**", "*.py"), recursive=True):
        with open(path) as f:
            found.update(re.findall(r'trace\.(?:span|record)\(\s*"([a-z0-9_.]+)"', f.read()))
    return found


def _names_in_perf_md():
    with open(os.path.join(ROOT, "PERF.md")) as f:
        table = f.read().split("<!-- spans -->")[1].split("<!-- /spans -->")[0]
    return set(re.findall(r"`((?:validation|blocksync|types|state|store|batch|engine|supervisor|hybrid|device|grpc|sidecar|p2p)\.[a-z_]+)`", table))


@pytest.mark.parametrize("name", trace.NAMES)
def test_every_name_is_one_the_code_emits_and_perf_md_lists(name):
    assert name in _names_in_code(), "NAMES lists a span no site emits"
    assert name in _names_in_perf_md(), "PERF.md's span table lacks it"


def test_no_name_outside_the_list():
    assert _names_in_code() == set(trace.NAMES)
    assert _names_in_perf_md() == set(trace.NAMES)
    assert len(set(trace.NAMES)) == len(trace.NAMES)
    # one module defines spans: nothing else in the program touches the profiler's annotation
    for path in glob.glob(os.path.join(ROOT, "cometbft_tpu", "**", "*.py"), recursive=True):
        if not path.endswith(os.path.join("libs", "trace.py")):
            with open(path) as f:
                assert "TraceAnnotation" not in f.read(), path


def test_pprof_jax_trace_writes_the_captures_spans(tmp_path, auto_chain):
    import json

    from cometbft_tpu.libs import pprof

    _verify_one(1)  # the share's program is loaded before the capture starts
    worker = threading.Thread(target=lambda: (time.sleep(0.3), _verify_one(2)))
    worker.start()
    msg = pprof.jax_trace(1.5, str(tmp_path))
    worker.join(30)
    assert not worker.is_alive()
    with open(tmp_path / "spans.json") as f:
        got = json.load(f)
    assert "spans.json" in msg and got["dropped"] == 0
    assert {s["name"] for s in got["spans"]} >= {"validation.verify_commit", "batch.verify"}
    assert all(got["t0"] <= s["t0"] for s in got["spans"])
    assert all(s["pcpu"] is not None for s in got["spans"] if s["name"] != "engine.queue_wait")
    # the session's CPU by role, closing on the process
    threads = got["threads"]
    assert threads["process"] > 0 and threads["verify-engine"] >= 0
    assert threads["process"] == pytest.approx(
        sum(v for k, v in threads.items() if k != "process"), abs=1e-6)


def test_the_counters_reach_metrics_through_lazy_gauges():
    import types

    from cometbft_tpu.blocksync.reactor import BlocksyncReactor
    from cometbft_tpu.libs.metrics import Registry
    from cometbft_tpu.node.node import Node

    class Chain:  # the shape counters() has under CMTPU_BACKEND=auto
        name = "coalesce"

        def counters(self):
            hybrid = {"split_calls": 7, "share_changes": 3, "plan_abs_err_ms": 12.5,
                      "wall_ms": 800.0, "resident_repeat_sightings": 3,
                      "resident_repeat_lanes": 95232}
            return {"requests": 1, "dispatches": 1, "queue_wait_p95_ms": 0.0,
                    "inner": {"tiers": {"hybrid": {"backend": hybrid}}}}

    reg = Registry(namespace="cmt")
    Node._register_backend_metrics(reg)
    Node._register_host_metrics(reg)
    reactor = BlocksyncReactor.__new__(BlocksyncReactor)  # counters() reads these alone
    vars(reactor).update(
        pipeline_overlap_ms=0.0, heights_applied=9, fetch_wait_ms=4.2,
        verify_wait_ms=0.0, idle_sleeps=2, redo_requests=1, block_bytes_received=311,
        prefetch_windows=3, prefetch_lanes=95232, prefetch_ms=870.4,
        pool=types.SimpleNamespace(counters=lambda: {
            "requests_sent": 8, "requests_to_busiest_peer": 2, "peers_asked": 4}),
    )
    Node._register_hotpath_metrics(types.SimpleNamespace(blocksync_reactor=reactor), reg)
    assert "cmt_hybrid_split_calls 0" in reg.render()  # no backend yet: nothing constructed
    be.set_backend(Chain())
    try:
        out = reg.render()
    finally:
        be.set_backend(None)
    assert "cmt_hybrid_split_calls 7" in out and "cmt_hybrid_share_changes 3" in out
    assert "cmt_blocksync_heights_applied 9" in out and "cmt_blocksync_idle_sleeps 2" in out
    assert "cmt_blocksync_redo_requests 1" in out
    assert "cmt_blocksync_requests_sent 8" in out and "cmt_blocksync_peers_asked 4" in out
    assert "cmt_blocksync_requests_to_busiest_peer 2" in out
    assert "cmt_blocksync_block_bytes_received 311" in out
    assert "cmt_blocksync_prefetch_windows 3" in out and "cmt_blocksync_prefetch_lanes 95232" in out
    assert "cmt_blocksync_prefetch_ms 870" in out
    assert "cmt_hybrid_resident_repeat_sightings 3" in out
    assert "cmt_hybrid_resident_repeat_lanes 95232" in out
    size = ed25519.verified_cache_counters()["size"]
    assert f"cmt_verify_cache_size {size}" in out and "cmt_verify_cache_hits " in out
    from cometbft_tpu.types import validator_set
    built = validator_set.columns_counters()["built"]
    assert f"cmt_verify_columns_built {built}" in out and "cmt_verify_columns_reused " in out
    # CPU by thread role: one reading a scrape, closing on the process
    host = {m.group(1): float(m.group(2)) for m in
            re.finditer(r"^cmt_host_thread_cpu_seconds_(\w+) (\S+)$", out, re.M)}
    assert set(host) == {r.replace("-", "_") for r in trace.ROLES} | {
        "other", "process", "ended_or_native"}
    assert host["process"] > 0 and host["other"] > 0, "this thread is of no named role"
    assert host["process"] == pytest.approx(sum(host.values()) - host["process"], abs=1e-5)
    assert "scheduler_" not in out, "the coalescer's gauges went: engine_* has the numbers"


def test_the_engines_totals_are_engine_gauges():
    from cometbft_tpu.libs.metrics import Registry
    from cometbft_tpu.node.node import Node

    class Inner:
        def batch_verify(self, pubs, msgs, sigs):
            return True, [True] * len(pubs)

    reg = Registry(namespace="cmt")
    Node._register_engine_metrics(reg)
    assert "cmt_engine_requests 0" in reg.render()  # no backend yet: nothing constructed
    eng = engine_mod.VerificationEngine(Inner())
    be.set_backend(eng)
    try:
        for _ in range(3):
            assert eng.batch_verify([b"p"], [b"m"], [b"s"]) == (True, [True])
        out = reg.render()
    finally:
        be.set_backend(None)
        eng.close()
    assert "cmt_engine_requests 3" in out and "cmt_engine_dispatches 3" in out
    assert "cmt_engine_batched_requests 0" in out and "cmt_engine_fallback_splits 0" in out
    assert re.search(r"^cmt_engine_queue_wait_p95_us \d+$", out, re.M)
