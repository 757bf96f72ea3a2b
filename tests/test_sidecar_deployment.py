"""The deployment BASELINE.json's north star names (ISSUE 30): a node that
never imports JAX, `CMTPU_BACKEND=auto` with `CMTPU_SIDECAR_ADDR` set (its
chain: engine -> `ResilientBackend` (`grpc` -> `cpu`)), beside a sidecar
that owns the device and serves the supervised chain over the framed
protocol. Held here on a CPU at small sizes: the served answers against the
scalar ZIP-215 reference lane for lane (batch sizes that stream included),
whole-batch delivery, the node's degradation to its own anchor, both sides'
spans, and the one assembly."""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

import chip_smoke
from cometbft_tpu import native
from cometbft_tpu.libs import trace
from cometbft_tpu.sidecar import backend as be
from cometbft_tpu.sidecar import service
from cometbft_tpu.sidecar.engine import engine_of
from cometbft_tpu.sidecar.service import GrpcBackend, SidecarServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(ROOT, "tests", "sidecar_node_worker.py")
CHUNK = 64  # what the tests' servers advertise, so that small batches stream

pytestmark = pytest.mark.sidecar

needs_native = pytest.mark.skipif(
    not native.available(), reason="native tier unavailable"
)


@pytest.fixture(scope="module")
def chain_server():
    """A sidecar over the supervised chain (engine -> supervisor -> hybrid ->
    cpu) on XLA:CPU at the rehearsal's settings, built with no backend given."""
    mp = pytest.MonkeyPatch()
    for k, v in {"CMTPU_HYBRID_MIN": "8", "CMTPU_DEV_RATE": "1000",
                 "CMTPU_HOST_RATE": "1000", "CMTPU_DEV_OVERHEAD_MS": "0"}.items():
        mp.setenv(k, v)
    mp.delenv("CMTPU_DEADLINE_MS", raising=False)
    mp.delenv("CMTPU_SIDECAR_ADDR", raising=False)
    backend = chip_smoke.open_auto_chain("cpu")
    native.available()
    backend.inner.tiers[0].backend._n_dev = 1  # price the virtual mesh as one chip
    server = SidecarServer("127.0.0.1:0")
    server._preferred_chunk = lambda: CHUNK
    server.start()
    try:
        yield server, backend
    finally:
        server.shutdown()
        backend.close()
        be.set_backend(None)
        os.environ.pop("CMTPU_BACKEND", None)
        mp.undo()


def _node(addr: str, **job) -> dict:
    """Runs the node worker against `addr`; its last line, decoded."""
    env = {k: v for k, v in os.environ.items() if not k.startswith("CMTPU_")}
    env.pop("XLA_FLAGS", None)
    env.update(JAX_PLATFORMS="cpu", CMTPU_BACKEND="auto", CMTPU_SIDECAR_ADDR=addr)
    out = subprocess.run(
        [sys.executable, WORKER, json.dumps({"seed": 30, **job})],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _children(spans, parent):
    return [s for s in spans if s["parent"] == parent["id"]]


def _one(spans, name, **attrs):
    found = [s for s in spans if s["name"] == name
             and all(s["attrs"].get(k) == v for k, v in attrs.items())]
    assert found, (name, attrs, sorted({s["name"] for s in spans}))
    return found


# -- (a) the served path against the scalar reference -------------------------------


@needs_native
@pytest.mark.parametrize("validators", [96, 300])
def test_a_node_without_jax_verifies_through_the_supervised_sidecar(chain_server, validators):
    server, backend = chain_server
    before = server.counters()
    # bitmap batches under, at and several times the advertised chunk
    sizes = [CHUNK - 8, CHUNK, validators]
    got = _node(server.bound_addr, validators=validators, sizes=sizes)
    assert got["jax_imported"] is False
    assert got["chain"] == ["grpc", "cpu"] and got["active_tier"] == "grpc"
    assert got["events"] == {"trips": 0, "degraded_calls": 0, "deadline_exceeded": 0,
                             "crosscheck_catches": 0}
    assert sorted(got["bitmaps"]) == sorted(str(n) for n in sizes)
    for n in sizes:  # false at the flipped lanes and at the edge vectors the reference rejects
        assert set(got["flipped"]) < set(got["bitmaps"][str(n)])
    # two commits and three batches: the larger ones streamed, the small ones did not
    grpc = got["grpc"]
    assert grpc["streaming"] is True and grpc["remote_chunk"] == CHUNK
    assert grpc["streamed_calls"] >= 3 and grpc["unary_calls"] >= 2
    assert grpc["lanes_sent"] == 2 * validators + sum(sizes)
    # three columns a frame that carried triples: a call, or a chunk of a stream
    assert (grpc["columns_fixed"] + grpc["columns_ragged"]
            == 3 * (grpc["unary_calls"] + grpc["streamed_chunks"]))
    assert grpc["columns_fixed"] >= 2 * (grpc["unary_calls"] + grpc["streamed_chunks"]), "keys and signatures"
    after = server.counters()
    assert after["lanes_in"] - before["lanes_in"] == grpc["lanes_sent"]
    assert after["bytes_in"] - before["bytes_in"] == grpc["bytes_sent"]
    assert after["bytes_out"] - before["bytes_out"] == grpc["bytes_received"]
    assert after["streams_failed"] == before["streams_failed"]
    sup = backend.counters()["inner"]
    assert sup["active_tier"] == "hybrid" and sup["degraded_calls"] == 0


# -- (b) whole-batch delivery ---------------------------------------------------------


class _Recording:
    name = "recording"

    def __init__(self):
        self.calls = []

    def batch_verify(self, pubs, msgs, sigs):
        self.calls.append((list(pubs), list(msgs), list(sigs)))
        return True, [True] * len(pubs)


@pytest.mark.parametrize("n", [1, 7, 8, 9, 20], ids=lambda n: f"{n}-of-chunk-8")
def test_a_batch_reaches_the_backend_as_one_call_in_the_order_sent(n):
    """1, chunk - 1, chunk, chunk + 1 and 2.5 x chunk lanes: unary or
    streamed, the backend behind the server sees one call of n lanes, byte
    for byte the triples sent."""
    rec = _Recording()
    server = SidecarServer("127.0.0.1:0", backend=rec)
    server._preferred_chunk = lambda: 8
    server.start()
    client = GrpcBackend(server.bound_addr, timeout_s=10)
    try:
        assert client.ping() and client.chunk_size() == 8
        pubs = [bytes([i]) * 32 for i in range(n)]
        msgs = [b"vote-%03d-" % i + bytes([255 - i]) * 113 for i in range(n)]
        sigs = [bytes([i, n]) * 32 for i in range(n)]
        assert client.batch_verify(pubs, msgs, sigs) == (True, [True] * n)
        assert rec.calls == [(pubs, msgs, sigs)]
        c = client.counters()
        assert (c["streamed_calls"], c["unary_calls"]) == ((1, 0) if n > 8 else (0, 1))
        assert c["streamed_chunks"] == (-(-n // 8) if n > 8 else 0)
        assert c["lanes_sent"] == n == server.counters()["lanes_in"]
        assert (c["columns_fixed"], c["columns_ragged"]) == (3 * max(1, c["streamed_chunks"]), 0)
        assert server.counters()["requests"] == 2  # the Ping and the batch: a stream is one
    finally:
        client.close()
        server.shutdown()


def _chunk(seq, final, columns=None, sid=1):
    """A ChunkReq by hand: one triple at one stride unless `columns` is given."""
    from cometbft_tpu.wire import proto
    from tests.test_sidecar import _payload

    if columns is None:
        columns = _payload([b"p" * 32], [b"m"], [b"s" * 64], first=4)
    return (proto.field_varint(1, sid, emit_default=True)
            + proto.field_varint(2, seq, emit_default=True) + proto.field_bool(3, final) + columns)


def test_a_failed_stream_is_an_error_and_is_counted():
    """Never a shorter bitmap: a chunk out of sequence tears the stream
    down, and the server counts it."""
    server = SidecarServer("127.0.0.1:0", backend=_Recording()).start()
    client = GrpcBackend(server.bound_addr, timeout_s=10)
    try:
        assert client._call("BatchVerifyChunk", _chunk(0, False)) == b""
        with pytest.raises(RuntimeError, match="chunk seq 2, expected 1"):
            client._call("BatchVerifyChunk", _chunk(2, True))
        with pytest.raises(RuntimeError, match="unknown stream 1"):
            client._call("BatchVerifyChunk", _chunk(1, True))
        c = server.counters()
        assert c["streams_failed"] == 1 and c["lanes_in"] == 1 and c["requests"] == 2
        assert server.backend.calls == []
    finally:
        client.close()
        server.shutdown()


@pytest.mark.parametrize("at", [0, 1], ids=["first-chunk", "later-chunk"])
@pytest.mark.parametrize("name", ["count-over", "blob-shorter", "blob-longer", "stride-with-lengths",
                                  "lengths-sum-short", "lengths-for-fewer", "counts-differ"])
def test_a_malformed_chunk_fails_its_stream_whole(name, at):
    """A column the decoder refuses, in a stream's first chunk or a later
    one: that chunk's error response, the stream gone with every lane it
    held, `streams_failed` counted, nothing handed to the backend, and the
    same connection then streams a batch to its whole bitmap."""
    from tests.test_sidecar import MALFORMED, _malformed_payload

    server = SidecarServer("127.0.0.1:0", backend=_Recording())
    server._preferred_chunk = lambda: 8
    server.start()
    client = GrpcBackend(server.bound_addr, timeout_s=10)
    try:
        assert client.ping()
        sock = client._sock
        for seq in range(at):
            assert client._call("BatchVerifyChunk", _chunk(seq, False, sid=9)) == b""
        with pytest.raises(RuntimeError, match="sidecar error: ValueError: .*" + MALFORMED[name][1]):
            client._call("BatchVerifyChunk", _chunk(at, False, _malformed_payload(name, first=4), sid=9))
        with pytest.raises(RuntimeError, match="unknown stream 9"):
            client._call("BatchVerifyChunk", _chunk(at + 1, True, sid=9))
        c = server.counters()
        assert c["streams_failed"] == 1 and c["lanes_in"] == at and c["requests"] == 3
        assert server.backend.calls == []
        pubs, msgs, sigs = [b"p" * 32] * 20, [b"m%d" % i for i in range(20)], [b"s" * 64] * 20
        assert client.batch_verify(pubs, msgs, sigs) == (True, [True] * 20)
        assert client._sock is sock and client.counters()["streamed_calls"] == 1
        assert server.backend.calls == [(pubs, msgs, sigs)]
        assert server.counters()["streams_failed"] == 1
    finally:
        client.close()
        server.shutdown()


# -- (c) the server gone mid-run ------------------------------------------------------


def test_with_the_server_gone_the_nodes_cpu_anchor_answers(monkeypatch):
    server = SidecarServer("127.0.0.1:0", backend=be.CpuBackend()).start()
    for k, v in {"CMTPU_BACKEND": "auto", "CMTPU_SIDECAR_ADDR": server.bound_addr,
                 "CMTPU_BACKOFF_MS": "1", "CMTPU_RETRIES": "1"}.items():
        monkeypatch.setenv(k, v)
    old = be._backend
    be.set_backend(None)
    node = be.get_backend()  # JAX_PLATFORMS=cpu (conftest): grpc -> cpu, as a node without JAX
    try:
        assert node.counters()["inner"]["chain"] == ["grpc", "cpu"]
        vals, commits = chip_smoke.make_commits(30, 96, 3, "server-gone")
        (bid1, c1), (bid2, c2), (bid3, c3) = commits
        vals.verify_commit(chip_smoke.CHAIN_ID, bid1, c1.height, c1)
        sup = node.counters()["inner"]
        assert sup["degraded_calls"] == 0 and sup["tiers"]["grpc"]["backend"]["lanes_sent"] == 96
        served = server.counters()["lanes_in"]
        server.shutdown()  # listener and open connections
        vals.verify_commit(chip_smoke.CHAIN_ID, bid2, c2.height, c2)
        with pytest.raises(ValueError, match=r"wrong signature \(#5\)"):
            vals.verify_commit(chip_smoke.CHAIN_ID, bid3, c3.height,
                               chip_smoke.flip_signatures(c3, [5, 40]))
        sup = node.counters()["inner"]
        assert sup["degraded_calls"] == 2 and sup["tiers"]["cpu"]["calls"] == 2
        assert sup["tiers"]["grpc"]["failures"] >= 1
        assert server.counters()["lanes_in"] == served
    finally:
        node.close()
        be.set_backend(old)


# -- (d) both sides' spans ------------------------------------------------------------


@needs_native
def test_both_processes_record_one_request_under_a_capture_and_nothing_without(chain_server):
    server, _ = chain_server
    trace.clear()
    with trace.capture():
        got = _node(server.bound_addr, validators=96, sizes=[CHUNK], capture=True)
    theirs, mine = got["spans"], trace.spans()
    assert {s["name"] for s in theirs + mine} <= set(trace.NAMES)
    # the node: validation.verify_commit > batch.verify > batch.dispatch > grpc.call
    op = _one(theirs, "validation.verify_commit")[0]
    assert op["parent"] is None
    by_id = {s["id"]: s for s in theirs}
    calls = [s for s in _one(theirs, "grpc.call") if s["root"] == op["id"]]
    assert len(calls) == 1, "one span a request"
    call = calls[0]
    dispatch = by_id[by_id[call["parent"]]["parent"]]  # engine.dispatch > supervisor.tier_call > grpc.call
    assert by_id[call["parent"]]["name"] == "supervisor.tier_call"
    assert by_id[call["parent"]]["attrs"]["tier"] == "grpc"
    assert dispatch["name"] == "engine.dispatch"
    assert by_id[dispatch["parent"]]["name"] == "batch.dispatch"
    assert call["attrs"]["method"] == "BatchVerifyChunk" and call["attrs"]["lanes"] == 96
    assert call["attrs"]["chunks"] == 2
    kids = _children(theirs, call)
    assert [k["name"] for k in kids].count("grpc.encode") == 2  # one a chunk, never one a lane
    assert {k["name"] for k in kids} == {"grpc.encode", "grpc.wait", "grpc.decode"}
    assert all(call["t0"] <= k["t0"] and k["t1"] <= call["t1"] for k in kids)
    encodes = [k for k in kids if k["name"] == "grpc.encode"]
    # the sidecar: sidecar.request > ... > hybrid.call, on the same clock
    req = _one(mine, "sidecar.request", req=call["attrs"]["req"], lanes=96)[0]
    assert req["attrs"]["method"] == "BatchVerifyChunk" and req["attrs"]["chunks"] == 2
    assert req["attrs"]["bytes_in"] == call["attrs"]["bytes_out"]
    assert req["attrs"]["bytes_out"] == call["attrs"]["bytes_in"]
    assert call["t0"] <= req["t0"] and req["t1"] <= call["t1"], "perf_counter is one clock"
    under = [s for s in mine if s["root"] == req["id"]]
    names = [s["name"] for s in under]
    assert names.count("sidecar.decode") == 2 and names.count("sidecar.encode") == 1
    # how many of a chunk's three columns went ragged: as written, so as read
    decodes = sorted(_one(under, "sidecar.decode"), key=lambda s: s["attrs"]["seq"])
    assert [d["attrs"]["ragged"] for d in decodes] == [e["attrs"]["ragged"] for e in encodes]
    assert all(r in (0, 1) for r in (e["attrs"]["ragged"] for e in encodes)), "the sign bytes at most"
    for name in ("engine.queue_wait", "engine.dispatch", "supervisor.tier_call", "hybrid.call"):
        assert names.count(name) == 1, name
    hybrid = _one(under, "hybrid.call")[0]
    assert hybrid["attrs"]["n"] == 96, "the planner saw the commit whole"
    assert req["t0"] <= hybrid["t0"] and hybrid["t1"] <= req["t1"]
    # a unary batch (the chunk's size) is one request with one decode
    unary = _one(theirs, "grpc.call", method="BatchVerify", lanes=CHUNK)[0]
    ureq = _one(mine, "sidecar.request", req=unary["attrs"]["req"], lanes=CHUNK)[0]
    assert ureq["attrs"]["bytes_in"] == unary["attrs"]["bytes_out"]
    assert [s["name"] for s in mine if s["parent"] == ureq["id"]].count("sidecar.decode") == 1
    # with neither a capture nor a profiler session: nothing, on either side
    trace.clear()
    got = _node(server.bound_addr, validators=96, sizes=[CHUNK])
    assert got["spans"] == [] and trace.spans() == []


# -- (d2) four nodes of one chain on four connections (ISSUE 32) -----------------------


class _SlowCpu(be.CpuBackend):
    """The host tier, slow enough that what arrives during a call queues."""

    name = "slow-cpu"

    def batch_verify(self, pubs, msgs, sigs):
        time.sleep(0.4)
        return super().batch_verify(pubs, msgs, sigs)


def test_four_clients_sending_one_commit_get_the_answers_they_would_get_alone(monkeypatch):
    """One real server, four real clients on four connections sending the
    same 96-signature commit at once, one of them a copy with flipped
    signatures, against a merge cap of 128 lanes (two requests offer more):
    every bitmap equals `answers_alone`, the copies shared a dispatch, and
    each side's spans name the connection."""
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    from reference.answers_alone import answers_alone

    monkeypatch.setenv("CMTPU_COALESCE_MAX", "128")
    vals, commits = chip_smoke.make_commits(32, 96, 1, "four-nodes")
    _, commit = commits[0]

    def columns(c):
        return ([v.pub_key.bytes() for v in vals.validators],
                [bytes(sb) for sb in c.vote_sign_bytes_all(chip_smoke.CHAIN_ID)],
                [s.signature for s in c.signatures])

    requests = [columns(commit), columns(chip_smoke.flip_signatures(commit, [5, 40])),
                columns(commit), columns(commit)]
    server = SidecarServer("127.0.0.1:0", backend=_SlowCpu())
    server._preferred_chunk = lambda: CHUNK
    server.start()
    clients = [GrpcBackend(server.bound_addr, timeout_s=60) for _ in requests]
    answers = [None] * len(requests)
    start = threading.Barrier(len(requests))

    def node(k):
        start.wait(10)
        answers[k] = clients[k].batch_verify(*requests[k])

    trace.clear()
    try:
        assert all(c.ping() for c in clients)
        before = server.scheduler_counters()
        with trace.capture():
            threads = [threading.Thread(target=node, args=(k,)) for k in range(len(requests))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
        spans = trace.spans()
        assert answers == answers_alone(requests)
        assert [ok for ok, _ in answers] == [True, False, True, True]
        assert [j for j, b in enumerate(answers[1][1]) if not b] == [5, 40]
        served, after = server.counters(), server.scheduler_counters()
        assert served["lanes_in"] == sum(c.counters()["lanes_sent"] for c in clients) == 4 * 96
        assert served["connections_accepted"] == 4 == served["connections_open"]
        assert after["coalesced_dispatches"] > before["coalesced_dispatches"]
        assert after["dedup_sigs"] - before["dedup_sigs"] >= 96, "two copies at least shared their lanes"
        assert after["dispatches"] - before["dispatches"] < len(requests)
        # the spans of the two sides are joined by connection and request id
        calls = _one(spans, "grpc.call", method="BatchVerifyChunk")
        asked = _one(spans, "sidecar.request", method="BatchVerifyChunk")
        assert len(calls) == len(asked) == 4
        ports = [c["attrs"]["port"] for c in calls]
        assert len(set(ports)) == 4 and 0 not in ports
        assert sorted((r["attrs"]["conn"], r["attrs"]["req"]) for r in asked) == sorted(
            (c["attrs"]["port"], c["attrs"]["req"]) for c in calls)
        assert len({c["attrs"]["req"] for c in calls}) == 1, "in lock-step the ids alone are equal"
        dispatches = _one(spans, "engine.dispatch")
        assert all(d["attrs"]["unique"] <= 128 for d in dispatches)
        assert any(d["attrs"]["requests"] >= 2 and d["attrs"]["lanes"] > 128 for d in dispatches)
    finally:
        for c in clients:
            c.close()
        server.shutdown()
    assert server.counters()["connections_accepted"] == 4


def test_a_frame_waits_for_a_host_pack_in_progress():
    """While the device tier packs a dispatch on the host (its `PACK_GATE`
    held), a connection's thread does not decode: the frame is answered once
    the pack is over, and at once where no pack runs."""
    from cometbft_tpu.ops import ed25519_kernel as ek

    server = SidecarServer("127.0.0.1:0", backend=_Recording()).start()
    client = GrpcBackend(server.bound_addr, timeout_s=10)
    answered = threading.Event()
    try:
        assert client.ping()
        with ek.PACK_GATE:
            t = threading.Thread(target=lambda: client.ping() and answered.set())
            t.start()
            assert not answered.wait(0.3), "answered while a pack was in progress"
        assert answered.wait(10)
        t.join(10)
    finally:
        client.close()
        server.shutdown()


# -- (e) one assembly -----------------------------------------------------------------


def test_a_server_with_no_backend_given_serves_get_backends_chain_and_holds_one_engine(chain_server):
    server, backend = chain_server
    assert server.backend is backend  # the fixture gave it none: it took get_backend()'s
    be.set_backend(backend)
    again = SidecarServer("127.0.0.1:0")
    try:
        assert again.backend is be.get_backend() is backend
    finally:
        again.shutdown()
    assert server._sched is None and server._front is engine_of(backend)
    assert server.scheduler_counters()["requests"] == backend.counters()["requests"]
    # the warm-up and the lines that say what is served find the device tier under the chain
    hybrid = backend.inner.tiers[0].backend
    assert service._device_tier(backend) is hybrid
    assert server.device_counters()["platform"] == "cpu"
    # a bare backend keeps the server's own scheduler, as before
    bare = SidecarServer("127.0.0.1:0", backend=be.CpuBackend())
    try:
        assert bare._front is bare._sched is not None
        assert bare.device_counters() == {} and bare.warmup() is False
    finally:
        bare.shutdown()


def test_a_sidecar_refuses_to_serve_the_grpc_backend():
    with pytest.raises(ValueError, match="cannot serve the grpc backend"):
        SidecarServer("127.0.0.1:0", backend=GrpcBackend("127.0.0.1:1"))


def test_the_command_takes_its_address_out_of_the_environment(monkeypatch, capsys):
    """`python -m cometbft_tpu.sidecar` under `auto` serves the supervised
    chain with no `grpc` tier that would dial its own listener, says what it
    serves, and on its way out what crossed the wire."""
    monkeypatch.setenv("CMTPU_SIDECAR_ADDR", "127.0.0.1:0")
    monkeypatch.setenv("CMTPU_SIDECAR_WARM", "0")
    monkeypatch.setenv("CMTPU_BACKEND", "auto")
    old = be._backend
    be.set_backend(None)

    real = SidecarServer.serve_forever

    def serve_once(self):
        client = GrpcBackend(self.bound_addr, timeout_s=10)
        threading.Thread(target=real, args=(self,), daemon=True).start()
        try:
            assert client.ping()
        finally:
            client.close()
        raise KeyboardInterrupt

    monkeypatch.setattr(SidecarServer, "serve_forever", serve_once)
    try:
        service.main()
        chain = be.get_backend().counters()["inner"]["chain"]
    finally:
        be.get_backend().close()
        be.set_backend(old)
    assert chain == ["cpu"], "JAX_PLATFORMS=cpu: the supervised chain of a host with no accelerator"
    assert "CMTPU_SIDECAR_ADDR" not in os.environ
    lines = capsys.readouterr().out.splitlines()
    assert lines[0].startswith("sidecar: serving on 127.0.0.1:") and "backend=coalesce" in lines[0]
    assert lines[1] == "sidecar: backend {}"
    served = json.loads(next(ln for ln in lines if ln.startswith("sidecar: stopping, server "))[26:])
    assert served["requests"] == 1 and served["bytes_in"] > 0 and served["bytes_out"] > 0
    sup = json.loads(next(ln for ln in lines if ln.startswith("sidecar: stopping, supervisor "))[30:])
    assert sup["chain"] == ["cpu"] and sup["degraded_calls"] == 0
