"""Verification sidecar tests: framed protocol server + GrpcBackend client
(SURVEY §7 design stance; reference seam: crypto/batch + types/validation.go).
"""

import socket
import threading
import time

import pytest

from cometbft_tpu.sidecar import backend as backend_mod
from cometbft_tpu.sidecar.backend import CpuBackend
from cometbft_tpu.sidecar.service import GrpcBackend, SidecarServer
from cometbft_tpu.crypto import ed25519
from cometbft_tpu.crypto.merkle import hash_from_byte_slices
from cometbft_tpu.types import validation
from cometbft_tpu.types.block import PRECOMMIT_TYPE, BlockID, Commit, PartSetHeader
from cometbft_tpu.types.cmttime import Time
from cometbft_tpu.types.priv_validator import MockPV
from cometbft_tpu.types.validator import Validator
from cometbft_tpu.types.validator_set import ValidatorSet
from cometbft_tpu.types.vote import Vote, vote_to_commit_sig

CHAIN_ID = "sidecar-chain"

pytestmark = pytest.mark.sidecar


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.fixture
def sidecar():
    addr = f"127.0.0.1:{_free_port()}"
    server = SidecarServer(addr, backend=CpuBackend()).start()
    client = GrpcBackend(addr, timeout_s=10)
    old = backend_mod._backend
    backend_mod.set_backend(client)
    yield client, server
    backend_mod.set_backend(old)
    client.close()
    server.shutdown()


def _make_commit(n_vals=4):
    pvs = [MockPV() for _ in range(n_vals)]
    vals = ValidatorSet([Validator.new(pv.get_pub_key(), 10) for pv in pvs])
    pvs = {pv.address(): pv for pv in pvs}
    bid = BlockID(b"\x11" * 32, PartSetHeader(1, b"\x22" * 32))
    sigs = []
    for idx, v in enumerate(vals.validators):
        vote = Vote(
            type=PRECOMMIT_TYPE,
            height=5,
            round=0,
            block_id=bid,
            timestamp=Time(1700000000 + idx, 0),
            validator_address=v.address,
            validator_index=idx,
        )
        signed = pvs[v.address].sign_vote(CHAIN_ID, vote)
        sigs.append(vote_to_commit_sig(signed))
    return vals, bid, Commit(height=5, round=0, block_id=bid, signatures=sigs)


def test_ping(sidecar):
    client, _ = sidecar
    assert client.ping()


def test_batch_verify_roundtrip(sidecar):
    client, _ = sidecar
    pvs = [ed25519.gen_priv_key() for _ in range(8)]
    msgs = [b"msg-%d" % i for i in range(8)]
    sigs = [pv.sign(m) for pv, m in zip(pvs, msgs)]
    pubs = [pv.pub_key().bytes() for pv in pvs]
    ok, bitmap = client.batch_verify(pubs, msgs, sigs)
    assert ok and bitmap == [True] * 8
    # Corrupt one signature: the bitmap must localize it.
    sigs[3] = sigs[3][:-1] + bytes([sigs[3][-1] ^ 1])
    ok, bitmap = client.batch_verify(pubs, msgs, sigs)
    assert not ok
    assert bitmap == [True] * 3 + [False] + [True] * 4


def test_merkle_root_matches_host(sidecar):
    client, _ = sidecar
    leaves = [b"leaf-%d" % i for i in range(100)]
    assert client.merkle_root(leaves) == hash_from_byte_slices(leaves)


def test_verify_commit_through_sidecar(sidecar):
    """The node-level path: types.verify_commit_light routed through the
    process-wide backend, which is now the remote sidecar (VERDICT r2 #2)."""
    client, _ = sidecar
    vals, bid, commit = _make_commit()
    validation.verify_commit_light(CHAIN_ID, vals, bid, 5, commit)
    # A tampered commit must still fail through the remote path.
    bad = Commit(
        height=5,
        round=0,
        block_id=bid,
        signatures=[
            type(s)(
                block_id_flag=s.block_id_flag,
                validator_address=s.validator_address,
                timestamp=s.timestamp,
                signature=b"\x00" * 64,
            )
            for s in commit.signatures
        ],
    )
    with pytest.raises(Exception):
        validation.verify_commit_light(CHAIN_ID, vals, bid, 5, bad)


def test_sidecar_error_isolated(sidecar):
    client, _ = sidecar
    with pytest.raises(RuntimeError, match="length mismatch"):
        client.batch_verify([b"\x00" * 32], [], [])
    assert client.ping()  # connection survives a request error


def test_reconnect_after_server_side_close(sidecar):
    client, server = sidecar
    assert client.ping()
    # Force-drop the client's socket; the next call must reconnect.
    client._sock.close()
    assert client.ping()


def test_backend_env_selects_grpc(monkeypatch, sidecar):
    client, server = sidecar
    monkeypatch.setenv("CMTPU_BACKEND", "grpc")
    monkeypatch.setenv("CMTPU_SIDECAR_ADDR", client.addr)
    backend_mod.set_backend(None)
    b = backend_mod.get_backend()
    assert isinstance(b, GrpcBackend)
    assert b.ping()
    b.close()


def test_pipelined_concurrent_requests(sidecar):
    """Many in-flight requests on ONE connection (VERDICT r3 weak #8): the
    client demultiplexes responses by id, so concurrent callers do not
    serialize on a write+read lock."""
    import threading

    client, _ = sidecar
    pv = ed25519.gen_priv_key_from_secret(b"pipeline")
    pub, msg = pv.pub_key().bytes(), b"pipelined"
    sig = pv.sign(msg)
    results = []
    errors = []

    def worker(i):
        try:
            if i % 2:
                ok, bits = client.batch_verify([pub] * 4, [msg] * 4, [sig] * 4)
                results.append(ok and all(bits))
            else:
                root = client.merkle_root([b"leaf-%d" % j for j in range(8)])
                results.append(root == hash_from_byte_slices([b"leaf-%d" % j for j in range(8)]))
        except Exception as e:
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not errors, errors
    assert len(results) == 16 and all(results)
    # the connection survives and serves a subsequent call
    assert client.ping()


class _WedgedServer:
    """Accepts connections, reads forever, never replies — the failure mode
    where the sidecar process is alive but its worker is stuck on-device."""

    def __init__(self):
        self._lsock = socket.socket()
        self._lsock.bind(("127.0.0.1", 0))
        self._lsock.listen(4)
        self.addr = "127.0.0.1:%d" % self._lsock.getsockname()[1]
        self._conns = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._serve, daemon=True)
        self._thread.start()

    def _serve(self):
        self._lsock.settimeout(0.2)
        while not self._stop.is_set():
            try:
                conn, _ = self._lsock.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            self._conns.append(conn)  # hold it open; never write back

    def shutdown(self):
        self._stop.set()
        self._lsock.close()
        for c in self._conns:
            c.close()
        self._thread.join(timeout=2)


def test_wedged_server_times_out_within_deadline():
    """Satellite: the server accepts but never replies. The client must
    surface TimeoutError within the configured deadline — not hang."""
    server = _WedgedServer()
    client = GrpcBackend(server.addr, timeout_s=0.3)
    try:
        t0 = time.perf_counter()
        with pytest.raises(TimeoutError, match="timed out"):
            client.ping()
        elapsed = time.perf_counter() - t0
        assert elapsed < 2.0, f"wedged ping took {elapsed:.1f}s"
    finally:
        client.close()
        server.shutdown()


def test_wedged_server_degrades_through_supervisor():
    """The full ISSUE shape: supervised chain over a wedged sidecar still
    answers correctly in bounded time, and the second call fails over
    without paying the deadline again (the breaker trips)."""
    from cometbft_tpu.sidecar.supervisor import ResilientBackend

    server = _WedgedServer()
    client = GrpcBackend(server.addr, timeout_s=30)  # client knob loose:
    # the SUPERVISOR deadline is what bounds the call.
    sup = ResilientBackend(
        [("grpc", client), ("cpu", CpuBackend())],
        deadline_ms=300, retries=0, backoff_ms=1,
        breaker_threshold=2, breaker_cooldown_ms=60_000, crosscheck="off",
    )
    try:
        pv = ed25519.gen_priv_key_from_secret(b"wedged-sidecar")
        pub, msg = pv.pub_key().bytes(), b"still-answered"
        sig = pv.sign(msg)
        t0 = time.perf_counter()
        ok, bits = sup.batch_verify([pub] * 4, [msg] * 4, [sig] * 4)
        first_ms = (time.perf_counter() - t0) * 1000
        assert ok and bits == [True] * 4
        assert first_ms < 2 * 300, f"degradation took {first_ms:.0f} ms"
        t0 = time.perf_counter()
        ok, _ = sup.batch_verify([pub] * 4, [msg] * 4, [sig] * 4)
        second_ms = (time.perf_counter() - t0) * 1000
        assert ok and second_ms < 300
        c = sup.counters()
        assert c["deadline_exceeded"] >= 1 and c["active_tier"] == "cpu"
    finally:
        sup.close()
        server.shutdown()


def test_redial_backoff_fails_fast_in_window():
    """Satellite: after a dial failure the client does not re-dial on every
    call — inside the backoff window it fails fast with ConnectionError."""
    port = _free_port()  # nothing listening
    client = GrpcBackend(f"127.0.0.1:{port}", timeout_s=1, connect_timeout_s=0.2)
    try:
        with pytest.raises((ConnectionError, OSError)):
            client.ping()
        assert client._redial_failures >= 1
        # Within the window: instant ConnectionError, no 0.2 s dial attempt.
        t0 = time.perf_counter()
        with pytest.raises(ConnectionError, match="redial backoff"):
            client.ping()
        assert time.perf_counter() - t0 < 0.1
    finally:
        client.close()


def test_redial_succeeds_after_window_when_server_returns():
    """The other half of the satellite: once the backoff window passes and
    the sidecar is back, the next call redials and succeeds."""
    port = _free_port()
    client = GrpcBackend(f"127.0.0.1:{port}", timeout_s=5, connect_timeout_s=0.2)
    try:
        with pytest.raises((ConnectionError, OSError)):
            client.ping()
        server = SidecarServer(f"127.0.0.1:{port}", backend=CpuBackend()).start()
        try:
            deadline = time.monotonic() + 5
            while True:
                try:
                    assert client.ping()
                    break
                except ConnectionError:
                    if time.monotonic() > deadline:
                        raise
                    time.sleep(0.05)
            assert client._redial_failures == 0  # reset on success
        finally:
            server.shutdown()
    finally:
        client.close()


# -- Ping capability reply: the serving pod's mesh width ---------------------


class _WideCpuBackend(CpuBackend):
    """A sidecar backend fronting an (imaginary) 8-chip pod."""

    def mesh_width(self) -> int:
        return 8


def test_ping_reply_carries_remote_mesh_width():
    addr = f"127.0.0.1:{_free_port()}"
    server = SidecarServer(addr, backend=_WideCpuBackend()).start()
    client = GrpcBackend(addr, timeout_s=10)
    try:
        assert client.mesh_width() == 1  # unprobed: never dials on its own
        assert client.ping()
        assert client.mesh_width() == 8  # learned from the capability reply
    finally:
        client.close()
        server.shutdown()


def test_ping_accepts_legacy_bare_pong(monkeypatch):
    # An old server answers the raw b"pong" body; the upgraded client must
    # treat that as healthy and leave the width at its unprobed default.
    client = GrpcBackend("127.0.0.1:1", timeout_s=1)
    monkeypatch.setattr(client, "_call", lambda method, payload: b"pong")
    assert client.ping()
    assert client.mesh_width() == 1


class _WidthStubBackend:
    """Minimal VerifyBackend with a settable width (no crypto involved)."""

    name = "stub"

    def __init__(self, width=1):
        self.width = width

    def mesh_width(self) -> int:
        return self.width

    def batch_verify(self, pubs, msgs, sigs):
        return True, [True] * len(pubs)

    def merkle_root(self, leaves):
        return hash_from_byte_slices(list(leaves))

    def ping(self) -> bool:
        return True


def test_supervisor_mesh_width_is_widest_tier():
    from cometbft_tpu.sidecar.supervisor import ResilientBackend

    sup = ResilientBackend(
        [("grpc", _WidthStubBackend(4)), ("cpu", _WidthStubBackend(1))],
        crosscheck="off",
    )
    try:
        assert sup.mesh_width() == 4
    finally:
        sup.close()


def test_coalescer_auto_cap_refreshes_from_width(monkeypatch):
    # The auto merge cap must follow the chain's width as a grpc tier
    # learns its pod's size from Ping — and never shrink back.
    from cometbft_tpu.sidecar.scheduler import CoalescingScheduler

    monkeypatch.delenv("CMTPU_COALESCE_MAX", raising=False)
    inner = _WidthStubBackend(1)
    sched = CoalescingScheduler(inner)
    initial = sched.max_sigs
    assert initial % 16384 == 0
    inner.width = (initial // 16384) * 2  # the remote pod is wider
    assert sched.refresh_cap() == 16384 * inner.width
    inner.width = 1  # a narrower reading later must not shrink the cap
    assert sched.refresh_cap() == sched.max_sigs
    sched.close()


def test_coalescer_pinned_cap_never_moves():
    from cometbft_tpu.sidecar.scheduler import CoalescingScheduler

    sched = CoalescingScheduler(_WidthStubBackend(8), max_sigs=99)
    assert sched.refresh_cap() == 99 and sched.max_sigs == 99
    sched.close()


# -- round 10: frame guard + chunked streaming -------------------------------


def _signed_triples(n, tag=b"stream", corrupt=()):
    pv = ed25519.gen_priv_key_from_secret(tag)
    pub = pv.pub_key().bytes()
    msgs = [b"%s-%d" % (tag, i) for i in range(n)]
    sigs = [pv.sign(m) for m in msgs]
    for i in corrupt:
        sigs[i] = sigs[i][:-1] + bytes([sigs[i][-1] ^ 1])
    return [pub] * n, msgs, sigs


def test_write_frame_refuses_oversized(monkeypatch):
    from cometbft_tpu.sidecar.service import FrameTooLarge, write_frame

    monkeypatch.setenv("CMTPU_SIDECAR_MAX_FRAME", "2048")

    class _NeverSock:
        def sendall(self, data):  # pragma: no cover - guard must fire first
            raise AssertionError("oversized frame reached the socket")

    with pytest.raises(FrameTooLarge, match="refusing to send"):
        write_frame(_NeverSock(), b"\x00" * 4096)


def test_oversized_frame_error_response_connection_survives(monkeypatch):
    """Satellite: an over-cap frame draws a loud error response instead of
    an unbounded allocation, and the SAME connection keeps serving."""
    import struct as _struct

    from cometbft_tpu.sidecar import service
    from cometbft_tpu.wire import proto

    monkeypatch.setenv("CMTPU_SIDECAR_MAX_FRAME", "2048")
    addr = f"127.0.0.1:{_free_port()}"
    server = SidecarServer(addr, backend=CpuBackend()).start()
    host, port = addr.rsplit(":", 1)
    sock = socket.create_connection((host, int(port)), timeout=5)
    try:
        sock.sendall(_struct.pack(">I", 4096) + b"\x00" * 4096)
        resp = service.read_frame(sock)
        fields = proto.decode_fields(resp)
        assert not proto.get_bool(fields, 2)
        assert "FrameTooLarge" in proto.get_string(fields, 3)
        # The connection survives: a well-formed Ping still answers.
        req = service._encode_request(7, "Ping", b"")
        sock.sendall(_struct.pack(">I", len(req)) + req)
        fields = proto.decode_fields(service.read_frame(sock))
        assert proto.get_uvarint(fields, 1) == 7
        assert proto.get_bool(fields, 2)
    finally:
        sock.close()
        server.shutdown()


def test_ping_advertises_streaming_capability(sidecar):
    client, _ = sidecar
    assert client._remote_streams is None  # unprobed
    assert client.ping()
    assert client._remote_streams is True
    assert client._remote_chunk >= 1
    assert client.counters()["streaming"] is True


def test_chunk_size_aligns_to_remote_width(monkeypatch):
    client = GrpcBackend("127.0.0.1:1", timeout_s=1)
    client._remote_mesh_width = 8
    monkeypatch.setenv("CMTPU_SIDECAR_CHUNK", "10")
    assert client.chunk_size() == 16  # rounded UP to a width multiple
    monkeypatch.delenv("CMTPU_SIDECAR_CHUNK")
    client._remote_chunk = 20
    assert client.chunk_size() == 24


def test_streamed_batch_verify_bit_identical(monkeypatch, sidecar):
    """The tentpole contract: a streamed call returns the exact bitmap the
    in-process backend computes, corrupted lanes localized across chunk
    boundaries, and actually went over the wire in chunks."""
    client, _ = sidecar
    monkeypatch.setenv("CMTPU_SIDECAR_CHUNK", "8")
    corrupt = (3, 8, 30)  # first chunk, a chunk boundary, a later chunk
    pubs, msgs, sigs = _signed_triples(37, corrupt=corrupt)
    ok, bitmap = client.batch_verify(pubs, msgs, sigs)
    ref_ok, ref_bits = CpuBackend().batch_verify(pubs, msgs, sigs)
    assert (ok, bitmap) == (ref_ok, ref_bits)
    assert not ok and [i for i, b in enumerate(bitmap) if not b] == list(corrupt)
    c = client.counters()
    assert c["streamed_calls"] == 1
    assert c["streamed_chunks"] == 5  # ceil(37 / 8)
    assert c["unary_calls"] == 0
    # All-good batch too (ok path), reusing the learned capability.
    pubs, msgs, sigs = _signed_triples(17, tag=b"stream2")
    ok, bitmap = client.batch_verify(pubs, msgs, sigs)
    assert ok and bitmap == [True] * 17


def test_small_batches_stay_unary(monkeypatch, sidecar):
    client, _ = sidecar
    monkeypatch.setenv("CMTPU_SIDECAR_CHUNK", "64")
    pubs, msgs, sigs = _signed_triples(8, tag=b"unary")
    ok, bitmap = client.batch_verify(pubs, msgs, sigs)
    assert ok and bitmap == [True] * 8
    c = client.counters()
    assert c["unary_calls"] == 1 and c["streamed_calls"] == 0


# -- the columnar payload (PR 31) ------------------------------------------------


def _column(num, entries, *, count=None, stride=None, lengths=None, data=None):
    """One Column field built from the schema in `service.py`'s docstring
    with `proto`'s primitives alone, never by `encode_columns`; a keyword
    overrides what the entries say, which is how a malformed one is made."""
    from cometbft_tpu.wire import proto

    sizes = {len(e) for e in entries}
    if stride is None:
        stride = sizes.pop() if len(sizes) == 1 else 0
    if lengths is None and not stride:
        lengths = [len(e) for e in entries]
    body = proto.field_varint(1, len(entries) if count is None else count)
    body += proto.field_varint(2, stride)
    if lengths is not None:
        if not isinstance(lengths, bytes):
            lengths = b"".join(map(proto.encode_uvarint, lengths))
        body += proto.field_bytes(3, lengths, emit_default=True)
    body += proto.field_bytes(4, b"".join(entries) if data is None else data)
    return proto.field_bytes(num, body, emit_default=True)


def _payload(pubs, msgs, sigs, first=1, **bad):
    """A BatchVerifyReq's three columns by hand; `bad` goes to the messages'."""
    return _column(first, pubs) + _column(first + 1, msgs, **bad) + _column(first + 2, sigs)


def _ragged_triples(n):
    """Signed triples whose every column is ragged: lane 1 has a 31-byte
    key, lane 2 an empty message (signed), lane 3 a 63-byte signature."""
    pubs, msgs, sigs = _signed_triples(n, tag=b"ragged", corrupt=(0,))
    pv = ed25519.gen_priv_key_from_secret(b"ragged")
    msgs[2], sigs[2] = b"", pv.sign(b"")
    pubs[1], sigs[3] = pubs[1][:31], sigs[3][:63]
    return pubs, msgs, sigs


COLUMN_CASES = {
    # name: (pubs, msgs, sigs), columns that go with a lengths array
    "empty": (([], [], []), 3),
    "one-lane": (([b"p" * 32], [b"vote"], [b"s" * 64]), 0),
    "all-messages-empty": (([b"p" * 32] * 3, [b""] * 3, [b"s" * 64] * 3), 1),
    "one-message-empty": (([b"p" * 32] * 3, [b"a", b"", b"c"], [b"s" * 64] * 3), 1),
    "31-byte-key-63-byte-signature": (
        ([b"p" * 32, b"q" * 31], [b"mm", b"nn"], [b"s" * 63, b"t" * 64]), 2),
    "10000-fixed": (
        ([bytes([i % 251]) * 32 for i in range(10_000)],
         [b"%06d" % i + b"v" * 134 for i in range(10_000)],
         [bytes([i % 241]) * 64 for i in range(10_000)]), 0),
    "10000-ragged-messages": (
        ([b"p" * 32] * 10_000, [b"v" * (120 + i % 7) for i in range(10_000)],
         [b"s" * 64] * 10_000), 1),
}


@pytest.mark.parametrize("name", COLUMN_CASES)
@pytest.mark.parametrize("first", [1, 4], ids=["BatchVerifyReq", "ChunkReq"])
def test_columns_round_trip_and_match_the_schema_built_by_hand(name, first):
    from cometbft_tpu.wire import proto
    from cometbft_tpu.wire.columns import decode_columns, encode_columns

    (pubs, msgs, sigs), n_ragged = COLUMN_CASES[name]
    payload, ragged = encode_columns(first, pubs, msgs, sigs)
    assert ragged == n_ragged
    got = decode_columns(proto.decode_fields(payload), first)
    assert got == (pubs, msgs, sigs, n_ragged)
    assert all(type(col) is list and all(type(e) is bytes for e in col) for col in got[:3])
    # the hand-built payload decodes to the same batch, and a fixed column
    # costs a header a column over its entries, not bytes a triple
    assert decode_columns(proto.decode_fields(_payload(pubs, msgs, sigs, first)), first) == got
    entries = sum(map(len, pubs)) + sum(map(len, msgs)) + sum(map(len, sigs))
    assert len(payload) - entries <= 3 * 16 + 2 * len(pubs) * n_ragged


def test_a_lengths_array_packs_and_unpacks_as_the_loop_over_uvarints_does():
    """The array code against `proto`'s entry-by-entry codec, the reference."""
    import random

    import numpy as np

    from cometbft_tpu.wire import proto
    from cometbft_tpu.wire.columns import _pack_uvarints, _unpack_uvarints

    rng = random.Random(31)
    edges = [0, 1, 127, 128, 16_383, 16_384, 2**21 - 1, 2**21, 2**28 - 1, 2**28, 2**32 - 1, 2**35 - 1]
    for n, pool in ((0, edges), (1, edges), (2, edges), (7, edges), (10_000, edges), (10_000, [138, 139])):
        values = [rng.choice(pool + [rng.randrange(2**35)] * (pool is edges)) for _ in range(n)]
        packed = b"".join(map(proto.encode_uvarint, values))
        assert _pack_uvarints(np.array(values, np.int64)) == packed
        assert _unpack_uvarints(packed, n).tolist() == values == proto.get_repeated_uvarint({3: [packed]}, 3)
        for bad in (packed + b"\x81", packed + b"\x01", packed[:-1]):
            if bad != packed[:0] or n:
                with pytest.raises(ValueError, match=f"lengths for {n} entries"):
                    _unpack_uvarints(bad, n)


MALFORMED = {
    # name: (overrides of the messages' column over 3 triples, the decoder's words)
    "count-over": (dict(count=4), "4 x 2 is not its 6 bytes"),
    "count-under": (dict(count=2), "2 x 2 is not its 6 bytes"),
    "count-beyond-64-bits-of-bytes": (dict(count=(1 << 62) + 3), "is not its 6 bytes"),
    "blob-shorter": (dict(data=b"aabbc"), "3 x 2 is not its 5 bytes"),
    "blob-longer": (dict(data=b"aabbccd"), "3 x 2 is not its 7 bytes"),
    "stride-with-lengths": (dict(lengths=[2, 2, 2]), "a stride and a lengths array"),
    "lengths-sum-short": (dict(stride=0, lengths=[2, 2, 1]), "lengths do not sum to its 6 bytes"),
    "lengths-sum-long": (dict(stride=0, lengths=[2, 2, 0xFFFFFFFF]), "lengths do not sum"),
    "lengths-for-fewer": (dict(stride=0, lengths=[3, 3]), "2 lengths for 3 entries"),
    "lengths-for-more": (dict(stride=0, lengths=[2, 2, 1, 1]), "4 lengths for 3 entries"),
    "lengths-cut-short": (dict(stride=0, lengths=b"\x02\x02\x82"), "2 lengths for 3 entries"),
    "length-of-six-bytes": (dict(stride=0, lengths=b"\x02\x02\x82\x80\x80\x80\x80\x00"),
                            "a length of more than five bytes of varint"),
    "counts-differ": (dict(count=2, data=b"aabb"), "pubs/msgs/sigs length mismatch"),
}


def _malformed_payload(name, first=1):
    return _payload([b"p" * 32] * 3, [b"aa", b"bb", b"cc"], [b"s" * 64] * 3, first,
                    **MALFORMED[name][0])


@pytest.mark.parametrize("name", MALFORMED)
def test_a_malformed_column_is_refused_by_the_decoder(name):
    from cometbft_tpu.wire import proto
    from cometbft_tpu.wire.columns import decode_columns

    with pytest.raises(ValueError, match=MALFORMED[name][1]):
        decode_columns(proto.decode_fields(_malformed_payload(name)), 1)


@pytest.mark.parametrize("name", MALFORMED)
def test_a_malformed_column_is_an_error_response_and_the_connection_serves_on(sidecar, name):
    client, server = sidecar
    assert client.ping()
    sock = client._sock
    with pytest.raises(RuntimeError, match="sidecar error: ValueError: .*" + MALFORMED[name][1]):
        client._call("BatchVerify", _malformed_payload(name))
    assert server.counters()["lanes_in"] == 0, "never a shorter, padded or reordered batch"
    pubs, msgs, sigs = _signed_triples(5, tag=b"after", corrupt=(4,))
    assert client.batch_verify(pubs, msgs, sigs) == (False, [True] * 4 + [False])
    assert client._sock is sock, "the connection survived the refusal"
    assert server.counters()["lanes_in"] == 5 and server.counters()["requests"] == 3


def test_a_hand_built_unary_payload_is_served(sidecar):
    """A client written from the schema alone (`_column`, no code of the
    package's encoder) is answered lane for lane."""
    from cometbft_tpu.wire import proto

    client, _ = sidecar
    pubs, msgs, sigs = _signed_triples(24, tag=b"by-hand", corrupt=(5,))
    out = client._call("BatchVerify", _payload(pubs, msgs, sigs))
    fields = proto.decode_fields(out)
    bitmap = [bool(b) for b in proto.get_bytes(fields, 2)]
    assert not proto.get_bool(fields, 1)
    assert bitmap == [i != 5 for i in range(24)]


@pytest.mark.parametrize("chunk", [64, 8], ids=["unary", "streamed"])
def test_ragged_columns_are_answered_as_the_cpu_reference_answers_them(monkeypatch, sidecar, chunk):
    """A 31-byte key, an empty message and a 63-byte signature ride as
    ragged columns and come back false or true lane for lane, one frame or
    five; the counters say how each column went."""
    client, server = sidecar
    monkeypatch.setenv("CMTPU_SIDECAR_CHUNK", str(chunk))
    pubs, msgs, sigs = _ragged_triples(37)
    ref = CpuBackend().batch_verify(pubs, msgs, sigs)
    assert client.batch_verify(pubs, msgs, sigs) == ref
    assert [i for i, b in enumerate(ref[1]) if not b] == [0, 1, 3]
    c = client.counters()
    frames = 1 if chunk == 64 else 5
    assert (c["unary_calls"], c["streamed_chunks"]) == ((1, 0) if chunk == 64 else (0, 5))
    assert c["columns_fixed"] + c["columns_ragged"] == 3 * frames
    # the frame that holds lanes 1-3 has three ragged columns; of the later
    # chunks only the second's messages differ in length ("ragged-8", "-9",
    # "-10" ..), the rest go at one stride
    assert c["columns_ragged"] == (3 if chunk == 64 else 3 + 1)
    assert server.counters()["lanes_in"] == 37 == c["lanes_sent"]
    # a batch of one stride a column: three fixed columns more
    pubs, msgs, sigs = _signed_triples(6, tag=b"fixed!")
    assert client.batch_verify(pubs, msgs, sigs) == (True, [True] * 6)
    after = client.counters()
    assert after["columns_fixed"] - c["columns_fixed"] == 3
    assert after["columns_ragged"] == c["columns_ragged"]


def test_the_column_counters_reach_metrics_beside_the_other_sidecar_gauges(sidecar):
    from cometbft_tpu.libs.metrics import Registry
    from cometbft_tpu.node.node import Node

    client, _ = sidecar  # the fixture made it the process's backend
    reg = Registry(namespace="cmt")
    Node._register_backend_metrics(reg)
    assert "cmt_sidecar_columns_fixed 0" in reg.render()
    client.batch_verify(*_ragged_triples(6))
    client.batch_verify(*_signed_triples(6, tag=b"fixed!"))
    out = reg.render()
    assert "cmt_sidecar_columns_fixed 3" in out and "cmt_sidecar_columns_ragged 3" in out
    assert "cmt_sidecar_lanes_sent 12" in out


def test_10000_lanes_cross_unary_and_streamed_in_the_order_sent(monkeypatch):
    """The commit's size through both methods: the backend behind the
    server sees the batch byte for byte, and the answer keeps its order."""

    class Odd:
        name = "odd"

        def __init__(self):
            self.calls = []

        def batch_verify(self, pubs, msgs, sigs):
            self.calls.append((pubs, msgs, sigs))
            return False, [m[5] % 2 == 1 for m in msgs]

    (pubs, msgs, sigs), _ = COLUMN_CASES["10000-fixed"]
    server = SidecarServer("127.0.0.1:0", backend=Odd()).start()
    client = GrpcBackend(server.bound_addr, timeout_s=30)
    try:
        assert client.ping() and client.chunk_size() == 1024
        want = (False, [i % 2 == 1 for i in range(10_000)])
        assert client.batch_verify(pubs, msgs, sigs) == want
        monkeypatch.setenv("CMTPU_SIDECAR_CHUNK", str(1 << 20))
        assert client.batch_verify(pubs, msgs, sigs) == want
        assert server.backend.calls == [(pubs, msgs, sigs)] * 2
        c = client.counters()
        assert (c["streamed_chunks"], c["unary_calls"]) == (10, 1)
        assert (c["columns_fixed"], c["columns_ragged"]) == (3 * 11, 0)
        # 236 bytes a triple of payload, and under a byte a triple of everything else
        assert 236 <= c["bytes_sent"] / c["lanes_sent"] < 236.1
    finally:
        client.close()
        server.shutdown()


def test_server_coalesces_across_connections(monkeypatch):
    """Tentpole part 3: concurrent CONNECTIONS merge into one device
    dispatch via the server-side scheduler, bitmaps sliced per request."""
    monkeypatch.setenv("CMTPU_COALESCE_WINDOW_MS", "75")
    addr = f"127.0.0.1:{_free_port()}"
    server = SidecarServer(addr, backend=CpuBackend()).start()
    clients = [GrpcBackend(addr, timeout_s=10) for _ in range(3)]
    try:
        pubs, msgs, sigs = _signed_triples(6, tag=b"merge", corrupt=(2,))
        results, errors = [], []

        def worker(cl):
            try:
                results.append(cl.batch_verify(pubs, msgs, sigs))
            except Exception as e:  # pragma: no cover - surfaced below
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(c,)) for c in clients]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not errors, errors
        expected = [i != 2 for i in range(6)]
        assert results == [(False, expected)] * 3
        c = server.scheduler_counters()
        assert c["requests"] == 3
        assert c["coalesced_dispatches"] >= 1
        assert c["batched_requests"] >= 2
        # Identical triples from different connections share lanes.
        assert c["dedup_sigs"] >= 6
    finally:
        for cl in clients:
            cl.close()
        server.shutdown()


class _KillMidStreamServer:
    """Speaks the framed protocol far enough to advertise streaming, then
    drops the connection AND the listener on the first chunk — the sidecar
    process dying mid-streamed-dispatch."""

    def __init__(self):
        self._lsock = socket.socket()
        # Accepted conns inherit SO_REUSEADDR; without it the killer's side
        # of the dropped stream sits in TIME_WAIT owning the port and the
        # replacement SidecarServer cannot bind it back.
        self._lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._lsock.bind(("127.0.0.1", 0))
        self._lsock.listen(4)
        self.addr = "127.0.0.1:%d" % self._lsock.getsockname()[1]
        self.port = self._lsock.getsockname()[1]
        self.chunk_seen = threading.Event()
        self.closed = threading.Event()  # listener really released the port
        self._thread = threading.Thread(target=self._serve, daemon=True)
        self._thread.start()

    def _serve(self):
        from cometbft_tpu.sidecar import service
        from cometbft_tpu.wire import proto

        try:
            conn, _ = self._lsock.accept()
        except OSError:
            return
        while True:
            try:
                body = service.read_frame(conn)
            except (OSError, ValueError):
                body = None
            if body is None:
                break
            fields = proto.decode_fields(body)
            req_id = proto.get_uvarint(fields, 1)
            method = proto.get_string(fields, 2)
            if method == "Ping":
                reply = (
                    proto.field_bytes(1, b"pong")
                    + proto.field_varint(2, 1)
                    + proto.field_varint(3, 1)
                    + proto.field_varint(4, 4)
                )
                service.write_frame(
                    conn, service._encode_response(req_id, True, "", reply)
                )
                continue
            # First streamed chunk: die mid-stream.
            self.chunk_seen.set()
            break
        conn.close()
        self._lsock.close()
        self.closed.set()

    def shutdown(self):
        try:
            self._lsock.close()
        except OSError:
            pass
        self.closed.set()


def test_redial_during_inflight_stream_degrades_then_recovers():
    """Satellite: kill the server mid-stream. The supervisor must degrade
    the call (bounded, full correct bitmap — never a partial one), and
    once a live server is back on the same port the next call reconnects
    and streams again."""
    from cometbft_tpu.sidecar.supervisor import ResilientBackend

    killer = _KillMidStreamServer()
    client = GrpcBackend(killer.addr, timeout_s=5, connect_timeout_s=0.5)
    sup = ResilientBackend(
        [("grpc", client), ("cpu", CpuBackend())],
        deadline_ms=0, retries=0, backoff_ms=1,
        breaker_threshold=3, breaker_cooldown_ms=100, crosscheck="off",
    )
    try:
        pubs, msgs, sigs = _signed_triples(20, tag=b"killed", corrupt=(7, 13))
        expected = [i not in (7, 13) for i in range(20)]
        assert client.ping()  # learn streaming capability + chunk 4
        t0 = time.perf_counter()
        ok, bits = sup.batch_verify(pubs, msgs, sigs)
        elapsed = time.perf_counter() - t0
        assert killer.chunk_seen.is_set(), "stream never reached the server"
        assert (ok, bits) == (False, expected)  # anchor answered, in full
        assert elapsed < 10, f"degradation took {elapsed:.1f}s"
        assert sup.counters()["degraded_calls"] >= 1
        # Server returns on the SAME port; past the breaker cooldown the
        # next call re-dials and streams end to end.
        assert killer.closed.wait(5), "killer never released the port"
        server = SidecarServer(f"127.0.0.1:{killer.port}", backend=CpuBackend()).start()
        try:
            deadline = time.monotonic() + 5
            while True:
                time.sleep(0.15)  # breaker cooldown + redial backoff
                ok, bits = sup.batch_verify(pubs, msgs, sigs)
                assert (ok, bits) == (False, expected)
                if client.counters()["streamed_calls"] >= 1:
                    break
                assert time.monotonic() < deadline, (
                    f"never streamed again: {client.counters()}"
                )
        finally:
            server.shutdown()
    finally:
        sup.close()
        killer.shutdown()
