"""Verification sidecar: one long-lived process owns the TPU and serves
batch verification + Merkle hashing to any number of node processes.

This is the §7 design stance ("JAX/Pallas behind a gRPC verification
sidecar", SURVEY.md). The transport is deliberately NOT grpcio (although
grpcio is importable in this image and abci/grpc.py uses it for ABCI
parity): the sidecar sits on the consensus hot path, and the hand-framed
protocol keeps per-call overhead to one length-prefixed write + read with
zero HTTP/2 machinery. It is the same shape as the reference's ABCI socket
protocol
(abci/client/socket_client.go:529 — length-prefixed protobuf over TCP/unix,
pipelined requests) carrying gRPC-style unary methods:

    BatchVerify(pubs, msgs, sigs) -> (ok, bitmap)   crypto.BatchVerifier
    MerkleRoot(leaves)            -> root           crypto/merkle/tree.go:11
    Ping()                        -> pong           health + capability probe
    Warmup(buckets)               -> ok             precompile batch buckets
    BatchVerifyChunk(...)         -> ack | bitmap   streamed BatchVerify

Wire format: every frame is a 4-byte big-endian length + protobuf body.
  Request  { 1: id (uvarint), 2: method (string), 3: payload (bytes) }
  Response { 1: id (uvarint), 2: ok (bool), 3: error (string), 4: payload }
  BatchVerifyReq  { 1: pubs, 2: msgs, 3: sigs (Column each) }
  Column          { 1: count (uvarint), 2: stride (uvarint),
                    3: lengths (packed uvarint, as `repeated uint32`),
                    4: data (bytes: the entries joined, in order) }
  BatchVerifyResp { 1: all_ok (bool), 2: bitmap (bytes, 1 byte per sig) }
  MerkleReq       { 1: repeated leaves (bytes) }
  MerkleResp      { 1: root (bytes) }
  WarmupReq       { 1: repeated buckets (uvarint) }
  PingResp        { 1: "pong", 2: mesh_width, 3: streaming, 4: chunk }
  ChunkReq        { 1: stream_id, 2: seq, 3: final (bool),
                    4: pubs, 5: msgs, 6: sigs (Column each) }

Columns (PR 31): the three columns of a batch cross the wire as three blobs,
never a field a triple. Where every entry of a column has one length (keys
at 32, signatures at 64) `stride` is that length and `lengths` is absent;
else (a commit's sign bytes, whose timestamps encode to different sizes)
`stride` is 0 and `lengths` holds `count` entries, two bytes each there.
The encoder reads which from the column itself; an empty column, and one of
empty entries, are stride 0. The decoder refuses, as the request's error
response on a connection that survives: a stride with a lengths array,
`count` x `stride` (or the lengths' sum) unequal to the blob's length to
the byte, a lengths array of another count than `count` or with a length
of over five bytes of varint, and three columns of different counts.
Never a shorter, padded or reordered batch.

Streaming (round 10): a large BatchVerify splits into mesh-width-aligned
chunks, each sent as an ordinary framed request (its own id, so the
pipelined reader/pending-table/deadline machinery is unchanged). The chunk
a server advertises is a bucket of the kernel's ladder
(`ed25519_kernel.preferred_stream_chunk`): 1,024 lanes on one chip. The
server decodes each chunk as it arrives and acks it at once, which
overlaps its decode of chunk k with the client's encode of chunk k+1, and
submits the stream ONCE, at the final chunk, as one call of all its lanes
in the order sent. The planner behind it must see a commit whole: ten
calls of 1,024 lanes sit under `CMTPU_HYBRID_MIN` and would all take the
host route with the device idle. The FINAL chunk's response carries the
whole stream's BatchVerifyResp; any chunk error fails the stream with an
error response (never a partial bitmap). Capability-gated: servers
advertise streaming in the Ping reply (field 3) and clients fall back to
unary against servers that do not.

Running the device behind one process also serializes TPU access — a chip
belongs to one process at a time, so N node processes on one host can only
share it through a server like this. What `python -m cometbft_tpu.sidecar`
(and `SidecarServer(addr)` with no backend given) serves is the chain
`get_backend()` assembles in that process, under `CMTPU_BACKEND=auto` the
supervised one every node runs in process (engine -> `ResilientBackend`
(`hybrid` -> `cpu`)): one assembly, with its deadline, breakers and cpu
anchor, and ONE engine, in which concurrent CONNECTIONS coalesce into
single columnar dispatches with per-request bitmap slicing (the merge cap
is on a dispatch's distinct lanes: `sidecar/engine.py`). A server
handed a bare backend (a test's, a fanout shard worker's) puts an engine
of its own over the device lock in front of it.

Both ends trace themselves into `libs/trace.py`'s ring: the client a
`grpc.call` a call (children `grpc.encode`, `grpc.wait`, `grpc.decode`),
the server a `sidecar.request` a request (children `sidecar.decode`,
`sidecar.encode`, and the chain's own spans); `req` is the id of the frame
that carried the answer on both. Request ids are each connection's own, so
the connection goes with them: `port` on a `grpc.call` is its socket's local
port, `conn` on a `sidecar.request` the peer's port, the same number.
`ragged` on `grpc.encode` / `sidecar.decode`
how many of the three columns went with a lengths array. Bytes, lanes and
columns (`columns_fixed`, `columns_ragged`) are counted always
(`GrpcBackend.counters()`, `SidecarServer.counters()`), as are the server's
connections (`connections_accepted`, `connections_open`).
"""

from __future__ import annotations

import json
import os
import random
import signal
import socket
import socketserver
import struct
import threading
import time

from cometbft_tpu.libs import trace
from cometbft_tpu.sidecar.backend import (
    LockedBackend,
    VerifyBackend,
    get_backend,
)
from cometbft_tpu.sidecar.engine import VerificationEngine, engine_of
from cometbft_tpu.wire import proto
from cometbft_tpu.wire.columns import decode_columns, encode_columns

DEFAULT_ADDR = "127.0.0.1:26670"
DEFAULT_BUCKETS = (128, 1024, 10240)
_LEN = struct.Struct(">I")
MAX_FRAME = 1 << 30
# Chunk size a server with no device tier loaded advertises (field 4 of the
# Ping reply); a device-backed server asks the kernel for a bucket-aligned
# size instead (ed25519_kernel.preferred_stream_chunk).
DEFAULT_STREAM_CHUNK = 1024


class FrameTooLarge(ValueError):
    """A frame exceeded CMTPU_SIDECAR_MAX_FRAME. Recoverable on the server
    (error response, connection survives); a client-side raise means the
    caller must chunk (the streaming path) — never silently truncate."""


def _max_frame() -> int:
    env = os.environ.get("CMTPU_SIDECAR_MAX_FRAME", "")
    if env:
        try:
            return max(1024, int(env))
        except ValueError:
            pass
    return MAX_FRAME


# -- framing ------------------------------------------------------------------


def write_frame(sock: socket.socket, body: bytes) -> None:
    cap = _max_frame()
    if len(body) > cap:
        raise FrameTooLarge(
            f"refusing to send {len(body)}-byte frame "
            f"(CMTPU_SIDECAR_MAX_FRAME={cap}); chunk the request instead"
        )
    sock.sendall(_LEN.pack(len(body)) + body)


def read_frame(sock: socket.socket) -> bytes | None:
    hdr = _read_exact(sock, 4)
    if hdr is None:
        return None
    (n,) = _LEN.unpack(hdr)
    cap = _max_frame()
    if n > cap:
        # Drain the oversized body in bounded chunks (never one n-byte
        # allocation) so the stream stays framed and the connection can
        # carry an error response + further requests.
        remaining = n
        while remaining:
            chunk = sock.recv(min(65536, remaining))
            if not chunk:
                return None
            remaining -= len(chunk)
        raise FrameTooLarge(
            f"peer sent {n}-byte frame (CMTPU_SIDECAR_MAX_FRAME={cap})"
        )
    return _read_exact(sock, n)


def _read_exact(sock: socket.socket, n: int) -> bytes | None:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            return None
        buf.extend(chunk)
    return bytes(buf)


def _encode_request(req_id: int, method: str, payload: bytes) -> bytes:
    return (
        proto.field_varint(1, req_id, emit_default=True)
        + proto.field_string(2, method)
        + proto.field_bytes(3, payload)
    )


def _encode_bitmap(ok: bool, bitmap) -> bytes:
    """BatchVerifyResp."""
    return proto.field_bool(1, ok) + proto.field_bytes(2, bytes(1 if b else 0 for b in bitmap))


def _encode_response(req_id: int, ok: bool, error: str, payload: bytes) -> bytes:
    return (
        proto.field_varint(1, req_id, emit_default=True)
        + proto.field_bool(2, ok)
        + proto.field_string(3, error)
        + proto.field_bytes(4, payload)
    )


def _decode_response(body: bytes) -> bytes:
    """A Response's payload; its error raised."""
    fields = proto.decode_fields(body)
    if not proto.get_bool(fields, 2):
        raise RuntimeError(f"sidecar error: {proto.get_string(fields, 3)}")
    return proto.get_bytes(fields, 4)


def _decode_bitmap(payload: bytes, n: int) -> tuple[bool, list[bool]]:
    """A BatchVerifyResp for `n` lanes: the whole bitmap or an error."""
    fields = proto.decode_fields(payload)
    bitmap = proto.get_bytes(fields, 2)
    if len(bitmap) != n:
        raise RuntimeError(f"sidecar answered {len(bitmap)} of {n} lanes")
    return proto.get_bool(fields, 1), [bool(b) for b in bitmap]


# -- server -------------------------------------------------------------------


class _ServerStream:
    """Per-connection state of one in-progress BatchVerifyChunk stream:
    the triples of every chunk so far in the order sent, the expected next
    sequence, and the stream's `sidecar.request` span, open from its first
    chunk to its answer, with the bytes of its frames."""

    __slots__ = ("pubs", "msgs", "sigs", "next_seq", "span", "bytes_in", "bytes_out")

    def __init__(self, span):
        self.pubs: list[bytes] = []
        self.msgs: list[bytes] = []
        self.sigs: list[bytes] = []
        self.next_seq = 0
        self.span = span
        self.bytes_in = 0
        self.bytes_out = 0


def _after_host_pack() -> None:
    """A connection's thread lets a host pack in progress finish before it
    decodes its frame: both are Python under one interpreter lock, the pack
    is on the critical path of the dispatch in flight, and the frame's
    request waits for that dispatch anyway. Never imports jax: no device
    tier loaded, no gate."""
    import sys

    ek = sys.modules.get("cometbft_tpu.ops.ed25519_kernel")
    gate = getattr(ek, "PACK_GATE", None)
    if gate is not None:
        with gate:
            pass


def _device_tier(backend):
    """The tier under `backend` that holds the device, for the warm-up and
    for the lines that say what is served: the backend itself when it is
    bare, else the first tier of the chain under it that can `warmup`.
    None for a host-only server."""
    queue, seen = [backend], set()
    while queue:
        b = queue.pop(0)
        if b is None or id(b) in seen:
            continue
        seen.add(id(b))
        if hasattr(b, "warmup") and not isinstance(b, GrpcBackend):
            return b
        queue.append(getattr(b, "inner", None))
        queue.extend(t.backend for t in getattr(b, "tiers", ()))
    return None


class SidecarServer:
    """The long-lived device owner. With no backend given it serves what
    `get_backend()` assembles in this process — under `CMTPU_BACKEND=auto`
    the supervised chain with its one engine, in which concurrent
    connections (many node processes sharing one chip) merge into single
    columnar dispatches with per-request bitmap slicing. What merges is what
    is queued together when the device frees and fits the cap (16,384 lanes
    a chip) by its DISTINCT lanes: four nodes of one chain that sent the
    same 10,000-signature commit are one 10,000-lane call, each answered
    with its own whole bitmap in its own order; two different commits of
    that size still take a dispatch each. A request that arrives while the
    same columns are in flight joins that dispatch and takes its answer
    (compared entry for entry on this connection's thread, inside
    `submit`), so the four nodes cost the chip one run a height whenever
    they arrive. While the
    device tier packs a dispatch on the host, the other connections' threads
    wait with their frames (`_after_host_pack`). A bare backend handed in
    gets this server's own engine over the device lock in front of it
    (`engine`). Socket handling is one thread per connection, so hosts can
    pipeline requests like the reference's socket ABCI client; MerkleRoot
    and Warmup are serialized by the device lock either way."""

    def __init__(self, addr: str = DEFAULT_ADDR, backend: VerifyBackend | None = None):
        self.addr = addr
        if backend is None:
            backend = get_backend()
        if isinstance(backend, GrpcBackend):
            raise ValueError("a sidecar cannot serve the grpc backend: it would dial a sidecar")
        self.backend = backend
        self._device_lock = threading.Lock()
        # Where verifications are submitted: the engine the backend already
        # carries, never a second one in front of it; else this server's own.
        self.engine = engine_of(backend)
        self._owns_engine = self.engine is None
        if self._owns_engine:
            self.engine = VerificationEngine(LockedBackend(backend, self._device_lock))
        self._count_lock = threading.Lock()  # the counters below
        self.counters_ = {
            "requests": 0,        # answered: a stream is one
            "bytes_in": 0,        # frames read, their 4-byte lengths included
            "bytes_out": 0,       # frames written, likewise
            "lanes_in": 0,        # triples received for verification
            "streams_failed": 0,  # streams torn down by an error
            "connections_accepted": 0,  # over the server's life
        }
        self._conns: set[socket.socket] = set()  # open connections (under _count_lock)
        self._serving = threading.Event()
        host, port = addr.rsplit(":", 1)
        outer = self

        class Handler(socketserver.BaseRequestHandler):
            def handle(self):
                sock = self.request
                # per-connection: the stream table, and the peer's port,
                # which tells its requests' spans from another connection's
                conn = {"streams": {}, "port": self.client_address[1]}
                threading.current_thread().name = f"sidecar-conn:{conn['port']}"
                with outer._count_lock:
                    outer._conns.add(sock)
                    outer.counters_["connections_accepted"] += 1
                try:
                    self._serve(sock, conn)
                finally:
                    with outer._count_lock:
                        outer._conns.discard(sock)

            def _serve(self, sock, conn):
                while True:
                    try:
                        body = read_frame(sock)
                    except FrameTooLarge as e:
                        # Loud but survivable: the offending request is
                        # unidentifiable (its body was drained, not parsed),
                        # so the error response carries id 0 and the
                        # connection keeps serving.
                        try:
                            write_frame(
                                sock,
                                _encode_response(0, False, f"FrameTooLarge: {e}", b""),
                            )
                            continue
                        except OSError:
                            return
                    except (OSError, ValueError):
                        return
                    if body is None:
                        return
                    try:
                        write_frame(sock, outer._answer(body, conn))
                    except OSError:
                        return

        class Server(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True

        self._server = Server((host, int(port)), Handler)

    def _count(self, **deltas) -> None:
        with self._count_lock:
            for key, d in deltas.items():
                self.counters_[key] += d

    def counters(self) -> dict:
        """What crossed this server's wire since it started, and over how
        many connections."""
        with self._count_lock:
            return {**self.counters_, "connections_open": len(self._conns)}

    def _answer(self, body: bytes, conn: dict) -> bytes:
        """One request frame to the body of its response frame. Faults are
        isolated per request, malformed bodies included. A unary request is
        one `sidecar.request` span; a stream's span opens at its first
        chunk and closes with its answer."""
        n_in = len(body) + _LEN.size
        req_id = 0
        _after_host_pack()
        try:
            fields = proto.decode_fields(body)
            req_id = proto.get_uvarint(fields, 1)
            method = proto.get_string(fields, 2)
            payload = proto.get_bytes(fields, 3)
        except Exception as e:
            resp = _encode_response(req_id, False, f"{type(e).__name__}: {e}", b"")
            self._count(requests=1, bytes_in=n_in, bytes_out=len(resp) + _LEN.size)
            return resp
        if method == "BatchVerifyChunk":
            return self._answer_chunk(req_id, payload, conn, n_in)
        with trace.span("sidecar.request", method=method, req=req_id, conn=conn["port"],
                        bytes_in=n_in) as sp:
            try:
                resp = _encode_response(req_id, True, "", self._dispatch(method, payload, conn, sp))
            except Exception as e:
                resp = _encode_response(req_id, False, f"{type(e).__name__}: {e}", b"")
            sp.set(bytes_out=len(resp) + _LEN.size)
        self._count(requests=1, bytes_in=n_in, bytes_out=len(resp) + _LEN.size)
        return resp

    def _preferred_chunk(self) -> int:
        """Streamed-chunk size advertised in the Ping reply: the kernel's
        bucket-aligned choice when the device tier is loaded (zero padding,
        mesh-width multiple), a flat default otherwise. Never imports jax —
        a host-only server must not pull the device stack for a Ping."""
        import sys

        ek = sys.modules.get("cometbft_tpu.ops.ed25519_kernel")
        if ek is not None:
            try:
                return int(ek.preferred_stream_chunk())
            except Exception:
                pass
        return DEFAULT_STREAM_CHUNK

    def _dispatch(self, method: str, payload: bytes, conn: dict | None = None,
                  sp=trace._OFF) -> bytes:
        if method == "Ping":
            # Capability reply: PingResp { 1: "pong", 2: mesh_width,
            # 3: streaming, 4: chunk }. The width is the REMOTE pod's chip
            # count, so client-side sizing (the engine's default merge
            # cap, chain pricing) sees the serving mesh, not the local
            # host's; field 3 advertises the chunked-streaming method and
            # field 4 the server's preferred chunk size. A client still
            # accepts a bare b"pong" (width 1, streaming off).
            width = 1
            mw = getattr(self.backend, "mesh_width", None)
            if mw is not None:
                try:
                    width = max(1, int(mw()))
                except Exception:
                    width = 1
            return (
                proto.field_bytes(1, b"pong")
                + proto.field_varint(2, width)
                + proto.field_varint(3, 1)
                + proto.field_varint(4, self._preferred_chunk())
            )
        if method == "BatchVerify":
            with trace.span("sidecar.decode") as dec:
                pubs, msgs, sigs, ragged = decode_columns(proto.decode_fields(payload), 1)
                dec.set(ragged=ragged)
            sp.set(lanes=len(pubs))
            self._count(lanes_in=len(pubs))
            if not pubs:
                # The engine short-circuits empty submissions with its
                # own sentinel; keep the backend's empty-batch answer.
                with self._device_lock:
                    ok, bitmap = self.backend.batch_verify(pubs, msgs, sigs)
            else:
                ok, bitmap = self.engine.submit(pubs, msgs, sigs).result()
            with trace.span("sidecar.encode"):
                return _encode_bitmap(ok, bitmap)
        if method == "MerkleRoot":
            fields = proto.decode_fields(payload)
            leaves = proto.get_repeated_bytes(fields, 1)
            with self._device_lock:
                root = self.backend.merkle_root(leaves)
            return proto.field_bytes(1, root)
        if method == "Warmup":
            fields = proto.decode_fields(payload)
            buckets = tuple(proto.get_repeated_uvarint(fields, 1)) or DEFAULT_BUCKETS
            self.warmup(buckets)
            return b""
        raise ValueError(f"unknown method {method!r}")

    def _answer_chunk(self, req_id: int, payload: bytes, conn: dict, n_in: int) -> bytes:
        """One chunk of a streamed BatchVerify (module docstring: ChunkReq)
        to the body of its response frame. A non-final chunk is decoded,
        kept, and acked at once. The final chunk submits the whole stream as
        ONE call, all its lanes in the order sent, and its response is the
        stream's BatchVerifyResp. Any failure tears the stream down and
        surfaces as this chunk's error response — never a partial bitmap."""
        streams = conn["streams"]
        st = None
        final = False
        try:
            began = trace.mark()  # None, and no clock read, with no session
            fields = proto.decode_fields(payload)
            sid = proto.get_uvarint(fields, 1)
            seq = proto.get_uvarint(fields, 2)
            final = proto.get_bool(fields, 3)
            if seq == 0:
                if sid in streams:
                    raise ValueError(f"stream {sid} already open")
                if len(streams) >= 64:  # a leaking client must not hoard triples
                    raise ValueError("too many open streams on this connection")
                # Only the envelope says that a stream starts here: its span
                # opens now and takes the decode's start.
                sp = trace.span("sidecar.request", method="BatchVerifyChunk",
                                conn=conn["port"]).__enter__()
                if began is not None:
                    sp.backdate(began[0])
                streams[sid] = _ServerStream(sp)
            st = streams.get(sid)
            if st is None:
                raise ValueError(f"unknown stream {sid} (chunk seq {seq})")
            st.bytes_in += n_in
            if seq != st.next_seq:
                raise ValueError(f"stream {sid}: chunk seq {seq}, expected {st.next_seq}")
            st.next_seq += 1
            # A column the decoder refuses fails the stream it belongs to.
            pubs, msgs, sigs, ragged = decode_columns(fields, 4)
            if began is not None:
                trace.record("sidecar.decode", parent=st.span, seq=seq, ragged=ragged,
                             **trace.since(began))
            self._count(lanes_in=len(pubs))
            st.pubs += pubs
            st.msgs += msgs
            st.sigs += sigs
            out = b""
            if final:
                n = len(st.pubs)
                ok, bits = self.engine.submit(st.pubs, st.msgs, st.sigs).result() if n else (True, [])
                if len(bits) != n:
                    raise ValueError(f"stream {sid}: answered {len(bits)} of {n} lanes")
                with trace.span("sidecar.encode"):
                    out = _encode_bitmap(ok, bits)
            resp = _encode_response(req_id, True, "", out)
        except Exception as e:
            final = True  # the stream ends here
            resp = _encode_response(req_id, False, f"{type(e).__name__}: {e}", b"")
            if st is not None:
                self._count(streams_failed=1)
        n_out = len(resp) + _LEN.size
        self._count(bytes_in=n_in, bytes_out=n_out, requests=1 if final else 0)
        if st is not None:
            st.bytes_out += n_out
            if final:
                streams.pop(sid, None)
                st.span.set(req=req_id, lanes=len(st.pubs), chunks=st.next_seq,
                            bytes_in=st.bytes_in, bytes_out=st.bytes_out)
                st.span.__exit__(None, None, None)
        return resp

    def warmup(self, buckets=DEFAULT_BUCKETS) -> bool:
        """Precompile what the held device tier would dispatch for batches
        of these sizes, so the first real commit does not pay an XLA
        compile (SURVEY §7 hard part 3, <2 ms budget). Whichever tier that
        is — bare device or hybrid, served bare or under the supervised
        chain — decides the programs; False when the backend has no device
        tier to warm (a host-only server)."""
        tier = _device_tier(self.backend)
        if tier is None:
            return False
        with self._device_lock:
            tier.warmup(buckets)
        return True

    def device_counters(self) -> dict:
        """The counters of the tier that holds the device (what it is, the
        lanes each side of it ran): {} for a host-only server."""
        tier = _device_tier(self.backend)
        return tier.counters() if hasattr(tier, "counters") else {}

    @property
    def bound_addr(self) -> str:
        """host:port actually bound — differs from `addr` when the caller
        asked for port 0 (the fanout shard workers and tests do, to dodge
        port races; they print this so the parent learns the real port)."""
        host, port = self._server.server_address[:2]
        return f"{host}:{port}"

    def serve_forever(self):
        self._serving.set()
        self._server.serve_forever()

    def start(self) -> "SidecarServer":
        t = threading.Thread(target=self.serve_forever, daemon=True)
        t.start()
        return self

    def shutdown(self):
        """Stops listening and closes every open connection: a client then
        sees the server gone, not a listener that went quiet. The backend is
        the caller's to close; the server's own engine goes with it."""
        if self._serving.is_set():
            self._server.shutdown()
        self._server.server_close()
        with self._count_lock:
            conns = list(self._conns)
        for sock in conns:
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
        if self._owns_engine:
            self.engine.close()


# -- client -------------------------------------------------------------------


def _local_port(sock: socket.socket | None) -> int:
    """This end's port of a connection: what the server's spans name it by
    (`conn`); 0 for a socket already closed."""
    try:
        return sock.getsockname()[1]
    except (AttributeError, OSError):
        return 0


class GrpcBackend(VerifyBackend):
    """The `CMTPU_BACKEND=grpc` client: speaks the framed protocol above.
    Thread-safe (one in-flight request per connection, guarded by a lock);
    reconnects once on a broken connection. Fails loudly when the sidecar is
    unreachable — an explicitly configured remote verifier must not silently
    fall back to a different trust path."""

    name = "grpc"

    # Redial backoff bounds: first failure waits _REDIAL_BASE_S, doubling
    # (with jitter inside the doubling) to the _REDIAL_MAX_S cap.
    _REDIAL_BASE_S = 0.05
    _REDIAL_MAX_S = 5.0

    def __init__(
        self,
        addr: str = DEFAULT_ADDR,
        timeout_s: float = 300.0,
        connect_timeout_s: float = 5.0,
    ):
        # timeout_s is the per-REQUEST deadline (slot wait below);
        # connect_timeout_s bounds dial time only. One 300 s knob doing
        # both meant a dead sidecar cost five minutes per connect attempt.
        self.addr = addr
        self.timeout_s = timeout_s
        self.connect_timeout_s = connect_timeout_s
        self._sock: socket.socket | None = None
        self._wlock = threading.Lock()  # serializes frame WRITES only
        self._plock = threading.Lock()  # connection + pending table
        # id -> [Event, body | None, owning socket]: the socket tag lets a
        # dead connection's reader sweep fail ONLY its own waiters.
        self._pending: dict[int, list] = {}
        self._next_id = 0
        # Capped redial-with-backoff (under _plock): a client object used
        # to die for good once the sidecar went away; now each failed dial
        # opens a backoff window in which calls fail FAST, and the next
        # call after the window redials.
        self._redial_failures = 0
        self._redial_not_before = 0.0
        # Remote pod width from the Ping capability reply (1 until probed).
        self._remote_mesh_width = 1
        # Streaming capability: None = never probed, False = legacy server,
        # True = server speaks BatchVerifyChunk. The first large
        # batch_verify self-probes (one Ping on the same connection).
        self._remote_streams: bool | None = None
        # Server-preferred chunk size from the Ping reply (field 4).
        self._remote_chunk = DEFAULT_STREAM_CHUNK
        self._next_stream = 0
        self.counters_ = {
            "unary_calls": 0,
            "streamed_calls": 0,
            "streamed_chunks": 0,
            "stream_retries": 0,
            "bytes_sent": 0,      # frames written, their 4-byte lengths included
            "bytes_received": 0,  # response frames read by a waiter, likewise
            "lanes_sent": 0,      # triples written for verification
            "columns_fixed": 0,   # columns encoded at one stride (three a call or a chunk)
            "columns_ragged": 0,  # columns encoded with a lengths array
        }

    def _connect_locked(self) -> None:
        now = time.monotonic()
        if self._redial_failures and now < self._redial_not_before:
            raise ConnectionError(
                f"sidecar {self.addr} in redial backoff "
                f"({self._redial_failures} consecutive dial failures)"
            )
        host, port = self.addr.rsplit(":", 1)
        try:
            s = socket.create_connection(
                (host, int(port)), timeout=self.connect_timeout_s
            )
        except OSError as e:
            self._redial_failures += 1
            base = min(
                self._REDIAL_BASE_S * 2 ** (self._redial_failures - 1),
                self._REDIAL_MAX_S,
            )
            self._redial_not_before = now + base * random.uniform(0.5, 1.0)
            raise ConnectionError(f"sidecar dial {self.addr}: {e}") from e
        self._redial_failures = 0
        # Blocking mode from here: request deadlines are enforced by the
        # waiter's Event (timeout_s), and a lingering socket timeout would
        # make the reader thread kill an idle-but-healthy connection.
        s.settimeout(None)
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._sock = s
        threading.Thread(
            target=self._reader_loop, args=(s,), daemon=True, name="sidecar-reader"
        ).start()

    def _reader_loop(self, sock: socket.socket) -> None:
        """Demultiplexes responses by request id so callers can PIPELINE:
        many requests may be in flight on the one connection (the server's
        handler advertises pipelining; the old client serialized write+read
        under a single lock — VERDICT r3 weak #8)."""
        while True:
            try:
                body = read_frame(sock)
            except (OSError, FrameTooLarge):
                # An over-cap RESPONSE means client and server disagree on
                # the frame cap; treat the connection as unusable rather
                # than strand its waiters.
                body = None
            if body is None:
                break
            fields = proto.decode_fields(body)
            req_id = proto.get_uvarint(fields, 1)
            with self._plock:
                slot = self._pending.pop(req_id, None)
            if slot is not None:
                slot[1] = body
                slot[0].set()
        # Connection died: fail the waiters that belong to THIS socket so
        # they can retry. A delayed cleanup must not sweep requests already
        # registered on a replacement connection (that race turned one
        # reconnect into a spurious second failure).
        with self._plock:
            if self._sock is sock:
                self._sock = None
            dead = {k: v for k, v in self._pending.items() if v[2] is sock}
            for k in dead:
                del self._pending[k]
        for slot in dead.values():
            slot[0].set()

    def _begin_call(self, method: str, payload: bytes, pin_sock=None, lanes: int = 0):
        """Register a pending slot and write the request frame; returns
        (slot, req_id) for _await_slot. `pin_sock` (streaming) demands the
        frame ride a specific connection: a mid-stream reconnect would
        scatter one stream's chunks across sockets, and the server would
        rightly reject the orphaned tail. `lanes` is what the frame carries
        for verification; the slot's last entry is the bytes written."""
        slot = [threading.Event(), None, None, 0]
        with self._plock:
            if pin_sock is not None and self._sock is not pin_sock:
                err = ConnectionError("sidecar connection lost mid-stream")
                err.sock = pin_sock
                raise err
            if self._sock is None:
                self._connect_locked()
            self._next_id += 1
            req_id = self._next_id
            sock = self._sock
            slot[2] = sock
            self._pending[req_id] = slot
        req = _encode_request(req_id, method, payload)
        try:
            with self._wlock:
                write_frame(sock, req)
        except FrameTooLarge:
            # Not a connection fault: fail fast, no retry, no teardown.
            with self._plock:
                self._pending.pop(req_id, None)
            raise
        except OSError as e:
            with self._plock:
                self._pending.pop(req_id, None)
            err = ConnectionError(str(e))
            err.sock = sock  # which connection failed (see _call)
            raise err from e
        slot[3] = len(req) + _LEN.size
        with self._plock:
            self.counters_["bytes_sent"] += slot[3]
            self.counters_["lanes_sent"] += lanes
        return slot, req_id

    def _await_slot(self, slot, req_id: int, method: str) -> bytes:
        if not slot[0].wait(self.timeout_s):
            with self._plock:
                self._pending.pop(req_id, None)
            raise TimeoutError(f"sidecar {method} timed out")
        if slot[1] is None:
            err = ConnectionError("sidecar connection lost mid-request")
            err.sock = slot[2]
            raise err
        with self._plock:
            self.counters_["bytes_received"] += len(slot[1]) + _LEN.size
        return slot[1]

    def _drop_failed(self, e: ConnectionError) -> None:
        """Tear down only the connection that actually failed: a thread
        handling a stale failure must not close the replacement another
        thread just established."""
        failed = getattr(e, "sock", None)
        with self._plock:
            if self._sock is not None and (failed is None or self._sock is failed):
                try:
                    self._sock.close()
                except OSError:
                    pass
                self._sock = None

    def _call(self, method: str, payload, lanes: int = 0, decode=None):
        """One unary call under its `grpc.call` span, two attempts. `payload`
        is the request's bytes, or a function of the `grpc.encode` span
        that encodes them under it; `decode` turns the response's payload
        into the answer (timed as `grpc.decode` with the envelope's)."""
        with trace.span("grpc.call", method=method, lanes=lanes, chunks=1) as call:
            if callable(payload):
                with trace.span("grpc.encode") as enc:
                    payload = payload(enc)
            sent = 0
            for attempt in (0, 1):
                try:
                    slot, req_id = self._begin_call(method, payload, lanes=lanes)
                    sent += slot[3]
                    with trace.span("grpc.wait"):
                        body = self._await_slot(slot, req_id, method)
                    break
                except ConnectionError as e:
                    self._drop_failed(e)
                    if attempt:
                        raise
            call.set(req=req_id, port=_local_port(slot[2]), bytes_out=sent,
                     bytes_in=len(body) + _LEN.size)
            with trace.span("grpc.decode"):
                out = _decode_response(body)
                return out if decode is None else decode(out)

    def ping(self) -> bool:
        body = self._call("Ping", b"")
        if body == b"pong":  # pre-capability server
            self._remote_streams = False
            return True
        try:
            fields = proto.decode_fields(body)
            if proto.get_bytes(fields, 1) != b"pong":
                return False
            width = proto.get_uvarint(fields, 2)
            if width:
                self._remote_mesh_width = int(width)
            self._remote_streams = bool(proto.get_uvarint(fields, 3))
            chunk = proto.get_uvarint(fields, 4)
            if chunk:
                self._remote_chunk = int(chunk)
            return True
        except Exception:
            return False

    def mesh_width(self) -> int:
        """The serving pod's chip count, learned from the Ping capability
        reply. Never dials: an unpinged client reports 1 and the caller's
        periodic refresh picks the real width up after the first probe."""
        return self._remote_mesh_width

    def chunk_size(self) -> int:
        """Streamed-chunk size: CMTPU_SIDECAR_CHUNK when set, else the
        server's Ping-advertised preference, rounded UP to a multiple of
        the remote pod's width so every chunk fills the serving mesh."""
        env = os.environ.get("CMTPU_SIDECAR_CHUNK", "")
        size = 0
        if env:
            try:
                size = int(env)
            except ValueError:
                size = 0
        if size <= 0:
            size = self._remote_chunk
        w = max(1, self._remote_mesh_width)
        if size % w:
            size += w - size % w
        return max(size, w)

    def batch_verify(self, pubs, msgs, sigs):
        n = len(pubs)
        chunk = self.chunk_size()
        if n > chunk:
            if self._remote_streams is None:
                # Lazy capability probe on the first oversized batch: one
                # Ping on the same connection (errors propagate exactly as
                # the unary call's would).
                self.ping()
            if self._remote_streams:
                return self._batch_verify_streamed(pubs, msgs, sigs, chunk)
        with self._plock:
            self.counters_["unary_calls"] += 1

        return self._call("BatchVerify", lambda enc: self._encode_columns(enc, 1, pubs, msgs, sigs),
                          lanes=n, decode=lambda out: _decode_bitmap(out, n))

    def _encode_columns(self, enc, first: int, pubs, msgs, sigs) -> bytes:
        """`encode_columns` under the `grpc.encode` span `enc`, counted."""
        payload, ragged = encode_columns(first, pubs, msgs, sigs)
        enc.set(ragged=ragged)
        with self._plock:
            self.counters_["columns_fixed"] += 3 - ragged
            self.counters_["columns_ragged"] += ragged
        return payload

    def _batch_verify_streamed(self, pubs, msgs, sigs, chunk: int):
        """Chunked-streaming BatchVerify with the same two-attempt redial
        discipline as _call: a ConnectionError tears down the failed
        socket and the SECOND attempt re-streams from chunk 0 on a fresh
        connection (streams never resume mid-way — the server holds no
        cross-connection state, so a partial bitmap is impossible)."""
        with trace.span("grpc.call", method="BatchVerifyChunk", lanes=len(pubs)) as call:
            tally = {"bytes_out": 0, "bytes_in": 0}
            try:
                for attempt in (0, 1):
                    try:
                        return self._stream_once(pubs, msgs, sigs, chunk, tally)
                    except ConnectionError as e:
                        self._drop_failed(e)
                        with self._plock:
                            self.counters_["stream_retries"] += 1
                        if attempt:
                            raise
            finally:
                call.set(**tally)

    @staticmethod
    def _stream_window() -> int:
        """Unacked-chunk pipeline depth. The server acks a chunk as soon as
        it has decoded it and dispatches once, at the final chunk — a
        deeper client window just keeps frames in the socket on their way
        there, which is what hides a long wire RTT and overlaps the
        server's decode with this side's encode. Floor 2: below that the
        pipeline degenerates into send/ack lockstep and the overlap
        disappears."""
        try:
            return max(2, int(os.environ.get("CMTPU_SIDECAR_WINDOW", "6")))
        except ValueError:
            return 6

    def _stream_once(self, pubs, msgs, sigs, chunk: int, tally: dict):
        """One attempt at a stream, on one connection. `tally` takes what
        the enclosing `grpc.call` span says of it: the bytes of its frames
        both ways, its chunks, `req`, the id of the final chunk's frame, and
        `port`, the connection's local port."""
        n = len(pubs)
        with self._plock:
            self._next_stream += 1
            sid = self._next_stream
        n_chunks = (n + chunk - 1) // chunk
        tally["chunks"] = n_chunks
        window = self._stream_window()
        slots: list[tuple] = []
        pinned = None

        def ack(i: int) -> bytes:
            body = self._await_slot(*slots[i], "BatchVerifyChunk")
            tally["bytes_in"] += len(body) + _LEN.size
            return body

        for seq in range(n_chunks):
            lo, hi = seq * chunk, min((seq + 1) * chunk, n)
            with trace.span("grpc.encode", seq=seq) as enc:
                payload = (
                    proto.field_varint(1, sid, emit_default=True)
                    + proto.field_varint(2, seq, emit_default=True)
                    + proto.field_bool(3, seq == n_chunks - 1)
                    + self._encode_columns(enc, 4, pubs[lo:hi], msgs[lo:hi], sigs[lo:hi])
                )
            # Windowed pipelining: at most `window` unacked chunks in
            # flight — the server is packing/dispatching chunk k while this
            # thread packs and sends later chunks, and the k-th ack gates
            # chunk k+window so a slow server applies backpressure instead
            # of buffering the whole batch in socket memory.
            if seq >= window:
                _decode_response(ack(seq - window))
            slots.append(self._begin_call(
                "BatchVerifyChunk", payload, pin_sock=pinned, lanes=hi - lo
            ))
            tally["bytes_out"] += slots[-1][0][3]
            if pinned is None:
                pinned = slots[0][0][2]
        tally["req"] = slots[-1][1]
        tally["port"] = _local_port(pinned)
        with self._plock:
            self.counters_["streamed_chunks"] += n_chunks
        with trace.span("grpc.wait"):
            for i in range(max(0, n_chunks - window), n_chunks - 1):
                _decode_response(ack(i))
            final = ack(n_chunks - 1)
        with trace.span("grpc.decode"):
            result = _decode_bitmap(_decode_response(final), n)
        with self._plock:
            self.counters_["streamed_calls"] += 1
        return result

    def counters(self) -> dict:
        with self._plock:
            out = dict(self.counters_)
        out["remote_mesh_width"] = self._remote_mesh_width
        out["remote_chunk"] = self._remote_chunk
        out["streaming"] = bool(self._remote_streams)
        return out

    def merkle_root(self, leaves):
        return self._call(
            "MerkleRoot",
            lambda enc: b"".join(
                proto.field_bytes(1, leaf, emit_default=True) for leaf in leaves
            ),
            decode=lambda out: proto.get_bytes(proto.decode_fields(out), 1),
        )

    def warmup(self, buckets=DEFAULT_BUCKETS) -> None:
        self._call(
            "Warmup",
            b"".join(proto.field_varint(1, b, emit_default=True) for b in buckets),
        )

    def close(self) -> None:
        with self._plock:
            if self._sock is not None:
                try:
                    self._sock.close()
                except OSError:
                    pass
                self._sock = None


def open_sidecar(addr: str, backend: VerifyBackend | None = None, say=print) -> SidecarServer:
    """What `python -m cometbft_tpu.sidecar` does once it has its address,
    for every process that is to be a sidecar (the benchmark's is one):
    a server over `backend`, or over what `get_backend()` assembles here,
    bound and not yet serving, with the lines that say which tier and
    device it resolved, so whoever runs it can tell a device server from a
    host one."""
    server = SidecarServer(addr, backend)
    say(f"sidecar: serving on {server.bound_addr} (backend={server.backend.name})")
    say(f"sidecar: backend {json.dumps(server.device_counters(), sort_keys=True)}")
    return server


def close_sidecar(server: SidecarServer, say=print) -> None:
    """Says what the served tier did (lanes on the device and on the host,
    the planner's last routes), what crossed the wire and, over a
    supervised chain, what its supervisor counted; then stops the server."""
    say(f"sidecar: stopping, backend {json.dumps(server.device_counters(), sort_keys=True)}")
    say(f"sidecar: stopping, server {json.dumps(server.counters(), sort_keys=True)}")
    chain = getattr(server.backend, "counters", dict)().get("inner", {})
    if "chain" in chain:
        events = {k: v for k, v in chain.items() if k != "tiers"}
        say(f"sidecar: stopping, supervisor {json.dumps(events, sort_keys=True)}")
    server.shutdown()


def main() -> None:
    """`python -m cometbft_tpu.sidecar`: serve until killed."""

    def on_term(signum, frame):
        raise SystemExit(0)

    def say(line: str) -> None:
        print(line, flush=True)

    # The address is this process's own listener: taken out of the
    # environment, so that the chain assembled here gets no `grpc` tier
    # that dials it.
    addr = os.environ.pop("CMTPU_SIDECAR_ADDR", DEFAULT_ADDR)
    server = open_sidecar(addr, say=say)
    signal.signal(signal.SIGTERM, on_term)
    try:
        if os.environ.get("CMTPU_SIDECAR_WARM", "1") == "1":
            warmed = server.warmup()
            say("sidecar: warmup complete" if warmed else "sidecar: no device tier to warm")
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        close_sidecar(server, say=say)


if __name__ == "__main__":
    main()
