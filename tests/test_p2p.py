"""P2P stack over real TCP sockets: SecretConnection handshake, NodeInfo
exchange, MConnection multiplexing, Switch routing, peer failure."""

import socket
import threading
import time

import pytest

from cometbft_tpu.crypto import ed25519
from cometbft_tpu.p2p.conn.connection import ChannelDescriptor
from cometbft_tpu.p2p.conn.secret_connection import SecretConnection
from cometbft_tpu.p2p.key import NodeKey
from cometbft_tpu.p2p.node_info import NodeInfo
from cometbft_tpu.p2p.reactor import Reactor
from cometbft_tpu.p2p.switch import Switch
from cometbft_tpu.p2p.transport import MultiplexTransport


def test_secret_connection_roundtrip():
    a, b = socket.socketpair()
    k1, k2 = ed25519.gen_priv_key(), ed25519.gen_priv_key()
    out = {}

    def server():
        sc = SecretConnection(b, k2)
        out["server"] = sc
        got = sc.read_exact(11)
        sc.write(b"pong:" + got)

    t = threading.Thread(target=server, daemon=True)
    t.start()
    sc1 = SecretConnection(a, k1)
    sc1.write(b"hello world")
    resp = sc1.read_exact(16)
    assert resp == b"pong:hello world"
    t.join(timeout=5)
    # Mutual authentication: each side learned the other's real pubkey.
    assert sc1.rem_pub_key.bytes() == k2.pub_key().bytes()
    assert out["server"].rem_pub_key.bytes() == k1.pub_key().bytes()
    # Large transfer crosses frame boundaries.
    big = bytes(range(256)) * 20  # 5120 bytes > 5 frames
    sc1.write(big)
    got = out["server"].read_exact(len(big))
    assert got == big


class _CountingSocket:
    """A socket that counts the reads asked of it."""

    def __init__(self, sock):
        self._sock = sock
        self.reads = 0

    def __getattr__(self, name):
        return getattr(self._sock, name)

    def recv(self, n):
        self.reads += 1
        return self._sock.recv(n)


@pytest.mark.parametrize("size", [1, 1024, 1025, 5000, 311_317])
def test_secret_connection_reads_the_socket_in_large_pieces(size):
    """A burst of 1 KiB frames is taken off the socket in a few reads, not
    one a frame, and every byte comes out once and in order."""
    a, b = socket.socketpair()
    b = _CountingSocket(b)
    k1, k2 = ed25519.gen_priv_key(), ed25519.gen_priv_key()
    out = {}
    t = threading.Thread(target=lambda: out.update(server=SecretConnection(b, k2)), daemon=True)
    t.start()
    sc1 = SecretConnection(a, k1)
    t.join(timeout=5)
    body = bytes(i * 7 % 253 for i in range(size))
    writer = threading.Thread(target=lambda: (sc1.write(body), sc1.write(b"tail")), daemon=True)
    writer.start()
    time.sleep(0.2)  # the burst is in the socket's buffer before the first read
    before = b.reads
    got = out["server"].read_exact(size)
    assert got == body and out["server"].read_exact(4) == b"tail"
    writer.join(timeout=5)
    frames = -(-size // 1024) + 1
    assert b.reads - before <= max(2, frames // 8), (b.reads - before, frames)
    a.close()
    b.close()


def test_secret_connection_rejects_tampered_ciphertext():
    """AEAD integrity: flipping any ciphertext bit on the wire must surface
    as a clean connection error on the reader — never plaintext corruption,
    never a hang (test/fuzz p2p/secretconnection analog)."""
    import random

    rng = random.Random(9)
    for trial in range(6):
        a, mitm_a = socket.socketpair()
        mitm_b, b = socket.socketpair()
        k1, k2 = ed25519.gen_priv_key(), ed25519.gen_priv_key()
        stop = threading.Event()

        def relay(src, dst, corrupt_after):
            """Forward bytes, flipping one bit in one byte past the
            handshake (the handshake itself must stay intact)."""
            forwarded = 0
            corrupted = False
            try:
                while not stop.is_set():
                    chunk = bytearray(src.recv(4096))
                    if not chunk:
                        break
                    if not corrupted and forwarded + len(chunk) > corrupt_after:
                        i = rng.randrange(len(chunk))
                        chunk[i] ^= 1 << rng.randrange(8)
                        corrupted = True
                    forwarded += len(chunk)
                    dst.sendall(bytes(chunk))
            except OSError:
                pass

        # handshake is ~100s of bytes each way; corrupt only after 700.
        threading.Thread(target=relay, args=(mitm_a, mitm_b, 700), daemon=True).start()
        threading.Thread(target=relay, args=(mitm_b, mitm_a, 10**9), daemon=True).start()

        result = {}

        def server():
            try:
                sc = SecretConnection(b, k2)
                result["got"] = sc.read_exact(4096)
            except Exception as e:
                result["err"] = e

        t = threading.Thread(target=server, daemon=True)
        t.start()
        try:
            sc1 = SecretConnection(a, k1)
            payload = bytes(rng.getrandbits(8) for _ in range(4096))
            sc1.write(payload)
        except Exception:
            pass  # tamper may already break the sender side
        t.join(timeout=10)
        stop.set()
        for s in (a, b, mitm_a, mitm_b):
            try:
                s.close()
            except OSError:
                pass
        assert not t.is_alive(), "reader hung on tampered ciphertext"
        if "got" in result:
            assert result["got"] == payload, "tampered frame yielded corrupted plaintext"
        else:
            assert "err" in result  # clean rejection


class EchoReactor(Reactor):
    def __init__(self, chan_id):
        super().__init__("echo")
        self.chan = chan_id
        self.received = []
        self.peers = []
        self.event = threading.Event()

    def get_channels(self):
        return [ChannelDescriptor(self.chan, priority=5)]

    def add_peer(self, peer):
        self.peers.append(peer)

    def receive(self, chan_id, peer, msg):
        self.received.append((peer.id, msg))
        self.event.set()


def _make_switch(name, network="p2p-test"):
    nk = NodeKey()
    ni = NodeInfo(node_id=nk.id, network=network, moniker=name)
    sw = Switch(ni, MultiplexTransport(ni, nk))
    return sw, nk


def test_switch_two_nodes():
    sw1, _ = _make_switch("n1")
    sw2, nk2 = _make_switch("n2")
    r1, r2 = EchoReactor(0x77), EchoReactor(0x77)
    sw1.add_reactor("echo", r1)
    sw2.add_reactor("echo", r2)
    addr2 = sw2.start("127.0.0.1:0")
    sw1.start("")
    try:
        peer = sw1.dial_peer(f"{nk2.id}@{addr2}")
        assert peer is not None and peer.id == nk2.id
        # Wait for the inbound side to register.
        for _ in range(100):
            if sw2.num_peers() == 1:
                break
            time.sleep(0.05)
        assert sw2.num_peers() == 1
        # Routed message over the multiplexed secret channel.
        assert peer.send(0x77, b"gossip-1")
        assert r2.event.wait(5), "message not received"
        assert r2.received[0][1] == b"gossip-1"
        # Broadcast path from node 2 back to node 1.
        sw2.broadcast(0x77, b"reply-broadcast")
        assert r1.event.wait(5)
        assert r1.received[0][1] == b"reply-broadcast"
    finally:
        sw1.stop()
        sw2.stop()


@pytest.mark.parametrize("size", [0, 1, 1023, 1024, 1025, 65536, 311_317])
def test_a_message_of_any_size_crosses_the_connection_whole(size):
    """One packet, a packet boundary, and a loaded block's 300 packets: the
    sender's shrinking view and the receiver's growing buffer lose nothing,
    and the next message starts clean."""
    sw1, _ = _make_switch("n1")
    sw2, nk2 = _make_switch("n2")
    r1, r2 = EchoReactor(0x77), EchoReactor(0x77)
    r2.get_channels = lambda: [ChannelDescriptor(0x77, priority=5, recv_message_capacity=1 << 20)]
    sw1.add_reactor("echo", r1)
    sw2.add_reactor("echo", r2)
    addr2 = sw2.start("127.0.0.1:0")
    sw1.start("")
    body = bytes(i * 31 % 251 for i in range(size))
    try:
        peer = sw1.dial_peer(f"{nk2.id}@{addr2}")
        assert peer is not None
        assert peer.send(0x77, body) and peer.send(0x77, b"after")
        deadline = time.time() + 10
        while time.time() < deadline and len(r2.received) < 2:
            time.sleep(0.01)
        assert [m for _, m in r2.received] == [body, b"after"]
    finally:
        sw1.stop()
        sw2.stop()


def test_network_mismatch_rejected():
    sw1, _ = _make_switch("n1", network="chain-A")
    sw2, nk2 = _make_switch("n2", network="chain-B")
    r1, r2 = EchoReactor(0x77), EchoReactor(0x77)
    sw1.add_reactor("echo", r1)
    sw2.add_reactor("echo", r2)
    addr2 = sw2.start("127.0.0.1:0")
    sw1.start("")
    try:
        with pytest.raises(Exception, match="different network"):
            sw1.dial_peer(f"{nk2.id}@{addr2}")
        assert sw1.num_peers() == 0
    finally:
        sw1.stop()
        sw2.stop()


def test_fuzzed_delay_connection_still_delivers():
    """p2p/fuzz.go delay mode: IO is jittered but messages arrive; switches
    built with a FuzzConnConfig transport stay functional."""
    from cometbft_tpu.p2p.fuzz import FuzzConnConfig
    from cometbft_tpu.p2p.conn.connection import ChannelDescriptor
    from cometbft_tpu.p2p.key import NodeKey
    from cometbft_tpu.p2p.node_info import NodeInfo
    from cometbft_tpu.p2p.reactor import Reactor
    from cometbft_tpu.p2p.switch import Switch
    from cometbft_tpu.p2p.transport import MultiplexTransport
    import threading as _threading
    import time as _time

    got = _threading.Event()

    class Echo(Reactor):
        def __init__(self, name):
            super().__init__(name)

        def get_channels(self):
            return [ChannelDescriptor(0x77, priority=1, send_queue_capacity=10)]

        def receive(self, chan_id, peer, msg_bytes):
            if msg_bytes == b"fuzzy":
                got.set()

    fuzz = FuzzConnConfig(mode="delay", max_delay=0.02, seed=7)
    sws = []
    for i in range(2):
        nk = NodeKey()
        ni = NodeInfo(node_id=nk.id, network="fuzz-chain", moniker=f"f{i}")
        sw = Switch(ni, MultiplexTransport(ni, nk, fuzz))
        sw.add_reactor("ECHO", Echo("ECHO"))
        sws.append((sw, nk))
    try:
        addr = sws[0][0].start("127.0.0.1:0")
        sws[1][0].start("127.0.0.1:0")
        peer = sws[1][0].dial_peer(f"{sws[0][1].id}@{addr}")
        assert peer is not None
        for _ in range(50):
            peer.try_send(0x77, b"fuzzy")
            if got.wait(0.1):
                break
        assert got.is_set(), "delayed link must still deliver"
    finally:
        for sw, _ in sws:
            sw.stop()


def test_fuzzed_drop_connection_reconnects():
    """p2p/fuzz.go drop mode: swallowed writes corrupt the framed stream,
    peers disconnect, and the persistent-peer redial machinery restores the
    connection — the churn loop the fuzzer exists to exercise."""
    import time as _time

    from cometbft_tpu.p2p.conn.connection import ChannelDescriptor
    from cometbft_tpu.p2p.fuzz import FuzzConnConfig
    from cometbft_tpu.p2p.key import NodeKey
    from cometbft_tpu.p2p.node_info import NodeInfo
    from cometbft_tpu.p2p.reactor import Reactor
    from cometbft_tpu.p2p.switch import Switch
    from cometbft_tpu.p2p.transport import MultiplexTransport

    class Chat(Reactor):
        def __init__(self):
            super().__init__("CHAT")
            self.got = 0

        def get_channels(self):
            return [ChannelDescriptor(0x78, priority=1, send_queue_capacity=10)]

        def receive(self, chan_id, peer, msg_bytes):
            self.got += 1

    # Only node A fuzzes; dropped WRITES are clean message drops in this
    # layering (whole sealed frames vanish pre-nonce), so connection churn
    # comes from prob_drop_conn, which hard-closes the socket.
    fuzz = FuzzConnConfig(mode="drop", prob_drop_rw=0.1, prob_drop_conn=0.1, seed=3)
    nk_a, nk_b = NodeKey(), NodeKey()
    ni_a = NodeInfo(node_id=nk_a.id, network="fuzz2", moniker="a")
    ni_b = NodeInfo(node_id=nk_b.id, network="fuzz2", moniker="b")
    sw_a = Switch(ni_a, MultiplexTransport(ni_a, nk_a, fuzz))
    sw_b = Switch(ni_b, MultiplexTransport(ni_b, nk_b))
    chat_a, chat_b = Chat(), Chat()
    sw_a.add_reactor("CHAT", chat_a)
    sw_b.add_reactor("CHAT", chat_b)
    try:
        addr_b = sw_b.start("127.0.0.1:0")
        sw_a.start("127.0.0.1:0")
        sw_a.add_persistent_peers([f"{nk_b.id}@{addr_b}"])
        sw_a.dial_persistent_peers()
        drops = reconnects = 0
        connected_before = False
        deadline = _time.time() + 30
        while _time.time() < deadline and reconnects < 2:
            connected = sw_a.get_peer(nk_b.id) is not None
            if connected:
                p = sw_a.get_peer(nk_b.id)
                if p:
                    p.try_send(0x78, b"chatter")
                if not connected_before:
                    if drops > 0:
                        reconnects += 1
                    connected_before = True
            elif connected_before:
                drops += 1
                connected_before = False
            _time.sleep(0.02)
        assert drops >= 1, "drop-mode fuzzing never broke the connection"
        assert reconnects >= 1, "persistent redial never restored the peer"
    finally:
        sw_a.stop()
        sw_b.stop()


def test_redial_delay_two_phase():
    """Healed partitions must reconnect in seconds: linear phase stays ~1 s
    for 20 attempts, then doubles to a 60 s cap (switch.go reconnectToPeer
    shape); jitter stays within +/-20%."""
    from cometbft_tpu.p2p.switch import redial_delay

    for attempt in range(1, 21):
        assert 0.8 <= redial_delay(attempt) <= 1.2
    assert 1.6 <= redial_delay(21) <= 2.4
    assert 3.2 <= redial_delay(22) <= 4.8
    for attempt in (26, 30, 100, 5000):
        # 5000: a peer down for days must neither overflow float in the
        # exponent nor kill the redial thread
        assert redial_delay(attempt) <= 60.0 * 1.2
    assert redial_delay(40) >= 60.0 * 0.8


def test_stale_peer_error_does_not_evict_replacement():
    """The partition-heal wedge (round 5): a dead connection errors from
    both its send and recv routines; if a replacement peer (same id) is
    already live when the late error fires, stop_peer_for_error must stop
    only the stale instance — evicting the replacement by id killed its
    gossip state and left a ghost conn the remote kept treating as live."""

    class Recorder(EchoReactor):
        def __init__(self, chan):
            super().__init__(chan)
            self.removed = []

        def remove_peer(self, peer, reason):
            self.removed.append(peer)

    sw1, _ = _make_switch("n1")
    sw2, nk2 = _make_switch("n2")
    r1 = Recorder(0x77)
    r2 = EchoReactor(0x77)
    sw1.add_reactor("echo", r1)
    sw2.add_reactor("echo", r2)
    addr2 = sw2.start("127.0.0.1:0")
    sw1.start("")
    try:
        old = sw1.dial_peer(f"{nk2.id}@{addr2}")
        assert old is not None
        # Simulate the reconnect completing before the old conn's second
        # error routine fires: remove old from the table the normal way,
        # then dial a fresh instance under the same id. sw2 must have
        # noticed the old conn's death first, or it will reject the redial
        # as a duplicate id.
        sw1.stop_peer_for_error(old, "first error (recv routine)")
        assert sw1.get_peer(nk2.id) is None
        assert r1.removed == [old]
        for _ in range(100):
            if sw2.num_peers() == 0:
                break
            time.sleep(0.05)
        assert sw2.num_peers() == 0
        replacement = sw1.dial_peer(f"{nk2.id}@{addr2}")
        assert replacement is not None and replacement is not old
        # The stale instance's OTHER error routine fires late.
        sw1.stop_peer_for_error(old, "second error (send routine)")
        # The replacement must still own the table entry, its reactor
        # state must be untouched, and its transport must actually deliver.
        assert sw1.get_peer(nk2.id) is replacement
        assert r1.removed == [old]
        assert replacement.send(0x77, b"still-alive")
        assert r2.event.wait(5), "replacement connection did not deliver"
        assert r2.received[-1][1] == b"still-alive"
    finally:
        sw1.stop()
        sw2.stop()
