"""The receive path in the ring (ISSUE 34): a message that reaches its EOF
packet on an `MConnection` is one `p2p.recv_msg`, never one a packet, with
the CPU its receive thread used for it; with no session the receive routine
reads no CPU clock; and a connection's three threads are named by their
role and their peer."""

import socket
import threading
import time

import pytest

from cometbft_tpu.libs import trace
from cometbft_tpu.p2p.conn.connection import ChannelDescriptor, MConnection

CHAN = 0x40
PACKETS = 300
BIG = bytes(range(256)) * 4 * (PACKETS - 1) + b"tail"  # 299 full packets and a short one


@pytest.fixture(autouse=True)
def clean():
    trace.clear()
    yield
    trace.clear()


@pytest.fixture(params=["1", "0"], ids=["demux", "inline"])
def pair(request, monkeypatch):
    """A loopback pair: `send(msg)` on one end, `got` fills on the other."""
    monkeypatch.setenv("CMTPU_RECVQ", request.param)
    got, arrived = [], threading.Condition()

    def on_recv(ch, msg):
        with arrived:
            got.append((ch, msg, threading.current_thread().name))
            arrived.notify_all()

    a, b = socket.socketpair()
    descs = [ChannelDescriptor(CHAN, priority=5, send_queue_capacity=64)]
    recv_c = MConnection(b, list(descs), on_recv, lambda e: None, name="0a1b2c3d4e")
    send_c = MConnection(a, list(descs), lambda *x: None, lambda e: None)
    recv_c.start()
    send_c.start()

    def wait_for(n):
        with arrived:
            assert arrived.wait_for(lambda: len(got) >= n, timeout=30), len(got)

    try:
        yield send_c, got, wait_for
    finally:
        send_c.stop()
        recv_c.stop()
        a.close()
        b.close()


def _recv_records():
    return [s for s in trace.spans() if s["name"] == "p2p.recv_msg"]


def test_a_message_is_one_record_whatever_its_packets(pair):
    send_c, got, wait_for = pair
    with trace.capture():
        for msg in (b"status", BIG, b"after"):
            assert send_c.send(CHAN, msg)
        wait_for(3)
        deadline = time.monotonic() + 10
        while len(_recv_records()) < 3 and time.monotonic() < deadline:
            time.sleep(0.01)  # inline delivery happens before the record
    assert [m for _, m, _ in got] == [b"status", BIG, b"after"]
    first, big, after = _recv_records()
    assert [r["attrs"] for r in (first, big, after)] == [
        {"chan": CHAN, "bytes": 6, "packets": 1},
        {"chan": CHAN, "bytes": len(BIG), "packets": PACKETS},
        {"chan": CHAN, "bytes": 5, "packets": 1},
    ]
    assert {r["thread"] for r in (first, big, after)} == {"p2p-recv:0a1b2c3d4e"}
    assert all(r["parent"] is None and r["root"] == r["id"] for r in (first, big, after))
    # the first of a session has no mark to count from; the others say what ran
    assert first["cpu"] is None and first["pcpu"] is None
    for r in (big, after):
        assert 0.0 < r["cpu"] <= r["pcpu"] + 1e-4
    assert big["t0"] < big["t1"] and big["cpu"] > after["cpu"], "300 packets cost more than one"
    assert first["t1"] <= big["t0"] and big["t1"] <= after["t1"]


def test_off_the_receive_path_reads_no_cpu_clock_and_records_nothing(pair, monkeypatch):
    send_c, got, wait_for = pair
    reads = []
    monkeypatch.setattr(time, "thread_time", lambda: reads.append("thread") or 0.0)
    monkeypatch.setattr(time, "process_time", lambda: reads.append("process") or 0.0)
    assert send_c.send(CHAN, BIG) and send_c.send(CHAN, b"after")
    wait_for(2)
    assert reads == [] and trace.spans() == []


def test_a_connections_threads_are_named_by_role_and_peer(pair):
    send_c, got, wait_for = pair
    assert send_c.send(CHAN, b"hello")
    wait_for(1)
    names = {t.name for t in threading.enumerate()}
    assert {"p2p-recv:0a1b2c3d4e", "p2p-send:0a1b2c3d4e", "p2p-recv", "p2p-send"} <= names
    delivered_on = got[0][2]
    if "p2p-drain:0a1b2c3d4e" in names:  # the demux is on: its drain thread delivers
        assert delivered_on == "p2p-drain:0a1b2c3d4e" and "p2p-drain" in names
    else:
        assert delivered_on == "p2p-recv:0a1b2c3d4e"
    assert {trace.role(n) for n in names if n.startswith("p2p-")} <= set(trace.ROLES)
