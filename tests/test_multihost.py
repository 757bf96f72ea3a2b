"""Multi-HOST sharded verification (ops/multihost.py, SURVEY §5.8): two
real OS processes, each a JAX process with 4 virtual CPU devices, form one
8-device global mesh over the gloo coordinator and run ONE sharded
commit-verification step — each host feeding only its lane slice. Both
hosts must read the identical replicated root (matching the host-crypto
tree) and all-valid bit; each sees only its half of the bitmap."""

import json
import os
import socket
import subprocess
import sys

import pytest


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_mesh_agrees_on_root_and_verdict():
    port = _free_port()
    here = os.path.dirname(os.path.abspath(__file__))
    worker = os.path.join(here, "multihost_worker.py")
    env = {
        k: v
        for k, v in os.environ.items()
        if k != "XLA_FLAGS"
    }
    procs = [
        subprocess.Popen(
            [sys.executable, worker, str(pid), "2", str(port)],
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            env=env,
        )
        for pid in (0, 1)
    ]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=560)
            assert p.returncode == 0, err.decode(errors="replace")[-3000:]
            outs.append(json.loads(out.decode().strip().splitlines()[-1]))
    finally:
        # One worker crashing leaves its peer blocked in the gloo
        # rendezvous; never leak it past the test.
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()

    from cometbft_tpu.crypto.merkle import hash_from_byte_slices
    from cometbft_tpu.ops.sharded import example_txs

    want_root = hash_from_byte_slices(example_txs(64)).hex()
    for rec in outs:
        assert rec["processes"] == 2 and rec["global_devices"] == 8
        assert rec["all_valid"] is True
        assert rec["ok_len"] == 16 and rec["ok_count"] == 16
        assert rec["root"] == want_root, rec
    assert outs[0]["root"] == outs[1]["root"]
