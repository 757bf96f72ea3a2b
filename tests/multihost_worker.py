"""Worker process for tests/test_multihost.py and test_fanout.py: one JAX
process of a multi-host verification cluster (ops/multihost.py).

Default mode runs one multihost_commit_step and prints one JSON line with
this host's view of the step so the test can assert cross-host agreement.

`serve` mode (round 15) turns the whole multi-process mesh into ONE
fanout shard: the leader (pid 0) accepts its followers on a side port,
serves a MultihostShardBackend through a real SidecarServer (port 0,
bound address printed as JSON), and re-broadcasts every client batch so
all processes verify it collectively; followers mirror the broadcasts in
follow_verify_loop. The leader exits when its stdin closes (the test's
shutdown handle); followers exit on the leader's shutdown sentinel."""

import json
import os
import socket
import sys

pid = int(sys.argv[1])
nproc = int(sys.argv[2])
port = sys.argv[3]
mode = sys.argv[4] if len(sys.argv) > 4 else "step"
side_port = int(sys.argv[5]) if len(sys.argv) > 5 else 0

# Serve-mode leader: bind the follower rendezvous BEFORE the (slow) jax
# import + gloo init and report the real port at once — a pre-picked free
# port would sit unbound for a minute and lose races to other tests.
_side_listener = None
if mode == "serve" and pid == 0:
    _side_listener = socket.socket()
    _side_listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    _side_listener.bind(("127.0.0.1", side_port))
    _side_listener.listen(nproc - 1)
    print(
        json.dumps({"pid": 0, "side_port": _side_listener.getsockname()[1]}),
        flush=True,
    )

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cometbft_tpu.ops import multihost  # noqa: E402

multihost.distributed_init(f"127.0.0.1:{port}", nproc, pid)

import jax  # noqa: E402

from cometbft_tpu.ops import sharded  # noqa: E402

from cometbft_tpu.ops import ed25519_kernel as ek  # noqa: E402
from cometbft_tpu.ops import sha256_kernel as sha  # noqa: E402
from cometbft_tpu.crypto import ed25519 as host_ed  # noqa: E402

mesh = sharded.make_mesh()  # global: nproc * 4 virtual devices


def run_step() -> None:
    # Deterministic global fixture; every host derives it, then contributes
    # ONLY its lane slice (packing is columnar, so slicing == per-host
    # packing).
    N = 32
    pubs, msgs, sigs = [], [], []
    for i in range(N):
        pv = host_ed.gen_priv_key_from_secret(b"mh-%d" % i)
        pubs.append(pv.pub_key().bytes())
        msgs.append(b"commit-vote-%d" % i)
        sigs.append(pv.sign(msgs[-1]))
    operands, host_ok = ek.pack_batch(pubs, msgs, sigs)
    assert all(host_ok[:N]) and operands[0].shape[1] == N

    leaves = sharded.make_example_leaves(64)  # uint32[8, 64], deterministic

    share = N // nproc
    lshare = leaves.shape[1] // nproc
    lo, hi = pid * share, (pid + 1) * share
    local_ops = []
    for op, spec in zip(operands, sharded._verify_specs("sig")):
        dim = list(spec).index("sig")
        local_ops.append(op[:, lo:hi] if dim == 1 else op[lo:hi])
    local_leaves = leaves[:, pid * lshare : (pid + 1) * lshare]

    ok_local, all_valid, root = multihost.multihost_commit_step(
        mesh, tuple(local_ops), local_leaves
    )
    root_hex = sha.digest_words_to_bytes(root)[0].hex()
    print(
        json.dumps(
            {
                "pid": pid,
                "processes": jax.process_count(),
                "global_devices": len(jax.devices()),
                "ok_count": int(ok_local.sum()),
                "ok_len": int(len(ok_local)),
                "all_valid": all_valid,
                "root": root_hex,
            }
        ),
        flush=True,
    )


def run_serve() -> None:
    if pid == 0:
        listener = _side_listener  # bound (and announced) before jax init
        followers = [listener.accept()[0] for _ in range(nproc - 1)]
        listener.close()

        from cometbft_tpu.sidecar.service import SidecarServer

        backend = multihost.MultihostShardBackend(mesh, followers)
        server = SidecarServer("127.0.0.1:0", backend=backend).start()
        print(
            json.dumps(
                {
                    "pid": 0,
                    "addr": server.bound_addr,
                    "width": backend.mesh_width(),
                }
            ),
            flush=True,
        )
        sys.stdin.read()  # serve until the parent closes our stdin
        server.shutdown()
        backend.close()
    else:
        side = socket.create_connection(("127.0.0.1", side_port), timeout=120)
        served = multihost.follow_verify_loop(mesh, side)
        print(json.dumps({"pid": pid, "served": served}), flush=True)


if mode == "serve":
    run_serve()
else:
    run_step()
