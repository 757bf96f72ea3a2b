"""One fanout shard as a real OS process: a SidecarServer on port 0 over
the host CPU backend, advertising an argv-chosen mesh width through the
Ping capability reply.  Prints the bound address as one JSON line, then
serves until stdin closes (the parent test's shutdown handle).

Used by tests/test_fanout.py's 3-process integration test — each process
is one member of the fleet, so the FanoutBackend client exercises the
real chunk-stream wire path and the width-weighted split across genuinely
concurrent servers."""

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"  # a test worker never takes the chip

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cometbft_tpu.sidecar.backend import CpuBackend  # noqa: E402
from cometbft_tpu.sidecar.service import SidecarServer  # noqa: E402

width = int(sys.argv[1]) if len(sys.argv) > 1 else 1


class _WideCpu(CpuBackend):
    """Host verification with a pretend chip count, so the parent can
    assert the width-weighted split without real accelerators."""

    def mesh_width(self) -> int:
        return width


server = SidecarServer("127.0.0.1:0", backend=_WideCpu()).start()
print(json.dumps({"addr": server.bound_addr, "width": width}), flush=True)
sys.stdin.read()
server.shutdown()
