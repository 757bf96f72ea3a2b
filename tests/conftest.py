"""Test harness configuration.

Force JAX onto the CPU backend with 8 virtual devices BEFORE jax import, so
multi-chip sharding (jax.sharding.Mesh over 8 devices) is exercised without
TPU hardware — the strategy the driver's dryrun_multichip also uses. The
tests never take the chip: on its machine they would hold it against
whatever else runs there, so JAX_PLATFORMS is OVERWRITTEN, not defaulted.

The persistent XLA compile cache (the sharded programs cost tens of
seconds each on XLA:CPU) is set by importing cometbft_tpu.ops.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long multi-process e2e runs, excluded from the tier-1 "
        "`-m 'not slow'` sweep",
    )
    config.addinivalue_line(
        "markers",
        "chaos: deterministic fault-injection tests (seeded CMTPU_FAULTS, "
        "CPU-only) for the verification-backend supervisor; runs in tier-1",
    )
    config.addinivalue_line(
        "markers",
        "liveness: fast consensus-liveness tests (round-catchup gossip, "
        "stall watchdog, restart-under-load with sub-second timeouts); "
        "runs in tier-1 — `-m liveness` selects just this group",
    )
    config.addinivalue_line(
        "markers",
        "ingress: QoS tx-ingress tests (envelope preverify, priority "
        "lanes/WFQ, token buckets, load shedding); fast unit/property "
        "tests run in tier-1, flood-scale runs carry `slow` too — "
        "`-m ingress` selects just this group",
    )
    config.addinivalue_line(
        "markers",
        "hotpath: consensus hot-path tests (micro-batched vote admission, "
        "WAL group commit, blocksync verify/apply pipeline); runs in "
        "tier-1 — `-m hotpath` selects just this group",
    )
    config.addinivalue_line(
        "markers",
        "lightgw: light-client gateway tests (MMR accumulator vs "
        "RFC-6962, gateway-vs-local bit-identity, poisoned-proof "
        "fallback, plan-sharing concurrency); runs in tier-1 — "
        "`-m lightgw` selects just this group",
    )
    config.addinivalue_line(
        "markers",
        "mesh: pod-scale sharding tests (mesh-aware bucket ladder, "
        "sharded-vs-single bitmap bit-identity, planner mesh pricing, "
        "pod-width coalescer cap, dryrun_multichip) on the 8-device "
        "virtual mesh; runs in tier-1 — `-m mesh` selects just this group",
    )
    config.addinivalue_line(
        "markers",
        "sidecar: verification-sidecar tests (framed protocol, chunked "
        "streaming, frame-size guard, cross-connection coalescing, "
        "mid-stream redial); runs in tier-1 — `-m sidecar` selects just "
        "this group",
    )
    config.addinivalue_line(
        "markers",
        "simnet: deterministic virtual-clock network tests (SimClock "
        "ordering, SimTransport link model/partitions, 50-node scenario "
        "determinism, sim e2e manifests); fast paths run in tier-1, the "
        "100-node acceptance scenario carries `slow` too — `-m simnet` "
        "selects just this group",
    )
    config.addinivalue_line(
        "markers",
        "engine: continuous-batching verification-engine tests (priority "
        "classes, starvation escape, deadline-aware dispatch sizing, "
        "mixed-load starvation-freedom property, scheduler-shim compat); "
        "runs in tier-1 — `-m engine` selects just this group",
    )
    config.addinivalue_line(
        "markers",
        "agg: aggregate BLS commit tests (BN254 aggregate wire form, "
        "three-mode verify bit-parity, poisoned-aggregate rejection, "
        "device multi-pairing kernel); fast paths run in tier-1, the "
        "kernel-compile test carries `slow` too — `-m agg` selects "
        "just this group",
    )
    config.addinivalue_line(
        "markers",
        "fanout: multi-host fan-out tests (weighted slicing/reassembly, "
        "per-shard failure redistribution, width-sum supervisor/engine "
        "scaling, real shard-server processes); fast paths run in tier-1, "
        "the multi-process mesh-shard rig carries `slow` too — "
        "`-m fanout` selects just this group",
    )
    config.addinivalue_line(
        "markers",
        "recvq: recv-path QoS tests (prioritized per-channel demux DRR "
        "drain order, shed/backpressure overflow policy, starvation "
        "promotion, bit-identical delivery demux on vs off, "
        "unknown-channel peer teardown, recv flow accounting); runs in "
        "tier-1 — `-m recvq` selects just this group",
    )
    config.addinivalue_line(
        "markers",
        "bundle: checkpoint-bundle tests (wire round-trip + content "
        "addressing, tamper-matrix refusal with fallback, client cold "
        "sync off origin/dir/peer sources, persisted-MMR restart-resume, "
        "same-chain export determinism); runs in tier-1 — `-m bundle` "
        "selects just this group",
    )


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _forget_resident_columns():
    """The device tier remembers the key columns it has seen for the life
    of the process (ops/ed25519_kernel `_columns`): a test's second call
    over the keys of an earlier test would ride that test's tables. Every
    test starts with none, where the kernel is loaded at all."""
    ek = sys.modules.get("cometbft_tpu.ops.ed25519_kernel")
    if ek is not None:
        ek._columns.clear()
    yield
