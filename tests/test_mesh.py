"""Pod-scale mesh verification (ops/ed25519_kernel + ops/merkle_kernel +
the supervisor chain above them): the mesh-aware bucket ladder, routing of
every standard bucket to the sharded program on the 8-device conftest mesh,
sharded-vs-single-device bitmap bit-identity (including bad-sig lanes and
padded tail lanes), the subtree-parallel Merkle route, mesh observability
gauges, dryrun_multichip, and chaos degradation of a wedged mesh tier
through the supervised chain.  CPU-only on the virtual 8-device mesh."""

from __future__ import annotations

import time

import numpy as np
import pytest

import jax

from cometbft_tpu.crypto import ed25519
from cometbft_tpu.crypto.merkle import hash_from_byte_slices
from cometbft_tpu.ops import ed25519_kernel as ek
from cometbft_tpu.ops import merkle_kernel as mk

pytestmark = pytest.mark.mesh


def _signed(n, tag=b"mesh"):
    pvs = [ed25519.gen_priv_key_from_secret(tag + b"-%d" % i) for i in range(n)]
    pubs = [pv.pub_key().bytes() for pv in pvs]
    msgs = [b"msg-%d" % i for i in range(n)]
    sigs = [pv.sign(m) for pv, m in zip(pvs, msgs)]
    return pubs, msgs, sigs


# -- mesh-aware bucket ladder ------------------------------------------------


def test_width_probe_sees_the_conftest_mesh():
    assert ek.mesh_width() == 8
    assert ek.known_mesh_width() == 8  # passive readers see the probe
    assert ek.mesh_floor() == 8  # floor defaults to the mesh width


def test_standard_ladder_unchanged_on_pow2_mesh():
    """Every standard bucket already divides the 8-wide mesh, so rounding
    is a no-op there: the compiled-program set is identical to the
    single-chip ladder (no surprise recompiles on pod deployments)."""
    for b in ek.BUCKETS:
        assert b % 8 == 0
        assert ek.bucket_for(b) == b
    assert ek.bucket_for(48) == 128
    assert ek.bucket_for(6) == 8


def test_bucket_ladder_rounds_to_non_pow2_width(monkeypatch):
    """A width that does NOT divide the standard buckets (5 chips) pads the
    bucket up to the next multiple so shard_map's lane split is exact."""
    monkeypatch.setattr(ek, "mesh_width", lambda: 5)
    assert ek.bucket_for(6) == 10  # base bucket 8 -> next multiple of 5
    assert ek.bucket_for(11) == 35  # 32 -> 35
    assert ek.bucket_for(3) == 10
    # buckets below an explicit floor stay on the single-chip ladder
    monkeypatch.setenv("CMTPU_MESH_FLOOR", "512")
    assert ek.bucket_for(6) == 8
    assert ek.bucket_for(400) == 515  # 512 >= floor -> still rounded


# -- routing -----------------------------------------------------------------


def _probe_operands(b, bmax=2):
    """Shape-only operand probe: the router reads operands[0].shape[1]
    (batch bucket) and operands[3].shape[1] (block bucket) and nothing
    else, so None placeholders keep the probe honest about that."""
    return (
        np.zeros((8, b), np.uint32),
        None,
        None,
        np.zeros((b, bmax * 32), np.uint32),
        None,
    )


def test_every_standard_bucket_routes_to_the_mesh(monkeypatch):
    monkeypatch.delenv("CMTPU_MESH_FLOOR", raising=False)
    for b in ek.BUCKETS:
        _, sharded = ek._route_for(_probe_operands(b))
        assert sharded, f"bucket {b} must shard on the 8-device mesh"


def test_hosthash_program_never_shards():
    """The 4-operand host-hash program (the oversized-message fallback) has
    no mesh variant; it must stay on the bucket program."""
    hh = (
        np.zeros((8, 128), np.uint32),
        None,
        None,
        np.zeros((128, 64), np.uint32),
    )
    _, sharded = ek._route_for(hh)
    assert not sharded


def test_floor_env_keeps_small_buckets_single_device(monkeypatch):
    monkeypatch.setenv("CMTPU_MESH_FLOOR", "512")
    assert not ek._route_for(_probe_operands(128))[1]
    assert ek._route_for(_probe_operands(512))[1]


# -- bit identity ------------------------------------------------------------


def test_sharded_bitmap_bit_identical_to_single_device():
    """The same packed operands through the single-device bucket program
    and the 8-way sharded program must agree on every lane: valid lanes,
    a corrupted-signature lane, a shape-invalid (zero-packed) lane, and
    the zero-padded tail lanes of the bucket."""
    pubs, msgs, sigs = _signed(6, tag=b"ident")
    sigs[2] = sigs[2][:-1] + bytes([sigs[2][-1] ^ 1])  # bad signature
    pubs[4] = pubs[4][:31]  # shape-invalid -> zero-packed, host-vetoed
    operands, host_ok = ek.pack_batch(pubs, msgs, sigs)
    key = ek._bucket_key(operands)
    assert key[0] == 8  # two padded tail lanes ride along
    sh = ek._sharded_verify()
    assert sh is not None and sh[0] == 8
    single = np.asarray(ek._compiled(*key)(*operands))
    mesh = np.asarray(sh[1](*operands))
    assert single.shape == mesh.shape == (8,)
    assert np.array_equal(single, mesh)

    # End to end: batch_verify routes this bucket over the mesh and the
    # bitmap (device verdict AND host mask) is exact.
    before = ek.mesh_counters()
    ok, bits = ek.batch_verify(pubs, msgs, sigs)
    after = ek.mesh_counters()
    assert not ok
    assert [i for i, b in enumerate(bits) if not b] == [2, 4]
    assert after["devices"] == 8
    assert after["sharded_dispatches"] == before["sharded_dispatches"] + 1
    assert after["padded_lanes"] == before["padded_lanes"] + 2


@pytest.mark.slow  # compiles a 5-wide shard_map program used nowhere else
def test_non_pow2_mesh_pads_tail_lanes(monkeypatch):
    """A 5-chip submesh: bucket_for(6) pads to 10 lanes (2 per chip), the
    padded tail is vetoed by the host mask, and the bitmap stays exact."""
    from cometbft_tpu.ops import sharded

    fn5 = sharded.sharded_verify_fn(sharded.make_mesh(jax.local_devices()[:5]))
    monkeypatch.setattr(ek, "mesh_width", lambda: 5)
    monkeypatch.setattr(ek, "_sharded_verify", lambda: (5, fn5))
    monkeypatch.delenv("CMTPU_MESH_FLOOR", raising=False)
    pubs, msgs, sigs = _signed(6, tag=b"w5")
    sigs[1] = b"\x00" * 64
    before = ek.mesh_counters()
    ok, bits = ek.batch_verify(pubs, msgs, sigs)
    after = ek.mesh_counters()
    assert not ok
    assert [i for i, b in enumerate(bits) if not b] == [1]
    assert after["sharded_dispatches"] == before["sharded_dispatches"] + 1
    assert after["padded_lanes"] == before["padded_lanes"] + 4


# -- subtree-parallel Merkle -------------------------------------------------


def test_large_forest_routes_to_subtree_parallel_mesh(monkeypatch):
    monkeypatch.setenv("CMTPU_MESH_MERKLE_FLOOR", "16")
    leaves = [b"leaf-%d" % i for i in range(64)]
    before = ek.mesh_counters()["merkle_sharded_dispatches"]
    root = mk.merkle_root_fused(leaves)
    assert root == hash_from_byte_slices(leaves)
    assert ek.mesh_counters()["merkle_sharded_dispatches"] == before + 1


def test_merkle_floor_default_keeps_small_forests_single_device(monkeypatch):
    monkeypatch.delenv("CMTPU_MESH_MERKLE_FLOOR", raising=False)
    leaves = [b"l-%d" % i for i in range(32)]
    before = ek.mesh_counters()["merkle_sharded_dispatches"]
    root = mk.merkle_root_fused(leaves)
    assert root == hash_from_byte_slices(leaves)
    assert ek.mesh_counters()["merkle_sharded_dispatches"] == before


def test_merkle_mesh_gate_requires_pow2_width(monkeypatch):
    """The subtree top reduction pairs level-synchronously, so a non-pow2
    mesh (or a single chip) must not build the sharded root program."""
    mk._sharded_root.cache_clear()
    try:
        monkeypatch.setattr(ek, "mesh_width", lambda: 6)
        assert mk._sharded_root() is None
        mk._sharded_root.cache_clear()
        monkeypatch.setattr(ek, "mesh_width", lambda: 1)
        assert mk._sharded_root() is None
    finally:
        mk._sharded_root.cache_clear()


# -- observability + driver entry -------------------------------------------


def test_mesh_gauges_render():
    from cometbft_tpu.libs.metrics import Registry
    from cometbft_tpu.node.node import Node

    ek.mesh_width()  # make sure the probe has run in this process
    reg = Registry(namespace="cmt")
    Node._register_mesh_metrics(reg)
    text = reg.render()
    assert "cmt_mesh_devices 8" in text
    for g in (
        "cmt_mesh_sharded_dispatches",
        "cmt_mesh_padded_lanes",
        "cmt_mesh_merkle_sharded_dispatches",
    ):
        assert g in text


# slow: the full sharded commit step compile; the tier-1 sweep covers the
# same programs via test_multihost + the bit-identity and forest tests
# above, and `-m mesh` still selects this.
@pytest.mark.slow
def test_dryrun_multichip_spans_the_virtual_pod():
    import __graft_entry__ as entry

    entry.dryrun_multichip(8)


# -- chaos composition -------------------------------------------------------


@pytest.mark.chaos
def test_wedged_mesh_tier_degrades_through_supervisor():
    """wedge:1.0 on the mesh-routing device tier: the supervisor's deadline
    fires, the breaker opens the tier, and the cpu anchor serves the exact
    verdict — a pod-scale tier failing does not change a single bit.

    Batch sized to the bucket-8 program the bit-identity test above already
    compiled, and a short wedge: the abandoned watchdog thread wakes soon
    after the deadline and replays a CACHED program — it must not spend the
    rest of the suite compiling in the background on this single-core host.
    """
    from cometbft_tpu.sidecar.backend import CpuBackend, TpuBackend
    from cometbft_tpu.sidecar.chaos import ChaosBackend
    from cometbft_tpu.sidecar.supervisor import ResilientBackend

    wedged = ChaosBackend(TpuBackend(), "wedge:1:2000", seed=7)
    chain = ResilientBackend(
        [("tpu", wedged), ("cpu", CpuBackend())],
        deadline_ms=200,
        retries=0,
        backoff_ms=1,
        breaker_threshold=1,
        breaker_cooldown_ms=60000,
        crosscheck="off",
    )
    pubs, msgs, sigs = _signed(6, tag=b"wedge")
    sigs[1] = b"\x00" * 64
    ok, bits = chain.batch_verify(pubs, msgs, sigs)
    assert not ok
    assert [i for i, b in enumerate(bits) if not b] == [1]
    assert chain.counters()["tiers"]["tpu"]["state"] == "open"
    assert chain.active_tier_index == 1
    time.sleep(2.2)  # let the abandoned thread drain inside this test
