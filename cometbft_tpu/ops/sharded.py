"""Multi-chip sharding of the verification data path (SURVEY.md §2.13, §5.7).

The "sequence parallelism" analog of this framework: a 10k+ signature commit
batch is sharded across chips on a 1-D `sig` mesh (pure data parallel — the
Shamir ladder is elementwise over lanes, zero communication), and Merkle
trees are sharded by subtree: each chip reduces its leaf shard level-by-level
locally, subtree roots ride one all_gather over ICI, and the (tiny) top of
the tree is finished replicated. The overall-valid bit is a psum reduction.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from cometbft_tpu.ops import ed25519_kernel as ek
from cometbft_tpu.ops import merkle_kernel as mk
from cometbft_tpu.ops import sha256_kernel as sha


def make_mesh(devices=None, axis: str = "sig") -> Mesh:
    devices = devices if devices is not None else jax.devices()
    return Mesh(np.array(devices), (axis,))


def _verify_specs(axis: str):
    return (
        P(None, axis),  # a_words [8, N]
        P(None, axis),  # r_words [8, N]
        P(None, axis),  # s_words [8, N]
        P(axis, None),  # msg_words [N, B*32]
        P(axis),  # msg_nblocks [N]
    )


def sharded_verify_fn(mesh: Mesh, axis: str = "sig"):
    """jit-compiled batch verify with operands sharded over the batch dim
    (raw words + padded challenge blocks: everything after message
    construction — SHA-512 included — runs shard-local on device). Returns
    ok bool[N] (sharded)."""
    return jax.jit(
        ek.verify_core,
        in_shardings=tuple(NamedSharding(mesh, s) for s in _verify_specs(axis)),
        out_shardings=NamedSharding(mesh, P(axis)),
    )


def sharded_build_fn(mesh: Mesh, axis: str = "sig"):
    """The build program of a resident key column with the lane axis
    sharded: keys int32[8, M] -> (window tables int32[64, 8, 4, 17, M]
    sharded on their last axis, the decoding verdicts bool[M]). Every lane's
    tables are built on the chip that will add against them."""
    return jax.jit(
        ek.build_key_tables,
        in_shardings=NamedSharding(mesh, P(None, axis)),
        out_shardings=(
            NamedSharding(mesh, P(None, None, None, None, axis)),
            NamedSharding(mesh, P(axis)),
        ),
    )


def sharded_resident_fn(mesh: Mesh, axis: str = "sig"):
    """sharded_verify_fn for a resident column: the tables and their
    verdicts, as sharded_build_fn left them, in the keys' place. Zero
    collectives, as there."""
    specs = (P(None, None, None, None, axis), P(axis), *_verify_specs(axis)[1:])
    return jax.jit(
        ek.verify_core_resident,
        in_shardings=tuple(NamedSharding(mesh, s) for s in specs),
        out_shardings=NamedSharding(mesh, P(axis)),
    )


def sharded_verify_replicated_fn(mesh: Mesh, axis: str = "sig"):
    """Batch verify with the ok bitmap REPLICATED instead of batch-sharded:
    on a multi-HOST mesh, `sharded_verify_fn`'s sharded output leaves each
    host holding only its addressable slice — but the fanout-serving seam
    (ops/multihost.py) needs the LEADER process to read the whole bitmap
    locally to answer the sidecar client. The replication all-gather is
    inserted by GSPMD from the out_sharding, same as the commit step's
    all-valid bit."""
    return jax.jit(
        ek.verify_core,
        in_shardings=tuple(NamedSharding(mesh, s) for s in _verify_specs(axis)),
        out_shardings=NamedSharding(mesh, P()),
    )


def _local_tree_root(leaves):
    """Reduce uint32[8, m] leaf digests (m a power of two) to one root [8, 1]
    with level-synchronous pairing."""
    cur = leaves
    while cur.shape[1] > 1:
        cur = mk._inner_core(cur[:, 0::2], cur[:, 1::2])
    return cur


def sharded_merkle_fn(mesh: Mesh, axis: str = "sig"):
    """shard_map'd subtree-parallel Merkle root: leaf digests uint32[8, n]
    (n = pow2, divisible by mesh size) -> replicated root uint32[8, 1]."""

    def local(leaf_shard):
        root = _local_tree_root(leaf_shard)  # [8, 1] per device
        roots = jax.lax.all_gather(root[:, 0], axis, axis=1)  # [8, ndev]
        # Every device computes the identical top reduction; emit one column
        # per device (JAX's varying-axis checker can't see the replication).
        return _local_tree_root(roots)

    fn = jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=P(None, axis),
            out_specs=P(None, axis),
        )
    )
    return lambda leaves: fn(leaves)[:, :1]


def sharded_leaves_to_root_fn(mesh: Mesh, axis: str = "sig"):
    """shard_map'd FUSED leaves->root: pre-padded leaf messages (blocks
    uint32[B, 16, n], nblocks int32[n]; n = pow2, n/mesh-size a pow2) are
    leaf-hashed shard-local, each chip reduces its subtree, subtree roots
    ride one all_gather, and every chip finishes the (tiny) replicated top.
    The multi-chip analog of merkle_kernel.leaves_to_root_core — one
    dispatch end to end. Returns uint32[8, 1]."""

    def local(block_shard, nblock_shard):
        root = _local_tree_root(mk._leaf_core(block_shard, nblock_shard))
        roots = jax.lax.all_gather(root[:, 0], axis, axis=1)  # [8, ndev]
        # Identical top reduction on every device; emit one column each
        # (JAX's varying-axis checker can't see the replication).
        return _local_tree_root(roots)

    fn = jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=(P(None, None, axis), P(axis)),
            out_specs=P(None, axis),
            # _leaf_core's fori_loop carry starts as the (unvarying) IV and
            # becomes shard-varying after one compression; the carry-type
            # check rejects that, and the kernel is shared with the
            # single-chip program, so the check is off for this map only.
            check_vma=False,
        )
    )
    return lambda blocks, nblocks: fn(blocks, nblocks)[:, :1]


def sharded_commit_step_fn(mesh: Mesh, axis: str = "sig"):
    """The full 'training step' analog: one jitted program that verifies a
    sharded signature batch AND reduces a sharded Merkle leaf forest, with a
    psum for the all-valid bit."""

    def step(a_words, r_words, s_words, msg_blocks, msg_nblocks, leaf_digests):
        ok = ek.verify_core(a_words, r_words, s_words, msg_blocks, msg_nblocks)

        def reduce_shard(ok_shard, leaf_shard):
            local_ok = jnp.all(ok_shard).astype(jnp.int32)
            total_ok = jax.lax.psum(local_ok, axis)  # ICI all-reduce
            root = _local_tree_root(leaf_shard)
            roots = jax.lax.all_gather(root[:, 0], axis, axis=1)
            top = _local_tree_root(roots)  # identical on every device
            return total_ok[None], top

        total_ok, root_cols = shard_map(
            reduce_shard,
            mesh=mesh,
            in_specs=(P(axis), P(None, axis)),
            out_specs=(P(axis), P(None, axis)),
        )(ok, leaf_digests)
        n_dev = mesh.devices.size
        all_valid = jnp.sum(total_ok) == n_dev * n_dev  # psum'd per shard
        return ok, all_valid, root_cols[:, :1]

    return jax.jit(
        step,
        in_shardings=tuple(
            NamedSharding(mesh, s)
            for s in (*_verify_specs(axis), P(None, axis))
        ),
        # Explicit out shardings so every HOST of a multi-process mesh can
        # read the verdict + root locally (ops/multihost.py): the bitmap
        # stays batch-sharded, the all-valid bit and root are replicated.
        out_shardings=(
            NamedSharding(mesh, P(axis)),
            NamedSharding(mesh, P()),
            NamedSharding(mesh, P(None, None)),
        ),
    )


def make_example_batch(n: int):
    """Deterministic signed batch packed for verify_core (host crypto is
    C-speed; used by the graft entry)."""
    from cometbft_tpu.crypto import ed25519 as host_ed

    pubs, msgs, sigs = [], [], []
    for i in range(n):
        priv = host_ed.gen_priv_key_from_secret(b"bench-%d" % i)
        pub = priv.pub_key().bytes()
        msg = b"commit-vote-%d" % i
        pubs.append(pub)
        msgs.append(msg)
        sigs.append(priv.sign(msg))
    operands, host_ok = ek.pack_batch(pubs, msgs, sigs)
    assert all(host_ok[: len(pubs)])
    return tuple(jnp.asarray(o) for o in operands)


def example_txs(n: int) -> list[bytes]:
    """The deterministic tx fixture shared by the multi-chip dryrun, the
    multi-host worker, and their root cross-checks — one definition so the
    copies cannot drift."""
    return [b"tx-%d" % i for i in range(n)]


def make_example_leaves(n: int):
    """Leaf digests uint32[8, n] for n power-of-two txs."""
    return jnp.asarray(mk.hash_leaves_device(example_txs(n)))
