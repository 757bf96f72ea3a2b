"""Multi-HOST distributed verification (SURVEY.md §5.8).

The reference's distribution substrate is its p2p TCP mesh — every node
re-verifies everything. This framework adds a second, orthogonal axis the
reference cannot express: ONE logical verification step sharded across
the chips of SEVERAL hosts, with XLA collectives riding ICI within a
host and DCN between hosts. A JAX "process" per host joins a
coordinator (`jax.distributed`), the global device list forms the same
1-D `sig` mesh `ops/sharded.py` uses, and each host contributes only its
process-local lane slice — packing is embarrassingly columnar (every
packed lane depends on its own signature only, ed25519_kernel.pack_batch),
so a host packs exactly the commit slice it was assigned. all_gather /
psum give every host the identical Merkle root and all-valid bit.

CPU hosts participate through the same code path via jaxlib's gloo
collectives backend — which is also how this is TESTED without multi-host
TPU hardware: tests/test_multihost.py spawns real OS processes, each with
virtual CPU devices, forms the global mesh over the gloo coordinator, and
cross-checks the root against the host tree (the same validation contract
as __graft_entry__.dryrun_multichip, one level up the scaling ladder).

Round 15 adds the FANOUT-SERVING seam: a multi-process mesh can act as
ONE shard of a `sidecar/fanout.py` fleet. The leader process (pid 0)
exposes a `MultihostShardBackend` through an ordinary `SidecarServer`; on
every batch it re-broadcasts the triples to its follower processes over
plain framed side sockets, then all processes enter the same collective
verify step (`multihost_verify`), whose replicated bitmap lets the leader
answer the fanout client alone. The Ping capability reply advertises the
GLOBAL device count, so the fleet's width sum counts every chip behind
every process of every shard.
"""

from __future__ import annotations

import functools
import os
import threading

import numpy as np

import jax

# NOTE: ops.sharded (and through it the kernels + field25519's lowering
# probe) is imported lazily inside the functions below — importing it at
# module scope initializes the XLA backend, which must not happen before
# distributed_init() joins the coordinator.


def distributed_init(
    coordinator: str,
    num_processes: int,
    process_id: int,
    local_devices: int | None = None,
) -> None:
    """Join (or form) the multi-host verification cluster.

    coordinator: "host:port" of process 0. For CPU hosts pass
    local_devices (virtual devices per host) — it is applied to XLA_FLAGS
    here, before backend init — and jaxlib's gloo backend carries the
    collectives; on TPU hosts leave it None and the PJRT topology
    provides the device set.
    """
    if local_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        if "--xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{local_devices}"
            ).strip()
    try:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except Exception:
        pass  # TPU-only jaxlib builds have no CPU collectives knob
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )


def process_local_columns(mesh, spec, global_shape, local_cols):
    """Assemble a globally-sharded array from THIS host's column slice.

    local_cols must be exactly the columns this process's devices own
    under `spec` (mesh is 1-D over the batch axis, so that is the
    contiguous [pid*shard : (pid+1)*shard] slice of the batch dim).
    """
    from jax.sharding import NamedSharding

    return jax.make_array_from_process_local_data(
        NamedSharding(mesh, spec), np.ascontiguousarray(local_cols), global_shape
    )


@functools.lru_cache(maxsize=None)
def _step_for(mesh, axis):
    """One jitted step per (mesh, axis) — a node runs this once per block,
    and rebuilding the jit wrapper per call would pay a cache lookup and
    wrapper allocation on the consensus hot path."""
    from cometbft_tpu.ops import sharded

    return sharded.sharded_commit_step_fn(mesh, axis)


def multihost_commit_step(mesh, local_operands, local_leaf_digests, axis="sig"):
    """Run ops/sharded.sharded_commit_step_fn with per-host inputs.

    local_operands: this host's lane slice of the packed verify operands
    (same tuple layout as ed25519_kernel.pack_batch, sliced on the batch
    dim). local_leaf_digests: uint32[8, n_local] leaf-digest columns of
    this host's Merkle shard. Returns (ok_local, all_valid, root_words):
    ok_local is this host's slice of the validity bitmap; all_valid and
    the root are replicated across every host by the step's collectives.
    """
    from cometbft_tpu.ops import sharded

    n_proc = jax.process_count()
    specs = (*sharded._verify_specs(axis), jax.sharding.PartitionSpec(None, axis))
    arrays = []
    for op, spec in zip((*local_operands, local_leaf_digests), specs):
        gshape = list(op.shape)
        # the sharded dim is the one carrying the batch axis in the spec
        dim = list(spec).index(axis)
        gshape[dim] = op.shape[dim] * n_proc
        arrays.append(process_local_columns(mesh, spec, tuple(gshape), op))
    *operands, leaves = arrays
    step = _step_for(mesh, axis)
    ok, all_valid, root = step(*operands, leaves)
    # Per-host view of the sharded bitmap: the addressable shards.
    local_ok = np.concatenate(
        [np.asarray(s.data) for s in sorted(
            ok.addressable_shards, key=lambda s: s.index[0].start or 0)]
    )
    return local_ok, bool(all_valid), np.asarray(root)


# -- fanout-serving seam (round 15) -------------------------------------------


@functools.lru_cache(maxsize=None)
def _verify_for(mesh, axis):
    from cometbft_tpu.ops import sharded

    return sharded.sharded_verify_replicated_fn(mesh, axis)


def multihost_verify(mesh, pubs, msgs, sigs, axis="sig"):
    """One collective batch verify over a multi-process mesh; every process
    must call this with IDENTICAL triples in the same order (the leader's
    broadcast guarantees that for the serving path).

    Every process packs the FULL batch — packing is cheap columnar host
    work, no crypto — and contributes its contiguous per-process column
    slice, exactly the tests/multihost_worker.py idiom, so the operand
    shapes agree across hosts by construction. The per-process slice is
    rounded up the kernel's bucket ladder (`bucket_for`), keeping the set
    of compiled global shapes as bounded as the single-host ladder; padded
    lanes are zeroed and fail device verification, and the returned bitmap
    is sliced back to the caller's n with the host-side veto applied.
    Returns (ok, bits) with the full bitmap on EVERY process (the
    replicated out-sharding of sharded_verify_replicated_fn)."""
    from cometbft_tpu.ops import ed25519_kernel as ek
    from cometbft_tpu.ops import sharded

    n = len(pubs)
    n_proc = jax.process_count()
    pid = jax.process_index()
    per = ek.bucket_for(max(1, -(-n // n_proc)))
    total = per * n_proc
    if total > n:
        pad = total - n
        pubs = list(pubs) + [b"\x00" * 32] * pad
        msgs = list(msgs) + [b""] * pad
        sigs = list(sigs) + [b"\x00" * 64] * pad
    operands, host_ok = ek.pack_batch(pubs, msgs, sigs)
    if len(operands) != 5:
        raise NotImplementedError(
            "host-hash packing (oversized messages) "
            "cannot serve the multi-host verify step"
        )
    specs = sharded._verify_specs(axis)
    lo, hi = pid * per, (pid + 1) * per
    arrays = []
    for op, spec in zip(operands, specs):
        dim = list(spec).index(axis)
        local = op[:, lo:hi] if dim == 1 else op[lo:hi]
        gshape = list(local.shape)
        gshape[dim] = local.shape[dim] * n_proc
        arrays.append(process_local_columns(mesh, spec, tuple(gshape), local))
    dev_ok = np.asarray(_verify_for(mesh, axis)(*arrays))
    bits = [bool(host_ok[i] and dev_ok[i]) for i in range(n)]
    return all(bits), bits


def _encode_triples(pubs, msgs, sigs) -> bytes:
    """BatchVerifyReq-shaped body for the leader -> follower broadcast
    (same fields as the sidecar's wire format, so nothing new to fuzz)."""
    from cometbft_tpu.wire import proto

    return (
        b"".join(proto.field_bytes(1, p, emit_default=True) for p in pubs)
        + b"".join(proto.field_bytes(2, m, emit_default=True) for m in msgs)
        + b"".join(proto.field_bytes(3, s, emit_default=True) for s in sigs)
    )


def _decode_triples(body: bytes):
    from cometbft_tpu.wire import proto

    fields = proto.decode_fields(body)
    return (
        proto.get_repeated_bytes(fields, 1),
        proto.get_repeated_bytes(fields, 2),
        proto.get_repeated_bytes(fields, 3),
    )


class MultihostShardBackend:
    """The VerifyBackend the LEADER process of a multi-process mesh serves
    through its SidecarServer when the whole mesh is one fanout shard.

    batch_verify re-broadcasts the triples to every follower over the side
    sockets (one framed write each; an empty frame means shutdown), then
    joins the collective step itself — every process runs
    `multihost_verify` on the same batch in the same order, which is what
    the collectives require. The lock serializes broadcasts so the frame
    order IS the collective order even if the server's engine ever grows
    a second dispatcher. A dead follower surfaces as a socket error or a
    wedged collective; either way the fanout tier times the shard out and
    redistributes its slice — exactly the failure contract fanout shards
    signed up for.

    merkle_root stays host-local (one tree per call has no cross-host
    slicing opportunity, and the leader's host tree is the same ground
    truth the supervisor's anchor uses)."""

    name = "multihost"

    def __init__(self, mesh, followers, axis: str = "sig"):
        self.mesh = mesh
        self.axis = axis
        self._followers = list(followers)  # connected side sockets
        self._lock = threading.Lock()

    def mesh_width(self) -> int:
        return int(self.mesh.devices.size)  # GLOBAL chips, every process

    def batch_verify(self, pubs, msgs, sigs):
        from cometbft_tpu.sidecar.service import write_frame

        if len(pubs) == 0:
            return False, []
        with self._lock:
            body = _encode_triples(pubs, msgs, sigs)
            for sock in self._followers:
                write_frame(sock, body)
            return multihost_verify(self.mesh, pubs, msgs, sigs, self.axis)

    def merkle_root(self, leaves):
        from cometbft_tpu.crypto.merkle.tree import hash_from_byte_slices

        return hash_from_byte_slices(list(leaves))

    def ping(self) -> bool:
        return True

    def close(self) -> None:
        with self._lock:
            for sock in self._followers:
                try:
                    write_frame(sock, b"")  # shutdown sentinel
                    sock.close()
                except OSError:
                    pass
            self._followers = []


def follow_verify_loop(mesh, sock, axis: str = "sig") -> int:
    """Follower side of the serving seam: block on the leader's side
    socket, mirror every broadcast batch into the collective verify step
    (result discarded — the replication already handed the leader the
    bitmap), return the number of batches served when the leader closes
    or sends the empty shutdown frame."""
    from cometbft_tpu.sidecar.service import read_frame

    served = 0
    while True:
        body = read_frame(sock)
        if not body:  # EOF or the b"" shutdown sentinel
            return served
        pubs, msgs, sigs = _decode_triples(body)
        multihost_verify(mesh, pubs, msgs, sigs, axis)
        served += 1
