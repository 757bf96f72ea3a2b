"""Batched Ed25519 ZIP-215 verification on TPU.

The device-tier implementation of the reference's `crypto/ed25519`
BatchVerifier (crypto/ed25519/ed25519.go:196-228). Instead of the reference's
random-linear-combination batch equation + bisection on failure, every lane
checks its own cofactored equation

    [8]([s]B + [k](-A) + (-R)) == identity

in SPMD lockstep, so one device call yields the exact per-signature validity
bitmap the callers need (types/validation.go:234-249) with no re-runs.

Host side: shape checks, the vectorized s-range check, and packing the
challenge messages R || A || M into padded SHA-512 blocks — no crypto at
all. The kernel takes the RAW 32-byte encodings as little-endian uint32
words plus the padded challenge blocks, and runs the WHOLE verification on
device: SHA-512 (sha512_kernel), k = digest mod L + signed-window recode +
point decoding (ops/unpack.py), the signed-4-bit-window double-scalar
multiplication, and the identity test — one jit-compiled program per
(batch, block-count) bucket pair. A batch with a message past the largest
block bucket is hashed on the host instead and the device receives 64-byte
digests (verify_core_hosthash): see pack_batch.

Two programs, chosen by what the call's key column is, nothing else:

- `verify_core`, for keys the tier has not seen as a column before (and the
  host-hash program, always): decompress A and R, build [0..8](-A) per
  lane, the ladder of edwards.windowed_double_base_mult — 252 doublings +
  128 additions a lane.
- `verify_core_resident`, for a column (or a long prefix of one) whose window
  tables are resident on the device: decompress R only, then
  edwards.windowed_table_mult — 128 table additions a lane and no doubling,
  about a third of the field multiplications. Same digits, same signed
  recoding, same cofactored equation, the key's decoding verdict kept
  beside its table: the bitmap is the other program's, lane for lane.

The tables (`_ResidentColumns`): a column of at least CMTPU_HYBRID_MIN
DISTINCT, well-formed keys is remembered at its first sighting (its joined
bytes) and its tables — int32[64, 8, 4, 17, bucket], 139,264 bytes a key,
1.43 GB for 10,240 lanes — are built by one device program queued BEHIND
the dispatch of its second sighting, which rides `verify_core` like the
first: no call waits for a build it did not need (a call that arrives while
its OWN column is being built waits for the tables and rides them). From
the build's landing on, calls whose joined key bytes equal the column's, or
a prefix of them at least half its bucket, ride the resident program, which
has one shape a column, the tables' own: fewer lanes are widened to it on
the host. Resident bytes are bounded (RESIDENT_MAX_BYTES a chip, oldest
column out); a table is derived state: lost, it is rebuilt.
"""

from __future__ import annotations

import atexit
import collections
import functools
import hashlib
import os
import queue
import sys
import threading
import time
from concurrent.futures import Future

import numpy as np

import jax
import jax.numpy as jnp

from cometbft_tpu.libs import trace
from cometbft_tpu.ops import edwards as ed
from cometbft_tpu.ops import field25519 as fe
from cometbft_tpu.ops import sha512_kernel as s5
from cometbft_tpu.ops import unpack

L = 2**252 + 27742317777372353535851937790883648493

# Fixed batch buckets: one compiled program per size, reused forever
# (SURVEY.md §7 "pre-compiled fixed-shape programs + bucketed batch sizes").
# 2048/6144/8192 exist for the hybrid tier's device share: splitting a
# 10,240-signature commit needs a bucket near the throughput-balanced
# point (device ~100 sigs/ms vs host MSM ~70 sigs/ms -> ~6k device lanes),
# and padding to the next coarse bucket would burn the whole saving.
BUCKETS = (8, 32, 128, 512, 1024, 2048, 4096, 6144, 8192, 10240, 16384, 32768)
# Challenge-message block counts bucket the other program axis: a canonical
# vote challenge is 64 + ~120 bytes = 2 blocks; odd app messages fall into
# the larger buckets.
BLOCK_BUCKETS = (2, 4, 8, 32)
# jax.named_scope of each stage of the verify program, in program order.
KERNEL_SCOPES = ("sha512", "unpack", "decompress", "ladder", "finish")


_probed_width = 0  # mesh_width()'s last answer; 0 = never probed


@functools.lru_cache(maxsize=1)
def mesh_width() -> int:
    """Process-local chips one verify dispatch can shard across (the 1-D
    `sig` mesh of ops/sharded). First call may initialize the JAX
    backend and raises whatever that raises: a device tier that cannot
    count its chips has not started. Callers that must never initialize
    it (node metric scrapes, the coalescer's default cap) read
    known_mesh_width() instead."""
    global _probed_width
    n = max(1, jax.local_device_count())
    _probed_width = n
    return n


def known_mesh_width() -> int:
    """mesh_width() if some caller already probed it, else 0. Never
    initializes jax — safe from lazy metric closures and constructors that
    must not be the ones to start the device backend."""
    return _probed_width


def mesh_floor() -> int:
    """Smallest batch bucket worth spreading across the mesh. Default:
    the mesh width itself (each chip gets at least one lane — the historic
    divisibility rule's implicit floor); CMTPU_MESH_FLOOR overrides for
    deployments where tiny sharded dispatches lose to collective setup."""
    env = os.environ.get("CMTPU_MESH_FLOOR", "")
    if env:
        try:
            return max(1, int(env))
        except ValueError:
            pass
    return mesh_width()


def bucket_for(n: int) -> int:
    """Batch bucket for n signatures, rounded up to a multiple of the mesh
    width once at/above the sharding floor — every bucket the router would
    shard divides the device count evenly, so a 6-chip host pads 2048 to
    2052 instead of leaving 5 chips idle (the pre-mesh ladder silently fell
    back to one chip for any non-divisible bucket)."""
    for b in BUCKETS:
        if n <= b:
            break
    else:
        b = int(2 ** np.ceil(np.log2(n)))
    w = mesh_width()
    if w > 1 and b >= mesh_floor() and b % w:
        b += w - b % w
    return b


def preferred_stream_chunk() -> int:
    """Chunk size the sidecar advertises to streaming clients (Ping
    capability field 4): the smallest compiled batch bucket that is both
    commit-sized and a mesh-width multiple, so every streamed chunk lands
    on the bucket ladder with zero padding and — at/above mesh_floor() —
    routes through the sharded program like the in-process tier. Uses the
    passively-known width only: a Ping must not be what starts the device
    backend of a sidecar that has never dispatched."""
    w = known_mesh_width() or 1
    target = max(1024, 128 * w)
    for b in BUCKETS:
        if target <= b:
            break
    else:
        b = int(2 ** np.ceil(np.log2(target)))
    if w > 1 and b % w:
        b += w - b % w
    return b


_mesh_lock = threading.Lock()
_mesh_counters = {
    "sharded_dispatches": 0,  # verify dispatches routed to the mesh program
    "padded_lanes": 0,        # bucket-padding lanes shipped on those
    "merkle_sharded_dispatches": 0,  # fused roots via the subtree program
}


def mesh_counters() -> dict:
    """Snapshot of the mesh routing counters plus the (passively read)
    device count — the source for the node's lazy mesh_* gauges."""
    with _mesh_lock:
        out = dict(_mesh_counters)
    out["devices"] = known_mesh_width()
    return out


def _mesh_count(key: str, delta: int = 1) -> None:
    with _mesh_lock:
        _mesh_counters[key] += delta


def block_bucket_for(b: int) -> int:
    for bb in BLOCK_BUCKETS:
        if b <= bb:
            return bb
    return int(2 ** np.ceil(np.log2(b)))


def _challenge_words(msg_words, msg_nblocks):
    """k's 64 digest bytes as int32[16, N] words: the padded SHA-512 byte
    stream (native uint32 words, uint32[N, B*32] — a FREE view of the host
    pack buffer — and per-lane block counts int32[N]) hashed on the device."""
    n, bwords = msg_words.shape
    bmax = bwords // 32
    with jax.named_scope("sha512"):
        # [N, B*32] LE words -> [B, 2(hi/lo), 16, N] big-endian block words:
        # layout shuffle + byte swap are the program's first (cheap, fused)
        # ops instead of multi-MB host passes.
        x = msg_words.astype(jnp.uint32).reshape(n, bmax, 16, 2)
        blocks_be = s5.bswap32(jnp.transpose(x, (1, 3, 2, 0)))
        return s5.digest_to_le_words(s5.hash_blocks_core(blocks_be, msg_nblocks))


def verify_core(a_words, r_words, s_words, msg_words, msg_nblocks):
    """Pure jittable core: raw little-endian words in (A, R, S as
    int32[8, N]) plus the padded SHA-512 challenge byte stream, bool[N] out.
    The whole verification is on-device: block-layout transpose + byte swap,
    challenge hash, k = digest mod L, digit recodes, point decoding, window
    ladder, identity test. The A and R decompressions ride ONE width-2N
    pass (lane-stacked) — same op count in half the program."""
    k_words = _challenge_words(msg_words, msg_nblocks)
    return _verify_from_words(a_words, r_words, s_words, k_words)


def verify_core_hosthash(a_words, r_words, s_words, k_words):
    """The program for batches pack_batch hashed on the host (a message past
    the largest block bucket): the 64-byte challenge digests come in as
    int32[16, N] little-endian words. Always the ladder: resident tables
    serve the device-hash program only."""
    return _verify_from_words(a_words, r_words, s_words, k_words)


def _verify_from_words(a_words, r_words, s_words, k_words):
    """The stages carry `jax.named_scope`s (KERNEL_SCOPES): HLO metadata
    only, so the compiled programs and their cache keys stay as they were,
    and a profile names each device operation's stage."""
    n = a_words.shape[1]
    with jax.named_scope("unpack"):
        y_a, sign_a = unpack.words_to_limbs255(a_words)
        y_r, sign_r = unpack.words_to_limbs255(r_words)
        s_digits = unpack.scalar_words_to_digits(s_words)
        k_digits = unpack.digest_words_to_digits(k_words)
    with jax.named_scope("decompress"):
        y2 = jnp.concatenate([y_a, y_r], axis=1)
        sg2 = jnp.concatenate([sign_a, sign_r])
        pt, ok = ed.decompress(y2, sg2)
        a = tuple(c[:, :n] for c in pt)
        r = tuple(c[:, n:] for c in pt)
        neg_a = ed.point_neg(a)
    with jax.named_scope("ladder"):
        acc = ed.windowed_double_base_mult(s_digits, k_digits, neg_a)
    with jax.named_scope("finish"):
        acc = ed.point_add(acc, ed.point_neg(r))
        acc = ed.point_double(ed.point_double(ed.point_double(acc)))
        return ok[:n] & ok[n:] & ed.point_is_identity(acc)


def verify_core_resident(tables_a, ok_a, r_words, s_words, msg_words, msg_nblocks):
    """verify_core for lanes whose keys' window tables are resident:
    tables_a int32[64, 8, 4, 17, N] and ok_a bool[N] as build_key_tables
    left them, lane for lane (a dispatch of fewer lanes is widened to the
    tables' on the host: _widen). The keys themselves are not an operand:
    the challenge stream holds their bytes and the tables hold their
    points. Hash, unpack, decompress R only, 128 table additions a lane,
    the same finish."""
    k_words = _challenge_words(msg_words, msg_nblocks)
    with jax.named_scope("unpack"):
        y_r, sign_r = unpack.words_to_limbs255(r_words)
        s_digits = unpack.scalar_words_to_digits(s_words)
        k_digits = unpack.digest_words_to_digits(k_words)
    with jax.named_scope("decompress"):
        r, ok_r = ed.decompress(y_r, sign_r)
    with jax.named_scope("ladder"):
        acc = ed.windowed_table_mult(s_digits, k_digits, tables_a)
    with jax.named_scope("finish"):  # verify_core's, to the letter
        acc = ed.point_add(acc, ed.point_neg(r))
        acc = ed.point_double(ed.point_double(ed.point_double(acc)))
        return ok_a & ok_r & ed.point_is_identity(acc)


def build_key_tables(a_words):
    """The build program of a resident column: keys as int32[8, M] words ->
    (window tables of -A, int32[64, 8, 4, 17, M]; the decoding verdict
    bool[M], kept: a key that does not decode stays false under ZIP-215
    exactly as in verify_core, whatever its lane's table holds)."""
    with jax.named_scope("unpack"):
        y_a, sign_a = unpack.words_to_limbs255(a_words)
    with jax.named_scope("decompress"):
        a, ok_a = ed.decompress(y_a, sign_a)
    with jax.named_scope("table_build"):
        return ed.build_window_tables(ed.point_neg(a)), ok_a


@functools.lru_cache(maxsize=None)
def _compiled(n: int, bmax: int = 0):
    """One jitted program per (batch, block-count) bucket pair; bmax 0 is
    the host-hash program (pre-hashed digests in). The lru wrapper (vs one
    global jax.jit) lets tests force a retrace after flipping the fe
    lowering mode via cache_clear()."""
    if bmax == 0:
        return jax.jit(verify_core_hosthash)
    return jax.jit(verify_core)


def warmup(buckets=(128, 1024, 6144, 10240), merkle_leaves=(1024, 65536)) -> None:
    """Precompile the verify program for the given batch buckets AND the
    fused Merkle leaves->root program ahead of first use (SURVEY §7 hard
    part 3: the <2 ms latency budget cannot absorb a per-call XLA compile).
    Feeds vote-shaped (2-block) challenge messages so the compiled
    executable (and the persistent compile cache entry) exists before the
    first real commit."""
    msg = b"\x00" * 120  # canonical-vote-sized: 64 + 120 -> 2 blocks
    for b in buckets:
        operands, _ = pack_batch([b"\x00" * 32] * b, [msg] * b, [b"\x00" * 64] * b)
        jax.block_until_ready(_verify_fn_for(operands)(*operands))
    from cometbft_tpu.ops import merkle_kernel as mk

    for n in merkle_leaves:
        blocks = np.zeros((1, 16, n), np.uint32)
        nblocks = np.ones(n, np.int32)
        jax.block_until_ready(mk._leaves_to_root_jit(1, n)(blocks, nblocks))


def _bucket_key(operands) -> tuple[int, int]:
    """(batch, block) bucket pair; bmax 0 selects the host-hash program
    (4 operands: the oversized-message fallback in pack_batch)."""
    # the keys' words are None where the pack saw resident tables serve
    n = (operands[1] if operands[0] is None else operands[0]).shape[1]
    bmax = operands[3].shape[1] // 32 if len(operands) == 5 else 0
    return n, bmax


def _whole_column(col, stride: int):
    """The column's entries joined, where every one is a bytes-like of
    `stride` bytes, else None: the join and the set of lengths are two
    C-level passes, whatever the column's length."""
    try:
        joined = b"".join(col)
    except TypeError:
        return None
    whole = len(joined) == stride * len(col) and set(map(len, col)) <= {stride}
    return joined if whole else None


def _lane_words(words: np.ndarray, nb: int) -> np.ndarray:
    """Little-endian uint32 words [n, k] -> int32[k, nb], lanes n..nb zero
    (unpack.bytes_to_words of the rows zero-padded to nb): one allocation
    and one transposing copy."""
    out = np.zeros((words.shape[1], nb), np.uint32)
    out[:, : words.shape[0]] = words.T
    return out.view(np.int32)


def _host_checks(pubs, sigs, keys=None):
    """The host's checks of a batch, on whole columns: (key rows uint8[n, 32],
    signature rows uint8[n, 64], shape_ok, s_in_range bool[n]). Where the
    joins' lengths prove every key 32 and every signature 64 bytes,
    shape_ok is None and the rows are views of the joined columns (`keys`,
    a sighting's, stands for the key column's join). A batch with any other
    entry takes the per-lane walk: shape_ok is a list of n verdicts and a
    refused lane's rows are zero."""
    n = len(pubs)
    sig_col = _whole_column(sigs, 64)
    key_col = _whole_column(pubs, 32) if keys is None else keys[:n]
    shape_ok = None
    if sig_col is None or key_col is None:
        zero_pub, zero_sig = b"\x00" * 32, b"\x00" * 64
        shape_ok = [len(pubs[i]) == 32 and len(sigs[i]) == 64 for i in range(n)]
        key_col = b"".join([pubs[i] if shape_ok[i] else zero_pub for i in range(n)])
        sig_col = b"".join([sigs[i] if shape_ok[i] else zero_sig for i in range(n)])
    key_rows = np.frombuffer(key_col, np.uint8).reshape(n, 32)
    sig_rows = np.frombuffer(sig_col, np.uint8).reshape(n, 64)
    # s < L, vectorized: compare the four little-endian uint64 words
    # most-significant first.
    s_words = sig_rows.view("<u8")[:, 4:]  # [n, 4]
    l_words = np.frombuffer(L.to_bytes(32, "little"), dtype="<u8")
    s_in_range = np.zeros(n, bool)
    decided = np.zeros(n, bool)
    for w in (3, 2, 1, 0):
        lt = ~decided & (s_words[:, w] < l_words[w])
        gt = ~decided & (s_words[:, w] > l_words[w])
        s_in_range |= lt
        decided |= lt | gt
    # s == L (all words equal) leaves decided False -> not in range.
    return key_rows, sig_rows, shape_ok, s_in_range


def pack_batch(pubs, msgs, sigs, sighting=None):
    """Host-side packing of one verification batch — no crypto: shape
    checks, the vectorized s < L check, raw-byte -> word views, and the
    challenge messages R || A || M padded into SHA-512 blocks (the hashing
    itself runs on device). Returns device operands plus the host-decided
    validity mask (shape errors, s >= L). Invalid entries are packed as
    zeros — lanes the device evaluates but the mask vetoes. A fixed number
    of passes and array assignments over whole columns, whatever n (only a
    malformed entry sends the batch through _host_checks' per-lane walk).
    With a `sighting` of the column these keys are the first of, its key
    rows stand for the key column's join, and where its tables serve the
    dispatch the keys' words, which the resident program does not take,
    are None."""
    return _pack(pubs, msgs, sigs, sighting or _NOTHING)[:2]


def _pack(pubs, msgs, sigs, sighting):
    """pack_batch, and whether the batch took the per-lane walk."""
    n = len(pubs)
    nb = bucket_for(n)
    key_rows, sig_rows, shape_ok, s_in_range = _host_checks(pubs, sigs, sighting.keys)
    walk = shape_ok is not None
    host_ok = np.zeros(nb, bool)
    if not walk:
        host_ok[:n] = s_in_range
        mlens = np.fromiter(map(len, msgs), np.int64, n)
    else:
        host_ok[:n] = np.asarray(shape_ok, bool) & s_in_range
        # shape-invalid rows are forced to length 0: their message is not read
        mlens = np.fromiter(
            (len(msgs[i]) if shape_ok[i] else 0 for i in range(n)), np.int64, n
        )
    sig_words = sig_rows.view("<u4")
    r_words = _lane_words(sig_words[:, :8], nb)
    s_words = _lane_words(sig_words[:, 8:], nb)
    if not s_in_range.all():
        s_words[:, np.nonzero(~s_in_range)[0]] = 0
    # Oversized messages (past the largest block bucket) fall back to host
    # hashing: the hosthash program's shapes are independent of message
    # length, so an adversary feeding growing messages cannot force a fresh
    # XLA compile per size.
    if n > 0 and int(mlens.max()) + 64 > BLOCK_BUCKETS[-1] * 128 - 17:
        k_le = np.zeros((nb, 64), np.uint8)
        for i in np.nonzero(host_ok)[0].tolist():
            h = hashlib.sha512(sig_rows[i, :32])
            h.update(key_rows[i])
            h.update(msgs[i])
            k_le[i] = np.frombuffer(h.digest(), np.uint8)
        a_words = _lane_words(key_rows.view("<u4"), nb)
        return (a_words, r_words, s_words, unpack.bytes_to_words(k_le)), host_ok, walk

    # Challenge blocks R || A || M, padded: R and A are block copies of the
    # columns' rows; messages and the pad go in per DISTINCT length (a
    # commit's sign-bytes have 1-3 layouts), each length one join, one
    # reshaped assignment and two constant column writes.
    tot = mlens + 64
    nblocks = s5.blocks_for(tot)
    bmax = block_bucket_for(int(nblocks.max()) if n else 1)
    buf = np.zeros((nb, bmax * 128), np.uint8)
    rows_n = buf[:n]
    rows_n[:, 0:32] = sig_rows[:, :32]
    rows_n[:, 32:64] = key_rows
    by_length = s5.rows_by_length(tot)
    for tl, rows in by_length:
        if tl == 64:
            continue  # empty messages, and the shape-invalid rows forced to 0
        of_length = msgs if isinstance(rows, slice) else map(msgs.__getitem__, rows.tolist())
        rows_n[rows, 64:tl] = np.frombuffer(b"".join(of_length), np.uint8).reshape(-1, tl - 64)
    s5.write_padding(rows_n, by_length)
    # padded lanes hash zero blocks (nblocks 0 -> IV digest): vetoed by mask
    pnb = np.zeros(nb, np.int32)
    pnb[:n] = nblocks
    resident = _tables_serve((nb, bmax), sighting.tables)
    a_words = None if resident else _lane_words(key_rows.view("<u4"), nb)
    # The stream goes as a native-LE word view (free — no copy, no transpose;
    # the device does the block-layout shuffle and byte swap itself).
    return (a_words, r_words, s_words, buf.view("<u4"), pnb), host_ok, walk


_device_pool = None
_device_pool_lock = threading.Lock()


class _DeviceOwner:
    """One DAEMON device-owner thread: serializes dispatches (one chip,
    one stream) and gives the hybrid tier a genuinely async seam even if
    the PJRT client's execute blocks until completion. Deliberately not a
    ThreadPoolExecutor: its workers are joined at interpreter exit, so one
    dispatch stuck in the device runtime would hang process shutdown
    forever."""

    def __init__(self):
        self._q = queue.Queue()
        t = threading.Thread(target=self._run, name="cmtpu-dev", daemon=True)
        t.start()

    def _run(self):
        while True:
            fn, fut = self._q.get()
            if not fut.set_running_or_notify_cancel():
                continue
            try:
                fut.set_result(fn())
            except BaseException as e:  # surfaced at fut.result()
                fut.set_exception(e)

    def submit(self, fn):
        fut = Future()
        self._q.put((fn, fut))
        return fut


def _pool() -> _DeviceOwner:
    global _device_pool
    if _device_pool is None:
        with _device_pool_lock:
            if _device_pool is None:
                _device_pool = _DeviceOwner()
    return _device_pool


@functools.lru_cache(maxsize=1)
def _sharded_verify():
    """(local_device_count, sharded verify fn) when this PROCESS owns
    multiple chips, else None. Routes the shipped BatchVerifier seam
    across every process-local chip (ops/sharded's 1-D sig mesh —
    lane-sharded operands, zero collectives in the verify body) instead
    of leaving n-1 chips idle. Local, not global, devices: after
    jax.distributed joins a multi-host cluster, a mesh over the global
    device list would contain non-addressable devices and break every
    ordinary local verify."""
    n_dev = mesh_width()
    if n_dev <= 1:
        return None
    from cometbft_tpu.ops import sharded

    return n_dev, sharded.sharded_verify_fn(sharded.make_mesh(jax.local_devices()))


@functools.lru_cache(maxsize=2)
def _resident_programs(sharded: bool):
    """(build, verify) programs of resident columns: lane-sharded over the
    mesh (the tables on their last axis, zero collectives, as
    _sharded_verify) for a column whose bucket takes the mesh route, else
    single-device."""
    if not sharded:
        return jax.jit(build_key_tables), jax.jit(verify_core_resident)
    from cometbft_tpu.ops import sharded as sh

    mesh = sh.make_mesh(jax.local_devices())
    return sh.sharded_build_fn(mesh), sh.sharded_resident_fn(mesh)


def _mesh_sharded(key) -> bool:
    """Whether a (batch, block) bucket pair takes the lane-sharded mesh
    program: several chips, at/above the sharding floor, the chips dividing
    the bucket; never the host-hash program, whose shapes aren't sharded."""
    if key[1] == 0:
        return False
    sh = _sharded_verify()
    return sh is not None and key[0] >= mesh_floor() and key[0] % sh[0] == 0


def _tables_serve(key, tables) -> bool:
    """Whether a resident column's (tables_a, ok_a) serve a dispatch of this
    bucket pair whose lanes are the column's first. The resident program
    has ONE shape a column, the tables' own: a dispatch of fewer lanes is
    widened to it (_widen), so the walk of a planner over its shares loads
    no program a share. That pays while the share's own bucket is at least
    half the tables' lanes (on the chip the tables' 10,240 lanes take
    36.4 ms, the ladder 33.1 at 4,096 and 47.5 at 6,144); a smaller share
    keeps the ladder, and so does the host-hash program."""
    if tables is None or key[1] == 0:
        return False
    lanes = tables[1].shape[0]
    return lanes // 2 <= key[0] <= lanes


def _widen(operands, lanes: int):
    """Packed (r_words, s_words, msg_words, msg_nblocks) with zero lanes
    appended up to `lanes`: lanes the device evaluates and nobody reads."""
    pad = lanes - operands[0].shape[1]
    if pad == 0:
        return operands
    r_words, s_words, msg_words, msg_nblocks = operands
    return (
        np.pad(r_words, ((0, 0), (0, pad))),
        np.pad(s_words, ((0, 0), (0, pad))),
        np.pad(msg_words, ((0, pad), (0, 0))),
        np.pad(msg_nblocks, (0, pad)),
    )


def _route_for(operands, tables=None):
    """(program, mesh-sharded?) the routing layer would run for these packed
    operands: the lane-sharded multi-chip program when this process owns
    several chips and the bucket is at/above the sharding floor (the
    mesh-aware ladder guarantees such buckets divide the device count),
    else the single-device bucket program. Where `tables` serve the
    dispatch (_tables_serve) the resident program of the tables' own
    bucket, which takes the tables in the keys' place."""
    key = _bucket_key(operands)
    if _tables_serve(key, tables):
        lanes = tables[1].shape[0]
        sharded = _mesh_sharded((lanes, key[1]))
        resident = _resident_programs(sharded)[1]
        return (lambda _a_words, *rest: resident(*tables, *_widen(rest, lanes))), sharded
    if _mesh_sharded(key):
        return _sharded_verify()[1], True
    return _compiled(*key), False


def _verify_fn_for(operands):
    """Shared by batch_verify_submit and warmup so warmup precompiles what
    will actually run."""
    return _route_for(operands)[0]


def clear_compiled_caches() -> None:
    """Retrace seam for the fe-lowering tests: drops the program caches
    (the per-bucket single-device jits and the sharded-mesh jit), the cached
    mesh width and JAX's own trace cache, which is keyed by the function and
    its operand shapes and would otherwise hand a flipped lowering
    (field25519._ACCEL) the jaxpr of the one before."""
    _compiled.cache_clear()
    _sharded_verify.cache_clear()
    _resident_programs.cache_clear()
    mesh_width.cache_clear()
    jax.clear_caches()


# -- resident key columns ------------------------------------------------------------

# Bytes of window tables kept on each chip: a quarter of a v5e's 16 GiB,
# two 10,240-lane columns with room to spare. Oldest column out.
RESIDENT_MAX_BYTES = 4 << 30
TABLE_BYTES_PER_LANE = ed.DIGITS * 8 * 4 * fe.LIMBS * 4  # 139,264
# Columns seen but not (yet) built whose joined bytes are remembered.
_COLUMNS_REMEMBERED = 8

_SEEN, _WANTED, _BUILDING, _RESIDENT = range(4)


class _Column:
    """One remembered key column: its joined bytes, and once built its
    (tables_a, ok_a) on the device."""

    __slots__ = ("joined", "lanes", "state", "tables", "landed")

    def __init__(self, joined: bytes, lanes: int):
        self.joined = joined
        self.lanes = lanes
        self.state = _SEEN
        self.tables = None
        self.landed = threading.Event()  # set when a build ends, however


# What one look-up found: `tables`, the column's (tables_a, ok_a) where the
# call's keys are a resident column or a prefix of one, else None; `build`,
# the column whose build the call's dispatch is to be followed by, else None;
# `distinct`, how many keys a column refused for repeating one holds, else 0;
# `keys`, the column's well-formed keys as uint8[lanes, 32] rows (a view of
# the look-up's own join, which the pack then does not make again), else None.
Sighting = collections.namedtuple("Sighting", "tables build distinct keys", defaults=(0, None))
_NOTHING = Sighting(None, None)


class _ResidentColumns:
    """Which key columns the tier has seen, and the window tables of those
    it has seen twice. Policy in the module docstring; all of it is decided
    by the call's key bytes."""

    def __init__(self):
        self._lock = threading.Lock()
        self._by_first: dict[bytes, list[_Column]] = {}
        self._lru: collections.OrderedDict = collections.OrderedDict()  # id -> column
        self._closing = False  # the interpreter is exiting: start no build
        self._counters = dict.fromkeys(
            (
                "resident_lanes", "resident_calls", "resident_builds",
                "resident_build_ms", "resident_bytes", "resident_evictions",
                "resident_first_sightings",
                "resident_repeat_sightings", "resident_repeat_lanes",
            ),
            0,
        )

    def counters(self) -> dict:
        with self._lock:
            out = dict(self._counters)
        out["resident_build_ms"] = round(out["resident_build_ms"], 2)
        return out

    def count_call(self, lanes: int) -> None:
        with self._lock:
            self._counters["resident_calls"] += 1
            self._counters["resident_lanes"] += lanes

    def sight(self, pubs) -> Sighting:
        """The one look-up of a call, made where the whole column is in
        view (before any split): by (first key, length), confirmed by one
        compare of the joined bytes; a column that is a prefix of a resident
        one matches too. Counts the sighting of a whole column."""
        n = len(pubs)
        if n < _resident_min():
            return _NOTHING
        joined = _whole_column(pubs, 32)
        if joined is None:
            return _NOTHING  # a malformed key: never resident
        rows = np.frombuffer(joined, np.uint8).reshape(n, 32)
        with self._lock:
            col = self._match(joined, n)
            if col is None:
                # Distinct keys only: a column that repeats a key is several
                # heights of a smaller set (a blocksync prefetch window) and
                # wants THAT set's tables indexed by lane, which is another
                # mechanism.
                keys = {joined[i : i + 32] for i in range(0, 32 * n, 32)}
                if len(keys) < n:
                    # Counted, not served: how much of the tier's traffic
                    # the smaller set's tables would carry.
                    self._counters["resident_repeat_sightings"] += 1
                    self._counters["resident_repeat_lanes"] += n
                    return Sighting(None, None, len(keys), rows)
                if self._nbytes(n) <= RESIDENT_MAX_BYTES:
                    self._remember(_Column(joined, n))
                return Sighting(None, None, 0, rows)
            self._lru.move_to_end(id(col))
            if col.state == _SEEN:
                col.state = _WANTED  # the second sighting
            if col.state == _WANTED:
                return Sighting(None, col, 0, rows)
        # A call that arrives while its own column's tables are being built
        # would queue behind the build on the device-owner thread anyway:
        # it waits here and rides them, instead of walking the planner to
        # one more ladder bucket (a program load of seconds) for nothing.
        col.landed.wait()
        return Sighting(col.tables, None, 0, rows)

    def _match(self, joined: bytes, n: int):
        """The remembered column these n keys are (whichever has tables, if
        two do), else a resident one they are a prefix of, else None."""
        found = None
        for c in self._by_first.get(joined[:32], ()):
            if c.lanes == n and c.joined == joined:
                found = c
                if c.tables is not None:
                    break
            elif c.lanes > n and c.tables is not None and c.joined.startswith(joined):
                return c
        return found

    @staticmethod
    def _nbytes(lanes: int) -> int:
        """Table bytes a column of `lanes` keys holds on each chip."""
        return TABLE_BYTES_PER_LANE * bucket_for(lanes) // mesh_width()

    def _remember(self, col: _Column) -> None:
        self._counters["resident_first_sightings"] += 1
        self._by_first.setdefault(col.joined[:32], []).append(col)
        self._lru[id(col)] = col
        waiting = [c for c in self._lru.values() if c.tables is None]
        for old in waiting[: max(0, len(waiting) - _COLUMNS_REMEMBERED)]:
            if old.state != _BUILDING:
                self._forget(old)

    def _forget(self, col: _Column) -> None:
        del self._lru[id(col)]
        cols = self._by_first[col.joined[:32]]
        cols.remove(col)
        if not cols:
            del self._by_first[col.joined[:32]]
        if col.tables is not None:
            # a dispatch in flight keeps its own reference to the arrays
            col.tables = None
            self._counters["resident_bytes"] -= self._nbytes(col.lanes)

    def queue_build(self, col: _Column) -> None:
        """Once per column: build(col) goes on the device-owner thread's
        queue, so behind the dispatch of the call that asks."""
        with self._lock:
            if col.state != _WANTED or self._closing:
                return
            col.state = _BUILDING
        fut = _pool().submit(lambda: self.build(col))
        # Nobody waits for a build, except the interpreter's exit: a daemon
        # thread left inside the device runtime while Python finalizes
        # aborts the process.
        atexit.register(self._close, fut)

    def _close(self, fut: Future) -> None:
        self._closing = True
        if fut.running():
            try:  # bounded: a wedged device must not hold the exit for good
                fut.exception(timeout=120)
            except TimeoutError:
                pass

    def build(self, col: _Column) -> None:
        """On the device-owner thread: the column's tables, by one device
        program. A build that fails (the device's memory) forgets the
        column; its calls ride the ladder as before."""
        bucket = bucket_for(col.lanes)
        nbytes = self._nbytes(col.lanes)
        t0 = time.perf_counter()
        try:
            if self._closing:
                return
            with trace.span("device.table_build", lanes=bucket, bytes=nbytes):
                a_enc = np.zeros((bucket, 32), np.uint8)
                a_enc[: col.lanes] = np.frombuffer(col.joined, np.uint8).reshape(-1, 32)
                build_fn = _resident_programs(_mesh_sharded((bucket, 1)))[0]
                tables = jax.block_until_ready(build_fn(unpack.bytes_to_words(a_enc)))
            with self._lock:
                if id(col) not in self._lru:
                    return  # forgotten meanwhile
                col.tables, col.state = tuple(tables), _RESIDENT
                self._counters["resident_builds"] += 1
                self._counters["resident_build_ms"] += (time.perf_counter() - t0) * 1000
                self._counters["resident_bytes"] += nbytes
                for old in list(self._lru.values()):  # oldest first
                    if self._counters["resident_bytes"] <= RESIDENT_MAX_BYTES:
                        break
                    if old is not col and old.tables is not None:
                        self._forget(old)
                        self._counters["resident_evictions"] += 1
        except Exception as e:  # nobody reads a build's result: say it here
            print(f"ed25519_kernel: table build of {col.lanes} keys failed: "
                  f"{type(e).__name__}: {e}", file=sys.stderr, flush=True)
            with self._lock:
                if id(col) in self._lru:
                    self._forget(col)
        finally:
            col.landed.set()

    def clear(self) -> None:
        """Forget every column (tests; tables of another lowering or mesh)."""
        with self._lock:
            for col in list(self._lru.values()):
                self._forget(col)


def _resident_min() -> int:
    """Smallest key column worth tables: the hybrid tier's split threshold,
    below which a batch never reaches the device."""
    try:
        return max(1, int(os.environ.get("CMTPU_HYBRID_MIN", "2048")))
    except ValueError:
        return 2048


_columns = _ResidentColumns()
sight_column = _columns.sight
resident_counters = _columns.counters


# Held while a batch is packed on the host: Python under the interpreter
# lock, on the critical path of the dispatch it belongs to. A sidecar's
# connection threads pass through it before they touch a frame
# (sidecar/service.py `_answer`), so a stream that arrives while another
# request's dispatch packs waits those few milliseconds instead of taking
# them from the packer (PR 32: `device.pack` read 17-25 ms, not 6.5, with
# three streams decoding beside it, and the amount moved from run to run).
PACK_GATE = threading.Lock()
# Dispatches packed, and those of them a malformed entry sent through the
# per-lane walk (_host_checks); counted under PACK_GATE.
_pack_counters = {"pack_calls": 0, "pack_walk_calls": 0}


def pack_counters() -> dict:
    return dict(_pack_counters)


def batch_verify_submit(pubs, msgs, sigs, sighting: Sighting | None = None):
    """Pack on the calling thread, dispatch on the device-owner thread,
    return a collect() -> (ok, bitmap) closure. The hybrid backend runs its
    host MSM share between submit and collect; callers that want the
    blocking behavior just collect immediately (batch_verify below).
    `sighting` is sight_column's answer for the column these lanes are the
    first of, from a caller that saw the whole column before it split it;
    a caller with none (a bare device tier) has the look-up made here."""
    n = len(pubs)
    if sighting is None:
        sighting = sight_column(pubs) if n else _NOTHING
    with trace.span("device.pack", lanes=n) as pack, PACK_GATE:
        operands, host_ok, walk = _pack(pubs, msgs, sigs, sighting)
        key = _bucket_key(operands)
        # the pack left the keys' words out where the sighting's tables serve
        tables = sighting.tables if operands[0] is None else None
        if tables is not None:  # the resident program runs the tables' own bucket
            key = (tables[1].shape[0], key[1])
        pack.set(bucket=key[0], resident=tables is not None, walk=walk)
        _pack_counters["pack_calls"] += 1  # under PACK_GATE
        _pack_counters["pack_walk_calls"] += walk
    fn, sharded = _route_for(operands, tables)
    if sharded:
        _mesh_count("sharded_dispatches")
        _mesh_count("padded_lanes", key[0] - n)
    if tables is not None:
        _columns.count_call(n)
    caller = trace.current()

    def run():  # on the device-owner thread, traced under the caller
        started = time.perf_counter()
        with trace.span(
            "device.run", parent=caller, bucket=key[0], sharded=sharded,
            resident=tables is not None,
        ):
            dev_ok = np.asarray(fn(*operands))
        return dev_ok, (started, time.perf_counter())

    fut = _pool().submit(run)
    if sighting.build is not None:
        # BEHIND this call's dispatch: no call waits for a build it did not need.
        _columns.queue_build(sighting.build)

    def collect() -> tuple[bool, list]:
        with trace.span("device.wait"):
            dev_ok, collect.run_times = fut.result()
        with trace.span("device.unpack"):
            ok = host_ok[:n] & dev_ok[:n]
            return bool(ok.all()), ok.tolist()

    # (batch bucket, block bucket, lanes of the resident tables or 0) — the
    # compiled-program identity, so callers can tell a first dispatch (XLA
    # compile) from a steady one, and the ladder's walls from the tables'.
    collect.program_key = (*key, key[0] if tables is not None else 0)
    # (start, return) of the program on the device-owner thread's clock,
    # time.perf_counter(), once collect() has run: the device's wall is known
    # to the caller even when it came to collect() long after the device
    # finished. Always stamped; the device.run span exists only while traced.
    collect.run_times = None
    return collect


def batch_verify(pubs, msgs, sigs) -> tuple[bool, list]:
    """The crypto.BatchVerifier device path: (overall ok, per-sig bitmap)."""
    n = len(pubs)
    if n == 0:
        return False, []
    return batch_verify_submit(pubs, msgs, sigs)()
