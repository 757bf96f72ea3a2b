"""Blocksync reactor (reference: blocksync/reactor.go, channel 0x40).

The sync loop validates each block with the NEXT block's LastCommit via
VerifyCommitLight — the TPU-batched hot path (reactor.go:355-400, call at
:360, SURVEY.md §3.3) — then applies it; switches to consensus when caught
up.

Wire (proto/tendermint/blocksync/types.proto): Message oneof
{block_request=1{height}, no_block_response=2{height}, block_response=3
{block}, status_request=4, status_response=5{height, base}}.
"""

from __future__ import annotations

import os
import threading
import time

from cometbft_tpu.blocksync.pool import BlockPool
from cometbft_tpu.libs import trace
from cometbft_tpu.p2p.conn.connection import ChannelDescriptor
from cometbft_tpu.p2p.reactor import BLOCKSYNC_CHANNEL, Reactor
from cometbft_tpu.sidecar import engine
from cometbft_tpu.types.block import Block, BlockID
from cometbft_tpu.wire import proto as wire


def _encode(tag: int, inner: bytes) -> bytes:
    return wire.field_message(tag, inner, emit_empty=True)


def encode_block_request(height: int) -> bytes:
    return _encode(1, wire.field_varint(1, height))


def encode_no_block_response(height: int) -> bytes:
    return _encode(2, wire.field_varint(1, height))


def encode_block_response(block: Block) -> bytes:
    return _encode(3, wire.field_message(1, block.encode(), emit_empty=True))


def encode_status_request() -> bytes:
    return _encode(4, b"")


def encode_status_response(height: int, base: int) -> bytes:
    return _encode(5, wire.field_varint(1, height) + wire.field_varint(2, base))


def decode_message(data: bytes):
    f = wire.decode_fields(data)
    if 1 in f:
        return ("block_request", wire.get_varint(wire.decode_fields(wire.get_bytes(f, 1)), 1))
    if 2 in f:
        return ("no_block_response", wire.get_varint(wire.decode_fields(wire.get_bytes(f, 2)), 1))
    if 3 in f:
        inner = wire.decode_fields(wire.get_bytes(f, 3))
        return ("block_response", Block.decode(wire.get_bytes(inner, 1)))
    if 4 in f:
        return ("status_request", None)
    if 5 in f:
        inner = wire.decode_fields(wire.get_bytes(f, 5))
        return ("status_response", (wire.get_varint(inner, 1), wire.get_varint(inner, 2)))
    raise ValueError("unknown blocksync message")


# First byte of an encoded block_response: field 3, length-delimited.
_BLOCK_RESPONSE_KEY = bytes([3 << 3 | 2])


class BlocksyncReactor(Reactor):
    """blocksync/reactor.go Reactor."""

    def __init__(
        self, state, block_exec, block_store, block_sync: bool,
        on_caught_up=None, clock=None,
    ):
        from cometbft_tpu.simnet.clock import MonotonicClock

        super().__init__("BLOCKSYNC")
        self.clock = clock or MonotonicClock()
        self.state = state
        self.block_exec = block_exec
        self.block_store = block_store
        self.block_sync_enabled = block_sync
        self.on_caught_up = on_caught_up  # fn(state) -> switch to consensus
        self.pool = BlockPool(
            state.last_block_height + 1, self._send_request, clock=self.clock
        )
        self._running = False
        self.synced = False
        self._prefetched_to = 0  # height up to which the window was batched
        # One-deep verify/apply pipeline: the prefetch producer (device-
        # bound commit verification for the window ahead) runs on a worker
        # while apply_block (app-bound) runs on the sync thread, so the
        # serial per-block decision path below stays unchanged and lands on
        # cache hits. CMTPU_BLOCKSYNC_PIPELINE=0 restores the inline
        # prefetch-then-verify ordering.
        self._pipeline_enabled = (
            os.environ.get("CMTPU_BLOCKSYNC_PIPELINE", "1") != "0"
        )
        self._pf_job: tuple[threading.Event, list[float]] | None = None
        self.pipeline_overlap_ms = 0.0  # verify/apply overlap accumulated
        # Always counted (counters(), on /metrics through the node's lazy
        # gauges): where the sync thread's time goes when it is not applying
        # a block. The two waits are timed round their spans, so a traced
        # run's counter reads the span plus the span's own enter and exit.
        self.heights_applied = 0
        self.fetch_wait_ms = 0.0   # no pair of blocks to verify yet
        self.verify_wait_ms = 0.0  # blocked on the prefetch worker
        self.idle_sleeps = 0       # 10 ms sleeps of the pool routine
        self.redo_requests = 0     # blocks refused and asked for again
        self.block_bytes_received = 0  # encoded block responses, as they left the wire
        # The prefetch worker's own (one job at a time): windows it handed
        # to the batch seam, their lanes, and its time from the first look
        # at the window to the seam's answer.
        self.prefetch_windows = 0
        self.prefetch_lanes = 0
        self.prefetch_ms = 0.0
        # The fetch wait under way: (its span, perf_counter at its start,
        # idle_sleeps at its start), from the first peek that found no pair
        # to the next that finds one.
        self._fetch_wait: tuple | None = None

    def get_channels(self):
        return [
            ChannelDescriptor(
                BLOCKSYNC_CHANNEL, priority=5, send_queue_capacity=1000,
                recv_message_capacity=50 * 1024 * 1024,
            )
        ]

    def start(self) -> None:
        self._running = True
        if self.block_sync_enabled:
            threading.Thread(target=self._pool_routine, daemon=True, name="blocksync-pool").start()

    def stop(self) -> None:
        self._running = False

    def switch_to_block_sync(self, state, block_exec=None) -> None:
        """reactor.go SwitchToBlockSync: statesync finished — start fast-sync
        from the freshly bootstrapped state (node.go:423-433 boot phasing)."""
        self.state = state
        if block_exec is not None:
            self.block_exec = block_exec
        self.synced = False
        with self.pool._mtx:
            self.pool.height = state.last_block_height + 1
        was_enabled = self.block_sync_enabled
        self.block_sync_enabled = True
        if self._running and not was_enabled:
            threading.Thread(target=self._pool_routine, daemon=True, name="blocksync-pool").start()

    # -- peers ----------------------------------------------------------------

    def add_peer(self, peer) -> None:
        peer.try_send(
            BLOCKSYNC_CHANNEL,
            encode_status_response(self.block_store.height(), self.block_store.base()),
        )
        peer.try_send(BLOCKSYNC_CHANNEL, encode_status_request())

    def remove_peer(self, peer, reason) -> None:
        self.pool.remove_peer(peer.id)

    def counters(self) -> dict:
        return {
            "heights_applied": self.heights_applied,
            "fetch_wait_ms": round(self.fetch_wait_ms, 3),
            "verify_wait_ms": round(self.verify_wait_ms, 3),
            "idle_sleeps": self.idle_sleeps,
            "redo_requests": self.redo_requests,
            "pipeline_overlap_ms": round(self.pipeline_overlap_ms, 3),
            "block_bytes_received": self.block_bytes_received,
            "prefetch_windows": self.prefetch_windows,
            "prefetch_lanes": self.prefetch_lanes,
            "prefetch_ms": round(self.prefetch_ms, 3),
            **self.pool.counters(),
        }

    def receive(self, chan_id: int, peer, msg_bytes: bytes) -> None:
        if msg_bytes[:1] == _BLOCK_RESPONSE_KEY:
            # the one message with real work behind it: block decode + pool
            self.block_bytes_received += len(msg_bytes)
            with trace.span("blocksync.decode", bytes=len(msg_bytes)):
                self._receive(peer, msg_bytes)
        else:
            self._receive(peer, msg_bytes)

    def _receive(self, peer, msg_bytes: bytes) -> None:
        kind, payload = decode_message(msg_bytes)
        if kind == "block_request":
            block = self.block_store.load_block(payload)
            if block is not None:
                peer.try_send(BLOCKSYNC_CHANNEL, encode_block_response(block))
            else:
                peer.try_send(BLOCKSYNC_CHANNEL, encode_no_block_response(payload))
        elif kind == "block_response":
            self.pool.add_block(peer.id, payload)
        elif kind == "status_request":
            peer.try_send(
                BLOCKSYNC_CHANNEL,
                encode_status_response(self.block_store.height(), self.block_store.base()),
            )
        elif kind == "status_response":
            height, base = payload
            self.pool.set_peer_range(peer.id, base, height)
        elif kind == "no_block_response":
            pass

    def _send_request(self, peer_id: str, height: int) -> None:
        peer = self.switch.get_peer(peer_id) if self.switch else None
        if peer is not None:
            peer.try_send(BLOCKSYNC_CHANNEL, encode_block_request(height))

    # -- sync loop (reactor.go:280-410 poolRoutine) ---------------------------

    def _pool_routine(self) -> None:
        try:
            self._pool_loop()
        finally:
            self._fetch_wait_end()

    def _fetch_wait_begin(self) -> None:
        if self._fetch_wait is None:
            t0 = time.perf_counter()
            wait = trace.span("blocksync.fetch_wait")
            wait.__enter__()
            self._fetch_wait = (wait, t0, self.idle_sleeps)

    def _fetch_wait_end(self) -> None:
        if self._fetch_wait is not None:
            wait, t0, sleeps0 = self._fetch_wait
            self._fetch_wait = None
            wait.set(sleeps=self.idle_sleeps - sleeps0)
            wait.__exit__(None, None, None)
            self.fetch_wait_ms += (time.perf_counter() - t0) * 1000.0

    def _pool_loop(self) -> None:
        status_tick = 0.0
        while self._running and not self.synced:
            with trace.span("blocksync.make_requests") as req:
                req.set(sent=self.pool.make_requests())
            now = self.clock.now()
            if now - status_tick > 10:
                status_tick = now
                if self.switch:
                    self.switch.broadcast(BLOCKSYNC_CHANNEL, encode_status_request())
            if self._try_sync_one():
                continue  # immediately try the next pair
            # IsCaughtUp needs >= 1 peer STATUS (pool._peers non-empty), so a
            # fresh all-genesis net switches to consensus as soon as statuses
            # arrive — matching reactor.go's switchToConsensusTicker, which
            # gates on IsCaughtUp alone (a max-height>0 guard would deadlock
            # the everyone-at-height-0 boot).
            if self.pool.is_caught_up():
                self.synced = True
                if self.on_caught_up:
                    self.on_caught_up(self.state)
                return
            self.idle_sleeps += 1
            self.clock.sleep(0.01)

    # Prefetch window: how many consecutive fetched blocks to batch-verify
    # in ONE device dispatch. A window of 32 blocks covers 31 commits (each
    # block's commit rides in the next block's LastCommit), so at 1,024
    # validators it is 31,744 lanes, which pad to the 32768 bucket; the
    # verified-triple cache then makes both the trySync VerifyCommitLight
    # AND ApplyBlock's full LastCommit check cache hits.
    PREFETCH_WINDOW = 32
    # Signature budget for one prefetch dispatch: stay within the largest
    # precompiled device bucket AND well under the verified-triple cache
    # (ed25519._VERIFIED_MAX = 131072), else a large validator set makes the
    # window force a one-off oversized XLA compile and evict its own cache
    # entries before trySync consumes them.
    PREFETCH_MAX_SIGS = 32768

    def _prefetch_verify_window(self) -> None:
        """TPU-first fast sync: while validator sets are unchanged
        (header.validators_hash pins the exact set that signed each
        commit), the signatures of MANY consecutive blocks' commits are
        independent — verify them all in one batched device call and let
        the per-commit protocol checks hit the verified-triple cache.
        Failures are simply not cached; the per-block path then attributes
        the bad block and punishes the peer as before."""
        from cometbft_tpu.crypto import ed25519

        if self.pool.height < self._prefetched_to:
            return
        vals = self.state.validators
        # Clamp the window in SIGNATURES, not blocks (a 10k-validator set
        # at 32 blocks would be ~320k triples in one dispatch).  Below 3
        # blocks there is nothing to batch (window covers window-1 commits);
        # skip before paying the pool-mutex peek.
        window_blocks = min(
            self.PREFETCH_WINDOW,
            self.PREFETCH_MAX_SIGS // max(1, len(vals.validators)),
        )
        if window_blocks < 3:
            return
        window = self.pool.peek_window(window_blocks)
        if len(window) < 3:
            return
        # Only ed25519 carries the verified-triple cache; for other key
        # types a prefetch would be pure extra work (three verifications
        # per commit instead of two).
        if not all(
            isinstance(v.pub_key, ed25519.PubKey) for v in vals.validators
        ):
            self._prefetched_to = self.pool.height + self.PREFETCH_WINDOW
            return
        # A pure optimization must never take down the sync thread: blocks
        # here are unvalidated peer input (oversized signatures etc. make
        # bv.add raise), and backend hiccups surface from bv.verify — the
        # per-block path re-verifies, attributes, and punishes as before.
        t0 = time.perf_counter()
        try:
            with trace.span("blocksync.prefetch") as job:
                # The walk of the window into triples: sign bytes and one
                # bv.add a lane, everything before the seam.
                with trace.span("blocksync.prefetch_collect") as collect:
                    bv = ed25519.BatchVerifier()
                    vh = vals.hash()
                    chain_id = self.state.chain_id
                    covered = 0
                    for j in range(len(window) - 1):
                        blk, nxt = window[j], window[j + 1]
                        commit = nxt.last_commit
                        if (
                            blk.header.validators_hash != vh
                            or commit is None
                            or commit.height != blk.header.height
                            or len(commit.signatures) != len(vals.validators)
                        ):
                            break
                        sbs = commit.vote_sign_bytes_all(chain_id)
                        for idx, cs in enumerate(commit.signatures):
                            if cs.is_absent():
                                continue
                            bv.add(vals.validators[idx].pub_key, sbs[idx], cs.signature)
                        covered += 1
                    collect.set(blocks=covered, lanes=len(bv))
                job.set(blocks=covered, lanes=len(bv))
                self._prefetched_to = self.pool.height + max(covered, 1)
                if covered >= 2 and len(bv):
                    # Blocksync-class engine admission (the untagged default,
                    # made explicit): window pre-verify yields to consensus
                    # votes but outranks ingress and light prewarm.
                    with engine.submission_class(engine.CLASS_BLOCKSYNC):
                        bv.verify()  # populates the cache; bad sigs fall to per-block
                    self.prefetch_windows += 1
                    self.prefetch_lanes += len(bv)
        except Exception:
            self._prefetched_to = self.pool.height + 1
        finally:
            self.prefetch_ms += (time.perf_counter() - t0) * 1000.0

    # -- verify/apply pipeline ------------------------------------------------

    def _pipeline_submit(self) -> bool:
        """Kick the prefetch producer on a worker so it overlaps the
        apply_block that follows. One-deep: a still-running job means the
        producer is already ahead — never stack a second one. Returns
        whether it started a worker."""
        job = self._pf_job
        if job is not None and not job[0].is_set():
            return False
        done = threading.Event()
        times = [time.monotonic(), 0.0]

        def run():
            try:
                self._prefetch_verify_window()
            finally:
                times[1] = time.monotonic()
                done.set()

        self._pf_job = (done, times)
        threading.Thread(target=run, daemon=True, name="blocksync-prefetch").start()
        return True

    def _pipeline_wait(self) -> None:
        """Barrier before the serial verify: the producer must have finished
        populating the verified cache for the height we are about to check.
        Bounded — _prefetch_verify_window swallows its own errors, so the
        worker always terminates."""
        job = self._pf_job
        if job is not None:
            job[0].wait(timeout=60.0)

    def _pipeline_account(self, apply_t0: float, apply_t1: float) -> None:
        job = self._pf_job
        if job is None:
            return
        done, times = job
        end = times[1] if done.is_set() else apply_t1
        overlap = min(apply_t1, end) - max(apply_t0, times[0])
        if overlap > 0:
            self.pipeline_overlap_ms += overlap * 1000.0

    def _try_sync_one(self) -> bool:
        """reactor.go:340-400 trySync: verify `first` with `second.LastCommit`
        (VerifyCommitLight — batched on device), then apply."""
        first, second = self.pool.peek_two_blocks()
        if first is None or second is None:
            self._fetch_wait_begin()
            return False
        self._fetch_wait_end()
        with trace.span("blocksync.sync_one", height=first.header.height) as one:
            applied = self._sync_pair(first, second)
            one.set(applied=applied)
        return applied

    def _sync_pair(self, first, second) -> bool:
        if self._pipeline_enabled:
            t0 = time.perf_counter()
            with trace.span("blocksync.verify_wait"):
                self._pipeline_wait()
            self.verify_wait_ms += (time.perf_counter() - t0) * 1000.0
        else:
            self._prefetch_verify_window()
        with trace.span("blocksync.part_set"):
            first_parts = first.make_part_set()
            first_id = BlockID(first.hash(), first_parts.header())
        try:
            # ★ the TPU call (types/validation.go:59 via blocksync/reactor.go:360)
            with trace.span("blocksync.verify_light"):
                self.state.validators.verify_commit_light(
                    self.state.chain_id, first_id, first.header.height,
                    second.last_commit,
                )
            with trace.span("blocksync.validate"):
                self.block_exec.validate_block(self.state, first)
        except Exception:
            self.redo_requests += 1
            bad_peer = self.pool.redo_request(first.header.height)
            if bad_peer and self.switch:
                peer = self.switch.get_peer(bad_peer)
                if peer:
                    self.switch.stop_peer_for_error(peer, "sent us an invalid block")
            return False
        with trace.span("blocksync.save"):
            self.block_store.save_block(first, first_parts, second.last_commit)
        if self._pipeline_enabled:
            # Overlap the next window's verification (device) with this
            # block's application (app). The worker only POPULATES the
            # verified-triple cache — the accepting verify_commit_light
            # above still runs serially on this thread, so a validator-set
            # change simply misses the cache and verifies inline.
            with trace.span("blocksync.pipeline_submit") as kick:
                kick.set(started=self._pipeline_submit())
            t0 = time.monotonic()
            with trace.span("blocksync.apply"):
                self.state, _ = self.block_exec.apply_block(self.state, first_id, first)
            self._pipeline_account(t0, time.monotonic())
        else:
            with trace.span("blocksync.apply"):
                self.state, _ = self.block_exec.apply_block(self.state, first_id, first)
        self.pool.pop_request()
        self.heights_applied += 1
        return True
