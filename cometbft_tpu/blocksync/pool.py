"""Block pool: tracks in-flight block requests across peers
(reference: blocksync/pool.go).

Requesters cover a moving window of heights (~600 in flight, pool.go:63);
peers advertise their heights via status messages; timed-out or bad peers
get their requests redistributed. A request goes to the peer with the
fewest requests pending, and to none that holds MAX_PENDING_PER_PEER
(pool.go pickIncrAvailablePeer, maxPendingRequestsPerPeer): a catch-up is
carried by every peer's connection, not by the first one's. pool.go ranges
over a Go map, so among equals its choice is random; here it goes round.
"""

from __future__ import annotations

import threading
import time

MAX_PENDING_REQUESTS = 600
MAX_PENDING_PER_PEER = 20  # pool.go maxPendingRequestsPerPeer
REQUEST_TIMEOUT = 15.0
POOL_WINDOW = 200


class _Requester:
    def __init__(self, height: int):
        self.height = height
        self.peer_id: str | None = None
        self.block = None
        self.requested_at = 0.0


class BlockPool:
    """blocksync/pool.go BlockPool."""

    def __init__(self, start_height: int, send_request, clock=None):
        from cometbft_tpu.simnet.clock import MonotonicClock

        self.height = start_height  # next height to sync
        self._send_request = send_request  # fn(peer_id, height)
        self.clock = clock or MonotonicClock()
        self._mtx = threading.RLock()
        self._requesters: dict[int, _Requester] = {}
        self._peers: dict[str, int] = {}  # peer_id -> reported height
        self.max_peer_height = 0
        self._last_advance = self.clock.now()
        self._requests_sent = 0
        self._requests_to: dict[str, int] = {}  # live peer_id -> requests sent to it
        self._asked_last: str | None = None

    # -- peers ----------------------------------------------------------------

    def set_peer_range(self, peer_id: str, base: int, height: int) -> None:
        with self._mtx:
            self._peers[peer_id] = height
            self.max_peer_height = max(self.max_peer_height, height)

    def remove_peer(self, peer_id: str) -> None:
        with self._mtx:
            self._peers.pop(peer_id, None)
            self._requests_to.pop(peer_id, None)
            for req in self._requesters.values():
                if req.peer_id == peer_id and req.block is None:
                    req.peer_id = None

    # -- scheduling -----------------------------------------------------------

    def make_requests(self) -> int:
        """Spawn requesters for the window and (re)assign idle ones.
        Returns how many block requests it sent."""
        sent = 0
        with self._mtx:
            for h in range(self.height, min(self.height + POOL_WINDOW, self.max_peer_height + 1)):
                if h not in self._requesters:
                    if len(self._requesters) >= MAX_PENDING_REQUESTS:
                        break
                    self._requesters[h] = _Requester(h)
            now = self.clock.now()
            pending = self.pending_by_peer()
            for req in self._requesters.values():
                if req.block is not None:
                    continue
                if req.peer_id is not None:
                    if now - req.requested_at < REQUEST_TIMEOUT:
                        continue
                    if req.peer_id in pending:  # timed out: its place is free again
                        pending[req.peer_id] -= 1
                    req.peer_id = None
                peer = self._pick_peer(req.height, pending)
                if peer is None:
                    continue
                req.peer_id = self._asked_last = peer
                req.requested_at = now
                pending[peer] += 1
                self._requests_sent += 1
                self._requests_to[peer] = self._requests_to.get(peer, 0) + 1
                self._send_request(peer, req.height)
                sent += 1
        return sent

    def pending_by_peer(self) -> dict[str, int]:
        """Requests each known peer has been sent and not yet answered:
        counted from the requesters, so a block that arrives, a redo and a
        removed peer all give their place back without being told to."""
        with self._mtx:
            pending = dict.fromkeys(self._peers, 0)
            for req in self._requesters.values():
                if req.block is None and req.peer_id in pending:
                    pending[req.peer_id] += 1
            return pending

    def _pick_peer(self, height: int, pending: dict[str, int]) -> str | None:
        """The peer tall enough with the fewest requests pending, none at
        MAX_PENDING_PER_PEER. Among equals the first after the peer asked
        last: a joiner that applies slower than its peers answer finds them
        all at 0 on every turn, and would else ask the first for everything."""
        peers = list(self._peers)
        if self._asked_last in self._peers:
            at = peers.index(self._asked_last) + 1
            peers = peers[at:] + peers[:at]
        best = None
        for peer_id in peers:
            if self._peers[peer_id] < height or pending[peer_id] >= MAX_PENDING_PER_PEER:
                continue
            if best is None or pending[peer_id] < pending[best]:
                best = peer_id
        return best

    def requests_by_peer(self) -> dict[str, int]:
        """Requests sent so far to each peer still known."""
        with self._mtx:
            return dict(self._requests_to)

    def counters(self) -> dict:
        """`requests_sent` over the pool's life; the other two over the
        peers it still knows, so a peer that left is not counted as a link."""
        with self._mtx:
            return {
                "requests_sent": self._requests_sent,
                "requests_to_busiest_peer": max(self._requests_to.values(), default=0),
                "peers_asked": len(self._requests_to),
            }

    # -- block flow -----------------------------------------------------------

    def add_block(self, peer_id: str, block) -> bool:
        """pool.go:246 AddBlock."""
        with self._mtx:
            req = self._requesters.get(block.header.height)
            if req is None or req.block is not None:
                return False
            req.block = block
            req.peer_id = peer_id
            return True

    def peek_two_blocks(self):
        """pool.go:193 PeekTwoBlocks: (first, second) at height, height+1."""
        with self._mtx:
            first = self._requesters.get(self.height)
            second = self._requesters.get(self.height + 1)
            return (
                first.block if first else None,
                second.block if second else None,
            )

    def peek_window(self, max_k: int) -> list:
        """Consecutive fetched blocks starting at the sync height (up to
        max_k) — the prefetch window the reactor batch-verifies in one
        device dispatch."""
        with self._mtx:
            out = []
            h = self.height
            while len(out) < max_k:
                req = self._requesters.get(h)
                if req is None or req.block is None:
                    break
                out.append(req.block)
                h += 1
            return out

    def pop_request(self) -> None:
        """Advance after the first block validated + applied."""
        with self._mtx:
            self._requesters.pop(self.height, None)
            self.height += 1
            self._last_advance = self.clock.now()

    def redo_request(self, height: int) -> str | None:
        """Invalid block: drop both pending blocks, re-request (reactor.go:375)."""
        with self._mtx:
            bad_peer = None
            for h in (height, height + 1):
                req = self._requesters.get(h)
                if req is not None:
                    if bad_peer is None:
                        bad_peer = req.peer_id
                    req.block = None
                    req.peer_id = None
            return bad_peer

    def is_caught_up(self) -> bool:
        """pool.go IsCaughtUp."""
        with self._mtx:
            if not self._peers:
                return False
            return self.height >= self.max_peer_height

    def stalled_for(self) -> float:
        with self._mtx:
            return self.clock.now() - self._last_advance
