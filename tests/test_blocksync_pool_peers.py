"""`BlockPool` peer selection (blocksync/pool.go pickIncrAvailablePeer,
maxPendingRequestsPerPeer) against a scripted clock: no threads, no
network. A scripted peer answers a request when the script says so."""

from __future__ import annotations

import types

import pytest

from cometbft_tpu.blocksync import pool as pool_mod
from cometbft_tpu.blocksync.pool import MAX_PENDING_PER_PEER, REQUEST_TIMEOUT, BlockPool


class ScriptedClock:
    def __init__(self):
        self.t = 0.0

    def now(self) -> float:
        return self.t

    def sleep(self, seconds: float) -> None:
        self.t += seconds


def _block(height: int):
    return types.SimpleNamespace(header=types.SimpleNamespace(height=height))


class Script:
    """A pool, the requests it sent, and peers that answer on demand."""

    def __init__(self, n_peers: int, tip: int):
        self.clock = ScriptedClock()
        self.sent: list[tuple[str, int]] = []
        self.pool = BlockPool(1, lambda peer, h: self.sent.append((peer, h)), clock=self.clock)
        self.peers = [f"peer{i}" for i in range(n_peers)]
        for p in self.peers:
            self.pool.set_peer_range(p, 1, tip)
        self.unanswered: list[tuple[str, int]] = []
        self.most_pending = 0

    def turn(self) -> int:
        """One turn of the reactor's loop: ask, and note the fullest peer."""
        before = len(self.sent)
        n = self.pool.make_requests()
        assert n == len(self.sent) - before
        self.unanswered += self.sent[before:]
        pending = self.pool.pending_by_peer()
        self.most_pending = max([self.most_pending, *pending.values()])
        return n

    def answer(self, count: int, silent=()) -> None:
        """The oldest `count` requests are answered, except by silent peers."""
        kept, answered = [], 0
        for peer, h in self.unanswered:
            if peer in silent or answered >= count:
                kept.append((peer, h))
                continue
            self.pool.add_block(peer, _block(h))
            answered += 1
        self.unanswered = kept

    def apply_ready(self) -> int:
        applied = 0
        while all(b is not None for b in self.pool.peek_two_blocks()):
            self.pool.pop_request()
            applied += 1
        return applied


SIZES = [(1, 30), (2, 45), (3, 77), (4, 100), (7, 333)]


@pytest.mark.parametrize("n_peers, tip", SIZES)
def test_no_peer_holds_more_than_its_cap_and_every_peer_is_asked(n_peers, tip):
    s = Script(n_peers, tip)
    for _ in range(10 * tip):
        s.turn()
        s.answer(3)
        s.apply_ready()
        s.clock.sleep(0.01)
        if s.pool.height >= tip:
            break
    assert s.pool.height == tip, "the tip itself has no next block to verify it"
    assert s.most_pending <= MAX_PENDING_PER_PEER
    assert {p for p, _ in s.sent} == set(s.peers)
    assert sorted(h for _, h in s.sent) == list(range(1, tip + 1)), "each height asked for once"
    c = s.pool.counters()
    assert c["requests_sent"] == tip and c["peers_asked"] == n_peers
    by_peer = [sum(p == q for q, _ in s.sent) for p in s.peers]
    assert c["requests_to_busiest_peer"] == max(by_peer)
    if n_peers > 1 and tip >= 4 * n_peers:
        assert max(by_peer) - min(by_peer) <= MAX_PENDING_PER_PEER, "the peers share the work"


@pytest.mark.parametrize("n_peers, tip", SIZES)
def test_first_turn_fills_every_peer_to_the_cap_and_no_further(n_peers, tip):
    s = Script(n_peers, tip)
    want = min(tip, pool_mod.POOL_WINDOW, n_peers * MAX_PENDING_PER_PEER)
    assert s.turn() == want
    assert s.turn() == 0, "nothing was answered: nothing more may be asked"
    pending = s.pool.pending_by_peer()
    assert sum(pending.values()) == want
    assert max(pending.values()) - min(pending.values()) <= 1, "fewest pending first"
    assert [h for _, h in s.sent] == list(range(1, want + 1)), "in order of height"


@pytest.mark.parametrize("n_peers", [2, 3, 4, 7])
def test_peers_that_answer_at_once_are_asked_in_turn(n_peers):
    """A joiner that applies slower than its peers answer finds every peer
    at 0 pending on every turn: the tie goes round, not to the first."""
    s = Script(n_peers, 10**6)
    while s.turn():  # until the whole look-ahead is fetched
        s.answer(10**6)
    first = len(s.sent)
    for _ in range(40 * n_peers):
        s.pool.pop_request()  # one height applied: the look-ahead moves by one
        assert s.turn() == 1
        s.answer(10**6)       # answered before the next turn
    by_peer = [sum(p == q for q, _ in s.sent[first:]) for p in s.peers]
    assert max(by_peer) - min(by_peer) <= 1, by_peer


def test_a_single_peer_is_asked_for_every_height_in_order_as_before():
    """What one peer saw before the cap it sees now, 20 at a time."""
    s = Script(1, 60)
    asked = 0
    while s.pool.height < 60:
        asked += s.turn()
        assert s.pool.pending_by_peer() == {"peer0": len(s.unanswered)}
        s.answer(5)
        s.apply_ready()
    assert s.sent == [("peer0", h) for h in range(1, 61)]
    assert asked == 60 and s.most_pending == MAX_PENDING_PER_PEER
    assert s.pool.counters() == {
        "requests_sent": 60, "requests_to_busiest_peer": 60, "peers_asked": 1,
    }


@pytest.mark.parametrize("n_peers, tip", [(2, 45), (4, 100), (7, 333)])
def test_a_removed_peers_heights_are_asked_for_elsewhere(n_peers, tip):
    s = Script(n_peers, tip)
    s.turn()
    gone = s.peers[1]
    orphans = sorted(h for p, h in s.unanswered if p == gone)
    assert orphans
    s.pool.remove_peer(gone)
    s.unanswered = [(p, h) for p, h in s.unanswered if p != gone]
    assert gone not in s.pool.pending_by_peer() and gone not in s.pool.requests_by_peer()
    c = s.pool.counters()
    assert c["peers_asked"] == n_peers - 1 and c["requests_sent"] == len(s.sent)
    s.answer(len(orphans))  # the others answer, which frees places for the orphans
    first_again = len(s.sent)
    s.turn()
    again = s.sent[first_again:]
    assert sorted(h for _, h in again)[: len(orphans)] == orphans
    assert all(p != gone for p, _ in again)
    for _ in range(10 * tip):
        s.turn()
        s.answer(4)
        s.apply_ready()
        if s.pool.height >= tip:
            break
    assert s.pool.height == tip and s.most_pending <= MAX_PENDING_PER_PEER
    assert all(p != gone for p, _ in s.sent[first_again:])


@pytest.mark.parametrize("n_peers, tip", [(2, 45), (4, 100)])
def test_a_silent_peer_loses_its_requests_at_the_timeout(n_peers, tip):
    s = Script(n_peers, tip)
    silent = s.peers[0]
    s.turn()
    stuck = sorted(h for p, h in s.unanswered if p == silent)
    s.answer(10**6, silent=(silent,))
    s.clock.sleep(REQUEST_TIMEOUT - 1.0)
    first_again = len(s.sent)
    s.turn()
    assert not set(stuck) & {h for _, h in s.sent[first_again:]}, "not before the timeout"
    s.clock.sleep(1.5)
    s.answer(10**6, silent=(silent,))
    s.unanswered = []
    first_again = len(s.sent)
    s.turn()
    again = {h: p for p, h in s.sent[first_again:]}
    assert set(stuck) <= set(again), "every timed-out height is asked for again"
    assert len(set(again.values())) == n_peers, "its places are free again, and every peer has some"
    assert max(s.pool.pending_by_peer().values()) <= MAX_PENDING_PER_PEER


def test_the_only_peer_is_asked_again_after_its_timeout():
    s = Script(1, 30)
    s.turn()
    s.clock.sleep(REQUEST_TIMEOUT + 0.5)
    assert s.turn() == MAX_PENDING_PER_PEER, "there is no other peer to ask"
    assert s.pool.pending_by_peer() == {"peer0": MAX_PENDING_PER_PEER}


def test_a_refused_block_frees_both_heights_and_names_the_peer():
    s = Script(4, 100)
    s.turn()
    asked = dict((h, p) for p, h in s.sent)
    s.answer(10)
    pending_before = s.pool.pending_by_peer()
    assert s.pool.redo_request(1) == asked[1]
    pending_after = s.pool.pending_by_peer()
    assert pending_after == pending_before, "an answered request held no place"
    first_again = len(s.sent)
    s.turn()
    assert {1, 2} <= {h for _, h in s.sent[first_again:]}
    assert s.pool.peek_two_blocks() == (None, None)


def test_a_block_from_a_peer_that_was_not_asked_still_frees_the_asked_peers_place():
    s = Script(2, 50)
    s.turn()
    asked = dict((h, p) for p, h in s.sent)
    other = next(p for p in s.peers if p != asked[1])
    before = s.pool.pending_by_peer()
    assert s.pool.add_block(other, _block(1))
    after = s.pool.pending_by_peer()
    assert after[asked[1]] == before[asked[1]] - 1 and after[other] == before[other]
    assert not s.pool.add_block(asked[1], _block(1)), "the second copy is not wanted"
