"""Round-catchup gossip cascade (consensus/reactor.py _gossip_votes).

The reference's gossipVotesRoutine serves votes for the PEER'S round, not
the sender's — that asymmetry is what lets a node restarted into round 0
climb back to the live round. These tests drive _gossip_once directly
against a fake peer, covering every cascade pick plus the mark/unmark
symmetry under a rejecting (full-queue) try_send.
"""

import pytest

from cometbft_tpu.abci.example.kvstore import KVStoreApplication
from cometbft_tpu.config import test_config as make_test_config
from cometbft_tpu.consensus import messages as cmsg
from cometbft_tpu.consensus.cstypes import (
    STEP_COMMIT,
    STEP_NEW_HEIGHT,
    STEP_PREVOTE,
    STEP_PREVOTE_WAIT,
    STEP_PROPOSE,
    HeightVoteSet,
)
from cometbft_tpu.consensus.reactor import ConsensusReactor, PeerState
from cometbft_tpu.consensus.state import ConsensusState
from cometbft_tpu.libs.db import MemDB
from cometbft_tpu.mempool import CListMempool
from cometbft_tpu.p2p.reactor import CONSENSUS_DATA_CHANNEL
from cometbft_tpu.proxy import AppConns, local_client_creator
from cometbft_tpu.state import BlockExecutor, StateStore, make_genesis_state
from cometbft_tpu.store import BlockStore
from cometbft_tpu.types import GenesisDoc, GenesisValidator, Time
from cometbft_tpu.types.block import (
    PRECOMMIT_TYPE,
    PREVOTE_TYPE,
    BlockID,
    Commit,
    PartSetHeader,
)
from cometbft_tpu.types.priv_validator import MockPV
from cometbft_tpu.types.proposal import Proposal
from cometbft_tpu.types.vote import Vote
from cometbft_tpu.types.vote_set import VoteSet

pytestmark = pytest.mark.liveness

CHAIN_ID = "catchup-chain"


class FakePeer:
    """Peer double with a switchable try_send (full send queue = False)."""

    def __init__(self, peer_id: str = "peer1", accept: bool = True):
        self.id = peer_id
        self.accept = accept
        self.sent = []

    def try_send(self, chan, data) -> bool:
        if not self.accept:
            return False
        self.sent.append((chan, cmsg.decode_consensus_message(data)))
        return True

    def send(self, chan, data):
        return self.try_send(chan, data)

    def set(self, key, val):
        pass

    def votes(self):
        return [m.vote for _, m in self.sent if isinstance(m, cmsg.VoteMessage)]

    def msgs(self, kind):
        return [m for _, m in self.sent if isinstance(m, kind)]


class _Counter:
    def __init__(self):
        self.n = 0

    def inc(self, k: int = 1):
        self.n += k


@pytest.fixture
def net():
    pvs = [MockPV() for _ in range(4)]
    gen = GenesisDoc(
        chain_id=CHAIN_ID,
        genesis_time=Time(1700000000, 0),
        validators=[
            GenesisValidator(pv.address(), pv.get_pub_key(), 10, f"v{i}")
            for i, pv in enumerate(pvs)
        ],
    )
    gen.validate_and_complete()
    state = make_genesis_state(gen)
    conns = AppConns(local_client_creator(KVStoreApplication()))
    conns.start()
    cfg = make_test_config()
    mempool = CListMempool(cfg.mempool, conns.mempool)
    state_store = StateStore(MemDB())
    state_store.save(state)
    block_store = BlockStore(MemDB())
    executor = BlockExecutor(state_store, conns.consensus, mempool, None, block_store)
    cs = ConsensusState(
        cfg.consensus, state, executor, block_store, mempool, name="catchup"
    )
    cs.set_priv_validator(pvs[0])
    reactor = ConsensusReactor(cs, gossip_sleep=0.001)
    yield cs, reactor, pvs, state, executor
    cs.stop()


def _signed_vote(state, pv, vtype, height, round_, block_id=None):
    vals = state.validators
    idx, _ = vals.get_by_address(pv.address())
    vote = Vote(
        type=vtype,
        height=height,
        round=round_,
        block_id=block_id or BlockID(),
        timestamp=Time(1700000001, 0),
        validator_address=pv.address(),
        validator_index=idx,
    )
    return pv.sign_vote(CHAIN_ID, vote)


def _fill_round(cs, state, pvs, round_, types=(PREVOTE_TYPE, PRECOMMIT_TYPE),
                block_id=None):
    for pv in pvs:
        for t in types:
            v = _signed_vote(state, pv, t, cs.rs.height, round_, block_id)
            assert cs.rs.votes.add_vote(v, "filler")


def _gossip(reactor, ps, passes=40):
    for _ in range(passes):
        reactor._gossip_once(ps)


def _last_commit_set(state, pvs, height):
    block_id = BlockID(b"\x11" * 32, PartSetHeader(total=1, hash=b"\x22" * 32))
    vs = VoteSet(CHAIN_ID, height, 0, PRECOMMIT_TYPE, state.validators)
    for pv in pvs:
        assert vs.add_vote(_signed_vote(state, pv, PRECOMMIT_TYPE, height, 0, block_id))
    return vs


# -- the cascade ----------------------------------------------------------


def test_peer_behind_in_rounds_gets_its_round_votes(net):
    """A peer stuck at round 0 while we are at round 2 must be fed the
    ROUND-0 prevotes AND precommits — this is the livelock fix."""
    cs, reactor, pvs, state, _ = net
    rs = cs.rs
    rs.votes.set_round(3)
    rs.round = 2
    rs.step = STEP_PREVOTE
    _fill_round(cs, state, pvs, 0)
    counter = _Counter()
    cs.metrics.round_catchup_votes_sent = counter

    peer = FakePeer()
    ps = PeerState(peer)
    ps.height, ps.round, ps.step = rs.height, 0, STEP_PREVOTE_WAIT
    _gossip(reactor, ps)

    got = {(v.round, v.type) for v in peer.votes()}
    assert (0, PREVOTE_TYPE) in got and (0, PRECOMMIT_TYPE) in got
    assert len([v for v in peer.votes() if v.round == 0]) == 8  # 4 pv + 4 pc
    assert counter.n == 8  # every one was a catchup pick


def test_new_height_peer_gets_last_commit_precommits(net):
    cs, reactor, pvs, state, _ = net
    rs = cs.rs
    rs.height = 2
    rs.votes = HeightVoteSet(CHAIN_ID, 2, state.validators)
    rs.last_commit = _last_commit_set(state, pvs, 1)

    peer = FakePeer()
    ps = PeerState(peer)
    ps.height, ps.round, ps.step = 2, 0, STEP_NEW_HEIGHT
    _gossip(reactor, ps)

    lc = [v for v in peer.votes() if v.height == 1 and v.type == PRECOMMIT_TYPE]
    assert len(lc) == 4


def test_propose_step_peer_gets_pol_prevotes(net):
    """Peer at OUR round but stuck in Propose with a POL proposal: it needs
    the POL-round prevotes to consider the proposal complete."""
    cs, reactor, pvs, state, _ = net
    rs = cs.rs
    rs.votes.set_round(3)
    rs.round = 2
    rs.step = STEP_PREVOTE
    block_id = BlockID(b"\x33" * 32, PartSetHeader(total=1, hash=b"\x44" * 32))
    _fill_round(cs, state, pvs, 1, types=(PREVOTE_TYPE,), block_id=block_id)

    peer = FakePeer()
    ps = PeerState(peer)
    ps.height, ps.round, ps.step = rs.height, 2, STEP_PROPOSE
    ps.proposal_pol_round = 1
    _gossip(reactor, ps)

    pol = [v for v in peer.votes() if v.round == 1 and v.type == PREVOTE_TYPE]
    assert len(pol) == 4


def test_peer_one_height_behind_without_stored_block_gets_last_commit(net):
    """Height catchup when the block store has nothing yet for the peer's
    height: our live last_commit precommits finish its height."""
    cs, reactor, pvs, state, _ = net
    rs = cs.rs
    rs.height = 2
    rs.votes = HeightVoteSet(CHAIN_ID, 2, state.validators)
    rs.last_commit = _last_commit_set(state, pvs, 1)

    peer = FakePeer()
    ps = PeerState(peer)
    ps.height, ps.round, ps.step = 1, 0, STEP_PREVOTE
    _gossip(reactor, ps)

    lc = [v for v in peer.votes() if v.height == 1 and v.type == PRECOMMIT_TYPE]
    assert len(lc) == 4


def test_peer_behind_in_height_gets_parts_and_seen_commit(net):
    cs, reactor, pvs, state, executor = net
    # Commit a real block at height 1 into the store.
    block = executor.create_proposal_block(
        1, state, Commit(height=0, round=0, block_id=BlockID(), signatures=[]),
        pvs[0].address(),
    )
    parts = block.make_part_set()
    block_id = BlockID(block.hash(), parts.header())
    vs = VoteSet(CHAIN_ID, 1, 0, PRECOMMIT_TYPE, state.validators)
    for pv in pvs:
        assert vs.add_vote(_signed_vote(state, pv, PRECOMMIT_TYPE, 1, 0, block_id))
    cs.block_store.save_block(block, parts, vs.make_commit())

    rs = cs.rs
    rs.height = 2
    rs.votes = HeightVoteSet(CHAIN_ID, 2, state.validators)

    peer = FakePeer()
    ps = PeerState(peer)
    ps.height, ps.round, ps.step = 1, 0, STEP_PREVOTE
    _gossip(reactor, ps)

    got_parts = peer.msgs(cmsg.BlockPartMessage)
    assert {p.part.index for p in got_parts} == set(range(parts.total))
    commit_votes = [
        v for v in peer.votes() if v.height == 1 and v.type == PRECOMMIT_TYPE
    ]
    assert len(commit_votes) == 4


def test_peer_entering_commit_gets_catchup_parts_again(net):
    """A catch-up part that reaches a peer before it has set up a part set
    for the height is dropped there. When the peer then enters Commit at
    the same height and round, its parts are sent once more; a repeated
    announcement of the same step sends nothing new."""
    cs, reactor, pvs, state, executor = net
    block = executor.create_proposal_block(
        1, state, Commit(height=0, round=0, block_id=BlockID(), signatures=[]),
        pvs[0].address(),
    )
    parts = block.make_part_set()
    block_id = BlockID(block.hash(), parts.header())
    vs = VoteSet(CHAIN_ID, 1, 0, PRECOMMIT_TYPE, state.validators)
    for pv in pvs:
        assert vs.add_vote(_signed_vote(state, pv, PRECOMMIT_TYPE, 1, 0, block_id))
    cs.block_store.save_block(block, parts, vs.make_commit())
    rs = cs.rs
    rs.height = 2
    rs.votes = HeightVoteSet(CHAIN_ID, 2, state.validators)

    peer = FakePeer()
    ps = PeerState(peer)
    ps.apply_new_round_step(cmsg.NewRoundStepMessage(height=1, round=0, step=STEP_PROPOSE))
    _gossip(reactor, ps)
    assert len(peer.msgs(cmsg.BlockPartMessage)) == parts.total

    def parts_after(step):
        peer.sent.clear()
        ps.apply_new_round_step(cmsg.NewRoundStepMessage(height=1, round=0, step=step))
        _gossip(reactor, ps)
        return {p.part.index for p in peer.msgs(cmsg.BlockPartMessage)}

    assert parts_after(STEP_PREVOTE) == set()
    assert parts_after(STEP_COMMIT) == set(range(parts.total))
    assert parts_after(STEP_COMMIT) == set()


# -- mark/unmark symmetry under backpressure -------------------------------


def test_rejecting_try_send_leaves_no_marks(net):
    """A full send queue must never consume a mark: otherwise the vote or
    part is considered delivered and is lost to the peer forever."""
    cs, reactor, pvs, state, executor = net
    rs = cs.rs
    _fill_round(cs, state, pvs, 0)
    block = executor.create_proposal_block(
        1, state, Commit(height=0, round=0, block_id=BlockID(), signatures=[]),
        pvs[0].address(),
    )
    parts = block.make_part_set()
    proposal = Proposal(
        height=1, round=0, pol_round=-1,
        block_id=BlockID(block.hash(), parts.header()),
        timestamp=Time(1700000001, 0),
    )
    rs.proposal = pvs[0].sign_proposal(CHAIN_ID, proposal)
    rs.proposal_block_parts = parts
    rs.step = STEP_PREVOTE

    peer = FakePeer(accept=False)
    ps = PeerState(peer)
    ps.height, ps.round, ps.step = 1, 0, STEP_PREVOTE
    _gossip(reactor, ps, passes=10)
    assert not peer.sent
    assert not ps._sent_votes and not ps._sent_parts  # nothing marked-but-dropped

    # Queue drains: everything is still deliverable.
    peer.accept = True
    _gossip(reactor, ps)
    assert len(peer.msgs(cmsg.ProposalMessage)) == 1
    assert {p.part.index for p in peer.msgs(cmsg.BlockPartMessage)} == set(
        range(parts.total)
    )
    assert len(peer.votes()) == 8


def test_proposal_pol_message_sent_and_applied(net):
    """A POL proposal is chased by a ProposalPOL hint, and receiving one
    updates the peer's POL round for the cascade."""
    cs, reactor, pvs, state, executor = net
    rs = cs.rs
    rs.votes.set_round(2)
    rs.round = 1
    rs.step = STEP_PROPOSE
    block = executor.create_proposal_block(
        1, state, Commit(height=0, round=0, block_id=BlockID(), signatures=[]),
        pvs[0].address(),
    )
    parts = block.make_part_set()
    block_id = BlockID(block.hash(), parts.header())
    _fill_round(cs, state, pvs, 0, types=(PREVOTE_TYPE,), block_id=block_id)
    proposal = Proposal(
        height=1, round=1, pol_round=0, block_id=block_id,
        timestamp=Time(1700000001, 0),
    )
    rs.proposal = pvs[0].sign_proposal(CHAIN_ID, proposal)
    rs.proposal_block_parts = parts

    peer = FakePeer()
    ps = PeerState(peer)
    ps.height, ps.round, ps.step = 1, 1, STEP_PROPOSE
    reactor._gossip_once(ps)
    pol_msgs = peer.msgs(cmsg.ProposalPOLMessage)
    assert len(pol_msgs) == 1 and pol_msgs[0].proposal_pol_round == 0

    # Receiving a ProposalPOL from a peer updates its PeerState.
    reactor.peer_states[peer.id] = ps
    reactor.receive(
        CONSENSUS_DATA_CHANNEL,
        peer,
        cmsg.encode_consensus_message(pol_msgs[0]),
    )
    assert ps.proposal_pol_round == 0


def test_stale_round_part_mark_does_not_suppress_current_round(net):
    """Regression (round 15, the e2e matrix height stall): a block part
    relayed ROUNDS LATE used to mark the peer as having the CURRENT
    round's part — (height, index) keying — silently starving part gossip
    for every later round of the height while proposals and votes (whose
    keys carry the round) kept flowing.  Marks are round-scoped now: a
    stale round-0 receipt must not block round-2's parts."""
    cs, reactor, pvs, state, executor = net
    rs = cs.rs
    rs.votes.set_round(3)
    rs.round = 2
    rs.step = STEP_PREVOTE
    block = executor.create_proposal_block(
        1, state, Commit(height=0, round=0, block_id=BlockID(), signatures=[]),
        pvs[0].address(),
    )
    parts = block.make_part_set()
    proposal = Proposal(
        height=1, round=2, pol_round=-1,
        block_id=BlockID(block.hash(), parts.header()),
        timestamp=Time(1700000001, 0),
    )
    rs.proposal = pvs[0].sign_proposal(CHAIN_ID, proposal)
    rs.proposal_block_parts = parts

    peer = FakePeer()
    ps = PeerState(peer)
    ps.height, ps.round, ps.step = 1, 2, STEP_PROPOSE
    # The poisoning receipt: the peer relays round 0's part index 0 rounds
    # late (receive-path bookkeeping keys it under its OWN round).
    assert ps.mark_part_sent(1, 0, 0)

    _gossip(reactor, ps)
    got = {p.part.index for p in peer.msgs(cmsg.BlockPartMessage)}
    assert got == set(range(parts.total)), "round-2 parts starved by stale mark"
    # Each namespace stays independent: the round-0 mark survives, catchup
    # marks (round -1) are their own space, and round-2 is now consumed.
    assert not ps.mark_part_sent(1, 0, 0)
    assert ps.mark_part_sent(1, -1, 0)
    assert not ps.mark_part_sent(1, 2, 0)
