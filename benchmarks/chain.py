"""A seeded chain on disk and the peers that serve it: the serving side of
the blocksync cells. Everything here runs in child processes that never
import JAX (the parent holds the chip, and the serving side's interpreter
lock must not be the joiner's).

The chain is built through the program's own executor, as
`tests/test_blocksync.py::_populated_chain` does, into a `libs/db.SQLiteDB`
block store under the benchmark's cache directory: building is sequential
(every block needs the one before it) and costs tens of milliseconds a
block, so it is paid once per (configuration, traffic, seed) in a checkout.
"""

from __future__ import annotations

import json
import os
import sys
import time

import fixtures

STORE_FILE = "blockstore.db"
MARKER_FILE = "chain.json"


def genesis_for(seed: int, tag: str, n_vals: int):
    """(GenesisDoc, private keys by address) of the seeded validator set."""
    from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey

    from cometbft_tpu.crypto import ed25519
    from cometbft_tpu.types import GenesisDoc, GenesisValidator, Time

    keys = {}
    gvals = []
    for i in range(n_vals):
        sk = Ed25519PrivateKey.from_private_bytes(fixtures.key_seed(seed, tag, i))
        pub = ed25519.PubKey(sk.public_key().public_bytes_raw())
        keys[pub.address()] = sk
        gvals.append(GenesisValidator(pub.address(), pub, 10, ""))
    gen = GenesisDoc(
        chain_id=fixtures.chain_id_for(seed, tag),
        genesis_time=Time(1_700_000_000 + seed % 1_000_000, 0),
        validators=gvals,
    )
    gen.validate_and_complete()
    return gen, keys


def fresh_node(gen, block_db=None):
    """(state, block store, executor) of a node at genesis with a kvstore
    application; the stores are in memory unless a block db is given."""
    from cometbft_tpu.abci.example.kvstore import KVStoreApplication
    from cometbft_tpu.config import test_config
    from cometbft_tpu.libs.db import MemDB
    from cometbft_tpu.mempool import CListMempool
    from cometbft_tpu.proxy import AppConns, local_client_creator
    from cometbft_tpu.state import BlockExecutor, StateStore, make_genesis_state
    from cometbft_tpu.store import BlockStore

    state = make_genesis_state(gen)
    conns = AppConns(local_client_creator(KVStoreApplication()))
    conns.start()
    mempool = CListMempool(test_config().mempool, conns.mempool)
    state_store = StateStore(MemDB())
    block_store = BlockStore(block_db if block_db is not None else MemDB())
    state_store.save(state)
    executor = BlockExecutor(state_store, conns.consensus, mempool, None, block_store)
    return state, block_store, executor


def build_chain(seed: int, tag: str, n_vals: int, heights: int, out_dir: str) -> dict:
    """Builds `heights` empty blocks, every validator signing every commit,
    into out_dir/blockstore.db; writes the marker last, so a directory with
    a marker holds a whole chain. Signatures are OpenSSL's, each verified
    by OpenSSL as it is made."""
    from cometbft_tpu.libs.db import MemDB, SQLiteDB
    from cometbft_tpu.types import BlockID, Commit
    from cometbft_tpu.types.block import BLOCK_ID_FLAG_COMMIT, CommitSig

    t0 = time.time()
    os.makedirs(out_dir, exist_ok=True)
    db_path = os.path.join(out_dir, STORE_FILE)
    for leftover in (db_path, db_path + "-wal", db_path + "-shm"):
        if os.path.exists(leftover):
            os.remove(leftover)
    gen, keys = genesis_for(seed, tag, n_vals)
    os.environ["CMTPU_BACKEND"] = "cpu"  # this child has no chip to ask
    mem = MemDB()  # SQLiteDB commits per key; the copy below is one pass
    state, block_store, executor = fresh_node(gen, mem)
    last_commit = Commit(height=0, round=0)
    for h in range(1, heights + 1):
        proposer = state.validators.get_proposer()
        block = executor.create_proposal_block(h, state, last_commit, proposer.address)
        parts = block.make_part_set()
        bid = BlockID(block.hash(), parts.header())
        addrs = [v.address for v in state.validators.validators]
        skel = Commit(
            height=h, round=0, block_id=bid,
            signatures=[
                CommitSig(
                    BLOCK_ID_FLAG_COMMIT, a,
                    block.header.time.add_nanos(10**9 + 1000 * (idx + 1)),
                    fixtures.PLACEHOLDER_SIG,
                )
                for idx, a in enumerate(addrs)
            ],
        )
        sigs = []
        for cs, sb in zip(skel.signatures, skel.vote_sign_bytes_all(gen.chain_id)):
            sig = keys[cs.validator_address].sign(bytes(sb))
            sigs.append(CommitSig(cs.block_id_flag, cs.validator_address, cs.timestamp, sig))
        seen = Commit(height=h, round=0, block_id=bid, signatures=sigs)
        block_store.save_block(block, parts, seen)
        state, _ = executor.apply_block(state, bid, block)
        last_commit = seen
    db = SQLiteDB(db_path)
    for k, v in mem.iterator():
        db.set(k, v)
    db.compact()
    built_s = time.time() - t0
    checked = check_chain_signatures(out_dir, seed, tag, n_vals, heights)
    marker = {
        "seed": seed, "validators": n_vals, "heights": heights,
        "chain_id": gen.chain_id, "build_s": round(built_s, 3),
        "openssl_checked_signatures": checked,
        "openssl_check_s": round(time.time() - t0 - built_s, 3),
    }
    with open(os.path.join(out_dir, MARKER_FILE), "w") as f:
        json.dump(marker, f)
    if "jax" in sys.modules:
        raise RuntimeError("the chain builder imported JAX")
    return marker


def _check_slice(job) -> int:
    """Worker: every signature of the commits for heights lo..hi, as the
    stored blocks carry them, verified by OpenSSL."""
    out_dir, seed, tag, n_vals, lo, hi = job
    gen, keys = genesis_for(seed, tag, n_vals)
    pubs = {addr: sk.public_key() for addr, sk in keys.items()}
    store = open_store(out_dir)
    n = 0
    for h in range(lo, hi):
        commit = store.load_block(h + 1).last_commit
        for cs, sb in zip(commit.signatures, commit.vote_sign_bytes_all(gen.chain_id)):
            pubs[cs.validator_address].verify(cs.signature, bytes(sb))  # raises
            n += 1
    return n


def check_chain_signatures(out_dir, seed, tag, n_vals, heights) -> int:
    """Every signature of the stored chain against OpenSSL, over worker
    processes (175 validators x 2,000 heights is 40 s on one core)."""
    pool = fixtures.start_pool()
    try:
        step = max(1, -(-(heights - 1) // (4 * fixtures.worker_count())))
        jobs = [
            (out_dir, seed, tag, n_vals, lo, min(lo + step, heights))
            for lo in range(1, heights, step)
        ]
        return sum(pool.map(_check_slice, jobs))
    finally:
        pool.close()
        pool.join()


def have_chain(out_dir: str, seed: int, n_vals: int, heights: int) -> bool:
    try:
        with open(os.path.join(out_dir, MARKER_FILE)) as f:
            m = json.load(f)
    except (OSError, ValueError):
        return False
    return (m.get("seed"), m.get("validators"), m.get("heights")) == (seed, n_vals, heights)


def open_store(out_dir: str):
    from cometbft_tpu.libs.db import SQLiteDB
    from cometbft_tpu.store import BlockStore

    return BlockStore(SQLiteDB(os.path.join(out_dir, STORE_FILE)))


def new_switch(chain_id: str, moniker: str):
    from cometbft_tpu.p2p.key import NodeKey
    from cometbft_tpu.p2p.node_info import NodeInfo
    from cometbft_tpu.p2p.switch import Switch
    from cometbft_tpu.p2p.transport import MultiplexTransport

    nk = NodeKey()
    ni = NodeInfo(node_id=nk.id, network=chain_id, moniker=moniker)
    return nk, Switch(ni, MultiplexTransport(ni, nk))


def serve_peer(conn, out_dir: str, seed: int, tag: str, n_vals: int, tamper: dict | None):
    """Child process: a node that only serves the stored chain over the
    real p2p stack (loopback TCP, rates as shipped). Sends its address up
    the pipe, then serves until told to stop. With `tamper`
    ({"height": h, "index": i}) the commit for height h, as carried in block
    h+1's LastCommit, has one bit of signature i flipped."""
    from cometbft_tpu.blocksync.reactor import BlocksyncReactor
    from cometbft_tpu.state import make_genesis_state

    gen, _ = genesis_for(seed, tag, n_vals)
    store = open_store(out_dir)
    if tamper:
        store = _TamperedStore(store, tamper["height"], tamper["index"])
    nk, sw = new_switch(gen.chain_id, "serving-peer")
    sw.add_reactor(
        "BLOCKSYNC",
        BlocksyncReactor(
            state=make_genesis_state(gen), block_exec=None, block_store=store, block_sync=False
        ),
    )
    addr = sw.start("127.0.0.1:0")
    conn.send({"addr": f"{nk.id}@{addr}", "height": store.height()})
    try:
        conn.recv()  # anything, or EOF when the parent goes away
    except EOFError:
        pass
    finally:
        sw.stop()
        conn.send({"jax_imported": "jax" in sys.modules})
        conn.close()


class _TamperedStore:
    """The stored chain with one signature of one commit flipped, as a
    faulty or malicious peer would serve it."""

    def __init__(self, store, height: int, index: int):
        self._store = store
        self._height = height
        self._index = index

    def __getattr__(self, name):
        return getattr(self._store, name)

    def load_block(self, height: int):
        block = self._store.load_block(height)
        if block is not None and height == self._height + 1:
            block.last_commit = fixtures.flip_signatures(block.last_commit, [self._index])
        return block
