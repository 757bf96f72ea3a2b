"""A seeded chain whose blocks carry transactions, and a peer that serves it
with one of them altered: the serving side of the loaded blocksync cell.

`chain.py` builds empty blocks through the mempool's (empty) reap; this
builder hands `State.make_block` the block's seeded `key=value`
transactions and is otherwise the same walk: every validator signs with
OpenSSL, the program's own executor applies the block, and the chain lands
in a `libs/db.SQLiteDB` block store under the benchmark's cache directory.
Everything here runs in child processes that never import JAX.

    python3 -c "import sys; sys.path[:0] = ['.', 'benchmarks']; import loaded_chain; \
        print(loaded_chain.build_chain(5, 'qa-175-loaded', 175, 674, 285, 1024, '/tmp/chain'))"
"""

from __future__ import annotations

import hashlib
import json
import os
import sys
import time

import chain
import fixtures

KEY_HEX = 16  # a tx is <16 hex digits>=<padding>, `tx_bytes` long in all


def block_txs(seed: int, height: int, n_txs: int, tx_bytes: int) -> list[bytes]:
    """The transactions of one height: key and padding from the seed."""
    pad = tx_bytes - KEY_HEX - 1
    if pad < 0:
        raise ValueError(f"a tx of {tx_bytes} bytes cannot hold its key")
    step = KEY_HEX // 2 + pad
    stream = hashlib.shake_256(b"txs/%d/%d" % (seed, height)).digest(n_txs * step)
    return [
        stream[i : i + KEY_HEX // 2].hex().encode() + b"=" + stream[i + KEY_HEX // 2 : i + step]
        for i in range(0, n_txs * step, step)
    ]


def make_chain(seed: int, tag: str, n_vals: int, heights: int, n_txs: int, tx_bytes: int, db):
    """Builds `heights` blocks of `n_txs` seeded transactions into a block
    store over `db`, every validator signing every commit (OpenSSL's
    signatures). Returns (genesis, block store)."""
    from cometbft_tpu.types import BlockID, Commit
    from cometbft_tpu.types.block import BLOCK_ID_FLAG_COMMIT, CommitSig

    gen, keys = chain.genesis_for(seed, tag, n_vals)
    state, block_store, executor = chain.fresh_node(gen, db)
    last_commit = Commit(height=0, round=0)
    for h in range(1, heights + 1):
        proposer = state.validators.get_proposer()
        block = state.make_block(
            h, block_txs(seed, h, n_txs, tx_bytes), last_commit, [], proposer.address
        )
        parts = block.make_part_set()
        bid = BlockID(block.hash(), parts.header())
        skel = Commit(
            height=h, round=0, block_id=bid,
            signatures=[
                CommitSig(
                    BLOCK_ID_FLAG_COMMIT, v.address,
                    block.header.time.add_nanos(10**9 + 1000 * (idx + 1)),
                    fixtures.PLACEHOLDER_SIG,
                )
                for idx, v in enumerate(state.validators.validators)
            ],
        )
        sigs = [
            CommitSig(cs.block_id_flag, cs.validator_address, cs.timestamp,
                      keys[cs.validator_address].sign(bytes(sb)))
            for cs, sb in zip(skel.signatures, skel.vote_sign_bytes_all(gen.chain_id))
        ]
        seen = Commit(height=h, round=0, block_id=bid, signatures=sigs)
        block_store.save_block(block, parts, seen)
        state, _ = executor.apply_block(state, bid, block)
        last_commit = seen
    return gen, block_store


def _shape(seed, n_vals, heights, n_txs, tx_bytes) -> dict:
    return {"seed": seed, "validators": n_vals, "heights": heights,
            "txs_per_block": n_txs, "tx_bytes": tx_bytes}


def build_chain(seed: int, tag: str, n_vals: int, heights: int, n_txs: int, tx_bytes: int,
                out_dir: str) -> dict:
    """Child process: the chain into out_dir/blockstore.db, every stored
    signature checked by OpenSSL, the marker written last."""
    from cometbft_tpu.libs.db import MemDB, SQLiteDB

    t0 = time.time()
    os.makedirs(out_dir, exist_ok=True)
    db_path = os.path.join(out_dir, chain.STORE_FILE)
    for leftover in (db_path, db_path + "-wal", db_path + "-shm"):
        if os.path.exists(leftover):
            os.remove(leftover)
    os.environ["CMTPU_BACKEND"] = "cpu"  # this child has no chip to ask
    mem = MemDB()  # SQLiteDB commits per key; the copy below is one pass
    gen, _ = make_chain(seed, tag, n_vals, heights, n_txs, tx_bytes, mem)
    db = SQLiteDB(db_path)
    for k, v in mem.iterator():
        db.set(k, v)
    db.compact()
    built_s = time.time() - t0
    checked = chain.check_chain_signatures(out_dir, seed, tag, n_vals, heights)
    marker = {
        **_shape(seed, n_vals, heights, n_txs, tx_bytes),
        "chain_id": gen.chain_id, "build_s": round(built_s, 3),
        "store_bytes": os.path.getsize(db_path),
        "openssl_checked_signatures": checked,
        "openssl_check_s": round(time.time() - t0 - built_s, 3),
    }
    with open(os.path.join(out_dir, chain.MARKER_FILE), "w") as f:
        json.dump(marker, f)
    if "jax" in sys.modules:
        raise RuntimeError("the chain builder imported JAX")
    return marker


def have_chain(out_dir: str, seed: int, n_vals: int, heights: int, n_txs: int, tx_bytes: int) -> bool:
    try:
        with open(os.path.join(out_dir, chain.MARKER_FILE)) as f:
            m = json.load(f)
    except (OSError, ValueError):
        return False
    want = _shape(seed, n_vals, heights, n_txs, tx_bytes)
    return {k: m.get(k) for k in want} == want


class AlteredStore:
    """The stored chain with the transactions of one height altered, as a
    faulty or malicious peer would serve it: `flip` = (tx, byte) flips one
    bit of one transaction, `swap` = (tx, tx) exchanges two of them (two
    that lie in different parts exchange the parts' contents)."""

    def __init__(self, store, height: int, flip=None, swap=None):
        self._store = store
        self._height = height
        self._flip = flip
        self._swap = swap

    def __getattr__(self, name):
        return getattr(self._store, name)

    def load_block(self, height: int):
        block = self._store.load_block(height)
        if block is not None and height == self._height:
            txs = list(block.data.txs)
            if self._flip is not None:
                i, at = self._flip
                txs[i] = txs[i][:at] + bytes([txs[i][at] ^ 0x01]) + txs[i][at + 1 :]
            if self._swap is not None:
                i, j = self._swap
                txs[i], txs[j] = txs[j], txs[i]
            block.data = type(block.data)(txs=txs)
        return block


def serve_altered_peer(conn, out_dir: str, seed: int, tag: str, n_vals: int, altered: dict):
    """Child process: `chain.serve_peer` over an `AlteredStore`
    (`altered` = its keyword arguments). `serve_peer` opens its own store
    and `chain.py` is not this PR's to edit, so this process, which does
    nothing else, hands it an opener that wraps what it opens."""
    opened = chain.open_store
    chain.open_store = lambda d: AlteredStore(opened(d), **altered)
    chain.serve_peer(conn, out_dir, seed, tag, n_vals, None)
