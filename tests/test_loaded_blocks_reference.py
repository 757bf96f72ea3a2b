"""Seeded loaded blocks (`benchmarks/loaded_chain.py`) against the plain
references the benchmark decides `correct` by
(`benchmarks/reference/rfc6962.py`, `kvstore_replay.py`): tx root, part-set
root and every part's proof, results hash, app hash. Small sizes, no chip."""

from __future__ import annotations

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")

# (txs a block, bytes a tx): one tx, a non-power-of-two, a power of two,
# two blocks of several 64 KiB parts (3 and 5), the last one the QA load's
SIZES = [(1, 64), (5, 100), (16, 64), (37, 4096), (285, 1024)]


@pytest.fixture(scope="module")
def bench():
    sys.path.insert(0, BENCH)  # as run.py has it: chain, fixtures, reference lie there
    try:
        import loaded_chain
        from reference import block_proto, kvstore_replay, rfc6962

        yield types.SimpleNamespace(
            loaded_chain=loaded_chain, rfc6962=rfc6962, kvstore_replay=kvstore_replay,
            block_proto=block_proto,
        )
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def chains(bench):
    from cometbft_tpu.libs.db import MemDB

    made = {}

    def get(n_txs, tx_bytes):
        if (n_txs, tx_bytes) not in made:
            _, store = bench.loaded_chain.make_chain(11, "ref-test", 4, 3, n_txs, tx_bytes, MemDB())
            made[n_txs, tx_bytes] = store
        return made[n_txs, tx_bytes]

    return get


def test_the_references_import_nothing_of_the_program():
    for name in ("rfc6962.py", "kvstore_replay.py", "block_proto.py"):
        with open(os.path.join(BENCH, "reference", name)) as f:
            code = f.read()
        assert "cometbft_tpu" not in code.split('"""', 2)[2], name
        assert "import jax" not in code


@pytest.mark.parametrize("n_txs, tx_bytes", SIZES)
def test_seeded_txs_have_their_size_and_a_key_each(bench, n_txs, tx_bytes):
    txs = bench.loaded_chain.block_txs(11, 2, n_txs, tx_bytes)
    assert len(txs) == n_txs and {len(t) for t in txs} == {tx_bytes}
    keys = [t.split(b"=", 1)[0] for t in txs]
    assert all(len(k) == 16 and int(k, 16) >= 0 for k in keys) and len(set(keys)) == n_txs
    assert txs == bench.loaded_chain.block_txs(11, 2, n_txs, tx_bytes)
    assert txs != bench.loaded_chain.block_txs(11, 3, n_txs, tx_bytes)


@pytest.mark.parametrize("n_txs, tx_bytes", SIZES)
def test_stored_parts_are_the_plain_encoding_of_the_block(bench, chains, n_txs, tx_bytes):
    """Height 1 has an empty last commit, the others a signed one."""
    generator = _load_generator()
    store = chains(n_txs, tx_bytes)
    for h in (1, 2, 3):
        block, meta = store.load_block(h), store.load_block_meta(h)
        total = meta.block_id.part_set_header.total
        stored = [store.load_block_part(h, i).bytes for i in range(total)]
        plain = bench.block_proto.block(generator._plain_values(block))
        assert bench.block_proto.parts(plain, 65536) == stored
        assert meta.block_id.part_set_header.hash == bench.rfc6962.root(stored)


def test_the_plain_encoding_tells_an_altered_block(bench, chains):
    generator = _load_generator()
    block = chains(5, 100).load_block(2)
    values = generator._plain_values(block)
    assert bench.block_proto.block(values) == block.encode()
    values["header"]["height"] += 1
    assert bench.block_proto.block(values) != block.encode()
    values = generator._plain_values(block)
    values["txs"][3] = values["txs"][3][:-1]
    assert bench.block_proto.block(values) != block.encode()
    assert bench.block_proto.parts(b"", 65536) == [b""]


def _load_generator():
    import harness

    return harness.load_by_path(
        os.path.join(BENCH, "generators", "blocksync_join_loaded.py"), "generator_loaded_for_test"
    )


@pytest.mark.parametrize("n_txs, tx_bytes", SIZES)
def test_tx_root_equals_the_plain_rfc6962_root(bench, chains, n_txs, tx_bytes):
    store = chains(n_txs, tx_bytes)
    for h in (1, 2, 3):
        block = store.load_block(h)
        txs = bench.loaded_chain.block_txs(11, h, n_txs, tx_bytes)
        assert list(block.data.txs) == txs
        assert block.header.data_hash == bench.rfc6962.root(txs)
        fresh = type(block.data)(txs=list(txs))  # as decoded: nothing memoized
        assert fresh.hash() == bench.rfc6962.root(txs)


@pytest.mark.parametrize("n_txs, tx_bytes", SIZES)
def test_part_set_root_and_every_proof_equal_the_plain_reference(bench, chains, n_txs, tx_bytes):
    store = chains(n_txs, tx_bytes)
    block, meta = store.load_block(2), store.load_block_meta(2)
    raw = block.encode()
    chunks = [raw[i : i + 65536] for i in range(0, len(raw), 65536)]
    header = meta.block_id.part_set_header
    assert header.total == len(chunks) == -(-len(raw) // 65536)
    if (n_txs, tx_bytes) == (285, 1024):
        assert len(chunks) == 5, "the QA load's block is five parts"
    assert header.hash == bench.rfc6962.root(chunks)
    parts = block.make_part_set()
    assert parts.header() == header
    for i, chunk in enumerate(chunks):
        part = store.load_block_part(2, i)
        assert part.bytes == chunk == parts.get_part(i).bytes
        assert list(part.proof.aunts) == bench.rfc6962.audit_path(chunks, i)
        assert bench.rfc6962.includes(header.hash, chunk, i, len(chunks), list(part.proof.aunts))
        part.proof.verify(header.hash, chunk)  # the program agrees with itself
        other = chunks[(i + 1) % len(chunks)] if len(chunks) > 1 else chunk + b"x"
        assert not bench.rfc6962.includes(header.hash, other, i, len(chunks), list(part.proof.aunts))
        with pytest.raises(ValueError):
            part.proof.verify(header.hash, other)


@pytest.mark.parametrize("n_txs, tx_bytes", SIZES)
def test_results_hash_and_app_hash_equal_the_plain_replay(bench, chains, n_txs, tx_bytes):
    store = chains(n_txs, tx_bytes)
    blocks = [bench.loaded_chain.block_txs(11, h, n_txs, tx_bytes) for h in (1, 2)]
    app_hashes, kv = bench.kvstore_replay.replay(blocks)
    results_root = bench.rfc6962.root([bench.kvstore_replay.result_leaf()] * n_txs)
    for h in (1, 2):
        nxt = store.load_block(h + 1).header
        assert nxt.app_hash == app_hashes[h - 1]
        assert nxt.last_results_hash == results_root
    assert len(kv) == 2 * n_txs and all(len(k) + 1 + len(v) == tx_bytes for k, v in kv.items())


@pytest.mark.parametrize("leaves", [0, 1, 2, 3, 5, 8, 13, 285])
def test_the_plain_tree_agrees_with_the_programs_on_any_count(bench, leaves):
    from cometbft_tpu.crypto import merkle
    from cometbft_tpu.crypto.merkle.proof import proofs_from_byte_slices

    items = [bytes([i % 251]) * (1 + i % 40) for i in range(leaves)]
    assert bench.rfc6962.root(items) == merkle.hash_from_byte_slices(items)
    if leaves:
        root, proofs = proofs_from_byte_slices(items)
        for i in (0, leaves // 2, leaves - 1):
            assert list(proofs[i].aunts) == bench.rfc6962.audit_path(items, i)
            assert bench.rfc6962.root_from_path(items[i], i, leaves, list(proofs[i].aunts)) == root
            if leaves > 1:  # a path of the wrong length fits no tree of this size
                short = list(proofs[i].aunts)[:-1]
                assert bench.rfc6962.root_from_path(items[i], i, leaves, short) is None


@pytest.mark.parametrize("delivered", [0, 1, 63, 64, 285, 8191, 8192, 192_090, 2**40])
def test_the_plain_app_hash_is_the_kvstores(bench, delivered):
    from cometbft_tpu.abci.example.kvstore import _put_varint_8

    assert bench.kvstore_replay.app_hash_after(delivered) == _put_varint_8(delivered)
    assert len(bench.kvstore_replay.app_hash_after(delivered)) == 8


@pytest.mark.parametrize(
    "fields", [(0, b"", 0, 0), (1, b"", 0, 0), (3, b"ab", 5, 300), (0, b"x" * 200, 0, 2**33)]
)
def test_the_plain_result_leaf_is_the_programs(bench, fields):
    from cometbft_tpu.types.results import deterministic_response_deliver_tx

    assert bench.kvstore_replay.result_leaf(*fields) == deterministic_response_deliver_tx(*fields)
