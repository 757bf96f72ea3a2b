"""The host-native inclusion proofs (crypto/merkle/proof._LazyProofs over
native.merkle_proof_parts: every level and every aunt in one C pass, taken
by proofs_from_byte_slices from 32 items up) must equal the pure-Python
construction exactly — totals, indexes, leaf hashes, aunts — for
power-of-two AND odd-promotion sizes, and every proof must verify against
the root."""

import pytest

from cometbft_tpu import native
from cometbft_tpu.crypto.merkle import hash_from_byte_slices, proofs_from_byte_slices
from cometbft_tpu.crypto.merkle.proof import _LazyProofs


@pytest.fixture(autouse=True)
def _native_built():
    native.require()


def _native_proofs(items):
    """What proofs_from_byte_slices hands out from 32 items up; below that
    size it builds in Python, so the sequence is made here the same way."""
    if len(items) >= 32:
        root, proofs = proofs_from_byte_slices(items)
        assert isinstance(proofs, _LazyProofs)
        return root, proofs
    root, leaf_hashes, packed, stride, counts = native.merkle_proof_parts(items)
    return root, _LazyProofs(len(items), leaf_hashes, packed, stride, counts)


def _python_proofs(items, monkeypatch):
    with monkeypatch.context() as m:
        m.setattr(native, "ready", lambda: None)
        m.setattr(native, "ensure_built_async", lambda: None)
        root, proofs = proofs_from_byte_slices(items)
    assert isinstance(proofs, list)
    return root, proofs


@pytest.mark.parametrize("n", [1, 2, 3, 5, 7, 8, 13, 64, 100, 255, 256])
def test_native_proofs_equal_pure_python(n, monkeypatch):
    txs = [b"t-%d" % i for i in range(n)]
    root_p, proofs_p = _python_proofs(txs, monkeypatch)
    root_n, proofs_n = _native_proofs(txs)
    assert root_p == root_n == hash_from_byte_slices(txs)
    assert len(proofs_n) == len(proofs_p) == n
    for i in range(n):
        pp, pn = proofs_p[i], proofs_n[i]
        assert (pp.total, pp.index) == (pn.total, pn.index) == (n, i)
        assert pp.leaf_hash == pn.leaf_hash
        assert pp.aunts == pn.aunts
        assert pn.verify(root_n, txs[i]) is None


def test_native_proofs_reject_cross_tree():
    txs = [b"x-%d" % i for i in range(64)]
    root, proofs = _native_proofs(txs)
    other_root, _ = _native_proofs([b"y-%d" % i for i in range(64)])
    assert proofs[0].verify(root, txs[0]) is None
    with pytest.raises(ValueError):
        proofs[0].verify(other_root, txs[0])
    with pytest.raises(ValueError):
        proofs[0].verify(root, txs[1])


def test_native_proofs_lazy_sequence_protocol():
    txs = [b"s-%d" % i for i in range(37)]
    _, proofs = _native_proofs(txs)
    assert len(proofs) == 37 and len(list(proofs)) == 37
    assert [p.index for p in proofs[1:3]] == [1, 2]
    assert proofs[-1].index == 36
    with pytest.raises(IndexError):
        proofs[37]
    with pytest.raises(IndexError):
        proofs[-38]
