"""ValidatorSet machinery (reference: types/validator_set_test.go — its
largest test file): weighted proposer rotation fairness, priority
centering/rescaling, and the update change-set rules (add, power change,
removal via 0, rejection of bad change-sets)."""

import pytest

from cometbft_tpu.crypto import ed25519
from cometbft_tpu.types.validator import Validator
from cometbft_tpu.types.validator_set import ValidatorSet


def mkval(seed: bytes, power: int) -> Validator:
    pub = ed25519.gen_priv_key_from_secret(seed).pub_key()
    return Validator(pub.address(), pub, power)


@pytest.fixture
def vset():
    return ValidatorSet([mkval(b"a", 10), mkval(b"b", 20), mkval(b"c", 30)])


def test_weighted_proposer_rotation_fairness(vset):
    """Over total_power rounds every validator proposes proportionally to
    its power (the reference's round-robin invariant)."""
    counts: dict[bytes, int] = {}
    total = vset.total_voting_power()
    for _ in range(total):
        p = vset.get_proposer()
        counts[p.address] = counts.get(p.address, 0) + 1
        vset.increment_proposer_priority(1)
    by_power = {v.address: v.voting_power for v in vset.validators}
    assert counts == by_power, f"rotation not power-proportional: {counts}"


def test_priorities_stay_centered(vset):
    for _ in range(1000):
        vset.increment_proposer_priority(1)
    prios = [v.proposer_priority for v in vset.validators]
    total = vset.total_voting_power()
    assert max(prios) - min(prios) <= 2 * total
    assert abs(sum(prios)) <= len(prios)  # centered near zero


def test_update_change_set_add_update_remove(vset):
    d = mkval(b"d", 15)
    vset.update_with_change_set([d])
    assert vset.size() == 4 and vset.total_voting_power() == 75
    # power change
    b_up = mkval(b"b", 5)
    vset.update_with_change_set([b_up])
    assert vset.total_voting_power() == 60
    _, got = vset.get_by_address(b_up.address)
    assert got.voting_power == 5
    # removal via power 0
    vset.update_with_change_set([mkval(b"a", 0)])
    assert vset.size() == 3
    assert not vset.has_address(mkval(b"a", 0).address)


def test_update_rejects_bad_change_sets(vset):
    # duplicate addresses in one change set
    with pytest.raises(Exception):
        vset.update_with_change_set([mkval(b"x", 5), mkval(b"x", 6)])
    # deleting an unknown validator
    with pytest.raises(Exception):
        vset.update_with_change_set([mkval(b"ghost", 0)])
    # negative power
    with pytest.raises(Exception):
        vset.update_with_change_set([mkval(b"y", -3)])
    # removing everyone
    with pytest.raises(Exception):
        vset.update_with_change_set(
            [mkval(b"a", 0), mkval(b"b", 0), mkval(b"c", 0)]
        )


def test_update_preserves_rotation_fairness(vset):
    """After an update, rotation must still be power-proportional over a
    full cycle (priorities of new entrants are penalized, not zeroed —
    validator_set.go computeNewPriorities)."""
    vset.update_with_change_set([mkval(b"d", 40)])
    counts: dict[bytes, int] = {}
    total = vset.total_voting_power()
    for _ in range(total * 2):
        p = vset.get_proposer()
        counts[p.address] = counts.get(p.address, 0) + 1
        vset.increment_proposer_priority(1)
    by_power = {v.address: v.voting_power * 2 for v in vset.validators}
    for addr, want in by_power.items():
        assert abs(counts.get(addr, 0) - want) <= 2, (
            f"unfair rotation after update: {counts} vs {by_power}"
        )


def test_hash_changes_with_membership(vset):
    h0 = vset.hash()
    vset.update_with_change_set([mkval(b"d", 1)])
    assert vset.hash() != h0


# -- the columns commit verification reads (ISSUE 27) ---------------------------


def _counts():
    from cometbft_tpu.types import validator_set

    return validator_set.columns_counters()


def test_columns_are_the_sets_keys_powers_and_key_type_in_order(vset):
    cols, reused = vset.columns()
    assert not reused
    assert cols.pub_keys == tuple(v.pub_key for v in vset.validators)
    assert cols.powers == (30, 20, 10) == tuple(v.voting_power for v in vset.validators)
    assert cols.key_type == ed25519.KEY_TYPE
    again, reused = vset.columns()
    assert reused and again is cols


@pytest.mark.parametrize("change", ["add", "remove", "repower"])
def test_columns_are_rebuilt_after_a_change_set(vset, change):
    before, _ = vset.columns()
    vset.update_with_change_set(
        [{"add": mkval(b"d", 15), "remove": mkval(b"a", 0), "repower": mkval(b"b", 5)}[change]]
    )
    after, reused = vset.columns()
    assert not reused and after is not before
    assert after.pub_keys == tuple(v.pub_key for v in vset.validators)
    assert after.powers == tuple(v.voting_power for v in vset.validators)
    assert after.powers == {"add": (30, 20, 15, 10), "remove": (30, 20), "repower": (30, 10, 5)}[change]


def test_columns_survive_proposer_rotation(vset):
    cols, _ = vset.columns()
    vset.increment_proposer_priority(3)
    assert vset.columns() == (cols, True)


@pytest.mark.parametrize("copy", ["copy", "copy_increment_proposer_priority"])
def test_a_copy_carries_the_columns_both_ways(vset, copy):
    def make(s):
        return s.copy() if copy == "copy" else s.copy_increment_proposer_priority(1)

    cols, _ = vset.columns()
    c = make(vset)
    assert c.columns() == (cols, True) and c.columns()[0] is cols
    # a node's state copies next_validators every height and verifies against
    # the copy only: what the copy builds, later copies of the original find
    fresh = ValidatorSet([mkval(b"a", 10), mkval(b"b", 20)])
    child = make(fresh)
    built, reused = child.columns()
    assert not reused
    assert make(make(fresh)).columns() == (built, True)
    # and a copy that changes leaves the original's columns alone
    child.update_with_change_set([mkval(b"c", 5)])
    assert child.columns()[0].powers == (20, 10, 5)
    assert fresh.columns() == (built, True) and fresh.columns()[0].powers == (20, 10)


def test_the_two_counters_count_builds_and_reuses(vset):
    c0 = _counts()
    vset.columns()
    vset.copy().columns()
    vset.columns()
    c1 = _counts()
    assert (c1["built"] - c0["built"], c1["reused"] - c0["reused"]) == (1, 2)
    vset.update_with_change_set([mkval(b"d", 15)])
    vset.columns()
    c2 = _counts()
    assert (c2["built"] - c1["built"], c2["reused"] - c1["reused"]) == (1, 0)


def test_a_mixed_or_keyless_set_reads_no_key_type():
    from cometbft_tpu.crypto import sr25519
    from cometbft_tpu.types.block import Commit, CommitSig
    from cometbft_tpu.types.validation import _batch_key_type

    sr = sr25519.gen_priv_key().pub_key()
    mixed = ValidatorSet([mkval(b"a", 10), Validator(sr.address(), sr, 10)])
    commit = Commit(signatures=[CommitSig(), CommitSig()])
    assert mixed.columns()[0].key_type is None
    assert _batch_key_type(mixed, commit) is None
    keyless = ValidatorSet()
    keyless.validators = [Validator(b"\x01" * 20, None, 1), mkval(b"a", 10)]
    assert keyless.columns()[0].key_type is None
    assert _batch_key_type(keyless, commit) is None
    whole = ValidatorSet([mkval(b"a", 10), mkval(b"b", 10)])
    assert _batch_key_type(whole, commit) == ed25519.KEY_TYPE
    assert _batch_key_type(whole, Commit(signatures=[CommitSig()])) is None  # under the threshold
