"""The columnar batch engine of types/validation.py (ISSUE 27) held to the
two per-signature references: `_verify_commit_single`, and the loop the
engine replaced (kept here as `_loop_reference`, since the scalar engine
and the seam word one refusal differently: a signature of the wrong size
is "invalid signature" at the seam's add(), "wrong signature (#idx)" in
the scalar engine). Same outcome, same triples to the seam in the same
order, same quorum cut."""

import dataclasses
import random

import pytest

from cometbft_tpu import crypto
from cometbft_tpu.crypto import ed25519
from cometbft_tpu.types import validation
from cometbft_tpu.types.block import (
    BLOCK_ID_FLAG_ABSENT,
    BLOCK_ID_FLAG_COMMIT,
    BLOCK_ID_FLAG_NIL,
    BlockID,
    Commit,
    CommitSig,
)
from cometbft_tpu.types.cmttime import Time
from cometbft_tpu.types.part_set import PartSetHeader
from cometbft_tpu.types.validation import ErrNotEnoughVotingPowerSigned
from cometbft_tpu.types.validator import Validator
from cometbft_tpu.types.validator_set import ValidatorSet

CHAIN = "columnar-chain"
HEIGHT = 7
BID = BlockID(b"\x11" * 32, PartSetHeader(1, b"\x22" * 32))
N = 14

# (ignore_sig, count_sig, count_all_signatures, look_up_by_index), as the
# three callers hand them to an engine.
MODES = {
    "full": (lambda c: c.is_absent(), lambda c: c.for_block_flag(), True, True),
    "light": (lambda c: not c.for_block_flag(), lambda c: True, False, True),
    "trusting": (lambda c: not c.for_block_flag(), lambda c: True, False, False),
}

KEYS = [ed25519.gen_priv_key_from_secret(b"columnar/%d" % i) for i in range(N + 3)]
BY_ADDR = {k.pub_key().address(): k for k in KEYS}


class RecordingVerifier(ed25519.BatchVerifier):
    """The seam with the backend taken out: keeps what was added and how it
    was handed over, and answers verify() lane by lane through the scalar
    verifier."""

    def __init__(self):
        super().__init__()
        self.handed = []  # (keys, key_bytes) of each add_many

    def add_many(self, keys, messages, signatures, key_bytes=None):
        self.handed.append((keys, key_bytes))
        super().add_many(keys, messages, signatures, key_bytes)

    def triples(self):
        return list(zip(self._pubs, self._msgs, self._sigs))

    def verify(self):
        bits = [ed25519.PubKey(p).verify_signature(m, s) for p, m, s in self.triples()]
        return all(bits), bits


def _signers(seed: int) -> ValidatorSet:
    rng = random.Random(f"columnar/powers/{seed}")
    return ValidatorSet(
        [Validator.new(k.pub_key(), rng.randint(1, 40)) for k in KEYS[:N]]
    )


def _trusted(seed: int, signers: ValidatorSet) -> ValidatorSet:
    """Another set for the by-address mode: most of the signers under other
    powers (so in another order), and validators the commit never names."""
    rng = random.Random(f"columnar/trusted/{seed}")
    members = rng.sample(KEYS[:N], N - 3) + KEYS[N:]
    return ValidatorSet([Validator.new(k.pub_key(), rng.randint(1, 40)) for k in members])


def _commit(signers: ValidatorSet, flags) -> Commit:
    """The signers' commit with flags[i] for validator i, every entry that
    is not absent truly signed."""
    shape = [
        CommitSig()
        if f == BLOCK_ID_FLAG_ABSENT
        else CommitSig(f, v.address, Time(1_700_000_000 + i % 3, 1_000 + 37 * i), b"\x00" * 64)
        for i, (v, f) in enumerate(zip(signers.validators, flags))
    ]
    skel = Commit(height=HEIGHT, round=0, block_id=BID, signatures=shape)
    sigs = [
        cs
        if cs.is_absent()
        else dataclasses.replace(
            cs, signature=BY_ADDR[cs.validator_address].sign(skel.vote_sign_bytes(CHAIN, i))
        )
        for i, cs in enumerate(shape)
    ]
    return Commit(height=HEIGHT, round=0, block_id=BID, signatures=sigs)


def _mixed_flags(seed: int):
    rng = random.Random(f"columnar/flags/{seed}")
    return rng.choices(
        [BLOCK_ID_FLAG_ABSENT, BLOCK_ID_FLAG_NIL, BLOCK_ID_FLAG_COMMIT], [2, 2, 9], k=N
    )


def _with(commit: Commit, idx: int, **changes) -> Commit:
    sigs = list(commit.signatures)
    sigs[idx] = dataclasses.replace(sigs[idx], **changes)
    return dataclasses.replace(commit, signatures=sigs)


def _flipped(commit: Commit, idx: int) -> Commit:
    s = commit.signatures[idx].signature
    return _with(commit, idx, signature=s[:5] + bytes([s[5] ^ 0x40]) + s[6:])


def _fresh(commit: Commit) -> Commit:
    return Commit(
        height=commit.height, round=commit.round, block_id=commit.block_id,
        signatures=list(commit.signatures),
    )


def _needed(mode: str, vals: ValidatorSet) -> int:
    total = vals.total_voting_power()
    return total // 3 if mode == "trusting" else total * 2 // 3


def _outcome(call):
    try:
        call()
    except (ValueError, TypeError, ErrNotEnoughVotingPowerSigned) as e:
        return type(e).__name__, str(e)
    return "accepted"


def _loop_reference(chain_id, vals, commit, needed, ignore_sig, count_sig,
                    count_all, by_index, bv):
    """The per-signature loop of `_verify_commit_batch` as it stood before
    ISSUE 27, word for word but for the spans."""
    seen_vals = {}
    batch_sig_idxs = []
    tallied = 0
    all_sign_bytes = commit.vote_sign_bytes_all(chain_id)
    for idx, commit_sig in enumerate(commit.signatures):
        if ignore_sig(commit_sig):
            continue
        if by_index:
            val = vals.validators[idx]
        else:
            val_idx, val = vals.get_by_address(commit_sig.validator_address)
            if val is None:
                continue
            if val_idx in seen_vals:
                raise ValueError(f"double vote from {val} ({seen_vals[val_idx]} and {idx})")
            seen_vals[val_idx] = idx
        bv.add(val.pub_key, all_sign_bytes[idx], commit_sig.signature)
        batch_sig_idxs.append(idx)
        if count_sig(commit_sig):
            tallied += val.voting_power
        if not count_all and tallied > needed:
            break
    if tallied <= needed:
        raise ErrNotEnoughVotingPowerSigned(tallied, needed)
    ok, valid_sigs = bv.verify()
    if ok:
        return
    for i, sig_ok in enumerate(valid_sigs):
        if not sig_ok:
            idx = batch_sig_idxs[i]
            sig = commit.signatures[idx]
            raise ValueError(f"wrong signature (#{idx}): {sig.signature.hex().upper()}")
    raise RuntimeError("BUG: batch verification failed with no invalid signatures")


def _three_ways(mode, vals, commit, needed, monkeypatch):
    """(outcome, triples) of the columnar engine, the old loop and the
    scalar engine, each on a commit object of its own."""
    ignore, count, count_all, by_index = MODES[mode]
    bv = RecordingVerifier()
    cols = vals.columns()[0]
    columnar = _outcome(lambda: validation._verify_commit_batch(
        CHAIN, vals, _fresh(commit), needed, ignore, count, count_all, by_index,
        cols, bv))
    # (ISSUE 29) the keys go over as the set's raw bytes, selected like the
    # key objects; a selection that is the whole commit is no copy at all
    assert cols.pub_bytes == tuple(k.bytes() for k in cols.pub_keys)
    for keys, key_bytes in bv.handed:
        assert list(key_bytes) == [k.bytes() for k in keys]
        whole = by_index and len(keys) == len(commit.signatures)
        assert (keys is cols.pub_keys and key_bytes is cols.pub_bytes) == whole
    ref_bv = RecordingVerifier()
    loop = _outcome(lambda: _loop_reference(
        CHAIN, vals, _fresh(commit), needed, ignore, count, count_all, by_index, ref_bv))
    asked = []
    real = ed25519.PubKey.verify_signature

    def recording(self, msg, sig):
        asked.append((self.bytes(), bytes(msg), bytes(sig)))
        return real(self, msg, sig)

    with monkeypatch.context() as m:
        m.setattr(ed25519.PubKey, "verify_signature", recording)
        single = _outcome(lambda: validation._verify_commit_single(
            CHAIN, vals, _fresh(commit), needed, ignore, count, count_all, by_index))
    return (columnar, bv.triples()), (loop, ref_bv.triples()), (single, asked)


def _case(mode: str, seed: int, flags=None):
    signers = _signers(seed)
    commit = _commit(signers, flags or _mixed_flags(seed))
    vals = _trusted(seed, signers) if mode == "trusting" else signers
    return signers, vals, commit


def _submitted_idxs(commit: Commit, triples) -> list[int]:
    """Commit indices of the submitted triples (signatures are distinct)."""
    at = {cs.signature: i for i, cs in enumerate(commit.signatures) if cs.signature}
    return [at[s] for _, _, s in triples]


@pytest.mark.parametrize("seed", range(8))
@pytest.mark.parametrize("flags", ["mixed_flags", "all_for_the_block"])
@pytest.mark.parametrize("mode", MODES)
def test_sound_commits_decide_alike_and_submit_the_same_triples(mode, flags, seed, monkeypatch):
    _, vals, commit = _case(
        mode, seed, None if flags == "mixed_flags" else [BLOCK_ID_FLAG_COMMIT] * N)
    needed = _needed(mode, vals)
    columnar, loop, single = _three_ways(mode, vals, commit, needed, monkeypatch)
    assert columnar == loop == single
    outcome, triples = columnar
    assert outcome == "accepted" or outcome[0] == "ErrNotEnoughVotingPowerSigned"
    if mode == "full" and flags == "all_for_the_block":
        assert len(triples) == N  # the whole commit: the columns went as they stand
    if mode != "full" and outcome == "accepted":
        # the cut: the entry that carried the tally over the quorum is the last
        powers = {v.pub_key.bytes(): v.voting_power for v in vals.validators}
        got = [powers[p] for p, _, _ in triples]
        assert sum(got) > needed >= sum(got[:-1])


@pytest.mark.parametrize("where", ["first", "middle", "last"])
@pytest.mark.parametrize("mode", MODES)
def test_a_bad_signature_is_reported_by_its_commit_index(mode, where, monkeypatch):
    _, vals, commit = _case(mode, 21, [BLOCK_ID_FLAG_COMMIT] * N)
    needed = _needed(mode, vals)
    (_, sound), _, _ = _three_ways(mode, vals, commit, needed, monkeypatch)
    submitted = _submitted_idxs(commit, sound)
    idx = {"first": submitted[0], "middle": submitted[len(submitted) // 2],
           "last": submitted[-1]}[where]
    bad = _flipped(commit, idx)
    columnar, loop, single = _three_ways(mode, vals, bad, needed, monkeypatch)
    text = f"wrong signature (#{idx}): {bad.signatures[idx].signature.hex().upper()}"
    assert columnar[0] == loop[0] == single[0] == ("ValueError", text)
    at = submitted.index(idx)
    pub, msg, _ = sound[at]
    expected = sound[:at] + [(pub, msg, bad.signatures[idx].signature)] + sound[at + 1:]
    assert columnar[1] == loop[1] == expected  # every triple still goes to the seam
    assert single[1] == expected[: at + 1]  # the scalar engine stops at the bad one


@pytest.mark.parametrize("mode", MODES)
def test_two_bad_signatures_report_the_earlier(mode, monkeypatch):
    _, vals, commit = _case(mode, 22, [BLOCK_ID_FLAG_COMMIT] * N)
    needed = _needed(mode, vals)
    (_, sound), _, _ = _three_ways(mode, vals, commit, needed, monkeypatch)
    first, second = sorted(_submitted_idxs(commit, sound)[-2:])
    bad = _flipped(_flipped(commit, second), first)
    columnar, loop, single = _three_ways(mode, vals, bad, needed, monkeypatch)
    assert columnar[0] == loop[0] == single[0]
    assert columnar[0][1].startswith(f"wrong signature (#{first}):")


@pytest.mark.parametrize("mode", MODES)
def test_a_short_signature_before_the_cut_is_refused_by_the_seam(mode, monkeypatch):
    _, vals, commit = _case(mode, 23, [BLOCK_ID_FLAG_COMMIT] * N)
    needed = _needed(mode, vals)
    (_, sound), _, _ = _three_ways(mode, vals, commit, needed, monkeypatch)
    idx = _submitted_idxs(commit, sound)[1]
    bad = _with(commit, idx, signature=commit.signatures[idx].signature[:63])
    columnar, loop, single = _three_ways(mode, vals, bad, needed, monkeypatch)
    assert columnar[0] == loop[0] == ("ValueError", "invalid signature")
    assert single[0][1].startswith(f"wrong signature (#{idx}):")  # the scalar wording


@pytest.mark.parametrize("mode", ["light", "trusting"])
def test_a_short_signature_past_the_cut_is_never_looked_at(mode, monkeypatch):
    _, vals, commit = _case(mode, 24, [BLOCK_ID_FLAG_COMMIT] * N)
    needed = _needed(mode, vals)
    (_, sound), _, _ = _three_ways(mode, vals, commit, needed, monkeypatch)
    past = max(_submitted_idxs(commit, sound)) + 1
    assert past < N, "the case needs an entry past the cut"
    bad = _with(commit, past, signature=commit.signatures[past].signature[:63])
    columnar, loop, single = _three_ways(mode, vals, bad, needed, monkeypatch)
    assert columnar == loop == single == ("accepted", sound)


def test_a_short_signature_in_the_full_mode_is_refused_wherever_it_stands(monkeypatch):
    _, vals, commit = _case("full", 25, [BLOCK_ID_FLAG_COMMIT] * N)
    bad = _with(commit, N - 1, signature=commit.signatures[N - 1].signature[:63])
    columnar, loop, _ = _three_ways("full", vals, bad, _needed("full", vals), monkeypatch)
    assert columnar[0] == loop[0] == ("ValueError", "invalid signature")


def _double_vote(commit: Commit, vals: ValidatorSet, needed: int, monkeypatch):
    """The commit with a second entry under an address the trusted set has
    already counted, inside the cut; (commit, first index, second index)."""
    (_, sound), _, _ = _three_ways("trusting", vals, commit, needed, monkeypatch)
    idxs = _submitted_idxs(commit, sound)
    first, second = idxs[0], idxs[1]
    twice = _with(commit, second,
                  validator_address=commit.signatures[first].validator_address)
    return twice, first, second


def test_a_double_vote_by_address_is_refused_with_both_indices(monkeypatch):
    _, vals, commit = _case("trusting", 26, [BLOCK_ID_FLAG_COMMIT] * N)
    needed = _needed("trusting", vals)
    twice, first, second = _double_vote(commit, vals, needed, monkeypatch)
    columnar, loop, single = _three_ways("trusting", vals, twice, needed, monkeypatch)
    assert columnar == loop == single
    _, val = vals.get_by_address(commit.signatures[first].validator_address)
    assert columnar[0] == ("ValueError", f"double vote from {val} ({first} and {second})")
    assert len(columnar[1]) == 1  # only the entry before it reached the seam


def test_a_double_vote_past_the_cut_is_never_looked_at(monkeypatch):
    _, vals, commit = _case("trusting", 27, [BLOCK_ID_FLAG_COMMIT] * N)
    needed = _needed("trusting", vals)
    (_, sound), _, _ = _three_ways("trusting", vals, commit, needed, monkeypatch)
    known = [i for i, cs in enumerate(commit.signatures)
             if vals.has_address(cs.validator_address)]
    late = [i for i in known if i > max(_submitted_idxs(commit, sound))]
    assert late, "the case needs a known signer past the cut"
    # the first entry past the cut: the nearest one the loop never reaches
    twice = _with(commit, late[0], validator_address=commit.signatures[known[0]].validator_address)
    columnar, loop, single = _three_ways("trusting", vals, twice, needed, monkeypatch)
    assert columnar == loop == single == ("accepted", sound)


@pytest.mark.parametrize("earlier", ["short_signature", "double_vote"])
def test_of_two_faults_the_earlier_in_index_order_is_reported(earlier, monkeypatch):
    _, vals, commit = _case("trusting", 28, [BLOCK_ID_FLAG_COMMIT] * N)
    # the whole power, so that nothing is cut and both faults are in reach
    needed = vals.total_voting_power() - 1
    known = [i for i, cs in enumerate(commit.signatures)
             if vals.has_address(cs.validator_address)]
    a, b, c = known[0], known[2], known[4]
    short_at, again_at = (b, c) if earlier == "short_signature" else (c, b)
    bad = _with(commit, short_at, signature=commit.signatures[short_at].signature[:63])
    bad = _with(bad, again_at, validator_address=commit.signatures[a].validator_address)
    columnar, loop, _ = _three_ways("trusting", vals, bad, needed, monkeypatch)
    assert columnar == loop
    if earlier == "short_signature":
        assert columnar[0] == ("ValueError", "invalid signature")
    else:
        assert columnar[0][1].startswith("double vote from ")
        assert columnar[0][1].endswith(f"({a} and {again_at})")


@pytest.mark.parametrize("mode", MODES)
def test_power_one_short_of_the_quorum_is_not_enough(mode, monkeypatch):
    _, vals, commit = _case(mode, 29, [BLOCK_ID_FLAG_COMMIT] * N)
    (_, all_of_it), _, _ = _three_ways(mode, vals, commit, -1 if mode == "full" else 10**9, monkeypatch)
    powers = {v.pub_key.bytes(): v.voting_power for v in vals.validators}
    signed = sum(powers[p] for p, _, _ in all_of_it)
    columnar, loop, single = _three_ways(mode, vals, commit, signed, monkeypatch)
    assert columnar == loop == single
    assert columnar[0] == (
        "ErrNotEnoughVotingPowerSigned",
        f"invalid commit -- insufficient voting power: got {signed}, needed more than {signed}",
    )
    accepted, _, _ = _three_ways(mode, vals, commit, signed - 1, monkeypatch)
    assert accepted[0] == "accepted"


@pytest.mark.parametrize("mode", MODES)
def test_nil_votes_are_submitted_only_where_the_mode_counts_them_in(mode, monkeypatch):
    flags = [BLOCK_ID_FLAG_COMMIT] * N
    flags[1] = flags[4] = BLOCK_ID_FLAG_NIL
    flags[2] = BLOCK_ID_FLAG_ABSENT
    _, vals, commit = _case(mode, 30, flags)
    needed = vals.total_voting_power() // 4
    columnar, loop, single = _three_ways(mode, vals, commit, needed, monkeypatch)
    assert columnar == loop == single
    idxs = _submitted_idxs(commit, columnar[1])
    if mode == "full":  # every signature is checked, a nil vote's too; it is not counted
        assert idxs == [i for i in range(N) if i != 2]
    else:
        assert not {1, 2, 4} & set(idxs)


def test_the_default_add_many_loops_add_and_the_bulk_one_raises_what_add_raises():
    from cometbft_tpu.crypto import sr25519

    k = KEYS[0]
    msgs, sigs = [b"m0", b"m1", b"m2"], [k.sign(b"m0"), k.sign(b"m1"), k.sign(b"m2")]
    bulk, one_by_one = ed25519.BatchVerifier(), ed25519.BatchVerifier()
    bulk.add_many([k.pub_key()] * 3, msgs, sigs)
    for m, s in zip(msgs, sigs):
        one_by_one.add(k.pub_key(), m, s)
    assert (bulk._pubs, bulk._msgs, bulk._sigs) == (
        one_by_one._pubs, one_by_one._msgs, one_by_one._sigs)
    # a bytearray is copied, as add() copies it
    held = ed25519.BatchVerifier()
    held.add_many([k.pub_key()], [bytearray(b"m0")], [bytearray(sigs[0])])
    assert type(held._msgs[0]) is bytes and type(held._sigs[0]) is bytes
    foreign = sr25519.gen_priv_key().pub_key()
    for keys, ss, exc, text in (
        ([k.pub_key(), foreign, k.pub_key()], sigs, TypeError, "pubkey is not Ed25519"),
        ([k.pub_key(), ed25519.PubKey(b"\x01" * 31), k.pub_key()], sigs, ValueError,
         "pubkey size is incorrect; expected: 32, got 31"),
        ([k.pub_key()] * 3, [sigs[0], sigs[1][:63], sigs[2]], ValueError, "invalid signature"),
        # two faults: the earlier entry's is raised
        ([k.pub_key(), k.pub_key(), foreign], [sigs[0], sigs[1][:63], sigs[2]], ValueError,
         "invalid signature"),
    ):
        with pytest.raises(exc) as e:
            ed25519.BatchVerifier().add_many(keys, msgs, ss)
        assert str(e.value) == text
    with pytest.raises(ValueError):
        ed25519.BatchVerifier().add_many([k.pub_key()], msgs, sigs)
    # a key type without a bulk entry of its own takes the default
    sk = sr25519.gen_priv_key()
    bv = sr25519.BatchVerifier()
    bv.add_many([sk.pub_key()] * 2, [b"a", b"b"], [sk.sign(b"a"), sk.sign(b"b")])
    assert bv.verify() == (True, [True, True])


class _ForeignKey(crypto.PubKey):
    """Says it is an Ed25519 key and is none of this program's."""

    def __init__(self, inner):
        self._inner = inner

    def address(self):
        return self._inner.address()

    def bytes(self):
        return self._inner.bytes()

    def verify_signature(self, msg, sig):
        return self._inner.verify_signature(msg, sig)

    def type(self):
        return ed25519.KEY_TYPE


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("fault", ["short_key", "foreign_key"])
def test_a_key_the_seam_would_refuse_is_refused_with_adds_error(fault, mode, monkeypatch):
    """The set checks its keys once, for the bytes column; a set with a key
    add() refuses has no such column and the seam is handed the objects, so
    the refusal is add()'s, word for word, as the old loop raised it."""
    signers, vals, commit = _case(mode, 31, [BLOCK_ID_FLAG_COMMIT] * N)
    at = 1
    sound = vals.validators[at]
    bad_key = (ed25519.PubKey(sound.pub_key.bytes()[:31]) if fault == "short_key"
               else _ForeignKey(sound.pub_key))
    members = [dataclasses.replace(v, pub_key=bad_key) if i == at else v
               for i, v in enumerate(vals.validators)]
    bad_vals = ValidatorSet(members)
    assert [v.address for v in bad_vals.validators] == [v.address for v in vals.validators]
    cols, _ = bad_vals.columns()
    assert cols.key_type == ed25519.KEY_TYPE and cols.pub_bytes is None
    ignore, count, count_all, by_index = MODES[mode]
    needed = bad_vals.total_voting_power() - 1  # nothing is cut: the bad key is reached
    bv, ref_bv = RecordingVerifier(), RecordingVerifier()
    columnar = _outcome(lambda: validation._verify_commit_batch(
        CHAIN, bad_vals, _fresh(commit), needed, ignore, count, count_all, by_index, cols, bv))
    loop = _outcome(lambda: _loop_reference(
        CHAIN, bad_vals, _fresh(commit), needed, ignore, count, count_all, by_index, ref_bv))
    want = (("ValueError", "pubkey size is incorrect; expected: 32, got 31")
            if fault == "short_key" else ("TypeError", "pubkey is not Ed25519"))
    assert columnar == loop == want
    assert bv.handed[0][1] is None


def test_a_changed_set_gets_new_columns_and_new_key_bytes():
    from cometbft_tpu.types import validator_set

    vals = _signers(32)
    cols, _ = vals.columns()
    copy = vals.copy()
    before = validator_set.columns_counters()
    assert copy.columns() == (cols, True) and copy.columns()[0].pub_bytes is cols.pub_bytes
    newcomer = KEYS[N].pub_key()
    copy.update_with_change_set([Validator.new(newcomer, 1000)])
    changed, reused = copy.columns()
    after = validator_set.columns_counters()
    assert not reused and after["built"] - before["built"] == 1
    assert changed.pub_bytes is not cols.pub_bytes
    assert changed.pub_bytes == tuple(v.pub_key.bytes() for v in copy.validators)
    assert changed.pub_bytes[0] == newcomer.bytes() and newcomer.bytes() not in cols.pub_bytes
    assert vals.columns() == (cols, True)  # the set it was copied from keeps its own


def test_key_bytes_stand_for_the_key_objects_in_add_many():
    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.crypto import sr25519

    keys = [x.pub_key() for x in KEYS[:3]]
    msgs = [b"m0", b"m1", b"m2"]
    sigs = [x.sign(m) for x, m in zip(KEYS, msgs)]
    raw = ed25519.BatchVerifier.key_bytes(keys)
    assert raw == tuple(x.bytes() for x in keys) == crypto_batch.key_bytes("ed25519", keys)
    assert ed25519.BatchVerifier.key_bytes([keys[0], ed25519.PubKey(b"\x01" * 31)]) is None
    assert ed25519.BatchVerifier.key_bytes([keys[0], sr25519.gen_priv_key().pub_key()]) is None
    assert crypto_batch.key_bytes(None, keys) is None
    plain, handed = ed25519.BatchVerifier(), ed25519.BatchVerifier()
    plain.add_many(keys, msgs, sigs)

    class Untouchable:
        def __getattribute__(self, name):
            raise AssertionError("a key object was looked at")

    handed.add_many([Untouchable()] * 3, msgs, sigs, key_bytes=raw)
    assert (handed._pubs, handed._msgs, handed._sigs) == (plain._pubs, plain._msgs, plain._sigs)
    # a short signature is still add()'s to refuse, at the first bad entry
    with pytest.raises(ValueError) as e:
        ed25519.BatchVerifier().add_many(keys, msgs, [sigs[0], sigs[1][:63], sigs[2][:1]], raw)
    assert str(e.value) == "invalid signature"
    for columns in ((keys[:2], msgs, sigs, raw), (keys, msgs, sigs, raw[:2])):
        with pytest.raises(ValueError):
            ed25519.BatchVerifier().add_many(*columns)
    # an engine with no bulk entry of its own has no bytes column, and its
    # add_many is the default: a loop of add() over the key objects
    sk = sr25519.gen_priv_key()
    assert sr25519.BatchVerifier.key_bytes([sk.pub_key()]) is None
    assert crypto_batch.key_bytes(sr25519.KEY_TYPE, [sk.pub_key()]) is None
    assert sr25519.BatchVerifier.add_many is crypto.BatchVerifier.add_many
    added = []

    class Looping(sr25519.BatchVerifier):
        def add(self, key, message, signature):
            added.append((key, message))
            super().add(key, message, signature)

    bv = Looping()
    bv.add_many([sk.pub_key()] * 2, [b"a", b"b"], [sk.sign(b"a"), sk.sign(b"b")],
                key_bytes=[b"ignored"] * 2)
    assert [m for _, m in added] == [b"a", b"b"] and bv.verify() == (True, [True, True])
