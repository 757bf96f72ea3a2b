"""Aggregate BLS commits (BN254): wire form, three-mode verify parity, and
loud rejection of every tamper class.

The invariant under test is the ISSUE acceptance bar: aggregate accept /
reject decisions must be bit-identical to the per-vote path — a poisoned
aggregate REJECTS loudly in every verify mode, and no degraded tier can
wrong-accept one past the supervisor's anchor recompute.
"""

import copy
import os

import pytest

from cometbft_tpu.crypto import bn254, ed25519
from cometbft_tpu.sidecar.supervisor import ResilientBackend
from cometbft_tpu.types import BlockID, Commit, Vote
from cometbft_tpu.types.block import (
    AGG_SIGNATURE_SIZE,
    AGG_SIGNATURE_SIZE_COMPRESSED,
    PRECOMMIT_TYPE,
    CommitSig,
    aggregate_commit,
)
from cometbft_tpu.types.cmttime import Time
from cometbft_tpu.types.part_set import PartSetHeader
from cometbft_tpu.types.priv_validator import MockPV
from cometbft_tpu.types.validation import (
    Fraction,
    _batch_key_type,
    speculative_verify_triples,
    verify_commit,
    verify_commit_light,
    verify_commit_light_trusting,
)
from cometbft_tpu.types.validator import Validator
from cometbft_tpu.types.validator_set import ValidatorSet
from cometbft_tpu.types.vote import vote_to_commit_sig
from cometbft_tpu.wire import proto

pytestmark = pytest.mark.agg

CHAIN = "agg-chain"
HEIGHT = 5
BID = BlockID(b"h" * 32, PartSetHeader(1, b"p" * 32))


def _signed_commit(pvs, vals, height=HEIGHT, bid=BID):
    sigs = []
    by_addr = {pv.address(): pv for pv in pvs}
    for idx, val in enumerate(vals.validators):
        vote = Vote(
            type=PRECOMMIT_TYPE,
            height=height,
            round=0,
            block_id=bid,
            timestamp=Time(1700000000 + idx, 0),
            validator_address=val.address,
            validator_index=idx,
        )
        sigs.append(vote_to_commit_sig(by_addr[val.address].sign_vote(CHAIN, vote)))
    return Commit(height=height, round=0, block_id=bid, signatures=sigs)


@pytest.fixture(scope="module")
def bn_set():
    """One 4-validator all-bn254 set + per-vote commit + its aggregate,
    built once — BN254 pairings are pure-Python-slow, so every test below
    shares (and never mutates) these."""
    pvs = [MockPV(bn254.gen_priv_key()) for _ in range(4)]
    vals = ValidatorSet([Validator.new(pv.get_pub_key(), 10) for pv in pvs])
    commit = _signed_commit(pvs, vals)
    agg = aggregate_commit(commit, vals)
    return pvs, vals, commit, agg


def test_per_vote_commit_batches_through_registry(bn_set):
    # Satellite: the batch registry keys on the SET's single key type, not
    # the proposer's — a homogeneous bn254 set must pick the bn254 engine.
    _, vals, commit, _ = bn_set
    assert _batch_key_type(vals, commit) == bn254.KEY_TYPE
    verify_commit(CHAIN, vals, BID, HEIGHT, commit)


def test_mixed_valset_falls_back_to_scalar(bn_set):
    # Regression for the proposer-keyed dispatch bug: a bn254+ed25519 set
    # must neither batch nor aggregate, and still verify per-signature.
    mixed_pvs = [MockPV(bn254.gen_priv_key()) for _ in range(3)] + [
        MockPV(ed25519.gen_priv_key())
    ]
    mixed_vals = ValidatorSet(
        [Validator.new(pv.get_pub_key(), 10) for pv in mixed_pvs]
    )
    mcommit = _signed_commit(mixed_pvs, mixed_vals)
    assert _batch_key_type(mixed_vals, mcommit) is None
    assert aggregate_commit(mcommit, mixed_vals) is mcommit
    verify_commit(CHAIN, mixed_vals, BID, HEIGHT, mcommit)


def test_aggregate_form_and_wire_roundtrip(bn_set):
    _, vals, commit, agg = bn_set
    assert agg.is_aggregate()
    assert len(agg.agg_signature) == AGG_SIGNATURE_SIZE_COMPRESSED
    assert all(not cs.signature for cs in agg.signatures)
    assert all(agg.agg_signer(i) for i in range(len(vals.validators)))
    agg.validate_basic()
    dec = Commit.decode(agg.encode())
    assert dec == agg
    # The headline wire win: one G2 point + bitmap vs n per-vote columns.
    per_vote = sum(len(cs.signature) for cs in commit.signatures)
    assert len(agg.agg_signature) + len(agg.agg_bitmap) < per_vote / 3


def test_legacy_commit_encodes_without_agg_fields(bn_set):
    # Default-off fidelity: a per-vote commit's encoding must carry no
    # field-5/6 bytes at all (byte-identical to the pre-aggregate wire).
    _, _, commit, _ = bn_set
    fields = proto.decode_fields(commit.encode())
    assert proto.get_bytes(fields, 5) == b""
    assert proto.get_bytes(fields, 6) == b""
    assert Commit.decode(commit.encode()) == commit


def test_aggregate_verifies_in_all_three_modes(bn_set):
    _, vals, _, agg = bn_set
    verify_commit(CHAIN, vals, BID, HEIGHT, agg)
    verify_commit_light(CHAIN, vals, BID, HEIGHT, agg)
    verify_commit_light_trusting(CHAIN, vals, agg, Fraction(1, 3))


def test_speculative_triples_skip_aggregates(bn_set):
    # The light client's prewarm path has no per-sig triples to extract
    # from an aggregate; it must return empty, not crash or fabricate.
    _, vals, _, agg = bn_set
    assert speculative_verify_triples(CHAIN, vals, vals, agg, Fraction(1, 3)) == []


def test_poisoned_aggregate_rejected_in_all_modes(bn_set):
    _, vals, _, agg = bn_set
    bad = copy.deepcopy(agg)
    # A valid-looking G2 point over the WRONG signer subset.
    bad.agg_signature = bn254.aggregate_signatures(
        [cs.signature for cs in bn_set[2].signatures[:3]]
    )
    for fn in (
        lambda: verify_commit(CHAIN, vals, BID, HEIGHT, bad),
        lambda: verify_commit_light(CHAIN, vals, BID, HEIGHT, bad),
        lambda: verify_commit_light_trusting(CHAIN, vals, bad, Fraction(1, 3)),
    ):
        with pytest.raises(ValueError, match="invalid aggregate signature"):
            fn()


def test_bad_signer_poisons_whole_aggregate(bn_set):
    pvs, vals, commit, _ = bn_set
    sigs = list(commit.signatures)
    vote = Vote(
        type=PRECOMMIT_TYPE,
        height=HEIGHT,
        round=0,
        block_id=BID,
        timestamp=Time(1700000001, 0),
        validator_address=vals.validators[1].address,
        validator_index=1,
    )
    sigs[1] = vote_to_commit_sig(MockPV(bn254.gen_priv_key()).sign_vote(CHAIN, vote))
    agg_bad = aggregate_commit(
        Commit(height=HEIGHT, round=0, block_id=BID, signatures=sigs), vals
    )
    assert agg_bad.is_aggregate()
    with pytest.raises(ValueError, match="invalid aggregate signature"):
        verify_commit(CHAIN, vals, BID, HEIGHT, agg_bad)


def test_absent_entry_aggregate(bn_set):
    pvs, vals, commit, _ = bn_set
    sigs = list(commit.signatures)
    sigs[2] = CommitSig.absent()
    agg = aggregate_commit(
        Commit(height=HEIGHT, round=0, block_id=BID, signatures=sigs), vals
    )
    assert agg.is_aggregate()
    assert not agg.agg_signer(2) and agg.agg_signer(3)
    agg.validate_basic()
    verify_commit(CHAIN, vals, BID, HEIGHT, agg)  # 3/4 power > 2/3
    verify_commit_light(CHAIN, vals, BID, HEIGHT, agg)

    # Claiming the absent validator signed must fail BOTH validate_basic
    # (bitmap/flag consistency) and verify (never reaches the pairing).
    tam = copy.deepcopy(agg)
    bm = bytearray(tam.agg_bitmap)
    bm[0] |= 1 << 2
    tam.agg_bitmap = bytes(bm)
    with pytest.raises(ValueError):
        tam.validate_basic()
    with pytest.raises(ValueError):
        verify_commit(CHAIN, vals, BID, HEIGHT, tam)


def test_chaos_flip_cannot_wrong_accept(bn_set, monkeypatch):
    # Composition with the fault framework: a tier that ALWAYS flips its
    # verdict to accept must be caught by the supervisor's full anchor
    # recompute — the poisoned aggregate still rejects, loudly.
    _, vals, commit, agg = bn_set
    monkeypatch.setenv("CMTPU_FAULTS", "flip:1.0")
    monkeypatch.setenv("CMTPU_CROSSCHECK", "full")
    monkeypatch.setenv("CMTPU_RETRIES", "0")
    chain = ResilientBackend(bn254.build_bn254_chain())
    pubs = [v.pub_key.bytes() for v in vals.validators]
    msgs = [b"not-the-signed-bytes-%d" % i for i in range(4)]
    assert chain.aggregate_verify(pubs, msgs, agg.agg_signature) is False
    assert chain.counters_["crosscheck_catches"] >= 1

    # End-to-end: route the types-layer verify through the flipping chain.
    bn254.set_bn254_backend(chain)
    try:
        bad = copy.deepcopy(agg)
        bad.agg_signature = bn254.aggregate_signatures(
            [cs.signature for cs in commit.signatures[:3]]
        )
        with pytest.raises(ValueError, match="invalid aggregate signature"):
            verify_commit(CHAIN, vals, BID, HEIGHT, bad)
        verify_commit(CHAIN, vals, BID, HEIGHT, agg)  # good one still lands
    finally:
        bn254.set_bn254_backend(None)


@pytest.mark.slow
@pytest.mark.parametrize(
    "key_types,extra_env",
    [
        ("ed25519,bn254", {}),  # mixed set: per-vote, scalar dispatch
        ("bn254", {"CMTPU_AGG_COMMITS": "1"}),  # live aggregate consensus
    ],
    ids=["mixed-keys", "aggregate"],
)
def test_devnet_commits_with_key_types(key_types, extra_env):
    """End-to-end satellite: an in-process devnet with non-ed25519
    consensus keys produces and verifies blocks — and with
    CMTPU_AGG_COMMITS=1 every block past the first embeds (and every
    peer verifies) an aggregate last commit. Pure-Python pairings make
    this minutes-slow; `slow` keeps it out of tier-1."""
    import socket
    import subprocess
    import sys as _sys

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **extra_env}
    blocks = 2 if "bn254" == key_types else 1
    out = subprocess.run(
        [_sys.executable, "-m", "cometbft_tpu.cmd", "devnet",
         "--validators", "2", "--blocks", str(blocks),
         "--key-types", key_types, "--block-interval", "0.2",
         "--rpc-port", str(port)],
        capture_output=True, text=True, timeout=420, env=env,
    )
    assert f"devnet done at height {blocks}" in out.stdout, (
        out.stdout[-2000:] + out.stderr[-2000:]
    )


@pytest.mark.slow
def test_device_backend_decision_parity(bn_set, monkeypatch):
    # The device multi-pairing kernel must agree with the host engine on
    # both verdicts (bucket 8: 7 signers + the G1 generator lane). Carries
    # `slow`: first call pays the XLA compile (persistent cache softens it).
    monkeypatch.setenv("CMTPU_BN254_DEVICE", "1")
    from cometbft_tpu.ops import bn254_kernel

    if not bn254_kernel.device_available():
        pytest.skip("bn254 device kernel unavailable")
    privs = [bn254.gen_priv_key() for _ in range(7)]
    msgs = [b"msg-%d" % i for i in range(7)]
    pubs = [p.pub_key().bytes() for p in privs]
    agg = bn254.aggregate_signatures(
        [p.sign(m) for p, m in zip(privs, msgs)]
    )
    dev = bn254_kernel.Bn254DeviceBackend()
    assert dev.aggregate_verify(pubs, msgs, agg) is True
    assert dev.aggregate_verify(pubs, list(reversed(msgs)), agg) is False


# ---------------------------------------------------------------------------
# Round 10: compressed G2 aggregate wire form.


def test_g2_compression_roundtrip():
    privs = [bn254.gen_priv_key() for _ in range(5)]
    sigs = [p.sign(b"msg-%d" % i) for i, p in enumerate(privs)]
    # Round-trip each individual signature AND the aggregate sum, hitting
    # both flag values (sign of y varies per point).
    points = [bn254.g2_unmarshal(s) for s in sigs]
    points.append(bn254.g2_unmarshal(bn254.aggregate_signatures(sigs)))
    for q in points:
        comp = bn254.g2_compress(q)
        assert len(comp) == bn254.SIGNATURE_SIZE_COMPRESSED
        assert bn254.g2_decompress(comp) == q
        # g2_unmarshal dispatches on length, so the compressed form flows
        # through every verify path unchanged.
        assert bn254.g2_unmarshal(comp) == q
    # Infinity encodes to the flagged zero block and back.
    inf = bn254.g2_compress(None)
    assert inf[0] == 0b01 << 6 and not any(inf[1:])
    assert bn254.g2_decompress(inf) is None


def test_g2_compressed_and_uncompressed_verify_identically():
    privs = [bn254.gen_priv_key() for _ in range(4)]
    msgs = [b"m-%d" % i for i in range(4)]
    pubs = [p.pub_key().bytes() for p in privs]
    sigs = [p.sign(m) for p, m in zip(privs, msgs)]
    full = bn254.aggregate_signatures(sigs)
    comp = bn254.aggregate_signatures_compressed(sigs)
    assert len(full) == 128 and len(comp) == 64
    assert bn254.g2_unmarshal(comp) == bn254.g2_unmarshal(full)
    assert bn254.verify_aggregate(pubs, msgs, comp) is True
    assert bn254.verify_aggregate(pubs, msgs, full) is True
    assert bn254.verify_aggregate_slow(pubs, msgs, comp) is True
    # Wrong message set rejects in the compressed form too.
    assert bn254.verify_aggregate(pubs, list(reversed(msgs)), comp) is False


def test_g2_decompress_rejects_tampered_encodings():
    priv = bn254.gen_priv_key()
    comp = bytearray(bn254.g2_compress(bn254.g2_unmarshal(priv.sign(b"m"))))

    # Flipped flag: same x, other y root -> still on-curve and in-subgroup,
    # but it MUST decode to the negated point, not the original.
    flipped = bytearray(comp)
    flipped[0] ^= 0b01 << 6
    q = bn254.g2_decompress(bytes(comp))
    assert bn254.g2_decompress(bytes(flipped)) == (q[0], bn254.f2_neg(q[1]))

    # Uncompressed-flag first byte (0b00) is not a valid compressed form.
    bare = bytearray(comp)
    bare[0] &= 0b0011_1111
    with pytest.raises(ValueError):
        bn254.g2_decompress(bytes(bare))

    # Corrupt x: overwhelmingly lands off-curve (no Fp2 sqrt) or out of
    # subgroup; either way it must raise, never return a wrong point.
    bad_x = bytearray(comp)
    bad_x[40] ^= 0xFF
    with pytest.raises(ValueError):
        bn254.g2_decompress(bytes(bad_x))

    # Non-canonical infinity (flag set but trailing garbage).
    bad_inf = bytearray(64)
    bad_inf[0] = 0b01 << 6
    bad_inf[63] = 1
    with pytest.raises(ValueError):
        bn254.g2_decompress(bytes(bad_inf))

    # Wrong lengths.
    for n in (0, 32, 63, 65, 127):
        with pytest.raises(ValueError):
            bn254.g2_decompress(b"\x00" * n)


def test_uncompressed_aggregate_commit_still_validates(bn_set):
    # Blocks produced before round 10 carry the 128-byte aggregate; they
    # must keep decoding, validating, and verifying.
    _, vals, commit, agg = bn_set
    legacy = copy.deepcopy(agg)
    legacy.agg_signature = bn254.g2_marshal(
        bn254.g2_unmarshal(agg.agg_signature)
    )
    assert len(legacy.agg_signature) == AGG_SIGNATURE_SIZE
    legacy.validate_basic()
    dec = Commit.decode(legacy.encode())
    assert dec == legacy
    verify_commit(CHAIN, vals, BID, HEIGHT, legacy)


# ---------------------------------------------------------------------------
# Round 10: proof of possession at key registration.


def test_proof_of_possession_roundtrip():
    priv = bn254.gen_priv_key()
    pop = bn254.prove_possession(priv)
    assert len(pop) == bn254.SIGNATURE_SIZE_COMPRESSED
    assert bn254.verify_possession(priv.pub_key().bytes(), pop) is True
    # A proof is bound to ITS key: another key cannot reuse it, and junk
    # never verifies (and never raises).
    other = bn254.gen_priv_key()
    assert bn254.verify_possession(other.pub_key().bytes(), pop) is False
    assert bn254.verify_possession(priv.pub_key().bytes(), b"\x00" * 64) is False
    assert bn254.verify_possession(priv.pub_key().bytes(), b"junk") is False
    # The PoP domain tag means a consensus signature over the pubkey bytes
    # is NOT a valid proof — registration and voting never cross.
    vote_style = priv.sign(priv.pub_key().bytes())
    assert bn254.verify_possession(priv.pub_key().bytes(), vote_style) is False


def test_rogue_key_cannot_prove_possession():
    # The attack PoP exists to stop: publish pk' = [t]G1 - pk_honest so the
    # "aggregate" of {pk_honest, pk'} collapses to [t]G1, which the attacker
    # can sign for alone. The attacker KNOWS t but not the discrete log of
    # pk', so no valid proof for pk' can be produced from t.
    honest = bn254.gen_priv_key()
    t = 123456789
    pk_h = bn254.g1_decompress(honest.pub_key().bytes())
    rogue_pt = bn254._g1_add(
        bn254._g1_mul(t, bn254.G1), (pk_h[0], (bn254.P - pk_h[1]) % bn254.P)
    )
    rogue_pub = bn254.g1_compress(rogue_pt)
    # Best effort with what the attacker knows: sign the PoP message with t.
    forged = bn254.PrivKey(t.to_bytes(32, "big")).sign(
        bn254.pop_sign_bytes(rogue_pub)
    )
    assert bn254.verify_possession(rogue_pub, forged) is False


def _genesis_with(validators):
    from cometbft_tpu.types.cmttime import Time
    from cometbft_tpu.types.genesis import GenesisDoc

    return GenesisDoc(
        chain_id="pop-chain",
        genesis_time=Time(1700000000, 0),
        validators=validators,
    )


def test_genesis_enforces_bn254_pop():
    from cometbft_tpu.types.genesis import GenesisDoc, GenesisValidator

    priv = bn254.gen_priv_key()
    pub = priv.pub_key()

    missing = _genesis_with([GenesisValidator(pub.address(), pub, 10, "v0")])
    with pytest.raises(ValueError, match="proof_of_possession"):
        missing.validate_and_complete()

    wrong = _genesis_with(
        [
            GenesisValidator(
                pub.address(), pub, 10, "v0",
                pop=bn254.prove_possession(bn254.gen_priv_key()),
            )
        ]
    )
    with pytest.raises(ValueError, match="rogue"):
        wrong.validate_and_complete()

    good = _genesis_with(
        [
            GenesisValidator(
                pub.address(), pub, 10, "v0", pop=bn254.prove_possession(priv)
            )
        ]
    )
    good.validate_and_complete()
    # The proof survives the genesis.json round trip and re-validates
    # (from_json runs validate_and_complete itself).
    doc2 = GenesisDoc.from_json(good.to_json())
    assert doc2.validators[0].pop == good.validators[0].pop

    # Non-aggregating key types need no proof, and their JSON carries none.
    ed_pv = MockPV(ed25519.gen_priv_key())
    ed_doc = _genesis_with(
        [GenesisValidator(ed_pv.address(), ed_pv.get_pub_key(), 10, "e0")]
    )
    ed_doc.validate_and_complete()
    assert "proof_of_possession" not in ed_doc.validators[0].to_json()


def test_testnet_cli_emits_pops_for_bn254(tmp_path):
    from cometbft_tpu.cmd.__main__ import main as cli
    from cometbft_tpu.types.genesis import GenesisDoc

    out = str(tmp_path / "net")
    assert cli([
        "testnet", "--validators", "2", "--non-validators", "0",
        "--key-types", "bn254,ed25519",
        "--output-dir", out, "--chain-id", "pop-net",
    ]) == 0
    doc = GenesisDoc.from_file(
        os.path.join(out, "node0", "config", "genesis.json")
    )
    by_type = {v.pub_key.type(): v for v in doc.validators}
    assert by_type["bn254"].pop and not by_type["ed25519"].pop
