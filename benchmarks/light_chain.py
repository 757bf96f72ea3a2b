"""A seeded chain of light blocks whose validator set turns over, on disk,
and the provider that serves it: the serving side of the light-client cell.
Everything that builds runs in processes that never import JAX.

Validator k (creation order) holds `fixtures.key_seed(seed, tag, k)`; the
set at height h is validators `rotate * (h - 1)` to `rotate * (h - 1) +
validators - 1`, equal power: each height the `rotate` oldest leave and as
many new ones join (upstream's `ChangeKeys`, light/helpers_test.go). A
header every `block_seconds`; each light block's commit is signed by every
validator of its set, each signature made and checked by OpenSSL.

Built in three steps, the two wide ones in the fixture workers: every
set's hash; the headers, in order (each names the one before it); then
each height's commit, signed, checked and encoded with its header and set
as `LightBlock.encode()` gives it, into `lb-<height>.bin`. The marker,
written last, holds the headers' hashes.
"""

from __future__ import annotations

import hashlib
import json
import multiprocessing
import os
import sys
import time

import fixtures

MARKER_FILE = "light-chain.json"


def members(h: int, n_vals: int, rotate: int) -> range:
    """Creation indices of the validators of height h's set."""
    return range(rotate * (h - 1), rotate * (h - 1) + n_vals)


def header_time(seed: int, h: int, block_seconds: int):
    from cometbft_tpu.types import Time

    return Time(1_700_000_000 + seed % 1_000_000 + block_seconds * h, 0)


def block_file(out_dir: str, h: int) -> str:
    return os.path.join(out_dir, f"lb-{h}.bin")


def _keys(seed: int, tag: str, lo: int, hi: int) -> dict:
    """Private keys of validators lo..hi-1 by creation index."""
    from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey

    return {k: Ed25519PrivateKey.from_private_bytes(fixtures.key_seed(seed, tag, k)) for k in range(lo, hi)}


def _valset(keys: dict, idx: range):
    """(ValidatorSet, creation index of each validator in set order)."""
    from cometbft_tpu.crypto import ed25519
    from cometbft_tpu.types.validator import Validator
    from cometbft_tpu.types.validator_set import ValidatorSet

    pubs = {keys[k].public_key().public_bytes_raw(): k for k in idx}
    vals = ValidatorSet([Validator.new(ed25519.PubKey(p), 10) for p in pubs])
    return vals, [pubs[v.pub_key.bytes()] for v in vals.validators]


def _set_hashes(job) -> dict:
    """Worker: {height: (set hash, proposer address)} for heights lo..hi-1."""
    seed, tag, n_vals, rotate, lo, hi = job
    keys = _keys(seed, tag, rotate * (lo - 1), rotate * (hi - 2) + n_vals)
    out = {}
    for h in range(lo, hi):
        vals, _ = _valset(keys, members(h, n_vals, rotate))
        out[h] = (vals.hash(), vals.get_proposer().address)
    _no_jax()
    return out


def _sign_heights(job) -> int:
    """Worker: the light blocks of the given heights, from their encoded
    headers; every signature checked by OpenSSL. Returns signatures made."""
    from cometbft_tpu.types.block import (
        BLOCK_ID_FLAG_COMMIT, BlockID, Commit, CommitSig, Header, PartSetHeader, SignedHeader,
    )
    from cometbft_tpu.types.light_block import LightBlock

    seed, tag, n_vals, rotate, headers, out_dir = job
    lo, hi = min(headers), max(headers)
    keys = _keys(seed, tag, rotate * (lo - 1), rotate * (hi - 1) + n_vals)
    signed = 0
    for h, encoded in headers.items():
        header = Header.decode(encoded)
        vals, order = _valset(keys, members(h, n_vals, rotate))
        bid = BlockID(header.hash(), PartSetHeader(1, hashlib.sha256(b"parts/%d/%d" % (seed, h)).digest()))
        skel = Commit(height=h, round=0, block_id=bid, signatures=[
            CommitSig(BLOCK_ID_FLAG_COMMIT, v.address, header.time.add_nanos(10**9 + 1000 * (j + 1)),
                      fixtures.PLACEHOLDER_SIG)
            for j, v in enumerate(vals.validators)
        ])
        sigs = []
        for k, cs, sb in zip(order, skel.signatures, skel.vote_sign_bytes_all(header.chain_id)):
            sig = keys[k].sign(bytes(sb))
            keys[k].public_key().verify(sig, bytes(sb))  # raises InvalidSignature
            sigs.append(CommitSig(cs.block_id_flag, cs.validator_address, cs.timestamp, sig))
        commit = Commit(height=h, round=0, block_id=bid, signatures=sigs)
        with open(block_file(out_dir, h), "wb") as f:
            f.write(LightBlock(SignedHeader(header, commit), vals).encode())
        signed += len(sigs)
    _no_jax()
    return signed


def _no_jax() -> None:
    """A child (the builder, a fixture worker) that imported JAX would want
    the chip its parent holds."""
    if multiprocessing.parent_process() is not None and "jax" in sys.modules:
        raise RuntimeError("a light chain worker imported JAX")


def build_chain(seed: int, tag: str, n_vals: int, rotate: int, heights: int, block_seconds: int,
                out_dir: str, pool=None) -> dict:
    """Builds light blocks 1..heights into out_dir (module text); the marker
    last. `pool` (anything with `map`) stands in for the fixture workers."""
    from cometbft_tpu.types.block import BlockID, Header, PartSetHeader

    t0 = time.time()
    os.makedirs(out_dir, exist_ok=True)
    marker_path = os.path.join(out_dir, MARKER_FILE)
    if os.path.exists(marker_path):
        os.remove(marker_path)
    chain_id = fixtures.chain_id_for(seed, tag)
    own = pool is None
    if own:
        pool = fixtures.start_pool()
    try:
        n_jobs = 4 * fixtures.worker_count()
        step = -(-(heights + 1) // n_jobs)
        found = {}
        for part in pool.map(_set_hashes, [(seed, tag, n_vals, rotate, lo, min(lo + step, heights + 2))
                                           for lo in range(1, heights + 2, step)]):
            found.update(part)
        encoded, hashes = {}, {}
        last = BlockID()
        for h in range(1, heights + 1):
            header = Header(
                chain_id=chain_id, height=h, time=header_time(seed, h, block_seconds), last_block_id=last,
                validators_hash=found[h][0], next_validators_hash=found[h + 1][0],
                app_hash=hashlib.sha256(b"app/%d/%d" % (seed, h)).digest(), proposer_address=found[h][1],
            )
            encoded[h] = header.encode()
            hashes[h] = header.hash()
            last = BlockID(hashes[h], PartSetHeader(1, hashlib.sha256(b"parts/%d/%d" % (seed, h)).digest()))
        step = -(-heights // n_jobs)
        jobs = [(seed, tag, n_vals, rotate, {h: encoded[h] for h in range(lo, min(lo + step, heights + 1))}, out_dir)
                for lo in range(1, heights + 1, step)]
        checked = sum(pool.map(_sign_heights, jobs))
    finally:
        if own:
            pool.close()
            pool.join()
    marker = {
        "seed": seed, "validators": n_vals, "rotate": rotate, "heights": heights,
        "block_seconds": block_seconds, "chain_id": chain_id,
        "openssl_checked_signatures": checked, "build_s": round(time.time() - t0, 3),
        "header_hashes": {str(h): hashes[h].hex() for h in hashes},
    }
    with open(marker_path, "w") as f:
        json.dump(marker, f)
    _no_jax()
    return marker


def load_marker(out_dir: str, seed: int, n_vals: int, rotate: int, heights: int) -> dict | None:
    """The marker of a whole chain of these sizes in out_dir, else None."""
    try:
        with open(os.path.join(out_dir, MARKER_FILE)) as f:
            m = json.load(f)
    except (OSError, ValueError):
        return None
    want = (seed, n_vals, rotate, heights)
    return m if (m.get("seed"), m.get("validators"), m.get("rotate"), m.get("heights")) == want else None


def load_blocks(out_dir: str, heights: int) -> dict[int, bytes]:
    out = {}
    for h in range(1, heights + 1):
        with open(block_file(out_dir, h), "rb") as f:
            out[h] = f.read()
    return out


def make_provider(chain_id: str, encoded: dict[int, bytes]):
    """A provider over encoded light blocks (imported late: the class
    derives from the program's)."""
    from cometbft_tpu.light.provider import ErrLightBlockNotFound, Provider
    from cometbft_tpu.types.light_block import LightBlock

    class StoredProvider(Provider):
        """Each fetch decodes the stored bytes afresh, as a client decodes
        what arrived: never the same objects twice, nothing memoized on
        them (the set's hash and columns, the commit's sign bytes)."""

        def __init__(self):
            self.bytes_received = 0
            self.evidences = []

        def chain_id(self) -> str:
            return chain_id

        def light_block(self, height: int):
            data = encoded.get(height if height else max(encoded))
            if data is None:
                raise ErrLightBlockNotFound(f"no light block at height {height}")
            self.bytes_received += len(data)
            return LightBlock.decode(data)

        def report_evidence(self, ev) -> None:
            self.evidences.append(ev)

    return StoredProvider()


def plain(lb) -> dict:
    """A light block of the program as `reference/light_skipping.py` takes it."""
    def block_id(b):
        return b.hash, b.part_set_header.total, b.part_set_header.hash

    def at(t):
        return t.seconds, t.nanos

    h, c = lb.signed_header.header, lb.signed_header.commit
    return {
        "header": {
            "version_block": h.version.block, "version_app": h.version.app,
            "chain_id": h.chain_id, "height": h.height, "time": at(h.time),
            "last_block_id": block_id(h.last_block_id),
            "last_commit_hash": h.last_commit_hash, "data_hash": h.data_hash,
            "validators_hash": h.validators_hash, "next_validators_hash": h.next_validators_hash,
            "consensus_hash": h.consensus_hash, "app_hash": h.app_hash,
            "last_results_hash": h.last_results_hash, "evidence_hash": h.evidence_hash,
            "proposer_address": h.proposer_address,
        },
        "commit": {
            "height": c.height, "round": c.round, "block_id": block_id(c.block_id),
            "signatures": [(s.block_id_flag, s.validator_address, at(s.timestamp), s.signature)
                           for s in c.signatures],
        },
        "validators": [(v.pub_key.bytes(), v.voting_power) for v in lb.validator_set.validators],
    }


def reference_sync(job):
    """Fixture worker: the plain reference's sync of one root over the stored
    chain, every signature it reaches answered by `known` or else by the
    scalar ZIP-215 reference (each triple computed once here). With `tamper`
    = (height, encoded block) the sync is made again over the chain with that
    block in place of the stored one. Returns (the sync, the distinct triples
    it verified, the tampered sync or None)."""
    from fractions import Fraction

    from cometbft_tpu.types.light_block import LightBlock
    from reference import ed25519_zip215
    from reference import light_skipping as ref

    out_dir, chain_id, root, target, now, period_ns, drift_ns, trust, known, tamper = job
    answers = dict(known)
    touched = set()

    def verify(pub, msg, sig):
        key = (pub, msg, sig)
        touched.add(key)
        got = answers.get(key)
        if got is None:
            got = answers[key] = ed25519_zip215.verify_zip215(pub, msg, sig)
        return got

    def reader(swap):
        blocks = {}

        def fetch(h):
            if h not in blocks:
                if swap is not None and h == swap[0]:
                    data = swap[1]
                else:
                    with open(block_file(out_dir, h), "rb") as f:
                        data = f.read()
                blocks[h] = plain(LightBlock.decode(data))
            return blocks[h]

        return fetch

    def sync(fetch):
        return ref.verify_skipping(chain_id, fetch(root), fetch(target), fetch, period_ns, now, drift_ns,
                                   verify, Fraction(*trust))

    clean = sync(reader(None))
    distinct = len(touched)
    tampered = None if tamper is None else sync(reader(tamper))
    _no_jax()
    return clean, distinct, tampered


def tamper_index(chain_id: str, provider, root: int, target: int, now, period_ns: int, drift_ns: int,
                 trust, seed) -> int:
    """The commit signature of `target` a tampered chain flips, chosen by the
    plain reference's walk with every signature taken as good: inside the
    light check's prefix of the target's commit (so the hop that reaches the
    target verifies it), of a validator that no trusted block refused on the
    way to the target knows (so no refused hop reaches it first, in the
    reference's single path or in the client's batch). None where none is."""
    import random
    from fractions import Fraction

    from reference import light_skipping as ref
    from reference.commit_replay import COMMIT, address

    blocks = {}

    def fetch(h):
        if h not in blocks:
            blocks[h] = plain(provider.light_block(h))
        return blocks[h]

    walk = ref.verify_skipping(chain_id, fetch(root), fetch(target), fetch, period_ns, now, drift_ns,
                               lambda *_: True, Fraction(*trust))
    known = {address(pub) for f, to, how in walk.tried if to == target and how == "refused"
             for pub, _ in fetch(f)["validators"]}
    commit, vals = fetch(target)["commit"], fetch(target)["validators"]
    needed, tallied, candidates = sum(p for _, p in vals) * 2 // 3, 0, []
    for idx, (flag, addr, _, _) in enumerate(commit["signatures"]):
        if flag != COMMIT:
            continue
        if addr not in known:
            candidates.append(idx)
        tallied += vals[idx][1]
        if tallied > needed:
            break
    return random.Random(f"{seed}/tamper").choice(candidates) if candidates else None


def flip_commit_signature(data: bytes, index: int) -> bytes:
    """The encoded light block with one bit of commit signature `index` flipped."""
    from cometbft_tpu.types.block import SignedHeader
    from cometbft_tpu.types.light_block import LightBlock

    lb = LightBlock.decode(data)
    commit = fixtures.flip_signatures(lb.signed_header.commit, [index])
    return LightBlock(SignedHeader(lb.signed_header.header, commit), lb.validator_set).encode()
