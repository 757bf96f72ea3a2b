"""Seeded fixtures, made on the host by processes that never import JAX.

Everything here is a function of `--seed`: keys, chain id, timestamps,
block ids. Signatures are made with OpenSSL (`cryptography`) and checked
with OpenSSL as they are made, so a fixture that the system later refuses
is the system's fault and not the fixture's.

Adapted from `chip_smoke.py`'s `make_commits` / `flip_signatures` (PR 21),
which stay where they are; this copy signs in worker processes because
10,000 validators x 16 heights is some 25 s of OpenSSL on one core.
"""

from __future__ import annotations

import dataclasses
import hashlib
import multiprocessing
import os
import random

from cryptography.hazmat.primitives.asymmetric.ed25519 import (
    Ed25519PrivateKey,
    Ed25519PublicKey,
)

PLACEHOLDER_SIG = b"\x00" * 64


def chain_id_for(seed: int, tag: str) -> str:
    return f"bench-{tag}-{seed}"


def key_seed(seed: int, tag: str, i: int) -> bytes:
    return hashlib.sha256(b"%d/%s/%d" % (seed, tag.encode(), i)).digest()


def derive_pubkeys(seed: int, tag: str, n: int) -> list[bytes]:
    """The 32-byte public keys of validators 0..n-1, in creation order."""
    return [
        Ed25519PrivateKey.from_private_bytes(key_seed(seed, tag, i))
        .public_key()
        .public_bytes_raw()
        for i in range(n)
    ]


def vote_time(seed: int, height: int, idx: int):
    """Each validator's own clock: a second per height from a seeded epoch,
    nanoseconds scattered per validator as real precommits are."""
    from cometbft_tpu.types import Time

    return Time(
        1_700_000_000 + seed % 1_000_000 + height,
        (idx * 7919 + height * 104_729 + seed) % 1_000_000_000,
    )


def block_id_for(seed: int, height: int):
    from cometbft_tpu.types import BlockID
    from cometbft_tpu.types.part_set import PartSetHeader

    h = hashlib.sha256(b"block/%d/%d" % (seed, height)).digest()
    p = hashlib.sha256(b"parts/%d/%d" % (seed, height)).digest()
    return BlockID(h, PartSetHeader(1, p))


def commit_skeleton(seed: int, height: int, addresses, lo: int = 0):
    """A fully signed commit's shape over validators lo..lo+len(addresses),
    with placeholder signatures: what the sign bytes are computed from."""
    from cometbft_tpu.types import Commit
    from cometbft_tpu.types.block import CommitSig

    bid = block_id_for(seed, height)
    sigs = [
        CommitSig(
            block_id_flag=2,
            validator_address=addr,
            timestamp=vote_time(seed, height, lo + j),
            signature=PLACEHOLDER_SIG,
        )
        for j, addr in enumerate(addresses)
    ]
    return bid, Commit(height=height, round=0, block_id=bid, signatures=sigs)


def _sign_slice(job) -> dict[int, bytes]:
    """Worker: signatures of validators lo..hi (set order) at each height,
    as one 64-byte-per-validator blob per height. Every signature is
    verified by OpenSSL before it is returned."""
    seed, tag, chain_id, heights, lo, order = job
    keys = [Ed25519PrivateKey.from_private_bytes(key_seed(seed, tag, i)) for i in order]
    pubs = [k.public_key() for k in keys]
    out = {}
    for h in heights:
        _, skel = commit_skeleton(seed, h, [b"\x00" * 20] * len(order), lo)
        sbs = skel.vote_sign_bytes_all(chain_id)
        sigs = []
        for k, p, sb in zip(keys, pubs, sbs):
            s = k.sign(bytes(sb))
            p.verify(s, bytes(sb))  # raises InvalidSignature
            sigs.append(s)
        out[h] = b"".join(sigs)
    import sys

    if "jax" in sys.modules:
        raise RuntimeError("a fixture worker imported JAX")
    return out


def worker_count() -> int:
    return max(1, min(12, (os.cpu_count() or 2) - 2))


def start_pool():
    """A pool of fixture workers (spawned: they start from a fresh import
    and never see the parent's JAX)."""
    return multiprocessing.get_context("spawn").Pool(worker_count())


def make_validator_set(seed: int, tag: str, n_vals: int):
    """(ValidatorSet, order): `order[j]` is the creation index of the
    validator at position j of the set (the set sorts by address)."""
    from cometbft_tpu.crypto import ed25519
    from cometbft_tpu.types.validator import Validator
    from cometbft_tpu.types.validator_set import ValidatorSet

    pubs = derive_pubkeys(seed, tag, n_vals)
    vals = ValidatorSet([Validator.new(ed25519.PubKey(p), 10) for p in pubs])
    index_of = {p: i for i, p in enumerate(pubs)}
    order = [index_of[v.pub_key.bytes()] for v in vals.validators]
    return vals, order


def make_commits_async(pool, seed: int, tag: str, n_vals: int, heights: int):
    """Starts the signing of one fully signed Commit per height 1..heights
    by a seeded ValidatorSet in the pool's workers; the returned function
    waits for them and gives (chain_id, vals, [(block_id, commit)])."""
    from cometbft_tpu.types import Commit
    from cometbft_tpu.types.block import CommitSig

    chain_id = chain_id_for(seed, tag)
    vals, order = make_validator_set(seed, tag, n_vals)
    hs = list(range(1, heights + 1))
    n_jobs = max(1, min(n_vals // 64, 4 * worker_count()))
    step = -(-n_vals // n_jobs)
    jobs = [
        (seed, tag, chain_id, hs, lo, order[lo : lo + step])
        for lo in range(0, n_vals, step)
    ]
    pending = pool.map_async(_sign_slice, jobs)

    def finish():
        parts = pending.get()
        addresses = [v.address for v in vals.validators]
        out = []
        for h in hs:
            blob = b"".join(p[h] for p in parts)
            bid = block_id_for(seed, h)
            sigs = [
                CommitSig(2, addr, vote_time(seed, h, j), blob[64 * j : 64 * j + 64])
                for j, addr in enumerate(addresses)
            ]
            out.append((bid, Commit(height=h, round=0, block_id=bid, signatures=sigs)))
        return chain_id, vals, out

    return finish


def fresh_commit(commit):
    """The same commit as a new object, as a node that decoded it from the
    wire holds it: nothing memoized on it (sign bytes, hash)."""
    from cometbft_tpu.types import Commit

    return Commit(
        height=commit.height, round=commit.round,
        block_id=commit.block_id, signatures=list(commit.signatures),
    )


def flip_signatures(commit, indices):
    """The same commit with one bit of each given signature flipped."""
    sigs = list(commit.signatures)
    for i in indices:
        s = sigs[i].signature
        sigs[i] = dataclasses.replace(
            sigs[i], signature=s[:7] + bytes([s[7] ^ 0x10]) + s[8:]
        )
    bad = fresh_commit(commit)
    bad.signatures = sigs
    return bad


def openssl_verify(pub: bytes, msg: bytes, sig: bytes) -> bool:
    """Strict RFC 8032 verification by OpenSSL: the fixtures' first check."""
    from cryptography.exceptions import InvalidSignature

    try:
        Ed25519PublicKey.from_public_bytes(pub).verify(sig, msg)
        return True
    except (InvalidSignature, ValueError):
        return False


def sample_lanes(seed: int, n: int, k: int, must=()) -> list[int]:
    """A seeded sample of at least k lanes of n, always holding `must`."""
    rng = random.Random(f"{seed}/sample/{n}")
    chosen = set(must)
    rest = [i for i in range(n) if i not in chosen]
    chosen |= set(rng.sample(rest, min(len(rest), max(0, k - len(chosen)))))
    return sorted(chosen)
