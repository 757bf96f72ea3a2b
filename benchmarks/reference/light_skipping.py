"""The benchmark's yardstick for a light client's skipping sync: which light
blocks a client that trusts the root may store, and where a sync from a
chain with a bad commit stops, by CometBFT's own rules in plain Python:

    light/client.go:706 verifySkipping   the walk: try the target from the
        last verified block; a hop the trusted set cannot vouch for
        (ErrNewValSetCantBeTrusted) sends it to the block halfway
        (verifySkippingNumerator / Denominator = 1/2), fetched only when no
        block already fetched lies there; a verified block becomes the new
        lower bound and the walk goes back to the target; any other error
        ends the sync (ErrVerificationFailed{From, To, Reason});
    light/verifier.go:32-80 VerifyNonAdjacent, 93-133 VerifyAdjacent,
        153-192 verifyNewHeaderAndVals, 207-210 HeaderExpired;
    types/validation.go:94-135 VerifyCommitLightTrusting (by address, a
        validator counted once), 59-84 VerifyCommitLight (by index), both
        by the single path of 265-340: signatures in commit order, each FOR
        the block checked as it is reached, power added, stop past the
        quorum; a signature that does not verify before then refuses.

No batch, no cache, no speculation, no columns. What a light client stores
is the trace: the root, every block a hop verified, and the target. A sync
that fails stores nothing of its walk (client.go verifyLightBlock: the
store is written only once the whole walk verified), so the store holds the
root alone.

A light block is a dict: `header` (`commit_replay.header_hash`'s dict),
`commit` (`height`, `round`, `block_id` as (hash, parts total, parts hash),
`signatures` as (flag, validator address, time, signature) tuples) and
`validators`, (public key, power) in the set's order. Hashes are
`rfc6962.py`'s, sign bytes `commit_replay.py`'s. Nothing here imports the
program. `verify(pub, msg, sig)` may be handed in by a caller that holds the
scalar reference's answers for the same triples; whatever is handed in must
be those answers.
"""

from __future__ import annotations

from fractions import Fraction
from typing import NamedTuple

from . import block_proto as bp
from . import rfc6962
from .commit_replay import COMMIT, address, header_hash, sign_bytes
from .ed25519_zip215 import verify_zip215

TRUST_LEVEL = Fraction(1, 3)  # light/verifier.go DefaultTrustLevel


class CantBeTrusted(Exception):
    """ErrNewValSetCantBeTrusted: the trusted set signed too little."""


class Invalid(Exception):
    """Any other reason a hop fails: the sync ends there."""


class Sync(NamedTuple):
    stored: list  # heights the client's store holds after the sync, in order
    refused: tuple | None  # (from, to) of the hop that ended a failed sync
    why: str | None
    tried: list  # every hop tried, in order, as (from, to, "ok" / "refused" / "invalid")


def valset_hash(validators) -> bytes:
    """ValidatorSet.Hash: the root over SimpleValidator{PublicKey{ed25519}, power}."""
    return rfc6962.root([bp._every(1, bp._bytes(1, pub)) + bp._scalar(2, power)
                         for pub, power in validators])


def _block_id_of(lb) -> tuple:
    return tuple(lb["commit"]["block_id"])


def _walk(chain_id: str, commit, verify, needed: int, validator_of) -> None:
    """The single path: `validator_of(idx, address)` is (key, power, who) or
    None for a signer the set does not know; `who` names a validator once."""
    seen: dict = {}
    tallied = 0
    for idx, (flag, addr, at, sig) in enumerate(commit["signatures"]):
        if flag != COMMIT:
            continue
        val = validator_of(idx, addr)
        if val is None:
            continue
        pub, power, who = val
        if who in seen:
            raise Invalid(f"double vote from validator {who} ({seen[who]} and {idx})")
        seen[who] = idx
        msg = sign_bytes(chain_id, commit["height"], commit["round"], tuple(commit["block_id"]), at)
        if not verify(pub, msg, sig):
            raise Invalid(f"wrong signature (#{idx})")
        tallied += power
        if tallied > needed:
            return
    raise CantBeTrusted(f"voting power {tallied} does not pass {needed}")


def verify_commit_light(chain_id: str, validators, commit, height: int, verify) -> None:
    if len(validators) != len(commit["signatures"]):
        raise Invalid(f"{len(commit['signatures'])} signatures for {len(validators)} validators")
    if commit["height"] != height:
        raise Invalid(f"the commit is for height {commit['height']}, not {height}")
    needed = sum(p for _, p in validators) * 2 // 3
    try:
        _walk(chain_id, commit, verify, needed, lambda i, _: (*validators[i], i))
    except CantBeTrusted as e:  # too little of the block's own set: not a bisection
        raise Invalid(str(e)) from None


def verify_commit_light_trusting(chain_id: str, validators, commit, verify,
                                 trust_level: Fraction = TRUST_LEVEL) -> None:
    by_address = {address(pub): (pub, power, j) for j, (pub, power) in enumerate(validators)}
    needed = sum(p for _, p in validators) * trust_level.numerator // trust_level.denominator
    _walk(chain_id, commit, verify, needed, lambda _, addr: by_address.get(addr))


def _later(a, b) -> bool:
    return tuple(a) > tuple(b)


def verify_new_header_and_vals(chain_id: str, untrusted, trusted, now, max_clock_drift_ns: int) -> None:
    h, c = untrusted["header"], untrusted["commit"]
    if h["chain_id"] != chain_id:
        raise Invalid(f"header belongs to chain {h['chain_id']!r}")
    if c["height"] != h["height"] or c["block_id"][0] != header_hash(h):
        raise Invalid("the commit does not commit the header")
    if h["height"] <= trusted["header"]["height"]:
        raise Invalid("the new header is not above the trusted one")
    if not _later(h["time"], trusted["header"]["time"]):
        raise Invalid("the new header's time is not after the trusted one's")
    limit = (now[0] + max_clock_drift_ns // 10**9, now[1] + max_clock_drift_ns % 10**9)
    if not _later(limit, h["time"]):
        raise Invalid("the new header is from the future")
    if h["validators_hash"] != valset_hash(untrusted["validators"]):
        raise Invalid("the header's validators hash is not the supplied set's")


def expired(trusted, trusting_period_ns: int, now) -> bool:
    s, n = trusted["header"]["time"]
    end = s * 10**9 + n + trusting_period_ns
    return not end > now[0] * 10**9 + now[1]


def verify_hop(chain_id: str, trusted, untrusted, trusting_period_ns: int, now, max_clock_drift_ns: int,
               verify, trust_level: Fraction = TRUST_LEVEL) -> None:
    """light/verifier.go Verify: adjacent or not."""
    if expired(trusted, trusting_period_ns, now):
        raise Invalid("the trusted header has expired")
    verify_new_header_and_vals(chain_id, untrusted, trusted, now, max_clock_drift_ns)
    height = untrusted["header"]["height"]
    if height == trusted["header"]["height"] + 1:
        if untrusted["header"]["validators_hash"] != trusted["header"]["next_validators_hash"]:
            raise Invalid("the old header's next validators are not the new header's")
    else:
        verify_commit_light_trusting(chain_id, trusted["validators"], untrusted["commit"], verify, trust_level)
    verify_commit_light(chain_id, untrusted["validators"], untrusted["commit"], height, verify)


def verify_skipping(chain_id: str, root, target, fetch, trusting_period_ns: int, now,
                    max_clock_drift_ns: int, verify=verify_zip215,
                    trust_level: Fraction = TRUST_LEVEL) -> Sync:
    """A cold sync from `root` (trusted) to `target`; `fetch(height)` is the
    primary's light block at that height."""
    cache = [target]
    depth = 0
    verified = root
    trace = [root["header"]["height"]]
    tried = []
    while True:
        upper = cache[depth]
        hop = (verified["header"]["height"], upper["header"]["height"])
        try:
            verify_hop(chain_id, verified, upper, trusting_period_ns, now, max_clock_drift_ns,
                       verify, trust_level)
        except CantBeTrusted:
            tried.append((*hop, "refused"))
            if depth == len(cache) - 1:
                pivot = hop[0] + (hop[1] - hop[0]) // 2
                cache.append(fetch(pivot))
            depth += 1
            continue
        except Invalid as e:
            tried.append((*hop, "invalid"))
            return Sync([root["header"]["height"]], hop, str(e), tried)
        tried.append((*hop, "ok"))
        trace.append(hop[1])
        if depth == 0:
            return Sync(trace, None, None, tried)
        verified = upper
        del cache[depth:]
        depth = 0
