"""Ed25519 key types with ZIP-215 verification (reference: crypto/ed25519/ed25519.go).

Key layout matches the reference: PrivKey = 64 bytes (seed || pubkey)
(ed25519.go:71-80), PubKey = 32 bytes, Signature = 64 bytes, address =
SHA256-20(pubkey) (ed25519.go:162-168).

Verification strategy (host tier): try the C-speed strict RFC 8032 verifier
from `cryptography` first — its acceptance set is a subset of ZIP-215's — and
only on rejection fall back to the pure-Python cofactored ZIP-215 check, so
honest signatures verify at library speed while adversarial edge encodings
still get exact ZIP-215 semantics (reference uses curve25519-voi with
VerifyOptionsZIP_215, ed25519.go:27-29). Bulk verification goes through the
TPU batch verifier instead (cometbft_tpu/ops/ed25519_kernel.py).
"""

from __future__ import annotations

import hashlib
import os
import threading
from itertools import islice

from cometbft_tpu import crypto
from cometbft_tpu.crypto import ed25519_pure, tmhash
from cometbft_tpu.crypto.compat import (
    Ed25519PrivateKey,
    Ed25519PublicKey,
    InvalidSignature,
)
from cometbft_tpu.libs import trace

KEY_TYPE = "ed25519"
PUB_KEY_SIZE = 32
PRIVATE_KEY_SIZE = 64
SIGNATURE_SIZE = 64
SEED_SIZE = 32

PRIV_KEY_NAME = "tendermint/PrivKeyEd25519"
PUB_KEY_NAME = "tendermint/PubKeyEd25519"

# Expanded-pubkey verification cache analog (reference ed25519.go:31,56
# cacheSize=4096): we cache parsed `cryptography` pubkey handles.
_CACHE_SIZE = 4096
_pubkey_cache: dict[bytes, Ed25519PublicKey] = {}


def _cached_pubkey(pub: bytes) -> Ed25519PublicKey | None:
    h = _pubkey_cache.get(pub)
    if h is None:
        try:
            h = Ed25519PublicKey.from_public_bytes(pub)
        except Exception:
            return None
        if len(_pubkey_cache) >= _CACHE_SIZE:
            _pubkey_cache.pop(next(iter(_pubkey_cache)))
        _pubkey_cache[pub] = h
    return h


class PubKey(crypto.PubKey):
    def __init__(self, data: bytes):
        self._bytes = bytes(data)

    def address(self) -> bytes:
        if len(self._bytes) != PUB_KEY_SIZE:
            raise ValueError("pubkey is incorrect size")
        return tmhash.sum_truncated(self._bytes)

    def bytes(self) -> bytes:
        return self._bytes

    def verify_signature(self, msg: bytes, sig: bytes) -> bool:
        if len(sig) != SIGNATURE_SIZE or len(self._bytes) != PUB_KEY_SIZE:
            return False
        # The verified-triple cache serves single verifies too: the
        # consensus loop batch-pre-verifies drained vote queues and fast
        # sync pre-verifies block windows, so the per-vote/per-commit
        # checks that follow land here already proven.
        key = (self._bytes, bytes(sig), bytes(msg))
        if key in _verified:
            return True
        handle = _cached_pubkey(self._bytes)
        if handle is not None:
            try:
                handle.verify(sig, msg)
                _verified_put(key)
                return True
            except InvalidSignature:
                pass
        # Fast path rejected: settle edge cases under exact ZIP-215 rules.
        ok = ed25519_pure.verify_zip215(self._bytes, msg, sig)
        if ok:
            _verified_put(key)
        return ok

    def type(self) -> str:
        return KEY_TYPE

    def __repr__(self) -> str:
        return f"PubKeyEd25519{{{self._bytes.hex().upper()}}}"


class PrivKey(crypto.PrivKey):
    def __init__(self, data: bytes):
        if len(data) != PRIVATE_KEY_SIZE:
            raise ValueError(f"ed25519 privkey must be {PRIVATE_KEY_SIZE} bytes")
        self._bytes = bytes(data)
        self._handle = Ed25519PrivateKey.from_private_bytes(self._bytes[:SEED_SIZE])

    def bytes(self) -> bytes:
        return self._bytes

    def sign(self, msg: bytes) -> bytes:
        return self._handle.sign(msg)

    def pub_key(self) -> PubKey:
        if not any(self._bytes[32:]):
            raise ValueError("expected ed25519 PrivKey to include concatenated pubkey bytes")
        return PubKey(self._bytes[32:])

    def type(self) -> str:
        return KEY_TYPE


def gen_priv_key() -> PrivKey:
    """GenPrivKey (ed25519.go:124-135)."""
    seed = crypto.c_random(SEED_SIZE)
    return _from_seed(seed)


def gen_priv_key_from_secret(secret: bytes) -> PrivKey:
    """GenPrivKeyFromSecret (ed25519.go:141-148): seed = SHA256(secret)."""
    return _from_seed(hashlib.sha256(secret).digest())


def _from_seed(seed: bytes) -> PrivKey:
    handle = Ed25519PrivateKey.from_private_bytes(seed)
    pub = handle.public_key().public_bytes_raw()
    return PrivKey(seed + pub)


# Verified-triple cache: the device analog of the reference's caching
# verifier seam (ed25519.go:31-56 caches EXPANDED KEYS; here whole verified
# (pub, sig, msg) triples are cached, because fast sync verifies every
# commit twice — VerifyCommitLight in blocksync's trySync, then the full
# VerifyCommit in ApplyBlock's validation — and the blocksync reactor
# pre-verifies whole windows of blocks in one device dispatch). Only VALID
# results are cached (deterministic; an attacker replaying a valid triple
# gets the same answer crypto would give), keyed by the (pub, sig, msg)
# TUPLE — bytes objects hash once and cache it, so tuple keys skip the
# per-lookup concatenation a bytes key would pay (~8 MB of copies per
# 10k-commit cached verify). Bounded (`CMTPU_VERIFY_CACHE_MAX`, mirroring
# the _CACHE_SIZE pubkey-cache pattern): oldest quarter evicted on
# overflow, re-verified triples refreshed to the young end, so a
# long-running node under heavy traffic holds its working set instead of
# growing without limit.
_VERIFIED_MAX = int(os.environ.get("CMTPU_VERIFY_CACHE_MAX", "") or 131072)
_verified: dict[tuple, None] = {}
_verified_lock = threading.Lock()
# What the cache did, always counted (under _verified_lock): per verify()
# call by arithmetic on lengths, never per triple. entries = hits + dups +
# dispatched; whole_miss + whole_hit + mixed = the verify() calls, by the
# case each batch was (BatchVerifier.verify).
_cache_counts = dict.fromkeys(
    ("entries", "hits", "dups", "dispatched", "inserted", "evicted",
     "whole_miss", "whole_hit", "mixed"), 0
)


def verified_cache_counters() -> dict:
    """Running counts of the verified-triple cache plus its present size."""
    with _verified_lock:
        return {**_cache_counts, "size": len(_verified)}


def _verified_put_many(keys: list[tuple]) -> int:
    """Insert verified triples under one lock acquisition (10k inserts after
    a commit verify would otherwise take the lock 10k times).  Writers race
    from multiple threads (blocksync pool routine, consensus, light client);
    eviction shares the lock so list(dict) never races an insert.  The
    oldest-quarter eviction repeats until the bound holds, so even a batch
    larger than a quarter of the cache cannot push it past _VERIFIED_MAX.
    Returns how many triples the sweeps evicted."""
    if not keys:
        return 0
    evicted = 0
    with _verified_lock:
        size = len(_verified)
        for key in keys:
            if key in _verified:
                # LRU refresh: a re-verified triple moves to the young end
                # (dict order is insertion order), so hot validators survive
                # eviction sweeps.
                del _verified[key]
            elif len(_verified) >= _VERIFIED_MAX:
                before = len(_verified)
                for k in list(_verified)[: max(1, _VERIFIED_MAX // 4)]:
                    _verified.pop(k, None)
                evicted += before - len(_verified)
            _verified[key] = None
        _cache_counts["inserted"] += len(_verified) - size + evicted
        _cache_counts["evicted"] += evicted
    return evicted


def _verified_put_missed(missed: dict[tuple, None], inserted_before: int) -> int:
    """_verified_put_many(list(missed)) for distinct triples that were all
    absent when `_cache_counts["inserted"]` read `inserted_before`: what the
    per-triple loop leaves behind, by one eviction and one dict.update.

    The loop sweeps the oldest quarter each time an insert finds the cache
    full, so over the whole batch it takes as many whole quarters off the
    old end of (the cache, then the batch) as the overflow needs: a batch
    larger than the cache loses its own first triples too. Where a writer
    has inserted since (`inserted` moved), a triple of the batch may be
    there already and has to move to the young end: that batch goes through
    the loop. Returns how many triples the sweeps evicted."""
    with _verified_lock:
        if _cache_counts["inserted"] == inserted_before:
            size, quarter = len(_verified), max(1, _VERIFIED_MAX // 4)
            over = size + len(missed) - _VERIFIED_MAX
            evicted = -(-over // quarter) * quarter if over > 0 else 0
            for key in list(islice(_verified, evicted)):
                del _verified[key]
            if evicted > size:
                missed = dict.fromkeys(islice(missed, evicted - size, None))
            _verified.update(missed)
            _cache_counts["inserted"] += len(_verified) - size + evicted
            _cache_counts["evicted"] += evicted
            return evicted
    return _verified_put_many(list(missed))


def _verified_put(key: tuple) -> None:
    _verified_put_many([key])


def mark_self_signed(pub: bytes, msg: bytes, sig: bytes) -> None:
    """Seed the verified cache with a signature THIS process just produced
    with its own private key. Signing is deterministic and the signer needs
    no cryptographic evidence about itself, so re-verifying an own vote on
    admission (state.go does) is pure overhead — material on the pure-Python
    scalar fallback, where one skipped verify saves milliseconds."""
    _verified_put((bytes(pub), bytes(sig), bytes(msg)))


def _as_bytes(column):
    """The column with add()'s bytes() made of every entry that needs it."""
    return column if set(map(type, column)) <= {bytes} else map(bytes, column)


class BatchVerifier(crypto.BatchVerifier):
    """Ed25519 batch verification (ed25519.go:196-228).

    Entries accumulate host-side, one at a time (`add`) or as whole columns
    (`add_many`, which takes a validator set's checked key bytes as they
    stand); `verify()` decides the batch against the verified-triple cache
    as whole columns where it can and dispatches what the cache cannot
    answer to the configured backend (TPU sidecar by default when a device
    is present, pure-CPU otherwise) — the same seam as the reference's
    cachingVerifier.AddWithOptions + BatchVerifier.Verify.
    """

    def __init__(self):
        self._pubs: list[bytes] = []
        self._msgs: list[bytes] = []
        self._sigs: list[bytes] = []

    def add(self, key: crypto.PubKey, message: bytes, signature: bytes) -> None:
        if not isinstance(key, PubKey):
            raise TypeError("pubkey is not Ed25519")
        pk = key.bytes()
        if len(pk) != PUB_KEY_SIZE:
            raise ValueError(
                f"pubkey size is incorrect; expected: {PUB_KEY_SIZE}, got {len(pk)}"
            )
        if len(signature) != SIGNATURE_SIZE:
            raise ValueError("invalid signature")
        self._pubs.append(pk)
        self._msgs.append(bytes(message))
        self._sigs.append(bytes(signature))

    @staticmethod
    def key_bytes(keys) -> tuple | None:
        """add()'s two checks on a key, each as one pass over the column."""
        if all(issubclass(t, PubKey) for t in set(map(type, keys))):
            pubs = tuple(key._bytes for key in keys)
            if set(map(len, pubs)) <= {PUB_KEY_SIZE}:
                return pubs
        return None

    def add_many(self, keys, messages, signatures, key_bytes=None) -> None:
        """add()'s three checks on every entry, each as one pass over its
        column; the two on the keys are not made again where `key_bytes`
        brings their outcome, and no key object is then looked at. A batch
        with an entry that fails one goes through add() entry by entry, so
        the first refused entry raises add()'s error."""
        pubs = self.key_bytes(keys) if key_bytes is None else key_bytes
        columns = (keys, messages, signatures) + (() if pubs is None else (pubs,))
        if len(set(map(len, columns))) != 1:
            raise ValueError("add_many: columns of unequal length")
        if pubs is not None and set(map(len, signatures)) <= {SIGNATURE_SIZE}:
            self._pubs.extend(pubs)
            self._msgs.extend(_as_bytes(messages))
            self._sigs.extend(_as_bytes(signatures))
            return
        super().add_many(keys, messages, signatures)

    def __len__(self) -> int:
        return len(self._pubs)

    def verify(self) -> tuple[bool, list[bool]]:
        from cometbft_tpu.sidecar.backend import get_backend
        from cometbft_tpu.sidecar.supervisor import ChainExhausted

        n = len(self._pubs)
        if not n:
            return False, []
        # Dispatch only the triples the cache cannot answer, deduplicating
        # repeats within the batch (the light client's trusting and light
        # checks of one hop share most of their triples; bisection descents
        # revisit pivot commits). Which of three cases a batch is, the
        # dict's own loops decide from the batch itself: all of it unseen
        # and distinct (`whole_miss`: the columns are dispatched as they
        # stand), all of it cached (`whole_hit`), or neither (`mixed`: the
        # per-triple walk, where lane_of records each unique uncached
        # triple's lane in the sub-batch and cached/duplicate entries
        # resolve from it after the dispatch). Membership is decided ONCE
        # here — concurrent writers may grow the cache mid-verify, and the
        # merge below must honor the filter's snapshot, not a fresher one.
        with trace.span("batch.verify", entries=n) as call:
            with trace.span("batch.cache_filter"):
                # Read before any membership is decided: while it stands,
                # no triple found missing here has been inserted since.
                inserted_before = _cache_counts["inserted"]
                keys = list(zip(self._pubs, self._sigs, self._msgs))
                missed = dict.fromkeys(keys)  # what to dispatch, in lane order
                lanes = range(n)  # per-entry lane; mixed: -1 = cache hit
                sub_pubs, sub_msgs, sub_sigs = self._pubs, self._msgs, self._sigs
                if len(missed) == n and _verified.keys().isdisjoint(missed):
                    path, hits = "whole_miss", 0
                elif all(map(_verified.__contains__, missed)):
                    path, hits, missed = "whole_hit", n, {}
                else:
                    path = "mixed"
                    lane_of: dict[tuple, int] = {}
                    lanes = []
                    sub_pubs, sub_msgs, sub_sigs = [], [], []
                    for key in keys:
                        if key in _verified:
                            lanes.append(-1)
                            continue
                        lane = lane_of.get(key)
                        if lane is None:
                            lane = len(sub_pubs)
                            lane_of[key] = lane
                            sub_pubs.append(key[0])
                            sub_msgs.append(key[2])
                            sub_sigs.append(key[1])
                        lanes.append(lane)
                    hits = lanes.count(-1)
                    missed = dict.fromkeys(lane_of)
                dispatched = len(missed)
                dups = n - hits - dispatched
                with _verified_lock:
                    _cache_counts["entries"] += n
                    _cache_counts["hits"] += hits
                    _cache_counts["dups"] += dups
                    _cache_counts["dispatched"] += dispatched
                    _cache_counts[path] += 1
            call.set(hits=hits, dups=dups, dispatched=dispatched, evicted=0, path=path)
            if not missed:
                return True, [True] * n
            with trace.span("batch.dispatch"):
                try:
                    _, sub_bits = get_backend().batch_verify(
                        sub_pubs, sub_msgs, sub_sigs
                    )
                except ChainExhausted:
                    # Every tier of the supervised chain failed (chaos runs
                    # can arrange this). Consensus liveness outranks batch
                    # speed: verify each signature through the scalar
                    # ZIP-215 path.
                    sub_bits = [
                        ed25519_pure.verify_zip215(p, m, s)
                        for p, m, s in zip(sub_pubs, sub_msgs, sub_sigs)
                    ]
            with trace.span("batch.cache_insert"):
                if all(sub_bits) and not dups:
                    # Every lane verified, so every entry did, and what the
                    # filter found missing goes in whole. (A repeat is put
                    # once an occurrence and ends where its last one stood:
                    # the walk's.)
                    bits = [True] * n
                    evicted = _verified_put_missed(missed, inserted_before)
                else:
                    bits = [True if lane < 0 else sub_bits[lane] for lane in lanes]
                    evicted = _verified_put_many(
                        [k for k, lane in zip(keys, lanes) if lane >= 0 and sub_bits[lane]]
                    )
            call.set(evicted=evicted)
            return all(bits), bits
