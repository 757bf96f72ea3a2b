"""ValidatorSet: sorted validator array with proposer-priority rotation
(reference: types/validator_set.go).

Consensus-critical integer arithmetic ported semantically: int64 overflow
clipping (safeAddClip/safeSubClip), priority rescaling to a 2*totalPower
window, and the -1.125*totalPower penalty for newly bonded validators.
Ordering invariant: validators sorted by voting power descending, ties by
address ascending (ValidatorsByVotingPower, validator_set.go:755-764).
"""

from __future__ import annotations

import operator
import threading
from typing import NamedTuple

from cometbft_tpu.crypto import batch as crypto_batch
from cometbft_tpu.crypto import merkle
from cometbft_tpu.types.validator import Validator, canonical_validator, wire_head

INT64_MAX = 2**63 - 1
INT64_MIN = -(2**63)

MAX_TOTAL_VOTING_POWER = INT64_MAX // 8  # validator_set.go:25
PRIORITY_WINDOW_SIZE_FACTOR = 2  # validator_set.go:30


def safe_add_clip(a: int, b: int) -> int:
    v = a + b
    return min(max(v, INT64_MIN), INT64_MAX)


def safe_sub_clip(a: int, b: int) -> int:
    v = a - b
    return min(max(v, INT64_MIN), INT64_MAX)


def safe_mul(a: int, b: int) -> tuple[int, bool]:
    """(product, overflowed) with int64 semantics (libs/math/safemath.go)."""
    v = a * b
    if v > INT64_MAX or v < INT64_MIN:
        return 0, True
    return v, False


def _go_div(a: int, b: int) -> int:
    """Go integer division truncates toward zero (Python's // floors)."""
    q = abs(a) // abs(b)
    return -q if (a < 0) != (b < 0) else q


def _by_voting_power_key(v: Validator):
    return (-v.voting_power, v.address)


class SetColumns(NamedTuple):
    """A validator set as commit verification reads it: one column per
    attribute, in set order, and the one key type every validator shares
    (None for a mixed set or a validator without a key). `pub_bytes` is the
    keys' raw bytes as that type's batch engine takes them, its checks on a
    key made here, once (crypto.BatchVerifier.key_bytes): None where the
    engine takes key objects only or would refuse one of these keys, and
    the engine then says which when it is handed the objects."""

    pub_keys: tuple
    powers: tuple
    key_type: str | None
    pub_bytes: tuple | None


class _SetMemo:
    """What a set derives from its entries' addresses, keys and powers, in
    order, and proposer rotation leaves alone: the SetColumns commit
    verification reads, each validator's wire head (fields 1-3 of
    tendermint.types.Validator) and the Merkle root. A set and its copies
    share ONE holder (what it holds is immutable), so the first of them to
    need a piece fills it for all: a node's state copies its sets every
    height, then verifies against, hashes and stores the copies. A
    membership or power change gives the changed set a new one.

    `entries` is the (address, pub_key, voting_power) of every validator
    the heads and the root were built from; they serve a set only while its
    entries are still those (a validator changed in place is encoded and
    hashed afresh, never served stale)."""

    __slots__ = ("columns", "entries", "heads", "root")

    def __init__(self):
        self.columns: SetColumns | None = None
        self.entries: list | None = None
        self.heads: list | None = None
        self.root: bytes | None = None


# How often ValidatorSet.columns() computed the columns and how often it
# found them, always counted: a node whose `reused` stands still rebuilds
# them every height.
_columns_counts = {"built": 0, "reused": 0}
_columns_lock = threading.Lock()


def columns_counters() -> dict:
    with _columns_lock:
        return dict(_columns_counts)


# How often a set's wire heads and Merkle root were built and how often a
# copy found them in the holder it shares, always counted: on a chain whose
# set does not change, `heads_built` stands still after the first height.
# And how many validator records (the entries and the proposer)
# ValidatorSet.decode read in one walk (`walked`) and how many were in
# another form or held another key type and went to Validator.decode
# (`generic`).
_wire_counts = {
    "heads_built": 0, "heads_reused": 0, "roots_built": 0, "roots_reused": 0,
    "validator_records_walked": 0, "validator_records_generic": 0,
}


def wire_counters() -> dict:
    """The sets' counts above, and the commits' (types.block): records
    built once a commit, encodes that found the commit's bytes kept, and
    signature records decoded by the walk or the generic decoder."""
    from cometbft_tpu.types import block

    with _columns_lock:
        out = dict(_wire_counts)
    out.update(block.commit_wire_counters())
    return out


def _count(key: str) -> None:
    with _columns_lock:
        _wire_counts[key] += 1


_ENTRY = operator.attrgetter("address", "pub_key", "voting_power")


def _decode_validators(records) -> list:
    """Validator records as Validator.decode reads them: each in
    Validator.encode's form with an Ed25519 key read by
    canonical_validator, any other by Validator.decode."""
    from cometbft_tpu.wire import proto as wire

    vals, generic = wire.decode_records(records, canonical_validator, Validator.decode)
    with _columns_lock:
        _wire_counts["validator_records_walked"] += len(vals) - generic
        _wire_counts["validator_records_generic"] += generic
    return vals


class ValidatorSet:
    """types/validator_set.go:51-97."""

    def __init__(self, validators: list[Validator] | None = None):
        self.validators: list[Validator] = []
        self.proposer: Validator | None = None
        self._total_voting_power = 0
        # address -> index, built lazily: commit verification looks every
        # signature's validator up by address, which is O(n^2) per commit as
        # a linear scan at 4k+ validators.  Invalidated on membership change.
        self._addr_index: dict[bytes, int] | None = None
        # The columns, wire heads and Merkle root (_SetMemo): they cover
        # (address, key, power) per validator in order, so they share
        # _addr_index's invalidation points (membership/power changes);
        # proposer-priority rotation leaves them intact, and copies share
        # the holder.
        self._memo = _SetMemo()
        if validators:
            err = self._update_with_change_set(
                [v.copy() for v in validators], allow_deletes=False
            )
            if err is not None:
                raise ValueError(f"Cannot create validator set: {err}")
            self.increment_proposer_priority(1)

    # -- basic accessors ----------------------------------------------------

    def is_nil_or_empty(self) -> bool:
        return len(self.validators) == 0

    def size(self) -> int:
        return len(self.validators)

    def _index(self) -> dict[bytes, int]:
        if self._addr_index is None:
            self._addr_index = {
                v.address: i for i, v in enumerate(self.validators)
            }
        return self._addr_index

    def has_address(self, address: bytes) -> bool:
        return address in self._index()

    def get_by_address(self, address: bytes):
        i = self._index().get(address, -1)
        if i < 0:
            return -1, None
        return i, self.validators[i].copy()

    def get_by_index(self, index: int):
        if index < 0 or index >= len(self.validators):
            return None, None
        v = self.validators[index]
        return v.address, v.copy()

    def copy(self) -> "ValidatorSet":
        c = ValidatorSet()
        c.validators = [v.copy() for v in self.validators]
        c.proposer = self.proposer
        c._total_voting_power = self._total_voting_power
        c._memo = self._memo
        return c

    def columns(self) -> tuple[SetColumns, bool]:
        """The set's columns, and whether they were there already (False:
        this call computed them, one walk of the set)."""
        memo = self._memo
        cols = memo.columns
        reused = cols is not None
        if not reused:
            keys = tuple(v.pub_key for v in self.validators)
            types = {None if k is None else k.type() for k in keys}
            key_type = types.pop() if len(types) == 1 else None
            cols = memo.columns = SetColumns(
                keys,
                tuple(v.voting_power for v in self.validators),
                key_type,
                crypto_batch.key_bytes(key_type, keys),
            )
        with _columns_lock:
            _columns_counts["reused" if reused else "built"] += 1
        return cols, reused

    def total_voting_power(self) -> int:
        if self._total_voting_power == 0:
            self._update_total_voting_power()
        return self._total_voting_power

    def _update_total_voting_power(self) -> None:
        s = 0
        for v in self.validators:
            s = safe_add_clip(s, v.voting_power)
            if s > MAX_TOTAL_VOTING_POWER:
                raise OverflowError(
                    f"Total voting power should be guarded to not exceed "
                    f"{MAX_TOTAL_VOTING_POWER}; got: {s}"
                )
        self._total_voting_power = s

    def get_proposer(self) -> Validator | None:
        if not self.validators:
            return None
        if self.proposer is None:
            self.proposer = self._find_proposer()
        return self.proposer.copy()

    def _find_proposer(self) -> Validator:
        proposer = None
        for v in self.validators:
            if proposer is None or v.address != proposer.address:
                proposer = v.compare_proposer_priority(proposer) if proposer else v
        return proposer

    def _shared(self) -> _SetMemo | None:
        """The holder, when the heads and root it has (or will have) are
        this set's: it was built from these entries, or from none yet and
        now adopts them. None when an entry changed in place since."""
        memo = self._memo
        entries = list(map(_ENTRY, self.validators))
        if memo.entries is None:
            memo.entries = entries
        elif memo.entries != entries:
            return None
        return memo

    def hash(self) -> bytes:
        """Merkle root over SimpleValidator leaves (validator_set.go:347)."""
        memo = self._shared()
        if memo is not None and memo.root is not None:
            _count("roots_reused")
            return memo.root
        root = merkle.hash_from_byte_slices([v.bytes() for v in self.validators])
        if memo is not None:
            memo.root = root
        _count("roots_built")
        return root

    def validate_basic(self) -> None:
        if self.is_nil_or_empty():
            raise ValueError("validator set is nil or empty")
        for idx, v in enumerate(self.validators):
            try:
                v.validate_basic()
            except ValueError as e:
                raise ValueError(f"invalid validator #{idx}: {e}") from e
        if self.proposer is None:
            raise ValueError("proposer failed validate basic, error: nil validator")
        self.proposer.validate_basic()

    # -- proposer priority rotation (validator_set.go:107-247) ---------------

    def copy_increment_proposer_priority(self, times: int) -> "ValidatorSet":
        c = self.copy()
        c.increment_proposer_priority(times)
        return c

    def increment_proposer_priority(self, times: int) -> None:
        if self.is_nil_or_empty():
            raise ValueError("empty validator set")
        if times <= 0:
            raise ValueError(
                "Cannot call IncrementProposerPriority with non-positive times"
            )
        diff_max = PRIORITY_WINDOW_SIZE_FACTOR * self.total_voting_power()
        self.rescale_priorities(diff_max)
        self._shift_by_avg_proposer_priority()
        proposer = None
        for _ in range(times):
            proposer = self._increment_proposer_priority()
        self.proposer = proposer

    def _increment_proposer_priority(self) -> Validator:
        for v in self.validators:
            v.proposer_priority = safe_add_clip(v.proposer_priority, v.voting_power)
        mostest = None
        for v in self.validators:
            mostest = v.compare_proposer_priority(mostest) if mostest else v
        mostest.proposer_priority = safe_sub_clip(
            mostest.proposer_priority, self.total_voting_power()
        )
        return mostest

    def rescale_priorities(self, diff_max: int) -> None:
        if self.is_nil_or_empty():
            raise ValueError("empty validator set")
        if diff_max <= 0:
            return
        diff = self._compute_max_min_priority_diff()
        ratio = (diff + diff_max - 1) // diff_max
        if diff > diff_max:
            for v in self.validators:
                v.proposer_priority = _go_div(v.proposer_priority, ratio)

    def _compute_max_min_priority_diff(self) -> int:
        prios = [v.proposer_priority for v in self.validators]
        diff = max(prios) - min(prios)
        return -diff if diff < 0 else diff

    def _compute_avg_proposer_priority(self) -> int:
        n = len(self.validators)
        s = sum(v.proposer_priority for v in self.validators)
        # Go big.Int Div is Euclidean-style floor for positive divisor.
        return s // n

    def _shift_by_avg_proposer_priority(self) -> None:
        avg = self._compute_avg_proposer_priority()
        for v in self.validators:
            v.proposer_priority = safe_sub_clip(v.proposer_priority, avg)

    # -- update machinery (validator_set.go:366-660) -------------------------

    def update_with_change_set(self, changes: list[Validator]) -> None:
        err = self._update_with_change_set([v.copy() for v in changes], True)
        if err is not None:
            raise ValueError(err)

    def _update_with_change_set(self, changes, allow_deletes: bool):
        if not changes:
            return None
        # processChanges: sort by address, detect duplicates, split.
        changes = sorted(changes, key=lambda v: v.address)
        updates, deletes = [], []
        prev_addr = None
        for v in changes:
            if v.address == prev_addr:
                return f"duplicate entry {v} in {changes}"
            if v.voting_power < 0:
                return f"voting power can't be negative: {v.voting_power}"
            if v.voting_power > MAX_TOTAL_VOTING_POWER:
                return (
                    f"to prevent clipping/overflow, voting power can't be higher "
                    f"than {MAX_TOTAL_VOTING_POWER}, got {v.voting_power}"
                )
            if v.voting_power == 0:
                deletes.append(v)
            else:
                updates.append(v)
            prev_addr = v.address
        if not allow_deletes and deletes:
            return f"cannot process validators with voting power 0: {deletes}"
        num_new = sum(1 for u in updates if not self.has_address(u.address))
        if num_new == 0 and len(self.validators) == len(deletes):
            return "applying the validator changes would result in empty set"
        # verifyRemovals
        removed_power = 0
        for d in deletes:
            _, val = self.get_by_address(d.address)
            if val is None:
                return f"failed to find validator {d.address.hex().upper()} to remove"
            removed_power += val.voting_power
        if len(deletes) > len(self.validators):
            raise ValueError("more deletes than validators")
        # verifyUpdates

        def delta(update: Validator) -> int:
            _, val = self.get_by_address(update.address)
            if val is not None:
                return update.voting_power - val.voting_power
            return update.voting_power

        tvp_after_removals = self.total_voting_power() - removed_power
        for upd in sorted(updates, key=delta):
            tvp_after_removals += delta(upd)
            if tvp_after_removals > MAX_TOTAL_VOTING_POWER:
                return (
                    f"total voting power of resulting valset exceeds max "
                    f"{MAX_TOTAL_VOTING_POWER}"
                )
        tvp_after_updates_before_removals = tvp_after_removals + removed_power
        # computeNewPriorities: new validators start at -1.125*totalPower.
        for upd in updates:
            _, val = self.get_by_address(upd.address)
            if val is None:
                upd.proposer_priority = -(
                    tvp_after_updates_before_removals
                    + (tvp_after_updates_before_removals >> 3)
                )
            else:
                upd.proposer_priority = val.proposer_priority
        self._apply_updates(updates)
        self._apply_removals(deletes)
        self._update_total_voting_power()
        self.rescale_priorities(PRIORITY_WINDOW_SIZE_FACTOR * self.total_voting_power())
        self._shift_by_avg_proposer_priority()
        self.validators.sort(key=_by_voting_power_key)
        self._membership_changed()
        return None

    def _membership_changed(self) -> None:
        self._addr_index = None
        self._memo = _SetMemo()

    def _apply_updates(self, updates: list[Validator]) -> None:
        existing = sorted(self.validators, key=lambda v: v.address)
        merged = []
        i = j = 0
        while i < len(existing) and j < len(updates):
            if existing[i].address < updates[j].address:
                merged.append(existing[i])
                i += 1
            else:
                merged.append(updates[j])
                if existing[i].address == updates[j].address:
                    i += 1
                j += 1
        merged.extend(existing[i:])
        merged.extend(updates[j:])
        self.validators = merged
        self._membership_changed()

    def _apply_removals(self, deletes: list[Validator]) -> None:
        if not deletes:
            return
        dset = {d.address for d in deletes}
        self.validators = [v for v in self.validators if v.address not in dset]
        self._membership_changed()

    # -- verification wrappers (validator_set.go:662-680) --------------------

    def verify_commit(self, chain_id: str, block_id, height: int, commit) -> None:
        from cometbft_tpu.types import validation

        validation.verify_commit(chain_id, self, block_id, height, commit)

    def verify_commit_light(self, chain_id: str, block_id, height: int, commit) -> None:
        from cometbft_tpu.types import validation

        validation.verify_commit_light(chain_id, self, block_id, height, commit)

    def verify_commit_light_trusting(self, chain_id: str, commit, trust_level) -> None:
        from cometbft_tpu.types import validation

        validation.verify_commit_light_trusting(chain_id, self, commit, trust_level)

    # -- wire ----------------------------------------------------------------

    def _heads(self) -> list:
        """Each validator's wire head, from the shared holder once a
        membership (built on the first encode, then reused by every copy)."""
        memo = self._shared()
        if memo is None:
            _count("heads_built")
            return [wire_head(*e) for e in map(_ENTRY, self.validators)]
        heads = memo.heads
        if heads is None:
            heads = memo.heads = [wire_head(*e) for e in memo.entries]
            _count("heads_built")
        else:
            _count("heads_reused")
        return heads

    def encode(self) -> bytes:
        """tendermint.types.ValidatorSet: one record a validator (its head,
        then field 4 where the priority is not 0), the proposer, the total
        power; joined once."""
        from cometbft_tpu.wire import proto as wire

        uvarint, varint = wire.encode_uvarint, wire.encode_varint_signed
        parts = []
        add = parts.extend
        for head, v in zip(self._heads(), self.validators):
            prio = v.proposer_priority
            body = head + b"\x20" + varint(prio) if prio else head
            add((b"\x0a", uvarint(len(body)), body))
        if self.proposer is not None:
            parts.append(wire.field_message(2, self.proposer.encode(), emit_empty=True))
        parts.append(
            wire.field_varint(3, self.total_voting_power() if self.validators else 0)
        )
        return b"".join(parts)

    @classmethod
    def decode(cls, data: bytes) -> "ValidatorSet":
        from cometbft_tpu.wire import proto as wire

        f = wire.decode_fields(data)
        vs = cls()
        vs.validators = _decode_validators(wire.get_repeated_bytes(f, 1))
        if 2 in f:
            (vs.proposer,) = _decode_validators([wire.get_bytes(f, 2)])
        vs._total_voting_power = 0
        return vs
