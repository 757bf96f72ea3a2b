"""Key-type dispatch for batch verification (reference: crypto/batch/batch.go).

The reference registry covers ed25519 and sr25519 (batch.go:11-32); this
rebuild adds bn254, whose BatchVerifier rides the supervised multi-pairing
chain. The registry is keyed by the key-type STRING so commit verification
can pick an engine for whatever key type a homogeneous signer run actually
uses — dispatching on the proposer's key alone mis-batches mixed validator
sets (see types/validation._batch_key_type).
"""

from __future__ import annotations

from cometbft_tpu import crypto
from cometbft_tpu.crypto import bn254, ed25519, sr25519

# key type -> (PubKey class, BatchVerifier factory)
_REGISTRY: dict[str, tuple] = {
    ed25519.KEY_TYPE: (ed25519.PubKey, ed25519.BatchVerifier),
    sr25519.KEY_TYPE: (sr25519.PubKey, sr25519.BatchVerifier),
    bn254.KEY_TYPE: (bn254.PubKey, bn254.BatchVerifier),
}


def _key_type_of(key) -> str | None:
    if isinstance(key, str):
        return key if key in _REGISTRY else None
    for kt, (cls, _) in _REGISTRY.items():
        if isinstance(key, cls):
            return kt
    return None


def create_batch_verifier(key) -> crypto.BatchVerifier:
    """batch.CreateBatchVerifier (batch.go:11-21), extended to accept either
    a PubKey instance or a key-type string."""
    kt = _key_type_of(key)
    if kt is None:
        raise ValueError(
            f"only {', '.join(sorted(_REGISTRY))} support batch verification"
        )
    return _REGISTRY[kt][1]()


def supports_batch_verifier(key) -> bool:
    """batch.SupportsBatchVerifier (batch.go:25-32); PubKey or key-type
    string."""
    return _key_type_of(key) is not None


def key_bytes(key_type: str | None, keys) -> tuple | None:
    """What the key type's batch engine makes of a column of keys
    (crypto.BatchVerifier.key_bytes); None where no engine batches the type."""
    if key_type not in _REGISTRY:
        return None
    return _REGISTRY[key_type][1].key_bytes(keys)
