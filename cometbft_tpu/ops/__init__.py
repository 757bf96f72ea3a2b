"""Device-tier kernels (JAX/XLA, TPU-first).

Everything the reference dispatches through `crypto.BatchVerifier`
(crypto/ed25519/ed25519.go:196-228) and `crypto/merkle`
(crypto/merkle/tree.go:11) runs here as vectorized, jit-compiled programs:

  - field25519:    GF(2^255-19) limb arithmetic, batch-last layout
  - edwards:       complete twisted-Edwards point ops + Shamir ladder
  - sha256_kernel: vectorized SHA-256 compression
  - ed25519_kernel: batched ZIP-215 signature verification
  - merkle_kernel: level-synchronous RFC-6962 tree hashing

Layouts put the batch dimension LAST ([limbs, N] / [words, N]) so the batch
fills TPU vector lanes while limb/word indices stay static Python ints.

Importing this package points JAX at the shared persistent compile cache
(ops/xla_cache.py) before any of its programs can compile.
"""

from cometbft_tpu.ops.xla_cache import enable_persistent_cache

enable_persistent_cache()
