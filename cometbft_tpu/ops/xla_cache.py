"""The repo's one persistent XLA compile-cache setting.

Every program under `cometbft_tpu/ops` costs seconds to minutes to
compile, and every product entry point (`python -m cometbft_tpu.sidecar`,
`cmd start`, `cmd devnet`), the tests and `chip_smoke.py` reach them
through this package — so `ops/__init__.py` calls the function below once
at import and all of them share one cache.

Where `JAX_COMPILATION_CACHE_DIR` is set, that directory is JAX's own
default and nothing here overrides it (a machine that provides a cache
keeps it); otherwise the cache lives at `<checkout>/.jax_cache`. The
directory is part of the cache key, so it must not move between runs.
"""

from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def enable_persistent_cache() -> str:
    """Point this process's JAX at the shared on-disk compile cache, with
    the size/time floors zeroed so even small programs persist. Touches
    jax's config layer only, never the backend. Returns the directory."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir", os.path.join(_REPO_ROOT, ".jax_cache")
        )
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir
