"""Coalescing scheduler — compat shim over the continuous-batching engine.

Round 6 built `CoalescingScheduler` as the micro-batching front of the
`CMTPU_BACKEND=auto` chain: concurrent callers' requests merge into one
columnar dispatch with within-batch triple dedup, per-request bitmap
slicing, and per-request fallback retries when a merged dispatch fails.
Round 14 generalized that machinery into the continuous-batching
verification engine (`sidecar/engine.py`) — priority classes, starvation
escape, deadline-aware dispatch sizing — and this class became a thin
shim that embeds one.

The public surface is unchanged: `submit()` returns a `VerifyFuture`,
`batch_verify` is submit + wait, the knobs keep their names
(`CMTPU_COALESCE_WINDOW_MS` maps onto the engine's compat hold,
`CMTPU_COALESCE_MAX` pins the merge cap, `CMTPU_COALESCE=0` still strips
the layer in backend.py), `counters()` keeps its legacy keys, and
`refresh_cap()` delegates to the engine so a Ping-advertised wider remote
mesh still grows the auto merge cap (grow-only; pinned caps never move).
Everything a caller observed of the round-6 scheduler — dispatch shapes,
slicing, error isolation — is the engine behaving identically for
untagged (blocksync-class) traffic under a compat hold.

The sidecar SERVER embeds the same shim over its device lock
(sidecar/service.py, round 10): there the concurrent submitters are
CONNECTIONS — many node processes sharing one chip — and streamed
chunks, so cross-process requests merge into one columnar dispatch with
the identical slicing/fallback discipline.
"""

from __future__ import annotations

import os

from cometbft_tpu.sidecar.backend import VerifyBackend
from cometbft_tpu.sidecar.engine import (  # noqa: F401  (re-exports)
    VerificationEngine,
    VerifyFuture,
    _env_float,
    _mesh_width_for_cap,
)


class CoalescingScheduler(VerifyBackend):
    """Micro-batching front of the verification chain (module docstring)."""

    name = "coalesce"

    def __init__(
        self,
        inner: VerifyBackend,
        window_ms: float | None = None,
        max_sigs: int | None = None,
    ):
        if window_ms is None:
            window_ms = _env_float("CMTPU_COALESCE_WINDOW_MS", 2.0)
        if max_sigs is None and os.environ.get("CMTPU_COALESCE_MAX", ""):
            max_sigs = int(_env_float("CMTPU_COALESCE_MAX", 16384))
        # max_sigs None -> the engine derives its pod-width auto cap
        # (16384 x mesh width, grow-only via refresh_cap).
        self.engine = VerificationEngine(
            inner, hold_ms=window_ms, max_sigs=max_sigs
        )

    # -- engine views (no local copies: refresh_cap must never leave a
    # stale cap behind on the shim) ---------------------------------------

    @property
    def inner(self) -> VerifyBackend:
        return self.engine.inner

    @property
    def window_ms(self) -> float:
        return self.engine.hold_ms

    @window_ms.setter
    def window_ms(self, v: float) -> None:
        self.engine.hold_ms = v

    @property
    def max_sigs(self) -> int:
        return self.engine.max_sigs

    @max_sigs.setter
    def max_sigs(self, v: int) -> None:
        self.engine.max_sigs = v

    @property
    def counters_(self) -> dict:
        return self.engine.counters_

    # -- delegated surface -------------------------------------------------

    def submit(self, pubs, msgs, sigs) -> VerifyFuture:
        return self.engine.submit(pubs, msgs, sigs)

    def batch_verify(self, pubs, msgs, sigs):
        return self.engine.batch_verify(pubs, msgs, sigs)

    def aggregate_verify(self, pubs, msgs, agg_sig):
        return self.engine.aggregate_verify(pubs, msgs, agg_sig)

    def merkle_root(self, leaves):
        return self.engine.merkle_root(leaves)

    def mesh_width(self) -> int:
        return self.engine.mesh_width()

    def refresh_cap(self) -> int:
        return self.engine.refresh_cap()

    def ping(self):
        return self.engine.ping()

    def counters(self) -> dict:
        return self.engine.counters()

    def close(self) -> None:
        self.engine.close()
