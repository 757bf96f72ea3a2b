"""Crypto verification backends.

Selection (env `CMTPU_BACKEND`, default `auto`):
  - `cpu`:    host-only verification (native C MSM batch + OpenSSL fallback)
  - `tpu`:    in-process JAX batch kernels on the accelerator JAX finds;
              refuses to start on XLA:CPU unless JAX_PLATFORMS=cpu asks
              for exactly that (tests, CPU dry runs)
  - `hybrid`: device + host tiers concurrently — a throughput-balanced,
              bucket-aligned split of each large batch, small batches routed
              to whichever tier's cost model wins
  - `grpc`:   remote verification sidecar over gRPC (sidecar/service.py)
  - `auto`:   the SUPERVISED degradation chain (sidecar/supervisor.py):
              `grpc|tpu -> hybrid -> cpu` with per-call deadlines, bounded
              retry and per-tier circuit breakers. The device tier is
              `hybrid` whenever a JAX accelerator is visible (it degrades
              per-call to device-only until/unless the native library
              builds, so selection never blocks on gcc), else the chain is
              cpu-only.

This mirrors where the reference chooses batch vs single verification
(types/validation.go:14-16, 43-50): the caller keeps its fallback path, the
backend only changes who executes the batch.
"""

from __future__ import annotations

import collections
import os
import statistics
import sys
import threading
import time

from cometbft_tpu.libs import trace

_fallback_logged = False


def _log_fallback(reason: str) -> None:
    """One stderr line at selection time, first fallback only: the old bare
    `except Exception: pass` swallowed WHY a host silently ran cpu-only."""
    global _fallback_logged
    if not _fallback_logged:
        _fallback_logged = True
        print(f"backend: auto -> cpu ({reason})", file=sys.stderr, flush=True)


class VerifyBackend:
    """Interface for the device tier."""

    name = "abstract"

    def batch_verify(
        self, pubs: list[bytes], msgs: list[bytes], sigs: list[bytes]
    ) -> tuple[bool, list[bool]]:
        raise NotImplementedError

    def merkle_root(self, leaves: list[bytes]) -> bytes:
        raise NotImplementedError


# Below this, per-signature OpenSSL verification beats the MSM's fixed
# costs (two decompressions per signature, window bookkeeping).
_NATIVE_BATCH_MIN = 16


class CpuBackend(VerifyBackend):
    """Host tier: the native C batch verifier (random-linear-combination
    equation over one Pippenger MSM — the same construction as the
    reference's curve25519-voi batch path, crypto/ed25519/ed25519.go:196)
    when the extension is built, per-signature OpenSSL otherwise.  Both
    preserve the (ok, per-sig bitmap) contract with ZIP-215 semantics."""

    name = "cpu"

    def __init__(self):
        from cometbft_tpu import native

        # Start the (possibly multi-second) gcc build off-thread now so the
        # first commit verification never stalls behind it; until it lands,
        # batch_verify falls through to per-signature OpenSSL.
        native.ensure_built_async()

    def batch_verify(self, pubs, msgs, sigs):
        if len(pubs) >= _NATIVE_BATCH_MIN:
            from cometbft_tpu import native

            if native.ready() is not None:
                return native.batch_verify(pubs, msgs, sigs)
        from cometbft_tpu.crypto import ed25519

        results = [
            ed25519.PubKey(p).verify_signature(m, s)
            for p, m, s in zip(pubs, msgs, sigs)
        ]
        return all(results), results

    def merkle_root(self, leaves):
        from cometbft_tpu.crypto.merkle import hash_from_byte_slices

        return hash_from_byte_slices(leaves)


class TpuBackend(VerifyBackend):
    """In-process JAX batch kernels (cometbft_tpu/ops/*). Records at
    construction which device JAX handed it (`platform`, `device_kind`,
    `device_count`, also in counters()), so nothing downstream has to take
    the tier's name for the hardware. A machine where JAX found only its
    CPU backend is refused unless JAX_PLATFORMS=cpu asked for XLA:CPU."""

    name = "tpu"

    def __init__(self):
        # Import lazily so host-only deployments never pay for JAX.
        import jax

        from cometbft_tpu.ops import ed25519_kernel, merkle_kernel

        devs = jax.devices()
        self.platform = devs[0].platform
        self.device_kind = devs[0].device_kind
        self.device_count = len(devs)
        if self.platform == "cpu" and (jax.config.jax_platforms or "") != "cpu":
            raise RuntimeError(
                "JAX found no accelerator (platform cpu): the device tier "
                "will not run on XLA:CPU under a device name; set "
                "JAX_PLATFORMS=cpu to ask for that deliberately"
            )
        self._ed = ed25519_kernel
        self._merkle = merkle_kernel
        self.device_lanes = 0  # lanes this tier verified on the device

    def device_info(self) -> dict:
        return {
            "platform": self.platform,
            "device_kind": self.device_kind,
            "device_count": self.device_count,
        }

    def counters(self) -> dict:
        return {
            **self.device_info(),
            "device_lanes": self.device_lanes,
            **self._ed.resident_counters(),
            **self._ed.pack_counters(),
        }

    def batch_verify(self, pubs, msgs, sigs):
        self.device_lanes += len(pubs)
        return self._ed.batch_verify(pubs, msgs, sigs)

    def warmup(self, buckets) -> None:
        """Compile the verify program of each batch size's bucket and the
        fused Merkle programs ahead of the first real call."""
        self._ed.warmup(tuple(buckets))

    def merkle_root(self, leaves):
        # Power-of-two forests take the fused single-dispatch program (one
        # host round-trip instead of 2 + log-levels); merkle_root_fused
        # falls back to the level loop for ragged counts.
        return self._merkle.merkle_root_fused(leaves)

    def mesh_width(self) -> int:
        # Safe to probe: constructing this tier already ran jax.devices().
        return self._ed.mesh_width()


def _route_name(share: int, n: int) -> str:
    return "host" if share <= 0 else "device" if share >= n else "split"


class HybridBackend(VerifyBackend):
    """Device + host tiers working the same batch concurrently.

    The priors below (device ~100 sigs/ms plus a fixed per-dispatch cost,
    native host MSM ~70 sigs/ms with none) were taken on an earlier
    installation and only start the model: every call that reaches the
    device books the wall of the bucket it ran, from the start of the pack
    to the device-owner thread's own stamp of the program's return
    (ed25519_kernel.batch_verify_submit), whichever tier was late, and the
    host rate is an EMA over the host share's own interval. Neither tier
    dominates: the device wins big batches, the host wins small ones, and
    for the headline commit shape the OPTIMUM is both at once. batch_verify
    splits each large batch at the bucket that minimizes the predicted
    max(device wall, host time), dispatches the device share asynchronously,
    runs the host MSM share in the calling thread, and merges the bitmaps.
    Merkle roots go to
    the host SHA-NI tree (measured 10 ms vs 34 ms on device at 64k leaves,
    with no device round-trip).

    The reference has no analog — its batch verifier is single-tier
    (crypto/ed25519/ed25519.go:196-228); this is the TPU-first redesign's
    answer to owning both an accelerator and host SIMD.
    """

    name = "hybrid"

    def __init__(self):
        from cometbft_tpu import native

        self._native = native
        native.ensure_built_async()
        self._tpu = TpuBackend()
        self._cpu = CpuBackend()
        from cometbft_tpu.ops import ed25519_kernel as _ek

        # Chips one dispatch shards across: the planner prices the mesh as
        # ONE large device (per-chip rate x width, shared dispatch
        # overhead). TpuBackend() above already initialized the backend.
        self._n_dev = _ek.mesh_width()
        # sigs/ms PER CHIP; priors from the July 2026 stage splits (verify
        # 102 ms / 10,240 sigs device-side, 147 ms native), corrected by an
        # EMA after every call that reaches the tier.
        self._dev_rate = float(os.environ.get("CMTPU_DEV_RATE", "100"))
        self._host_rate = float(os.environ.get("CMTPU_HOST_RATE", "70"))
        # Fixed per-dispatch device cost (pack + dispatch round trip), ms.
        self._dev_overhead = float(os.environ.get("CMTPU_DEV_OVERHEAD_MS", "8"))
        self._min_split = int(os.environ.get("CMTPU_HYBRID_MIN", "2048"))
        self._rate_lock = threading.Lock()
        # Compiled-program keys (batch bucket, block bucket, lanes of the
        # resident tables or 0, mesh width) that have already run once in
        # this process: the first dispatch of a program can pay a
        # multi-second XLA compile, which must not be charged to the
        # steady-state rate model.
        self._warmed: set[tuple] = set()
        # Measured device wall per (batch bucket, mesh width, resident?):
        # pack to the device-owner thread's return, the median of the last
        # three calls that ran the bucket (_dev_recent). One bucket has two
        # programs — the ladder, and the table sum over a resident key
        # column (ops/ed25519_kernel) — whose walls differ threefold, so a
        # call is priced with the walls of the kind its look-up found.
        # The device's own time repeats
        # to a millisecond, and what disturbs a sample is one-sided and
        # rare — a host stall inside the pack, 116 ms once in 815 calls on
        # the chip's machine — but a bucket the planner has left is never
        # measured again, so one such sample must not move its wall. The
        # device cost is AFFINE — a fixed dispatch latency plus a per-lane
        # slope — so a single sigs/ms rate learned at one bucket misprices
        # every other; real walls win. Width in the key so a mesh-size
        # change (or a test flipping the virtual mesh) can't reuse stale
        # single-chip walls.
        self._dev_wall: dict[tuple[int, int, bool], float] = {}
        self._dev_recent: dict[tuple[int, int, bool], collections.deque] = {}
        # Share + stage walls of the most recent split call (observability;
        # chip_smoke.py and the benchmark's readers report them).
        self.last_share = 0
        self.last_timing: dict = {}
        # Where every lane this tier was sent actually ran, plus the last
        # few routing decisions with the rates that made them (counters()).
        self._device_lanes = 0
        self._host_lanes = 0
        self._routes: collections.deque = collections.deque(maxlen=64)
        # Running aggregates over every call that reached the device, a
        # program's first use left out (counters()): how often the planner
        # splits and moves the split, and how far its predicted wall was
        # from the measured one.
        self._split_calls = 0
        self._share_changes = 0
        self._last_split_share = 0
        self._plan_abs_err_ms = 0.0
        self._wall_ms = 0.0

    def _plan(self, n: int) -> int:
        """Device share (a bucket size, possibly 0=all-host or >=n=all-device)
        minimizing predicted max(device time, host time)."""
        return self._plan_cost(n)[0]

    def _plan_cost(self, n: int, resident_lanes: int = 0) -> tuple[int, float]:
        """_plan's share with the wall in ms the model predicts for it.
        `resident_lanes` > 0: the call's key column has its tables on the
        device, that many lanes wide, so the resident program's walls price
        it — at the tables' own bucket whatever the share, because that
        program has one shape a column (ops/ed25519_kernel `_tables_serve`)."""
        from cometbft_tpu.ops import ed25519_kernel as ek

        # Snapshot under the lock: _update_rates inserts first-observation
        # bucket keys from other callers' threads, and iterating the live
        # dict here would race that insert (RuntimeError: dictionary changed
        # size during iteration) escaping into consensus/blocksync callers.
        # Only walls observed at the CURRENT mesh width apply.
        n_dev = self._n_dev
        with self._rate_lock:
            walls = {
                b: w
                for (b, nd, res), w in self._dev_wall.items()
                if nd == n_dev and res == (resident_lanes > 0)
            }
        # Mesh pricing: lanes run data-parallel across the chips, so the
        # modeled throughput is per-chip rate x width over ONE shared
        # dispatch overhead — without this an 8-chip mesh gets starved
        # with single-chip-sized shares.
        mesh_rate = self._dev_rate * n_dev

        def dev_ms(b):  # padded lanes compute like real ones
            bucket = resident_lanes or ek.bucket_for(b)
            wall = walls.get(bucket)
            if wall is not None:
                return wall
            obs = sorted(walls.items())
            if len(obs) >= 2:
                # affine fit over the widest observed span
                (b1, w1), (b2, w2) = obs[0], obs[-1]
                slope = max((w2 - w1) / (b2 - b1), 0.0)
                return max(w1 + slope * (bucket - b1), 1.0)
            if len(obs) == 1:
                b1, w1 = obs[0]
                if bucket > b1:
                    return w1 + (bucket - b1) / mesh_rate
                # smaller buckets still pay the fixed dispatch floor
                return max(
                    w1 - (b1 - bucket) / mesh_rate, self._dev_overhead
                )
            return bucket / mesh_rate + self._dev_overhead

        def host_ms(k):
            return k / self._host_rate

        ladder = [*[b for b in ek.BUCKETS if b < n], n]
        best_b, best_cost = 0, host_ms(n)
        for b in ladder:
            cost = max(dev_ms(b), host_ms(n - b))
            # The resident program costs the device the same whatever the
            # share: of equal walls, the larger share (less for the host).
            if cost < best_cost or (resident_lanes and cost == best_cost):
                best_b, best_cost = b, cost
        return best_b, best_cost

    def _planned_call(self, pubs, msgs, sigs, between=None):
        """One call the planner routes, under its span: _plan_cost, then
        _routed_call with the share and the wall predicted for it."""
        from cometbft_tpu.ops import ed25519_kernel as ek

        n = len(pubs)
        with trace.span("hybrid.call", n=n) as call:
            with trace.span("hybrid.plan") as plan:
                # The one look-up of the call's key column, here where the
                # whole column is in view: the device share is a prefix.
                sighting = ek.sight_column(pubs)
                lanes = sighting.tables[1].shape[0] if sighting.tables is not None else 0
                share, predicted_ms = self._plan_cost(n, lanes)
                plan.set(share=share, predicted_ms=predicted_ms, resident=lanes > 0)
                if sighting.distinct:  # the column repeats a smaller set's keys
                    plan.set(distinct=sighting.distinct)
            call.set(share=share, route=_route_name(share, n))
            return self._routed_call(
                pubs, msgs, sigs, share, between, predicted_ms, sighting
            )

    def _note_route(self, n: int, share: int) -> None:
        """Count where n lanes went; calls big enough to be planned also
        leave the decision and the rates behind it in `routes`."""
        share = max(0, min(share, n))
        with self._rate_lock:
            self._device_lanes += share
            self._host_lanes += n - share
            if n >= self._min_split:
                self._routes.append(
                    {
                        "n": n,
                        "device": share,
                        "dev_rate": round(self._dev_rate, 2),
                        "host_rate": round(self._host_rate, 2),
                    }
                )

    def counters(self) -> dict:
        """What the tier is and what it did: the device JAX resolved, the
        native library's state, lanes per tier, the planner's last
        decisions. The sidecar prints this on shutdown and the supervisor
        embeds it per tier, so a run that never reached the device shows."""
        with self._rate_lock:
            out = {
                "device_lanes": self._device_lanes,
                "host_lanes": self._host_lanes,
                "routes": list(self._routes),
                "split_calls": self._split_calls,
                "share_changes": self._share_changes,
                "plan_abs_err_ms": round(self._plan_abs_err_ms, 2),
                "wall_ms": round(self._wall_ms, 2),
            }
        return {
            **self._tpu.counters(),  # the device, and its resident key columns
            "native": self._native.status(),
            **out,
            "last_share": self.last_share,
            "last_timing": dict(self.last_timing),
        }

    def warmup(self, buckets) -> None:
        """Compile what a call of each given size would dispatch right now:
        the planner's device share, and the whole batch (the all-device
        route taken while the native library is unavailable). Sizes below
        the split threshold never reach the device and warm nothing."""
        from cometbft_tpu.ops import ed25519_kernel as ek

        self._native.available()  # plan against the steady-state tiers
        warm: set[int] = set()
        for b in buckets:
            if b < self._min_split:
                continue
            warm.add(b)
            share = self._plan(b)
            if share > 0:
                warm.add(min(share, b))
        if warm:
            # Merkle roots run on the host tier here: no device program.
            ek.warmup(tuple(sorted(warm)), merkle_leaves=())

    def batch_verify(self, pubs, msgs, sigs):
        n = len(pubs)
        if n == 0:
            return False, []
        if n >= self._min_split and self._native.ready() is not None:
            return self._planned_call(pubs, msgs, sigs)[0]
        with trace.span("hybrid.call", n=n) as call:
            if n < self._min_split:
                # Small batches route host-side REGARDLESS of the native
                # build's state: below the split threshold even per-signature
                # OpenSSL (CpuBackend's own fallback) beats the device's fixed
                # dispatch cost, and tiny batches carry no useful rate signal
                # and must not move the model learned on commit-sized ones.
                call.set(share=0, route="host")
                self._note_route(n, 0)
                with trace.span("hybrid.host_msm", lanes=n):
                    return self._cpu.batch_verify(pubs, msgs, sigs)
            # Native tier still building (first seconds of a fresh host):
            # for commit-sized batches the device beats sequential OpenSSL.
            call.set(share=n, route="device")
            self._note_route(n, n)
            return self._tpu.batch_verify(pubs, msgs, sigs)

    def _routed_call(
        self, pubs, msgs, sigs, share, between=None, predicted_ms=None, sighting=None
    ):
        """Execute one planned verification: all-host (share<=0), all-device
        (share>=n), or the concurrent split — the ONE copy of the
        plan->submit->host MSM->overlap->collect->rate-update protocol.
        `between` (optional) runs under the device wait (verify_and_root's
        merkle); `predicted_ms` is the wall the planner expected, booked
        against the measured one; `sighting` is the planner's look-up of the
        whole key column, which the device share is a prefix of (None: the
        device tier looks the share up itself). Returns ((ok, bitmap),
        between_result)."""
        from cometbft_tpu.ops import ed25519_kernel as ek

        n = len(pubs)
        extra = None
        self._note_route(n, share)
        if share <= 0:
            self.last_share = 0
            t0 = time.perf_counter()
            with trace.span("hybrid.host_msm", lanes=n):
                res = self._cpu.batch_verify(pubs, msgs, sigs)
            host_ms = (time.perf_counter() - t0) * 1000
            with self._rate_lock:
                if host_ms > 1:
                    r = min(max(n / host_ms, 5.0), 5000.0)
                    self._host_rate += 0.3 * (r - self._host_rate)
            if between is not None:
                extra = between()
            return res, extra
        share = min(share, n)
        self.last_share = share
        t0 = time.perf_counter()
        collect = ek.batch_verify_submit(
            pubs[:share], msgs[:share], sigs[:share], sighting
        )
        t_disp = time.perf_counter()
        if share < n:
            with trace.span("hybrid.host_msm", lanes=n - share):
                ok_h, bits_h = self._native.batch_verify(
                    pubs[share:], msgs[share:], sigs[share:]
                )
        else:
            ok_h, bits_h = True, []
        t_host = time.perf_counter()
        if between is not None:
            extra = between()
        t_wait = time.perf_counter()
        ok_d, bits_d = collect()
        t_dev = time.perf_counter()
        self._update_rates(
            collect.program_key, share, n - share, t0, t_disp, t_host, t_wait, t_dev,
            collect.run_times, predicted_ms,
        )
        if share < n:
            return (ok_d and ok_h, bits_d + bits_h), extra
        return (ok_d, bits_d), extra

    def _update_rates(
        self, key, n_dev, n_host, t0, t_disp, t_host, t_wait, t_dev, t_run,
        predicted_ms=None,
    ):
        """Update the model from what this call measured. The host share ran
        exclusively in [t_disp, t_host]. The device's wall is t0 (the pack's
        start) to t_run[1], the device-owner thread's own stamp of the
        program's return (t_run is its (start, return) pair), so it is
        known on every call, whichever tier was late: never t_dev, which
        is the HOST's wall whenever the caller came to collect() after the
        device had finished. A program's first dispatch is left out: it can
        carry a multi-second XLA compile that would poison the steady-state
        model in one step."""
        alpha = 0.3
        host_ms = (t_host - t_disp) * 1000
        call_ms = (t_dev - t0) * 1000
        dev_ms = (t_run[1] - t0) * 1000
        resident = key[2] > 0
        warm_key = (*key, self._n_dev)
        first_use = warm_key not in self._warmed
        self._warmed.add(warm_key)
        self.last_timing = {
            "n_dev": n_dev,
            "mesh_devices": self._n_dev,
            "n_host": n_host,
            "pack_dispatch_ms": round((t_disp - t0) * 1000, 2),
            "host_msm_ms": round(host_ms, 2),
            "overlap_extra_ms": round((t_wait - t_host) * 1000, 2),
            "dev_wait_ms": round((t_dev - t_wait) * 1000, 2),
            "dev_run_ms": round((t_run[1] - t_run[0]) * 1000, 2),
            "dev_wall_ms": round(dev_ms, 2),
            "total_ms": round(call_ms, 2),
            "first_use": first_use,
            "resident": resident,
        }
        with self._rate_lock:
            if n_host > 0:
                self._split_calls += 1
                if self._last_split_share not in (0, n_dev):
                    self._share_changes += 1
                self._last_split_share = n_dev
            if not first_use and predicted_ms is not None:
                self._plan_abs_err_ms += abs(predicted_ms - call_ms)
                self._wall_ms += call_ms
            if host_ms > 1:
                r = min(max(n_host / host_ms, 5.0), 5000.0)
                self._host_rate += alpha * (r - self._host_rate)
            if not first_use:
                if dev_ms > self._dev_overhead:
                    # Learned rate stays PER CHIP (observed mesh throughput
                    # / width) so it transfers if the mesh width changes.
                    r = n_dev / (dev_ms - self._dev_overhead) / self._n_dev
                    r = min(max(r, 5.0), 5000.0)
                    self._dev_rate += alpha * (r - self._dev_rate)
                wall_key = (key[0], self._n_dev, resident)
                recent = self._dev_recent.setdefault(
                    wall_key, collections.deque(maxlen=3)
                )
                recent.append(dev_ms)
                self._dev_wall[wall_key] = statistics.median_low(recent)

    def merkle_root(self, leaves):
        if self._native.ready() is not None:
            return self._native.merkle_root(leaves)
        return self._tpu.merkle_root(leaves)

    def mesh_width(self) -> int:
        return self._n_dev

    def verify_and_root(self, pubs, msgs, sigs, leaves):
        """The commit-verification + block-tree fusion: device share in
        flight while the host runs its MSM share AND the SHA-NI merkle tree
        (_routed_call's `between` hook). Returns ((ok, bitmap), root)."""
        n = len(pubs)
        if n == 0:
            return (False, []), self.merkle_root(leaves)
        if n < self._min_split or self._native.ready() is None:
            ok, bits = self.batch_verify(pubs, msgs, sigs)
            return (ok, bits), self.merkle_root(leaves)
        return self._planned_call(
            pubs, msgs, sigs, between=lambda: self.merkle_root(leaves)
        )


class LockedBackend(VerifyBackend):
    """Serializes every device call of a wrapped backend behind one lock.

    The sidecar server wires this under its CoalescingScheduler: the
    scheduler's single dispatcher merges requests from many CONNECTIONS
    into one columnar dispatch, and this wrapper keeps the one-chip /
    one-XLA-stream discipline for the calls that bypass the scheduler —
    merkle roots, warmup — without the handler threads holding the lock
    across a whole verification."""

    def __init__(self, inner: VerifyBackend, lock: threading.Lock):
        self.inner = inner
        self.name = getattr(inner, "name", "device")
        self._device_lock = lock

    def batch_verify(self, pubs, msgs, sigs):
        with self._device_lock:
            return self.inner.batch_verify(pubs, msgs, sigs)

    def merkle_root(self, leaves):
        with self._device_lock:
            return self.inner.merkle_root(leaves)

    def mesh_width(self) -> int:
        mw = getattr(self.inner, "mesh_width", None)
        return int(mw()) if mw is not None else 1


_backend: VerifyBackend | None = None
_lock = threading.Lock()


def device_backend(choice: str = "auto") -> VerifyBackend:
    """cpu/tpu/hybrid/auto selection shared by the in-process path and the
    sidecar server. auto: prefer hybrid (device + host MSM) when an
    accelerator is visible and a native toolchain exists, device-only
    otherwise; fall back to CPU if the device tier can't initialize rather
    than failing the first call."""
    if choice == "cpu":
        return CpuBackend()
    if choice == "tpu":
        return TpuBackend()
    if choice == "hybrid":
        return HybridBackend()
    # auto: a JAX_PLATFORMS=cpu environment means "no accelerator" without
    # importing jax at all, so a CPU deployment's first commit verification
    # never pays for the import.
    want = os.environ.get("JAX_PLATFORMS", "")
    if want == "cpu":
        return CpuBackend()
    try:
        import jax
    except ImportError as e:
        _log_fallback(f"jax not importable: {e}")
        return CpuBackend()
    try:
        if want:
            jax.config.update("jax_platforms", want)
        if any(d.platform != "cpu" for d in jax.devices()):
            # Hybrid degrades gracefully to pure-device while (or if) the
            # native build is unavailable, so select it without blocking on
            # native.available()'s gcc run (first-call-stall discipline).
            return HybridBackend()
    except (RuntimeError, OSError, ValueError) as e:
        # Device-probe failures only (no PJRT backend, plugin init error,
        # bad platform name). Anything else — a real bug in a tier's
        # constructor — propagates instead of silently degrading.
        _log_fallback(f"device probe failed: {type(e).__name__}: {e}")
        return CpuBackend()
    return CpuBackend()


def _make_backend() -> VerifyBackend:
    choice = os.environ.get("CMTPU_BACKEND", "auto").lower()
    if choice == "grpc":
        from cometbft_tpu.sidecar.service import GrpcBackend

        return GrpcBackend(os.environ.get("CMTPU_SIDECAR_ADDR", "127.0.0.1:26670"))
    if choice not in ("auto", "cpu", "tpu", "hybrid"):
        raise ValueError(f"unknown CMTPU_BACKEND {choice!r}")
    if choice == "auto":
        # auto ships the supervised degradation chain (grpc|tpu -> hybrid
        # -> cpu with deadlines + circuit breakers, sidecar/supervisor.py):
        # a wedged tier costs one CMTPU_DEADLINE_MS, never liveness.
        # Explicit single-tier choices stay bare — forcing `tpu` or `grpc`
        # means "fail loudly", not "silently verify somewhere else".
        from cometbft_tpu.sidecar.supervisor import build_resilient

        chain = build_resilient()
        if os.environ.get("CMTPU_COALESCE", "1") != "0":
            # Outermost tier: coalesce concurrent callers' requests into
            # single dispatches (sidecar/scheduler.py). CMTPU_COALESCE=0
            # strips the layer for A/B and for callers that need the bare
            # supervised chain.
            from cometbft_tpu.sidecar.scheduler import CoalescingScheduler

            return CoalescingScheduler(chain)
        return chain
    return device_backend(choice)


def get_backend() -> VerifyBackend:
    global _backend
    if _backend is None:
        with _lock:
            if _backend is None:
                _backend = _make_backend()
    return _backend


def set_backend(backend: VerifyBackend | None) -> None:
    """Override the process-wide backend (tests, node bootstrap)."""
    global _backend
    with _lock:
        _backend = backend
