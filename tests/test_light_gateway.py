"""Light-client gateway tests (light/gateway.py + light/mmr.py).

Covers the MMR accumulator's RFC-6962 equivalence against crypto/merkle,
gateway-vs-local bit-identity of trust decisions, poisoned proof/plan
rejection with guaranteed fallback, plan-cache sharing + dispatch
coalescing under a concurrent swarm, and the LightStore cache knob."""

import threading

import pytest

from cometbft_tpu.crypto import ed25519 as _ed
from cometbft_tpu.crypto.merkle import (
    hash_from_byte_slices,
    proofs_from_byte_slices,
)
from cometbft_tpu.libs.db import MemDB
from cometbft_tpu.light import Client, LightStore, TrustOptions
from cometbft_tpu.light.gateway import GatewayError, LightGateway
from cometbft_tpu.light.mmr import MMR, verify_inclusion
from cometbft_tpu.sidecar import backend as _be
from cometbft_tpu.sidecar.backend import CpuBackend
from cometbft_tpu.sidecar.engine import VerificationEngine
from cometbft_tpu.types.cmttime import Time

from tests.test_light import (
    CHAIN_ID,
    HOUR_NS,
    NOW,
    T0,
    ChainMaker,
    CountingProvider,
    _client,
)

pytestmark = pytest.mark.lightgw


# -- MMR: RFC-6962 equivalence --------------------------------------------


def test_mmr_matches_rfc6962_tree():
    """Roots and audit paths must be bit-identical to crypto/merkle's
    RFC-6962 tree for every size — the MMR is the same tree, grown
    incrementally."""
    items = [f"leaf-{i}".encode() for i in range(40)]
    mmr = MMR()
    for n in range(1, len(items) + 1):
        mmr.append(items[n - 1])
        assert mmr.size == n
        assert mmr.root() == hash_from_byte_slices(items[:n])
        assert len(mmr.peaks()) == bin(n).count("1")
    _, proofs = proofs_from_byte_slices(items)
    root = mmr.root()
    for i, p in enumerate(proofs):
        got = mmr.prove(i)
        assert got.aunts == p.aunts, f"audit path diverges at leaf {i}"
        verify_inclusion(root, len(items), i, got.aunts, items[i])


def test_mmr_empty_and_single():
    mmr = MMR()
    assert mmr.root() == hash_from_byte_slices([])
    mmr.append(b"only")
    assert mmr.root() == hash_from_byte_slices([b"only"])
    verify_inclusion(mmr.root(), 1, 0, mmr.prove(0).aunts, b"only")


def test_mmr_rejects_corrupt_proof():
    items = [bytes([i]) for i in range(13)]
    mmr = MMR()
    for it in items:
        mmr.append(it)
    proof = mmr.prove(5)
    with pytest.raises(Exception):
        verify_inclusion(mmr.root(), 13, 5, proof.aunts, b"not-the-leaf")
    bad = list(proof.aunts)
    bad[0] = b"\x00" * 32
    with pytest.raises(Exception):
        verify_inclusion(mmr.root(), 13, 5, bad, items[5])


# -- gateway plan mode: bit-identical to local bisection -------------------


def _gateway(chain, **kw):
    return LightGateway(CHAIN_ID, chain.provider(), **kw)


def test_gateway_plan_sync_bit_identical():
    """Same hash, same stored trace heights, same decision as a plain
    local bisection — the gateway only accelerates."""
    chain = ChainMaker(n_vals=6, heights=40, rotate=2)
    now = Time(T0 + 40 * 10 + 600, 0)

    local = _client(chain)
    lb_local = local.verify_light_block_at_height(40, now)
    local_heights = sorted(local.store._heights())

    provider = CountingProvider(CHAIN_ID, chain.blocks)
    gw_client = _client(
        chain, provider=provider,
        gateway=_gateway(chain), gateway_proofs=False,
    )
    lb_gw = gw_client.verify_light_block_at_height(40, now)

    assert lb_gw.hash() == lb_local.hash()
    assert sorted(gw_client.store._heights()) == local_heights
    assert gw_client.gateway_stats["plan_syncs"] == 1
    assert gw_client.gateway_stats["fallbacks"] == 0
    # Pivots came from the plan, not the client's own primary: only the
    # latest-height probe and the target fetch hit the real provider.
    assert provider.fetches < len(local_heights)


def test_gateway_proof_sync_and_reject_fallback():
    """MMR cold sync lands on the local hash; a corrupted root is
    rejected client-side and the sync still completes correctly.  The
    chain keeps the anchor's trusting overlap (no rotation): the proof
    path never extends trust past what the skipping rule allows."""
    chain = ChainMaker(n_vals=4, heights=24)
    now = Time(T0 + 24 * 10 + 600, 0)
    local_hash = _client(chain).verify_light_block_at_height(24, now).hash()

    gw_client = _client(chain, gateway=_gateway(chain), gateway_proofs=True)
    lb = gw_client.verify_light_block_at_height(24, now)
    assert lb.hash() == local_hash
    assert gw_client.gateway_stats["proof_syncs"] == 1
    assert gw_client.gateway_stats["proof_rejects"] == 0
    assert gw_client.gateway_stats["proof_bytes"] > 0
    # O(log n) wire size: strictly below a sequential cold replay.
    full = sum(len(chain.blocks[h].encode()) for h in range(1, 25))
    assert gw_client.gateway_stats["proof_bytes"] < full

    class EvilGateway:
        """Serves structurally valid proofs under a forged root, and no
        plan at all — the client must reject and bisect locally."""

        def __init__(self, inner):
            self.inner = inner

        def sync_plan(self, *a, **kw):
            raise GatewayError("no plans today")

        def prove(self, height, anchor_height=0):
            out = self.inner.prove(height, anchor_height=anchor_height)
            out["root"] = b"\xde\xad" * 16
            return out

    evil = _client(
        chain, gateway=EvilGateway(_gateway(chain)), gateway_proofs=True
    )
    lb = evil.verify_light_block_at_height(24, now)
    assert lb.hash() == local_hash  # never a wrong accept
    assert evil.gateway_stats["proof_rejects"] == 1
    assert evil.gateway_stats["proof_syncs"] == 0
    assert evil.gateway_stats["fallbacks"] == 1  # plan refused too


def test_gateway_forged_history_never_accepted():
    """A malicious node serving BOTH primary RPC and the gateway (the
    deployed RemoteGateway topology) builds an MMR over [real anchor,
    forged headers] whose fabricated validator set signs itself +2/3.
    Both inclusion proofs verify by construction — acceptance must still
    die on the trusting-overlap check against the client's anchor set,
    with zero honest validator keys compromised."""
    from cometbft_tpu.light.provider import MockProvider

    real = ChainMaker(n_vals=4, heights=24)
    forged = ChainMaker(n_vals=4, heights=24)  # fresh random keys
    now = Time(T0 + 24 * 10 + 600, 0)

    mmr = MMR()
    mmr.append(real.blocks[1].hash())
    for h in range(2, 25):
        mmr.append(forged.blocks[h].hash())

    class ForgingGateway:
        def sync_plan(self, *a, **kw):
            raise GatewayError("no plan")

        def prove(self, height, anchor_height=0):
            target = mmr.prove(height - 1)
            anchor = mmr.prove(anchor_height - 1)
            return {
                "size": mmr.size,
                "root": mmr.root(),
                "light_block": forged.blocks[height],
                "target": {"index": target.index, "aunts": list(target.aunts)},
                "anchor": {"index": anchor.index, "aunts": list(anchor.aunts)},
                "bytes": 1,
            }

    # The primary serves the forged chain above the (real) trust anchor.
    provider = MockProvider(
        CHAIN_ID,
        {1: real.blocks[1], **{h: forged.blocks[h] for h in range(2, 25)}},
    )
    client = _client(
        real, provider=provider, gateway=ForgingGateway(), gateway_proofs=True
    )
    # Proof path rejected, plan refused, and the local-bisection fallback
    # cannot verify the forged chain either: the sync errors out rather
    # than ever accepting a header the anchor set did not vouch for.
    with pytest.raises(Exception):
        client.verify_light_block_at_height(24, now)
    assert client.gateway_stats["proof_syncs"] == 0
    assert client.gateway_stats["proof_rejects"] == 1


def test_gateway_proof_diluted_trust_falls_back_to_plan():
    """Full rotation between anchor and target: the MMR shortcut must NOT
    extend trust past the skipping rule — the proof path refuses and the
    plan walk (which bisects hop by hop) lands on the local hash."""
    chain = ChainMaker(n_vals=4, heights=24, rotate=1)
    now = Time(T0 + 24 * 10 + 600, 0)
    local_hash = _client(chain).verify_light_block_at_height(24, now).hash()

    c = _client(chain, gateway=_gateway(chain), gateway_proofs=True)
    lb = c.verify_light_block_at_height(24, now)
    assert lb.hash() == local_hash
    assert c.gateway_stats["proof_syncs"] == 0
    assert c.gateway_stats["proof_rejects"] == 1
    assert c.gateway_stats["plan_syncs"] == 1
    assert c.gateway_stats["fallbacks"] == 0


def test_gateway_pruned_source_refuses_proofs():
    """A pruned source (base > 1) cannot serve leaf index = height - 1:
    prove() must shed with a clear GatewayError up front (clients fall
    back to bisection), not fail height by height."""
    chain = ChainMaker(n_vals=4, heights=12)

    class PrunedProvider:
        def __init__(self, inner):
            self._inner = inner

        def base_height(self):
            return 5

        def chain_id(self):
            return self._inner.chain_id()

        def light_block(self, height):
            return self._inner.light_block(height)

        def report_evidence(self, ev):
            self._inner.report_evidence(ev)

    gw = LightGateway(CHAIN_ID, PrunedProvider(chain.provider()))
    with pytest.raises(GatewayError, match="pruned"):
        gw.prove(12, anchor_height=1)
    assert gw.stats()["mmr_size"] == 0
    # Plan serving does not need the pruned prefix.
    assert [b.height for b in gw.sync_plan(6, 12)] == [12]


def test_gateway_claim_returns_cached_plan():
    """Single-flight race: the computing session finished (and popped its
    inflight event) between a rider's cache miss and its claim — the
    claim must hand back the cached plan, never ownership of a
    recompute."""
    chain = ChainMaker(n_vals=4, heights=8)
    gw = _gateway(chain)
    gw.sync_plan(1, 8)  # populate the cache, clear inflight
    cached, mine, evt = gw._claim((1, 8))
    assert cached == (8,)
    assert mine is False and evt is None
    assert gw.stats()["plan_misses"] == 1


def test_gateway_poisoned_plan_block_caught_by_reverify():
    """A tampered pivot in the plan fails the client's own hop
    verification; the walk falls back to the real primary and the final
    decision is unchanged."""
    chain = ChainMaker(n_vals=6, heights=40, rotate=2)
    now = Time(T0 + 40 * 10 + 600, 0)
    local_hash = _client(chain).verify_light_block_at_height(40, now).hash()

    real = _gateway(chain)

    class PoisonGateway:
        def sync_plan(self, trusted_height, target_height, now=None):
            plan = real.sync_plan(trusted_height, target_height, now)
            for lb in plan:
                if lb.height not in (trusted_height, target_height):
                    # Swap in a different height's validator set: hashes
                    # stop matching, the client's verify of this hop fails.
                    from cometbft_tpu.types.light_block import LightBlock

                    donor = chain.blocks[lb.height - 1]
                    idx = plan.index(lb)
                    plan[idx] = LightBlock(
                        signed_header=lb.signed_header,
                        validator_set=donor.validator_set,
                    )
                    break
            return plan

        def prove(self, *a, **kw):
            raise GatewayError("mmr disabled")

    client = _client(chain, gateway=PoisonGateway(), gateway_proofs=False)
    lb = client.verify_light_block_at_height(40, now)
    assert lb.hash() == local_hash
    assert client.gateway_stats["fallbacks"] == 1
    assert client.gateway_stats["plan_syncs"] == 0


def test_gateway_expired_anchor_skips_proof_path():
    """An expired trust anchor must raise out of the proof path (the
    gateway cannot extend trust) — the client then fails exactly like a
    local client would."""
    chain = ChainMaker(n_vals=4, heights=12)
    far_future = Time(T0 + 10 * 365 * 24 * 3600, 0)
    client = _client(chain, gateway=_gateway(chain), gateway_proofs=True)
    with pytest.raises(Exception):
        client.verify_light_block_at_height(12, far_future)
    assert client.gateway_stats["proof_syncs"] == 0


# -- gateway internals: sessions, plan cache -------------------------------


def test_gateway_session_cap_sheds():
    chain = ChainMaker(n_vals=4, heights=8)
    gw = _gateway(chain, max_sessions=1)
    gw._enter()  # occupy the only slot
    try:
        with pytest.raises(GatewayError):
            gw.sync_plan(1, 8)
    finally:
        gw._exit()
    assert gw.stats()["sessions_rejected"] == 1
    # Slot released: the same call now succeeds.
    assert [b.height for b in gw.sync_plan(1, 8)] == [8]


def test_gateway_plan_cache_lru_and_stats():
    chain = ChainMaker(n_vals=6, heights=40, rotate=2)
    gw = _gateway(chain, plan_cache=2)
    gw.sync_plan(1, 40)
    assert gw.stats()["plan_misses"] == 1
    gw.sync_plan(1, 40)
    assert gw.stats()["plan_hits"] == 1
    gw.sync_plan(1, 30)   # second key
    gw.sync_plan(1, 40)   # refresh 1->40 (young end)
    gw.sync_plan(1, 20)   # third key evicts the oldest = (1, 30)
    assert (1, 30) not in gw._plans
    assert (1, 40) in gw._plans
    assert gw.stats()["plans_cached"] == 2
    with pytest.raises(GatewayError):
        gw.sync_plan(5, 5)  # degenerate range


def test_gateway_concurrent_swarm_shares_plan():
    """N clients, one target: the plan is computed once (misses==1, the
    rest hit the cache or ride the single-flight) and every member lands
    on the same hash."""
    chain = ChainMaker(n_vals=6, heights=40, rotate=2)
    now = Time(T0 + 40 * 10 + 600, 0)
    n_clients = 6

    gw = _gateway(chain)
    results: list = [None] * n_clients
    barrier = threading.Barrier(n_clients)

    def sync(i):
        try:
            barrier.wait(timeout=30)
            c = _client(chain, gateway=gw, gateway_proofs=False)
            lb = c.verify_light_block_at_height(40, now)
            results[i] = ("ok", lb.hash(), dict(c.gateway_stats))
        except Exception as exc:
            results[i] = ("error", repr(exc), None)

    threads = [
        threading.Thread(target=sync, args=(i,), daemon=True)
        for i in range(n_clients)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)

    assert all(r is not None and r[0] == "ok" for r in results), results
    assert len({r[1] for r in results}) == 1
    assert all(r[2]["plan_syncs"] == 1 for r in results)

    st = gw.stats()
    assert st["plan_misses"] == 1
    assert st["plan_hits"] + st["plan_waits"] == n_clients - 1
    assert st["plan_share_ratio"] == float(n_clients)


def test_gateway_concurrent_distinct_targets_coalesce():
    """N clients with DISTINCT targets on a shared VerificationEngine:
    each plan computation dispatches its own verification work, and the
    concurrent dispatches must merge into batched columnar calls (the
    coalesce ratio the whole design leans on)."""
    chain = ChainMaker(n_vals=6, heights=40, rotate=2)
    n_clients = 6
    targets = [40 - 2 * i for i in range(n_clients)]  # 40, 38, ... 30

    saved = _be._backend
    eng = VerificationEngine(CpuBackend(), hold_ms=60)
    _be.set_backend(eng)
    _ed._verified.clear()
    try:
        gw = _gateway(chain)
        results: list = [None] * n_clients
        barrier = threading.Barrier(n_clients)

        def sync(i):
            try:
                barrier.wait(timeout=30)
                now = Time(T0 + targets[i] * 10 + 600, 0)
                c = _client(chain, gateway=gw, gateway_proofs=False)
                lb = c.verify_light_block_at_height(targets[i], now)
                results[i] = ("ok", lb.hash(), dict(c.gateway_stats))
            except Exception as exc:
                results[i] = ("error", repr(exc), None)

        threads = [
            threading.Thread(target=sync, args=(i,), daemon=True)
            for i in range(n_clients)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)

        assert all(r is not None and r[0] == "ok" for r in results), results
        for i, r in enumerate(results):
            assert r[1] == chain.blocks[targets[i]].hash()
            assert r[2]["fallbacks"] == 0

        assert gw.stats()["plan_misses"] == n_clients  # all distinct keys
        c = eng.counters()
        assert c["batched_requests"] > 0, c
        assert c["requests"] / max(1, c["dispatches"]) > 1.0, c
    finally:
        _be.set_backend(saved)
        eng.close()
        _ed._verified.clear()


# -- LightStore cache knob -------------------------------------------------


def test_light_store_cache_knob(monkeypatch):
    chain = ChainMaker(n_vals=4, heights=10)

    monkeypatch.setenv("CMTPU_LIGHT_STORE_CACHE", "3")
    store = LightStore(MemDB())
    assert store._cache_blocks == 3
    for h in (1, 2, 3):
        store.save_light_block(chain.blocks[h])
    store.save_light_block(chain.blocks[1])  # refresh-on-reput: 1 young
    store.save_light_block(chain.blocks[4])  # evicts oldest = 2
    assert sorted(store._cache) == [1, 3, 4]
    # Evicted heights still come back from the DB (and re-enter the cache).
    assert store.light_block(2).height == 2
    assert 2 in store._cache

    monkeypatch.setenv("CMTPU_LIGHT_STORE_CACHE", "junk")
    assert LightStore(MemDB())._cache_blocks == 16  # default on bad input
    assert LightStore(MemDB(), cache_blocks=7)._cache_blocks == 7  # kwarg wins
    assert LightStore(MemDB(), cache_blocks=0)._cache_blocks == 1  # floor
