"""Node: dependency assembly + lifecycle (reference: node/node.go:137 NewNode,
:371 OnStart, node/setup.go:64 DefaultNewNode).

Assembly order mirrors the reference: DBs → state → ABCI conns → handshake
replay → event bus + indexers → mempool/evidence/executor → consensus →
P2P switch + reactors → RPC.

Boot phasing (node/node.go:423-433): when statesync is enabled and the
store is empty, OnStart runs the light-client-verified snapshot restore
first, hands the bootstrapped state to blocksync (SwitchToBlockSync), and
blocksync's caught-up hook starts consensus. Without statesync, blocksync
runs from the store head unless this node is the only validator
(onlyValidatorIsUs, node/node.go:174), in which case consensus starts
immediately.
"""

from __future__ import annotations

import os
import sys
import threading
import time

from cometbft_tpu.abci.client import LocalClientCreator
from cometbft_tpu.abci.example.kvstore import KVStoreApplication
from cometbft_tpu.config import Config
from cometbft_tpu.consensus.state import ConsensusState
from cometbft_tpu.consensus.wal import WAL
from cometbft_tpu.evidence import EvidencePool
from cometbft_tpu.libs.db import new_db
from cometbft_tpu.mempool import CListMempool
from cometbft_tpu.privval import FilePV
from cometbft_tpu.proxy import AppConns
from cometbft_tpu.rpc.core import Environment, routes
from cometbft_tpu.rpc.jsonrpc.server import JSONRPCServer
from cometbft_tpu.state import BlockExecutor, StateStore, make_genesis_state
from cometbft_tpu.state.txindex import (
    IndexerService,
    KVBlockIndexer,
    KVTxIndexer,
    NullTxIndexer,
)
from cometbft_tpu.store import BlockStore
from cometbft_tpu.types.events import EventBus
from cometbft_tpu.types.genesis import GenesisDoc


class Node:
    """node/node.go Node."""

    def __init__(
        self,
        config: Config,
        genesis_doc: GenesisDoc,
        priv_validator,
        client_creator,
        logger=None,
        custom_reactors: dict | None = None,
        transport_factory=None,
        clock=None,
    ):
        from cometbft_tpu.simnet.clock import MonotonicClock

        self.config = config
        self.genesis_doc = genesis_doc
        self.priv_validator = priv_validator
        self.logger = logger
        # Injected time source, threaded into consensus + p2p + blocksync so
        # a simulated deployment (simnet) controls every timer from one
        # virtual clock. Default: wall clock, behavior unchanged.
        self.clock = clock or MonotonicClock()
        # fn(node_info, node_key, fuzz_config) -> transport duck-typing
        # MultiplexTransport (listen/dial/close). None = real TCP transport;
        # simnet injects SimTransport here.
        self._transport_factory = transport_factory
        # node/node.go CustomReactors option: name -> Reactor, added to the
        # switch after the built-ins (same-name entries replace built-ins in
        # the reference; here extra names only — replacement would need the
        # channel table rebuilt).
        self._custom_reactors = custom_reactors or {}

        # Storage (node/node.go:147 initDBs).
        db_dir = config.base.db_path()
        self.block_store = BlockStore(new_db("blockstore", config.base.db_backend, db_dir))
        self.state_store = StateStore(
            new_db("state", config.base.db_backend, db_dir),
            discard_abci_responses=config.storage.discard_abci_responses,
        )

        # State from DB or genesis (node/node.go:156).
        state = self.state_store.load()
        if state is None:
            state = make_genesis_state(genesis_doc)
            self.state_store.save(state)

        # ABCI connections (node/node.go:164).
        self.proxy_app = AppConns(client_creator)
        self.proxy_app.start()

        # Event bus + indexers are created AND started before the handshake
        # (node/node.go:173-182 precede :210 doHandshake) so a block applied
        # during crash-recovery replay is published and indexed.
        self.event_bus = EventBus()
        if config.tx_index.indexer == "kv":
            self.tx_indexer = KVTxIndexer(new_db("tx_index", config.base.db_backend, db_dir))
            self.block_indexer = KVBlockIndexer(
                new_db("block_index", config.base.db_backend, db_dir)
            )
        elif config.tx_index.indexer == "psql":
            # SQL event sink (state/indexer/sink/psql): write-only relational
            # indexing for external SQL consumers; /tx_search et al refuse.
            from cometbft_tpu.state.sink_sql import SqlEventSink

            conn = config.tx_index.psql_conn or os.path.join(
                db_dir, "event_sink.sqlite"
            )
            self.event_sink = SqlEventSink(conn, genesis_doc.chain_id)
            self.tx_indexer = self.event_sink.tx_indexer()
            self.block_indexer = self.event_sink.block_indexer()
        else:
            self.tx_indexer = NullTxIndexer()
            self.block_indexer = NullTxIndexer()
        self.indexer_service = IndexerService(
            self.tx_indexer, self.block_indexer, self.event_bus
        )
        self.event_bus.start()
        self.indexer_service.start()

        # Handshake: full replay.go height-case analysis so consensus state,
        # block store, and app advance together (node/node.go:210).
        from cometbft_tpu.consensus.replay import Handshaker

        handshaker = Handshaker(
            self.state_store,
            state,
            self.block_store,
            genesis_doc,
            event_bus=self.event_bus,
            logger=logger,
        )
        state = handshaker.handshake(self.proxy_app)

        # Mempool + evidence + executor (node/node.go:230-248).
        self.mempool = CListMempool(config.mempool, self.proxy_app.mempool)
        # QoS ingress: admission pipeline (envelope preverify, lanes,
        # rate limits, shedding) fronting the clist mempool. RPC and the
        # gossip reactor submit through it; consensus/executor keep the
        # raw mempool (reap/update are not admission).
        self.ingress = None
        if getattr(config.mempool, "ingress_enable", True):
            from cometbft_tpu.mempool.ingress import IngressPipeline

            self.ingress = IngressPipeline(config.mempool, self.mempool)
        self.evidence_pool = EvidencePool(
            new_db("evidence", config.base.db_backend, db_dir),
            self.state_store,
            self.block_store,
            logger,
        )
        self.block_executor = BlockExecutor(
            self.state_store,
            self.proxy_app.consensus,
            self.mempool,
            self.evidence_pool,
            self.block_store,
            self.event_bus,
            logger,
        )

        # Metrics (node/node.go:385-387 + each subsystem's PrometheusMetrics).
        self.metrics_registry = None
        self.metrics_server = None
        cs_metrics = None
        if config.instrumentation.prometheus:
            from cometbft_tpu.consensus.metrics import Metrics as CsMetrics
            from cometbft_tpu.libs.metrics import MetricsServer, Registry

            reg = Registry(namespace=config.instrumentation.namespace)
            self.metrics_registry = reg
            cs_metrics = CsMetrics(reg)
            reg.gauge_func("mempool", "size", "Txs in the mempool.",
                           lambda: self.mempool.size())
            if self.ingress is not None:
                self.ingress.register_metrics(reg)
            reg.gauge_func("p2p", "peers", "Connected peers.",
                           lambda: self.switch.num_peers() if self.switch else 0)
            reg.gauge_func("blockstore", "height", "Block store tip height.",
                           lambda: self.block_store.height())
            reg.gauge_func("blockstore", "base", "Block store base height.",
                           lambda: self.block_store.base())
            self._register_backend_metrics(reg)
            self._register_engine_metrics(reg)
            self._register_host_metrics(reg)
            self._register_recvq_metrics(reg)
            self._register_mesh_metrics(reg)
            self._register_fanout_metrics(reg)
            self._register_hotpath_metrics(reg)
            self._register_lightgw_metrics(reg)
            self._register_evidence_metrics(reg)
            addr = config.instrumentation.prometheus_listen_addr
            host, _, port = addr.rpartition(":")
            self.metrics_server = MetricsServer(
                reg, host.replace("tcp://", "") or "127.0.0.1", int(port)
            )

        # Consensus (node/node.go:256).
        wal = WAL(config.consensus.wal_path()) if config.base.root_dir else None
        self.consensus_state = ConsensusState(
            config.consensus,
            state,
            self.block_executor,
            self.block_store,
            self.mempool,
            self.evidence_pool,
            self.event_bus,
            wal=wal,
            metrics=cs_metrics,
            clock=self.clock,
        )
        if priv_validator is not None:
            self.consensus_state.set_priv_validator(priv_validator)

        # Boot mode (node/node.go:174 onlyValidatorIsUs + :423 stateSync
        # gating: statesync only ever runs into an empty store).
        self._state_sync = bool(config.statesync.enable) and state.last_block_height == 0
        self._block_sync = (
            config.base.block_sync
            and config.blocksync.enable
            and not _only_validator_is_us(state, priv_validator)
        )

        # P2P switch + reactors (node/node.go:285-345), assembled whenever a
        # p2p listen address is configured; in-process meshes (devnet) leave
        # it empty and wire consensus broadcast directly.
        self.switch = None
        self.p2p_laddr = ""
        if config.p2p.laddr:
            from cometbft_tpu.blocksync.reactor import BlocksyncReactor
            from cometbft_tpu.consensus.reactor import ConsensusReactor
            from cometbft_tpu.evidence.reactor import EvidenceReactor
            from cometbft_tpu.mempool.reactor import MempoolReactor
            from cometbft_tpu.p2p.key import NodeKey
            from cometbft_tpu.p2p.node_info import NodeInfo
            from cometbft_tpu.p2p.switch import Switch
            from cometbft_tpu.p2p.transport import MultiplexTransport
            from cometbft_tpu.statesync import StatesyncReactor

            if config.base.root_dir:
                self.node_key = NodeKey.load_or_gen(config.base.node_key_path())
            else:
                self.node_key = NodeKey()
            self.node_info = NodeInfo(
                node_id=self.node_key.id,
                network=genesis_doc.chain_id,
                moniker=config.base.moniker,
            )
            fuzz_config = None
            if config.p2p.test_fuzz:
                from cometbft_tpu.p2p.fuzz import FuzzConnConfig

                fuzz_config = FuzzConnConfig(
                    mode=config.p2p.test_fuzz_mode,
                    max_delay=config.p2p.test_fuzz_max_delay,
                    prob_drop_rw=config.p2p.test_fuzz_prob_drop_rw,
                )
            make_transport = self._transport_factory or (
                lambda ni, nk, fz: MultiplexTransport(ni, nk, fz)
            )
            self.switch = Switch(
                self.node_info,
                make_transport(self.node_info, self.node_key, fuzz_config),
                config=config.p2p,
                clock=self.clock,
            )
            self.consensus_reactor = ConsensusReactor(
                self.consensus_state,
                gossip_sleep=config.consensus.peer_gossip_sleep_duration,
            )
            # Gossiped txs enter the same admission path as RPC submissions
            # (preverify + lanes), with the peer id recorded as sender.
            self.mempool_reactor = MempoolReactor(
                config.mempool, self.ingress or self.mempool, clock=self.clock
            )
            self.evidence_reactor = EvidenceReactor(self.evidence_pool)
            self.blocksync_reactor = BlocksyncReactor(
                self.consensus_state.state,
                self.block_executor,
                self.block_store,
                block_sync=self._block_sync and not self._state_sync,
                on_caught_up=self._on_blocksync_caught_up,
                clock=self.clock,
            )
            self.statesync_reactor = StatesyncReactor(
                snapshot_conn=self.proxy_app.snapshot
            )
            self.switch.add_reactor("MEMPOOL", self.mempool_reactor)
            self.switch.add_reactor("EVIDENCE", self.evidence_reactor)
            self.switch.add_reactor("CONSENSUS", self.consensus_reactor)
            self.switch.add_reactor("BLOCKSYNC", self.blocksync_reactor)
            self.switch.add_reactor("STATESYNC", self.statesync_reactor)

            # PEX + address book (node/setup.go createPEXReactorAndAddToSwitch),
            # unless discovery is disabled (config.go PexReactor).
            self.pex_reactor = None
            if config.p2p.pex:
                from cometbft_tpu.p2p.pex import AddrBook, PexReactor

                book_path = (
                    os.path.join(config.base.root_dir, config.p2p.addr_book_file)
                    if config.base.root_dir
                    else ""
                )
                self.addr_book = AddrBook(book_path, strict=config.p2p.addr_book_strict)
                self.addr_book.add_our_address(self.node_key.id)
                self.addr_book.add_private_ids(
                    [i for i in config.p2p.private_peer_ids.split(",") if i]
                )
                self.pex_reactor = PexReactor(
                    self.addr_book,
                    seeds=[s.strip() for s in config.p2p.seeds.split(",") if s.strip()],
                    seed_mode=config.p2p.seed_mode,
                    max_outbound=config.p2p.max_num_outbound_peers,
                )
                self.switch.add_reactor("PEX", self.pex_reactor)

            for name, reactor in self._custom_reactors.items():
                self.switch.add_reactor(name, reactor)

        # RPC (node/node.go:392 startRPC).
        self.rpc_server = None
        self.grpc_server = None
        self._rpc_env = None

        # Light-client gateway (light/gateway.py): built on first
        # light_sync/light_proof RPC, never at boot — the lazy accessor is
        # what the RPC env carries and the metrics gauges deliberately
        # bypass (they read _light_gateway directly, so a scrape never
        # constructs it).
        self._light_gateway = None
        self._light_gateway_lock = threading.Lock()

        # Checkpoint-bundle origin (light/origin.py): same lazy contract —
        # built on the first light_bundle RPC / export, never at boot, and
        # the bundle gauges read _bundle_origin directly.
        self._bundle_origin = None
        self._bundle_origin_lock = threading.Lock()

    def _mmr_state_path(self) -> str:
        """One persisted accumulator state file under the node's db dir,
        shared by the gateway and the bundle origin (identical content at
        any size; writes are atomic replaces)."""
        return os.path.join(self.config.base.db_path(), "light_mmr.state")

    def light_gateway(self):
        """The node's LightGateway over its local stores; None when
        CMTPU_LIGHTGW=0 disables serving."""
        if os.environ.get("CMTPU_LIGHTGW", "1").strip().lower() in (
            "0", "false", "off",
        ):
            return None
        with self._light_gateway_lock:
            if self._light_gateway is None:
                from cometbft_tpu.light.gateway import LightGateway
                from cometbft_tpu.light.provider import BlockStoreProvider

                self._light_gateway = LightGateway(
                    self.genesis_doc.chain_id,
                    BlockStoreProvider(
                        self.genesis_doc.chain_id,
                        self.block_store,
                        self.state_store,
                    ),
                    state_path=self._mmr_state_path(),
                    logger=self.logger,
                )
            return self._light_gateway

    def bundle_origin(self, build: bool = True):
        """The node's BundleOrigin over its local stores; None when
        CMTPU_BUNDLE=0 disables the subsystem.  build=False peeks at the
        already-constructed origin (stats/metrics paths) without ever
        constructing one."""
        from cometbft_tpu.light.origin import bundles_enabled

        if not bundles_enabled():
            return None
        if not build:
            return self._bundle_origin
        with self._bundle_origin_lock:
            if self._bundle_origin is None:
                from cometbft_tpu.light.origin import BundleOrigin
                from cometbft_tpu.light.provider import BlockStoreProvider

                self._bundle_origin = BundleOrigin(
                    self.genesis_doc.chain_id,
                    BlockStoreProvider(
                        self.genesis_doc.chain_id,
                        self.block_store,
                        self.state_store,
                    ),
                    state_path=self._mmr_state_path(),
                    logger=self.logger,
                )
            return self._bundle_origin

    @staticmethod
    def _register_backend_metrics(reg) -> None:
        """backend_trips / backend_retries / backend_deadline_exceeded /
        backend_active_tier gauges plus the hybrid_* and sidecar_* ones,
        sampled lazily off the process-wide verification backend.  Sampling (not registering) checks for the
        supervisor so scraping never forces backend construction — under
        CMTPU_BACKEND=auto with an accelerator visible that would import
        jax at node boot instead of first verification."""
        from cometbft_tpu.sidecar import backend as backend_mod

        def sample(key):
            def fn():
                b = backend_mod._backend  # no get_backend(): never constructs
                if getattr(b, "name", "") == "coalesce":
                    b = b.inner  # supervisor gauges read the wrapped chain
                counters = getattr(b, "counters", None)
                if counters is None:
                    return 0
                c = counters()
                if key == "active_tier":
                    return b.active_tier_index
                return c.get(key, 0)

            return fn

        reg.gauge_func("backend", "trips",
                       "Verification-tier circuit-breaker trips.",
                       sample("trips"))
        reg.gauge_func("backend", "retries",
                       "Verification-tier transient-error retries.",
                       sample("retries"))
        reg.gauge_func("backend", "deadline_exceeded",
                       "Verification calls past CMTPU_DEADLINE_MS.",
                       sample("deadline_exceeded"))
        reg.gauge_func("backend", "active_tier",
                       "Degradation-chain index of the serving tier "
                       "(0 = primary).",
                       sample("active_tier"))
        def hybrid_sample(key):
            # The hybrid tier's running planner counters, wherever the chain
            # holds it; zeros until the backend exists and has such a tier.
            def fn():
                b = backend_mod._backend
                counters = getattr(b, "counters", None)
                if counters is None:
                    return 0
                c = counters()
                c = c.get("inner", c).get("tiers", {}).get("hybrid", {})
                return int(c.get("backend", {}).get(key, 0))

            return fn

        for key, text in (
            ("split_calls", "Calls the hybrid planner split between device and host."),
            ("share_changes", "Split calls whose device share differs from the one before."),
            ("plan_abs_err_ms", "Sum of |predicted - measured| call wall, ms."),
            ("wall_ms", "Sum of measured call wall where a prediction was made, ms."),
            ("resident_lanes", "Lanes verified against a resident key column's window tables."),
            ("resident_calls", "Dispatches that rode a resident key column's window tables."),
            ("resident_builds", "Key columns whose window tables were built on the device."),
            ("resident_build_ms", "Sum of the table builds' time on the device-owner thread, ms."),
            ("resident_bytes", "Bytes of window tables resident on each chip."),
            ("resident_evictions", "Resident key columns given up to the bytes bound."),
            ("resident_first_sightings", "Key columns remembered at their first sighting."),
            ("resident_repeat_sightings", "Key columns refused tables because a key repeats."),
            ("resident_repeat_lanes", "Lanes of the key columns refused because a key repeats."),
            ("pack_calls", "Dispatches the device tier packed on the host."),
            ("pack_walk_calls", "Packed dispatches a malformed entry sent through the per-lane walk."),
        ):
            reg.gauge_func("hybrid", key, text, hybrid_sample(key))

        def sidecar_sample(key):
            # Lazy like the others: zeros until a grpc tier exists (bare
            # CMTPU_BACKEND=grpc client, or the auto chain's sidecar tier,
            # possibly chaos-wrapped). Never dials or constructs.
            def fn():
                b = backend_mod._backend
                if getattr(b, "name", "") == "coalesce":
                    b = b.inner
                g = None
                if getattr(b, "name", "") == "grpc":
                    g = b
                else:
                    for t in getattr(b, "tiers", []):
                        be = t.backend
                        if getattr(be, "name", "").startswith("chaos"):
                            be = be.inner
                        if getattr(be, "name", "") == "grpc":
                            g = be
                            break
                counters = getattr(g, "counters", None)
                if counters is None:
                    return 0
                return counters().get(key, 0)

            return fn

        reg.gauge_func("sidecar", "streamed_calls",
                       "Batch verifications streamed to the sidecar in "
                       "chunks.",
                       sidecar_sample("streamed_calls"))
        reg.gauge_func("sidecar", "streamed_chunks",
                       "Chunks sent on streamed sidecar verifications.",
                       sidecar_sample("streamed_chunks"))
        reg.gauge_func("sidecar", "unary_calls",
                       "Batch verifications sent to the sidecar as one "
                       "frame.",
                       sidecar_sample("unary_calls"))
        reg.gauge_func("sidecar", "stream_retries",
                       "Streamed sidecar calls retried on a fresh "
                       "connection.",
                       sidecar_sample("stream_retries"))
        reg.gauge_func("sidecar", "remote_mesh_width",
                       "Serving pod chip count from the Ping capability "
                       "reply.",
                       sidecar_sample("remote_mesh_width"))
        for key, text in (
            ("bytes_sent", "Bytes of request frames written to the sidecar."),
            ("bytes_received", "Bytes of response frames read from the sidecar."),
            ("lanes_sent", "Signatures sent to the sidecar for verification."),
            ("columns_fixed", "Columns sent to the sidecar at one stride."),
            ("columns_ragged", "Columns sent to the sidecar with a lengths array."),
        ):
            reg.gauge_func("sidecar", key, text, sidecar_sample(key))

    @staticmethod
    def _register_engine_metrics(reg) -> None:
        """engine_* gauges: the continuous-batching verification engine's
        per-class view (consensus/blocksync/ingress/light admission counts,
        dispatched signatures, p95 admission wait, starvation promotions)
        plus its totals (requests, dispatches, requests that shared one,
        fallback splits, p95 queue wait). Lazy like the backend gauges — the
        sampler peeks `backend_mod._backend` (never get_backend()) and
        unwraps the CoalescingScheduler shim, so a scrape never constructs
        the chain. Zeros under CMTPU_COALESCE=0."""
        from cometbft_tpu.sidecar import backend as backend_mod

        def _engine():
            from cometbft_tpu.sidecar.engine import engine_of

            return engine_of(backend_mod._backend)

        def eng_sample(fn0):
            def fn():
                eng = _engine()
                if eng is None:
                    return 0
                try:
                    return fn0(eng)
                except Exception:
                    return 0

            return fn

        reg.gauge_func(
            "engine", "dispatches",
            "Device dispatches the continuous-batching engine issued.",
            eng_sample(lambda e: e.counters_["dispatches"]),
        )
        for key, text in (
            ("requests", "Verification requests submitted to the engine."),
            ("batched_requests", "Requests that shared a dispatch with another."),
            ("fallback_splits", "Merged dispatches split into per-request retries."),
        ):
            reg.gauge_func("engine", key, text,
                           eng_sample(lambda e, k=key: e.counters_[k]))
        reg.gauge_func(
            "engine", "queue_wait_p95_us",
            "95th-percentile wait in the engine's queue, all classes, microseconds.",
            eng_sample(lambda e: int(e.counters()["queue_wait_p95_ms"] * 1000)),
        )
        from cometbft_tpu.sidecar.engine import CLASS_NAMES

        for klass, cname in enumerate(CLASS_NAMES):
            reg.gauge_func(
                "engine", f"{cname}_admitted",
                f"{cname}-class requests admitted to the engine.",
                eng_sample(
                    lambda e, k=klass: e.class_counters_[k]["admitted"]
                ),
            )
            reg.gauge_func(
                "engine", f"{cname}_dispatched_sigs",
                f"{cname}-class signatures dispatched to the device.",
                eng_sample(
                    lambda e, k=klass: e.class_counters_[k]["dispatched_sigs"]
                ),
            )
            reg.gauge_func(
                "engine", f"{cname}_p95_us",
                f"{cname}-class 95th-percentile admission wait, microseconds.",
                eng_sample(
                    lambda e, k=klass: int(e.class_wait_p95_ms(k) * 1000)
                ),
            )
            reg.gauge_func(
                "engine", f"{cname}_starvation_promotions",
                f"{cname}-class requests promoted past fresher "
                "higher-class work by the starvation hatch.",
                eng_sample(
                    lambda e, k=klass: e.class_counters_[k][
                        "starvation_promotions"
                    ]
                ),
            )

    @staticmethod
    def _register_host_metrics(reg) -> None:
        """host_thread_cpu_seconds_<role>: CPU seconds the live threads of
        each role the hot paths name (`trace.ROLES`) have used, `other` for
        live threads of any other name (MainThread, consensus, rpc), and
        `process` and `ended_or_native` so that they close
        (`trace.thread_cpu()`): under one interpreter lock, whose rate
        grows is who holds it. A scrape reads clocks and constructs
        nothing; its gauges share one reading."""
        from cometbft_tpu.libs import trace

        held = [0.0, {}]

        def sample(pick):
            def fn():
                now = time.monotonic()
                if now - held[0] > 0.1:
                    held[:] = now, trace.thread_cpu()
                return pick(held[1])
            return fn

        closing = ("process", "ended_or_native")
        for key in trace.ROLES + closing:
            reg.gauge_func(
                "host", "thread_cpu_seconds_" + key.replace("-", "_"),
                f"CPU seconds used: {key}.", sample(lambda c, k=key: c.get(k, 0.0)),
            )
        reg.gauge_func(
            "host", "thread_cpu_seconds_other", "CPU seconds used: live threads of no named role.",
            sample(lambda c: sum(v for k, v in c.items()
                                 if k not in trace.ROLES and k not in closing)),
        )

    def _register_recvq_metrics(self, reg) -> None:
        """recvq_* gauges: the prioritized p2p recv demux, aggregated across
        every live peer connection plus retired-peer totals (per-channel
        queue depth, per-class deliveries, sheds, starvation promotions,
        max queue delay).  Lazy like the backend gauges — the sampler reads
        `self.switch` via getattr (registration runs before __init__ builds
        it) and the switch only walks already-built MConnections, so a
        scrape never constructs anything.  Empty/zero under CMTPU_RECVQ=0."""

        def _stats():
            sw = getattr(self, "switch", None)
            if sw is None:
                return None
            try:
                return sw.recvq_stats()
            except Exception:
                return None

        def rq(key):
            def fn():
                st = _stats()
                return int(st.get(key, 0)) if st else 0

            return fn

        reg.gauge_func("recvq", "depth",
                       "Messages queued in recv demux queues (all peers).",
                       rq("depth"))
        reg.gauge_func("recvq", "delivered_total",
                       "Messages the recv demux delivered to reactors.",
                       rq("delivered_total"))
        reg.gauge_func("recvq", "shed_total",
                       "Sheddable-class messages dropped on queue overflow.",
                       rq("shed_total"))
        reg.gauge_func("recvq", "promoted_total",
                       "Messages promoted past higher-class backlog by the "
                       "starvation hatch.",
                       rq("promoted_total"))
        reg.gauge_func("recvq", "backpressure_waits",
                       "Framer waits on a full consensus/blocksync queue "
                       "(TCP backpressure engaged).",
                       rq("backpressure_waits"))
        reg.gauge_func("recvq", "max_delay_us",
                       "Worst observed recv queue delay, microseconds.",
                       rq("max_delay_us"))
        from cometbft_tpu.p2p.conn.recvq import CLASS_NAMES as _RQ_CLASSES

        for cname in _RQ_CLASSES:
            reg.gauge_func(
                "recvq", f"{cname}_delivered",
                f"{cname}-class messages delivered by the recv demux.",
                rq(f"{cname}_delivered"),
            )
        # Per-channel depth over the reserved global channel ids
        # (p2p/reactor.py); unknown future channels still show up in the
        # recvq_stats RPC's `channels` map.
        from cometbft_tpu.p2p import reactor as _reactor_mod

        for chan in (
            _reactor_mod.PEX_CHANNEL,
            _reactor_mod.CONSENSUS_STATE_CHANNEL,
            _reactor_mod.CONSENSUS_DATA_CHANNEL,
            _reactor_mod.CONSENSUS_VOTE_CHANNEL,
            _reactor_mod.CONSENSUS_VOTE_SET_BITS_CHANNEL,
            _reactor_mod.MEMPOOL_CHANNEL,
            _reactor_mod.EVIDENCE_CHANNEL,
            _reactor_mod.BLOCKSYNC_CHANNEL,
            _reactor_mod.SNAPSHOT_CHANNEL,
            _reactor_mod.CHUNK_CHANNEL,
        ):
            def chan_depth(c=chan):
                st = _stats()
                if not st:
                    return 0
                return int(st.get("channels", {}).get(f"{c:#04x}", 0))

            reg.gauge_func("recvq", f"depth_ch{chan:02x}",
                           f"Recv demux queue depth on channel {chan:#04x}.",
                           chan_depth)

    def _register_evidence_metrics(self, reg) -> None:
        """evidence_* gauges: the misbehavior-accountability pipeline
        (pending pool size, lifetime reported/added/committed/expired).
        Lazy like the other families — the sampler reads
        `self.evidence_pool` via getattr, and `pending` walks only the
        pool's own DB prefix, so a scrape never constructs anything."""

        def ev(key):
            def fn():
                pool = getattr(self, "evidence_pool", None)
                if pool is None:
                    return 0
                try:
                    return int(pool.stats_snapshot().get(key, 0))
                except Exception:
                    return 0

            return fn

        reg.gauge_func("evidence", "pending",
                       "Evidence pieces pending inclusion in a block.",
                       ev("pending"))
        reg.gauge_func("evidence", "reported_total",
                       "Conflicting-vote reports received from consensus.",
                       ev("reported_total"))
        reg.gauge_func("evidence", "added_total",
                       "Evidence pieces accepted into the pending pool.",
                       ev("added_total"))
        reg.gauge_func("evidence", "committed_total",
                       "Evidence pieces committed in blocks.",
                       ev("committed_total"))
        reg.gauge_func("evidence", "expired_total",
                       "Pending evidence pruned past max-age.",
                       ev("expired_total"))

    @staticmethod
    def _register_mesh_metrics(reg) -> None:
        """mesh_* gauges: pod-scale sharding of the device verify tier
        (device count, sharded dispatches, bucket-padding lanes, sharded
        merkle roots).  Strictly passive — the sampler reads the ed25519
        kernel module only if something else already imported it, and the
        device count only if something already probed it, so a scrape never
        imports jax or starts the device backend."""
        import sys as _sys

        def mesh_sample(key):
            def fn():
                ek = _sys.modules.get("cometbft_tpu.ops.ed25519_kernel")
                if ek is None:
                    return 0
                return ek.mesh_counters().get(key, 0)

            return fn

        reg.gauge_func("mesh", "devices",
                       "Process-local chips one verify dispatch shards "
                       "across (0 until the device tier probes).",
                       mesh_sample("devices"))
        reg.gauge_func("mesh", "sharded_dispatches",
                       "Verify dispatches routed to the multi-chip program.",
                       mesh_sample("sharded_dispatches"))
        reg.gauge_func("mesh", "padded_lanes",
                       "Bucket-padding lanes shipped on sharded dispatches.",
                       mesh_sample("padded_lanes"))
        reg.gauge_func("mesh", "merkle_sharded_dispatches",
                       "Fused merkle roots served by the subtree-parallel "
                       "mesh program.",
                       mesh_sample("merkle_sharded_dispatches"))

    @staticmethod
    def _register_fanout_metrics(reg) -> None:
        """fanout_* gauges: the multi-host verification fleet (shard count,
        combined width, dispatches, redistributions, shards cooling down).
        Lazy like the backend gauges — the sampler walks the ALREADY-BUILT
        chain under `backend_mod._backend` for a tier named `fanout` (never
        get_backend(), never a dial), so a scrape with no fleet configured
        costs a few getattr probes and reads zero."""
        from cometbft_tpu.sidecar import backend as backend_mod

        def _fanout():
            stack, seen = [backend_mod._backend], set()
            while stack:
                b = stack.pop()
                if b is None or id(b) in seen:
                    continue
                seen.add(id(b))
                if getattr(b, "name", "") == "fanout":
                    return b
                stack.append(getattr(b, "inner", None))
                for t in getattr(b, "tiers", ()) or ():
                    stack.append(getattr(t, "backend", None))
            return None

        def fan_sample(fn0):
            def fn():
                fan = _fanout()
                if fan is None:
                    return 0
                try:
                    return fn0(fan)
                except Exception:
                    return 0

            return fn

        import time as _time

        reg.gauge_func("fanout", "shards",
                       "Shards in the verification fleet (0 = no fleet).",
                       fan_sample(lambda f: len(f.shards)))
        reg.gauge_func("fanout", "width",
                       "Combined fleet width (sum of shard mesh widths).",
                       fan_sample(lambda f: f.mesh_width()))
        reg.gauge_func("fanout", "dispatches",
                       "Batches the fleet fanned out across its shards.",
                       fan_sample(lambda f: f.counters_["dispatches"]))
        reg.gauge_func("fanout", "shard_failures",
                       "Per-shard slice failures (error or deadline).",
                       fan_sample(lambda f: f.counters_["shard_failures"]))
        reg.gauge_func("fanout", "redistributions",
                       "Retry rounds that re-split dead shards' slices "
                       "across survivors.",
                       fan_sample(lambda f: f.counters_["redistributions"]))
        reg.gauge_func("fanout", "redistributed_sigs",
                       "Signatures re-dispatched by redistribution rounds.",
                       fan_sample(lambda f: f.counters_["redistributed_sigs"]))
        reg.gauge_func("fanout", "shards_down",
                       "Shards currently sitting out a failure cooldown.",
                       fan_sample(lambda f: sum(
                           1 for s in f.shards
                           if not s.healthy(_time.monotonic())
                       )))

    def _register_hotpath_metrics(self, reg) -> None:
        """Consensus hot-path gauges: the vote-admission micro-batcher, WAL
        group commit, and the blocksync verify/apply pipeline. Lazy like the
        backend gauges — `sigbatch.counters()` never constructs a batcher,
        and the WAL/blocksync reads are getattr probes on objects built
        later in __init__, so a scrape is always side-effect free."""
        from cometbft_tpu.crypto import sigbatch

        def vb(key):
            return lambda: sigbatch.counters().get(key, 0)

        def vb_ratio():
            c = sigbatch.counters()
            return int(1000 * c["requests"] / max(1, c["dispatches"]))

        reg.gauge_func("vote_batch", "requests",
                       "Signature-verify requests to the vote micro-batcher.",
                       vb("requests"))
        reg.gauge_func("vote_batch", "dispatches",
                       "Columnar dispatches the vote micro-batcher issued.",
                       vb("dispatches"))
        reg.gauge_func("vote_batch", "coalesce_ratio_milli",
                       "Vote-batch requests per dispatch x1000.",
                       vb_ratio)
        reg.gauge_func("vote_batch", "cache_hits",
                       "Vote admissions answered by the verified-triple cache.",
                       vb("cache_hits"))
        reg.gauge_func("wal", "group_commits_total",
                       "WAL fsyncs that covered more than one write_sync caller.",
                       lambda: getattr(
                           getattr(getattr(self, "consensus_state", None),
                                   "wal", None),
                           "group_commits", 0) or 0)
        reg.gauge_func("blocksync", "pipeline_overlap_ms",
                       "Accumulated verify/apply overlap in blocksync, ms.",
                       lambda: int(getattr(
                           getattr(self, "blocksync_reactor", None),
                           "pipeline_overlap_ms", 0) or 0))

        def bs(key):
            def fn():
                reactor = getattr(self, "blocksync_reactor", None)
                return int(reactor.counters()[key]) if reactor else 0
            return fn

        for key, text in (
            ("heights_applied", "Heights the blocksync reactor applied."),
            ("fetch_wait_ms", "Sync thread with no pair of blocks to verify, ms."),
            ("verify_wait_ms", "Sync thread blocked on the prefetch worker, ms."),
            ("idle_sleeps", "10 ms sleeps of the blocksync pool routine."),
            ("redo_requests", "Blocks refused and requested again."),
            ("requests_sent", "Block requests the catch-up pool sent."),
            ("requests_to_busiest_peer", "Block requests sent to the connected peer asked most."),
            ("peers_asked", "Connected peers the catch-up pool has sent a block request."),
            ("block_bytes_received", "Bytes of encoded block responses received."),
            ("prefetch_windows", "Prefetch windows handed to the batch seam."),
            ("prefetch_lanes", "Lanes of the prefetch windows handed to the batch seam."),
            ("prefetch_ms", "Prefetch worker's time in its windows, collect and verify, ms."),
        ):
            reg.gauge_func("blocksync", key, text, bs(key))

        def counter(module, counters, key):
            # sys.modules, not an import: a scrape constructs nothing.
            def fn():
                mod = sys.modules.get(module)
                return getattr(mod, counters)()[key] if mod else 0
            return fn

        for key in ("entries", "hits", "dups", "dispatched", "inserted",
                    "evicted", "size", "whole_miss", "whole_hit", "mixed"):
            reg.gauge_func("verify_cache", key, f"Verified-triple cache: {key}.",
                           counter("cometbft_tpu.crypto.ed25519",
                                   "verified_cache_counters", key))
        for key, text in (
            ("built", "Validator-set columns computed for commit verification."),
            ("reused", "Commit verifications that found their set's columns computed."),
        ):
            reg.gauge_func("verify_columns", key, text,
                           counter("cometbft_tpu.types.validator_set",
                                   "columns_counters", key))
        for key, text in (
            ("heads_built", "Validator-set wire heads built, once a membership."),
            ("heads_reused", "Validator-set encodes that found their set's heads built."),
            ("roots_built", "Validator-set Merkle roots computed."),
            ("roots_reused", "Validator-set hashes that found their set's root computed."),
            ("commit_records_built", "Commits whose signature records were built, once a commit."),
            ("commit_encodes_reused", "Commit encodes that found the commit's bytes kept."),
            ("sig_records_walked", "Commit signature records decoded by the canonical walk."),
            ("sig_records_generic", "Commit signature records in another form, decoded field by field."),
            ("validator_records_walked", "Validator records decoded by the canonical walk."),
            ("validator_records_generic", "Validator records in another form or key type, decoded field by field."),
        ):
            reg.gauge_func("wire", key, text,
                           counter("cometbft_tpu.types.validator_set",
                                   "wire_counters", key))

    def _register_lightgw_metrics(self, reg) -> None:
        """Light-client gateway gauges. Strictly passive: they read the
        `_light_gateway` attribute (getattr-guarded — registration runs
        before __init__ assigns it) and never call the light_gateway()
        accessor, so a metrics scrape can never construct the gateway."""

        def gw(key):
            def fn():
                g = getattr(self, "_light_gateway", None)
                if g is None:
                    return 0
                return int(g.stats().get(key, 0))
            return fn

        def gw_share_milli():
            g = getattr(self, "_light_gateway", None)
            if g is None:
                return 0
            return int(1000 * g.stats()["plan_share_ratio"])

        reg.gauge_func("lightgw", "sessions_total",
                       "Light-gateway sync sessions admitted.",
                       gw("sessions_total"))
        reg.gauge_func("lightgw", "sessions_active",
                       "Light-gateway sync sessions currently in flight.",
                       gw("sessions_active"))
        reg.gauge_func("lightgw", "sessions_rejected",
                       "Light-gateway sessions shed at the concurrency cap.",
                       gw("sessions_rejected"))
        reg.gauge_func("lightgw", "plan_cache_hits",
                       "Descent plans answered from the memoized plan cache.",
                       gw("plan_hits"))
        reg.gauge_func("lightgw", "proofs_served",
                       "MMR cold-sync inclusion proofs served.",
                       gw("proofs_served"))
        reg.gauge_func("lightgw", "plan_share_ratio_milli",
                       "Plans served per plan computed x1000.",
                       gw_share_milli)
        reg.gauge_func("lightgw", "proof_bytes_served",
                       "Total wire bytes of MMR cold-sync proofs served.",
                       gw("proof_bytes_served"))

        # Bundle-origin gauges: same passive contract against
        # _bundle_origin — a scrape never constructs the origin.
        def bo(key):
            def fn():
                o = getattr(self, "_bundle_origin", None)
                if o is None:
                    return 0
                return int(o.stats().get(key, 0))
            return fn

        reg.gauge_func("lightgw", "bundles_built",
                       "Checkpoint bundles frozen by the origin.",
                       bo("bundles_built"))
        reg.gauge_func("lightgw", "bundle_hits",
                       "Checkpoint bundle serves (RPC/export/in-process).",
                       bo("bundle_hits"))
        reg.gauge_func("lightgw", "bundle_fallbacks",
                       "Bundle requests refused (no checkpoint/pruned/"
                       "mismatch) — the client fell back interactively.",
                       bo("bundle_fallbacks"))
        reg.gauge_func("lightgw", "bundle_bytes_served",
                       "Total wire bytes of checkpoint bundles served.",
                       bo("bundle_bytes_served"))

    # -- lifecycle ------------------------------------------------------------

    def start(self) -> None:
        """node/node.go:371 OnStart (event bus/indexer already run from
        __init__, as in NewNode): p2p listen + dial, then the statesync →
        blocksync → consensus phase chain."""
        if self.switch is not None:
            host, port = _parse_laddr(self.config.p2p.laddr)
            self.p2p_laddr = self.switch.start(f"{host}:{port}")
            if self.logger:
                self.logger.info(
                    "p2p listening", module="p2p", addr=self.p2p_laddr,
                    node_id=self.node_key.id,
                )
            # Persistent peers ride the switch's backoff redial loop
            # (switch.go reconnectToPeer): peers that aren't up yet — the
            # normal case when a testnet launches in parallel — must not
            # fail OnStart.
            self.switch.add_persistent_peers(
                [a.strip() for a in self.config.p2p.persistent_peers.split(",") if a.strip()]
            )
            self.switch.dial_persistent_peers()

        if self.metrics_server is not None:
            self.metrics_server.start()
        if self.config.rpc.pprof_laddr:
            from cometbft_tpu.libs.pprof import PprofServer

            host, _, port = self.config.rpc.pprof_laddr.split("://")[-1].rpartition(":")
            self.pprof_server = PprofServer(
                host or "127.0.0.1",
                int(port),
                trace_dir=os.path.join(self.config.base.root_dir or ".", "jax-trace"),
            )
            self.pprof_server.start()
        if os.environ.get("CMTPU_WATCHDOG"):
            from cometbft_tpu.libs.deadlock import Watchdog

            self.watchdog = Watchdog(
                lambda: self.consensus_state.rs.height,
                stall_after=float(os.environ["CMTPU_WATCHDOG"]),
                logger=self.logger,
                on_stall=lambda report: print(report),
            )
            self.watchdog.start()

        if self._state_sync and self.switch is not None:
            threading.Thread(
                target=self._statesync_routine, daemon=True, name="statesync"
            ).start()
        elif self._block_sync and self.switch is not None:
            pass  # blocksync reactor's pool routine runs; caught-up hook
            # starts consensus (_on_blocksync_caught_up)
        else:
            self.consensus_state.start()
        rpc_laddr = self.config.rpc.laddr
        if rpc_laddr:
            host, port = _parse_laddr(rpc_laddr)
            pub = None
            if self.priv_validator is not None:
                pub = self.priv_validator.get_pub_key()
            env = Environment(
                config=self.config,
                state_store=self.state_store,
                block_store=self.block_store,
                consensus_state=self.consensus_state,
                consensus_reactor=getattr(self, "consensus_reactor", None),
                mempool=self.ingress or self.mempool,
                ingress=self.ingress,
                evidence_pool=self.evidence_pool,
                event_bus=self.event_bus,
                genesis_doc=self.genesis_doc,
                priv_validator_pub_key=pub,
                node_info={"moniker": self.config.base.moniker, "network": self.genesis_doc.chain_id},
                tx_indexer=self.tx_indexer,
                block_indexer=self.block_indexer,
                proxy_app_query=self.proxy_app.query,
                p2p_peers=self.switch,
                light_gateway=self.light_gateway,
                bundle_origin=self.bundle_origin,
            )
            self._rpc_env = env
            routes_map = routes(env)
            self.rpc_server = JSONRPCServer(routes_map, host, port)
            self.rpc_server.start()
            if self.config.rpc.grpc_laddr:
                # node/node.go startRPC grpcListener branch: the minimal
                # BroadcastAPI (Ping/BroadcastTx) on its own port.
                from cometbft_tpu.rpc.grpc_server import GrpcBroadcastServer

                self.grpc_server = GrpcBroadcastServer(
                    routes_map, self.config.rpc.grpc_laddr
                )
                self.grpc_server.start()

    def stop(self) -> None:
        self.consensus_state.stop()
        if self.ingress is not None:
            self.ingress.close()
        if getattr(self, "pprof_server", None) is not None:
            self.pprof_server.stop()
        if getattr(self, "watchdog", None) is not None:
            self.watchdog.stop()
        if self.metrics_server is not None:
            self.metrics_server.stop()
        if self.switch is not None:
            self.switch.stop()
        self.indexer_service.stop()
        self.event_bus.stop()
        if getattr(self, "event_sink", None) is not None:
            self.event_sink.stop()
        if self.rpc_server:
            self.rpc_server.stop()
        if self.grpc_server is not None:
            self.grpc_server.stop()
        # last: RPC handlers reach ABCI through these clients — close them
        # only after no request can arrive
        self.proxy_app.stop()

    @property
    def rpc_port(self) -> int:
        return self.rpc_server.port if self.rpc_server else 0

    # -- boot phases (node/node.go:423-433) -----------------------------------

    def _on_blocksync_caught_up(self, state) -> None:
        """blocksync's SwitchToConsensus hook (blocksync/reactor.go:392)."""
        self.consensus_state.update_to_state(state)
        self.consensus_state.start()

    def _make_state_provider(self):
        """node/setup.go-style light StateProvider over the configured RPC
        servers (config.go StateSyncConfig.RPCServers)."""
        from cometbft_tpu.light.provider import HTTPProvider
        from cometbft_tpu.rpc.client import HTTPClient
        from cometbft_tpu.statesync import LightClientStateProvider
        from cometbft_tpu.types import cmttime

        cfg = self.config.statesync
        if not cfg.rpc_servers:
            raise ValueError("statesync.rpc_servers must be set when statesync is enabled")
        providers = [
            HTTPProvider(self.genesis_doc.chain_id, HTTPClient(s))
            for s in cfg.rpc_servers
        ]
        return LightClientStateProvider(
            self.genesis_doc.chain_id,
            providers[0],
            providers[1:],
            trust_height=cfg.trust_height,
            trust_hash=bytes.fromhex(cfg.trust_hash),
            trust_period_ns=int(cfg.trust_period * 10**9),
            consensus_params=self.consensus_state.state.consensus_params,
            now=cmttime.now,
        )

    def _statesync_routine(self) -> None:
        """node/node.go:423-433 startStateSync: snapshot restore verified by
        the light client, store bootstrap, then SwitchToBlockSync — whose
        caught-up hook starts consensus."""
        from cometbft_tpu.statesync import Syncer

        cfg = self.config.statesync
        try:
            provider = self._make_state_provider()
            syncer = Syncer(
                self.proxy_app.snapshot,
                self.proxy_app.query,
                provider,
                self.statesync_reactor.request_chunk,
                chunk_timeout=cfg.chunk_request_timeout,
                chunk_fetchers=cfg.chunk_fetchers,
            )
            self.statesync_reactor.set_syncer(syncer)
            if self.logger:
                self.logger.info("starting statesync", module="statesync")
            state, commit = syncer.sync_any(
                discovery_time=cfg.discovery_time, timeout=600
            )
            if self.logger:
                self.logger.info(
                    "snapshot restored; switching to blocksync",
                    module="statesync", height=state.last_block_height,
                )
            self.state_store.bootstrap(state)
            self.block_store.save_seen_commit(state.last_block_height, commit)
            self.blocksync_reactor.switch_to_block_sync(state, self.block_executor)
        except Exception as e:
            # Fall back to blocksync-from-genesis rather than leaving a
            # zombie node (consensus only starts via blocksync's caught-up
            # hook, and the reactor was built with block_sync=False while
            # statesync was armed).
            if self.logger:
                self.logger.error(
                    "statesync failed; falling back to blocksync",
                    module="statesync", err=str(e),
                )
            else:
                print(f"statesync failed ({e}); falling back to blocksync")
            self.blocksync_reactor.switch_to_block_sync(
                self.consensus_state.state, self.block_executor
            )


def _only_validator_is_us(state, priv_validator) -> bool:
    """node/node.go:174: a 1-validator net that IS us must not wait for
    blocksync peers before producing blocks."""
    if priv_validator is None:
        return False
    if state.validators.size() != 1:
        return False
    return state.validators.validators[0].address == priv_validator.get_pub_key().address()


def _parse_laddr(laddr: str) -> tuple[str, int]:
    addr = laddr.split("://", 1)[-1]
    host, _, port = addr.rpartition(":")
    return host or "127.0.0.1", int(port)


def default_new_node(config: Config, logger=None, app=None) -> Node:
    """node/setup.go:64 DefaultNewNode: files from config; the app comes
    from proxy_app — "kvstore"/"noop" in-process, otherwise a socket address
    served by an external ABCI app (proxy/client.go DefaultClientCreator);
    a remote signer when priv_validator_laddr is set (node/node.go:181
    createAndStartPrivValidator SocketVal branch)."""
    if logger is None:
        from cometbft_tpu.libs.log import new_logger

        logger = new_logger(level=config.base.log_level, fmt=config.base.log_format)
    genesis = GenesisDoc.from_file(config.base.genesis_path())
    if config.base.priv_validator_laddr:
        from cometbft_tpu.privval.signer import (
            RetrySignerClient,
            SignerClient,
            SignerListenerEndpoint,
        )

        endpoint = SignerListenerEndpoint(config.base.priv_validator_laddr)
        pv = RetrySignerClient(SignerClient(endpoint, genesis.chain_id))
    else:
        pv = FilePV.load_or_generate(
            config.base.priv_validator_key_path(),
            config.base.priv_validator_state_path(),
        )
    if app is not None:
        creator = LocalClientCreator(app)
    elif config.base.proxy_app == "kvstore":
        creator = LocalClientCreator(
            KVStoreApplication(snapshot_interval=config.base.snapshot_interval)
        )
    elif config.base.proxy_app == "persistent_kvstore":
        from cometbft_tpu.abci.example.kvstore import PersistentKVStoreApplication

        creator = LocalClientCreator(
            PersistentKVStoreApplication(
                snapshot_interval=config.base.snapshot_interval
            )
        )
    elif config.base.proxy_app == "noop":
        from cometbft_tpu.abci import types as abci_types

        creator = LocalClientCreator(abci_types.Application())
    elif config.base.proxy_app.startswith("grpc://"):
        from cometbft_tpu.abci.grpc import GrpcClientCreator

        creator = GrpcClientCreator(config.base.proxy_app)
    else:
        from cometbft_tpu.abci.client import SocketClientCreator

        creator = SocketClientCreator(config.base.proxy_app)
    return Node(config, genesis, pv, creator, logger)
