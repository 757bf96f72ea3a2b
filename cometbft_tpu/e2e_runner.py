"""Manifest-driven e2e testnet runner (reference: test/e2e/pkg/manifest.go +
test/e2e/runner).

The reference drives docker-compose testnets from a TOML manifest: node
topology, per-node perturbation schedules (kill / pause / disconnect /
restart — plus this framework's own ``backend_faults``, which restarts a
node with a chaos-injected supervised verification chain, and
``vote_batch``, which does that with a widened vote-admission micro-batch
window and asserts the validator's precommit still lands), transaction
load, then a liveness + hash-agreement check and an optional benchmark
report.  This is that runner over OS processes on
loopback (the deployment substrate this framework's e2e tier uses —
tests/test_e2e_processes.py holds the individual perturbations to their
semantics; this module sequences them from a manifest).

Manifest subset (same field names as the reference where they apply):

    initial_height = 1
    load_tx_rate = 100          # tx/s sustained against node 0
    target_blocks = 12          # blocks every node must reach post-perturb
    abci_protocol = "builtin"   # informational default; per-node overrides
    backend = "cpu"             # CMTPU_BACKEND for every node (cpu | hybrid)
    app = "kvstore"             # kvstore | persistent_kvstore
    snapshot_interval = 3       # app-side snapshots on genesis nodes
    validator_churn = true      # add+remove a validator via val: txs mid-run
    light_client = true         # sequentially verify the agreed height
    [node.validator01]
    [node.validator02]
    perturb = ["pause", "kill"]
    [node.validator03]
    key_type = "secp256k1"      # consensus key: ed25519 default
    abci = "socket"             # local | socket | grpc app boundary
    [node.full01]
    mode = "full"
    start_at = 5                # late join once the net reaches this height
    state_sync = true           # join via verified snapshot restore

Ordering contract (the generator enforces, load() validates): genesis
validators come first — node 0 is the height reference, load target and
statesync trust source, so it must be a genesis validator.

Run: ``python -m cometbft_tpu.cmd e2e --manifest m.toml`` or
``E2ERunner(manifest_path).run()``.
"""

from __future__ import annotations

import base64
import json
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time
import tomllib
from dataclasses import dataclass, field

MODES = ("validator", "full", "seed")
ABCI_MODES = ("local", "socket", "grpc")
PERTURBATIONS = (
    "kill", "pause", "disconnect", "restart", "backend_faults",
    "concurrent_light_clients", "tx_flood", "vote_batch",
    "light_gateway", "mixed_load", "recv_flood", "bundle_cold_sync",
)
BACKENDS = ("cpu", "hybrid")
APPS = ("kvstore", "persistent_kvstore")


@dataclass
class ManifestNode:
    name: str
    mode: str = "validator"  # validator | full | seed
    key_type: str = "ed25519"  # consensus key type (validators)
    start_at: int = 0  # 0 = genesis; >0 = join at that net height
    state_sync: bool = False  # late join via snapshot restore
    abci: str = "local"  # local | socket | grpc app boundary
    perturb: list[str] = field(default_factory=list)

    def is_validator(self) -> bool:
        return self.mode == "validator"


@dataclass
class Manifest:
    initial_height: int = 1
    load_tx_rate: int = 50
    target_blocks: int = 8
    backend: str = "cpu"  # CMTPU_BACKEND handed to every node
    app: str = "kvstore"  # ABCI app all nodes run
    snapshot_interval: int = 0  # app snapshots on genesis nodes
    validator_churn: bool = False  # val: tx add/remove mid-run
    light_client: bool = False  # verify the agreed height
    seed: int = -1  # generator seed (informational; -1 = hand-written)
    network: str = "real"  # real = OS processes; sim = virtual-clock simnet
    sim: dict = field(default_factory=dict)  # scenario spec (network = "sim")
    nodes: list[ManifestNode] = field(default_factory=list)

    @classmethod
    def load(cls, path: str) -> "Manifest":
        with open(path, "rb") as f:
            raw = tomllib.load(f)
        network = str(raw.get("network", "real"))
        if network == "sim":
            return cls._load_sim(raw)
        if network != "real":
            raise ValueError(f"unknown network {network!r} (want real | sim)")
        from cometbft_tpu.privval.file import KEY_TYPES

        nodes = [
            ManifestNode(
                name=name,
                mode=str(spec.get("mode", "validator")),
                key_type=str(spec.get("key_type", "ed25519")),
                start_at=int(spec.get("start_at", 0)),
                state_sync=bool(spec.get("state_sync", False)),
                abci=str(spec.get("abci", "local")),
                perturb=list(spec.get("perturb", [])),
            )
            for name, spec in raw.get("node", {}).items()
        ]
        if not nodes:
            raise ValueError("manifest has no [node.*] entries")
        m = cls(
            initial_height=int(raw.get("initial_height", 1)),
            load_tx_rate=int(raw.get("load_tx_rate", 50)),
            target_blocks=int(raw.get("target_blocks", 8)),
            backend=str(raw.get("backend", "cpu")),
            app=str(raw.get("app", "kvstore")),
            snapshot_interval=int(raw.get("snapshot_interval", 0)),
            validator_churn=bool(raw.get("validator_churn", False)),
            light_client=bool(raw.get("light_client", False)),
            seed=int(raw.get("seed", -1)),
            nodes=nodes,
        )
        for n in nodes:
            bad = set(n.perturb) - set(PERTURBATIONS)
            if bad:
                raise ValueError(f"{n.name}: unknown perturbations {sorted(bad)}")
            if n.mode not in MODES:
                raise ValueError(f"{n.name}: unknown mode {n.mode!r}")
            if n.key_type not in KEY_TYPES:
                raise ValueError(f"{n.name}: unknown key_type {n.key_type!r}")
            if n.abci not in ABCI_MODES:
                raise ValueError(f"{n.name}: unknown abci mode {n.abci!r}")
            if n.state_sync and n.start_at <= 0:
                raise ValueError(f"{n.name}: state_sync requires start_at > 0")
        if m.backend not in BACKENDS:
            raise ValueError(f"unknown backend {m.backend!r}")
        if m.app not in APPS:
            raise ValueError(f"unknown app {m.app!r}")
        if m.validator_churn and m.app != "persistent_kvstore":
            raise ValueError("validator_churn requires app = 'persistent_kvstore'")
        if any(n.state_sync for n in nodes) and m.snapshot_interval <= 0:
            raise ValueError("state_sync nodes need snapshot_interval > 0")
        first = nodes[0]
        if not (first.is_validator() and first.start_at == 0):
            raise ValueError(
                "node 0 must be a genesis validator (height reference + "
                "load target + statesync trust source)"
            )
        if not any(n.is_validator() and n.start_at == 0 for n in nodes):
            raise ValueError("manifest needs at least one genesis validator")
        # Equal-power quorum: the genesis validators that start at t0 must
        # alone hold > 2/3 of the validator power, or the chain never moves.
        v_total = sum(1 for n in nodes if n.is_validator())
        v_late = sum(1 for n in nodes if n.is_validator() and n.start_at > 0)
        if v_late and 3 * (v_total - v_late) <= 2 * v_total:
            raise ValueError(
                f"{v_late} late-join validators of {v_total} break quorum "
                "at genesis"
            )
        return m

    @classmethod
    def _load_sim(cls, raw: dict) -> "Manifest":
        """network = "sim": the [sim] table IS the scenario spec.

        Partition/churn schedules arrive as parallel flat arrays
        (``partition_at_s``/``partition_heal_s``/``partition_fraction``,
        ``churn_at_s``/``churn_down_s``/``churn_nodes``) — the TOML subset
        this repo parses has no inline tables — and are zipped back into
        the list-of-dicts form ``simnet.scenario.default_spec`` takes.
        No [node.*] sections: every simulated node is an equal validator.
        """
        from cometbft_tpu.simnet.scenario import default_spec

        sim_raw = dict(raw.get("sim", {}))
        parts = [
            {"at_s": a, "heal_s": h, "fraction": f}
            for a, h, f in zip(
                sim_raw.pop("partition_at_s", []),
                sim_raw.pop("partition_heal_s", []),
                sim_raw.pop("partition_fraction", []),
            )
        ]
        churn = [
            {"at_s": a, "down_s": d, "nodes": n}
            for a, d, n in zip(
                sim_raw.pop("churn_at_s", []),
                sim_raw.pop("churn_down_s", []),
                sim_raw.pop("churn_nodes", []),
            )
        ]
        byz = [
            {"role": r, "node": n, "from_s": f, "until_s": u}
            for r, n, f, u in zip(
                sim_raw.pop("byz_role", []),
                sim_raw.pop("byz_node", []),
                sim_raw.pop("byz_from_s", []),
                sim_raw.pop("byz_until_s", []),
            )
        ]
        # only_partitioned is an equivocator-only knob; the aligned array
        # carries false placeholders for other roles (make_actor rejects
        # the key elsewhere).
        for entry, op in zip(byz, sim_raw.pop("byz_only_partitioned", [])):
            if entry["role"] == "equivocator":
                entry["only_partitioned"] = bool(op)
        joins = [
            {"node": n, "at_s": a}
            for n, a in zip(
                sim_raw.pop("join_node", []),
                sim_raw.pop("join_at_s", []),
            )
        ]
        if parts:
            sim_raw["partitions"] = parts
        if churn:
            sim_raw["churn"] = churn
        if byz:
            sim_raw["byzantine"] = byz
        if joins:
            sim_raw["joins"] = joins
        sim = default_spec(**sim_raw)  # validates: unknown keys raise
        return cls(
            network="sim",
            sim=sim,
            seed=int(raw.get("seed", sim["seed"])),
            target_blocks=int(sim["blocks"]),
        )

    def validators(self) -> list[ManifestNode]:
        return [n for n in self.nodes if n.is_validator()]


def _free_ports(n: int) -> list[int]:
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


class E2ERunner:
    def __init__(self, manifest_path: str, home: str, log=print):
        self.manifest = Manifest.load(manifest_path)
        self.home = home
        self.log = log
        self.procs: dict[str, subprocess.Popen] = {}
        self.app_procs: dict[str, subprocess.Popen] = {}
        self.rpc_ports: dict[str, int] = {}
        self.p2p_ports: dict[str, int] = {}
        self._log_files: list = []
        # Nodes whose verification backend runs fault-injected (the
        # backend_faults perturbation arms this before relaunch).
        self._fault_armed: set[str] = set()
        # Per-node results of the concurrent_light_clients perturbation
        # (swarm agreement + the runner-process coalesce counter deltas).
        self._light_swarms: dict[str, dict] = {}
        # Per-node results of the light_gateway perturbation (cold-sync
        # swarm against the node's MMR proof path).
        self._light_gateways: dict[str, dict] = {}
        # Nodes relaunched with per-sender ingress rate limiting armed, and
        # the per-node results of the tx_flood perturbation.
        self._flood_armed: set[str] = set()
        self._tx_floods: dict[str, dict] = {}
        # Nodes relaunched with a widened vote-admission micro-batch window
        # on top of the faulted chain, and the per-node results of the
        # vote_batch perturbation's zero-valid-vote-loss probe.
        self._votebatch_armed: set[str] = set()
        self._vote_batches: dict[str, dict] = {}
        # Per-node results of the mixed_load perturbation (tx flood + light
        # swarm driven CONCURRENTLY: all engine classes contend at once).
        self._mixed_loads: dict[str, dict] = {}
        # Per-node results of the recv_flood perturbation (gossip-side
        # mempool flood pressuring the target's prioritized recv demux).
        self._recv_floods: dict[str, dict] = {}
        # Per-node results of the bundle_cold_sync perturbation (checkpoint
        # bundle exported live, swarm syncs from the flat dir with the
        # origin node DOWN, then the node relaunches).
        self._bundle_syncs: dict[str, dict] = {}
        # Stall forensics: every node's consensus round-state, captured at
        # the moment a wait_height deadline expires (the nodes are SIGKILLed
        # during teardown, so this is the only window to collect it).
        self.last_round_states: dict | None = None
        # network = "sim": the scenario's full resolved schedule (latency
        # matrix, partition/churn timeline, seeds) — repro.json embeds it so
        # a failing run replays bit-identically from the artifact alone.
        self.sim_schedule: dict | None = None

    # -- setup ------------------------------------------------------------

    def setup(self) -> None:
        """testnet homes + config.toml per node (runner/setup.go shape).

        The testnet CLI lays down homes validators-first (matching the
        manifest's ordering contract); per-node config then specializes
        the proxy_app boundary, statesync arming, and snapshot cadence."""
        from cometbft_tpu.cmd.__main__ import main as cli
        from cometbft_tpu.config import default_config
        from cometbft_tpu.config.toml import write_config_file
        from cometbft_tpu.p2p.key import NodeKey

        nodes = self.manifest.nodes
        n_validators = len(self.manifest.validators())
        key_types = ",".join(n.key_type for n in nodes)
        assert cli(
            ["testnet", "--validators", str(n_validators),
             "--non-validators", str(len(nodes) - n_validators),
             "--key-types", key_types,
             "--output-dir", self.home, "--chain-id", "e2e-manifest"]
        ) == 0
        p2p = _free_ports(len(nodes))
        rpc = _free_ports(len(nodes))
        node_ids = [
            NodeKey.load(
                os.path.join(self.home, f"node{i}", "config", "node_key.json")
            ).id
            for i in range(len(nodes))
        ]
        peers = [
            f"{node_ids[i]}@127.0.0.1:{p2p[i]}" for i in range(len(nodes))
        ]
        # Every node dials the genesis cohort; late joiners are dial-only
        # (nobody lists a peer that isn't up yet — the switch would retry
        # forever, which is allowed but noisy).
        genesis_idx = [i for i, n in enumerate(nodes) if n.start_at == 0]
        for i, node in enumerate(nodes):
            home = os.path.join(self.home, f"node{i}")
            cfg = default_config()
            cfg.rpc.laddr = f"tcp://127.0.0.1:{rpc[i]}"
            cfg.p2p.laddr = f"tcp://127.0.0.1:{p2p[i]}"
            cfg.p2p.persistent_peers = ",".join(
                peers[j] for j in genesis_idx if j != i
            )
            cfg.p2p.addr_book_strict = False
            cfg.p2p.allow_duplicate_ip = True
            cfg.p2p.seed_mode = node.mode == "seed"
            cfg.consensus.timeout_commit = 0.2
            cfg.consensus.skip_timeout_commit = False
            cfg.base.proxy_app = self._proxy_app_addr(i, node)
            if node.start_at == 0:
                # Only genesis nodes serve snapshots — a restoring node
                # re-offering its own half-built snapshot is the reference's
                # self-serve footgun.
                cfg.base.snapshot_interval = self.manifest.snapshot_interval
            if node.state_sync:
                cfg.statesync.enable = True
                # Trust basis (height + hash) is only knowable at launch
                # time; _launch_late rewrites this file then.
                rpc_servers = [
                    f"http://127.0.0.1:{rpc[j]}" for j in genesis_idx[:2]
                ]
                if len(rpc_servers) == 1:
                    rpc_servers *= 2  # primary + witness may be the same
                cfg.statesync.rpc_servers = tuple(rpc_servers)
                cfg.statesync.trust_height = 1
                cfg.statesync.discovery_time = 2.0
            write_config_file(os.path.join(home, "config", "config.toml"), cfg)
            self.rpc_ports[node.name] = rpc[i]
            self.p2p_ports[node.name] = p2p[i]

    def _proxy_app_addr(self, idx: int, node: ManifestNode) -> str:
        """local -> in-process app name; socket/grpc -> a unix socket under
        the node home served by an external app process."""
        if node.abci == "local":
            return self.manifest.app
        sock = os.path.join(self.home, f"node{idx}", "app.sock")
        return f"grpc://{sock}" if node.abci == "grpc" else f"unix://{sock}"

    # -- process management ----------------------------------------------

    def _node_env(self) -> dict:
        return {
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            "CMTPU_BACKEND": self.manifest.backend,
        }

    def _open_log(self, idx: int, suffix: str = "node"):
        path = os.path.join(self.home, f"node{idx}", f"{suffix}.log")
        f = open(path, "ab")
        self._log_files.append(f)
        return f

    def _launch_app(self, idx: int, node: ManifestNode) -> None:
        """External ABCI app process for socket/grpc nodes (the reference
        runs the e2e app in its own container entrypoint)."""
        if node.abci == "local":
            return
        sock = os.path.join(self.home, f"node{idx}", "app.sock")
        if os.path.exists(sock):
            os.unlink(sock)
        addr = f"grpc://{sock}" if node.abci == "grpc" else f"unix://{sock}"
        logf = self._open_log(idx, suffix="app")
        snapshot = (
            self.manifest.snapshot_interval if node.start_at == 0 else 0
        )
        self.app_procs[node.name] = subprocess.Popen(
            [sys.executable, "-m", "cometbft_tpu.abci.server",
             self.manifest.app, "--addr", addr,
             "--transport", "grpc" if node.abci == "grpc" else "socket",
             "--snapshot-interval", str(snapshot)],
            stdout=logf, stderr=logf, env=self._node_env(),
        )
        deadline = time.time() + 15
        while not os.path.exists(sock):
            if self.app_procs[node.name].poll() is not None:
                raise RuntimeError(f"{node.name}: ABCI app process died at start")
            if time.time() > deadline:
                raise TimeoutError(f"{node.name}: ABCI app socket never appeared")
            time.sleep(0.05)

    def _fault_env(self, idx: int) -> dict:
        """The backend_faults environment: a supervised (CMTPU_BACKEND=auto)
        chain whose primary tier injects deterministic latency + errors
        (sidecar/chaos.py), seeded from the manifest seed + node index so a
        failing seed reproduces its exact fault sequence.  Probabilities
        stay moderate — the point is degrading THROUGH faults, not a dead
        node — and the anchor tier is always clean."""
        seed = max(self.manifest.seed, 0) * 1000 + idx
        return {
            "CMTPU_BACKEND": "auto",
            "CMTPU_FAULTS": "latency:0.2:25,error:0.25",
            "CMTPU_FAULTS_SEED": str(seed),
            "CMTPU_DEADLINE_MS": "2000",
            "CMTPU_BACKOFF_MS": "10",
            "CMTPU_BREAKER_COOLDOWN_MS": "2000",
        }

    def _launch(self, idx: int) -> subprocess.Popen:
        node = self.manifest.nodes[idx]
        if node.name not in self.app_procs or \
           self.app_procs[node.name].poll() is not None:
            self._launch_app(idx, node)
        logf = self._open_log(idx)
        env = self._node_env()
        if node.name in self._fault_armed:
            env.update(self._fault_env(idx))
        if node.name in self._votebatch_armed:
            # vote_batch: widen the admission micro-batch window (5x the
            # default, so concurrent peer admissions really share windows)
            # and keep the chaos-faulted supervised chain underneath it.
            env.update(self._fault_env(idx))
            env["CMTPU_VOTE_BATCH_WINDOW_MS"] = "10"
        if node.name in self._flood_armed:
            # tx_flood arms a finite per-sender admission rate so the
            # hostile signer gets shed instead of squatting the mempool.
            # The rate must sit well under what the spammer can push
            # through one HTTP connection on a slow host (~20/s observed
            # single-core) and well over the honest cadence (~1 tx/s).
            env["CMTPU_INGRESS_SENDER_RPS"] = "4"
        if "bundle_cold_sync" in node.perturb:
            # Checkpoint every 2 blocks so a short e2e run crosses several
            # boundaries — the default 1000 would never checkpoint here.
            # Armed from genesis (the perturb list is known up front).
            env["CMTPU_BUNDLE_INTERVAL"] = "2"
        return subprocess.Popen(
            [sys.executable, "-m", "cometbft_tpu.cmd", "--home",
             os.path.join(self.home, f"node{idx}"), "start"],
            stdout=logf, stderr=logf,
            env=env,
        )

    def start(self) -> None:
        """Launch the genesis cohort; late joiners wait for their height."""
        started = 0
        for i, node in enumerate(self.manifest.nodes):
            if node.start_at == 0:
                self.procs[node.name] = self._launch(i)
                started += 1
        late = len(self.manifest.nodes) - started
        self.log(f"started {started} nodes" + (f" ({late} join late)" if late else ""))

    def _launch_late(self, idx: int, node: ManifestNode) -> None:
        """runner/start.go second wave: wait for the net to reach the node's
        start_at height, arm the statesync trust basis from live chain data,
        then launch."""
        first = self.manifest.nodes[0].name
        self.wait_height(first, node.start_at)
        if node.state_sync:
            from cometbft_tpu.config import default_config
            from cometbft_tpu.config.toml import load_toml, write_config_file
            from cometbft_tpu.rpc.client import HTTPClient

            blk = HTTPClient(
                f"http://127.0.0.1:{self.rpc_ports[first]}", timeout=5
            ).block(1)
            toml_path = os.path.join(
                self.home, f"node{idx}", "config", "config.toml"
            )
            cfg = load_toml(toml_path, default_config())
            cfg.statesync.trust_height = 1
            cfg.statesync.trust_hash = blk["block_id"]["hash"]
            write_config_file(toml_path, cfg)
        self.log(f"late join {node.name} at height {node.start_at}"
                 + (" (statesync)" if node.state_sync else " (blocksync)"))
        self.procs[node.name] = self._launch(idx)

    # -- RPC helpers ------------------------------------------------------

    def _height(self, name: str) -> int:
        from cometbft_tpu.rpc.client import HTTPClient

        st = HTTPClient(
            f"http://127.0.0.1:{self.rpc_ports[name]}", timeout=3
        ).status()
        return int(st["sync_info"]["latest_block_height"])

    def wait_height(self, name: str, target: int, timeout: float = 240) -> int:
        deadline = time.time() + timeout
        last = -1
        while time.time() < deadline:
            try:
                last = self._height(name)
                if last >= target:
                    return last
            except Exception:
                pass
            time.sleep(0.3)
        self.last_round_states = self.dump_round_states()
        raise TimeoutError(f"{name}: height {target} not reached (last {last})")

    def dump_round_states(self) -> dict:
        """Every live node's dump_consensus_state — height/round/step,
        per-round vote bitmaps, and peer round views. A round-livelock is
        diagnosable from this alone: who is stuck at which round, holding
        whose votes."""
        from cometbft_tpu.rpc.client import HTTPClient

        out: dict = {}
        for node in self.manifest.nodes:
            port = self.rpc_ports.get(node.name)
            if port is None:
                continue
            try:
                dump = HTTPClient(
                    f"http://127.0.0.1:{port}", timeout=3
                ).dump_consensus_state()
            except Exception as e:
                dump = {"unreachable": repr(e)}
            out[node.name] = dump
        return out

    # -- perturbations (runner/perturb.go) --------------------------------

    def perturb(self, node: ManifestNode, kind: str) -> None:
        name = node.name
        idx = [n.name for n in self.manifest.nodes].index(name)
        proc = self.procs[name]
        self.log(f"perturb {name}: {kind}")
        if kind == "kill" or kind == "restart":
            proc.send_signal(signal.SIGKILL)
            proc.wait()
            time.sleep(1.0)
            self.procs[name] = self._launch(idx)
        elif kind == "backend_faults":
            # Relaunch with a fault-injected supervised verification chain
            # (stays armed for the rest of the run): the heal check below
            # proves the node keeps committing while its primary tier
            # throws injected errors and latency.
            self._fault_armed.add(name)
            proc.send_signal(signal.SIGKILL)
            proc.wait()
            time.sleep(1.0)
            self.procs[name] = self._launch(idx)
        elif kind == "pause":
            proc.send_signal(signal.SIGSTOP)
            time.sleep(3.0)
            proc.send_signal(signal.SIGCONT)
        elif kind == "tx_flood":
            # Relaunch with per-sender rate limiting armed, wait for the
            # node to rejoin, then run the flood: one hostile signer
            # saturating admission while well-behaved signers keep
            # submitting.  QoS holds if the honest txs still commit within
            # bound and the spammer's excess is shed (counter delta).
            self._flood_armed.add(name)
            proc.send_signal(signal.SIGKILL)
            proc.wait()
            time.sleep(1.0)
            self.procs[name] = self._launch(idx)
            h0 = self.wait_height(self.manifest.nodes[0].name, 1)
            self.wait_height(name, h0 + 1, timeout=420)
            self._tx_floods[name] = self._tx_flood(node)
        elif kind == "vote_batch":
            # Relaunch with a widened vote-admission micro-batch window AND
            # the chaos-faulted supervised chain armed (_launch reads
            # _votebatch_armed), then demand the armed validator's precommit
            # lands in a commit minted AFTER the restart: micro-batched
            # admission under injected backend faults must degrade, never
            # drop, valid votes.
            self._votebatch_armed.add(name)
            h0 = self._height(self.manifest.nodes[0].name)
            proc.send_signal(signal.SIGKILL)
            proc.wait()
            time.sleep(1.0)
            self.procs[name] = self._launch(idx)
            self._vote_batches[name] = self._vote_batch_check(name, h0)
        elif kind == "mixed_load":
            # All verification classes at once: relaunch with per-sender
            # rate limiting armed (the tx_flood arming), then drive the
            # hostile-signer flood AND a light-client bisection swarm
            # against the same node CONCURRENTLY.  Ingress preverify,
            # light-client commit verification and the node's own consensus
            # votes now contend for the one engine queue — QoS holds if the
            # flood is shed, every honest tx commits within bound, the
            # swarm agrees, and honest blocks keep landing (heal check
            # below).
            self._flood_armed.add(name)
            proc.send_signal(signal.SIGKILL)
            proc.wait()
            time.sleep(1.0)
            self.procs[name] = self._launch(idx)
            h0 = self.wait_height(self.manifest.nodes[0].name, 1)
            self.wait_height(name, h0 + 1, timeout=420)
            results: dict[str, dict] = {}
            errors: list[BaseException] = []

            def _arm(key: str, fn) -> None:
                try:
                    results[key] = fn(node)
                except BaseException as e:  # re-raised on the main thread
                    errors.append(e)

            flood_t = threading.Thread(
                target=_arm, args=("tx_flood", self._tx_flood)
            )
            swarm_t = threading.Thread(
                target=_arm, args=("light_swarm", self._light_client_swarm)
            )
            flood_t.start()
            swarm_t.start()
            flood_t.join(timeout=600)
            swarm_t.join(timeout=600)
            if errors:
                raise errors[0]
            if flood_t.is_alive() or swarm_t.is_alive():
                raise AssertionError(f"{name}: mixed_load arm never finished")
            self._mixed_loads[name] = results
        elif kind == "recv_flood":
            # No process disruption: the flooded BYTES are the perturbation.
            # Other nodes' mempools gossip a sustained tx stream into the
            # target's recv path; with the old serialized recv loop this is
            # exactly the seeds-2/3/9 stall shape (block parts queued behind
            # tx bytes past timeout_propose).  The prioritized demux must
            # keep consensus committing through the flood.
            self._recv_floods[name] = self._recv_flood(node)
        elif kind == "concurrent_light_clients":
            # No process disruption: the stress IS the perturbation.  N
            # light clients bisect against this node simultaneously; their
            # commit verifications land in the runner-process coalescing
            # scheduler, which must merge them into shared dispatches while
            # every swarm member still converges on the same hash.
            self._light_swarms[name] = self._light_client_swarm(node)
        elif kind == "light_gateway":
            # Cold-sync swarm against the node's MMR proof path: every
            # client starts from a genesis-adjacent trust anchor and syncs
            # to the tip through light_proof instead of bisecting, then the
            # result hash must agree with a plain local bisection.  No
            # process disruption here either.
            self._light_gateways[name] = self._light_gateway_swarm(node)
        elif kind == "bundle_cold_sync":
            # Export a checkpoint bundle from the live node, KILL the node,
            # cold-sync a swarm from the static flat-dir artifact with zero
            # origin interactivity, then relaunch — the heal check proves
            # the origin was never needed during the syncs.
            self._bundle_syncs[name] = self._bundle_cold_sync(node, idx)
        elif kind == "disconnect":
            pid = proc.pid
            t_end = time.time() + 4.0
            while time.time() < t_end:
                out = subprocess.run(
                    ["ss", "-tnp", "state", "established"],
                    capture_output=True, text=True,
                ).stdout
                for line in out.splitlines():
                    if f"pid={pid}," not in line:
                        continue
                    m = re.search(
                        r"(\d+\.\d+\.\d+\.\d+):(\d+)\s+"
                        r"(\d+\.\d+\.\d+\.\d+):(\d+)", line)
                    if not m:
                        continue
                    lip, lport, rip, rport = m.groups()
                    if int(lport) == self.rpc_ports[name] or \
                       int(rport) == self.rpc_ports[name]:
                        continue
                    subprocess.run(
                        ["ss", "-K", "src", lip, "sport", "=", lport,
                         "dst", rip, "dport", "=", rport],
                        capture_output=True,
                    )
                time.sleep(0.2)
        else:
            raise ValueError(kind)
        # After every perturbation the node must make progress again.  The
        # heal window is generous: a stall grows consensus round timeouts
        # (the reference's per-round timeout deltas), so the first
        # post-heal commit can take minutes after a partition.  Node 0 (a
        # genesis validator by the ordering contract) is the reference.
        h = self.wait_height(self.manifest.nodes[0].name, 1)
        self.wait_height(name, h + 1, timeout=420)
        self.log(f"perturb {name}: {kind} healed")

    # -- load (loadtime payloads over RPC) --------------------------------

    def _load_pump(self, stop: threading.Event) -> None:
        from cometbft_tpu.loadtime import make_payload
        from cometbft_tpu.rpc.client import HTTPClient

        rate = max(1, self.manifest.load_tx_rate)
        target = self.manifest.nodes[0].name
        k = 0
        next_t = time.monotonic()
        while not stop.is_set():
            try:
                cli = HTTPClient(
                    f"http://127.0.0.1:{self.rpc_ports[target]}", timeout=3
                )
                tx = make_payload(k, time.time_ns())
                cli.call("broadcast_tx_async", tx="0x" + tx.hex())
                k += 1
            except Exception:
                pass
            next_t += 1.0 / rate
            delay = next_t - time.monotonic()
            if delay > 0:
                time.sleep(delay)

    # -- validator churn (test/e2e persistent_kvstore val: txs) -----------

    def churn_validators(self) -> dict:
        """Add a fresh ed25519 validator (power 1), wait for it to enter the
        set, then vote it back out (power 0).  The extra validator never
        runs a node — with equal powers the running cohort keeps quorum."""
        from cometbft_tpu.crypto import ed25519
        from cometbft_tpu.rpc.client import HTTPClient

        first = self.manifest.nodes[0].name
        cli = HTTPClient(
            f"http://127.0.0.1:{self.rpc_ports[first]}", timeout=5
        )
        pub = ed25519.gen_priv_key().pub_key()
        b64 = base64.b64encode(pub.bytes()).decode()

        def set_size() -> int:
            return len(cli.call("validators")["validators"])

        def tx_and_settle(power: int, want_size: int) -> None:
            """Broadcast the update and poll the validator set until it
            reflects it.  Waiting a fixed two heights is NOT enough: under
            a concurrent tx flood the churn tx can land several blocks
            after broadcast, so a height-anchored query races the update
            (observed as "4 -> 5" when the add activates only after the
            post-add query, between the two reads)."""
            tx = f"val:{b64}!{power}".encode()
            res = cli.call("broadcast_tx_sync", tx="0x" + tx.hex())
            if int(res.get("code", 0)) != 0:
                raise AssertionError(f"churn tx rejected: {res}")
            deadline = time.time() + 60
            n = set_size()
            while n != want_size and time.time() < deadline:
                time.sleep(0.25)
                n = set_size()
            if n != want_size:
                raise AssertionError(
                    f"validator set stuck at {n} (wanted {want_size}) after "
                    f"power={power} update"
                )

        base = set_size()
        self.log(f"churn: adding validator {pub.address().hex()[:12]}…")
        tx_and_settle(1, base + 1)
        self.log("churn: removing it again")
        tx_and_settle(0, base)
        return {"added_then_removed": b64, "set_size": base}

    # -- light client (runner/test.go + light package) --------------------

    def verify_light_client(self, height: int) -> dict:
        """Sequentially verify node 0's chain up to the agreed height with
        the light client — the reference's evidence/light e2e leg."""
        from cometbft_tpu.libs.db import MemDB
        from cometbft_tpu.light.client import Client, TrustOptions
        from cometbft_tpu.light.provider import HTTPProvider
        from cometbft_tpu.light.store import LightStore
        from cometbft_tpu.rpc.client import HTTPClient
        from cometbft_tpu.types import cmttime

        first = self.manifest.nodes[0].name
        url = f"http://127.0.0.1:{self.rpc_ports[first]}"
        blk = HTTPClient(url, timeout=5).block(1)
        trust = TrustOptions(
            period_ns=int(3600 * 10**9),
            height=1,
            hash=bytes.fromhex(blk["block_id"]["hash"]),
        )
        primary = HTTPProvider("e2e-manifest", HTTPClient(url, timeout=5))
        client = Client(
            "e2e-manifest", trust, primary, [], LightStore(MemDB()),
            skip_verification="sequential",
        )
        lb = client.verify_light_block_at_height(height, cmttime.now())
        return {"height": lb.height, "hash": lb.hash().hex().upper()}

    def _coalesce_counters(self) -> dict | None:
        """Runner-process engine counter snapshot (integer counts only).

        None when verification isn't routed through an engine — backend
        not yet built, or an explicit single tier."""
        from cometbft_tpu.sidecar import backend as backend_mod
        from cometbft_tpu.sidecar.engine import engine_of

        eng = engine_of(backend_mod._backend)
        if eng is None:
            return None
        return {k: v for k, v in eng.counters().items() if isinstance(v, int)}

    def _gateway_stats(self, url: str) -> dict | None:
        """The node's light_gateway_stats counters, or None when the
        gateway is disabled on that node (CMTPU_LIGHTGW=0)."""
        from cometbft_tpu.rpc.client import HTTPClient

        try:
            st = HTTPClient(url, timeout=5).call("light_gateway_stats")
        except Exception:
            return None
        if not st.get("enabled"):
            return None
        return {k: v for k, v in st.items() if isinstance(v, (int, float))}

    def _light_client_swarm(self, node: ManifestNode, n_clients: int = 4) -> dict:
        """N skipping-mode light clients bisect against `node` at once.

        The swarm's commit verifications all land in this (runner)
        process's verification backend, so concurrent bisections should
        coalesce into shared dispatches.  When the node serves the light
        gateway the clients sync gateway-assisted (plan mode: the shared
        descent plan is fetched once and re-verified locally by everyone)
        and the node-side gateway counter deltas ride the report.  Every
        member must converge on the same hash; the returned dict carries
        the swarm result plus the engine counter deltas attributable to
        the swarm."""
        from cometbft_tpu.libs.db import MemDB
        from cometbft_tpu.light.client import Client, TrustOptions
        from cometbft_tpu.light.gateway import RemoteGateway
        from cometbft_tpu.light.provider import HTTPProvider
        from cometbft_tpu.light.store import LightStore
        from cometbft_tpu.rpc.client import HTTPClient
        from cometbft_tpu.types import cmttime

        name = node.name
        url = f"http://127.0.0.1:{self.rpc_ports[name]}"
        target = max(2, self._height(name))
        blk = HTTPClient(url, timeout=5).block(1)
        trust = TrustOptions(
            period_ns=int(3600 * 10**9),
            height=1,
            hash=bytes.fromhex(blk["block_id"]["hash"]),
        )
        before = self._coalesce_counters() or {}
        gw_before = self._gateway_stats(url)
        results: list = [None] * n_clients
        barrier = threading.Barrier(n_clients)

        def bisect(i: int) -> None:
            try:
                barrier.wait(timeout=30)
                gateway = None
                if gw_before is not None:
                    gateway = RemoteGateway(HTTPClient(url, timeout=5))
                client = Client(
                    "e2e-manifest", trust,
                    HTTPProvider("e2e-manifest", HTTPClient(url, timeout=5)),
                    [], LightStore(MemDB()),
                    gateway=gateway, gateway_proofs=False,
                )
                lb = client.verify_light_block_at_height(target, cmttime.now())
                results[i] = ("ok", lb.hash().hex().upper(),
                              dict(client.gateway_stats))
            except Exception as exc:  # surfaced by the agreement check
                results[i] = ("error", repr(exc))

        threads = [
            threading.Thread(target=bisect, args=(i,), daemon=True)
            for i in range(n_clients)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=240)
        bad = [r for r in results if r is None or r[0] != "ok"]
        if bad:
            raise AssertionError(f"{name}: light swarm failures: {bad}")
        hashes = {r[1] for r in results}
        if len(hashes) != 1:
            raise AssertionError(
                f"{name}: light swarm hash disagreement: {hashes}"
            )
        out = {"clients": n_clients, "height": target, "hash": hashes.pop()}
        after = self._coalesce_counters()
        if after is not None:
            delta = {k: v - before.get(k, 0) for k, v in after.items()}
            disp = delta.get("dispatches", 0)
            delta["coalesce_ratio"] = (
                round(delta.get("requests", 0) / disp, 3) if disp else 0.0
            )
            out["coalesce"] = delta
        if gw_before is not None:
            gw_after = self._gateway_stats(url) or {}
            out["gateway"] = {
                k: round(v - gw_before.get(k, 0), 3)
                for k, v in gw_after.items()
                if k in ("sessions_total", "plan_hits", "plan_misses",
                         "plan_waits", "prewarmed_sigs")
            }
            out["gateway"]["plan_syncs"] = sum(
                r[2]["plan_syncs"] for r in results
            )
            out["gateway"]["fallbacks"] = sum(
                r[2]["fallbacks"] for r in results
            )
            if out["gateway"]["plan_syncs"] == 0:
                # Hash agreement alone would pass even if every client
                # fell back to a plain bisection — the perturbation exists
                # to exercise the gateway path, so never-took-it fails.
                raise AssertionError(
                    f"{name}: gateway armed but no client synced via the "
                    f"plan path: {out['gateway']}"
                )
        return out

    def _light_gateway_swarm(self, node: ManifestNode, n_clients: int = 4) -> dict:
        """Cold-sync swarm against `node`'s MMR proof path: every client
        trusts height 1 and jumps straight to the tip via light_proof
        (O(log n) accumulator proof + one commit verification), and the
        resulting hash must agree with a plain local bisection run after
        the swarm.  A gateway-disabled node fails loudly — this
        perturbation only appears in manifests that arm the gateway."""
        from cometbft_tpu.libs.db import MemDB
        from cometbft_tpu.light.client import Client, TrustOptions
        from cometbft_tpu.light.gateway import RemoteGateway
        from cometbft_tpu.light.provider import HTTPProvider
        from cometbft_tpu.light.store import LightStore
        from cometbft_tpu.rpc.client import HTTPClient
        from cometbft_tpu.types import cmttime

        name = node.name
        url = f"http://127.0.0.1:{self.rpc_ports[name]}"
        gw_before = self._gateway_stats(url)
        if gw_before is None:
            raise AssertionError(
                f"{name}: light_gateway perturbation but gateway disabled"
            )
        target = max(2, self._height(name))
        blk = HTTPClient(url, timeout=5).block(1)
        trust = TrustOptions(
            period_ns=int(3600 * 10**9),
            height=1,
            hash=bytes.fromhex(blk["block_id"]["hash"]),
        )
        results: list = [None] * n_clients
        barrier = threading.Barrier(n_clients)

        def cold_sync(i: int) -> None:
            try:
                barrier.wait(timeout=30)
                client = Client(
                    "e2e-manifest", trust,
                    HTTPProvider("e2e-manifest", HTTPClient(url, timeout=5)),
                    [], LightStore(MemDB()),
                    gateway=RemoteGateway(HTTPClient(url, timeout=5)),
                    gateway_proofs=True,
                )
                lb = client.verify_light_block_at_height(target, cmttime.now())
                results[i] = ("ok", lb.hash().hex().upper(),
                              dict(client.gateway_stats))
            except Exception as exc:
                results[i] = ("error", repr(exc))

        threads = [
            threading.Thread(target=cold_sync, args=(i,), daemon=True)
            for i in range(n_clients)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=240)
        bad = [r for r in results if r is None or r[0] != "ok"]
        if bad:
            raise AssertionError(f"{name}: gateway cold-sync failures: {bad}")
        hashes = {r[1] for r in results}
        if len(hashes) != 1:
            raise AssertionError(
                f"{name}: gateway cold-sync hash disagreement: {hashes}"
            )
        # Reference arm: the same sync, gateway-less — the MMR shortcut
        # must land on the bit-identical header.
        local = Client(
            "e2e-manifest", trust,
            HTTPProvider("e2e-manifest", HTTPClient(url, timeout=5)),
            [], LightStore(MemDB()),
        )
        local_hash = local.verify_light_block_at_height(
            target, cmttime.now()
        ).hash().hex().upper()
        agreed = hashes.pop()
        if local_hash != agreed:
            raise AssertionError(
                f"{name}: gateway vs local hash mismatch at {target}: "
                f"{agreed} vs {local_hash}"
            )
        gw_after = self._gateway_stats(url) or {}
        out = {
            "clients": n_clients,
            "height": target,
            "hash": agreed,
            "proof_syncs": sum(r[2]["proof_syncs"] for r in results),
            "proof_rejects": sum(r[2]["proof_rejects"] for r in results),
            "fallbacks": sum(r[2]["fallbacks"] for r in results),
            "proof_bytes": sum(r[2]["proof_bytes"] for r in results),
            "gateway": {
                k: round(v - gw_before.get(k, 0), 3)
                for k, v in gw_after.items()
                if k in ("sessions_total", "proofs_served", "proof_bytes",
                         "mmr_size")
            },
        }
        if out["proof_syncs"] == 0:
            raise AssertionError(
                f"{name}: cold-sync swarm never took the proof path: {out}"
            )
        return out

    def _bundle_cold_sync(
        self, node: ManifestNode, idx: int, n_clients: int = 4
    ) -> dict:
        """Static cold sync off a checkpoint bundle: fetch the latest
        bundle over light_bundle while the node is live (plus the trust
        anchor and expected checkpoint hash), write it to a flat directory
        the way `cmd bundle export` would, SIGKILL the node, and have N
        clients sync to the checkpoint from the directory alone — the
        origin is down for the entire swarm, so any interactivity beyond
        the bundle fails loudly.  Every client must take the bundle path
        (no rejects, no fallbacks) and land on the hash the node itself
        reported before it went down.  The node is relaunched afterwards;
        the run's heal check proves it rejoins."""
        from cometbft_tpu.libs.db import MemDB
        from cometbft_tpu.light.bundle import (
            Bundle, DirBundleSource, check_name,
        )
        from cometbft_tpu.light.client import Client, TrustOptions
        from cometbft_tpu.light.provider import HTTPProvider, MockProvider
        from cometbft_tpu.light.store import LightStore
        from cometbft_tpu.rpc.client import HTTPClient
        from cometbft_tpu.types import cmttime

        name = node.name
        url = f"http://127.0.0.1:{self.rpc_ports[name]}"
        rpc = HTTPClient(url, timeout=5)
        # Let the chain cross a few CMTPU_BUNDLE_INTERVAL=2 boundaries.
        self.wait_height(name, 4, timeout=420)
        res = rpc.call("light_bundle")
        if not res.get("enabled"):
            raise AssertionError(
                f"{name}: bundle_cold_sync armed but origin disabled: {res}"
            )
        bname = res["name"]
        data = base64.b64decode(res["bundle"])
        check_name(bname, data)
        boundary = int(res["height"])
        bundle = Bundle.decode(data)
        # Origin counters ride inside light_gateway_stats (peeked — the
        # light_bundle call above already constructed the origin).
        try:
            origin_stats = rpc.call("light_gateway_stats").get("bundle")
        except Exception:
            origin_stats = None
        # Everything a client will need once the node is dead: the trust
        # anchor light block and the node's own claim for the checkpoint.
        live = HTTPProvider("e2e-manifest", rpc)
        lb1 = live.light_block(1)
        trust = TrustOptions(
            period_ns=int(3600 * 10**9), height=1, hash=lb1.hash(),
        )
        expected = rpc.block(boundary)["block_id"]["hash"].upper()
        # The flat-dir artifact exactly as `cmd bundle export` lays it out.
        out_dir = os.path.join(self.home, f"{name}_bundles")
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, f"{bname}.bundle"), "wb") as f:
            f.write(data)
        with open(os.path.join(out_dir, "index.json"), "w") as f:
            json.dump({
                "chain_id": "e2e-manifest",
                "interval": 2,
                "latest": bname,
                "bundles": {str(boundary): bname},
            }, f)
        # Origin goes DOWN.
        proc = self.procs[name]
        proc.send_signal(signal.SIGKILL)
        proc.wait()

        results: list = [None] * n_clients
        barrier = threading.Barrier(n_clients)

        def cold_sync(i: int) -> None:
            try:
                barrier.wait(timeout=30)
                client = Client(
                    "e2e-manifest", trust,
                    MockProvider(
                        "e2e-manifest", {1: lb1, boundary: bundle.anchor}
                    ),
                    [], LightStore(MemDB()),
                    bundle_source=DirBundleSource(out_dir),
                )
                lb = client.verify_light_block_at_height(
                    boundary, cmttime.now()
                )
                results[i] = ("ok", lb.hash().hex().upper(),
                              dict(client.gateway_stats))
            except Exception as exc:
                results[i] = ("error", repr(exc))

        threads = [
            threading.Thread(target=cold_sync, args=(i,), daemon=True)
            for i in range(n_clients)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=240)
        try:
            bad = [r for r in results if r is None or r[0] != "ok"]
            if bad:
                raise AssertionError(
                    f"{name}: bundle cold-sync failures: {bad}"
                )
            hashes = {r[1] for r in results}
            if hashes != {expected}:
                raise AssertionError(
                    f"{name}: bundle cold-sync hash disagreement at "
                    f"{boundary}: {hashes} vs node's {expected}"
                )
            syncs = sum(r[2]["bundle_syncs"] for r in results)
            rejects = sum(r[2]["bundle_rejects"] for r in results)
            if syncs != n_clients or rejects:
                raise AssertionError(
                    f"{name}: swarm did not take the bundle path cleanly: "
                    f"syncs={syncs} rejects={rejects}"
                )
        finally:
            time.sleep(1.0)
            self.procs[name] = self._launch(idx)
        return {
            "clients": n_clients,
            "height": boundary,
            "name": bname,
            "bundle_bytes": len(data),
            "hash": expected,
            "bundle_syncs": syncs,
            "origin": origin_stats,
        }

    def _vote_batch_check(self, name: str, after_height: int) -> dict:
        """Zero-valid-vote-loss probe for the vote_batch perturbation: scan
        commits minted after the restart until one carries the armed
        validator's BLOCK_ID_FLAG_COMMIT signature.  A widened window plus
        injected faults may slow admission (degraded tiers, retries) but a
        single lost valid precommit would show up here as the signature
        never landing.  Non-validator nodes have no precommit to lose —
        recorded and skipped."""
        from cometbft_tpu.rpc.client import HTTPClient
        from cometbft_tpu.types.block import BLOCK_ID_FLAG_COMMIT

        ref = self.manifest.nodes[0].name
        ref_cli = HTTPClient(
            f"http://127.0.0.1:{self.rpc_ports[ref]}", timeout=5
        )
        deadline = time.time() + 300
        val_info: dict = {}
        while time.time() < deadline and not val_info.get("address"):
            try:
                val_info = HTTPClient(
                    f"http://127.0.0.1:{self.rpc_ports[name]}", timeout=5
                ).status()["validator_info"]
            except Exception:
                time.sleep(1.0)
        addr = (val_info.get("address") or "").upper()
        if not addr or int(val_info.get("voting_power", "0") or 0) <= 0:
            self.log(f"vote_batch {name}: not a validator; sig probe skipped")
            return {"validator": False, "signed": False}
        scanned = 0
        probe = after_height + 1
        while time.time() < deadline:
            h = self._height(ref)
            while probe <= h:
                sh = ref_cli.commit(probe).get("signed_header") or {}
                for s in (sh.get("commit") or {}).get("signatures", []):
                    if (
                        (s.get("validator_address") or "").upper() == addr
                        and int(s.get("block_id_flag", 0)) == BLOCK_ID_FLAG_COMMIT
                    ):
                        self.log(
                            f"vote_batch {name}: precommit landed at height "
                            f"{probe} ({scanned} commits scanned)"
                        )
                        return {
                            "validator": True,
                            "signed": True,
                            "height": probe,
                            "commits_scanned": scanned + 1,
                        }
                scanned += 1
                probe += 1
            time.sleep(1.0)
        raise AssertionError(
            f"{name}: no post-restart commit signature within the "
            f"vote_batch window ({scanned} commits after height "
            f"{after_height}) — a valid precommit was lost or the node "
            f"never rejoined"
        )

    def _tx_flood(
        self,
        node: ManifestNode,
        duration_s: float = 6.0,
        honest_senders: int = 3,
        honest_rounds: int = 5,
        commit_bound: int = 10,
    ) -> dict:
        """One hostile signer floods `node` with signed envelopes while
        well-behaved signers submit at a civil rate (>= 10:1 offered-load
        ratio).  Asserts QoS end to end: every honest tx is accepted by
        admission AND committed within `commit_bound` blocks of the flood
        start, while the spammer's excess is rate-limited/shed (non-zero
        ingress shed counter delta on the flooded node)."""
        from cometbft_tpu.crypto import ed25519
        from cometbft_tpu.mempool.ingress import encode_envelope
        from cometbft_tpu.rpc.client import HTTPClient

        name = node.name
        url = f"http://127.0.0.1:{self.rpc_ports[name]}"
        cli = HTTPClient(url, timeout=5)
        before = cli.call("ingress_stats")
        if not before.get("enabled"):
            raise AssertionError(f"{name}: ingress pipeline not enabled")
        seed = max(self.manifest.seed, 0)
        spammer = ed25519.gen_priv_key_from_secret(b"e2e-spam-%d" % seed)
        honest = [
            ed25519.gen_priv_key_from_secret(b"e2e-honest-%d-%d" % (seed, i))
            for i in range(honest_senders)
        ]
        start_h = self._height(name)
        stop = threading.Event()
        spam_sent = [0]

        def spam() -> None:
            scli = HTTPClient(url, timeout=3)
            k = 0
            while not stop.is_set():
                tx = encode_envelope(
                    spammer, b"spam/%d/%d=x" % (seed, k), priority=2, nonce=k
                )
                try:
                    scli.call("broadcast_tx_async", tx="0x" + tx.hex())
                    spam_sent[0] += 1
                except Exception:
                    pass
                k += 1
                time.sleep(0.002)

        spam_thread = threading.Thread(target=spam, daemon=True)
        spam_thread.start()
        honest_txs: list[bytes] = []
        interval = duration_s / (honest_rounds + 1)
        for j in range(honest_rounds):
            time.sleep(interval)
            for i, priv in enumerate(honest):
                tx = encode_envelope(
                    priv, b"honest/%d/%d/%d=x" % (seed, i, j), priority=3, nonce=j
                )
                res = cli.call("broadcast_tx_sync", tx="0x" + tx.hex())
                if int(res.get("code", -1)) != 0:
                    stop.set()
                    raise AssertionError(
                        f"{name}: honest tx rejected during flood: {res}"
                    )
                honest_txs.append(tx)
        time.sleep(interval)
        stop.set()
        spam_thread.join(timeout=5)
        after = cli.call("ingress_stats")
        delta = {
            k: after[k] - before.get(k, 0)
            for k in after
            if isinstance(after.get(k), int) and isinstance(before.get(k, 0), int)
        }
        if delta.get("shed_total", 0) <= 0:
            raise AssertionError(
                f"{name}: flood of {spam_sent[0]} spam txs was never shed: {delta}"
            )
        # Commit-within-bound: scan node 0's chain for every honest tx.
        first = self.manifest.nodes[0].name
        end_h = start_h + commit_bound
        self.wait_height(first, end_h, timeout=420)
        cli0 = HTTPClient(f"http://127.0.0.1:{self.rpc_ports[first]}", timeout=5)
        want = {base64.b64encode(t).decode() for t in honest_txs}
        seen: set[str] = set()
        for h in range(start_h, end_h + 1):
            blk = cli0.block(h)
            if blk.get("block"):
                seen.update(blk["block"]["data"]["txs"] or [])
        missing = want - seen
        if missing:
            raise AssertionError(
                f"{name}: {len(missing)}/{len(want)} honest txs not committed "
                f"within {commit_bound} blocks of the flood"
            )
        return {
            "spam_offered": spam_sent[0],
            "honest_offered": len(honest_txs),
            "honest_committed": len(want),
            "commit_bound_blocks": commit_bound,
            "ingress_delta": delta,
            "lane_depths_after": after.get("lane_depths"),
        }

    def _recv_flood(self, node: ManifestNode, duration_s: float = 6.0) -> dict:
        """Gossip-side recv flood: pump legacy txs into every OTHER node so
        mempool gossip saturates `node`'s inbound p2p connections while
        consensus block parts keep arriving on the same sockets.  Asserts
        the prioritized demux is live on the target (recvq_stats RPC),
        that the chain keeps advancing DURING the flood (the serialized
        recv path's failure mode was zero progress), and that the
        per-class counters show both mempool and consensus traffic was
        delivered through the queues."""
        from cometbft_tpu.loadtime import make_payload
        from cometbft_tpu.rpc.client import HTTPClient

        name = node.name
        cli = HTTPClient(f"http://127.0.0.1:{self.rpc_ports[name]}", timeout=5)
        before = cli.call("recvq_stats")
        if not before.get("enabled"):
            raise AssertionError(f"{name}: recv demux not enabled")
        others = [n.name for n in self.manifest.nodes if n.name != name] or [name]
        start_h = self._height(name)
        stop = threading.Event()
        offered = [0]

        def flood(target: str) -> None:
            fcli = HTTPClient(
                f"http://127.0.0.1:{self.rpc_ports[target]}", timeout=3
            )
            k = 0
            while not stop.is_set():
                tx = make_payload(k, time.time_ns())
                try:
                    fcli.call("broadcast_tx_async", tx="0x" + tx.hex())
                    offered[0] += 1
                except Exception:
                    pass
                k += 1
                time.sleep(0.002)

        threads = [
            threading.Thread(target=flood, args=(t,), daemon=True)
            for t in others
        ]
        for t in threads:
            t.start()
        time.sleep(duration_s)
        stop.set()
        for t in threads:
            t.join(timeout=5)
        end_h = self._height(name)
        after = cli.call("recvq_stats")
        delta = {
            k: after[k] - before.get(k, 0)
            for k in after
            if isinstance(after.get(k), int) and isinstance(before.get(k, 0), int)
        }
        if end_h <= start_h:
            raise AssertionError(
                f"{name}: no commit during a {duration_s}s recv flood "
                f"({offered[0]} txs offered) — consensus bytes starved"
            )
        if delta.get("mempool_delivered", 0) <= 0:
            raise AssertionError(
                f"{name}: flood never reached the recv demux: {delta}"
            )
        if delta.get("consensus_delivered", 0) <= 0:
            raise AssertionError(
                f"{name}: no consensus traffic through the demux during "
                f"the flood: {delta}"
            )
        return {
            "flood_offered": offered[0],
            "flood_senders": len(others),
            "blocks_during_flood": end_h - start_h,
            "recvq_delta": delta,
            "max_delay_us_after": after.get("max_delay_us", 0),
            "promoted_during": delta.get("promoted_total", 0),
        }

    # -- the run ----------------------------------------------------------

    def _run_sim(self) -> dict:
        """network = "sim": one in-process virtual-clock scenario instead of
        OS processes. The scenario enforces the same core invariants the
        real runner does (target height + hash agreement); its resolved
        schedule is kept for the repro artifact."""
        from cometbft_tpu.simnet.scenario import run_scenario

        sim = self.manifest.sim
        self.log(
            f"simnet: {sim['validators']} validators, "
            f"{sim['blocks']} blocks, seed {sim['seed']}, "
            f"{len(sim['partitions'])} partitions, {len(sim['churn'])} churns"
        )
        report = run_scenario(dict(sim))
        self.sim_schedule = report.get("schedule")
        if not report.get("hash_agreement", True):
            raise AssertionError(
                f"simnet hash disagreement at height {report['agreed_height']}"
            )
        if not report.get("safety_ok", True):
            raise AssertionError(
                "simnet SAFETY VIOLATION: conflicting honest commits at "
                f"heights {report['conflicting_heights']}"
            )
        if not report["ok"]:
            # Height never reached: the stall signature (run_matrix maps
            # TimeoutError to `stalled`, same as a wall-clock wait_height).
            raise TimeoutError(
                f"simnet: height {sim['blocks'] + 1} not reached "
                f"(node0 at {report['height_node0']} after "
                f"{report['sim_time_s']} sim-s)"
            )
        self.log(
            f"simnet: height {report['height_node0']} in "
            f"{report['sim_time_s']} sim-s / {report['wall_time_s']} wall-s "
            f"({report['accel']}x), {report['events']} events"
        )
        return {
            "network": "sim",
            "nodes": report["validators"],
            "final_heights": {
                "min": report["heights_min"], "max": report["heights_max"]
            },
            **{
                k: report[k]
                for k in (
                    "seed", "agreed_height", "agreed_hash", "stragglers",
                    "sim_time_s", "wall_time_s", "accel", "events",
                    "counters", "block_hashes", "safety_ok", "evidence",
                    "recovery", "joins",
                )
            },
        }

    def run(self) -> dict:
        if self.manifest.network == "sim":
            return self._run_sim()
        self.setup()
        self.start()
        stop = threading.Event()
        pump = threading.Thread(target=self._load_pump, args=(stop,), daemon=True)
        try:
            first = self.manifest.nodes[0].name
            h0 = self.wait_height(first, self.manifest.initial_height + 2)
            pump.start()
            churn_report = None
            if self.manifest.validator_churn:
                churn_report = self.churn_validators()
            # Second start wave, in join order (runner/start.go sorts by
            # start_at the same way).
            late = sorted(
                (
                    (i, n)
                    for i, n in enumerate(self.manifest.nodes)
                    if n.start_at > 0
                ),
                key=lambda t: t[1].start_at,
            )
            for i, node in late:
                self._launch_late(i, node)
            for node in self.manifest.nodes:
                for kind in node.perturb:
                    self.perturb(node, kind)
            target = max(
                h0 + self.manifest.target_blocks,
                max((n.start_at for n in self.manifest.nodes), default=0) + 2,
            )
            heights = {
                n.name: self.wait_height(n.name, target, timeout=420)
                for n in self.manifest.nodes
            }
            # hash agreement at a common committed height (runner/test.go)
            from cometbft_tpu.rpc.client import HTTPClient

            common = min(heights.values())
            hashes = {
                n.name: HTTPClient(
                    f"http://127.0.0.1:{self.rpc_ports[n.name]}", timeout=5
                ).block(common)["block_id"]["hash"]
                for n in self.manifest.nodes
            }
            if len(set(hashes.values())) != 1:
                raise AssertionError(f"hash disagreement at {common}: {hashes}")
            light_report = None
            if self.manifest.light_client:
                light_report = self.verify_light_client(common)
                if light_report["hash"].lower() != \
                        next(iter(hashes.values())).lower():
                    raise AssertionError(
                        f"light client hash mismatch at {common}: "
                        f"{light_report['hash']} vs {hashes}"
                    )
            report = {
                "nodes": len(self.manifest.nodes),
                "perturbations": sum(len(n.perturb) for n in self.manifest.nodes),
                "late_joins": len(late),
                "backend": self.manifest.backend,
                "app": self.manifest.app,
                "final_heights": heights,
                "agreed_height": common,
                "agreed_hash": next(iter(hashes.values())),
            }
            if self._fault_armed:
                report["backend_faults"] = sorted(self._fault_armed)
            if self._light_swarms:
                report["concurrent_light_clients"] = self._light_swarms
            if self._light_gateways:
                report["light_gateway"] = self._light_gateways
            if self._tx_floods:
                report["tx_flood"] = self._tx_floods
            if self._vote_batches:
                report["vote_batch"] = self._vote_batches
            if self._mixed_loads:
                report["mixed_load"] = self._mixed_loads
            if self._recv_floods:
                report["recv_flood"] = self._recv_floods
            if self._bundle_syncs:
                report["bundle_cold_sync"] = self._bundle_syncs
            if churn_report is not None:
                report["validator_churn"] = churn_report
            if light_report is not None:
                report["light_client"] = light_report
            self.log(json.dumps(report))
            return report
        finally:
            stop.set()
            for proc in list(self.procs.values()) + list(self.app_procs.values()):
                if proc.poll() is None:
                    proc.send_signal(signal.SIGKILL)
                    proc.wait()
            for f in self._log_files:
                try:
                    f.close()
                except OSError:
                    pass

    def node_logs(self) -> dict[str, str]:
        """Per-node log paths (repro artifacts reference these)."""
        out = {}
        for i, node in enumerate(self.manifest.nodes):
            for suffix in ("node", "app"):
                p = os.path.join(self.home, f"node{i}", f"{suffix}.log")
                if os.path.exists(p):
                    out[f"{node.name}.{suffix}"] = p
        return out
