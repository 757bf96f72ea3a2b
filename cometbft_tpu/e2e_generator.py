"""Seeded randomized-testnet generator (reference: test/e2e/generator).

The reference's generator turns one RNG seed into a valid runner manifest,
sampling the testnet dimensions the e2e harness can exercise — topology,
sync modes, ABCI boundaries, key types, perturbations — under the
constraints that keep the result runnable (quorum at genesis, snapshot
sources for statesync, a stable node-0 reference).  ``generate(seed)`` here
is that: a pure function from an integer seed to TOML text, byte-identical
across runs, loadable by :class:`cometbft_tpu.e2e_runner.Manifest`.

Profiles mirror the reference's groups:

* ``full`` — the whole sampling space: up to 6 validators plus full/seed
  nodes, mixed consensus key types, socket/grpc ABCI boundaries, late
  joins via blocksync or verified statesync, validator churn, hybrid
  backend, any perturbation — including ``backend_faults``, which
  restarts a node with a chaos-injected supervised verification chain
  (CMTPU_FAULTS, sidecar/chaos.py) and demands it keeps committing, and
  ``vote_batch``, which restarts a node with a widened vote-admission
  micro-batch window (CMTPU_VOTE_BATCH_WINDOW_MS) on top of that faulted
  chain and demands the validator's precommit lands in a fresh commit —
  batching under faults must degrade, never drop, valid votes.
* ``small`` — the CI-sized corner (≤4 validators, ≤6 target blocks, ≤1
  perturbation, ed25519 only, cpu backend): what ``e2e matrix`` smokes in
  the test tier.

``run_matrix(seeds, out_dir)`` sweeps seeds through the runner
(generator.go's Makefile loop + runner invocation).  Every run gets its
own directory; a failure freezes the evidence as ``repro.json`` — seed,
manifest text, error, per-node log tails — so one file reproduces the
testnet that broke.
"""

from __future__ import annotations

import json
import os
import random
import traceback

from cometbft_tpu.e2e_runner import Manifest

PROFILES = ("full", "small", "sim")

# Weighted sampling tables (generator/generate.go's uniformChoice /
# weightedChoice analogs).  Non-ed25519 verification is pure Python here —
# heavy key types stay out of the small profile so the CI tier keeps its
# 0.2s commit cadence.
_KEY_TYPES_FULL = (
    ("ed25519",) * 11 + ("secp256k1",) * 3 + ("sr25519",) * 3 + ("bn254",) * 3
)
_ABCI_FULL = ("local",) * 5 + ("socket",) * 3 + ("grpc",) * 2
_ABCI_SMALL = ("local",) * 7 + ("socket",) * 3
_PERTURB_FULL = (
    "kill", "pause", "disconnect", "restart", "backend_faults",
    "concurrent_light_clients", "tx_flood", "vote_batch",
    "light_gateway", "mixed_load", "recv_flood", "bundle_cold_sync",
)
# _PERTURB_SMALL is FROZEN: the matrix regression suite pins small-profile
# seeds by number (the round-15 stall forensics and the round-18 un-pinned
# seeds 2/3/9), and any change here reshuffles every seed's draw sequence,
# silently swapping which manifests those seed numbers denote.  New
# perturbations go in _PERTURB_FULL only.
_PERTURB_SMALL = ("pause", "restart", "backend_faults", "tx_flood")


def generate(seed: int, profile: str = "full") -> str:
    """One integer seed -> one deterministic, runnable TOML manifest."""
    spec = generate_spec(seed, profile)
    text = render_toml(spec)
    return text


def generate_spec(seed: int, profile: str = "full") -> dict:
    """The structured form of the sampled manifest (render_toml emits it).

    Everything flows from ``random.Random(seed)`` — no clocks, no global
    RNG — so the same (seed, profile) always yields the same testnet.
    """
    if profile not in PROFILES:
        raise ValueError(f"unknown profile {profile!r} (want one of {PROFILES})")
    rng = random.Random(f"{profile}:{seed}")
    if profile == "sim":
        return _generate_sim_spec(seed, rng)
    small = profile == "small"

    n_validators = rng.choice((2, 3, 4) if small else (2, 3, 4, 4, 5, 6))
    n_full = rng.choice((0, 1) if small else (0, 0, 1, 2))
    with_seed_node = (not small) and rng.random() < 0.2

    # Late-join validators must leave > 2/3 of the (equal-power) genesis
    # set online; (V-1)//3 is the largest count that keeps 3*(V-L) > 2*V.
    late_validators = 0
    if not small and (n_validators - 1) // 3 > 0 and rng.random() < 0.3:
        late_validators = 1

    backend = "cpu" if small else rng.choice(("cpu",) * 3 + ("hybrid",))
    validator_churn = rng.random() < (0.2 if small else 0.25)
    if validator_churn:
        app = "persistent_kvstore"
    else:
        app = rng.choice(("kvstore", "kvstore", "persistent_kvstore"))
    light_client = rng.random() < 0.3
    load_tx_rate = rng.choice((10, 25) if small else (10, 25, 50, 100))
    target_blocks = rng.randint(4, 6) if small else rng.randint(8, 16)

    nodes: list[dict] = []
    abci_table = _ABCI_SMALL if small else _ABCI_FULL
    for i in range(n_validators):
        nodes.append({
            "name": f"validator{i + 1:02d}",
            "mode": "validator",
            "key_type": "ed25519" if small else rng.choice(_KEY_TYPES_FULL),
            "start_at": 0,
            "state_sync": False,
            "abci": rng.choice(abci_table),
            "perturb": [],
        })
    for i in range(n_full):
        late = rng.random() < 0.5
        nodes.append({
            "name": f"full{i + 1:02d}",
            "mode": "full",
            "key_type": "ed25519",
            "start_at": rng.choice((2, 3) if small else (3, 5, 8)) if late else 0,
            "state_sync": False,
            "abci": rng.choice(abci_table),
            "perturb": [],
        })
    if with_seed_node:
        nodes.append({
            "name": "seed01",
            "mode": "seed",
            "key_type": "ed25519",
            "start_at": 0,
            "state_sync": False,
            "abci": "local",
            "perturb": [],
        })

    # Late validators come last among the validators -> node 0 stays a
    # genesis validator (the runner's height/load/trust reference).
    for node in reversed(nodes):
        if late_validators and node["mode"] == "validator":
            node["start_at"] = rng.choice((3, 5))
            late_validators -= 1
            break

    # Statesync only where a snapshot source exists; full profile only.
    snapshot_interval = 0
    if not small:
        late_nodes = [n for n in nodes if n["start_at"] > 0]
        wants_sync = [n for n in late_nodes if rng.random() < 0.5]
        if wants_sync:
            snapshot_interval = rng.choice((2, 3, 4))
            for n in wants_sync:
                n["state_sync"] = True
        elif rng.random() < 0.3:
            snapshot_interval = 3  # snapshots taken, nobody restores: still valid

    # Perturbations never hit node 0 (the heal check's reference) and the
    # small profile keeps at most one in total.
    budget = 1 if small else 3
    table = _PERTURB_SMALL if small else _PERTURB_FULL
    for node in nodes[1:]:
        if budget <= 0:
            break
        if rng.random() < 0.4:
            count = 1 if small else rng.choice((1, 1, 2))
            count = min(count, budget)
            node["perturb"] = [rng.choice(table) for _ in range(count)]
            budget -= count

    # Leave headroom past the last join; the small bump keeps target_blocks
    # within the profile's ≤6-block ceiling (late starts there are ≤3).
    max_start = max((n["start_at"] for n in nodes), default=0)
    target_blocks = max(target_blocks, max_start + (2 if small else 4))

    return {
        "seed": seed,
        "profile": profile,
        "initial_height": 1,
        "load_tx_rate": load_tx_rate,
        "target_blocks": target_blocks,
        "backend": backend,
        "app": app,
        "snapshot_interval": snapshot_interval,
        "validator_churn": validator_churn,
        "light_client": light_client,
        "nodes": nodes,
    }


def _generate_sim_spec(seed: int, rng: random.Random) -> dict:
    """The ``sim`` profile: one 50–200 node virtual-clock scenario.

    Samples the WAN shape (zones, jitter, drop), one quorum-breaking
    partition + heal, and optional churn.  The zone latency matrix itself
    is synthesized inside the scenario from the same seed, so the manifest
    stays small while the resolved schedule still lands in repro.json.
    """
    validators = rng.choice((50, 50, 75, 100, 100, 150, 200))
    blocks = rng.randint(6, 10)
    part_at = round(rng.uniform(15.0, 35.0), 1)
    sim = {
        "seed": seed,
        "validators": validators,
        "blocks": blocks,
        "zones": rng.randint(2, 6),
        "jitter_ms": round(rng.uniform(5.0, 25.0), 1),
        "drop_p": rng.choice((0.0, 0.0, round(rng.uniform(0.002, 0.02), 4))),
        "vote_window_ms": rng.choice((0.0, 25.0, 50.0)),
        "max_sim_s": float(blocks * 40 + 120),
        "partitions": [{
            "at_s": part_at,
            "heal_s": round(part_at + rng.uniform(10.0, 30.0), 1),
            "fraction": 0.5,
        }],
        "churn": (
            [{
                "at_s": round(rng.uniform(10.0, 30.0), 1),
                "down_s": round(rng.uniform(10.0, 25.0), 1),
                "nodes": rng.randint(1, max(1, validators // 10)),
            }]
            if rng.random() < 0.4
            else []
        ),
    }
    # Byzantine window (round 19): about half the seeds run one adversary.
    # Equivocators are biased toward the partition window with
    # only_partitioned set — the accountability path (heal -> vote-knowledge
    # merge -> DuplicateVoteEvidence committed) is the property under test.
    if rng.random() < 0.5:
        role = rng.choice(("equivocator", "equivocator", "withholder", "flooder"))
        entry = {
            "role": role,
            "node": rng.randint(1, validators - 1),
            "from_s": round(rng.uniform(5.0, part_at), 1),
            "until_s": round(sim["partitions"][0]["heal_s"]
                             + rng.uniform(5.0, 20.0), 1),
        }
        if role == "equivocator":
            entry["only_partitioned"] = rng.random() < 0.5
        sim["byzantine"] = [entry]
    # Occasional in-sim blocksync late-join, never colliding with the
    # adversary (a byzantine joiner is rejected by the scenario).
    if rng.random() < 0.3:
        taken = {e["node"] for e in sim.get("byzantine", [])}
        candidates = [i for i in range(1, validators) if i not in taken]
        sim["joins"] = [{
            "node": rng.choice(candidates),
            "at_s": round(rng.uniform(30.0, 60.0), 1),
        }]
    return {"seed": seed, "profile": "sim", "network": "sim", "sim": sim}


def render_toml(spec: dict) -> str:
    """Stable TOML rendering: fixed key order, no timestamps — the
    determinism contract is byte-identical output per (seed, profile)."""
    if spec.get("network") == "sim":
        return _render_sim_toml(spec)
    lines = [
        "# Randomized e2e testnet manifest "
        f"(seed {spec['seed']}, profile {spec['profile']}).",
        "# Regenerate: python -m cometbft_tpu.cmd e2e generate "
        f"--seed {spec['seed']} --profile {spec['profile']}",
        "",
        f"seed = {spec['seed']}",
        f"initial_height = {spec['initial_height']}",
        f"load_tx_rate = {spec['load_tx_rate']}",
        f"target_blocks = {spec['target_blocks']}",
        f'backend = "{spec["backend"]}"',
        f'app = "{spec["app"]}"',
        f"snapshot_interval = {spec['snapshot_interval']}",
        f"validator_churn = {_toml_bool(spec['validator_churn'])}",
        f"light_client = {_toml_bool(spec['light_client'])}",
    ]
    for node in spec["nodes"]:
        lines.append("")
        lines.append(f"[node.{node['name']}]")
        if node["mode"] != "validator":
            lines.append(f'mode = "{node["mode"]}"')
        if node["key_type"] != "ed25519":
            lines.append(f'key_type = "{node["key_type"]}"')
        if node["start_at"]:
            lines.append(f"start_at = {node['start_at']}")
        if node["state_sync"]:
            lines.append("state_sync = true")
        if node["abci"] != "local":
            lines.append(f'abci = "{node["abci"]}"')
        if node["perturb"]:
            quoted = ", ".join(f'"{p}"' for p in node["perturb"])
            lines.append(f"perturb = [{quoted}]")
    return "\n".join(lines) + "\n"


def _render_sim_toml(spec: dict) -> str:
    """network = "sim" manifests: scalars + flat parallel arrays only (the
    partition/churn schedules are unzipped — the repo's TOML subset has no
    inline tables; Manifest._load_sim zips them back)."""
    sim = spec["sim"]
    lines = [
        "# Randomized simnet scenario manifest "
        f"(seed {spec['seed']}, profile sim).",
        "# Regenerate: python -m cometbft_tpu.cmd e2e generate "
        f"--seed {spec['seed']} --profile sim",
        "",
        f"seed = {spec['seed']}",
        'network = "sim"',
        "",
        "[sim]",
        f"seed = {sim['seed']}",
        f"validators = {sim['validators']}",
        f"blocks = {sim['blocks']}",
        f"zones = {sim['zones']}",
        f"jitter_ms = {sim['jitter_ms']}",
        f"drop_p = {sim['drop_p']}",
        f"vote_window_ms = {sim['vote_window_ms']}",
        f"max_sim_s = {sim['max_sim_s']}",
    ]
    parts = sim.get("partitions", [])
    if parts:
        lines.append(
            "partition_at_s = [" + ", ".join(str(p["at_s"]) for p in parts) + "]"
        )
        lines.append(
            "partition_heal_s = ["
            + ", ".join(str(p["heal_s"]) for p in parts) + "]"
        )
        lines.append(
            "partition_fraction = ["
            + ", ".join(str(p["fraction"]) for p in parts) + "]"
        )
    churn = sim.get("churn", [])
    if churn:
        lines.append(
            "churn_at_s = [" + ", ".join(str(c["at_s"]) for c in churn) + "]"
        )
        lines.append(
            "churn_down_s = [" + ", ".join(str(c["down_s"]) for c in churn) + "]"
        )
        lines.append(
            "churn_nodes = [" + ", ".join(str(c["nodes"]) for c in churn) + "]"
        )
    byz = sim.get("byzantine", [])
    if byz:
        lines.append(
            "byz_role = [" + ", ".join(f'"{b["role"]}"' for b in byz) + "]"
        )
        lines.append(
            "byz_node = [" + ", ".join(str(b["node"]) for b in byz) + "]"
        )
        lines.append(
            "byz_from_s = [" + ", ".join(str(b["from_s"]) for b in byz) + "]"
        )
        lines.append(
            "byz_until_s = [" + ", ".join(str(b["until_s"]) for b in byz) + "]"
        )
        lines.append(
            "byz_only_partitioned = ["
            + ", ".join(
                _toml_bool(bool(b.get("only_partitioned", False))) for b in byz
            )
            + "]"
        )
    joins = sim.get("joins", [])
    if joins:
        lines.append(
            "join_node = [" + ", ".join(str(j["node"]) for j in joins) + "]"
        )
        lines.append(
            "join_at_s = [" + ", ".join(str(j["at_s"]) for j in joins) + "]"
        )
    return "\n".join(lines) + "\n"


def _toml_bool(b: bool) -> str:
    return "true" if b else "false"


def run_matrix(
    seeds,
    out_dir: str,
    profile: str = "small",
    runner_cls=None,
    log=print,
) -> dict:
    """Sweep seeds through the runner (the reference generator's CI loop).

    Per seed: ``<out_dir>/seed<N>/manifest.toml`` + ``net/`` homes.  Hash
    agreement (and every other invariant the runner enforces) failing
    freezes ``repro.json`` alongside — seed, frozen manifest, error, and
    per-node log tails — the whole repro in one artifact.
    """
    if runner_cls is None:
        from cometbft_tpu.e2e_runner import E2ERunner as runner_cls  # noqa: N813

    results: dict[int, dict] = {}
    for seed in seeds:
        sdir = os.path.join(out_dir, f"seed{seed}")
        os.makedirs(sdir, exist_ok=True)
        text = generate(seed, profile)
        manifest_path = os.path.join(sdir, "manifest.toml")
        with open(manifest_path, "w") as f:
            f.write(text)
        # The generator's own output must satisfy the runner's schema —
        # fail loudly here, not three minutes into a testnet.
        Manifest.load(manifest_path)
        log(f"matrix seed {seed}: starting")
        runner = runner_cls(manifest_path, os.path.join(sdir, "net"), log=log)
        try:
            report = runner.run()
        except Exception as e:
            repro_path = _write_repro(sdir, seed, profile, text, e, runner)
            # A wait_height deadline (TimeoutError) is the stall signature —
            # height stopped advancing, i.e. a consensus livelock or a dead
            # node — distinct from invariant failures (hash disagreement...).
            stalled = isinstance(e, TimeoutError)
            log(f"matrix seed {seed}: FAILED ({e!r}); repro at {repro_path}")
            results[seed] = {
                "ok": False,
                "stalled": stalled,
                "error": repr(e),
                "repro": repro_path,
            }
        else:
            results[seed] = {"ok": True, "report": report}
            log(f"matrix seed {seed}: ok at height {report['agreed_height']}")
    passed = sorted(s for s, r in results.items() if r["ok"])
    failed = sorted(s for s, r in results.items() if not r["ok"])
    stalled = sorted(s for s, r in results.items() if r.get("stalled"))
    # One grep-able line per sweep: per-seed verdicts.
    verdicts = " ".join(
        f"seed{s}:" + (
            "ok" if results[s]["ok"]
            else ("stall" if results[s].get("stalled") else "fail")
        )
        for s in sorted(results)
    )
    log(
        f"e2e matrix summary [{profile}]: {len(passed)}/{len(results)} passed,"
        f" {len(stalled)} stalled | {verdicts}"
    )
    return {
        "profile": profile,
        "passed": passed,
        "failed": failed,
        "stalled": stalled,
        "results": {str(s): r for s, r in results.items()},
    }


def _write_repro(sdir, seed, profile, manifest_text, exc, runner) -> str:
    """Freeze everything needed to replay a failing seed into one JSON."""
    logs = {}
    try:
        for name, path in runner.node_logs().items():
            logs[name] = {"path": path, "tail": _tail(path)}
    except Exception:
        pass  # a half-constructed runner must not mask the real failure
    repro = {
        "seed": seed,
        "profile": profile,
        "regenerate": (
            f"python -m cometbft_tpu.cmd e2e generate --seed {seed} "
            f"--profile {profile}"
        ),
        "manifest": manifest_text,
        "error": repr(exc),
        "traceback": traceback.format_exc(),
        "node_logs": logs,
        # Per-node consensus round-state at the moment the stall was
        # detected (None for non-stall failures): height/round/step,
        # per-round vote bitmaps, peer round views.
        "round_states": getattr(runner, "last_round_states", None),
        # network = "sim": the scenario's full resolved schedule (latency
        # matrix, partition/churn timeline, seeds) — this artifact alone
        # replays the failing run bit-identically.
        "sim_schedule": getattr(runner, "sim_schedule", None),
    }
    path = os.path.join(sdir, "repro.json")
    with open(path, "w") as f:
        json.dump(repro, f, indent=2)
    return path


def _tail(path: str, max_bytes: int = 8192) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            size = f.tell()
            f.seek(max(0, size - max_bytes))
            return f.read().decode("utf-8", "replace")
    except OSError:
        return ""
