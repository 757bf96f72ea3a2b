#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the verification path still
starts on the chip.

    python chip_smoke.py            # on the chip's machine (via the chip tool)

Drives the path a node ships — `types/validation` -> `crypto.batch` ->
`sidecar.backend.get_backend()` — at BASELINE.json's own widths (a
10,240-validator commit, a 65,536-leaf block tree, one 32 x 1,024 blocksync
window), and checks every answer against the repo's plain references:
scalar ZIP-215 `crypto/ed25519_pure.verify_zip215` and the pure-Python
RFC-6962 tree `hash_from_byte_slices_iterative`.

The parent process is an orchestrator that never imports JAX. It runs three
phases as child processes, one after another, so exactly one process holds
the chip at any time:

  device   CMTPU_BACKEND=tpu, bare — nothing to fall back on
  sidecar  `python -m cometbft_tpu.sidecar` with its default device choice;
           the parent is the client (CMTPU_BACKEND=grpc)
  node     CMTPU_BACKEND=auto: the full chain (scheduler -> engine ->
           supervisor -> hybrid), then `cmd devnet --backend auto`

Any failing phase fails the run: no result line is printed and the exit
code is non-zero. A child that was to use the chip and finds another
platform exits before compiling anything, so on a machine without one the
whole command fails within seconds. On success the last line of stdout is
one JSON object with exactly these keys,
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`, and
the line before it is `SUMMARY {..., "claim": null}`, the run's record.
Numbers printed on the way are facts about this one run, not measurements.

`--platform cpu` with the small sizes is for the repo's own slow test on a
machine without a chip; its result line says `cpu`.
"""

from __future__ import annotations

import argparse
import base64
import contextlib
import dataclasses
import functools
import io
import json
import os
import queue
import random
import re
import signal
import socket
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CHAIN_ID = "chip-smoke"
# The run must end inside the chip check's 1200 s; phases are cut to fit.
TIME_LIMIT_S = 1140
# Set aside for the sidecar and node phases when the device phase decides
# whether the 32,768-lane window still fits.
LATER_PHASES_S = 300


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def require(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(msg: str, **fields) -> None:
    """One machine-readable line from a child to the parent."""
    print("SMOKE " + json.dumps({"msg": msg, **fields}, default=str), flush=True)


def child_step(label: str) -> None:
    """Names what a child does next, so the parent can say which step a
    compile fell in."""
    emit("step", label=label)


def say(msg: str) -> None:
    print(msg, flush=True)


# -- seeded fixtures, built on the host -----------------------------------------


@functools.lru_cache(maxsize=2)
def make_commits(seed: int, n_vals: int, heights: int, tag: str):
    """A seeded ValidatorSet and one fully signed Commit per height, shaped
    as the shipped path sees them. Returns (vals, [(block_id, commit)])."""
    from cometbft_tpu.crypto import ed25519
    from cometbft_tpu.types import BlockID, Commit, Time, Vote
    from cometbft_tpu.types.block import PRECOMMIT_TYPE
    from cometbft_tpu.types.part_set import PartSetHeader
    from cometbft_tpu.types.priv_validator import MockPV
    from cometbft_tpu.types.validator import Validator
    from cometbft_tpu.types.validator_set import ValidatorSet
    from cometbft_tpu.types.vote import vote_to_commit_sig

    pvs = [
        MockPV(ed25519.gen_priv_key_from_secret(b"%d/%s/%d" % (seed, tag.encode(), i)))
        for i in range(n_vals)
    ]
    vals = ValidatorSet([Validator.new(pv.get_pub_key(), 10) for pv in pvs])
    pv_by_addr = {pv.address(): pv for pv in pvs}
    out = []
    for h in range(1, heights + 1):
        bid = BlockID(h.to_bytes(8, "big") * 4, PartSetHeader(1, b"\x02" * 32))
        sigs = []
        for idx, v in enumerate(vals.validators):
            vote = Vote(
                type=PRECOMMIT_TYPE, height=h, round=0, block_id=bid,
                timestamp=Time(1700000000 + h, 0),
                validator_address=v.address, validator_index=idx,
            )
            sigs.append(
                vote_to_commit_sig(pv_by_addr[v.address].sign_vote(CHAIN_ID, vote))
            )
        out.append((bid, Commit(height=h, round=0, block_id=bid, signatures=sigs)))
    return vals, out


def flip_signatures(commit, indices):
    """The same commit with one bit of each given signature flipped."""
    from cometbft_tpu.types import Commit

    sigs = list(commit.signatures)
    for i in indices:
        s = sigs[i].signature
        sigs[i] = dataclasses.replace(
            sigs[i], signature=s[:7] + bytes([s[7] ^ 0x10]) + s[8:]
        )
    return Commit(
        height=commit.height, round=commit.round,
        block_id=commit.block_id, signatures=sigs,
    )


def make_leaves(seed: int, n: int) -> list[bytes]:
    rng = random.Random(f"{seed}/leaves/{n}")
    return [rng.randbytes(32) for _ in range(n)]


def clear_verified_cache() -> None:
    """Without this the second verification of a triple is a dict lookup."""
    from cometbft_tpu.crypto import ed25519

    with ed25519._verified_lock:
        ed25519._verified.clear()


# -- the comparisons, shared by all three phases --------------------------------


def check_commit(args, label: str, step, *, light: bool) -> dict:
    """The commit checks every phase runs through whatever backend
    get_backend() resolved: the commit is accepted; with 3 signatures
    flipped it is rejected and BatchVerifier.verify()'s bitmap is false at
    exactly those lanes; the ZIP-215 edge vectors ride the same dispatch;
    and a seeded sample of at least 64 lanes equals scalar verify_zip215."""
    from cometbft_tpu.crypto import ed25519, ed25519_pure
    from cometbft_tpu.types import validation

    n = args.validators
    vals, [(bid, commit)] = make_commits(args.seed, n, 1, "commit")
    rng = random.Random(f"{args.seed}/flip")
    # Within the first 2/3 of the set, so VerifyCommitLight (which stops at
    # quorum) has to meet them too.
    flipped = sorted(rng.sample(range(n * 2 // 3), 3))
    bad = flip_signatures(commit, flipped)
    times = {}

    def timed(name, fn):
        clear_verified_cache()
        step(f"{label}:{name}")
        t0 = time.perf_counter()
        out = fn()
        times[name] = round(time.perf_counter() - t0, 3)
        return out

    if light:
        timed("verify_commit_light",
              lambda: validation.verify_commit_light(CHAIN_ID, vals, bid, 1, commit))
    timed("verify_commit",
          lambda: validation.verify_commit(CHAIN_ID, vals, bid, 1, commit))

    def must_reject(fn, name):
        try:
            timed(name, fn)
        except ValueError as e:
            require(
                f"wrong signature (#{flipped[0]})" in str(e),
                f"{label}: {name} rejected for another reason: {e}",
            )
        else:
            raise SmokeFailure(f"{label}: {name} accepted a commit with flipped signatures")

    must_reject(
        lambda: validation.verify_commit(CHAIN_ID, vals, bid, 1, bad),
        "verify_commit(flipped)",
    )
    if light:
        must_reject(
            lambda: validation.verify_commit_light(CHAIN_ID, vals, bid, 1, bad),
            "verify_commit_light(flipped)",
        )

    # The bitmap: every lane of the flipped commit, its tail replaced by
    # the well-formed ZIP-215 edge vectors so they share the dispatch (and
    # its compiled program) instead of costing a small bucket of their own.
    edges = [
        c for c in ed25519_pure.zip215_edge_cases()
        if len(c[1]) == 32 and len(c[3]) == 64
    ]
    require(n > len(edges) + 3, "validator count too small for the edge vectors")
    sign_bytes = bad.vote_sign_bytes_all(CHAIN_ID)
    triples = [
        (vals.validators[i].pub_key.bytes(), sign_bytes[i], bad.signatures[i].signature)
        for i in range(n - len(edges))
    ] + [(p, m, s) for _, p, m, s in edges]
    require(flipped[-1] < n - len(edges), "flipped lane fell into the edge tail")
    bv = ed25519.BatchVerifier()
    for p, m, s in triples:
        bv.add(ed25519.PubKey(p), m, s)
    ok, bits = timed("batch_verifier.verify(bitmap)", bv.verify)
    require(not ok and len(bits) == n, f"{label}: bitmap call returned ok={ok}, {len(bits)} lanes")
    edge_lanes = list(range(n - len(edges), n))
    sample = set(flipped) | set(edge_lanes)
    rest = [i for i in range(n) if i not in sample]
    sample |= set(rng.sample(rest, min(len(rest), max(0, 64 - len(sample)))))
    for i in sorted(sample):
        p, m, s = triples[i]
        want = ed25519_pure.verify_zip215(p, m, s)
        require(
            bits[i] == want,
            f"{label}: lane {i} bitmap={bits[i]} but verify_zip215={want}",
        )
    false_lanes = [i for i, b in enumerate(bits) if not b]
    want_false = sorted(
        set(flipped) | {i for i in edge_lanes if not ed25519_pure.verify_zip215(*triples[i])}
    )
    require(
        false_lanes == want_false,
        f"{label}: bitmap false at {false_lanes[:8]}..., expected exactly {want_false}",
    )
    say(
        f"{label}: {n}-validator commit accepted; flipped {flipped} rejected; bitmap "
        f"false at exactly those + {len(want_false) - 3} invalid edge vectors; "
        f"{len(sample)} sampled lanes == verify_zip215; seconds {times}"
    )
    return {"flipped": flipped, "sampled_lanes": len(sample), "seconds": times}


def check_merkle(args, label: str, step, root_fn) -> dict:
    """Roots over the full seeded leaf set and over one ragged count equal
    the pure-Python RFC-6962 tree."""
    from cometbft_tpu.crypto.merkle.tree import hash_from_byte_slices_iterative

    leaves = make_leaves(args.seed, args.leaves)
    times = {}
    for count in (args.leaves, args.leaves * 5 // 8 + 1):
        step(f"{label}:merkle_root({count})")
        t0 = time.perf_counter()
        got = root_fn(leaves[:count])
        times[count] = round(time.perf_counter() - t0, 3)
        want = hash_from_byte_slices_iterative(leaves[:count])
        require(got == want, f"{label}: root over {count} leaves {got.hex()} != host {want.hex()}")
    say(f"{label}: merkle roots over {list(times)} leaves == host tree; seconds {times}")
    return {"leaf_counts": list(times), "seconds": times}


# -- child: device ---------------------------------------------------------------


def child_preamble(args) -> dict:
    """First output line of every chip-holding child: what JAX sees. Exits
    before anything can compile when it is not the platform asked for."""
    import jax

    try:
        import libtpu

        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    import jaxlib

    import cometbft_tpu.ops  # noqa: F401  (sets the compile cache)

    devs = jax.devices()
    cache_dir = jax.config.jax_compilation_cache_dir
    info = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu_version,
        "cpu_count": os.cpu_count(),
        "cache_dir": cache_dir,
        "cache_entries": cache_entries(cache_dir),
    }
    emit("device", **info)
    if info["platform"] != args.platform:
        say(f"wanted platform {args.platform!r}, JAX found {info['platform']!r}: not running")
        sys.exit(3)
    return info


def cache_entries(cache_dir: str) -> int:
    try:
        return sum(1 for f in os.listdir(cache_dir) if f.endswith("-cache"))
    except OSError:
        return 0


def child_device(args) -> None:
    child_preamble(args)
    os.environ["CMTPU_BACKEND"] = "tpu"
    from cometbft_tpu import native
    from cometbft_tpu.blocksync.reactor import BlocksyncReactor
    from cometbft_tpu.crypto import ed25519
    from cometbft_tpu.ops import ed25519_kernel as ek
    from cometbft_tpu.sidecar import engine
    from cometbft_tpu.sidecar.backend import HybridBackend, get_backend

    native.require()  # a failed gcc stops the smoke here, with gcc's message
    backend = get_backend()
    require(backend.name == "tpu", f"CMTPU_BACKEND=tpu resolved {backend.name}")
    say(f"device: backend {backend.name} {backend.device_info()} mesh_width {ek.mesh_width()}")
    result = {"commit": check_commit(args, "device", child_step, light=True)}
    result["merkle"] = check_merkle(args, "device", child_step, backend.merkle_root)

    # What the default sidecar and the auto chain will dispatch first: the
    # hybrid planner's device share of a full commit under the shipped
    # priors. Nothing else warms that bucket; verify it here so its compile
    # is on record and the later phases find it in the cache.
    n = args.validators
    share = HybridBackend()._plan(n)
    say(f"device: hybrid planner's first share of {n} lanes under shipped priors: {share}")
    if 0 < share < n:
        vals, [(_, commit)] = make_commits(args.seed, n, 1, "commit")
        sbs = commit.vote_sign_bytes_all(CHAIN_ID)
        child_step(f"device:share({share})")
        ok, bits = backend.batch_verify(
            [v.pub_key.bytes() for v in vals.validators[:share]],
            sbs[:share],
            [cs.signature for cs in commit.signatures[:share]],
        )
        require(ok and all(bits), "device: the planner's share bucket rejected valid lanes")
        result["hybrid_first_share"] = share

    # One blocksync prefetch window, verified as _prefetch_verify_window
    # does it: every commit of the window in ONE BatchVerifier dispatch
    # under the blocksync class. Dropped first when time runs short.
    w_lanes = args.window_commits * args.window_validators
    left = args.deadline - time.time() - LATER_PHASES_S
    need = 2.5 * max(result["commit"]["seconds"].values())
    if left < need:
        say(
            f"device: DROPPED the {w_lanes}-lane window: {left:.0f} s left for it, "
            f"the slowest program so far took {need / 2.5:.0f} s"
        )
        result["window"] = "dropped"
    else:
        vals, commits = make_commits(
            args.seed, args.window_validators, args.window_commits, "window"
        )
        require(
            w_lanes <= BlocksyncReactor.PREFETCH_MAX_SIGS,
            "window larger than the reactor's own prefetch budget",
        )
        clear_verified_cache()
        bv = ed25519.BatchVerifier()
        for _, commit in commits:
            sbs = commit.vote_sign_bytes_all(CHAIN_ID)
            for idx, cs in enumerate(commit.signatures):
                bv.add(vals.validators[idx].pub_key, sbs[idx], cs.signature)
        child_step(f"device:window({w_lanes})")
        t0 = time.perf_counter()
        with engine.submission_class(engine.CLASS_BLOCKSYNC):
            ok, bits = bv.verify()
        dt = round(time.perf_counter() - t0, 3)
        require(ok and len(bits) == w_lanes, f"device: window ok={ok} lanes={len(bits)}")
        # The reactor's per-block checks then hit the verified-triple cache.
        from cometbft_tpu.types import validation

        lanes_before = backend.device_lanes
        for h, (bid, commit) in enumerate(commits, start=1):
            validation.verify_commit_light(CHAIN_ID, vals, bid, h, commit)
        require(
            backend.device_lanes == lanes_before,
            "device: per-block checks after the window dispatched again",
        )
        say(
            f"device: {args.window_commits} x {args.window_validators} window = one "
            f"{w_lanes}-lane dispatch in {dt} s; per-block checks all cache hits"
        )
        result["window"] = {"lanes": w_lanes, "seconds": dt}
    result["mesh_counters"] = ek.mesh_counters()
    result["device_lanes"] = backend.device_lanes
    say(f"device: mesh_counters {result['mesh_counters']}")
    emit("result", **result)


# -- child: node -----------------------------------------------------------------


def check_auto_chain(counters: dict, platform: str) -> dict:
    """The assertions that keep a dead device tier from passing as a live
    one under CMTPU_BACKEND=auto. `counters` is get_backend().counters():
    the engine's, with the supervisor's under `inner`."""
    from cometbft_tpu.sidecar import backend as backend_mod

    chain = counters.get("inner", counters)
    require(chain.get("chain", [None])[0] == "hybrid", f"chain is {chain.get('chain')}, not hybrid-first")
    require(chain["active_tier"] == "hybrid", f"active tier is {chain['active_tier']}")
    for key in ("trips", "degraded_calls", "deadline_exceeded", "crosscheck_catches"):
        require(chain[key] == 0, f"supervisor counted {key} = {chain[key]}")
    tier = chain["tiers"]["hybrid"]
    require(tier["failures"] == 0, f"hybrid tier failed {tier['failures']} calls")
    hybrid = tier.get("backend", {})
    require(hybrid.get("platform") == platform, f"hybrid tier runs on {hybrid.get('platform')}")
    require(hybrid.get("native") == "ready", f"native library: {hybrid.get('native')}")
    require(hybrid.get("device_lanes", 0) > 0, "no lane of the auto chain ran on the device")
    require(not backend_mod._fallback_logged, "`backend: auto -> cpu` was printed")
    return hybrid


def open_auto_chain(platform: str):
    """get_backend() under CMTPU_BACKEND=auto. On the chip that is the
    product's own selection. `auto` never puts a device tier on XLA:CPU, so
    for the CPU test the selection alone is answered for it; the chain is
    still assembled by build_chain()."""
    from unittest import mock

    from cometbft_tpu.sidecar import backend as backend_mod
    from cometbft_tpu.sidecar import supervisor

    os.environ["CMTPU_BACKEND"] = "auto"
    backend_mod.set_backend(None)
    if platform != "cpu":
        return backend_mod.get_backend()
    with mock.patch.object(
        supervisor, "device_backend", lambda choice: backend_mod.HybridBackend()
    ):
        return backend_mod.get_backend()


def send_txs(port: int, n_txs: int, seed: int, report: dict) -> None:
    """Client of the devnet's real RPC: waits for it, sends the txs, then
    reads the last one back once it is committed."""
    from cometbft_tpu.rpc.client import HTTPClient

    client = HTTPClient(f"http://127.0.0.1:{port}", timeout=15)
    try:
        for _ in range(300):
            try:
                client.status()
                break
            except OSError:
                time.sleep(0.1)
        for i in range(n_txs):
            res = client.broadcast_tx_sync(b"smoke%d-%d=v%d" % (seed, i, i))
            require(res["code"] == 0, f"tx {i} refused: {res}")
        report["sent"] = n_txs
        key, value = b"smoke%d-%d" % (seed, n_txs - 1), b"v%d" % (n_txs - 1)
        for _ in range(300):
            q = client.abci_query("", key)
            if base64.b64decode(q["response"].get("value") or "") == value:
                report["read_back"] = True
                return
            time.sleep(0.1)
        report["error"] = "last tx never became readable"
    except Exception as e:  # surfaced by the caller, which fails the phase
        report["error"] = f"{type(e).__name__}: {e}"


def child_node(args) -> None:
    child_preamble(args)
    backend = open_auto_chain(args.platform)
    say(f"node: get_backend() -> {backend.name} over {backend.inner.name}")
    result = {"commit": check_commit(args, "node", child_step, light=False)}
    counters = backend.counters()
    hybrid = check_auto_chain(counters, args.platform)
    say(f"node: chain {counters['inner']['chain']} active {counters['inner']['active_tier']}")
    say(f"node: hybrid last_share {hybrid['last_share']} last_timing {hybrid['last_timing']}")
    say(f"node: hybrid routes {hybrid['routes']}")
    result["hybrid"] = {k: hybrid[k] for k in ("device_lanes", "host_lanes", "last_share", "last_timing")}

    # The devnet through the CLI's own entry point, in this process (it is
    # the one that holds the chip), with a client on its real RPC.
    from cometbft_tpu.cmd.__main__ import main as cmd_main

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    tx_report: dict = {}
    sender = threading.Thread(
        target=send_txs, args=(port, args.devnet_txs, args.seed, tx_report), daemon=True
    )
    sender.start()
    child_step("node:devnet")
    printed = io.StringIO()
    with contextlib.redirect_stdout(printed):
        rc = cmd_main([
            "devnet", "--validators", "4", "--backend", "auto",
            "--blocks", str(args.devnet_blocks), "--rpc-port", str(port),
        ])
    say(printed.getvalue().rstrip("\n"))
    sender.join(timeout=30)
    require(rc == 0, f"devnet exited {rc}")
    require(not sender.is_alive() and "error" not in tx_report, f"tx client: {tx_report}")
    require(tx_report.get("read_back"), f"tx client: {tx_report}")
    m = re.search(r"^devnet summary: (\{.*\})$", printed.getvalue(), re.M)
    require(m is not None, "devnet printed no summary")
    summary = json.loads(m.group(1))
    require(summary["nodes_agree"], f"devnet nodes disagree: {summary}")
    require(summary["height"] >= args.devnet_blocks, f"devnet stopped early: {summary}")
    require(int(summary["app_hash"], 16) > 0, f"no tx reached the app: {summary}")
    from cometbft_tpu.sidecar.backend import get_backend

    require(get_backend() is backend, "devnet replaced the process backend")
    check_auto_chain(backend.counters(), args.platform)
    say(
        f"node: devnet committed {summary['height']} blocks, {args.devnet_txs} txs over RPC, "
        f"app hash {summary['app_hash']} agreed by 4 nodes; first block after "
        f"{summary['first_block_s']} s; heights with round > 0: {summary['heights_round_gt0']}"
    )
    result["devnet"] = summary
    result["supervisor"] = {
        k: backend.counters()["inner"][k]
        for k in ("calls", "trips", "degraded_calls", "deadline_exceeded", "crosscheck_catches")
    }
    emit("result", **result)


# -- parent ----------------------------------------------------------------------

_HIT = re.compile(r"Persistent compilation cache hit for '([^']+)' with key '([^']+)'")
_MISS = re.compile(r"PERSISTENT COMPILATION CACHE MISS for '([^']+)' with key '([^']+)'")
_TOOK = re.compile(r"'([^']+)' took at least [\d.]+ seconds to compile \(([\d.]+)s\)")
# JAX's debug lines (and the device lists they wrap onto further lines), and
# XLA:CPU's loader chatter in the CPU test.
_NOISE = re.compile(
    r"^(DEBUG:|\s+\w+Device\(id=|[EWI]\d{4} \d\d:\d\d:\d\d\.\d+ +\d+ cpu_aot_loader)"
)


class Phase:
    """One child's output as the parent reads it: relays what a person
    needs, files the rest, and keeps the compile record (per program: the
    step it fell in, cache hit or miss, compile seconds)."""

    def __init__(self, name: str, log_dir: str):
        self.name = name
        self.step = "start-up"
        self.device: dict | None = None
        self.result: dict | None = None
        self.programs: list[dict] = []
        self.lines: list[str] = []
        os.makedirs(log_dir, exist_ok=True)
        self.log = open(os.path.join(log_dir, f"{name}.log"), "w")

    def feed(self, line: str) -> None:
        line = line.rstrip("\n")
        self.log.write(line + "\n")
        self.log.flush()
        if line.startswith("SMOKE "):
            msg = json.loads(line[6:])
            kind = msg.pop("msg")
            if kind == "device":
                self.device = msg
                print(f"[{self.name}] device {json.dumps(msg)}", flush=True)
            elif kind == "step":
                self.step = msg["label"]
            elif kind == "result":
                self.result = msg
            return
        for pat, how in ((_HIT, "hit"), (_MISS, "miss")):
            m = pat.search(line)
            if m:
                self.programs.append(
                    {"step": self.step, "name": m.group(1), "key": m.group(2), "cache": how, "compile_s": 0.0}
                )
                return
        m = _TOOK.search(line)
        if m:
            for p in reversed(self.programs):
                if p["name"] == m.group(1) and p["cache"] == "miss":
                    p["compile_s"] = float(m.group(2))
                    break
            return
        if _NOISE.match(line):
            return
        self.lines.append(line)
        shown = line if len(line) <= 600 else line[:600] + f" ... (+{len(line) - 600} chars in the log)"
        print(f"[{self.name}] {shown}", flush=True)

    def keys(self, how: str) -> set:
        return {p["key"] for p in self.programs if p["cache"] == how}

    def report(self, earlier: list["Phase"]) -> dict:
        """Prints the compile record and returns its summary. Programs are
        named by the step they compiled in (the step label carries the
        program's bucket), small helper programs are only counted."""
        known = {}
        for ph in earlier:
            for p in ph.programs:
                verb = "compiled" if p["cache"] == "miss" else "loaded"
                known.setdefault(p["key"], f"{verb} by {ph.name}")
        big = [p for p in self.programs if p["compile_s"] >= 1.0 or p["key"] in known]
        for p in big:
            origin = f" ({known[p['key']]})" if p["cache"] == "hit" and p["key"] in known else ""
            print(
                f"[{self.name}] program {p['name']} in {p['step']}: cache {p['cache']}{origin}, "
                f"compile {p['compile_s']:.2f} s",
                flush=True,
            )
        hits_from_earlier = sum(1 for p in self.programs if p["cache"] == "hit" and p["key"] in known)
        out = {
            "programs": len(self.programs),
            "misses": len(self.keys("miss")),
            "hits": len(self.keys("hit")),
            "hits_of_earlier_children": hits_from_earlier,
            "compile_s": round(sum(p["compile_s"] for p in self.programs), 2),
        }
        print(f"[{self.name}] compile record {json.dumps(out)}", flush=True)
        return out

    def close(self) -> None:
        self.log.close()


def child_env(extra: dict) -> dict:
    env = dict(os.environ)
    for k in ("CMTPU_BACKEND", "CMTPU_FAULTS", "CMTPU_SIDECAR_ADDR", "CMTPU_SIDECAR_CHUNK"):
        env.pop(k, None)
    # JAX's own compile log is where cache hits and compile seconds come from.
    env["JAX_DEBUG_LOG_MODULES"] = "jax._src.compiler"
    env["PYTHONUNBUFFERED"] = "1"
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra)
    return env


def child_args(args, which: str) -> list[str]:
    return [
        sys.executable, os.path.abspath(__file__), "--child", which,
        "--seed", str(args.seed), "--platform", args.platform,
        "--validators", str(args.validators), "--leaves", str(args.leaves),
        "--window-commits", str(args.window_commits),
        "--window-validators", str(args.window_validators),
        "--devnet-blocks", str(args.devnet_blocks), "--devnet-txs", str(args.devnet_txs),
        "--deadline", str(args.deadline),
    ]


def run_child(args, phase: Phase, which: str, extra_env: dict) -> None:
    """Runs one of this script's own children to its end, or kills it at
    the run's deadline."""
    proc = subprocess.Popen(
        child_args(args, which), env=child_env(extra_env), cwd=HERE,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    killer = threading.Timer(max(1.0, args.deadline - time.time()), proc.kill)
    killer.start()
    try:
        for line in proc.stdout:
            phase.feed(line)
        rc = proc.wait()
    finally:
        killer.cancel()
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    require(rc == 0, f"{which} child exited {rc}")
    require(phase.device is not None, f"{which} child never named its device")
    require(phase.result is not None, f"{which} child ended without a result")


def run_sidecar_phase(args, phase: Phase) -> dict:
    """`python -m cometbft_tpu.sidecar` as the chip-holding child; this
    process is its client through CMTPU_BACKEND=grpc."""
    from cometbft_tpu.sidecar import backend as backend_mod

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        addr = f"127.0.0.1:{s.getsockname()[1]}"
    extra = {"CMTPU_SIDECAR_ADDR": addr}
    if args.platform == "cpu":
        # The default choice (auto) never puts a device tier on XLA:CPU, so
        # the CPU test's server is asked for the bare hybrid tier; and warming
        # the server's full-size default buckets there takes minutes.
        extra["CMTPU_BACKEND"] = "hybrid"
        extra["CMTPU_SIDECAR_WARM"] = "0"
    proc = subprocess.Popen(
        [sys.executable, "-m", "cometbft_tpu.sidecar"], env=child_env(extra), cwd=HERE,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    lines: queue.Queue = queue.Queue()

    def pump():
        for line in proc.stdout:
            phase.feed(line)
            lines.put(line)
        lines.put(None)

    reader = threading.Thread(target=pump, daemon=True)
    reader.start()

    def wait_for(prefix: str) -> str:
        while True:
            try:
                line = lines.get(timeout=max(1.0, args.deadline - time.time()))
            except queue.Empty:
                raise SmokeFailure(f"sidecar: no `{prefix}` line before the deadline")
            require(line is not None, f"sidecar ended before `{prefix}`")
            if line.startswith(prefix):
                return line[len(prefix):].strip()

    result: dict = {}

    def step(label: str) -> None:  # the client's steps, straight into the record
        phase.step = label

    try:
        started = json.loads(wait_for("sidecar: backend "))
        require(
            started.get("platform") == args.platform,
            f"sidecar resolved {started or 'a host-only backend'}, wanted {args.platform}",
        )
        phase.device = {"platform": started["platform"], "kind": started["device_kind"],
                        "count": started["device_count"]}
        if "CMTPU_SIDECAR_WARM" not in extra:
            phase.step = "sidecar:warmup (buckets in ascending order)"
            t0 = time.time()
            wait_for("sidecar: warmup complete")
            say(f"[sidecar] warmup took {time.time() - t0:.1f} s")

        os.environ["CMTPU_BACKEND"] = "grpc"
        os.environ["CMTPU_SIDECAR_ADDR"] = addr
        backend_mod.set_backend(None)
        client = backend_mod.get_backend()
        require(client.name == "grpc", f"client backend is {client.name}")
        require(client.ping(), "sidecar did not answer Ping")
        remote = client.counters()
        say(f"[sidecar] Ping ok: {remote}")
        n = args.validators
        # Unary: one BatchVerify frame per call.
        os.environ["CMTPU_SIDECAR_CHUNK"] = str(1 << 20)
        result["unary"] = check_commit(args, "sidecar/unary", step, light=False)
        # Streamed: the server's advertised chunk, or a quarter of the
        # batch where the batch is smaller than one chunk (the CPU test).
        if n > remote["remote_chunk"]:
            del os.environ["CMTPU_SIDECAR_CHUNK"]
        else:
            os.environ["CMTPU_SIDECAR_CHUNK"] = str(max(8, n // 4))
        result["streamed"] = check_commit(args, "sidecar/streamed", step, light=False)
        calls = client.counters()
        require(
            calls["unary_calls"] >= 3 and calls["streamed_calls"] >= 3,
            f"both wire paths should have carried the commit: {calls}",
        )
        say(f"[sidecar] client counters {calls}")
        result["merkle"] = check_merkle(args, "sidecar", step, client.merkle_root)
        client.close()
    finally:
        backend_mod.set_backend(None)
        for k in ("CMTPU_BACKEND", "CMTPU_SIDECAR_ADDR", "CMTPU_SIDECAR_CHUNK"):
            os.environ.pop(k, None)
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
        reader.join(timeout=10)
    stopped = [ln for ln in phase.lines if ln.startswith("sidecar: stopping, backend ")]
    require(stopped, "sidecar printed no counters on SIGTERM")
    served = json.loads(stopped[-1][len("sidecar: stopping, backend "):])
    require(served.get("native") == "ready", f"sidecar's native library: {served.get('native')}")
    say(
        f"[sidecar] lanes on device {served['device_lanes']}, on host {served['host_lanes']}; "
        f"last_share {served['last_share']}"
    )
    sizes = sorted({(r["n"], r["device"]) for r in served["routes"]})
    say(f"[sidecar] planned dispatches seen, as (lanes, device share): {sizes}")
    for r in served["routes"]:
        if r["device"] == 0:
            say(
                f"[sidecar] FINDING: the planner sent a {r['n']}-lane call all-host "
                f"(dev_rate {r['dev_rate']}, host_rate {r['host_rate']} sigs/ms)"
            )
    require(served["device_lanes"] > 0, "the sidecar ran no lane on the device")
    result["lanes"] = {"device": served["device_lanes"], "host": served["host_lanes"]}
    phase.result = result
    return result


def parent(args) -> int:
    import cometbft_tpu  # noqa: F401  (fails here when the repo is not beside this file)

    args.deadline = time.time() + TIME_LIMIT_S
    t_start = time.time()
    phases: list[Phase] = []
    record = {}
    try:
        for name in ("device", "sidecar", "node"):
            phase = Phase(name, args.log_dir)
            t0 = time.time()
            try:
                if name == "sidecar":
                    run_sidecar_phase(args, phase)
                else:
                    run_child(args, phase, name, {})
            finally:
                phase.close()
            cache_dir = (phases or [phase])[0].device["cache_dir"]
            compiles = phase.report(phases)
            if phases:
                require(
                    compiles["hits_of_earlier_children"] > 0,
                    f"{name} found none of the earlier children's programs in the compile cache",
                )
            print(
                f"[{name}] ok in {time.time() - t0:.0f} s; cache {cache_dir} now holds "
                f"{cache_entries(cache_dir)} entries",
                flush=True,
            )
            record[name] = {"seconds": round(time.time() - t0, 1), "compiles": compiles}
            phases.append(phase)
        devices = [
            {k: ph.device[k] for k in ("platform", "kind", "count")} for ph in phases
        ]
        require(
            all(d == devices[0] for d in devices), f"children saw different devices: {devices}"
        )
        require(devices[0]["platform"] == args.platform, f"ran on {devices[0]}")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    assert "jax" not in sys.modules, "the smoke's parent must never import JAX"
    first = phases[0]
    record["device"]["cache_entries_before"] = first.device["cache_entries"]
    record["device"]["window"] = first.result["window"]
    record["device"]["mesh_counters"] = first.result["mesh_counters"]
    record["sidecar"]["lanes"] = phases[1].result["lanes"]
    record["node"]["hybrid"] = phases[2].result["hybrid"]
    record["node"]["devnet"] = phases[2].result["devnet"]
    summary = {
        "versions": {k: first.device[k] for k in ("jax", "jaxlib", "libtpu")},
        "cpu_count": first.device["cpu_count"],
        "seed": args.seed,
        "widths": {"validators": args.validators, "leaves": args.leaves},
        "seconds": round(time.time() - t_start, 1),
        "phases": record,
        "claim": None,
    }
    print("SUMMARY " + json.dumps(summary), flush=True)
    # The result line: these two keys and nothing else, the device as JAX
    # reported it to the children.
    device = {
        "platform": str(devices[0]["platform"]),
        "kind": str(devices[0]["kind"]),
        "count": int(devices[0]["count"]),
    }
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--platform", default="tpu", help="platform every chip-holding child must find")
    ap.add_argument("--validators", type=int, default=10240)
    ap.add_argument("--leaves", type=int, default=65536)
    ap.add_argument("--window-commits", type=int, default=32)
    ap.add_argument("--window-validators", type=int, default=1024)
    ap.add_argument("--devnet-blocks", type=int, default=20)
    ap.add_argument("--devnet-txs", type=int, default=50)
    ap.add_argument("--log-dir", default=os.path.join(HERE, "chiprun_out", "chip_smoke"))
    ap.add_argument("--child", choices=["device", "node"], help=argparse.SUPPRESS)
    ap.add_argument("--deadline", type=float, default=0.0, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child is None:
        return parent(args)
    if not args.deadline:
        args.deadline = time.time() + TIME_LIMIT_S
    try:
        {"device": child_device, "node": child_node}[args.child](args)
    except SmokeFailure as e:
        print(f"chip_smoke: {args.child} FAILED: {e}", flush=True)
        return 1
    return 0


if __name__ == "__main__":
    sys.path.insert(0, HERE)
    sys.exit(main())
