"""Round benchmark: the north-star configs from BASELINE.md.

Prints exactly ONE JSON line on stdout:
  {"metric": ..., "value": ..., "unit": ..., "vs_baseline": ..., "stages": {...}}

Headline metric (unchanged across rounds): wall time to verify a
10,240-signature commit + the 64k-leaf block Merkle root — ONE combined
device dispatch from packed operands (the kernel number). `stages` carries
the rest of BASELINE.md's configs so regressions are attributable:

  pack_sigs_ms            host: SHA-512 challenges + limb/digit packing (10,240 sigs)
  pack_leaves_ms          host: SHA-256 padding/packing (65,536 leaves)
  verify_ms               device: ZIP-215 batch verify dispatch, steady state
  merkle_ms               device: leaf-hash + tree root dispatch, steady state
  combined_ms             device: ONE dispatch doing both  <- headline
  first_dispatch_s        cold-cache wall for the first combined dispatch
                          (compile or persistent-cache hit; VERDICT r3 #2)
  commit_light_e2e_ms     the SHIPPED path: types/validation VerifyCommitLight
                          over a real 10,240-validator Commit -> crypto.batch
                          -> backend -> kernel (includes all marshalling);
                          COLD — the verified-triple cache is cleared per rep
  commit_light_cached_ms  same call with the cache warm (production behavior
                          for blocksync's double verification)
  blocksync_replay_ms_per_block   100-block fast-sync replay, 1,024-validator
                          commits (blocksync/reactor.go:355 trySync shape)
  light_bisection_ms      light-client skipping verification to height 500
                          over 4,096-validator sets with rotation forcing
                          multi-hop bisection (light/client.go:706)

vs_baseline: reference Go path cost for the headline work, from BASELINE.md:
RFC-6962 Merkle ~77.7us/100 leaves -> ~50.9 ms at 64k; curve25519-voi batch
verify ~32us/sig -> ~327 ms for 10,240 sigs; total ~378 ms.
vs_baseline = baseline_ms / measured_ms (>1 = faster than the reference).

Stage plan (driver records the stderr tail):
  1. device probe (subprocess; must find an accelerator), 2. TPU worker
  (phase-logged, optional stages time-gated so the JSON line lands).
  A run that wanted the chip and got none exits non-zero with no JSON line.
  JAX_PLATFORMS=cpu asks for the host-tier run instead (C-speed host path,
  not XLA:CPU), labeled `cpu-host`.
"""

import json
import os
import socket
import subprocess
import sys
import time

BASELINE_MS = 10240 * 0.032 + 50.9
# Overridable for smoke tests on hosts without the device (the driver runs
# the defaults).
N_SIGS = int(os.environ.get("CMTPU_BENCH_SIGS", "10240"))
N_LEAVES = int(os.environ.get("CMTPU_BENCH_LEAVES", "65536"))
BS_VALS = int(os.environ.get("CMTPU_BENCH_BS_VALS", "1024"))
BS_BLOCKS = int(os.environ.get("CMTPU_BENCH_BS_BLOCKS", "100"))
LIGHT_VALS = int(os.environ.get("CMTPU_BENCH_LIGHT_VALS", "4096"))
PROBE_TIMEOUT_S = int(os.environ.get("CMTPU_BENCH_PROBE_TIMEOUT", "120"))
TPU_TIMEOUT_S = int(os.environ.get("CMTPU_BENCH_TPU_TIMEOUT", "480"))
MESH_TIMEOUT_S = int(os.environ.get("CMTPU_BENCH_MESH_TIMEOUT", "480"))
# Leave headroom before TPU_TIMEOUT_S: optional stages are skipped once the
# worker passes this many seconds.
STAGE_BUDGET_S = int(os.environ.get("CMTPU_BENCH_STAGE_BUDGET", "330"))
HERE = os.path.dirname(os.path.abspath(__file__))

T0 = time.time()


def log(msg: str) -> None:
    print(f"[bench {time.time() - T0:7.1f}s] {msg}", file=sys.stderr, flush=True)


def run_phase_logged(args: list, timeout_s: int, tag: str, env=None):
    out_path = os.path.join(HERE, f".bench_{tag}.out")
    err_path = os.path.join(HERE, f".bench_{tag}.err")
    with open(out_path, "w") as out_f, open(err_path, "w") as err_f:
        try:
            proc = subprocess.run(
                args, stdout=out_f, stderr=err_f, timeout=timeout_s, env=env
            )
            rc = proc.returncode
        except subprocess.TimeoutExpired:
            rc = "timeout"
    tail = open(err_path).read()[-2000:]
    for line in tail.splitlines():
        log(f"  {tag}| {line}")
    if rc != 0:
        log(f"{tag}: rc={rc} after <= {timeout_s}s")
        return None
    return open(out_path).read()


# -- workload builders (host crypto is C-speed) --------------------------------


def _devnet_throughput(
    seconds: float = 12.0, n_vals: int = 4, target_blocks: int | None = None
):
    """System-level stage: an in-process 4-validator devnet over real TCP
    (SecretConnection, gossip, mempool) under continuous tx load. Returns
    (blocks/s, committed tx/s) — the analog of the reference's QA
    saturation measurements (docs/qa/: ~0.7 blocks/s, ~400 tx/s on a
    200-node DigitalOcean testnet; here everything shares one host).
    `target_blocks` ends the run early once that many blocks committed
    (`seconds` stays the hard cap) — the hotpath A/B uses it so both arms
    measure the same amount of work."""
    import threading

    from cometbft_tpu.abci.client import LocalClientCreator
    from cometbft_tpu.abci.example.kvstore import KVStoreApplication
    from cometbft_tpu.config import test_config
    from cometbft_tpu.crypto import ed25519
    from cometbft_tpu.node.node import Node
    from cometbft_tpu.privval import FilePV
    from cometbft_tpu.types import cmttime
    from cometbft_tpu.types.genesis import GenesisDoc, GenesisValidator

    pvs = [FilePV(ed25519.gen_priv_key_from_secret(b"bench-val-%d" % i)) for i in range(n_vals)]
    gen = GenesisDoc(
        chain_id="bench-devnet",
        genesis_time=cmttime.now(),
        validators=[
            GenesisValidator(pv.get_pub_key().address(), pv.get_pub_key(), 10, f"v{i}")
            for i, pv in enumerate(pvs)
        ],
    )
    gen.validate_and_complete()
    nodes = []
    for pv in pvs:
        cfg = test_config()
        cfg.base.db_backend = "memdb"
        cfg.rpc.laddr = ""
        cfg.p2p.laddr = "tcp://127.0.0.1:0"
        nodes.append(Node(cfg, gen, pv, LocalClientCreator(KVStoreApplication())))
    try:
        for nd in nodes:
            nd.start()
        addrs = [nd.switch.node_info.listen_addr for nd in nodes]
        for i, nd in enumerate(nodes):
            for j, a in enumerate(addrs):
                if i != j:
                    nd.switch.dial_peer(a)
        stop = [False]

        def pump():
            k = 0
            while not stop[0]:
                for nd in nodes:
                    try:
                        nd.mempool.check_tx(b"bench%d=v" % k)
                    except Exception:
                        pass
                k += 1
                time.sleep(0.002)

        threading.Thread(target=pump, daemon=True).start()
        t0 = time.time()
        h0 = nodes[0].block_store.height()  # committed-height semantics
        deadline = t0 + seconds
        while time.time() < deadline:
            time.sleep(0.25)
            if (
                target_blocks is not None
                and nodes[0].block_store.height() - h0 >= target_blocks
            ):
                break
        stop[0] = True
        dt = time.time() - t0
        h1 = nodes[0].block_store.height()
        txs = 0
        for h in range(h0 + 1, h1 + 1):
            blk = nodes[0].block_store.load_block(h)
            if blk is not None:
                txs += len(blk.data.txs)
        return (h1 - h0) / dt, txs / dt
    finally:
        for nd in nodes:
            try:
                nd.stop()
            except Exception:
                pass


def _pick_headline(stages: dict) -> float:
    """Headline = fastest measured combined path; records which one won so
    the JSON schema is identical for full and truncated emits.  A truncated
    snapshot may predate the combined stage entirely — emit a -1 sentinel
    then, so the watchdog's partial record still goes out instead of a
    KeyError being swallowed by its bare except."""
    headline = stages.get("combined_ms")
    stages["combined_path"] = "device"
    hyb = stages.get("combined_hybrid_ms")
    if headline is None:
        headline, stages["combined_path"] = (
            (hyb, "hybrid") if hyb is not None else (-1.0, "none")
        )
    elif hyb is not None and hyb < headline:
        headline, stages["combined_path"] = hyb, "hybrid"
    return headline


def best_of(f, reps=3):
    """Best wall time over reps calls, in ms."""
    best = float("inf")
    for _ in range(reps):
        t1 = time.perf_counter()
        f()
        best = min(best, time.perf_counter() - t1)
    return best * 1000.0


def _signed_batch(n, tag=b"bench"):
    from cometbft_tpu.crypto import ed25519 as host_ed

    pvs = [host_ed.gen_priv_key_from_secret(tag + b"-%d" % i) for i in range(n)]
    pubs = [pv.pub_key().bytes() for pv in pvs]
    msgs = [b"commit-vote-%d" % i for i in range(n)]
    sigs = [pv.sign(m) for pv, m in zip(pvs, msgs)]
    return pvs, pubs, msgs, sigs


def _commit_fixture(n_vals, heights=1, chain_id="bench-chain", tag=b"cl"):
    """Real ValidatorSet + Commit(s) shaped like the shipped path sees them."""
    from cometbft_tpu.types import BlockID, Commit, Time, Vote
    from cometbft_tpu.types.block import PRECOMMIT_TYPE
    from cometbft_tpu.types.part_set import PartSetHeader
    from cometbft_tpu.types.priv_validator import MockPV
    from cometbft_tpu.types.validator import Validator
    from cometbft_tpu.types.validator_set import ValidatorSet
    from cometbft_tpu.types.vote import vote_to_commit_sig

    pvs = sorted((MockPV() for _ in range(n_vals)), key=lambda p: p.address())
    vals = ValidatorSet([Validator.new(pv.get_pub_key(), 10) for pv in pvs])
    pv_by_addr = {pv.address(): pv for pv in pvs}
    commits = []
    for h in range(1, heights + 1):
        bid = BlockID(
            h.to_bytes(8, "big") * 4, PartSetHeader(1, b"\x02" * 32)
        )
        sigs = []
        for idx, v in enumerate(vals.validators):
            vote = Vote(
                type=PRECOMMIT_TYPE, height=h, round=0, block_id=bid,
                timestamp=Time(1700000000 + h, 0),
                validator_address=v.address, validator_index=idx,
            )
            sigs.append(vote_to_commit_sig(pv_by_addr[v.address].sign_vote(chain_id, vote)))
        commits.append((bid, Commit(height=h, round=0, block_id=bid, signatures=sigs)))
    return vals, commits


class _LazyChain:
    """Light blocks generated only when the bisection touches them:
    4,096-validator sets rotating 8 per height, so a 1 -> 500 jump dilutes
    trust below 1/3 and forces multi-hop bisection."""

    CHAIN_ID = "bench-light"

    def __init__(self, n_vals=4096, rotate=8, heights=500):
        from cometbft_tpu.types.priv_validator import MockPV

        self.n_vals, self.rotate, self.heights = n_vals, rotate, heights
        self.pool = [MockPV() for _ in range(n_vals + rotate * heights)]
        self.blocks = {}
        self.built = 0

    def _vals_at(self, h):
        from cometbft_tpu.types.validator import Validator
        from cometbft_tpu.types.validator_set import ValidatorSet

        start = (h - 1) * self.rotate
        return ValidatorSet(
            [
                Validator.new(pv.get_pub_key(), 10)
                for pv in self.pool[start : start + self.n_vals]
            ]
        )

    def light_block(self, h):
        from cometbft_tpu.types import BlockID, Commit, Time, Vote
        from cometbft_tpu.types.block import PRECOMMIT_TYPE, Header, SignedHeader
        from cometbft_tpu.types.light_block import LightBlock
        from cometbft_tpu.types.part_set import PartSetHeader
        from cometbft_tpu.types.vote import vote_to_commit_sig

        if h in self.blocks:
            return self.blocks[h]
        vals = self._vals_at(h)
        next_vals = self._vals_at(h + 1)
        header = Header(
            chain_id=self.CHAIN_ID, height=h, time=Time(1700000000 + 10 * h, 0),
            last_block_id=BlockID(b"\x01" * 32, PartSetHeader(1, b"\x01" * 32)),
            validators_hash=vals.hash(), next_validators_hash=next_vals.hash(),
            app_hash=b"\x00" * 32, proposer_address=vals.validators[0].address,
        )
        bid = BlockID(header.hash(), PartSetHeader(1, b"\x02" * 32))
        pv_by_addr = {pv.address(): pv for pv in self.pool}
        sigs = []
        for idx, v in enumerate(vals.validators):
            vote = Vote(
                type=PRECOMMIT_TYPE, height=h, round=0, block_id=bid,
                timestamp=header.time.add_nanos(10**9),
                validator_address=v.address, validator_index=idx,
            )
            sigs.append(vote_to_commit_sig(pv_by_addr[v.address].sign_vote(self.CHAIN_ID, vote)))
        lb = LightBlock(
            signed_header=SignedHeader(header, Commit(height=h, round=0, block_id=bid, signatures=sigs)),
            validator_set=vals,
        )
        self.blocks[h] = lb
        self.built += 1
        return lb

    def provider(self):
        from cometbft_tpu.light.provider import Provider

        chain = self

        class _P(Provider):
            def chain_id(self):
                return chain.CHAIN_ID

            def light_block(self, height):
                if height == 0:
                    height = chain.heights
                return chain.light_block(height)

            def report_evidence(self, ev):
                pass

        return _P()


# -- pod-scale mesh stage ------------------------------------------------------


def _fit_and_model(widths, n_sigs, ms_per_lane, overhead_ms):
    """Pure model: the verify wall for ONE merged n_sigs dispatch at each
    mesh width, from a measured per-lane rate and a fixed per-dispatch
    overhead (the sharded program is pure data parallel — zero collectives
    — so lanes split evenly; the mesh-aware ladder pads the remainder).
    Returns the curve narrowest-first, each row carrying its speedup vs the
    width-1 row."""
    curve = []
    for w in sorted({int(w) for w in widths if int(w) >= 1}):
        lanes = -(-n_sigs // w)  # ceil: the padded per-chip share
        curve.append(
            {
                "devices": w,
                "verify_ms": round(overhead_ms + lanes * ms_per_lane, 3),
            }
        )
    base = next(
        (r["verify_ms"] for r in curve if r["devices"] == 1),
        curve[0]["verify_ms"] if curve else 0.0,
    )
    for row in curve:
        row["speedup"] = (
            round(base / row["verify_ms"], 2) if row["verify_ms"] > 0 else 0.0
        )
    return curve


def _mesh_stage_inner(plog) -> dict:
    """Pod-scaling stage (runs inside a jax-capable process): calibrate the
    REAL single-device and mesh-sharded verify walls at two small buckets,
    assert the sharded program is bit-identical to the single-device bitmap,
    then model the CMTPU_BENCH_MESH_SIGS merged dispatch across
    CMTPU_BENCH_MESH_WIDTHS from the measured per-lane rate + dispatch
    overhead (`modeled: true` in the JSON — on the single-core virtual mesh
    the chips share one core, so the curve is the rate model's, same
    convention as the other stages' simulated dispatch costs; on a real pod
    the calibration walls themselves are the device evidence).  Also runs
    the subtree-parallel Merkle route against the host root."""
    t0 = time.time()
    import numpy as np

    from cometbft_tpu.ops import ed25519_kernel as ek

    n_sigs = int(os.environ.get("CMTPU_BENCH_MESH_SIGS", "65536"))
    widths = os.environ.get("CMTPU_BENCH_MESH_WIDTHS", "1,2,4,8").split(",")
    b2 = int(os.environ.get("CMTPU_BENCH_MESH_CAL_MAX", "4096"))
    b1 = 128 if b2 > 128 else 8
    width = ek.mesh_width()

    pvs, pubs, msgs, sigs = _signed_batch(b2, tag=b"mesh")
    plog(f"mesh: signed {b2} calibration messages (mesh width {width})")
    operands2, host_ok2 = ek.pack_batch(pubs, msgs, sigs)
    operands1, _ = ek.pack_batch(pubs[:b1], msgs[:b1], sigs[:b1])
    f1 = ek._compiled(*ek._bucket_key(operands1))
    f2 = ek._compiled(*ek._bucket_key(operands2))
    ok1 = np.asarray(f1(*operands1))  # compile + correctness
    ok2 = np.asarray(f2(*operands2))
    assert ok2[:b2].all(), "mesh calibration batch must verify"
    w1 = best_of(lambda: np.asarray(f1(*operands1)), reps=2)
    w2 = best_of(lambda: np.asarray(f2(*operands2)), reps=2)
    plog(f"mesh: single-device walls {b1}: {w1:.1f} ms, {b2}: {w2:.1f} ms")
    ms_per_lane = max((w2 - w1) / max(b2 - b1, 1), 1e-6)
    overhead_ms = max(w1 - b1 * ms_per_lane, 0.0)

    cal = {
        "bucket_small": b1,
        "bucket_large": b2,
        "single_ms_small": round(w1, 3),
        "single_ms_large": round(w2, 3),
        "ms_per_lane": round(ms_per_lane, 6),
        "dispatch_overhead_ms": round(overhead_ms, 3),
    }
    sh = ek._sharded_verify()
    if sh is not None and b2 % sh[0] == 0:
        sharded_ok = np.asarray(sh[1](*operands2))  # compile
        cal["sharded_ms_large"] = round(
            best_of(lambda: np.asarray(sh[1](*operands2)), reps=2), 3
        )
        cal["sharded_bit_identical"] = bool(np.array_equal(sharded_ok, ok2))
        assert cal["sharded_bit_identical"], "mesh bitmap != single-device"
        plog(
            f"mesh: sharded wall {b2} over {sh[0]} chips "
            f"{cal['sharded_ms_large']} ms (bit-identical)"
        )

    curve = _fit_and_model(widths, n_sigs, ms_per_lane, overhead_ms)
    result = {
        "n_devices": width,
        "sigs": n_sigs,
        "modeled": True,
        "calibration": cal,
        "curve": curve,
        "speedup_widest_vs_1": curve[-1]["speedup"] if curve else 0.0,
    }

    # ---- subtree-parallel Merkle route (time-gated: 2 more compiles) ----
    if time.time() - t0 < MESH_TIMEOUT_S * 0.6:
        try:
            from cometbft_tpu.crypto.merkle import hash_from_byte_slices
            from cometbft_tpu.ops import merkle_kernel as mk
            from cometbft_tpu.ops import sha256_kernel as sha

            n_leaves = int(os.environ.get("CMTPU_BENCH_MESH_LEAVES", "4096"))
            txs = [b"mesh-tx-%08d" % i for i in range(n_leaves)]
            blocks, nblocks = sha.pack_messages([b"\x00" + t for t in txs])
            want = hash_from_byte_slices(txs)
            shr = mk._sharded_root()
            if shr is not None and n_leaves % shr[0] == 0:
                import jax.numpy as jnp

                db, dn = jnp.asarray(blocks), jnp.asarray(nblocks)
                single_fn = mk._leaves_to_root_jit(blocks.shape[0], n_leaves)

                def _single():
                    return sha.digest_words_to_bytes(
                        np.asarray(single_fn(db, dn))
                    )[0]

                def _mesh_root():
                    return sha.digest_words_to_bytes(
                        np.asarray(shr[1](db, dn))
                    )[0]

                assert _single() == want and _mesh_root() == want
                result["merkle"] = {
                    "leaves": n_leaves,
                    "single_ms": round(best_of(_single, reps=2), 3),
                    "sharded_ms": round(best_of(_mesh_root, reps=2), 3),
                    "root_identical": True,
                }
                plog(
                    f"mesh: merkle {n_leaves} leaves single "
                    f"{result['merkle']['single_ms']} ms, sharded "
                    f"{result['merkle']['sharded_ms']} ms (roots match)"
                )
        except Exception as e:
            plog(f"mesh merkle sub-stage failed: {type(e).__name__}: {e}")

    result["mesh_counters"] = ek.mesh_counters()
    return result


def mesh_worker() -> None:
    """--mesh-worker argv mode: the mesh stage in its own jax process (the
    CPU fallback parent deliberately never imports jax), pinned to the
    virtual mesh by the parent's env. Emits one MESH_JSON line."""
    t0 = time.time()

    def plog(msg):
        print(f"[mesh {time.time() - t0:6.1f}s] {msg}", file=sys.stderr, flush=True)

    plog(f"start; JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}")
    import jax

    if os.environ.get("JAX_PLATFORMS"):
        jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])
    print("MESH_JSON " + json.dumps(_mesh_stage_inner(plog)), flush=True)


def _mesh_stage_subprocess():
    """Launch --mesh-worker on the 8-device virtual CPU mesh; returns the
    parsed stage dict or None (a wedged/failed worker never gates the
    fallback's JSON line)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    # Small real-wall calibration buckets: XLA:CPU verifies ~7 ms/lane, so
    # the defaults sized for a pod would spend minutes on calibration.
    env.setdefault("CMTPU_BENCH_MESH_CAL_MAX", "512")
    env.setdefault("CMTPU_BENCH_MESH_LEAVES", "1024")
    out = run_phase_logged(
        [sys.executable, "-u", __file__, "--mesh-worker"],
        MESH_TIMEOUT_S,
        "mesh",
        env=env,
    )
    for line in (out or "").splitlines():
        if line.startswith("MESH_JSON "):
            try:
                return json.loads(line[len("MESH_JSON "):])
            except ValueError:
                return None
    return None


# -- TPU worker ----------------------------------------------------------------


def tpu_worker() -> None:
    t0 = time.time()

    def plog(msg):
        print(f"[worker {time.time() - t0:6.1f}s] {msg}", file=sys.stderr, flush=True)

    def budget_left() -> bool:
        return time.time() - t0 < STAGE_BUDGET_S

    plog(f"start; JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}")
    import jax

    devs = jax.devices()
    plog(f"devices: {devs} platform={devs[0].platform}")
    if devs[0].platform == "cpu":
        # This worker's numbers go out under a device headline: XLA:CPU
        # must never produce them.
        plog("no accelerator: JAX resolved to its CPU backend")
        sys.exit(1)
    if "--probe-only" in sys.argv:
        import jax.numpy as jnp

        y = jax.block_until_ready(jnp.ones((256, 256)) @ jnp.ones((256, 256)))
        plog(f"matmul ok ({float(y[0, 0])})")
        print("PROBE_OK")
        return

    import numpy as np

    from cometbft_tpu.ops import ed25519_kernel as ek
    from cometbft_tpu.ops import merkle_kernel as mk
    from cometbft_tpu.ops import sha256_kernel as sha

    stages = {}
    stages["n_devices"] = len(devs)
    # Attribution: which kernel variant produced this line (the RESOLVED
    # lowering — 'auto' would label different variants identically).
    from cometbft_tpu.ops import field25519 as _fe

    stages["fe_mode"] = _fe._mode()
    stages["ladder"] = (
        "pallas" if os.environ.get("CMTPU_LADDER") == "pallas" else "xla"
    )
    if os.environ.get("CMTPU_HOST_HASH") == "1":
        stages["host_hash"] = True

    # ---- host packing ----
    pvs, pubs, msgs, sigs = _signed_batch(N_SIGS)
    plog(f"signed {N_SIGS} messages")
    operands, host_ok = ek.pack_batch(pubs, msgs, sigs)
    stages["pack_sigs_ms"] = round(best_of(lambda: ek.pack_batch(pubs, msgs, sigs)), 2)
    assert host_ok[:N_SIGS].all()
    txs = [b"bench-tx-%08d" % i for i in range(N_LEAVES)]
    leaf_msgs = [b"\x00" + t for t in txs]
    blocks, nblocks = sha.pack_messages(leaf_msgs)
    stages["pack_leaves_ms"] = round(
        best_of(lambda: sha.pack_messages(leaf_msgs)), 2
    )
    plog(f"host packing: sigs {stages['pack_sigs_ms']}ms leaves {stages['pack_leaves_ms']}ms")

    # ---- combined single-dispatch program (headline) ----
    import jax.numpy as jnp

    verify_fn = ek.verify_core_hosthash if len(operands) == 4 else ek.verify_core

    @jax.jit
    def combined(ops, blk, nblk):
        ok = verify_fn(*ops)
        root = mk.leaves_to_root_core(blk, nblk)
        return ok, root

    dev_operands = tuple(jnp.asarray(o) for o in operands)
    dev_blocks, dev_nblocks = jnp.asarray(blocks), jnp.asarray(nblocks)

    def run_combined():
        ok, root = combined(dev_operands, dev_blocks, dev_nblocks)
        return np.asarray(ok), np.asarray(root)

    t1 = time.time()
    ok, root = run_combined()
    first = time.time() - t1
    stages["first_dispatch_s"] = round(first, 2)
    plog(f"combined first dispatch {first:.1f}s (compile or cache hit)")
    assert ok.all(), "bench batch must verify"
    from cometbft_tpu.crypto.merkle import hash_from_byte_slices

    want_root = hash_from_byte_slices(txs)
    got_root = sha.digest_words_to_bytes(root)[0]
    assert got_root == want_root, "device merkle root != host root"

    stages["combined_ms"] = round(best_of(run_combined), 3)
    plog(f"combined steady {stages['combined_ms']} ms")

    # The headline number exists now; everything below is stage diagnostics.
    # A single wedged remote compile must not discard it (the parent kills
    # this worker at TPU_TIMEOUT_S and previously fell back to CPU, losing
    # the device evidence): a deadline watchdog emits whatever stages have
    # completed and exits 0 just before the parent's timeout.
    import threading

    finished = threading.Event()
    emit_once = threading.Lock()  # exactly one thread prints the JSON line

    def _watchdog():
        delay = (t0 + TPU_TIMEOUT_S - 30) - time.time()
        if delay > 0:
            time.sleep(delay)
        with emit_once:
            if finished.is_set():
                return
            finished.set()
            try:
                # Snapshot: the main thread may be mutating stages mid-stall.
                snap = dict(stages)
            except RuntimeError:
                snap = (
                    {"combined_ms": stages["combined_ms"]}
                    if stages.get("combined_ms") is not None
                    else {}
                )
            snap["truncated"] = True
            plog("stage budget exhausted mid-stage; emitting partial result")
            try:
                emit(_pick_headline(snap), snap, devs[0].platform)
            except BaseException:
                pass
            os._exit(0)

    threading.Thread(target=_watchdog, daemon=True).start()

    # ---- hybrid tier: device share in flight + host MSM + SHA-NI merkle --
    # The candidate headline: split the 10,240-sig batch at the rate-model
    # point (device bucket lanes async, native Pippenger MSM on the rest in
    # this thread, SHA-NI merkle under the device wait), merge bitmaps.
    if budget_left():
        try:
            from cometbft_tpu import native as _native
            from cometbft_tpu.sidecar import backend as _be

            if _native.available():
                hb = _be.HybridBackend()

                def run_hybrid():
                    (hok, _bits), hroot = hb.verify_and_root(pubs, msgs, sigs, txs)
                    return hok, hroot

                hok, hroot = run_hybrid()  # first call pays the share-bucket compile
                assert hok, "hybrid batch must verify"
                assert hroot == want_root, "hybrid root != host root"
                # 10 reps (~1.5 s total): the rate EMA learns from reps 2+
                # and re-plans the split each call, so later reps run at the
                # converged balance point, and the dispatch cost's
                # run-to-run variance needs several samples.
                stages["combined_hybrid_ms"] = round(
                    best_of(run_hybrid, reps=10), 3
                )
                stages["hybrid_device_share"] = hb.last_share
                stages["hybrid_timing"] = dict(hb.last_timing)
                stages["hybrid_rates"] = {
                    "dev_sigs_per_ms": round(hb._dev_rate, 1),
                    "host_sigs_per_ms": round(hb._host_rate, 1),
                }
                plog(
                    f"hybrid combined {stages['combined_hybrid_ms']} ms "
                    f"(device share {stages['hybrid_device_share']}, "
                    f"rates d={hb._dev_rate:.0f}/h={hb._host_rate:.0f} sigs/ms, "
                    f"last timing {stages['hybrid_timing']})"
                )
            else:
                plog("hybrid stage skipped: native tier unavailable")
        except Exception as e:
            plog(f"hybrid stage failed: {type(e).__name__}: {e}")

    # ---- stage splits ----
    if budget_left():
        try:
            verify = ek._compiled(*ek._bucket_key(dev_operands))
            stages["verify_ms"] = round(
                best_of(lambda: np.asarray(verify(*dev_operands))), 3
            )
            plog(f"split: verify {stages['verify_ms']}ms")
        except Exception as e:
            plog(f"verify split failed: {type(e).__name__}: {e}")
    if budget_left():
        try:
            root_fn = mk._leaves_to_root_jit(blocks.shape[0], N_LEAVES)
            stages["merkle_ms"] = round(
                best_of(lambda: np.asarray(root_fn(dev_blocks, dev_nblocks))), 3
            )
            plog(f"split: merkle {stages['merkle_ms']}ms")
        except Exception as e:
            plog(f"merkle split failed: {type(e).__name__}: {e}")

    # ---- BASELINE #3 tail: inclusion proofs for every tx (proof.go:35) ----
    # Shipped path (proofs_from_byte_slices routes to the native SHA-NI
    # one-pass tree at this scale) is the headline; the device levels+aunts
    # program stays as a diagnostic of the on-device path.
    if budget_left():
        try:
            from cometbft_tpu.crypto.merkle import proof as _proof_mod
            from cometbft_tpu.crypto.merkle import proofs_from_byte_slices

            stages["merkle_proofs_ms"] = round(
                best_of(lambda: proofs_from_byte_slices(txs), reps=2), 1
            )
            # Host-side by default even on device runs (CMTPU_DEVICE_PROOFS=1
            # opts back into the device path, which measured ~12x slower).
            stages["merkle_proofs_path"] = _proof_mod.last_proofs_path
            plog(
                f"proofs (shipped path): {stages['merkle_proofs_ms']} ms "
                f"[{stages['merkle_proofs_path']}]"
            )
        except Exception as e:
            plog(f"proofs stage failed: {type(e).__name__}: {e}")
    if budget_left():
        try:
            mk.proofs_aunts_device(txs)  # warm the all-levels program
            stages["merkle_proofs_device_ms"] = round(
                best_of(lambda: mk.proofs_aunts_device(txs), reps=2), 1
            )
            plog(
                f"proofs (device levels + aunts): "
                f"{stages['merkle_proofs_device_ms']} ms"
            )
        except Exception as e:
            plog(f"device proofs stage failed: {type(e).__name__}: {e}")

    # ---- pod-scale mesh scaling curve (calibrated + modeled widths) ----
    if budget_left():
        try:
            stages["mesh"] = _mesh_stage_inner(plog)
            plog(
                f"mesh: width {stages['mesh']['n_devices']}, "
                f"{stages['mesh'].get('speedup_widest_vs_1')}x vs 1 device"
            )
        except Exception as e:
            plog(f"mesh stage failed: {type(e).__name__}: {e}")

    # ---- shipped-path configs (BASELINE #2/#4/#5) over the shipped
    # backend: hybrid when the native tier built, device-only otherwise ----
    try:
        from cometbft_tpu import native as _native2

        ship = "hybrid" if _native2.available() else "tpu"
    except Exception:
        ship = "tpu"
    shipped_path_stages(stages, plog, budget_left, backend=ship)

    stages["mesh_counters"] = ek.mesh_counters()
    plog(f"done on {devs[0].platform}")
    with emit_once:
        finished.set()
    emit(_pick_headline(stages), stages, devs[0].platform)


def _resilience_stage(stages: dict, plog) -> None:
    """Supervisor observability (ISSUE 2): drive a deliberately wedged
    primary tier through the ResilientBackend degradation chain and report
    the trip/degradation counters in the JSON line.  Deterministic and
    device-free — every round records what a dead tier actually costs:
    one deadline for the first call, fail-fast after the breaker opens."""
    from cometbft_tpu.sidecar.backend import CpuBackend
    from cometbft_tpu.sidecar.chaos import ChaosBackend
    from cometbft_tpu.sidecar.supervisor import ResilientBackend

    deadline_ms = 200.0
    sup = ResilientBackend(
        [
            ("tpu", ChaosBackend(CpuBackend(), "wedge:1:30000", seed=1)),
            ("cpu", CpuBackend()),
        ],
        deadline_ms=deadline_ms,
        retries=0,
        breaker_threshold=2,
        breaker_cooldown_ms=60_000,
        crosscheck="off",
    )
    pvs, pubs, msgs, sigs = _signed_batch(128, tag=b"resil")
    # Pre-warm the verified-triple cache so the measured wall isolates the
    # supervisor + wedge cost (one deadline), not the anchor's verify time
    # (that's what the other stages measure).
    CpuBackend().batch_verify(pubs, msgs, sigs)
    t1 = time.perf_counter()
    ok, bits = sup.batch_verify(pubs, msgs, sigs)
    first_ms = (time.perf_counter() - t1) * 1000
    assert ok and all(bits), "degraded result must still be correct"
    t1 = time.perf_counter()
    ok, _ = sup.batch_verify(pubs, msgs, sigs)  # wedged worker: fail fast
    second_ms = (time.perf_counter() - t1) * 1000
    assert ok
    c = sup.counters()
    stages["resilience"] = {
        "deadline_ms": deadline_ms,
        "degraded_first_call_ms": round(first_ms, 2),
        "tripped_call_ms": round(second_ms, 2),
        "active_tier": c["active_tier"],
        "trips": c["trips"],
        "deadline_exceeded": c["deadline_exceeded"],
        "degraded_calls": c["degraded_calls"],
    }
    plog(
        f"resilience: wedged-primary call {first_ms:.0f} ms "
        f"(deadline {deadline_ms:.0f}), post-trip {second_ms:.0f} ms, "
        f"active tier {c['active_tier']}, trips {c['trips']}"
    )
    sup.close()


def _coalesce_stage(stages: dict, plog) -> None:
    """Scheduler micro-batching (ISSUE 3): K concurrent SIGS-sig commit
    verifications through the coalescing scheduler vs serialized per-caller
    dispatch.  Both arms run the same commits through the same host-MSM
    backend wrapped with a fixed per-dispatch latency
    (CMTPU_BENCH_DISPATCH_MS, default 50 — a SIMULATED cost chosen on an
    earlier installation, not a measurement of this one), so the number
    reports what coalescing
    saves when every dispatch pays the device round trip: the serialized
    arm pays it K times, the coalesced arm once or twice.  The simulated
    cost is labeled in the JSON (`simulated_dispatch_ms`; set it to 0 to
    measure raw host MSM coalescing alone)."""
    import threading as _threading

    from cometbft_tpu.crypto import ed25519 as _ed
    from cometbft_tpu.sidecar import backend as _be
    from cometbft_tpu.sidecar.backend import CpuBackend
    from cometbft_tpu.sidecar.scheduler import CoalescingScheduler
    from cometbft_tpu.types import validation

    k = int(os.environ.get("CMTPU_BENCH_COALESCE_K", "8"))
    sigs = int(os.environ.get("CMTPU_BENCH_COALESCE_SIGS", "1024"))
    dispatch_ms = float(os.environ.get("CMTPU_BENCH_DISPATCH_MS", "50"))

    vals, commits = _commit_fixture(sigs, heights=k, tag=b"co")
    plog(f"coalesce fixture built ({k} x {sigs})")
    for _, commit in commits:
        commit.vote_sign_bytes_all("bench-chain")  # warm encodes, both arms

    class _DispatchLatency:
        """CpuBackend plus the fixed per-dispatch cost a device pays."""

        name = "latency"

        def __init__(self):
            self._cpu = CpuBackend()
            self.calls = 0

        def batch_verify(self, pubs, msgs, sigs_):
            self.calls += 1
            if dispatch_ms > 0:
                time.sleep(dispatch_ms / 1000.0)
            return self._cpu.batch_verify(pubs, msgs, sigs_)

        def merkle_root(self, leaves):
            return self._cpu.merkle_root(leaves)

    def _run_commit(i):
        bid, commit = commits[i]
        validation.verify_commit_light("bench-chain", vals, bid, i + 1, commit)

    old_backend = _be._backend
    try:
        # -- serialized per-caller dispatch (the pre-scheduler world) --
        lat = _DispatchLatency()
        _be.set_backend(lat)
        _ed._verified.clear()
        t0 = time.perf_counter()
        for i in range(k):
            _run_commit(i)
        serialized_ms = (time.perf_counter() - t0) * 1000
        assert lat.calls == k

        # -- coalesced: K concurrent callers through the scheduler --
        lat2 = _DispatchLatency()
        sched = CoalescingScheduler(lat2, window_ms=5.0)
        _be.set_backend(sched)
        _ed._verified.clear()
        start = _threading.Barrier(k + 1)
        errors = []

        def _caller(i):
            start.wait()
            try:
                _run_commit(i)
            except Exception as e:  # pragma: no cover - stage must report
                errors.append(e)

        threads = [
            _threading.Thread(target=_caller, args=(i,)) for i in range(k)
        ]
        for t in threads:
            t.start()
        start.wait()
        t0 = time.perf_counter()
        for t in threads:
            t.join(300.0)
        coalesced_ms = (time.perf_counter() - t0) * 1000
        if errors:
            raise errors[0]
        c = sched.counters()
        sched.close()
        stages["coalesce"] = {
            "k": k,
            "sigs_per_request": sigs,
            "simulated_dispatch_ms": dispatch_ms,
            "serialized_ms": round(serialized_ms, 2),
            "coalesced_ms": round(coalesced_ms, 2),
            "speedup": round(serialized_ms / max(coalesced_ms, 1e-9), 2),
            "serialized_dispatches": lat.calls,
            "coalesced_dispatches": lat2.calls,
            "coalesce_ratio": c["coalesce_ratio"],
            "queue_wait_p50_ms": c["queue_wait_p50_ms"],
            "queue_wait_p95_ms": c["queue_wait_p95_ms"],
            "fallback_splits": c["fallback_splits"],
        }
        plog(
            f"coalesce: {k}x{sigs} serialized {serialized_ms:.0f} ms "
            f"-> coalesced {coalesced_ms:.0f} ms "
            f"({stages['coalesce']['speedup']}x, "
            f"{lat2.calls} dispatches, ratio {c['coalesce_ratio']})"
        )
    finally:
        _ed._verified.clear()
        _be.set_backend(old_backend)


def _engine_stage(stages: dict, plog) -> None:
    """Continuous-batching engine (ISSUE 14): all four verification classes
    (consensus votes, blocksync prefetch, ingress preverify, light-client
    descent) driven concurrently through ONE VerificationEngine vs the
    pre-engine world of four independent window-then-dispatch batchers over
    the same serialized simulated device (CMTPU_BENCH_ENGINE_DISPATCH_MS
    fixed cost per dispatch, default 5 — same convention as the other
    simulated stages, labeled in the JSON).  The engine arm skips the
    admission window entirely (dispatch sizing happens when the device
    frees up), drains strict-priority so a vote never queues behind bulk,
    and deadline-caps merged growth while a vote is pending.  The headline
    metric is per-class p95 ADMISSION latency — submit until the request
    is on the device, the part of the wall the scheduler controls (both
    arms pay the same simulated dispatch once admitted; end-to-end p95s
    are reported alongside as `*_done_p95_ms`).  Acceptance: consensus
    admission p95 >= 3x better with total dispatches no higher."""
    import threading as _threading

    from cometbft_tpu.sidecar.engine import (
        CLASS_BLOCKSYNC,
        CLASS_CONSENSUS,
        CLASS_INGRESS,
        CLASS_LIGHT,
        CLASS_NAMES,
        VerificationEngine,
    )

    dispatch_ms = float(os.environ.get("CMTPU_BENCH_ENGINE_DISPATCH_MS", "5"))
    votes = int(os.environ.get("CMTPU_BENCH_ENGINE_VOTES", "40"))
    flooders = int(os.environ.get("CMTPU_BENCH_ENGINE_FLOODERS", "3"))
    window_ms = float(os.environ.get("CMTPU_BENCH_ENGINE_WINDOW_MS", "2"))

    class _SimDev:
        """Serialized simulated device: fixed dispatch cost + tiny per-sig
        cost, verdicts from a marker byte (the stage measures scheduling,
        not crypto)."""

        name = "engine-sim"

        def __init__(self):
            self.calls = 0
            self._lock = _threading.Lock()

        def batch_verify(self, pubs, msgs, sigs_, on_start=None):
            with self._lock:
                if on_start is not None:
                    on_start()  # device actually free: admission happened
                self.calls += 1
                time.sleep(dispatch_ms / 1000.0 + len(pubs) * 10e-6)
            return True, [True] * len(pubs)

        def merkle_root(self, leaves):  # pragma: no cover - unused here
            raise NotImplementedError

    def _triples(n, tag):
        pubs = [(b"%s-p-%d" % (tag, i)).ljust(32, b"\x00") for i in range(n)]
        msgs = [b"%s-m-%d" % (tag, i) for i in range(n)]
        sigs_ = [(b"%s-s-%d" % (tag, i)).ljust(64, b"\x01") for i in range(n)]
        return pubs, msgs, sigs_

    class _WindowBatcher:
        """The pre-engine per-surface pattern: a private dispatcher thread
        batches a window from the first waiter, then merges everything
        queued into one dispatch — no cross-class priority, no
        device-freed admission.  Records each request's admission wait
        (submit -> dispatch start) in `waits`."""

        def __init__(self, dev):
            self._dev = dev
            self._cond = _threading.Condition()
            self._queue = []  # (pubs, msgs, sigs, event-box)
            self._closed = False
            self.waits = []  # admission waits, ms
            self._thread = _threading.Thread(target=self._loop, daemon=True)
            self._thread.start()

        def submit(self, pubs, msgs, sigs_):
            box = {"event": _threading.Event(), "t": time.perf_counter()}
            with self._cond:
                self._queue.append((pubs, msgs, sigs_, box))
                self._cond.notify()
            return box

        def _loop(self):
            while True:
                with self._cond:
                    while not self._queue and not self._closed:
                        self._cond.wait(0.1)
                    if self._closed and not self._queue:
                        return
                time.sleep(window_ms / 1000.0)  # window from first waiter
                with self._cond:
                    batch, self._queue = self._queue, []
                ps = [p for b in batch for p in b[0]]
                ms = [m for b in batch for m in b[1]]
                ss = [s for b in batch for s in b[2]]

                def _admitted(batch=batch):
                    t_disp = time.perf_counter()
                    for _, _, _, box in batch:
                        self.waits.append((t_disp - box["t"]) * 1000)

                _, bits = self._dev.batch_verify(ps, ms, ss, on_start=_admitted)
                off = 0
                for bp, _, _, box in batch:
                    box["bits"] = bits[off : off + len(bp)]
                    off += len(bp)
                    box["event"].set()

        def close(self):
            with self._cond:
                self._closed = True
                self._cond.notify()
            self._thread.join(5.0)

    def _drive(submit):
        """Shared mixed workload.  submit(klass, n, tag) -> wait().
        Returns {class_name: [admission_ms, ...]}."""
        lat = {name: [] for name in CLASS_NAMES}
        llock = _threading.Lock()
        stop = _threading.Event()

        def _timed(klass, n, tag):
            t0 = time.perf_counter()
            submit(klass, n, tag)()
            ms = (time.perf_counter() - t0) * 1000
            with llock:
                lat[CLASS_NAMES[klass]].append(ms)

        def _flood(klass, n, tid, pause_s=0.0):
            i = 0
            while not stop.is_set():
                _timed(klass, n, b"%d-%d-%d" % (klass, tid, i))
                i += 1
                if pause_s:
                    time.sleep(pause_s)

        threads = [
            _threading.Thread(target=_flood, args=(CLASS_INGRESS, 16, t))
            for t in range(flooders)
        ]
        threads.append(
            _threading.Thread(target=_flood, args=(CLASS_BLOCKSYNC, 64, 90))
        )
        threads.append(
            _threading.Thread(
                target=_flood, args=(CLASS_LIGHT, 8, 91), kwargs={"pause_s": 0.003}
            )
        )
        for t in threads:
            t.start()
        time.sleep(0.02)  # let the floods saturate the device first
        for i in range(votes):
            _timed(CLASS_CONSENSUS, 2, b"vote-%d" % i)
            time.sleep(0.002)
        stop.set()
        for t in threads:
            t.join(60.0)
        return lat

    def _p95(xs):
        if not xs:
            return 0.0
        return sorted(xs)[min(len(xs) - 1, int(0.95 * len(xs)))]

    # -- baseline: four independent window batchers, one serialized device --
    dev_base = _SimDev()
    batchers = [_WindowBatcher(dev_base) for _ in CLASS_NAMES]

    def _submit_base(klass, n, tag):
        box = batchers[klass].submit(*_triples(n, tag))
        return lambda: box["event"].wait(60.0)

    base_lat = _drive(_submit_base)
    for b in batchers:
        b.close()

    # -- engine: one continuous-batching queue, all classes --
    dev_eng = _SimDev()
    eng = VerificationEngine(dev_eng, hold_ms=0, max_sigs=16384)
    try:
        def _submit_eng(klass, n, tag):
            fut = eng.submit(*_triples(n, tag), klass=klass)
            return lambda: fut.result(60.0)

        eng_lat = _drive(_submit_eng)
        eng_counters = eng.counters()
    finally:
        eng.close()

    per_class = {}
    for klass, name in enumerate(CLASS_NAMES):
        per_class[name] = {
            # Headline: admission wait, submit -> on the device.
            "baseline_p95_ms": round(_p95(batchers[klass].waits), 2),
            "engine_p95_ms": round(
                eng_counters["classes"][name]["p95_us"] / 1000.0, 2
            ),
            # End-to-end (admission + the shared simulated dispatch).
            "baseline_done_p95_ms": round(_p95(base_lat[name]), 2),
            "engine_done_p95_ms": round(_p95(eng_lat[name]), 2),
            "baseline_n": len(base_lat[name]),
            "engine_n": len(eng_lat[name]),
        }
    cons = per_class["consensus"]
    speedup = round(
        cons["baseline_p95_ms"] / max(cons["engine_p95_ms"], 1e-9), 2
    )
    stages["engine"] = {
        "simulated_dispatch_ms": dispatch_ms,
        "votes": votes,
        "flooders": flooders,
        "baseline_window_ms": window_ms,
        "classes": per_class,
        "baseline_dispatches": dev_base.calls,
        "engine_dispatches": dev_eng.calls,
        "consensus_p95_speedup": speedup,
        "starvation_promotions": sum(
            c["starvation_promotions"] for c in eng_counters["classes"].values()
        ),
    }
    plog(
        f"engine: consensus p95 {cons['baseline_p95_ms']} ms -> "
        f"{cons['engine_p95_ms']} ms ({speedup}x), dispatches "
        f"{dev_base.calls} -> {dev_eng.calls}"
    )


def _ingress_stage(stages: dict, plog) -> None:
    """QoS ingress admission (ISSUE 5): K concurrent senders flood signed
    envelopes; serialized per-tx verification admission (the pre-ingress
    world — every tx pays its own backend dispatch) vs the ingress
    pipeline's micro-batched pre-verification through the coalescing
    scheduler.  Same convention as the coalesce stage: both arms run the
    same host-MSM backend wrapped with a fixed per-dispatch latency
    (CMTPU_BENCH_INGRESS_DISPATCH_MS, default 5 — deliberately far below
    the coalesce stage's 50 ms simulated cost, because the serialized arm
    pays it K*TXS times and the stage must stay inside the bench budget;
    the JSON labels it)."""
    import threading as _threading

    from cometbft_tpu.abci.example.kvstore import KVStoreApplication
    from cometbft_tpu.config.config import MempoolConfig
    from cometbft_tpu.crypto import ed25519 as _ed
    from cometbft_tpu.mempool.clist_mempool import CListMempool
    from cometbft_tpu.mempool.ingress import IngressPipeline, decode_envelope, encode_envelope
    from cometbft_tpu.proxy import LocalClientCreator
    from cometbft_tpu.sidecar import backend as _be
    from cometbft_tpu.sidecar.backend import CpuBackend
    from cometbft_tpu.sidecar.scheduler import CoalescingScheduler

    k = int(os.environ.get("CMTPU_BENCH_INGRESS_SENDERS", "8"))
    per = int(os.environ.get("CMTPU_BENCH_INGRESS_TXS", "512"))
    dispatch_ms = float(os.environ.get("CMTPU_BENCH_INGRESS_DISPATCH_MS", "5"))
    total = k * per

    privs = [_ed.gen_priv_key_from_secret(b"ing-%d" % i) for i in range(k)]
    floods = [
        [
            encode_envelope(privs[i], b"ing/%d/%d=v" % (i, j), priority=i % 4, nonce=j)
            for j in range(per)
        ]
        for i in range(k)
    ]
    plog(f"ingress fixture built ({k} senders x {per} envelopes)")

    class _DispatchLatency:
        name = "latency"

        def __init__(self):
            self._cpu = CpuBackend()
            self.calls = 0

        def batch_verify(self, pubs, msgs, sigs_):
            self.calls += 1
            if dispatch_ms > 0:
                time.sleep(dispatch_ms / 1000.0)
            return self._cpu.batch_verify(pubs, msgs, sigs_)

        def merkle_root(self, leaves):
            return self._cpu.merkle_root(leaves)

    def _fresh_mempool():
        app = KVStoreApplication()
        cli = LocalClientCreator(app).new_abci_client()
        return CListMempool(MempoolConfig(size=total * 2, cache_size=total * 2), cli)

    old_backend = _be._backend
    try:
        # -- serialized: each tx verified with its own dispatch, then admitted --
        lat = _DispatchLatency()
        _be.set_backend(lat)
        _ed._verified.clear()
        mp1 = _fresh_mempool()
        start = _threading.Barrier(k + 1)

        def _serial_sender(i):
            start.wait()
            for tx in floods[i]:
                env = decode_envelope(tx)
                ok, bits = _be.get_backend().batch_verify(
                    [env.pubkey], [env.sign_bytes()], [env.signature]
                )
                if bits[0]:
                    mp1.check_tx(tx, sender=env.sender)

        threads = [_threading.Thread(target=_serial_sender, args=(i,)) for i in range(k)]
        for t in threads:
            t.start()
        start.wait()
        t0 = time.perf_counter()
        for t in threads:
            t.join(600.0)
        serialized_ms = (time.perf_counter() - t0) * 1000
        assert lat.calls == total and mp1.size() == total

        # -- batched: the ingress pipeline's micro-batched preverify --
        lat2 = _DispatchLatency()
        sched = CoalescingScheduler(lat2, window_ms=2.0)
        _be.set_backend(sched)
        _ed._verified.clear()
        mp2 = _fresh_mempool()
        ing = IngressPipeline(
            MempoolConfig(
                size=total * 2,
                cache_size=total * 2,
                ingress_queue_max=total,
                ingress_window_ms=2.0,
            ),
            mp2,
        )
        start2 = _threading.Barrier(k + 1)

        def _ingress_sender(i):
            start2.wait()
            for tx in floods[i]:
                ing.check_tx(tx)

        threads = [_threading.Thread(target=_ingress_sender, args=(i,)) for i in range(k)]
        for t in threads:
            t.start()
        start2.wait()
        t0 = time.perf_counter()
        for t in threads:
            t.join(600.0)
        deadline = time.monotonic() + 120.0
        while mp2.size() < total and time.monotonic() < deadline:
            time.sleep(0.002)
        batched_ms = (time.perf_counter() - t0) * 1000
        st = ing.stats()
        ing.close()
        sched.close()
        if mp2.size() != total:
            raise RuntimeError(f"ingress arm admitted {mp2.size()}/{total}")
        stages["ingress"] = {
            "senders": k,
            "txs_per_sender": per,
            "simulated_dispatch_ms": dispatch_ms,
            "serialized_ms": round(serialized_ms, 2),
            "batched_ms": round(batched_ms, 2),
            "speedup": round(serialized_ms / max(batched_ms, 1e-9), 2),
            "serialized_dispatches": lat.calls,
            "batched_dispatches": lat2.calls,
            "preverify_batches": st["preverify_batches"],
            "preverify_batch_max": st["preverify_batch_max"],
            "admitted": st["admitted"],
            "shed_total": st["shed_total"],
        }
        plog(
            f"ingress: {k}x{per} serialized {serialized_ms:.0f} ms "
            f"-> batched {batched_ms:.0f} ms "
            f"({stages['ingress']['speedup']}x, {lat2.calls} dispatches, "
            f"max preverify batch {st['preverify_batch_max']})"
        )
    finally:
        _ed._verified.clear()
        _be.set_backend(old_backend)


def _hotpath_stage(stages: dict, plog) -> None:
    """Consensus hot path (ISSUE 6): vote-admission micro-batching A/B plus
    a devnet before/after.

    Micro-stage: K peers x M precommits each, admitted into K INDEPENDENT
    VoteSets — one VoteSet serializes admissions on its own mutex (the
    reference's addVote locking), so the window-sharing surface is many
    in-process nodes, the devnet shape.  The serialized arm pays one device
    dispatch per vote (SigBatcher inline mode); the batched arm lets the
    concurrent admissions share CMTPU_VOTE_BATCH_WINDOW_MS windows.  Both
    arms run the same votes over the same host-crypto backend wrapped with
    a fixed per-dispatch latency (CMTPU_BENCH_HOTPATH_DISPATCH_MS, default
    20 ms, a simulated cost), and the latency backend SERIALIZES
    dispatches: one device
    executes one dispatch at a time, so overlapping the sleeps would model
    an infinitely parallel device and hide exactly the cost batching
    removes.  The simulated cost is labeled in the JSON
    (`simulated_dispatch_ms`; 0 measures raw host-crypto batching alone).

    Devnet sub-stage: the in-process devnet run twice over real TCP —
    hot-path features forced off (window 0, pipeline off, group commit off)
    vs on — reporting blocks/s + tx/s for both arms.  On one host the
    in-process nodes share the verified-triple cache and consensus is
    timeout-paced, so this arm is expected to be flat; it is reported so
    the micro-stage's dispatch-bound win is never mistaken for a claim
    about timeout-bound block rate."""
    import threading as _threading

    from cometbft_tpu.crypto import ed25519 as _ed
    from cometbft_tpu.crypto import sigbatch
    from cometbft_tpu.sidecar import backend as _be
    from cometbft_tpu.sidecar.backend import CpuBackend
    from cometbft_tpu.state import make_genesis_state
    from cometbft_tpu.types import BlockID, GenesisDoc, GenesisValidator, Time, Vote
    from cometbft_tpu.types.block import PRECOMMIT_TYPE
    from cometbft_tpu.types.part_set import PartSetHeader
    from cometbft_tpu.types.priv_validator import MockPV
    from cometbft_tpu.types.vote_set import VoteSet

    k = int(os.environ.get("CMTPU_BENCH_HOTPATH_PEERS", "8"))
    per = int(os.environ.get("CMTPU_BENCH_HOTPATH_VOTES", "16"))
    dispatch_ms = float(os.environ.get("CMTPU_BENCH_HOTPATH_DISPATCH_MS", "20"))
    chain_id = "bench-hotpath"
    bid = BlockID(b"\x01" * 32, PartSetHeader(1, b"\x02" * 32))

    class _DeviceLatency:
        """CpuBackend plus the fixed per-dispatch device cost; the lock is
        the device itself — dispatches execute one at a time."""

        name = "latency"

        def __init__(self):
            self._cpu = CpuBackend()
            self._mtx = _threading.Lock()
            self.calls = 0

        def batch_verify(self, pubs, msgs, sigs_):
            with self._mtx:
                self.calls += 1
                if dispatch_ms > 0:
                    time.sleep(dispatch_ms / 1000.0)
                return self._cpu.batch_verify(pubs, msgs, sigs_)

        def merkle_root(self, leaves):
            return self._cpu.merkle_root(leaves)

    def _mk_rig(tag):
        pvs = [MockPV() for _ in range(per)]
        gen = GenesisDoc(
            chain_id=chain_id,
            genesis_time=Time(1700000000, 0),
            validators=[
                GenesisValidator(pv.address(), pv.get_pub_key(), 10, "")
                for pv in pvs
            ],
        )
        gen.validate_and_complete()
        vals = make_genesis_state(gen).validators
        by_addr = {pv.address(): pv for pv in pvs}
        ordered = [by_addr[v.address] for v in vals.validators]
        votes = [
            pv.sign_vote(
                chain_id,
                Vote(
                    type=PRECOMMIT_TYPE, height=1, round=0, block_id=bid,
                    timestamp=Time(1700000001, tag),
                    validator_address=pv.address(), validator_index=i,
                ),
            )
            for i, pv in enumerate(ordered)
        ]
        return vals, votes

    rigs = [_mk_rig(i) for i in range(k)]
    plog(f"hotpath fixture built ({k} peers x {per} votes)")

    def _admit_arm(batcher):
        old_b = sigbatch.set_batcher(batcher)
        with _ed._verified_lock:
            _ed._verified.clear()
        errs: list[str] = []
        sums: list[int] = []
        lock = _threading.Lock()
        barrier = _threading.Barrier(k)

        def worker(vals, votes):
            vs = VoteSet(chain_id, 1, 0, PRECOMMIT_TYPE, vals)
            barrier.wait()
            for v in votes:
                try:
                    if not vs.add_vote(v):
                        with lock:
                            errs.append("vote not added")
                except Exception as e:  # noqa: BLE001
                    with lock:
                        errs.append(repr(e))
            with lock:
                sums.append(vs.sum)

        threads = [
            _threading.Thread(target=worker, args=rig, daemon=True)
            for rig in rigs
        ]
        t1 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        dt = time.perf_counter() - t1
        sigbatch.set_batcher(old_b)
        assert not errs, f"hotpath arm rejected valid votes: {errs[:3]}"
        assert sums == [per * 10] * k, "a valid vote was dropped"
        return dt

    lat = _DeviceLatency()
    old_backend = _be._backend
    _be.set_backend(lat)
    try:
        ser_s = _admit_arm(sigbatch.SigBatcher(window_ms=0, inline=True))
        ser_dispatches = lat.calls
        batched = sigbatch.SigBatcher(window_ms=2)
        bat_s = _admit_arm(batched)
        bat_dispatches = lat.calls - ser_dispatches
        bc = batched.counters()
    finally:
        with _ed._verified_lock:
            _ed._verified.clear()
        _be.set_backend(old_backend)

    st = {
        "peers": k,
        "votes_per_peer": per,
        "simulated_dispatch_ms": dispatch_ms,
        "serialized_ms": round(ser_s * 1000, 1),
        "batched_ms": round(bat_s * 1000, 1),
        "speedup": round(ser_s / bat_s, 2) if bat_s > 0 else 0.0,
        "serialized_dispatches": ser_dispatches,
        "batched_dispatches": bat_dispatches,
        "batched_max_batch": bc["max_batch"],
        "batched_fallbacks": bc["fallbacks"],
    }
    plog(
        f"hotpath votes: serialized {st['serialized_ms']:.0f} ms "
        f"({ser_dispatches} dispatches) -> batched {st['batched_ms']:.0f} ms "
        f"({bat_dispatches} dispatches, max batch {bc['max_batch']}): "
        f"{st['speedup']}x @ {dispatch_ms:.0f} ms simulated dispatch"
    )

    # ---- devnet before/after: the same system stage, features off vs on ----
    n_vals = int(os.environ.get("CMTPU_BENCH_HOTPATH_VALS", "4"))
    blocks = int(os.environ.get("CMTPU_BENCH_HOTPATH_BLOCKS", "40"))
    knobs = (
        "CMTPU_VOTE_BATCH_WINDOW_MS",
        "CMTPU_BLOCKSYNC_PIPELINE",
        "CMTPU_WAL_GROUP_MS",
    )
    saved = {kk: os.environ.get(kk) for kk in knobs}

    def _devnet_arm(window, pipeline, group):
        os.environ["CMTPU_VOTE_BATCH_WINDOW_MS"] = window
        os.environ["CMTPU_BLOCKSYNC_PIPELINE"] = pipeline
        os.environ["CMTPU_WAL_GROUP_MS"] = group
        sigbatch.reset()  # singleton re-reads the window env on next use
        with _ed._verified_lock:
            _ed._verified.clear()
        return _devnet_throughput(
            seconds=15.0, n_vals=n_vals, target_blocks=blocks
        )

    try:
        bps0, tps0 = _devnet_arm("0", "0", "0")
        bps1, tps1 = _devnet_arm("2", "1", "2")
    finally:
        for kk, vv in saved.items():
            if vv is None:
                os.environ.pop(kk, None)
            else:
                os.environ[kk] = vv
        sigbatch.reset()
    st.update(
        {
            "devnet_vals": n_vals,
            "devnet_target_blocks": blocks,
            "devnet_before_blocks_per_s": round(bps0, 2),
            "devnet_before_tx_per_s": round(tps0, 1),
            "devnet_after_blocks_per_s": round(bps1, 2),
            "devnet_after_tx_per_s": round(tps1, 1),
            "devnet_speedup": round(bps1 / bps0, 2) if bps0 > 0 else 0.0,
        }
    )
    stages["hotpath"] = st
    plog(
        f"hotpath devnet ({n_vals} vals, {blocks}-block target): "
        f"off {bps0:.2f} blocks/s {tps0:.0f} tx/s -> "
        f"on {bps1:.2f} blocks/s {tps1:.0f} tx/s ({st['devnet_speedup']}x)"
    )


def _simnet_stage(stages: dict, plog) -> None:
    """Virtual-clock scenario throughput (ISSUE 13): VALS validators commit
    BLOCKS blocks in-process on one SimClock with a seeded WAN latency
    matrix.  Three arms on the same seed — baseline, vote-admission window
    armed (the sim analog of CMTPU_VOTE_BATCH_WINDOW_MS), and tx load
    injected — reporting blocks per simulated second, the sim-time /
    wall-time acceleration, and the block-rate deltas across arms.  Knobs:
    CMTPU_BENCH_SIMNET_VALS (100), CMTPU_BENCH_SIMNET_BLOCKS (20),
    CMTPU_BENCH_SIMNET_WINDOW_MS (50)."""
    from cometbft_tpu.simnet.scenario import run_scenario

    vals = int(os.environ.get("CMTPU_BENCH_SIMNET_VALS", "") or 100)
    blocks = int(os.environ.get("CMTPU_BENCH_SIMNET_BLOCKS", "") or 20)
    window = float(os.environ.get("CMTPU_BENCH_SIMNET_WINDOW_MS", "") or 50.0)
    base = dict(
        validators=vals, blocks=blocks, seed=1234,
        max_sim_s=40.0 * blocks + 120.0,
    )

    def _arm(name: str, **kw) -> dict:
        rep = run_scenario(**{**base, **kw})
        committed = rep["height_node0"] - 1
        rate = (
            round(committed / rep["sim_time_s"], 4) if rep["sim_time_s"] else 0.0
        )
        out = {
            "ok": rep["ok"],
            "sim_blocks_per_s": rate,
            "sim_time_s": rep["sim_time_s"],
            "wall_time_s": rep["wall_time_s"],
            "accel": rep["accel"],
            "events": rep["events"],
            "vote_dispatches": rep["counters"]["vote_dispatches"],
        }
        plog(
            f"simnet[{name}]: {committed} blocks, {rate} blocks/sim-s, "
            f"{rep['accel']}x accel ({rep['wall_time_s']:.1f}s wall)"
        )
        return out

    arms = {
        "base": _arm("base"),
        "vote_window": _arm("vote_window", vote_window_ms=window),
        "tx_load": _arm("tx_load", tx_interval_s=1.0, txs_per_interval=8),
    }
    b = arms["base"]["sim_blocks_per_s"] or 1.0
    stages["simnet"] = {
        "validators": vals,
        "blocks": blocks,
        "vote_window_ms": window,
        **{f"{k}_{m}": v for k, a in arms.items() for m, v in a.items()},
        "block_rate_vote_window_ratio": round(
            arms["vote_window"]["sim_blocks_per_s"] / b, 3
        ),
        "block_rate_tx_load_ratio": round(
            arms["tx_load"]["sim_blocks_per_s"] / b, 3
        ),
    }


def _byz_stage(stages: dict, plog) -> None:
    """Byzantine simnet accountability (ISSUE 19): the same seeded scenario
    run honest, with an equivocator under a partition+heal, and with a
    vote-flooder.  Reports the evidence pipeline's sim-latency (conflict
    detection -> DuplicateVoteEvidence committed in a block), the honest
    block-rate ratio under each adversary, and post-window recovery lag.
    Knobs: CMTPU_BENCH_BYZ_VALS (20), CMTPU_BENCH_BYZ_BLOCKS (10),
    CMTPU_BENCH_BYZ_FLOOD_HZ (10)."""
    from cometbft_tpu.simnet.scenario import run_scenario

    vals = int(os.environ.get("CMTPU_BENCH_BYZ_VALS", "") or 20)
    blocks = int(os.environ.get("CMTPU_BENCH_BYZ_BLOCKS", "") or 10)
    flood_hz = float(os.environ.get("CMTPU_BENCH_BYZ_FLOOD_HZ", "") or 10.0)
    base = dict(
        validators=vals, blocks=blocks, seed=1234, jitter_ms=5.0,
        max_sim_s=40.0 * blocks + 200.0,
        partitions=[{"at_s": 20.0, "heal_s": 45.0, "fraction": 0.5}],
    )

    def _arm(name: str, **kw) -> dict:
        rep = run_scenario(**{**base, **kw})
        committed = rep["height_node0"] - 1
        rate = (
            round(committed / rep["sim_time_s"], 4) if rep["sim_time_s"] else 0.0
        )
        ev = rep["evidence"]
        out = {
            "ok": rep["ok"],
            "safety_ok": rep["safety_ok"],
            "sim_blocks_per_s": rate,
            "sim_time_s": rep["sim_time_s"],
            "accel": rep["accel"],
            "evidence_detections": ev["detections"],
            "evidence_committed": ev["committed_count"],
            "evidence_commit_sim_s": ev["first_commit_sim_s"],
            "detect_to_commit_s": ev["detect_to_commit_s"],
            "recovery_lag_s": rep["recovery"].get("recovery_lag_s"),
        }
        plog(
            f"byz[{name}]: {committed} blocks, {rate} blocks/sim-s, "
            f"safety={rep['safety_ok']}, "
            f"evidence {ev['detections']} detected / "
            f"{ev['committed_count']} committed"
            + (
                f" (detect->commit {ev['detect_to_commit_s']} sim-s)"
                if ev["detect_to_commit_s"] is not None else ""
            )
        )
        return out

    arms = {
        "honest": _arm("honest"),
        "equivocator": _arm(
            "equivocator",
            byzantine=[{
                "role": "equivocator", "node": 1, "from_s": 10.0,
                "until_s": 50.0, "only_partitioned": True,
            }],
        ),
        "vote_flood": _arm(
            "vote_flood",
            byzantine=[{
                "role": "flooder", "node": 1, "from_s": 10.0,
                "until_s": 50.0, "rate_hz": flood_hz,
            }],
        ),
    }
    b = arms["honest"]["sim_blocks_per_s"] or 1.0
    stages["byz"] = {
        "validators": vals,
        "blocks": blocks,
        "flood_hz": flood_hz,
        **{f"{k}_{m}": v for k, a in arms.items() for m, v in a.items()},
        "block_rate_equivocator_ratio": round(
            arms["equivocator"]["sim_blocks_per_s"] / b, 3
        ),
        "block_rate_vote_flood_ratio": round(
            arms["vote_flood"]["sim_blocks_per_s"] / b, 3
        ),
    }


def _lightgw_stage(stages: dict, plog) -> None:
    """Light-client gateway (ISSUE 7): N concurrent light clients sync the
    same span, independent bisections vs one shared gateway.

    Arm A (the pre-gateway world): N clients bisect serially, each with a
    cold verified-triple cache — every client re-pays every hop's
    dispatch.  Arm B: the same N clients swarm a shared LightGateway whose
    descent plan is computed once and whose hop verifications land in the
    coalescing scheduler; the clients' mandatory re-verification then hits
    the warm shared cache.  Both arms run the same host-MSM backend
    wrapped with a fixed per-dispatch latency
    (CMTPU_BENCH_LIGHTGW_DISPATCH_MS, default 20 — labeled in the JSON;
    0 measures raw host coalescing).  The stage also reports the cold-sync
    story: the MMR inclusion-proof wire size (`lightgw_proof_bytes`,
    client-verified) vs shipping every block the bisection trace touches."""
    import threading as _threading

    from cometbft_tpu.crypto import ed25519 as _ed
    from cometbft_tpu.libs.db import MemDB
    from cometbft_tpu.light.client import Client, TrustOptions
    from cometbft_tpu.light.gateway import LightGateway
    from cometbft_tpu.light.mmr import verify_inclusion
    from cometbft_tpu.light.store import LightStore
    from cometbft_tpu.sidecar import backend as _be
    from cometbft_tpu.sidecar.backend import CpuBackend
    from cometbft_tpu.sidecar.scheduler import CoalescingScheduler
    from cometbft_tpu.types import Time as _Time

    n_clients = int(os.environ.get("CMTPU_BENCH_LIGHTGW_CLIENTS", "8"))
    height = int(os.environ.get("CMTPU_BENCH_LIGHTGW_HEIGHT", "120"))
    dispatch_ms = float(os.environ.get("CMTPU_BENCH_LIGHTGW_DISPATCH_MS", "20"))

    # 32-validator sets rotating 1/height: a 1 -> height jump dilutes trust
    # below 1/3 within ~22 heights, forcing a real multi-hop descent while
    # the lazily-signed fixture stays far cheaper than the 4,096-val
    # light_bisection stage.
    chain = _LazyChain(n_vals=32, rotate=1, heights=height)
    lb1 = chain.light_block(1)
    now = lambda: _Time(1700000000 + 10 * height + 600, 0)
    opts = TrustOptions(
        period_ns=365 * 24 * 3600 * 10**9, height=1, hash=lb1.hash()
    )

    def _fresh_client(gateway=None):
        return Client(
            chain.CHAIN_ID, opts, chain.provider(), [], LightStore(MemDB()),
            gateway=gateway, gateway_proofs=False,
        )

    class _DispatchLatency:
        """CpuBackend plus the fixed per-dispatch cost a device pays."""

        name = "latency"

        def __init__(self):
            self._cpu = CpuBackend()
            self.calls = 0

        def batch_verify(self, pubs, msgs, sigs_):
            self.calls += 1
            if dispatch_ms > 0:
                time.sleep(dispatch_ms / 1000.0)
            return self._cpu.batch_verify(pubs, msgs, sigs_)

        def merkle_root(self, leaves):
            return self._cpu.merkle_root(leaves)

    # Materialize the fixture blocks (provider-side OpenSSL signing cost,
    # not client cost) and record the bisection trace for the byte count.
    warm = _fresh_client()
    lb = warm.verify_light_block_at_height(height, now=now())
    assert lb.height == height
    trace_heights = sorted(warm.store._heights())
    bisection_bytes = sum(
        len(chain.light_block(h).encode()) for h in trace_heights
    )
    plog(
        f"lightgw fixture built ({chain.built} headers, "
        f"{len(trace_heights)}-hop trace)"
    )

    old_backend = _be._backend
    try:
        # -- arm A: N independent bisections, serialized cold clients --
        lat = _DispatchLatency()
        _be.set_backend(lat)
        solo_ms = []
        for _ in range(n_clients):
            _ed._verified.clear()
            t0 = time.perf_counter()
            assert _fresh_client().verify_light_block_at_height(
                height, now=now()
            ).height == height
            solo_ms.append((time.perf_counter() - t0) * 1000)
        serialized_ms = sum(solo_ms)

        # -- arm B: shared gateway, coalesced dispatch, one warm cache --
        lat2 = _DispatchLatency()
        sched = CoalescingScheduler(lat2, window_ms=5.0)
        _be.set_backend(sched)
        _ed._verified.clear()
        gw = LightGateway(chain.CHAIN_ID, chain.provider())
        swarm_ms: list = [0.0] * n_clients
        errors: list = []
        start = _threading.Barrier(n_clients + 1)

        def _sync(i):
            try:
                start.wait()
                t0 = time.perf_counter()
                c = _fresh_client(gateway=gw)
                assert c.verify_light_block_at_height(
                    height, now=now()
                ).height == height
                swarm_ms[i] = (time.perf_counter() - t0) * 1000
                if c.gateway_stats["fallbacks"]:
                    errors.append(RuntimeError("gateway fallback in bench"))
            except Exception as e:  # pragma: no cover - stage must report
                errors.append(e)

        threads = [
            _threading.Thread(target=_sync, args=(i,)) for i in range(n_clients)
        ]
        for t in threads:
            t.start()
        start.wait()
        t0 = time.perf_counter()
        for t in threads:
            t.join(300.0)
        swarm_wall_ms = (time.perf_counter() - t0) * 1000
        for i, t in enumerate(threads):
            if t.is_alive():
                # A hung client would leave swarm_ms[i] at 0.0 and skew
                # swarm_p95/speedup — fail the stage loudly instead.
                errors.append(RuntimeError(
                    f"lightgw swarm client {i} still running after 300s join"
                ))
        if errors:
            raise errors[0]
        c = sched.counters()
        sched.close()

        # -- cold sync: one MMR proof instead of the whole trace --
        proof = gw.prove(height, anchor_height=1)
        verify_inclusion(
            proof["root"], proof["size"], height - 1,
            proof["target"]["aunts"], proof["light_block"].hash(),
        )
        verify_inclusion(
            proof["root"], proof["size"], 0, proof["anchor"]["aunts"],
            lb1.hash(),
        )

        p95 = lambda xs: sorted(xs)[max(0, int(0.95 * (len(xs) - 1)))]
        gw_stats = gw.stats()
        stages["lightgw"] = {
            "clients": n_clients,
            "height": height,
            "trace_hops": len(trace_heights),
            "simulated_dispatch_ms": dispatch_ms,
            "serialized_ms": round(serialized_ms, 2),
            "swarm_wall_ms": round(swarm_wall_ms, 2),
            "speedup": round(serialized_ms / max(swarm_wall_ms, 1e-9), 2),
            "solo_p95_ms": round(p95(solo_ms), 2),
            "swarm_p95_ms": round(p95(swarm_ms), 2),
            "serialized_dispatches": lat.calls,
            "swarm_dispatches": lat2.calls,
            "coalesce_ratio": c["coalesce_ratio"],
            "plan_misses": gw_stats["plan_misses"],
            "plan_shared": gw_stats["plan_hits"] + gw_stats["plan_waits"],
            "lightgw_proof_bytes": proof["bytes"],
            "bisection_bytes": bisection_bytes,
            "proof_bytes_ratio": round(bisection_bytes / proof["bytes"], 1),
        }
        plog(
            f"lightgw: {n_clients} clients to {height}: serialized "
            f"{serialized_ms:.0f} ms -> swarm {swarm_wall_ms:.0f} ms "
            f"({stages['lightgw']['speedup']}x, {lat2.calls} dispatches); "
            f"cold proof {proof['bytes']} B vs {bisection_bytes} B "
            f"({stages['lightgw']['proof_bytes_ratio']}x)"
        )
    finally:
        _ed._verified.clear()
        _be.set_backend(old_backend)


def _bundle_stage(stages: dict, plog) -> None:
    """Checkpoint bundles (ISSUE 20): N clients cold-sync to a checkpoint,
    one shared cached bundle vs per-client gateway proofs vs per-client
    bisection.

    Every interaction with the origin node is billed one simulated RTT
    (CMTPU_BENCH_BUNDLE_RTT_MS, default 20) and its wire bytes counted.
    The trust anchor (height 1) ships in client config — no arm pays for
    it.  Arm `bundle`: the FIRST client pulls the checkpoint artifact; the
    rest read a dumb shared cache (content addressing is what makes that
    cache safe), and the target light block rides inside the bundle — one
    origin round trip for the whole swarm.  Arm `gateway_proof`: each
    client fetches the target AND calls light_proof.  Arm `bisection`:
    each client fetches the target and bisects (no-rotation chain: the
    1 -> target hop verifies directly, so this is the floor the bundle
    trace must be bit-identical to).  The stage asserts the acceptance
    bar: >= 3x fewer origin round trips AND >= 3x fewer total wire bytes
    than the gateway-proof arm, with bundle-arm trust decisions (stored
    trace heights + hashes) bit-identical to plain bisection."""
    import threading as _threading

    from cometbft_tpu.libs.db import MemDB
    from cometbft_tpu.light.bundle import Bundle
    from cometbft_tpu.light.client import Client, TrustOptions
    from cometbft_tpu.light.gateway import LightGateway
    from cometbft_tpu.light.origin import BundleOrigin
    from cometbft_tpu.light.provider import MockProvider
    from cometbft_tpu.light.store import LightStore
    from cometbft_tpu.types import Time as _Time

    n_clients = int(os.environ.get("CMTPU_BENCH_BUNDLE_CLIENTS", "8"))
    height = int(os.environ.get("CMTPU_BENCH_BUNDLE_HEIGHT", "120"))
    interval = int(os.environ.get("CMTPU_BENCH_BUNDLE_INTERVAL", str(height)))
    rtt_ms = float(os.environ.get("CMTPU_BENCH_BUNDLE_RTT_MS", "20"))

    chain = _LazyChain(n_vals=32, rotate=0, heights=height)
    lb1 = chain.light_block(1)
    now = lambda: _Time(1700000000 + 10 * height + 600, 0)
    opts = TrustOptions(
        period_ns=365 * 24 * 3600 * 10**9, height=1, hash=lb1.hash()
    )

    origin = BundleOrigin(chain.CHAIN_ID, chain.provider(), interval=interval)
    t0 = time.perf_counter()
    bname, bdata, boundary = origin.get_encoded(0)
    build_ms = (time.perf_counter() - t0) * 1000
    anchor = Bundle.decode(bdata).anchor
    plog(
        f"bundle fixture built: checkpoint {boundary}, {len(bdata)} B "
        f"({build_ms:.0f} ms origin-side build)"
    )

    class _Meter:
        """One origin round trip = one billed RTT + the bytes shipped."""

        def __init__(self):
            self.trips = 0
            self.bytes = 0
            self._lock = _threading.Lock()

        def bill(self, nbytes):
            with self._lock:
                self.trips += 1
                self.bytes += nbytes
            if rtt_ms > 0:
                time.sleep(rtt_ms / 1000.0)

    class _RemoteProvider:
        """Height 1 is the baked-in trust root (free); everything else is
        an origin round trip."""

        def __init__(self, meter):
            self._meter = meter

        def chain_id(self):
            return chain.CHAIN_ID

        def light_block(self, h):
            lb = chain.light_block(h if h else boundary)
            if lb.height != 1:
                self._meter.bill(len(lb.encode()))
            return lb

        def report_evidence(self, ev):
            pass

    class _RemoteGateway:
        def __init__(self, gw, meter):
            self._gw = gw
            self._meter = meter

        def prove(self, height_, anchor_height=0):
            resp = self._gw.prove(height_, anchor_height=anchor_height)
            self._meter.bill(int(resp.get("bytes", 0)))
            return resp

        def plan(self, *a, **kw):
            resp = self._gw.plan(*a, **kw)
            self._meter.bill(0)
            return resp

    class _CachedSource:
        """The CDN edge: one origin pull, then every client reads the
        content-addressed blob locally."""

        def __init__(self, meter):
            self._meter = meter
            self._lock = _threading.Lock()
            self._data = None

        def bundle(self, height_=0):
            with self._lock:
                if self._data is None:
                    _, data, _ = origin.get_encoded(height_)
                    self._meter.bill(len(data))
                    self._data = data
            return self._data

    def _swarm(make_client):
        times: list = [0.0] * n_clients
        stores: list = [None] * n_clients
        errors: list = []
        start = _threading.Barrier(n_clients + 1)

        def _run(i):
            try:
                start.wait()
                t1 = time.perf_counter()
                c = make_client()
                assert c.verify_light_block_at_height(
                    boundary, now=now()
                ).height == boundary
                times[i] = (time.perf_counter() - t1) * 1000
                stores[i] = c
            except Exception as e:  # pragma: no cover - stage must report
                errors.append(e)

        threads = [
            _threading.Thread(target=_run, args=(i,)) for i in range(n_clients)
        ]
        for t in threads:
            t.start()
        start.wait()
        t1 = time.perf_counter()
        for t in threads:
            t.join(300.0)
        wall = (time.perf_counter() - t1) * 1000
        if errors:
            raise errors[0]
        if any(t.is_alive() for t in threads):
            raise RuntimeError("bundle swarm client still running after 300s")
        return times, wall, stores

    p95 = lambda xs: sorted(xs)[max(0, int(0.95 * (len(xs) - 1)))]

    def _arm(meter, times, wall):
        return {
            "origin_round_trips": meter.trips,
            "wire_bytes": meter.bytes,
            "p95_ms": round(p95(times), 2),
            "wall_ms": round(wall, 2),
        }

    # -- arm A: per-client local bisection (the reference decision) --
    m_bis = _Meter()
    times, wall, clients = _swarm(lambda: Client(
        chain.CHAIN_ID, opts, _RemoteProvider(m_bis), [], LightStore(MemDB()),
    ))
    arm_bis = _arm(m_bis, times, wall)
    ref = clients[0]
    ref_trace = {
        h: ref.store.light_block(h).hash() for h in ref.store._heights()
    }

    # -- arm B: per-client gateway MMR proofs --
    gw = LightGateway(chain.CHAIN_ID, chain.provider())
    m_gw = _Meter()
    times, wall, clients = _swarm(lambda: Client(
        chain.CHAIN_ID, opts, _RemoteProvider(m_gw), [], LightStore(MemDB()),
        gateway=_RemoteGateway(gw, m_gw), gateway_proofs=True,
    ))
    arm_gw = _arm(m_gw, times, wall)
    for c in clients:
        if c.gateway_stats["proof_syncs"] != 1:
            raise RuntimeError("gateway arm client missed the proof path")

    # -- arm C: one cached bundle for the whole swarm --
    m_bun = _Meter()
    src = _CachedSource(m_bun)
    times, wall, clients = _swarm(lambda: Client(
        chain.CHAIN_ID, opts,
        MockProvider(chain.CHAIN_ID, {1: lb1, boundary: anchor}),
        [], LightStore(MemDB()), bundle_source=src,
    ))
    arm_bun = _arm(m_bun, times, wall)
    for c in clients:
        if c.gateway_stats["bundle_syncs"] != 1 or \
                c.gateway_stats["bundle_rejects"]:
            raise RuntimeError("bundle arm client missed the bundle path")
        got = {
            h: c.store.light_block(h).hash() for h in c.store._heights()
        }
        if got != ref_trace:
            raise RuntimeError(
                "bundle trust decisions diverge from plain bisection"
            )

    trip_ratio = arm_gw["origin_round_trips"] / max(
        arm_bun["origin_round_trips"], 1
    )
    bytes_ratio = arm_gw["wire_bytes"] / max(arm_bun["wire_bytes"], 1)
    if trip_ratio < 3 or bytes_ratio < 3:
        raise RuntimeError(
            f"bundle arm below the 3x bar: trips {trip_ratio:.1f}x, "
            f"bytes {bytes_ratio:.1f}x vs gateway proofs"
        )
    stages["bundle"] = {
        "clients": n_clients,
        "height": boundary,
        "interval": interval,
        "simulated_rtt_ms": rtt_ms,
        "bundle_bytes": len(bdata),
        "bundle_name": bname,
        "origin_build_ms": round(build_ms, 1),
        "arms": {
            "bisection": arm_bis,
            "gateway_proof": arm_gw,
            "bundle": arm_bun,
        },
        "round_trips_vs_proof": round(trip_ratio, 1),
        "wire_bytes_vs_proof": round(bytes_ratio, 1),
        "trace_identical": True,
    }
    plog(
        f"bundle: {n_clients} clients to {boundary}: "
        f"{arm_bun['origin_round_trips']} origin trips / "
        f"{arm_bun['wire_bytes']} B vs gateway "
        f"{arm_gw['origin_round_trips']} / {arm_gw['wire_bytes']} B "
        f"({trip_ratio:.0f}x trips, {bytes_ratio:.1f}x bytes), "
        f"p95 {arm_bun['p95_ms']} vs {arm_gw['p95_ms']} ms"
    )


def agg_worker() -> None:
    """--agg-worker argv mode: the bn254 device multi-pairing arm in its own
    jax process (always pinned to JAX_PLATFORMS=cpu by the parent — the
    kernel's exact-f64 limb arithmetic has no TPU-native f64 path, so the
    honest device evidence on this deployment is the XLA:CPU wall; a real
    f64-capable accelerator would run the same program). Emits one AGG_JSON
    line: warm per-lane slope fit over two buckets plus accept/reject
    decision checks against the host engine."""
    t0 = time.time()

    def plog(msg):
        print(f"[agg {time.time() - t0:6.1f}s] {msg}", file=sys.stderr, flush=True)

    plog(f"start; JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}")
    import jax

    os.environ["CMTPU_BN254_DEVICE"] = "1"
    from cometbft_tpu.crypto import bn254 as b
    from cometbft_tpu.ops import bn254_kernel as bk

    result = {
        "platform": jax.devices()[0].platform,
        "width": bk.mesh_width(),
    }
    k_small, k_large = 7, 15  # +1 aggregate lane each -> buckets 8 and 16
    privs = [b.gen_priv_key() for _ in range(k_large)]
    pubs = [p.pub_key().bytes() for p in privs]
    msgs = [b"agg-bench-vote-%06d" % i for i in range(k_large)]
    sigs = [p.sign(m) for p, m in zip(privs, msgs)]
    plog(f"signed {k_large} bn254 calibration votes")
    be = bk.Bn254DeviceBackend()

    agg_small = b.aggregate_signatures(sigs[:k_small])
    t1 = time.time()
    ok = be.aggregate_verify(pubs[:k_small], msgs[:k_small], agg_small)
    result["compile_s_small"] = round(time.time() - t1, 1)
    result["accept_ok"] = bool(ok)
    # Poisoned aggregate (signer 3's message swapped) must reject, and the
    # decision must match the host engine's.
    poisoned = list(msgs[:k_small])
    poisoned[3] = b"agg-bench-vote-POISON"
    dev_reject = be.aggregate_verify(pubs[:k_small], poisoned, agg_small)
    host_reject = b.verify_aggregate(pubs[:k_small], poisoned, agg_small)
    result["reject_ok"] = (not dev_reject) and (dev_reject == host_reject)
    plog(
        f"bucket 8: compile {result['compile_s_small']}s, "
        f"accept={result['accept_ok']} poisoned-reject={result['reject_ok']}"
    )

    agg_large = b.aggregate_signatures(sigs)
    t1 = time.time()
    assert be.aggregate_verify(pubs, msgs, agg_large)
    result["compile_s_large"] = round(time.time() - t1, 1)
    w_small = best_of(
        lambda: be.aggregate_verify(pubs[:k_small], msgs[:k_small], agg_small),
        reps=3,
    )
    w_large = best_of(lambda: be.aggregate_verify(pubs, msgs, agg_large), reps=3)
    # Linear fit over the two bucket walls: slope = per-lane cost (Miller
    # scan + host f12 product share), intercept = fixed cost (dispatch +
    # the one shared final exponentiation).
    slope = max((w_large - w_small) / (k_large - k_small), 1e-6)
    intercept = max(w_small - (k_small + 1) * slope, 0.0)
    result.update(
        {
            "lanes_small": k_small + 1,
            "lanes_large": k_large + 1,
            "wall_ms_small": round(w_small, 2),
            "wall_ms_large": round(w_large, 2),
            "ms_per_lane": round(slope, 4),
            "fixed_ms": round(intercept, 2),
            "counters": bk.counters(),
        }
    )
    plog(
        f"walls {k_small + 1}: {w_small:.0f} ms, {k_large + 1}: {w_large:.0f} ms "
        f"-> {slope:.1f} ms/lane + {intercept:.0f} ms fixed"
    )
    print("AGG_JSON " + json.dumps(result), flush=True)


def _agg_worker_subprocess(timeout_s: int):
    """Launch --agg-worker with jax pinned to CPU; returns the parsed dict
    or None (never gates the JSON line)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # Same 8-chip virtual mesh as the mesh stage: exercises the kernel's
    # sharded dispatch (bit-identical lanes) even though the virtual chips
    # share one core — the width scaling is reported modeled, never as a
    # measured wall.
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    out = run_phase_logged(
        [sys.executable, "-u", __file__, "--agg-worker"], timeout_s, "agg", env=env
    )
    for line in (out or "").splitlines():
        if line.startswith("AGG_JSON "):
            try:
                return json.loads(line[len("AGG_JSON "):])
            except ValueError:
                return None
    return None


def _agg_stage(stages: dict, plog) -> None:
    """Aggregate BLS commits (ISSUE 9): A/B one CMTPU_BENCH_AGG_VALS-
    validator commit across three arms — today's scalar pure-Python pairing
    (per-vote), the host multi-pairing aggregate (n+1 Miller loops sharing
    one final exponentiation), and the device multi-pairing kernel — plus
    honest wire-byte accounting. The scalar and host arms are calibrated on
    small real walls and extrapolated linearly to the target size
    (`modeled: true`); the device arm runs in a jax subprocess and reports
    its own platform, or `absent` with the reason."""
    from cometbft_tpu.crypto import bn254 as b

    n_vals = int(os.environ.get("CMTPU_BENCH_AGG_VALS", "10240"))
    cal = int(os.environ.get("CMTPU_BENCH_AGG_CAL", "8"))
    scalar_n = int(os.environ.get("CMTPU_BENCH_AGG_SCALAR_N", "2"))
    timeout_s = int(os.environ.get("CMTPU_BENCH_AGG_TIMEOUT", "300"))

    privs = [b.gen_priv_key() for _ in range(cal)]
    pubs = [p.pub_key().bytes() for p in privs]
    msgs = [b"agg-vote-%06d" % i for i in range(cal)]
    sigs = [p.sign(m) for p, m in zip(privs, msgs)]
    plog(f"agg: signed {cal} bn254 calibration votes (target {n_vals} vals)")

    # ---- arm 1: scalar pure-Python pairing, one check per vote ----
    t1 = time.perf_counter()
    for i in range(scalar_n):
        assert b.verify_signature_slow(pubs[i], msgs[i], sigs[i])
    scalar_per_sig = (time.perf_counter() - t1) * 1000.0 / scalar_n
    scalar_modeled = scalar_per_sig * n_vals
    plog(f"agg: scalar arm {scalar_per_sig:.0f} ms/sig ({scalar_n} measured)")

    # ---- arm 2: host multi-pairing aggregate, slope fit over two sizes ----
    half = max(cal // 2, 2)
    agg_full = b.aggregate_signatures(sigs)
    agg_half = b.aggregate_signatures(sigs[:half])
    assert b.verify_aggregate(pubs, msgs, agg_full)  # warms the H(m) cache
    assert not b.verify_aggregate(pubs, list(reversed(msgs)), agg_full)
    w_half = best_of(
        lambda: b.verify_aggregate(pubs[:half], msgs[:half], agg_half), reps=2
    )
    w_full = best_of(lambda: b.verify_aggregate(pubs, msgs, agg_full), reps=2)
    host_slope = max((w_full - w_half) / (cal - half), 1e-6)
    host_fixed = max(w_half - (half + 1) * host_slope, 0.0)
    host_modeled = host_slope * (n_vals + 1) + host_fixed
    plog(
        f"agg: host arm {host_slope:.0f} ms/pair + {host_fixed:.0f} ms "
        f"shared final exp"
    )

    # ---- arm 3: device multi-pairing kernel (own jax subprocess) ----
    device = _agg_worker_subprocess(timeout_s)
    if device is None:
        device = {"absent": "agg worker failed or timed out (see .bench_agg.err)"}

    # ---- wire bytes: per-vote columns vs bitmap + one G2 point ----
    # Round 10: the block carries the 64-byte COMPRESSED aggregate; the
    # uncompressed 128-byte form is kept for comparison (pre-round-10 wire).
    agg_bytes = 64 + (n_vals + 7) // 8
    agg_bytes_uncompressed = 128 + (n_vals + 7) // 8
    ed_bytes = 64 * n_vals
    wire = {
        "vals": n_vals,
        "ed25519_per_vote_bytes": ed_bytes,
        "bn254_per_vote_bytes": 128 * n_vals,
        "aggregate_bytes": agg_bytes,
        "aggregate_bytes_uncompressed": agg_bytes_uncompressed,
        "aggregate_vs_ed25519": round(agg_bytes / ed_bytes, 5),
    }

    result = {
        "vals": n_vals,
        "modeled": True,
        "scalar": {
            "measured_sigs": scalar_n,
            "ms_per_sig": round(scalar_per_sig, 1),
            "modeled_total_ms": round(scalar_modeled, 0),
        },
        "host_aggregate": {
            "cal_pairs": cal + 1,
            "ms_per_pair": round(host_slope, 2),
            "fixed_ms": round(host_fixed, 1),
            "modeled_total_ms": round(host_modeled, 0),
            "speedup_vs_scalar": round(scalar_modeled / max(host_modeled, 1e-9), 1),
        },
        "device": device,
        "wire": wire,
    }
    if "ms_per_lane" in device:
        # Width curve is the rate model's (lanes shard data-parallel, the
        # final exponentiation stays one shared host pass) — on the virtual
        # mesh the chips share a core, so only width 1 is a measured wall.
        width = max(int(device.get("width", 1)), 1)
        curve = {}
        for w in sorted({1, width}):
            total = device["ms_per_lane"] * (n_vals + 1) / w + device["fixed_ms"]
            curve[str(w)] = {
                "modeled_total_ms": round(total, 0),
                "speedup_vs_scalar": round(scalar_modeled / max(total, 1e-9), 1),
            }
        result["device_modeled"] = curve
        result["speedup_device_vs_scalar"] = curve[str(width)][
            "speedup_vs_scalar"
        ]
        plog(
            f"agg: device arm {device['ms_per_lane']:.1f} ms/lane "
            f"[{device.get('platform')}, width {width}] -> "
            f"{result['speedup_device_vs_scalar']}x vs scalar (modeled)"
        )
    stages["agg"] = result
    plog(
        f"agg: wire {agg_bytes} B vs {ed_bytes} B ed25519 per-vote "
        f"({wire['aggregate_vs_ed25519'] * 100:.2f}%), host aggregate "
        f"{result['host_aggregate']['speedup_vs_scalar']}x vs scalar"
    )


class _LatencyRelay:
    """TCP relay that delays every forwarded buffer by a fixed latency in
    each direction (pure latency, unbounded bandwidth): the WAN shape a
    remote sidecar actually sees. Frames queued behind each other
    stay ordered but do NOT serialize on the delay — that is exactly what
    lets a pipelined client overlap wire time with device dispatch, and
    what a sequential unary client cannot exploit."""

    def __init__(self, upstream_host: str, upstream_port: int, delay_s: float):
        import socket as _socket

        self._socket = _socket
        self._up = (upstream_host, upstream_port)
        self._delay = delay_s
        self._conns: list = []
        self._lsock = _socket.socket()
        self._lsock.setsockopt(_socket.SOL_SOCKET, _socket.SO_REUSEADDR, 1)
        self._lsock.bind(("127.0.0.1", 0))
        self._lsock.listen(16)
        self.port = self._lsock.getsockname()[1]
        self.addr = f"127.0.0.1:{self.port}"
        import threading as _threading

        self._threading = _threading
        t = _threading.Thread(target=self._accept_loop, daemon=True)
        t.start()

    def _accept_loop(self):
        while True:
            try:
                down, _ = self._lsock.accept()
            except OSError:
                return
            try:
                up = self._socket.create_connection(self._up, timeout=5)
            except OSError:
                down.close()
                continue
            down.setsockopt(self._socket.IPPROTO_TCP, self._socket.TCP_NODELAY, 1)
            up.setsockopt(self._socket.IPPROTO_TCP, self._socket.TCP_NODELAY, 1)
            self._conns += [down, up]
            self._pump(down, up)
            self._pump(up, down)

    def _pump(self, src, dst):
        import queue as _queue

        q = _queue.Queue()

        def reader():
            while True:
                try:
                    data = src.recv(65536)
                except OSError:
                    data = b""
                q.put((time.perf_counter() + self._delay, data))
                if not data:
                    return

        def writer():
            while True:
                deadline, data = q.get()
                dt = deadline - time.perf_counter()
                if dt > 0:
                    time.sleep(dt)
                if not data:
                    try:
                        dst.shutdown(self._socket.SHUT_WR)
                    except OSError:
                        pass
                    return
                try:
                    dst.sendall(data)
                except OSError:
                    return

        for fn in (reader, writer):
            self._threading.Thread(target=fn, daemon=True).start()

    def close(self):
        try:
            self._lsock.close()
        except OSError:
            pass
        for s in self._conns:
            try:
                s.close()
            except OSError:
                pass


def _sidecar_stage(stages: dict, plog) -> None:
    """Pod-scale sidecar streaming (ISSUE 10): one big BatchVerify against a
    remote sidecar behind a latency relay (every buffer delayed RTT/2 per
    direction) with a fixed simulated per-dispatch device cost on the
    server. The unary baseline splits the batch into chunk-sized requests
    and pays the full round trip per chunk, serially — the pre-round-10
    remote path under a frame cap. The streamed arm sends the same chunks
    through the windowed chunk protocol, overlapping wire time with device
    dispatch. Both simulated costs are labeled (`simulated_rtt_ms`,
    `simulated_dispatch_ms`; zero them to measure raw framing overhead).
    Also reports the server-side cross-connection merge ratio from
    concurrent unary clients, and asserts every bitmap bit-identical to the
    in-process CPU backend."""
    import threading as _threading

    from cometbft_tpu.sidecar.backend import CpuBackend
    from cometbft_tpu.sidecar.service import GrpcBackend, SidecarServer

    n = int(os.environ.get("CMTPU_BENCH_SIDECAR_SIGS", "512"))
    chunk = int(os.environ.get("CMTPU_BENCH_SIDECAR_CHUNK", "16"))
    rtt_ms = float(os.environ.get("CMTPU_BENCH_SIDECAR_RTT_MS", "40"))
    dispatch_ms = float(os.environ.get("CMTPU_BENCH_SIDECAR_DISPATCH_MS", "5"))

    _, pubs, msgs, sigs = _signed_batch(n, tag=b"sidecar")
    for i in (3, n // 2, n - 2):  # non-trivial bitmap
        sigs[i] = sigs[i][:-1] + bytes([sigs[i][-1] ^ 1])
    cpu = CpuBackend()
    expect_ok, expect_bits = cpu.batch_verify(pubs, msgs, sigs)  # also warms

    class _DispatchLatency:
        name = "latency"

        def __init__(self):
            self._cpu = CpuBackend()

        def batch_verify(self, pubs_, msgs_, sigs_):
            if dispatch_ms > 0:
                time.sleep(dispatch_ms / 1000.0)
            return self._cpu.batch_verify(pubs_, msgs_, sigs_)

        def merkle_root(self, leaves):
            return self._cpu.merkle_root(leaves)

    old_chunk_env = os.environ.get("CMTPU_SIDECAR_CHUNK")
    os.environ["CMTPU_SIDECAR_CHUNK"] = str(chunk)
    server = relay = client = None
    try:
        server = SidecarServer("127.0.0.1:0", backend=_DispatchLatency())
        server.addr = "127.0.0.1:%d" % server._server.server_address[1]
        server.start()
        relay = _LatencyRelay(
            "127.0.0.1", server._server.server_address[1], rtt_ms / 2000.0
        )
        client = GrpcBackend(relay.addr, timeout_s=120)
        n_chunks = (n + chunk - 1) // chunk

        # -- unary baseline: one frame-capped request per chunk, serial --
        t0 = time.perf_counter()
        un_bits: list = []
        un_ok = True
        for s in range(0, n, chunk):
            ok, bits = client.batch_verify(
                pubs[s : s + chunk], msgs[s : s + chunk], sigs[s : s + chunk]
            )
            un_ok = un_ok and ok
            un_bits.extend(bits)
        unary_ms = (time.perf_counter() - t0) * 1000
        assert client.counters_["unary_calls"] == n_chunks

        # -- streamed: the same chunks pipelined down one connection --
        t0 = time.perf_counter()
        st_ok, st_bits = client.batch_verify(pubs, msgs, sigs)
        streamed_ms = (time.perf_counter() - t0) * 1000
        c = client.counters()
        assert c["streamed_calls"] == 1 and c["streamed_chunks"] == n_chunks

        bit_identical = (
            un_bits == expect_bits
            and st_bits == expect_bits
            and un_ok == expect_ok
            and st_ok == expect_ok
        )
        if not bit_identical:  # pragma: no cover - acceptance guard
            raise AssertionError("sidecar bitmaps diverged from CPU backend")
    finally:
        if old_chunk_env is None:
            os.environ.pop("CMTPU_SIDECAR_CHUNK", None)
        else:
            os.environ["CMTPU_SIDECAR_CHUNK"] = old_chunk_env
        if client is not None:
            client.close()
        if relay is not None:
            relay.close()
        if server is not None:
            server.shutdown()

    # -- cross-connection merge: concurrent unary clients, fresh server --
    k_merge = 3
    old_window = os.environ.get("CMTPU_COALESCE_WINDOW_MS")
    os.environ["CMTPU_COALESCE_WINDOW_MS"] = "50"
    merge_server = None
    merge_clients: list = []
    try:
        merge_server = SidecarServer("127.0.0.1:0", backend=_DispatchLatency())
        merge_server.addr = (
            "127.0.0.1:%d" % merge_server._server.server_address[1]
        )
        merge_server.start()
        merge_clients = [
            GrpcBackend(merge_server.addr, timeout_s=60) for _ in range(k_merge)
        ]
        span = n // k_merge
        start = _threading.Barrier(k_merge)
        merge_errors: list = []

        def _merge_caller(i):
            s = i * span
            start.wait()
            try:
                ok, bits = merge_clients[i].batch_verify(
                    pubs[s : s + span], msgs[s : s + span], sigs[s : s + span]
                )
                assert bits == expect_bits[s : s + span]
            except Exception as e:  # pragma: no cover - stage must report
                merge_errors.append(e)

        threads = [
            _threading.Thread(target=_merge_caller, args=(i,))
            for i in range(k_merge)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120.0)
        if merge_errors:
            raise merge_errors[0]
        mc = merge_server.scheduler_counters()
    finally:
        if old_window is None:
            os.environ.pop("CMTPU_COALESCE_WINDOW_MS", None)
        else:
            os.environ["CMTPU_COALESCE_WINDOW_MS"] = old_window
        for mcli in merge_clients:
            mcli.close()
        if merge_server is not None:
            merge_server.shutdown()

    stages["sidecar"] = {
        "sigs": n,
        "chunk": chunk,
        "n_chunks": n_chunks,
        "simulated_rtt_ms": rtt_ms,
        "simulated_dispatch_ms": dispatch_ms,
        "unary_ms": round(unary_ms, 2),
        "streamed_ms": round(streamed_ms, 2),
        "speedup": round(unary_ms / max(streamed_ms, 1e-9), 2),
        "streamed_chunks": c["streamed_chunks"],
        "stream_retries": c["stream_retries"],
        "bitmap_identical": bit_identical,
        "merge": {
            "clients": k_merge,
            "requests": mc.get("requests", 0),
            "coalesced_dispatches": mc.get("coalesced_dispatches", 0),
            "batched_requests": mc.get("batched_requests", 0),
            "coalesce_ratio": mc.get("coalesce_ratio", 0),
        },
    }
    plog(
        f"sidecar: {n} sigs/{n_chunks} chunks @ rtt {rtt_ms} ms: "
        f"unary {unary_ms:.0f} ms -> streamed {streamed_ms:.0f} ms "
        f"({stages['sidecar']['speedup']}x), merge ratio "
        f"{stages['sidecar']['merge']['coalesce_ratio']}"
    )


def _fanout_stage(stages: dict, plog) -> None:
    """Multi-host fan-out (ISSUE 15): one batch split into width-weighted
    slices across N sidecar shards, each behind its own latency relay and
    a simulated rate-model device (fixed dispatch cost + n/rate ms, real
    CPU bits). Three arms: 1 shard (everything serial through one host),
    N shards (slices dispatched concurrently — the fleet), and N shards
    with one WEDGED (its slice must time out and redistribute across the
    survivors, completing with redistribution counter > 0). All simulated
    costs are labeled; every arm's bitmap is asserted bit-identical to the
    in-process CPU backend."""
    from cometbft_tpu.sidecar.backend import CpuBackend
    from cometbft_tpu.sidecar.fanout import FanoutBackend
    from cometbft_tpu.sidecar.service import GrpcBackend, SidecarServer

    n = int(os.environ.get("CMTPU_BENCH_FANOUT_SIGS", "2048"))
    n_shards = int(os.environ.get("CMTPU_BENCH_FANOUT_SHARDS", "4"))
    rate = float(os.environ.get("CMTPU_BENCH_FANOUT_RATE", "2.0"))
    dispatch_ms = float(os.environ.get("CMTPU_BENCH_FANOUT_DISPATCH_MS", "5"))
    rtt_ms = float(os.environ.get("CMTPU_BENCH_FANOUT_RTT_MS", "20"))
    # Wide enough that the serial 1-shard arm (the whole batch through one
    # host, plus real CPU verification) never trips it — only the wedged
    # shard's slice should time out.
    deadline_ms = float(
        os.environ.get("CMTPU_BENCH_FANOUT_DEADLINE_MS", "4000")
    )

    _, pubs, msgs, sigs = _signed_batch(n, tag=b"fanout")
    for i in (1, n // 3, n - 5):  # non-trivial bitmap
        sigs[i] = sigs[i][:-1] + bytes([sigs[i][-1] ^ 1])
    cpu = CpuBackend()
    expect_ok, expect_bits = cpu.batch_verify(pubs, msgs, sigs)
    # The shard servers answer from this table (real bits, computed ONCE by
    # the CPU backend above) instead of re-running crypto: all N "shards"
    # live in this one process, so real verification would serialize on the
    # GIL and dilute the dispatch-orchestration speedup this stage measures.
    # Slicing/reassembly correctness is still exercised for real — a
    # misplaced slice boundary scrambles which lanes carry the flipped bits.
    table = {
        (p, m, s): b for p, m, s, b in zip(pubs, msgs, sigs, expect_bits)
    }

    wedge_s = deadline_ms * 3 / 1000.0

    class _RateModel:
        """Simulated per-shard device: fixed dispatch cost + n/rate ms,
        bits from the precomputed table — shard walls scale with slice
        size, so splitting the batch is what buys the speedup."""

        name = "ratemodel"

        def __init__(self):
            self.wedged = False

        def batch_verify(self, pubs_, msgs_, sigs_):
            if self.wedged:
                time.sleep(wedge_s)
            time.sleep((dispatch_ms + len(pubs_) / rate) / 1000.0)
            bits = [
                table.get((p, m, s), False)
                for p, m, s in zip(pubs_, msgs_, sigs_)
            ]
            return all(bits), bits

        def merkle_root(self, leaves):
            return cpu.merkle_root(leaves)

    # Inline dispatch on the shard servers (no coalescer): the wedge sleep
    # must live in a disposable handler thread, not a dispatcher the
    # server shutdown would wait on.
    old_coalesce = os.environ.get("CMTPU_COALESCE")
    os.environ["CMTPU_COALESCE"] = "0"
    servers: list = []
    relays: list = []
    backends: list = []
    try:
        for _ in range(n_shards):
            backend = _RateModel()
            backends.append(backend)
            srv = SidecarServer("127.0.0.1:0", backend=backend).start()
            servers.append(srv)
            relays.append(
                _LatencyRelay(
                    "127.0.0.1",
                    srv._server.server_address[1],
                    rtt_ms / 2000.0,
                )
            )

        def run_arm(k: int):
            fan = FanoutBackend(
                [
                    (f"shard{i}", GrpcBackend(relays[i].addr, timeout_s=120))
                    for i in range(k)
                ],
                deadline_ms=deadline_ms,
            )
            try:
                t0 = time.perf_counter()
                ok, bits = fan.batch_verify(pubs, msgs, sigs)
                wall = (time.perf_counter() - t0) * 1000
                return wall, ok, bits, fan.counters()
            finally:
                fan.close()

        one_ms, ok1, bits1, _ = run_arm(1)
        n_ms, okn, bitsn, cn = run_arm(n_shards)
        backends[-1].wedged = True  # one sick host for the last arm
        wedged_ms, okw, bitsw, cw = run_arm(n_shards)

        bit_identical = (
            bits1 == expect_bits
            and bitsn == expect_bits
            and bitsw == expect_bits
            and ok1 == okn == okw == expect_ok
        )
        if not bit_identical:  # pragma: no cover - acceptance guard
            raise AssertionError("fanout bitmaps diverged from CPU backend")
        if cw["redistributions"] < 1:  # pragma: no cover - acceptance guard
            raise AssertionError("wedged-shard arm never redistributed")
    finally:
        if old_coalesce is None:
            os.environ.pop("CMTPU_COALESCE", None)
        else:
            os.environ["CMTPU_COALESCE"] = old_coalesce
        for r in relays:
            r.close()
        for s in servers:
            s.shutdown()

    stages["fanout"] = {
        "sigs": n,
        "shards": n_shards,
        "shard_widths": {k: v["width"] for k, v in cn["shards"].items()},
        "simulated_rate_sigs_per_ms": rate,
        "simulated_dispatch_ms": dispatch_ms,
        "simulated_rtt_ms": rtt_ms,
        "deadline_ms": deadline_ms,
        "one_shard_ms": round(one_ms, 2),
        "n_shard_ms": round(n_ms, 2),
        "speedup": round(one_ms / max(n_ms, 1e-9), 2),
        "wedged_ms": round(wedged_ms, 2),
        "redistributions": cw["redistributions"],
        "redistributed_sigs": cw["redistributed_sigs"],
        "bitmap_identical": bit_identical,
    }
    plog(
        f"fanout: {n} sigs @ rate {rate}/ms, rtt {rtt_ms} ms: "
        f"1 shard {one_ms:.0f} ms -> {n_shards} shards {n_ms:.0f} ms "
        f"({stages['fanout']['speedup']}x); wedged arm {wedged_ms:.0f} ms, "
        f"{cw['redistributions']} redistribution(s)"
    )


def _recvq_stage(stages: dict, plog) -> None:
    """Recv-path QoS: block-part delivery p95 on a flooded connection,
    prioritized demux vs the serialized baseline.

    One real MConnection pair over a socketpair.  The receiver's on_receive
    simulates reactor work (CMTPU_BENCH_RECVQ_HANDLE_MS per message — the
    cost that serializes the legacy recv path).  Phase 1 lands a burst of
    FLOOD mempool messages; phase 2 sends PARTS consensus-data messages
    ("block parts") at a steady cadence while the flood backlog drains.
    Baseline (CMTPU_RECVQ=0): each part waits behind every queued mempool
    message.  Demux: the drain loop delivers consensus first, so part
    latency collapses to ~one handler slot.  Both arms must deliver
    bit-identical per-channel payload sequences (the demux reorders only
    ACROSS channels, never within one)."""
    import threading

    from cometbft_tpu.p2p.conn.connection import ChannelDescriptor, MConnection

    flood_n = int(os.environ.get("CMTPU_BENCH_RECVQ_FLOOD", "300"))
    parts_n = int(os.environ.get("CMTPU_BENCH_RECVQ_PARTS", "20"))
    handle_ms = float(os.environ.get("CMTPU_BENCH_RECVQ_HANDLE_MS", "2"))
    CONS, MEMP = 0x21, 0x30
    # Small flood payloads: the whole burst must fit in the socketpair's
    # kernel buffer so the baseline backlog forms in the recv PROCESSING
    # path (the serialization under test), not in sendall().
    flood_msgs = [b"tx-%06d" % i for i in range(flood_n)]
    part_msgs = [bytes([j % 256]) * 64 + b"part-%04d" % j for j in range(parts_n)]

    def run_arm(demux: bool):
        old_q = os.environ.get("CMTPU_RECVQ")
        old_max = os.environ.get("CMTPU_RECVQ_MAX")
        os.environ["CMTPU_RECVQ"] = "1" if demux else "0"
        # No shedding in the A/B: bit-identity requires every message.
        os.environ["CMTPU_RECVQ_MAX"] = str(flood_n + parts_n + 64)
        a, b = socket.socketpair()
        try:
            seqs: dict[int, list] = {CONS: [], MEMP: []}
            lat: list[float] = []
            send_t: dict[bytes, float] = {}
            done = threading.Event()

            def on_recv(ch, msg):
                time.sleep(handle_ms / 1000.0)  # simulated reactor work
                if ch == CONS:
                    lat.append(time.perf_counter() - send_t[msg])
                seqs[ch].append(msg)
                if len(seqs[CONS]) == parts_n and len(seqs[MEMP]) == flood_n:
                    done.set()

            descs = [
                ChannelDescriptor(CONS, priority=10, send_queue_capacity=8192),
                ChannelDescriptor(MEMP, priority=5, send_queue_capacity=8192),
            ]
            recv_c = MConnection(b, list(descs), on_recv, lambda e: None)
            send_c = MConnection(
                a, list(descs), lambda *x: None, lambda e: None
            )
            recv_c.start()
            send_c.start()
            for m in flood_msgs:
                if not send_c.send(MEMP, m):
                    raise AssertionError("flood send failed")
            # Let the flood reach the wire before the first part goes out
            # (the backlog must already be in front of it).
            time.sleep(5 * handle_ms / 1000.0)
            for m in part_msgs:
                send_t[m] = time.perf_counter()
                if not send_c.send(CONS, m):
                    raise AssertionError("part send failed")
                time.sleep(2 * handle_ms / 1000.0)
            if not done.wait(timeout=60 + (flood_n + parts_n) * handle_ms / 500):
                raise AssertionError(
                    f"arm incomplete: {len(seqs[CONS])}/{parts_n} parts, "
                    f"{len(seqs[MEMP])}/{flood_n} flood"
                )
            st = recv_c.recvq_stats()
            send_c.stop()
            recv_c.stop()
            return lat, seqs, st
        finally:
            for sock in (a, b):
                try:
                    sock.close()
                except OSError:
                    pass
            for key, old in (("CMTPU_RECVQ", old_q), ("CMTPU_RECVQ_MAX", old_max)):
                if old is None:
                    os.environ.pop(key, None)
                else:
                    os.environ[key] = old

    def p95(xs):
        s = sorted(xs)
        return s[min(len(s) - 1, int(0.95 * len(s)))] * 1000.0

    base_lat, base_seqs, _ = run_arm(demux=False)
    demux_lat, demux_seqs, demux_stats = run_arm(demux=True)
    order_identical = (
        base_seqs[CONS] == demux_seqs[CONS] == part_msgs
        and base_seqs[MEMP] == demux_seqs[MEMP] == flood_msgs
    )
    if not order_identical:  # pragma: no cover - acceptance guard
        raise AssertionError("recvq per-channel delivery order diverged")
    base_p95, demux_p95 = p95(base_lat), p95(demux_lat)
    if base_p95 < 2.0 * demux_p95:  # pragma: no cover - acceptance guard
        raise AssertionError(
            f"recvq demux p95 {demux_p95:.2f} ms not >=2x better than "
            f"serialized {base_p95:.2f} ms"
        )
    stages["recvq"] = {
        "flood_msgs": flood_n,
        "parts": parts_n,
        "simulated_handle_ms": handle_ms,
        "baseline_p95_ms": round(base_p95, 2),
        "demux_p95_ms": round(demux_p95, 2),
        "speedup": round(base_p95 / max(demux_p95, 1e-9), 2),
        "order_identical": order_identical,
        "demux_delivered": demux_stats.get("delivered_total", 0),
        "demux_promoted": demux_stats.get("promoted_total", 0),
        "demux_shed": demux_stats.get("shed_total", 0),
    }
    plog(
        f"recvq: {flood_n} flood + {parts_n} parts @ {handle_ms} ms/handle: "
        f"part p95 {base_p95:.1f} ms serialized -> {demux_p95:.1f} ms demux "
        f"({stages['recvq']['speedup']}x), per-channel order identical"
    )


def shipped_path_stages(stages: dict, plog, budget_left, backend: str) -> None:
    """BASELINE.md configs measured through the SHIPPED call path
    (types/validation -> crypto.batch -> backend), shared by the TPU worker
    and the CPU fallback so every round records them: VerifyCommitLight over
    a real N_SIGS-validator commit, the BS_BLOCKS x BS_VALS blocksync-replay
    shape, and a multi-hop light bisection to height 500."""
    if budget_left():
        try:
            _resilience_stage(stages, plog)
        except Exception as e:
            plog(f"resilience stage failed: {type(e).__name__}: {e}")
    if budget_left():
        os.environ["CMTPU_BACKEND"] = backend
        from cometbft_tpu.sidecar import backend as be

        be.set_backend(None)
        from cometbft_tpu.types import validation

        from cometbft_tpu.crypto import ed25519 as _ed

        vals, commits = _commit_fixture(N_SIGS, heights=1)
        bid, commit = commits[0]
        plog(f"commit fixture built ({N_SIGS} validators)")
        validation.verify_commit_light("bench-chain", vals, bid, 1, commit)  # warm

        def _cold_verify():
            # The verified-triple cache would otherwise make every rep after
            # the first a cache hit; the e2e number must measure real crypto.
            _ed._verified.clear()
            validation.verify_commit_light("bench-chain", vals, bid, 1, commit)

        stages["commit_light_e2e_ms"] = round(best_of(_cold_verify), 2)
        # The cached path IS production behavior (blocksync re-verifies the
        # same commits in ApplyBlock) — report it separately, labeled.
        stages["commit_light_cached_ms"] = round(
            best_of(
                lambda: validation.verify_commit_light(
                    "bench-chain", vals, bid, 1, commit
                )
            ),
            2,
        )
        plog(
            f"VerifyCommitLight e2e {stages['commit_light_e2e_ms']} ms "
            f"(cached {stages['commit_light_cached_ms']} ms)"
        )

    # ---- blocksync replay: 100 blocks x 1,024-validator commits ----
    if budget_left():
        from cometbft_tpu.types import validation

        vals1k, commits1k = _commit_fixture(BS_VALS, heights=BS_BLOCKS, tag=b"bs")
        plog(f"blocksync fixture built ({BS_BLOCKS} x {BS_VALS})")
        t1 = time.perf_counter()
        for h, (bid, commit) in enumerate(commits1k, start=1):
            validation.verify_commit_light("bench-chain", vals1k, bid, h, commit)
        dt = time.perf_counter() - t1
        stages["blocksync_replay_ms_per_block"] = round(dt * 1000 / len(commits1k), 2)
        plog(
            f"blocksync replay {dt:.1f}s "
            f"({stages['blocksync_replay_ms_per_block']} ms/block)"
        )

    # ---- scheduler micro-batching: coalesced vs serialized dispatch ----
    if budget_left():
        try:
            _coalesce_stage(stages, plog)
        except Exception as e:
            plog(f"coalesce stage failed: {type(e).__name__}: {e}")

    # ---- QoS ingress: batched preverify admission vs per-tx dispatch ----
    if budget_left():
        try:
            _ingress_stage(stages, plog)
        except Exception as e:
            plog(f"ingress stage failed: {type(e).__name__}: {e}")

    # ---- consensus hot path: micro-batched vote admission + devnet A/B ----
    if budget_left():
        try:
            _hotpath_stage(stages, plog)
        except Exception as e:
            plog(f"hotpath stage failed: {type(e).__name__}: {e}")

    # ---- light gateway: shared-plan swarm vs independent bisections ----
    if budget_left():
        try:
            _lightgw_stage(stages, plog)
        except Exception as e:
            plog(f"lightgw stage failed: {type(e).__name__}: {e}")

    # ---- checkpoint bundles: cached artifact vs proofs vs bisection ----
    if budget_left():
        try:
            _bundle_stage(stages, plog)
        except Exception as e:
            plog(f"bundle stage failed: {type(e).__name__}: {e}")

    # ---- simnet: virtual-clock 100-node scenario, sim vs wall time ----
    if budget_left():
        try:
            _simnet_stage(stages, plog)
        except Exception as e:
            plog(f"simnet stage failed: {type(e).__name__}: {e}")

    # ---- byz: byzantine simnet arms, evidence-commit latency ----
    if budget_left():
        try:
            _byz_stage(stages, plog)
        except Exception as e:
            plog(f"byz stage failed: {type(e).__name__}: {e}")

    # ---- aggregate BLS commits: scalar / host / device multi-pairing ----
    if budget_left():
        try:
            _agg_stage(stages, plog)
        except Exception as e:
            plog(f"agg stage failed: {type(e).__name__}: {e}")

    # ---- pod-scale sidecar: unary vs streamed at simulated RTT ----
    if budget_left():
        try:
            _sidecar_stage(stages, plog)
        except Exception as e:
            plog(f"sidecar stage failed: {type(e).__name__}: {e}")

    # ---- continuous-batching engine: one queue vs four windows ----
    if budget_left():
        try:
            _engine_stage(stages, plog)
        except Exception as e:
            plog(f"engine stage failed: {type(e).__name__}: {e}")

    # ---- multi-host fan-out: 1 shard vs N shards vs N-with-one-wedged ----
    if budget_left():
        try:
            _fanout_stage(stages, plog)
        except Exception as e:
            plog(f"fanout stage failed: {type(e).__name__}: {e}")

    # ---- recv-path QoS: prioritized demux vs serialized recv ----
    if budget_left():
        try:
            _recvq_stage(stages, plog)
        except Exception as e:
            plog(f"recvq stage failed: {type(e).__name__}: {e}")

    # ---- BASELINE #3 tail on the host tier: all inclusion proofs ----
    if budget_left() and backend == "cpu":
        from cometbft_tpu.crypto.merkle import proof as _proof_mod
        from cometbft_tpu.crypto.merkle import proofs_from_byte_slices

        txs = [b"bench-tx-%08d" % i for i in range(N_LEAVES)]
        stages["merkle_proofs_ms"] = round(
            best_of(lambda: proofs_from_byte_slices(txs), reps=2), 1
        )
        # Which implementation served the shipped call (host by default;
        # device only under CMTPU_DEVICE_PROOFS=1).
        stages["merkle_proofs_path"] = _proof_mod.last_proofs_path
        plog(
            f"proofs (host) @{N_LEAVES}: {stages['merkle_proofs_ms']} ms "
            f"[{stages['merkle_proofs_path']}]"
        )

    # ---- system level: 4-validator devnet over real TCP, tx throughput ----
    if budget_left():
        try:
            bps, tps = _devnet_throughput(seconds=12)
            stages["devnet_blocks_per_s"] = round(bps, 2)
            stages["devnet_tx_per_s"] = round(tps, 1)
            plog(f"devnet: {bps:.2f} blocks/s, {tps:.0f} tx/s (4 vals, TCP)")
        except Exception as e:
            plog(f"devnet stage failed: {type(e).__name__}: {e}")

    # ---- loadtime: sustained-load block-interval/latency report over
    # >= 100 blocks (test/loadtime + e2e/runner/benchmark.go:14-56) ----
    if budget_left():
        try:
            from cometbft_tpu.loadtime import run_load

            rep = run_load(rate=200, min_blocks=100, timeout_s=60)
            stages["loadtime"] = {
                "blocks": rep.blocks,
                "tx_per_s": round(rep.tx_per_s, 1),
                "block_interval_mean_s": round(rep.block_interval_mean_s, 4),
                "block_interval_stddev_s": round(rep.block_interval_stddev_s, 4),
                "block_interval_min_s": round(rep.block_interval_min_s, 4),
                "block_interval_max_s": round(rep.block_interval_max_s, 4),
                "tx_latency_p50_s": round(rep.tx_latency_p50_s, 4),
                "tx_latency_p95_s": round(rep.tx_latency_p95_s, 4),
            }
            plog(
                f"loadtime: {rep.blocks} blocks @ {rep.tx_per_s:.0f} tx/s, "
                f"interval {rep.block_interval_mean_s*1000:.0f}"
                f"±{rep.block_interval_stddev_s*1000:.0f} ms, "
                f"tx p50 {rep.tx_latency_p50_s*1000:.0f} ms"
            )
        except Exception as e:
            plog(f"loadtime stage failed: {type(e).__name__}: {e}")

    # ---- light-client bisection to height 500 over 4,096-val sets ----
    if budget_left():
        from cometbft_tpu.libs.db import MemDB
        from cometbft_tpu.light.client import Client, TrustOptions
        from cometbft_tpu.light.store import LightStore
        from cometbft_tpu.types import Time as _Time

        chain = _LazyChain(n_vals=LIGHT_VALS, rotate=max(1, LIGHT_VALS // 512))
        lb1 = chain.light_block(1)
        now = lambda: _Time(1700000000 + 10 * 500 + 600, 0)
        opts = TrustOptions(
            period_ns=365 * 24 * 3600 * 10**9, height=1, hash=lb1.hash()
        )
        # Pass 1 materializes the lazily-signed fixture blocks the bisection
        # touches (8k+ OpenSSL signs — provider cost, not client cost); the
        # measured pass re-runs a FRESH client/store over the warm fixture
        # with the verified-triple cache cleared, so the number is the
        # client's verification work for the 500-header skipping trace.
        lb = Client(
            chain.CHAIN_ID, opts, chain.provider(), [], LightStore(MemDB())
        ).verify_light_block_at_height(500, now=now())
        assert lb.height == 500
        built = chain.built
        _ed._verified.clear()
        client = Client(
            chain.CHAIN_ID, opts, chain.provider(), [], LightStore(MemDB())
        )
        t1 = time.perf_counter()
        lb = client.verify_light_block_at_height(500, now=now())
        dt = time.perf_counter() - t1
        assert lb.height == 500
        stages["light_bisection_ms"] = round(dt * 1000, 2)
        plog(
            f"light bisection to 500: {dt * 1000:.0f} ms "
            f"({built} headers built)"
        )

    # Live supervisor counters when the shipped backend is the supervised
    # chain (CMTPU_BACKEND=auto): any degradations/trips the stages above
    # actually caused land in the JSON line.
    from cometbft_tpu.sidecar import backend as _be_mod

    live = _be_mod._backend
    if live is not None and hasattr(live, "counters"):
        stages["backend_counters"] = live.counters()


def cpu_fallback() -> None:
    """Stage 4: the host-tier C-speed path (what CpuBackend actually runs),
    plus the shipped-path stage configs so a device-less round still records
    the BASELINE numbers."""
    from cometbft_tpu.crypto import ed25519
    from cometbft_tpu.crypto.merkle import hash_from_byte_slices

    from cometbft_tpu import native
    from cometbft_tpu.sidecar import backend as be

    os.environ["CMTPU_BACKEND"] = "cpu"  # keep get_backend() away from jax
    be.set_backend(None)
    log(f"cpu fallback: building {N_SIGS} signed messages")
    pvs, pubs, msgs, sigs = _signed_batch(N_SIGS)
    keys = [ed25519.PubKey(p) for p in pubs]
    txs = [b"bench-tx-%08d" % i for i in range(N_LEAVES)]
    log("cpu fallback: measuring")
    best = float("inf")
    for _ in range(3):
        # The verified-triple cache would turn reps 2..3 into dict lookups;
        # this number must measure real verification work every rep.  The
        # path measured is exactly what CpuBackend ships: the native C
        # MSM batch verifier when built, per-signature OpenSSL otherwise.
        ed25519._verified.clear()
        t1 = time.perf_counter()
        bv = ed25519.BatchVerifier()
        for k, m, s in zip(keys, msgs, sigs):
            bv.add(k, m, s)
        ok, _bits = bv.verify()
        hash_from_byte_slices(txs)
        best = min(best, time.perf_counter() - t1)
        assert ok
    how = (
        "native C MSM + SHA-NI merkle"
        if native.available()
        else "cryptography/OpenSSL + hashlib"
    )
    log(f"cpu fallback best {best * 1000:.1f} ms ({how})")
    stages = {}
    t0 = time.time()
    try:
        shipped_path_stages(
            stages, log, lambda: time.time() - t0 < STAGE_BUDGET_S, backend="cpu"
        )
    except Exception as e:  # never lose the JSON line to a stage failure
        log(f"cpu shipped-path stages failed: {type(e).__name__}: {e}")
    # Pod-scale mesh curve on the virtual 8-device mesh (subprocess: this
    # process pinned CMTPU_BACKEND=cpu away from jax on purpose).
    if time.time() - t0 < STAGE_BUDGET_S:
        mesh = _mesh_stage_subprocess()
        if mesh is not None:
            stages["mesh"] = mesh
    emit(best * 1000.0, stages, "cpu-host")


def emit(measured_ms: float, stages: dict, platform: str) -> None:
    print(
        json.dumps(
            {
                "metric": "verify_10k_commit_plus_64k_merkle_ms",
                "value": round(measured_ms, 3),
                "unit": "ms",
                "vs_baseline": round(BASELINE_MS / measured_ms, 3),
                "platform": platform,
                "stages": stages,
            }
        ),
        flush=True,
    )


def main() -> int:
    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        # The host-tier run was asked for by name; it is labeled cpu-host.
        cpu_fallback()
        return 0
    log("probing device")
    out = run_phase_logged(
        [sys.executable, "-u", __file__, "--worker", "--probe-only"],
        PROBE_TIMEOUT_S,
        "probe",
    )
    if not (out and "PROBE_OK" in out):
        log("device probe failed: no accelerator, or PJRT init failed or hung")
        return 1
    log("device probe ok; running TPU bench")
    out = run_phase_logged(
        [sys.executable, "-u", __file__, "--worker"], TPU_TIMEOUT_S, "tpu"
    )
    for line in (out or "").splitlines():
        if line.startswith("{"):
            print(line)
            return 0
    log("TPU attempt produced no result")
    return 1


if __name__ == "__main__":
    if "--worker" in sys.argv:
        tpu_worker()
    elif "--mesh-worker" in sys.argv:
        mesh_worker()
    elif "--agg-worker" in sys.argv:
        agg_worker()
    else:
        sys.exit(main())
