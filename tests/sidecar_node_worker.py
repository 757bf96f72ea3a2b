"""The node beside a sidecar, as a process of its own (tests/test_sidecar_deployment.py):
`CMTPU_BACKEND=auto` with `CMTPU_SIDECAR_ADDR` set, so its chain is
engine -> `ResilientBackend` (`grpc` -> `cpu`), and it never imports JAX.

    python tests/sidecar_node_worker.py '<json job>'

The job: `validators`, `seed`, `sizes` (bitmap batch sizes), `capture`
(record spans under `trace.capture()`). It verifies seeded commits through
`vals.verify_commit`, has a flipped one refused by its first flipped lane,
and sends bitmap batches holding flipped lanes and the ZIP-215 edge vectors
at their tail, each compared lane for lane with the scalar reference. The
last line of standard output is one JSON object; any failed check raises.
"""

import json
import os
import random
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

import chip_smoke  # noqa: E402
from reference import ed25519_zip215 as ref  # noqa: E402

from cometbft_tpu.crypto import ed25519  # noqa: E402
from cometbft_tpu.libs import trace  # noqa: E402
from cometbft_tpu.sidecar import backend as backend_mod  # noqa: E402


def check(cond, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def bitmap_batch(vals, commit, n: int, flipped: list[int]):
    """n triples: the commit's first lanes with `flipped` flipped, then the
    edge vectors; and the lanes the scalar reference rejects."""
    edges = [c for c in ref.zip215_edge_cases() if len(c[1]) == 32 and len(c[3]) == 64]
    keep = n - len(edges)
    check(keep > max(flipped), f"batch of {n} too small for {len(edges)} edge vectors")
    bad = chip_smoke.flip_signatures(commit, flipped)
    sbs = bad.vote_sign_bytes_all(chip_smoke.CHAIN_ID)
    triples = [
        (vals.validators[j].pub_key.bytes(), bytes(sbs[j]), bad.signatures[j].signature)
        for j in range(keep)
    ] + [(p, m, s) for _, p, m, s in edges]
    want_false = [j for j, t in enumerate(triples) if not ref.verify_zip215(*t)]
    check(set(flipped) <= set(want_false) and len(want_false) > len(flipped),
          "the reference rejects the flipped lanes and some edge vectors")
    return triples, want_false


def main() -> int:
    job = json.loads(sys.argv[1])
    n_vals, seed = int(job["validators"]), int(job["seed"])
    vals, commits = chip_smoke.make_commits(seed, n_vals, 3, "sidecar-node")
    rng = random.Random(f"{seed}/flip/{n_vals}")
    flipped = sorted(rng.sample(range(min(n_vals, min(job["sizes"]) - 10) * 2 // 3), 3))
    out = {"validators": n_vals, "flipped": flipped, "bitmaps": {}}
    # The capability probe a client makes before its first batch over the
    # default chunk: here up front, so that the test server's small
    # advertised chunk is known and these small batches stream.
    check(backend_mod.get_backend().ping(), "the sidecar did not answer Ping")

    def run() -> None:
        bid, commit = commits[0]
        vals.verify_commit(chip_smoke.CHAIN_ID, bid, commit.height, commit)
        bid, commit = commits[1]
        try:
            vals.verify_commit(chip_smoke.CHAIN_ID, bid, commit.height,
                               chip_smoke.flip_signatures(commit, flipped))
        except ValueError as e:
            check(f"wrong signature (#{flipped[0]})" in str(e), f"refused for another reason: {e}")
        else:
            check(False, "a commit with flipped signatures was accepted")
        for n in job["sizes"]:
            triples, want_false = bitmap_batch(vals, commits[2][1], n, flipped)
            chip_smoke.clear_verified_cache()  # every batch goes out whole
            bv = ed25519.BatchVerifier()
            for p, m, s in triples:
                bv.add(ed25519.PubKey(p), m, s)
            ok, bits = bv.verify()
            got_false = [j for j, b in enumerate(bits) if not b]
            check(not ok and len(bits) == n, f"bitmap call of {n}: ok={ok}, {len(bits)} lanes")
            check(got_false == want_false,
                  f"bitmap of {n} false at {got_false}, the reference rejects {want_false}")
            out["bitmaps"][str(n)] = want_false

    if job.get("capture"):
        with trace.capture():
            run()
    else:
        run()
    chain = backend_mod.get_backend().counters()
    sup = chain["inner"]
    out["chain"] = sup["chain"]
    out["active_tier"] = sup["active_tier"]
    out["events"] = {k: sup[k] for k in ("trips", "degraded_calls", "deadline_exceeded",
                                          "crosscheck_catches")}
    out["grpc"] = sup["tiers"]["grpc"]["backend"]
    out["spans"] = trace.spans()
    out["jax_imported"] = "jax" in sys.modules
    check(not out["jax_imported"], "the node imported JAX")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
