"""The light client's skipping sync against the benchmark's plain reference
(`benchmarks/reference/light_skipping.py`: upstream verifySkipping and
VerifyNonAdjacent in plain Python, signature by signature) on seeded chains
whose set turns over, built by `benchmarks/light_chain.py` at a small size:
the same trace, the same refused hop, one hop with no rotation, and a
prewarm that raises changes nothing but its counter. No chip."""

from __future__ import annotations

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import light_chain  # noqa: E402
from reference import light_skipping as ref  # noqa: E402

from cometbft_tpu.libs.db import MemDB  # noqa: E402
from cometbft_tpu.light import Client, LightStore, TrustOptions  # noqa: E402
from cometbft_tpu.types.cmttime import Time  # noqa: E402
from cometbft_tpu.types.validation import Fraction  # noqa: E402

N_VALS, HEADERS, ROOTS = 64, 60, 3
HEIGHTS = ROOTS + HEADERS - 1
PERIOD_NS = 14 * 86400 * 10**9
DRIFT_NS = 10 * 10**9
IN_PROCESS = types.SimpleNamespace(map=lambda f, xs: list(map(f, xs)))


class Chain:
    def __init__(self, out_dir: str, seed: int, rotate: int):
        self.dir = out_dir
        marker = light_chain.build_chain(seed, "light-test", N_VALS, rotate, HEIGHTS, 60, out_dir, IN_PROCESS)
        self.chain_id = marker["chain_id"]
        self.hashes = {int(h): bytes.fromhex(x) for h, x in marker["header_hashes"].items()}
        self.encoded = light_chain.load_blocks(out_dir, HEIGHTS)
        tip = light_chain.header_time(seed, HEIGHTS, 60)
        self.now = Time(tip.seconds + 60, 0)
        self.seed = seed

    def client_sync(self, root: int, encoded=None):
        """(stored heights, the target block or the exception, counters)."""
        store = LightStore(MemDB())
        client = Client(
            self.chain_id, TrustOptions(PERIOD_NS, root, self.hashes[root]),
            light_chain.make_provider(self.chain_id, encoded or self.encoded),
            [light_chain.make_provider(self.chain_id, self.encoded)], store, trust_level=Fraction(1, 3),
        )
        try:
            got = client.verify_light_block_at_height(root + HEADERS - 1, self.now)
        except Exception as e:  # the refusal some tests want
            got = e
        stored = [h for h in range(root, root + HEADERS) if store.light_block(h) is not None]
        return stored, got, client.counters()

    def reference(self, root: int, tamper=None):
        clean, distinct, tampered = light_chain.reference_sync((
            self.dir, self.chain_id, root, root + HEADERS - 1, (self.now.seconds, self.now.nanos),
            PERIOD_NS, DRIFT_NS, (1, 3), {}, tamper))
        return clean, distinct, tampered


@pytest.fixture(scope="module", params=[(11, 1), (12, 2)], ids=["one-key-a-height", "two-keys-a-height"])
def chain(request, tmp_path_factory):
    seed, rotate = request.param
    return Chain(str(tmp_path_factory.mktemp(f"light-{rotate}")), seed, rotate)


@pytest.mark.parametrize("root", range(1, ROOTS + 1))
def test_the_client_stores_the_reference_trace(chain, root):
    clean, distinct, _ = chain.reference(root)
    stored, lb, counters = chain.client_sync(root)
    assert clean.refused is None and len(clean.stored) > 2, "the rotation makes the walk bisect"
    assert stored == clean.stored
    assert lb.hash() == chain.hashes[root + HEADERS - 1]
    assert counters["hops"] == len(clean.stored) - 1 and counters["refusals"] > 0
    assert counters["pivot_fetches"] == len(clean.stored) - 2
    assert counters["speculation_errors"] == 0 and counters["descents"] > 0
    assert distinct > 0


def test_a_flipped_signature_is_refused_at_the_same_hop(chain):
    root, target = 1, HEADERS
    index = light_chain.tamper_index(
        chain.chain_id, light_chain.make_provider(chain.chain_id, chain.encoded), root, target,
        (chain.now.seconds, chain.now.nanos), PERIOD_NS, DRIFT_NS, (1, 3), chain.seed)
    assert index is not None
    bad = light_chain.flip_commit_signature(chain.encoded[target], index)
    clean, _, tampered = chain.reference(root, (target, bad))
    assert tampered.refused == (clean.stored[-2], target) and tampered.stored == [root]

    from cometbft_tpu.libs import trace

    with trace.capture():
        trace.clear()
        stored, got, _ = chain.client_sync(root, {**chain.encoded, target: bad})
        invalid = [(s["attrs"]["from"], s["attrs"]["to"]) for s in trace.spans()
                   if s["name"] == "light.verify" and s["attrs"].get("result") == "invalid"]
    assert isinstance(got, Exception) and f"#{index}" in str(got)
    assert invalid == [tampered.refused]
    assert stored == tampered.stored, "nothing of a failed sync is stored"


def test_no_rotation_takes_one_hop(tmp_path):
    chain = Chain(str(tmp_path), 13, 0)
    clean, _, _ = chain.reference(2)
    stored, lb, counters = chain.client_sync(2)
    assert clean.stored == stored == [2, HEADERS + 1]
    assert clean.tried == [(2, HEADERS + 1, "ok")]
    assert (counters["hops"], counters["refusals"], counters["descents"]) == (1, 0, 0)


def test_a_prewarm_that_raises_is_counted_and_changes_no_trace(chain, monkeypatch):
    from cometbft_tpu.types import validation

    clean, _, _ = chain.reference(2)

    def broken(*args, **kwargs):
        raise RuntimeError("device lost")

    monkeypatch.setattr(validation, "speculative_verify_triples", broken)
    stored, lb, counters = chain.client_sync(2)
    assert stored == clean.stored and lb.hash() == chain.hashes[HEADERS + 1]
    assert counters["speculation_errors"] == counters["refusals"] > 0
    assert counters["descents"] == counters["prewarmed_sigs"] == 0


def test_the_reference_refuses_a_set_it_cannot_trust_before_any_signature(chain):
    """A refusal is decided on the tally: the walk from root 1 straight to
    the target reaches only the signatures of the root's validators still in
    the target's set, and none of the others."""
    blocks = {h: light_chain.plain(light_chain.make_provider(chain.chain_id, chain.encoded).light_block(h))
              for h in (1, HEADERS)}
    reached = []

    def verify(pub, msg, sig):
        reached.append(pub)
        return True

    with pytest.raises(ref.CantBeTrusted):
        ref.verify_commit_light_trusting(chain.chain_id, blocks[1]["validators"], blocks[HEADERS]["commit"], verify)
    known = {p for p, _ in blocks[1]["validators"]} & {p for p, _ in blocks[HEADERS]["validators"]}
    assert len(reached) == len(known)
