"""A later PR adds a cell by adding files and one `workloads` entry: a new
configuration, a new traffic kind with its generator, and a new per-layer
metric are found by name, with no edit to a file that is there."""

import json
import os
import textwrap

from conftest import BENCH, ROOT, result_line, run_bench

GENERATOR = '''
import time
import harness


def run(run):
    backend = run.start_backend()
    from cometbft_tpu.crypto import ed25519
    keys = [ed25519.gen_priv_key_from_secret(b"dropin%d/%d" % (run.seed, i)) for i in range(run.config["validators"])]
    before = run.counters()
    if run.traced:
        run.trace_start()
    t0 = time.perf_counter()
    bv = ed25519.BatchVerifier()
    for k in keys:
        bv.add(k.pub_key(), b"msg", k.sign(b"msg"))
    ok, bits = bv.verify()
    t1 = time.perf_counter()
    if run.traced:
        run.trace_stop()
    return harness.Observations(
        attempted=1, failed=0 if ok else 1, end_to_end={"dropin_ms": (t1 - t0) * 1000},
        setup_s=run.setup_done(), window=(t0, t1), counters_before=before,
        counters_after=run.counters(), correct_problems=[], samples={"bits": len(bits)},
    )
'''

LAYER = '''
def read(obs, run):
    return obs.samples["bits"] * run.traffic["factor"]
'''


def test_a_cell_dropped_in_as_files(tmp_path):
    files = {
        os.path.join(BENCH, "configs", "zz-dropin.json"): json.dumps(
            {"name": "zz-dropin", "source": "test", "validators": 24, "guarantees": ["x"]}),
        os.path.join(BENCH, "traffic", "zz-dropin-mix.json"): json.dumps(
            {"kind": "zz_dropin_kind", "factor": 2}),
        os.path.join(BENCH, "generators", "zz_dropin_kind.py"): textwrap.dedent(GENERATOR),
        os.path.join(BENCH, "layers", "zz_bits.dropin.py"): textwrap.dedent(LAYER),
    }
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "zz-dropin", "source": "test", "file": "benchmarks/configs/zz-dropin.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "zz-cell", "config": "zz-dropin", "traffic": "zz-dropin-mix",
                               "chips": 1, "why": "test"})
    bench["end_to_end"].append({"name": "dropin_ms", "unit": "ms", "better": "lower", "bound": 0.1,
                                "source": "host_clock", "workloads": ["zz-cell"]})
    bench["per_layer"].append({"name": "zz_bits.dropin", "unit": "lanes", "better": "higher",
                               "source": "program_counter", "layer": "test", "moves": "dropin_ms",
                               "workloads": ["zz-cell"]})
    alt = tmp_path / "BENCHMARK.json"
    alt.write_text(json.dumps(bench))
    try:
        for path, body in files.items():
            assert not os.path.exists(path)
            with open(path, "w") as f:
                f.write(body)
        common = ("--workload", "zz-cell", "--seed", "9", "--seconds", "1", "--platform", "cpu",
                  "--benchmark", str(alt))
        rc, lines, err = run_bench(*common, "--trace", "0")
        assert rc == 0, err
        res = result_line(lines)
        assert set(res["metrics"]) == {"dropin_ms", "setup_s"}
        rc, lines, err = run_bench(*common, "--trace", "1")
        assert rc == 0, err
        res = result_line(lines)
        assert res["metrics"] == {"zz_bits.dropin": {"value": 48.0, "unit": "lanes"}}
    finally:
        for path in files:
            if os.path.exists(path):
                os.remove(path)
