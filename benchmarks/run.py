#!/usr/bin/env python3
"""The benchmark's one command.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in `BENCHMARK.json`, its configuration and traffic files by
the names given there, the generator by the traffic file's `kind`
(`generators/<kind>.py`) and each per-layer metric's reader by the metric's
name (`layers/<name>.py`). It loads, warms, measures for `--seconds`, checks
the answers, and prints one JSON object as the last line of standard output.
Without the cell's chips it prints no result line and exits non-zero; there
is no CPU fallback. `--platform cpu` is the builder's rehearsal at tiny
sizes and always prints `correct: false`.

This file holds no cell's name and no validator count.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.time()

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _metrics_of(bench: dict, section: str, cell: str) -> list[dict]:
    return [
        m for m in bench[section]
        if "workloads" not in m or cell in m["workloads"]
    ]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--platform", default="tpu", choices=("tpu", "cpu"),
                    help="cpu: rehearsal at tiny sizes, never a result (correct: false)")
    ap.add_argument("--benchmark", default=os.path.join(ROOT, "BENCHMARK.json"),
                    help="the cell list (tests point this at their own)")
    args = ap.parse_args(argv)

    import harness

    try:
        import cometbft_tpu  # noqa: F401
    except ImportError as e:
        print(f"benchmark: the program is not beside the benchmark: {e}", file=sys.stderr)
        return 2
    bench = _load_json(args.benchmark)
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"benchmark: no workload {args.workload!r} in {args.benchmark}", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    config_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = _load_json(os.path.join(ROOT, config_entry["file"]))
    traffic = _load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    if args.platform == "cpu":
        config = {**config, **config.get("rehearsal", {})}
        traffic = {**traffic, **traffic.get("rehearsal", {})}
    generator = harness.load_by_path(
        os.path.join(HERE, "generators", traffic["kind"] + ".py"), "generator_" + traffic["kind"]
    )
    run = harness.Run(args, cell, config, traffic, T_START)
    try:
        obs = generator.run(run)
    except harness.BenchFailure as e:
        print(f"benchmark: {e}", file=sys.stderr, flush=True)
        return 3

    if args.trace:
        run.trace_finish()
        metrics = {}
        for m in _metrics_of(bench, "per_layer", cell["name"]):
            path = os.path.join(HERE, "layers", m["name"] + ".py")
            reader = harness.load_by_path(path, "layer_" + m["name"].replace(".", "_"))
            value = reader.read(obs, run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        values = {**obs.end_to_end, "setup_s": obs.setup_s}
        metrics = {
            m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in _metrics_of(bench, "end_to_end", cell["name"])
            if m["name"] in values
        }
    device = {**run.device, "memory_peak_bytes": run.memory_peak_bytes()}
    problems = list(obs.correct_problems)
    if args.platform != "tpu":
        problems.append("rehearsal off the chip: never a result")
    result = {
        "correct": not problems and obs.failed == 0 and obs.attempted > 0,
        "attempted": obs.attempted, "failed": obs.failed,
        "metrics": metrics, "device": device,
    }
    if args.trace and run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = {
            "device_ops": run.trace["device_ops"], "idle_gaps": run.trace["idle_gaps"],
        }
    for p in problems:
        harness.say(f"NOT CORRECT: {p}")
    harness.say(f"compile log {run.compile_log.summary()}, in the window "
                f"{len(run.compile_log.between(*obs.window))}; supervisor "
                f"{obs.counters_after['supervisor']}; window "
                f"{obs.window[1] - obs.window[0]:.3f} s; whole run {time.time() - T_START:.1f} s")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
