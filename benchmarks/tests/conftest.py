"""The benchmark's own tests: run by hand (`python -m pytest benchmarks/tests -q`)
and in the builder's rehearsal, on a CPU. They are not part of the repo's
tier-1 suite. The ones that start `run.py` take up to a minute each."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

# Small batches must still split between the tiers, as in chip_smoke.py's CPU test.
REHEARSAL_ENV = {
    "JAX_PLATFORMS": "cpu",
    "CMTPU_HYBRID_MIN": "8",
    "CMTPU_DEV_RATE": "1000",
    "CMTPU_HOST_RATE": "1000",
    "CMTPU_DEV_OVERHEAD_MS": "0",
}


def run_bench(*args, env=None, timeout=600):
    """Runs benchmarks/run.py; returns (exit code, stdout lines, stderr)."""
    full_env = {**os.environ, **REHEARSAL_ENV, **(env or {})}
    for k in ("CMTPU_BACKEND", "BENCH_RUN"):
        full_env.pop(k, None)
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), *args],
        cwd=ROOT, env=full_env, capture_output=True, text=True, timeout=timeout,
    )
    return proc.returncode, proc.stdout.splitlines(), proc.stderr


def result_line(lines):
    """The last line of standard output as the contract's JSON object, or None."""
    if not lines:
        return None
    try:
        obj = json.loads(lines[-1])
    except ValueError:
        return None
    return obj if isinstance(obj, dict) and "correct" in obj else None


@pytest.fixture(scope="session")
def benchmark_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)
