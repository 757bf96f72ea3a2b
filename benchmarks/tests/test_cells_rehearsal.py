"""The two traffic kinds at tiny sizes on a CPU (`--platform cpu`): never a
result (`correct: false`), but every check of the harness runs."""

import re

from conftest import result_line, run_bench

SEED = str(2**31 + 5)  # seeds go a little past 32 signed bits


def _problems(lines):
    return [ln for ln in lines if ln.startswith("NOT CORRECT: ")]


def test_commit_stream_pool_misses_a_tiny_cache():
    """Lanes dispatched = lanes offered when the pool is larger than the
    verified-triple cache, and the run says so when it is not."""
    args = ("--workload", "commit10k-cold", "--seed", SEED, "--seconds", "2",
            "--trace", "0", "--platform", "cpu")
    rc, lines, err = run_bench(*args, env={"CMTPU_VERIFY_CACHE_MAX": "256"})
    assert rc == 0, err
    res = result_line(lines)
    assert res is not None and res["correct"] is False
    assert set(res["metrics"]) == {"commit_verify_p50_ms", "commit_verify_p95_ms", "setup_s"}
    window = next(ln for ln in lines if ln.startswith("window: "))
    ops, lanes = map(int, re.search(r"window: (\d+) operations.*lanes dispatched (\d+)", window).groups())
    assert ops > 0 and lanes == ops * 96
    assert not any("hit the cache" in p for p in _problems(lines))
    assert any("flipped commit refused" in ln and "problems 0" in ln for ln in lines)
    # the same pool with the shipped cache (131,072 triples) fits, and is caught
    rc, lines, err = run_bench(*args)
    assert rc == 0, err
    assert any("hit the cache" in p for p in _problems(lines))


def test_blocksync_join_and_the_tampered_chain():
    rc, lines, err = run_bench("--workload", "qa175-blocksync", "--seed", SEED, "--seconds", "20",
                               "--trace", "1", "--platform", "cpu")
    assert rc == 0, err
    res = result_line(lines)
    assert res is not None and res["correct"] is False
    assert {"busy_s", "window_s"} <= set(res["device"]) and "breakdown" in res
    assert "lanes_per_dispatch.catchup" in res["metrics"]
    tampered = next(ln for ln in lines if ln.startswith("tampered chain: "))
    assert "joiner stopped at 5, peers left 0" in tampered
    hashes = next(ln for ln in lines if ln.startswith("hashes: "))
    assert hashes.endswith(" 0 problems")
    assert _problems(lines) == ["NOT CORRECT: rehearsal off the chip: never a result"] or all(
        "device" in p or "rehearsal" in p for p in _problems(lines)
    )
