"""Without the cell's chips there is no result line and a non-zero exit."""

from conftest import result_line, run_bench


def test_no_chip_prints_no_result(benchmark_json):
    cell = benchmark_json["workloads"][0]["name"]
    rc, lines, err = run_bench("--workload", cell, "--seed", "7", "--seconds", "1", "--trace", "0")
    assert rc != 0
    assert result_line(lines) is None
    assert "wanted platform 'tpu'" in err


def test_unknown_workload_prints_no_result():
    rc, lines, _ = run_bench("--workload", "no-such-cell", "--seed", "7", "--seconds", "1", "--trace", "0")
    assert rc != 0 and result_line(lines) is None
