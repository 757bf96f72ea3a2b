"""Median time of the native C MSM on the host's share of a commit: the
hybrid's `last_timing["host_msm_ms"]`, read after each operation (one caller
in a closed loop, so the last call is that operation)."""
from layerlib import median_timing


def read(obs, run):
    return median_timing(obs, "host_msm_ms", only_if="n_host")
