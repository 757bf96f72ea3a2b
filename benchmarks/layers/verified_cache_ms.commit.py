"""Median per operation of `batch.cache_filter` + `batch.cache_insert`: the
verified-triple cache's look-ups before the dispatch and its inserts (with
eviction) after it."""
from spanlib import median_per_op_ms


def read(obs, run):
    return median_per_op_ms(obs, "batch.cache_filter", "batch.cache_insert")
