"""Median over operations of `validation.verify_commit` minus the
`batch.dispatch` it holds: the caller's own host time outside the backend
seam (checks, sign bytes, tally, cache filter and insert)."""
from spanlib import outside_ms


def read(obs, run):
    return outside_ms(obs, "batch.dispatch")
