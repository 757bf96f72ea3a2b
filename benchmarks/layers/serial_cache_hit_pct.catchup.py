"""Over the `batch.verify` calls of the sync thread (root: a
`blocksync.sync_one`), 100 x hits / entries; each miss is a host-route call."""
from spanlib import serial_cache_hit_pct


def read(obs, run):
    return serial_cache_hit_pct(obs)
