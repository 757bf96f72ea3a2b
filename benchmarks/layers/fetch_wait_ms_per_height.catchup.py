"""`blocksync.fetch_wait` per applied height: the sync thread with no pair
of blocks to verify (requests, 10 ms sleeps)."""
from spanlib import per_height_ms


def read(obs, run):
    return per_height_ms(obs, "blocksync.fetch_wait")
