"""`blocksync.verify_wait` per applied height: the sync thread blocked on the
prefetch worker's window verification."""
from spanlib import per_height_ms


def read(obs, run):
    return per_height_ms(obs, "blocksync.verify_wait")
