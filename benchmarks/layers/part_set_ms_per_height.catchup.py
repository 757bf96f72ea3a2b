"""`blocksync.part_set` per applied height: part set, block hash, block id."""
from spanlib import per_height_ms


def read(obs, run):
    return per_height_ms(obs, "blocksync.part_set")
