"""`blocksync.decode` per applied height: block decode and pool hand-off on
the receive threads."""
from spanlib import per_height_ms


def read(obs, run):
    return per_height_ms(obs, "blocksync.decode")
