"""`blocksync.prefetch_collect` per applied height: the walk of a prefetch
window into triples (the commits' sign bytes and one `bv.add` a lane),
everything before the batch seam. A program from before the span gives
nothing to read."""
from loadedlib import per_height_ms


def read(obs, run):
    return per_height_ms(obs, "blocksync.prefetch_collect")
