"""`blocksync.prefetch` per applied height: the prefetch worker's time in its
windows, from the first look at the window to the batch seam's answer (the
walk into triples, the cache, the engine, the dispatch). It runs beside the
sync thread, so it costs a height its interpreter time and, where it is
longer than the heights it covers, the sync thread's `verify_wait`."""
from loadedlib import per_height_ms


def read(obs, run):
    return per_height_ms(obs, "blocksync.prefetch")
