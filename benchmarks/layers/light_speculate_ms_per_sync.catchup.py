"""`light.speculate` per sync: the client's speculative prewarms, from the
walk of the hops it expects into triples to the batch seam's answer (the
cache, the engine, the dispatch), summed over a sync's pivots. Each sits on
the sync's own thread, before the hop checks it makes cache hits of."""
from lightlib import per_sync_ms


def read(obs, run):
    return per_sync_ms(obs, "light.speculate")
