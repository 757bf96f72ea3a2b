"""`light.speculate_collect` per sync: the Python walk of each prewarm's hops
into triples (`speculative_verify_triples`) and one `bv.add` a lane,
everything a prewarm does before `bv.verify()`."""
from lightlib import per_sync_ms


def read(obs, run):
    return per_sync_ms(obs, "light.speculate_collect")
