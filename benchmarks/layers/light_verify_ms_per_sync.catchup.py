"""`light.verify` per sync: every hop the walk tried, verified or refused
(the header checks, the set's hash, the trusting and light tallies, and the
batch seam's look-ups, all hits when the prewarm ran first)."""
from lightlib import per_sync_ms


def read(obs, run):
    return per_sync_ms(obs, "light.verify")
