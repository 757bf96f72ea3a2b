"""`light.fetch` per sync: the target, every pivot and the witness's copy of
the target, each decoded from its encoding as it arrives (the trust root's
fetch comes before the sync and is not counted)."""
from lightlib import per_sync_ms


def read(obs, run):
    return per_sync_ms(obs, "light.fetch")
