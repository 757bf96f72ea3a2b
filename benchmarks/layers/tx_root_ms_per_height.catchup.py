"""`types.data_hash` per applied height: the RFC-6962 root over the block's
transactions, where the joiner first computes it (checking the header's
DataHash)."""
from loadedlib import per_height_ms


def read(obs, run):
    return per_height_ms(obs, "types.data_hash")
