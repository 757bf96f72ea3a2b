"""`state.results_hash` per applied height: the root over the block's
DeliverTx results that the next header carries as LastResultsHash."""
from loadedlib import per_height_ms


def read(obs, run):
    return per_height_ms(obs, "state.results_hash")
