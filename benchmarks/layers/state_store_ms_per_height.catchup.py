"""`state.save_responses` + `state.save_state` + `store.save_block` per
applied height: the stores."""
from spanlib import per_height_ms


def read(obs, run):
    return per_height_ms(obs, "state.save_responses", "state.save_state", "store.save_block")
