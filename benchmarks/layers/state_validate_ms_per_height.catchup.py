"""`state.validate` per applied height: both calls (the reactor's, then
apply_block's own)."""
from spanlib import per_height_ms


def read(obs, run):
    return per_height_ms(obs, "state.validate")
