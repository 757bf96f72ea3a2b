"""`state.exec_abci` + `state.commit` per applied height: the application."""
from spanlib import per_height_ms


def read(obs, run):
    return per_height_ms(obs, "state.exec_abci", "state.commit")
