"""Median over the window's merged dispatches of their `engine.merge` spans,
pack and slice together: the per-triple walk that builds the shared columns
and slices each request's bitmap back out (engine thread, before and after
the chain's call)."""
from multinodelib import engine_merge_ms


def read(obs, run):
    return engine_merge_ms(obs)
