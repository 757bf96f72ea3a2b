"""Requests the sidecar's engine admitted over the dispatches it made, over
the window (`requests` / `dispatches`, the engine's always-on counters): 1.0
where every commit is dispatched alone, 2.0 where four nodes' copies of a
height's commit take two dispatches (the first arrival alone, the other three
merged)."""
from multinodelib import requests_per_dispatch


def read(obs, run):
    return requests_per_dispatch(obs)
