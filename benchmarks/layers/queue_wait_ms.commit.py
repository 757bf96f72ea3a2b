"""Median per operation of both engines' `engine.queue_wait`: the node's
(before the `grpc` tier is called) and the sidecar's (before the `hybrid`
tier is): what two schedulers in a row cost one caller."""
from sidecarlib import both_ms


def read(obs, run):
    return both_ms(obs, ("engine.queue_wait",), ("engine.queue_wait",))
