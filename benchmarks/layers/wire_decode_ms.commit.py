"""Median per operation of the sidecar's `sidecar.decode` (one a chunk in a
stream) plus the node's `grpc.decode`: frames back into triples and bitmap."""
from sidecarlib import both_ms


def read(obs, run):
    return both_ms(obs, ("grpc.decode",), ("sidecar.decode",))
