"""Median per operation of the node's `grpc.encode` (one a chunk in a
stream) plus the sidecar's `sidecar.encode`: triples and bitmap into frames."""
from sidecarlib import both_ms


def read(obs, run):
    return both_ms(obs, ("grpc.encode",), ("sidecar.encode",))
