"""Bytes on the wire both ways over the lanes the node sent, from the
`GrpcBackend`'s always-on counters over the window."""
from sidecarlib import wire_bytes_per_sig


def read(obs, run):
    return wire_bytes_per_sig(obs)
