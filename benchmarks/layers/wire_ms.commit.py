"""Median over operations of the node's `grpc.call` minus the sidecar's
`hybrid.call` it holds: what the second process costs (codec, framing,
loopback TCP, both engines' queues)."""
from sidecarlib import wire_ms


def read(obs, run):
    return wire_ms(obs)
