"""Median `device.pack`: the host packing the device's share of a commit
(`ops/ed25519_kernel.pack_batch`)."""
from spanlib import median_ms


def read(obs, run):
    return median_ms(obs, "device.pack")
