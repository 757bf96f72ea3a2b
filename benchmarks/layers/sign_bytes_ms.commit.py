"""Median `validation.sign_bytes`: the sign bytes of every vote of a commit
not seen before."""
from spanlib import median_ms


def read(obs, run):
    return median_ms(obs, "validation.sign_bytes")
