"""Median `device.unpack`: the bitmap merge after the device's answer is in."""
from spanlib import median_ms


def read(obs, run):
    return median_ms(obs, "device.unpack")
