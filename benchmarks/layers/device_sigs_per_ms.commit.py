"""Signatures verified on the device per millisecond the device was busy,
over the traced part of the window (BASELINE.json's "sig-verifies/sec/chip",
measured): device lanes between trace start and stop / busy ms per chip /
chips."""


def read(obs, run):
    if not run.trace or run.trace["busy_s"] <= 0 or run.trace_counters is None:
        return None
    a, b = run.trace_counters
    lanes = b["hybrid"].get("device_lanes", 0) - a["hybrid"].get("device_lanes", 0)
    if lanes <= 0:
        return None
    return lanes / (run.trace["busy_s"] * 1000.0) / max(1, run.trace["chips"])
