"""What the per-layer readers share: differences of the chain's counters
over the window, and the verify program's entry in the reduced trace. Each
`layers/<metric>.py` is one `read(obs, run)`; it returns None where there is
nothing to read, and the harness then leaves the metric out of the line."""

from __future__ import annotations

import re
import statistics

VERIFY_MODULE = re.compile(r"verify_core")


def delta(obs, tier: str, key: str) -> float:
    return obs.counters_after[tier].get(key, 0) - obs.counters_before[tier].get(key, 0)


def lanes_per_dispatch(obs, run):
    """Lanes the hybrid tier was sent per engine dispatch, over the window."""
    dispatches = delta(obs, "engine", "dispatches")
    if dispatches <= 0:
        return None
    return (delta(obs, "hybrid", "device_lanes") + delta(obs, "hybrid", "host_lanes")) / dispatches


def device_lane_share_pct(obs, run):
    dev, host = delta(obs, "hybrid", "device_lanes"), delta(obs, "hybrid", "host_lanes")
    if dev + host <= 0:
        return None
    return 100.0 * dev / (dev + host)


def compiles_in_window(obs, run):
    """Programs JAX lowered, loaded or compiled inside the window; 0 is right."""
    return float(len(run.compile_log.between(*obs.window)))


def verify_module(run):
    """(dispatches per chip, device seconds per chip) of the verify program in the trace."""
    if not run.trace:
        return None
    count = total = 0.0
    for name, m in run.trace["modules"].items():
        if VERIFY_MODULE.search(name):
            count += m["count"]
            total += m["total_s"]
    return (count, total) if count > 0 else None


def verify_device_ms(obs, run):
    """Device time of the verify program per dispatch, from the trace."""
    found = verify_module(run)
    return None if found is None else 1000.0 * found[1] / found[0]


def median_timing(obs, key: str, only_if: str):
    """Median of one field of the hybrid's last_timing, read after each
    operation of the one caller, over the operations where `only_if` > 0."""
    xs = [t[key] for t in obs.samples.get("hybrid_timing", []) if t.get(only_if, 0) > 0 and key in t]
    return statistics.median(xs) if xs else None
