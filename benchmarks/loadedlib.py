"""What the readers of the loaded catch-up cell share: a per-height span
sum that is missing, not 0, under a program that has no such span, and the
growth of the blocksync reactor's counters over the window
(`obs.samples["reactor_counters"]`: the counters as they stood after the
height that opened the window and after the last height inside it, with
the seconds between the two)."""

from __future__ import annotations

import spanlib


def per_height_ms(obs, name: str):
    try:
        from cometbft_tpu.libs import trace
    except ImportError:  # a program from before the tracer
        return None
    if name not in trace.NAMES:  # a program from before the span: nothing to read
        return None
    return spanlib.per_height_ms(obs, name)


def reactor_growth(obs, *keys: str):
    """The growth of each named counter over the window, None where the
    program lacks one of them or no generator kept them."""
    kept = obs.samples.get("reactor_counters")
    if kept is None:
        return None
    first, last, _ = kept
    if any(k not in last for k in keys):
        return None
    return [last[k] - first[k] for k in keys]
