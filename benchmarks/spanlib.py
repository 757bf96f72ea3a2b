"""What the span readers share: the program's own spans
(`cometbft_tpu/libs/trace.py`, on while the profiler session of a
`--trace 1` run lasts) cut to the window, and sums, medians and counts over
them. Each `layers/<metric>.py` that reads spans is one `read(obs, run)`
built from these; it returns None where there is nothing to read — no span
in the window, a ring that wrapped inside it, or a program that has no
tracer — and the harness then leaves the metric out of the line.

A span is a dict: id, parent, root, name, t0, t1 (`time.perf_counter()`,
the clock of `obs.window`), thread, attrs. The spans of one request share
`root`: one `validation.verify_commit` in the commit cell, one
`blocksync.sync_one` per height in the catch-up cell.
"""

from __future__ import annotations

import statistics

OP = "validation.verify_commit"
HEIGHT = "blocksync.sync_one"


def select(spans: list[dict], dropped: int, window) -> list[dict] | None:
    """The spans that lie inside the window; None when there are none, or
    when the ring pushed spans out after the window opened (everything it
    dropped ended before its oldest span did)."""
    if not spans:
        return None
    t_open, t_close = window
    if dropped and spans[0]["t1"] > t_open:
        return None
    inside = [s for s in spans if s["t0"] >= t_open and s["t1"] <= t_close]
    return inside or None


def window_spans(obs) -> list[dict] | None:
    try:
        from cometbft_tpu.libs import trace
    except ImportError:  # a program from before the tracer
        return None
    return select(trace.spans(), trace.dropped(), obs.window)


def ms(span: dict) -> float:
    return (span["t1"] - span["t0"]) * 1000.0


def named(spans, *names) -> list[dict]:
    return [s for s in spans if s["name"] in names]


def ops(spans) -> list[dict]:
    """The operations of the commit cell: verify_commit spans that are roots."""
    return [s for s in spans if s["name"] == OP and s["parent"] is None]


def heights(spans) -> list[dict]:
    """The heights the reactor applied: their sync_one roots."""
    return [s for s in spans if s["name"] == HEIGHT and s["attrs"].get("applied")]


def median_ms(obs, name: str):
    """Median duration of the spans of one name."""
    spans = window_spans(obs)
    xs = [ms(s) for s in named(spans or [], name)]
    return statistics.median(xs) if xs else None


def median_per_op_ms(obs, *names):
    """Median over operations of the summed duration of the named spans
    each one holds; an operation with none of them is left out."""
    spans = window_spans(obs)
    if not spans:
        return None
    per_root: dict[int, float] = {}
    for s in named(spans, *names):
        per_root[s["root"]] = per_root.get(s["root"], 0.0) + ms(s)
    xs = [per_root[o["id"]] for o in ops(spans) if o["id"] in per_root]
    return statistics.median(xs) if xs else None


def outside_ms(obs, inner: str):
    """Median over operations of the operation's time minus the `inner`
    spans it holds (the caller's own time when `inner` is the dispatch)."""
    spans = window_spans(obs)
    if not spans:
        return None
    held: dict[int, float] = {}
    for s in named(spans, inner):
        held[s["root"]] = held.get(s["root"], 0.0) + ms(s)
    xs = [ms(o) - held[o["id"]] for o in ops(spans) if o["id"] in held]
    return statistics.median(xs) if xs else None


def per_height_ms(obs, *names):
    """Summed duration of the named spans over the applied heights. With
    heights applied and no such span the reading is 0 (the sync thread
    never waited, say), not a missing one."""
    spans = window_spans(obs)
    if not spans:
        return None
    n = len(heights(spans))
    if n == 0:
        return None
    return sum(ms(s) for s in named(spans, *names)) / n


def split_imbalance_ms(obs):
    """Median over split calls of |end of device.run - end of
    hybrid.host_msm|: how long one tier waited for the other."""
    spans = window_spans(obs)
    if not spans:
        return None
    ends: dict[int, dict[str, float]] = {}
    for s in named(spans, "device.run", "hybrid.host_msm"):
        ends.setdefault(s["parent"], {})[s["name"]] = s["t1"]
    xs = [
        abs(ends[c["id"]]["device.run"] - ends[c["id"]]["hybrid.host_msm"]) * 1000.0
        for c in named(spans, "hybrid.call")
        if c["attrs"].get("route") == "split" and len(ends.get(c["id"], ())) == 2
    ]
    return statistics.median(xs) if xs else None


def serial_cache_hit_pct(obs):
    """Of the triples the sync thread's own verify calls looked up, the
    share the verified-triple cache answered (the prefetch got there first)."""
    spans = window_spans(obs)
    if not spans:
        return None
    roots = {h["id"] for h in named(spans, HEIGHT)}
    calls = [s for s in named(spans, "batch.verify") if s["root"] in roots]
    entries = sum(s["attrs"].get("entries", 0) for s in calls)
    if entries <= 0:
        return None
    return 100.0 * sum(s["attrs"].get("hits", 0) for s in calls) / entries


def p95_ms(obs, name: str):
    """95th percentile of the named spans, by the rule of the engine's own
    ring (nearest rank on the sorted durations)."""
    spans = window_spans(obs)
    xs = sorted(ms(s) for s in named(spans or [], name))
    if not xs:
        return None
    return xs[min(len(xs) - 1, int(0.95 * (len(xs) - 1) + 0.5))]


def counter_ratio_pct(obs, tier: str, part: str, whole: str):
    """100 x the window's growth of one counter over another's; None where
    the program has neither or the denominator did not grow."""
    before, after = obs.counters_before[tier], obs.counters_after[tier]
    if part not in after or whole not in after:
        return None
    d_whole = after[whole] - before.get(whole, 0)
    if d_whole <= 0:
        return None
    return 100.0 * (after[part] - before.get(part, 0)) / d_whole
