"""What the readers of the sidecar cell share: the node's spans and the
sidecar's, merged into operations, and sums over them.

The node (a child that never imports JAX) records under the program's
`trace.capture()` and ships its spans; the sidecar is the process that runs
`run.py` and records under the profiler session. Both rings are on
`time.perf_counter()`, one clock for every process of a Linux host, so a
server span belongs to the node's call whose interval holds it; the `req`
attribute, the id of the frame that carried the answer, confirms it. Span
ids are each process's own and are never compared across the two.

`merge(obs)` gives one entry an operation of the window (a root
`validation.verify_commit` of the node): `op` (that span), `node` (the node's
spans under it) and `sidecar` (the spans under the `sidecar.request`s its
`grpc.call`s were answered by). Every reader is a median over operations and
returns None where there is nothing to read: an untraced run, or a program
without the capture or the wire's spans.
"""

from __future__ import annotations

import statistics

import spanlib


def grown(before: dict, after: dict, key: str):
    """Growth of one counter over the window; None where the program has none."""
    if key not in after:
        return None
    return after[key] - before.get(key, 0)


def wire_bytes_per_sig(obs):
    """Bytes on the wire, both ways, for each signature the node sent."""
    try:
        before, after = obs.counters_before["node"]["grpc"], obs.counters_after["node"]["grpc"]
    except KeyError:
        return None
    lanes = grown(before, after, "lanes_sent")
    sent, received = grown(before, after, "bytes_sent"), grown(before, after, "bytes_received")
    if not lanes or sent is None or received is None:
        return None
    return (sent + received) / lanes


def merge(obs) -> list[dict] | None:
    """The window's operations with both processes' spans (module text)."""
    theirs = spanlib.select(obs.samples.get("node_spans") or [],
                            obs.samples.get("node_dropped", 0), obs.window)
    mine = spanlib.window_spans(obs)
    if not theirs or not mine:
        return None
    requests = spanlib.named(mine, "sidecar.request")
    under_request: dict[int, list[dict]] = {}
    for s in mine:
        under_request.setdefault(s["root"], []).append(s)
    out = []
    for op in spanlib.ops(theirs):
        node = [s for s in theirs if s["root"] == op["id"]]
        sidecar = []
        for call in spanlib.named(node, "grpc.call"):
            for r in requests:
                if (r["t0"] >= call["t0"] and r["t1"] <= call["t1"]
                        and r["attrs"].get("req") == call["attrs"].get("req")):
                    sidecar += under_request.get(r["id"], [])
        out.append({"op": op, "node": node, "sidecar": sidecar})
    return out or None


# What the generator says of a traced run: one operation across both processes.
BREAKDOWN = (
    ("node", "validation.verify_commit"), ("node", "batch.dispatch"), ("node", "engine.queue_wait"),
    ("node", "grpc.call"), ("node", "grpc.encode"), ("node", "grpc.wait"), ("node", "grpc.decode"),
    ("sidecar", "sidecar.request"), ("sidecar", "sidecar.decode"), ("sidecar", "engine.queue_wait"),
    ("sidecar", "hybrid.call"), ("sidecar", "hybrid.host_msm"), ("sidecar", "device.pack"),
    ("sidecar", "device.run"), ("sidecar", "sidecar.encode"),
)


def breakdown(ops: list[dict]) -> str:
    """Medians over the merged operations that crossed the wire of each
    named span's summed duration, as one line."""
    crossed = [e for e in ops if e["sidecar"]]
    parts = [
        f"{side}:{name} {statistics.median(_sum_ms(e[side], name) for e in crossed):.2f}"
        for side, name in BREAKDOWN
    ]
    return f"one operation, medians over {len(crossed)} traced (ms): " + ", ".join(parts)


def _sum_ms(spans, *names) -> float:
    return sum(spanlib.ms(s) for s in spanlib.named(spans, *names))


def median_per_op(obs, reading):
    """Median over the merged operations of `reading(entry)`; an operation
    for which it gives None is left out."""
    xs = [x for x in map(reading, obs.samples.get("wire_ops") or []) if x is not None]
    return statistics.median(xs) if xs else None


def both_ms(obs, node_names=(), sidecar_names=()):
    """Median over operations of the named spans' summed durations, the
    node's and the sidecar's together; operations that crossed no wire
    (no `grpc.call` answered by a `sidecar.request`) are left out."""
    def reading(e):
        if not e["sidecar"]:
            return None
        return _sum_ms(e["node"], *node_names) + _sum_ms(e["sidecar"], *sidecar_names)

    return median_per_op(obs, reading)


def wire_ms(obs):
    """`grpc.call` minus the `hybrid.call` it holds: what the second process
    costs an operation (codec, framing, loopback, both engines' queues)."""
    def reading(e):
        if not spanlib.named(e["sidecar"], "hybrid.call"):
            return None
        return _sum_ms(e["node"], "grpc.call") - _sum_ms(e["sidecar"], "hybrid.call")

    return median_per_op(obs, reading)


def node_outside_seam_ms(obs):
    """The node's `validation.verify_commit` minus its `batch.dispatch`:
    `caller_outside_seam_ms.commit` of the in-process cell, read in the node."""
    def reading(e):
        if not spanlib.named(e["node"], "batch.dispatch"):
            return None
        return spanlib.ms(e["op"]) - _sum_ms(e["node"], "batch.dispatch")

    return median_per_op(obs, reading)
