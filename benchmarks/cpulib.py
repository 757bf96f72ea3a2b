"""What the CPU readers share: sums and means of the `cpu` and `pcpu` a
span of `cometbft_tpu/libs/trace.py` carries beside its `t0` / `t1`.

`cpu` is the seconds the span's own thread was on a CPU inside it, `pcpu`
the seconds of CPU the whole process used meanwhile. A span's wall clock
says where its thread stood; under one interpreter lock that is the sum of
the thread's own work and its waits for every other thread's, and `cpu` is
the first of the two. Each `layers/<metric>.py` that reads them is one
`read(obs, run)` built from these, beside the wall-clock reader of the same
span (`spanlib`); the window, the operations and the heights are `spanlib`'s.

Sums and means, never medians: where the platform's CPU clocks tick (10 ms
on the chip's machine, PR 34; nanoseconds in the sandbox) one span's `cpu`
is a whole number of ticks, 0 or 10 ms for 6 ms of work, and its median
over spans is one of those two; the tick charges whoever runs when it
falls, so a sum over many spans is a sample of what ran, as a profiler's.

A reader returns None where there is nothing to read: what `spanlib` lists
(no span in the window, a ring that wrapped, an untraced run), and a
program whose spans carry no `cpu` (one from before the tracer had it). A
single span without `cpu` (one closed on another thread, the first
`p2p.recv_msg` of a session on its thread) is left out of a sum, not read
as 0 seconds.
"""

from __future__ import annotations

import statistics

import spanlib


def cpus_ms(spans, key: str = "cpu") -> list[float]:
    """`cpu` (or `pcpu`) of the spans that carry one, in ms."""
    return [s[key] * 1000.0 for s in spans if s.get(key) is not None]


def sum_ms(spans, key: str = "cpu"):
    """Summed `cpu` of the spans; None where none of them carries it."""
    xs = cpus_ms(spans, key)
    return sum(xs) if xs else None


def per_height_cpu_ms(obs, *names):
    """Summed `cpu` of the named spans over the applied heights."""
    spans = spanlib.window_spans(obs)
    if not spans:
        return None
    n = len(spanlib.heights(spans))
    total = sum_ms(spanlib.named(spans, *names))
    if n == 0 or total is None:
        return None
    return total / n


def sync_cpu_ms_per_height(obs):
    """The sync thread's own CPU a height: `cpu` of the applied
    `blocksync.sync_one` roots over their number."""
    spans = spanlib.window_spans(obs)
    xs = cpus_ms(spanlib.heights(spans or []))
    return sum(xs) / len(xs) if xs else None


def interp_busy_pct(obs):
    """100 x the CPU the whole process used while a height was synced over
    the time it took, over the applied `blocksync.sync_one` roots: ~100 is
    one interpreter lock saturated, whoever held it; above, native threads
    ran beside it."""
    spans = spanlib.window_spans(obs)
    roots = [h for h in spanlib.heights(spans or []) if h.get("pcpu") is not None]
    wall = sum(spanlib.ms(h) for h in roots)
    if wall <= 0:
        return None
    return 100.0 * sum_ms(roots, "pcpu") / wall


def mean_cpu_ms(obs, name: str):
    """Mean `cpu` of the spans of one name."""
    xs = cpus_ms(spanlib.named(spanlib.window_spans(obs) or [], name))
    return statistics.fmean(xs) if xs else None


def wire_cpu_ms(obs):
    """Mean over the operations that own a `hybrid.call` (as
    `sidecarlib.wire_ms` picks them) of the CPU the wire's two threads used
    for it: `cpu` of the node's `grpc.call` (its caller's thread: encode,
    the wait, decode) plus `cpu` of the `sidecar.request` that answered it
    (the connection's thread: decode, submit, the wait, encode)."""
    def reading(e):
        if not spanlib.named(e["sidecar"], "hybrid.call"):
            return None
        node = sum_ms(spanlib.named(e["node"], "grpc.call"))
        sidecar = sum_ms(spanlib.named(e["sidecar"], "sidecar.request"))
        return None if node is None or sidecar is None else node + sidecar

    xs = [x for x in map(reading, obs.samples.get("wire_ops") or []) if x is not None]
    return statistics.fmean(xs) if xs else None
