"""Triples the verified-triple cache's sweeps gave up per applied height:
`evicted` of every `batch.verify` in the window (the prefetch worker's and
the sync thread's) over the heights applied. At 1,024 validators a window
inserts 31,744 triples into a cache of 131,072 whose sweep takes the oldest
32,768, so about every window carries one: ~1,024 a height at rest."""
import spanlib


def read(obs, run):
    spans = spanlib.window_spans(obs)
    if not spans:
        return None
    heights = len(spanlib.heights(spans))
    calls = [s for s in spanlib.named(spans, "batch.verify") if "evicted" in s["attrs"]]
    if heights == 0 or not calls:
        return None
    return sum(s["attrs"]["evicted"] for s in calls) / heights
