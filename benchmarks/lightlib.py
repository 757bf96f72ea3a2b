"""What the readers of the light-client cell share: the program's spans of
the `light.sync`s closed in the traced window (`spanlib.window_spans`), and
per sync sums over the spans each one holds. A sync's spans share its
`root`; a fetch made before it (the client's trust root) has a root of its
own and is not a sync's. Every reader returns None where there is nothing
to read: an untraced run, a ring that wrapped inside the window, or a
program without these spans.
"""

from __future__ import annotations

import spanlib

SYNC = "light.sync"


def _syncs(obs):
    """(the window's spans, the ids of its closed light.sync spans) or None."""
    spans = spanlib.window_spans(obs)
    if not spans:
        return None
    ids = {s["id"] for s in spans if s["name"] == SYNC}
    return (spans, ids) if ids else None


def per_sync_ms(obs, name: str):
    """Summed duration of the `name` spans inside the window's syncs, over
    those syncs. A sync with no such span adds 0."""
    found = _syncs(obs)
    if found is None:
        return None
    spans, ids = found
    return sum(spanlib.ms(s) for s in spans if s["name"] == name and s["root"] in ids) / len(ids)


def hop_hit_pct(obs):
    """Of the lanes the `batch.verify` calls under a `light.verify` offered
    (`entries`), the share the verified-triple cache answered (`hits`): 100
    where the prewarm covered every hop check."""
    found = _syncs(obs)
    if found is None:
        return None
    spans, ids = found
    by_id = {s["id"]: s for s in spans}

    def under_a_hop(s):
        p = by_id.get(s["parent"])
        while p is not None:
            if p["name"] == "light.verify":
                return True
            p = by_id.get(p["parent"])
        return False

    calls = [s for s in spans if s["name"] == "batch.verify" and s["root"] in ids and under_a_hop(s)]
    entries = sum(s["attrs"].get("entries", 0) for s in calls)
    if entries <= 0:
        return None
    return 100.0 * sum(s["attrs"].get("hits", 0) for s in calls) / entries
