"""What traffic kind `commit_stream_4nodes` and its readers share: the spans
of N node processes and the sidecar's joined into operations BY CONNECTION,
the readers of the engine's merge, and what a traced run says of a height.

Request ids are each connection's own, and N nodes in lock-step count them
in lock-step, so `sidecarlib.merge`'s rule (the request whose interval lies
in the call's and whose `req` is equal) cannot tell two nodes' requests
apart. Here a node's `grpc.call` (`port`: its socket's local port; `req`) is
answered by the one `sidecar.request` with `conn` = that port and the same
`req`. An entry is `sidecarlib.merge`'s (`op`, `node`, `sidecar`) plus
`node_index` and `requests` (the `sidecar.request`s found: exactly one for an
operation that crossed the wire once), so the readers of `commit10k-sidecar`
read it unchanged. In a merged dispatch the chain's spans (`engine.dispatch`
down to `device.*`) hang under the FIRST request's span only; every other
request it carried is given them too (the dispatch is the first the one
dispatcher thread opened after the request's own `engine.queue_wait` ended),
so each entry holds the one `hybrid.call` that answered it.

Every reader returns None where there is nothing to read: an untraced run,
or a program without these spans or counters.
"""

from __future__ import annotations

import statistics

import spanlib
from sidecarlib import grown


def reference_slice(job):
    """Fixture worker: `answers_alone` of one slice of a request's lanes."""
    from reference.answers_alone import answer_alone

    return answer_alone(*job)[1]


# -- the join ----------------------------------------------------------------------


def _subtree(spans: list[dict], top: dict) -> list[dict]:
    """`top` and every span under it, among `spans` (one root's)."""
    kids: dict[int, list[dict]] = {}
    for s in spans:
        kids.setdefault(s["parent"], []).append(s)
    out, todo = [], [top]
    while todo:
        s = todo.pop()
        out.append(s)
        todo += kids.get(s["id"], [])
    return out


def join(obs) -> list[dict] | None:
    """The window's node operations with the sidecar's spans (module text)."""
    mine = spanlib.window_spans(obs)
    shipped = obs.samples.get("nodes_spans") or []
    if not mine or not shipped:
        return None
    by_root: dict[int, list[dict]] = {}
    for s in mine:
        by_root.setdefault(s["root"], []).append(s)
    requests: dict[tuple, list[dict]] = {}
    for r in spanlib.named(mine, "sidecar.request"):
        requests.setdefault((r["attrs"].get("conn"), r["attrs"].get("req")), []).append(r)
    dispatches = sorted(spanlib.named(mine, "engine.dispatch"), key=lambda s: s["t0"])

    def served_by(r: dict) -> list[dict]:
        """The request's own spans and, where another request's span holds
        the dispatch that carried it, that dispatch's."""
        own = by_root.get(r["id"], [])
        waits = spanlib.named(own, "engine.queue_wait")
        if not waits or spanlib.named(own, "engine.dispatch"):
            return own
        after = [d for d in dispatches if d["t0"] >= waits[-1]["t1"]]
        return own + (_subtree(by_root[after[0]["root"]], after[0]) if after else [])

    out = []
    for k, node in enumerate(shipped):
        theirs = spanlib.select(node.get("spans") or [], node.get("dropped", 0), obs.window)
        for op in spanlib.ops(theirs or []):
            under = [s for s in theirs if s["root"] == op["id"]]
            found = []
            for call in spanlib.named(under, "grpc.call"):
                key = (call["attrs"].get("port"), call["attrs"].get("req"))
                if None in key:
                    continue  # a program whose spans do not name the connection
                found += [r for r in requests.get(key, ())
                          if r["t0"] >= call["t0"] and r["t1"] <= call["t1"]]
            sidecar = [s for r in found for s in served_by(r)]
            out.append({"node_index": k, "op": op, "node": under, "requests": found,
                        "sidecar": sidecar})
    return out or None


# -- the readers -------------------------------------------------------------------


def requests_per_dispatch(obs):
    """Requests the sidecar's engine admitted over the dispatches it made,
    over the window: 1.0 where nothing merges, N where N nodes' commits
    always share one dispatch."""
    before, after = obs.counters_before.get("engine", {}), obs.counters_after.get("engine", {})
    requests, dispatches = grown(before, after, "requests"), grown(before, after, "dispatches")
    if requests is None or not dispatches or dispatches < 0:
        return None
    return requests / dispatches


def dedup_lane_share_pct(obs):
    """Of the lanes the server received over the window, the share the
    engine's merge found already in the dispatch (`dedup_sigs`): lanes the
    chip did not run a second time."""
    try:
        dedup = grown(obs.counters_before["engine"], obs.counters_after["engine"], "dedup_sigs")
        lanes = grown(obs.counters_before["server"], obs.counters_after["server"], "lanes_in")
    except KeyError:
        return None
    if dedup is None or not lanes or lanes < 0:
        return None
    return 100.0 * dedup / lanes


def merged_dispatches(spans) -> list[tuple[dict, float]]:
    """(an `engine.dispatch` of two requests or more, the summed ms of its
    `engine.merge` spans, both phases)."""
    merge_ms: dict[int, float] = {}
    for s in spanlib.named(spans, "engine.merge"):
        merge_ms[s["parent"]] = merge_ms.get(s["parent"], 0.0) + spanlib.ms(s)
    return [(d, merge_ms[d["id"]]) for d in spanlib.named(spans, "engine.dispatch")
            if d["attrs"].get("requests", 1) >= 2 and d["id"] in merge_ms]


def engine_merge_ms(obs):
    """Median over the merged dispatches of the window of their
    `engine.merge` spans, pack and slice together: the per-triple walk."""
    xs = [ms for _, ms in merged_dispatches(spanlib.window_spans(obs) or [])]
    return statistics.median(xs) if xs else None


# -- what a traced run says of its heights ------------------------------------------


def _med(xs) -> str:
    xs = [x for x in xs if x is not None]
    return f"{statistics.median(xs):.2f}" if xs else "-"


def _one_ms(spans, name):
    found = spanlib.named(spans, name)
    return sum(spanlib.ms(s) for s in found) if found else None


def height_report(obs) -> list[str]:
    """Lines for the builder: a height across the processes, from the joined
    spans of the traced part of the window. An operation is `alone` where
    the dispatch that answered it carried one request, else `merged`."""
    ops = obs.samples.get("wire_ops") or []
    mine = spanlib.window_spans(obs) or []
    if not ops or not mine:
        return []
    dispatches = spanlib.named(mine, "engine.dispatch")
    heights = [h for h in obs.samples.get("heights", [])
               if any(h["t_release"] <= e["op"]["t0"] <= h["t_done"] for e in ops)]
    per_height = [sum(h["t_release"] <= d["t0"] <= h["t_done"] for d in dispatches) for h in heights]
    shape = {n: per_height.count(n) for n in sorted(set(per_height))}
    unique = [d["attrs"].get("unique") for d in dispatches if d["attrs"].get("unique") is not None]
    over = [d for d in dispatches if d["attrs"].get("requests", 1) >= 2
            and d["attrs"].get("lanes", 0) > obs.counters_after["engine"].get("max_sigs", 0)]
    lines = [
        f"traced heights {len(heights)}: dispatches a height {shape}, wall a height "
        f"{_med([(h['t_done'] - h['t_release']) * 1000 for h in heights])} ms; engine.dispatch spans "
        f"{len(dispatches)}, most unique lanes {max(unique, default=None)}, merged with lanes over "
        f"the cap {len(over)}; joined requests an operation "
        f"{sorted({len(e['requests']) for e in ops})}"
    ]
    roles: dict[str, list[dict]] = {"alone": [], "merged": []}
    for e in ops:
        d = spanlib.named(e["sidecar"], "engine.dispatch")
        if d:
            roles["alone" if d[0]["attrs"].get("requests", 1) < 2 else "merged"].append(e)
    for role, es in roles.items():
        if not es:
            continue
        lines.append(
            f"{role} ({len(es)} operations, medians in ms): verify_commit "
            f"{_med([spanlib.ms(e['op']) for e in es])}, node batch.dispatch "
            f"{_med([_one_ms(e['node'], 'batch.dispatch') for e in es])}, grpc.call "
            f"{_med([_one_ms(e['node'], 'grpc.call') for e in es])}, sidecar.request "
            f"{_med([_one_ms(e['sidecar'], 'sidecar.request') for e in es])}, sidecar.decode "
            f"{_med([_one_ms(e['sidecar'], 'sidecar.decode') for e in es])}, sidecar engine.queue_wait "
            f"{_med([_one_ms(e['sidecar'], 'engine.queue_wait') for e in es])}, engine.merge "
            f"{_med([_one_ms(e['sidecar'], 'engine.merge') for e in es])}, hybrid.call "
            f"{_med([_one_ms(e['sidecar'], 'hybrid.call') for e in es])}, device.pack "
            f"{_med([_one_ms(e['sidecar'], 'device.pack') for e in es])}, sidecar.encode "
            f"{_med([_one_ms(e['sidecar'], 'sidecar.encode') for e in es])}"
        )
    merged = merged_dispatches(mine)
    if merged:
        phase = {p: [spanlib.ms(s) for s in spanlib.named(mine, "engine.merge")
                     if s["attrs"].get("phase") == p] for p in ("pack", "slice")}
        lines.append(
            f"merged dispatches {len(merged)}: requests {_med([d['attrs']['requests'] for d, _ in merged])}, "
            f"lanes offered {_med([d['attrs']['lanes'] for d, _ in merged])}, unique "
            f"{_med([d['attrs'].get('unique') for d, _ in merged])}, fingerprint_ms "
            f"{_med([d['attrs'].get('fingerprint_ms') for d, _ in merged])}, engine.merge pack "
            f"{_med(phase['pack'])} + slice {_med(phase['slice'])} ms"
        )
    return lines
