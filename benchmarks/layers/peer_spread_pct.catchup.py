"""How far the catch-up pool spreads its block requests over its peers:
100 x (1 - requests to the peer asked most in the window / requests sent in
the window), from the per-peer counts at both edges of the window
(`requests_by_peer`). 0 when one peer is asked for everything, 75 when four
share evenly."""


def read(obs, run):
    kept = obs.samples.get("reactor_counters")
    if kept is None or "requests_by_peer" not in kept[1]:
        return None  # no generator kept them, or a program from before the counters
    first, last, _ = kept
    grown = [n - first["requests_by_peer"].get(peer, 0) for peer, n in last["requests_by_peer"].items()]
    sent = last["requests_sent"] - first["requests_sent"]
    if sent <= 0 or not grown:
        return None
    return 100.0 * (1.0 - max(grown) / sent)
