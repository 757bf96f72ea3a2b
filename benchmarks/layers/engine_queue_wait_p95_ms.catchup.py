"""The engine's own 95th-percentile admission wait (its ring of recent
requests at the end of the window; the ring is the program's, so it may
still hold requests of the warm-up)."""


def read(obs, run):
    return obs.counters_after["engine"].get("queue_wait_p95_ms")
