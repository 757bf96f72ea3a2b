"""95th percentile of `engine.queue_wait` inside the window (the engine's own
ring, read by `engine_queue_wait_p95_ms.catchup`, still holds the warm-up)."""
from spanlib import p95_ms


def read(obs, run):
    return p95_ms(obs, "engine.queue_wait")
