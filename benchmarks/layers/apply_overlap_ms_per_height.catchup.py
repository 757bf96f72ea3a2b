"""How much of each height's apply_block ran while the next prefetch window
was being verified: the reactor's pipeline_overlap_ms over the window /
heights applied in it."""


def read(obs, run):
    n = obs.samples.get("heights_since_open", 0)
    if n <= 0:
        return None
    return obs.samples["pipeline_overlap_ms"] / n
