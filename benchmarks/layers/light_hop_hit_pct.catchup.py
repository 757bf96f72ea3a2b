"""Of the lanes the hop checks' `batch.verify` calls offered (those under a
`light.verify` of a sync), the share the verified-triple cache answered: 100
where the prewarm covered every hop check, lower where a hop's triples went
to the device or the host on the sync's critical path."""
from lightlib import hop_hit_pct


def read(obs, run):
    return hop_hit_pct(obs)
