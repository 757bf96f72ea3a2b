"""Of the lanes the hybrid tier was sent over the window (`device_lanes` +
`host_lanes`), the share that came in key columns refused window tables
because a key repeats (`resident_repeat_lanes`): a prefetch window is several
commits of one validator set, so this is how much of the tier's traffic that
SET's tables, gathered by lane, would serve. A program without the counter
gives nothing to read."""
from layerlib import delta


def read(obs, run):
    if "resident_repeat_lanes" not in obs.counters_after["hybrid"]:
        return None
    sent = delta(obs, "hybrid", "device_lanes") + delta(obs, "hybrid", "host_lanes")
    if sent <= 0:
        return None
    return 100.0 * delta(obs, "hybrid", "resident_repeat_lanes") / sent
