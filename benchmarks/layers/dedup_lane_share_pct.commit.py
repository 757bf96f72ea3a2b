"""Of the lanes the server received over the window (`SidecarServer`'s
`lanes_in`), the share the engine's merge found already in the dispatch
(`dedup_sigs`): lanes four nodes of one chain offered and the chip ran once."""
from multinodelib import dedup_lane_share_pct


def read(obs, run):
    return dedup_lane_share_pct(obs)
