"""The block bytes the joiner received a second over the window, as a share
of what the connections it asked may carry: peers asked x the
configuration's `recv_rate` (the p2p flow limiter's ceiling for each)."""
from loadedlib import reactor_growth


def read(obs, run):
    grown = reactor_growth(obs, "block_bytes_received")
    if grown is None:
        return None
    _, last, seconds = obs.samples["reactor_counters"]
    ceiling = last.get("peers_asked", 0) * float(run.config["p2p"]["recv_rate"])
    if seconds <= 0 or ceiling <= 0:
        return None
    return 100.0 * grown[0] / seconds / ceiling
