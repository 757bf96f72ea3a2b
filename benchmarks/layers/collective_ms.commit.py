"""Device time of the cross-chip operations a dispatch of the verify
program holds (all-reduce, all-gather, all-to-all, reduce-scatter,
collective-permute, by the names the xplane gives them), mean over the
chips. Nothing to read on one chip. The verify program's lanes are sharded
in and out, so what the compiler leaves to exchange is small (its
collective-permutes); the host's scatter and gather round a dispatch are
not device operations and are not counted here."""
from layerlib import verify_module


def read(obs, run):
    found = verify_module(run)
    if found is None or run.trace["chips"] < 2:
        return None
    return 1000.0 * run.trace["collective_s"] / found[0]
