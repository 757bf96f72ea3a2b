"""CPU the sync thread used per applied height (`cpu` of the
`blocksync.sync_one` roots): what a height costs the thread that gates it,
whatever else held the interpreter meanwhile."""
from cpulib import sync_cpu_ms_per_height


def read(obs, run):
    return sync_cpu_ms_per_height(obs)
