"""100 x CPU the whole process used while heights were synced over the time
they took (`pcpu` over wall of the applied `blocksync.sync_one` roots):
near 100, one interpreter lock was saturated and the sync thread's waits
were for it; well under, the process slept (a peer, the limiter, the device)."""
from cpulib import interp_busy_pct


def read(obs, run):
    return interp_busy_pct(obs)
