"""CPU the sync thread used inside `blocksync.part_set` per applied height:
beside `part_set_ms_per_height.catchup`, its wall, the difference is the time
the thread stood there without running."""
from cpulib import per_height_cpu_ms


def read(obs, run):
    return per_height_cpu_ms(obs, "blocksync.part_set")
