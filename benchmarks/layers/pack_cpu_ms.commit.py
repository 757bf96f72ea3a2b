"""Mean CPU of `device.pack`'s own thread (a mean: `cpulib`): beside
`pack_ms.commit`, its median wall, the difference is the pack waiting (the
interpreter lock, `PACK_GATE`)."""
from cpulib import mean_cpu_ms


def read(obs, run):
    return mean_cpu_ms(obs, "device.pack")
