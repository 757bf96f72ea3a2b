"""Mean over operations (a mean: `cpulib`) of the CPU the wire's two threads used: the node's
`grpc.call` plus the `sidecar.request` that answered it. Beside
`wire_ms.commit`: where the wall differs between two cells and this does not,
the difference is waiting, not work."""
from cpulib import wire_cpu_ms


def read(obs, run):
    return wire_cpu_ms(obs)
