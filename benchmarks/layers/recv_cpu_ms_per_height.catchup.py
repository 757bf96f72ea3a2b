"""CPU the p2p receive threads used per applied height: `cpu` of every
`p2p.recv_msg` (decrypting, framing and reassembling a message's packets,
the flow limiter's bookkeeping), all connections together."""
from cpulib import per_height_cpu_ms


def read(obs, run):
    return per_height_cpu_ms(obs, "p2p.recv_msg")
