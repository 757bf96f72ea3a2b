"""Median over split calls of |end of `device.run` - end of
`hybrid.host_msm`|: how long the faster tier sat idle."""
from spanlib import split_imbalance_ms


def read(obs, run):
    return split_imbalance_ms(obs)
