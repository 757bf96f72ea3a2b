"""Median over operations of the node's `validation.verify_commit` minus the
`batch.dispatch` it holds: what `caller_outside_seam_ms.commit` reads in the
in-process cell, read in the node process (the two must agree)."""
from sidecarlib import node_outside_seam_ms


def read(obs, run):
    return node_outside_seam_ms(obs)
