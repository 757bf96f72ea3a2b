"""Traffic kind `commit_stream_mesh`: `commit_stream` on a host of several
chips. The operations, the warm-up, the window and the answer check are
`commit_stream`'s own (its file is loaded by path and run). What this kind
adds to `correct`: the planner's mesh is as wide as the cell's chips, and
every operation of the window took the sharded program
(`ops/ed25519_kernel.mesh_counters()["sharded_dispatches"]` grew by at
least the window's operations): a run in which one chip did the work of
four is not a slow run, it is wrong.

Parameters (the traffic file): those of `commit_stream`.
"""

from __future__ import annotations

import os
import sys

import harness

base = harness.load_by_path(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "commit_stream.py"),
    "generator_commit_stream",
)


def run(run: harness.Run) -> harness.Observations:
    chain_counters = run.counters

    def with_mesh() -> dict:
        ek = sys.modules["cometbft_tpu.ops.ed25519_kernel"]  # there since start_backend
        return {**chain_counters(), "mesh": ek.mesh_counters()}

    run.counters = with_mesh  # `commit_stream` reads them at the window's edges
    obs = base.run(run)
    width = obs.counters_after["hybrid"].get("last_timing", {}).get("mesh_devices")
    sharded = (obs.counters_after["mesh"]["sharded_dispatches"]
               - obs.counters_before["mesh"]["sharded_dispatches"])
    harness.say(f"mesh: width {width} for {run.chips} chip(s), {sharded} sharded dispatches for "
                f"{obs.attempted} operations")
    if width != run.chips:
        obs.correct_problems.append(f"the planner's mesh is {width} wide, the cell has {run.chips} chips")
    if sharded < obs.attempted:
        obs.correct_problems.append(
            f"{sharded} sharded dispatches for {obs.attempted} operations: some ran on one chip"
        )
    return obs
