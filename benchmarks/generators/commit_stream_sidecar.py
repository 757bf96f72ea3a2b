"""Traffic kind `commit_stream_sidecar`: `commit_stream` with the process
boundary of the deployment BASELINE.json's north star names. Two processes
on the chip's host: a node that never imports JAX (a child; its chain under
`CMTPU_BACKEND=auto` with `CMTPU_SIDECAR_ADDR` set is `grpc` -> `cpu`) and
the sidecar that owns the chip and serves the program's supervised chain
over the framed protocol on loopback TCP.

The process that runs `run.py` must hold the chip (the harness's device
gate, `memory_peak_bytes`, the profiler), so that process IS the sidecar: it
starts the backend as every cell does, serves it by the program's own
`open_sidecar` on `127.0.0.1:0`, and then only answers. The node child
(`sidecar_node.py`) runs `commit_stream`'s operations, warm-up, window and
answer check through the wire and times each operation by its own clock.
`run.counters()` and `health_problems()` read the sidecar's chain as in
every cell; the child adds its own chain's counters (`node`) and, in a traced
run, the spans it captured, merged here with this process's on the one clock
both share.

`correct` adds to `commit_stream`'s: the node's first tier answered every
call (chain `grpc` -> `cpu`, active tier `grpc`, no supervisor event, no
failure of the tier); the lanes the node sent are the lanes the sidecar's
hybrid tier counted over the window; the child never imported JAX.

Parameters (the traffic file): those of `commit_stream`.
"""

from __future__ import annotations

import multiprocessing

import harness
import sidecar_node
import sidecarlib

RPC_TIMEOUT_S = 1500.0  # no step of the child takes this long: then the run fails, it never hangs


def _program():
    """The program's shared server function; a program from before it
    cannot run this kind, and says so before anything starts."""
    from cometbft_tpu.sidecar import service

    if not hasattr(service, "open_sidecar"):
        raise harness.BenchFailure(
            "this program cannot run kind commit_stream_sidecar: cometbft_tpu.sidecar.service has "
            "no open_sidecar (a sidecar over the supervised chain, a stream delivered whole)"
        )
    return service


def _serve_node(run: harness.Run, conn, addr: str) -> harness.Observations:
    """Answers the child until it hands its observations up."""
    answers = {
        "addr": lambda: addr,
        "counters": run.counters,
        "compile_count": lambda: run.compile_log.count,
        "compile_summary": run.compile_log.summary,
        "health_problems": run.health_problems,
        "trace_start": run.trace_start,
        "trace_stop": run.trace_stop,
    }
    while True:
        if not conn.poll(RPC_TIMEOUT_S):
            raise harness.BenchFailure(f"the node child said nothing for {RPC_TIMEOUT_S:.0f} s")
        try:
            what, *args = conn.recv()
        except EOFError:
            raise harness.BenchFailure("the node child ended without a result")
        if what == "result":
            return args[0]
        if what == "failed":
            raise harness.BenchFailure(f"the node child failed:\n{args[0]}")
        conn.send((answers[what](*args),))


def _node_problems(before: dict, after: dict, sidecar_lanes: int) -> list[str]:
    out = []
    sup = after["supervisor"]
    if sup.get("chain") != ["grpc", "cpu"]:
        out.append(f"the node's chain is {sup.get('chain')}, not grpc -> cpu")
    if sup.get("active_tier") != "grpc":
        out.append(f"the node's active tier is {sup.get('active_tier')}")
    for key in harness.SUPERVISOR_EVENTS:
        if sup.get(key, 0) != 0:
            out.append(f"the node's supervisor counted {key} = {sup.get(key)}")
    if after["grpc_tier"].get("failures", 0) != 0:
        out.append(f"the node's grpc tier failed {after['grpc_tier']['failures']} calls")
    sent = sidecarlib.grown(before["grpc"], after["grpc"], "lanes_sent")
    if sent != sidecar_lanes:
        out.append(f"the node sent {sent} lanes over the window, the sidecar's hybrid tier "
                   f"counted {sidecar_lanes}")
    return out


def run(run: harness.Run) -> harness.Observations:
    service = _program()
    ctx = multiprocessing.get_context("spawn")
    conn, child_conn = ctx.Pipe()
    spec = {
        "cell": run.cell, "config": run.config, "traffic": run.traffic, "seed": run.seed,
        "seconds": run.seconds, "traced": run.traced, "t_start": run.t_start,
    }
    # Not a daemon: it starts the fixture workers. It signs while the backend starts here.
    proc = ctx.Process(target=sidecar_node.node_main, args=(child_conn, spec))
    proc.start()
    child_conn.close()
    server = None
    try:
        run.start_backend()
        server = service.open_sidecar("127.0.0.1:0", run.backend, say=harness.say).start()
        obs = _serve_node(run, conn, server.bound_addr)
    finally:
        child_problems = harness.stop_children([(proc, conn)])
        if server is not None:
            service.close_sidecar(server, say=harness.say)
    before, after = obs.counters_before, obs.counters_after
    sidecar_lanes = sum(sidecarlib.grown(before["hybrid"], after["hybrid"], k)
                        for k in ("device_lanes", "host_lanes"))
    obs.correct_problems += _node_problems(before["node"], after["node"], sidecar_lanes)
    obs.correct_problems += child_problems
    grpc = after["node"]["grpc"]
    client = {k: grpc.get(k) for k in ("unary_calls", "streamed_calls", "streamed_chunks",
                                       "stream_retries", "remote_chunk")}
    harness.say(f"node: chain {after['node']['supervisor'].get('chain')}, client {client}, "
                f"wire bytes a signature {sidecarlib.wire_bytes_per_sig(obs)}")
    if run.traced:
        obs.samples["wire_ops"] = sidecarlib.merge(obs)
        if obs.samples["wire_ops"]:
            harness.say(sidecarlib.breakdown(obs.samples["wire_ops"]))
    return obs
