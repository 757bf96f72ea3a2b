"""From a profiler trace to numbers: device busy time, the time of each
XLA module (program) and each device operation, collective time, and the
device's idle gaps named by what the host was doing in them.

The reduction works on a plain structure, so that it can be checked on a
small recorded trace (`tests/data/`) without the profiler:

    planes = [{"name": str, "lines": [{"name": str, "events": [[name, start_ns, dur_ns], ...]}]}]

`load_xplane_dir` makes that structure from what `jax.profiler` wrote.
Device planes are named `/device:TPU:<n>`; their line `XLA Ops` holds one
event per executed HLO operation and `XLA Modules` one per executed program.
Host spans are the `jax.profiler.TraceAnnotation`s whose names start with
`bench:` (the benchmark writes them) or `seam:`.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIXES = ("bench:", "seam:")
SEAM = "seam:batch_verify"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|all-to-all|reduce-scatter|collective-permute|collective-broadcast",
    re.I,
)
# XLA:CPU has no device plane: its executor threads stand in for one, so the
# rehearsal on a CPU walks the same code. Never a device number.
CPU_OPS_LINE = re.compile(r"^tf_XLAPjRtCpuClient/")


def load_xplane_dir(trace_dir: str) -> list[dict]:
    import jax

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        return []
    data = jax.profiler.ProfileData.from_file(paths[-1])
    planes = []
    for pl in data.planes:
        lines = []
        for ln in pl.lines:
            events = [[e.name, float(e.start_ns), float(e.duration_ns)] for e in ln.events]
            lines.append({"name": ln.name, "events": events})
        planes.append({"name": pl.name, "lines": lines})
    return planes


# -- interval arithmetic on sorted, disjoint [start, end) lists ----------------------


def union(intervals) -> list[tuple[float, float]]:
    out: list[tuple[float, float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def complement(u, lo: float, hi: float) -> list[tuple[float, float]]:
    out = []
    at = lo
    for s, e in u:
        if e <= lo or s >= hi:
            continue
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if at < hi:
        out.append((at, hi))
    return out


def intersect(a, b) -> list[tuple[float, float]]:
    out = []
    i = j = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a, b) -> list[tuple[float, float]]:
    if not a:
        return []
    return intersect(a, complement(b, a[0][0], a[-1][1]))


# -- the reduction ------------------------------------------------------------------


def _device_planes(planes):
    found = [(int(m.group(1)), pl) for pl in planes if (m := DEVICE_PLANE.match(pl["name"]))]
    if found:
        return [pl for _, pl in sorted(found, key=lambda x: x[0])], False
    # rehearsal on XLA:CPU
    for pl in planes:
        lines = [ln for ln in pl["lines"] if CPU_OPS_LINE.match(ln["name"])]
        if lines:
            events = [
                ev for ln in lines for ev in ln["events"]
                if ev[2] > 0 and not ev[0].startswith(("end: ", "ThreadpoolListener", "SlinkyThreadPool"))
            ]
            return [{"name": pl["name"], "lines": [{"name": OPS_LINE, "events": events}]}], True
    return [], False


def _line(plane, name):
    for ln in plane["lines"]:
        if ln["name"] == name:
            return ln["events"]
    return []


def host_spans(planes) -> list[tuple[str, float, float]]:
    out = []
    for pl in planes:
        if not pl["name"].startswith("/host:"):
            continue
        for ln in pl["lines"]:
            for name, start, dur in ln["events"]:
                if name.startswith(SPAN_PREFIXES):
                    out.append((name, start, start + dur))
    return sorted(out, key=lambda x: x[1])


def short_name(name: str) -> str:
    """`%while.369 = (s32[]...) while(...)` -> `while.369`: the trace names a
    device operation by its whole HLO text."""
    return name.split(" = ", 1)[0].lstrip("%")[:80]


def self_times(ops):
    """(short name, self ns) per event of one line: an operation that holds
    others (a while loop and its body) keeps only the time none of them
    covers, so the sums over names add up to the busy time."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    self_ns = [ev[2] for ev in ops]
    stack: list[int] = []  # indices of the operations open at this point
    for i in order:
        start, end = ops[i][1], ops[i][1] + ops[i][2]
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            parent = stack[-1]
            parent_end = ops[parent][1] + ops[parent][2]
            self_ns[parent] -= min(end, parent_end) - start
        stack.append(i)
    return [(short_name(ops[i][0]), max(self_ns[i], 0.0)) for i in range(len(ops))]


def reduce(planes: list[dict], window_s: float) -> dict:
    """The numbers the result line and the per-layer readers take from a
    trace. Seconds throughout; per-chip quantities are means over the chips."""
    devs, rehearsal = _device_planes(planes)
    out = {
        "chips": len(devs), "window_s": window_s, "busy_s": 0.0, "rehearsal": rehearsal,
        "modules": {}, "device_ops": [], "collective_s": 0.0, "idle_gaps": [],
    }
    if not devs:
        return out
    n = len(devs)
    op_time: dict[str, float] = {}
    modules: dict[str, dict] = {}
    busy = []
    for pl in devs:
        ops = _line(pl, OPS_LINE)
        busy.append(union((s, s + d) for _, s, d in ops))
        for name, d in self_times(ops):
            op_time[name] = op_time.get(name, 0.0) + d
        for name, _, d in _line(pl, MODULES_LINE):
            base = re.sub(r"\(\d+\)$", "", name)
            m = modules.setdefault(base, {"count": 0, "total_s": 0.0})
            m["count"] += 1
            m["total_s"] += d * 1e-9
    out["busy_s"] = sum(total(b) for b in busy) * 1e-9 / n
    for m in modules.values():  # per chip: every chip runs its share of a program
        m["count"] = m["count"] / n
        m["total_s"] = m["total_s"] / n
    out["modules"] = modules
    out["collective_s"] = sum(t for k, t in op_time.items() if COLLECTIVE.search(k)) * 1e-9 / n
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    out["device_ops"] = [[k, t * 1e-9 / n] for k, t in top]
    out["idle_gaps"] = idle_gaps(planes, devs[0], busy[0])
    return out


def idle_gaps(planes, dev, busy) -> list[list]:
    """Idle time of the first chip by what the host was doing, at most 10
    names, longest first. Idle inside a running program (between its
    operations) is the program's own; the rest goes to the innermost
    benchmark span that covers it, the backend seam first, split there into
    the part before the call's program started and the part after it ended."""
    spans = host_spans(planes)
    mods = union((s, s + d) for _, s, d in _line(dev, MODULES_LINE))
    if not busy:
        return []
    edges = [busy[0][0], busy[-1][1]] + [s for _, s, _ in spans] + [e for _, _, e in spans]
    lo, hi = min(edges), max(edges)
    gaps = complement(busy, lo, hi)
    named: dict[str, float] = {}

    def take(name, cover):
        nonlocal gaps
        got = total(intersect(gaps, cover))
        if got > 0:
            named[name] = named.get(name, 0.0) + got
        gaps = subtract(gaps, cover)

    take("within a program (between its operations)", mods)
    seam = [(s, e) for name, s, e in spans if name == SEAM]
    before, after, no_program = [], [], []
    for s, e in seam:
        inside = intersect(mods, [(s, e)]) if mods else intersect(busy, [(s, e)])
        if not inside:
            no_program.append((s, e))
            continue
        before.append((s, inside[0][0]))
        after.append((inside[-1][1], e))
    take("seam: pack+dispatch (before the call's program starts)", union(before))
    take("seam: host_msm tail + collect (after the call's program ends)", union(after))
    take("seam: call with no device program (host route)", union(no_program))
    # innermost first: a span that starts later lies inside one that started earlier
    by_name: dict[str, list] = {}
    for name, s, e in spans:
        if name != SEAM:
            by_name.setdefault(name, []).append((s, e))
    order = sorted(by_name, key=lambda k: total(by_name[k]) / len(by_name[k]))
    for name in order:
        take(name, union(by_name[name]))
    rest = total(gaps)
    if rest > 0:
        named["no benchmark span (between operations, fetch wait, reactor loop)"] = rest
    top = sorted(named.items(), key=lambda kv: -kv[1])[:10]
    return [[k, v * 1e-9] for k, v in top]
