"""From the profiler's trace to numbers: device busy time, time per
program, the operations that took most time, the longest idle gaps.

A trace is held as plain data, {"planes": [{"name", "lines": [{"name",
"events": [[name, start_ns, duration_ns], ...]}]}]}: `host_spans.from_xplane`
reads that out of the `.xplane.pb` the JAX profiler writes, and the fixture
under `fixtures/` is the same shape, so the reduction is tested without a chip.

On a TPU each chip is a plane "/device:TPU:<n>"; its line "XLA Modules"
has one event per executed program (named "<jitted name>(<id>)"), its line
"XLA Ops" one per operation (Pallas kernels by kernel name, XLA fusions by
fusion name). Busy time is the union of the operations' intervals; an idle
gap is a hole in that union, named by the scheduler's span it lies under
(`host_spans.gap_namer`) and, under none, by the program that ran next.
"""

from __future__ import annotations

import bisect
import re

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


def device_planes(trace: dict) -> list[dict]:
    return [p for p in trace["planes"] if p["name"].startswith("/device:TPU:")]


def _line(plane: dict, name: str) -> list:
    for ln in plane["lines"]:
        if ln["name"] == name:
            return ln["events"]
    return []


def _union(intervals):
    """Merged [start, end) intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def program_name(event_name: str) -> str:
    """'jit_step(1234)' -> 'jit_step'."""
    return re.sub(r"\(\d+\)$", "", event_name)


def op_name(event_name: str) -> str:
    """The result name of an HLO operation: '%fusion.3 = bf16[..] fusion(..)'
    -> '%fusion.3'. A Pallas kernel is a custom-call named after the kernel."""
    return event_name.split(" = ", 1)[0].strip()


# operations that only enclose others on the same line: counting them would
# count their bodies twice
CONTAINERS = ("%while", "%conditional", "%call")


def reduce(trace: dict, programs: dict[str, str], span_under=None) -> dict:
    """`programs` maps a role to the jitted name that plays it, e.g.
    {"decode": "jit_plain", "prefill": "jit_step"}. Returns, averaged over
    the device planes: busy_s, window_s (first operation's start to the last
    one's end), per role the device seconds and the count of executions, the
    ten operations with most time, the five longest idle gaps and the five
    largest totals of idle time. A gap is named "under <span>" where
    `span_under(plane index, start, end)` (`host_spans.gap_namer`) finds a
    span of the scheduler there, else by the program that ran next. A role
    whose program has no event in the trace is listed in `missing`."""
    planes = device_planes(trace)
    if not planes:
        raise ValueError("the trace holds no TPU device plane")
    busy = window = 0.0
    per_role = {r: [0.0, 0] for r in programs}
    ops_time: dict[str, float] = {}
    gaps: list[tuple[float, str]] = []
    gap_totals: dict[str, float] = {}
    for p, plane in enumerate(planes):
        ops = _line(plane, OPS_LINE)
        mods = sorted(_line(plane, MODULES_LINE), key=lambda e: e[1])
        if not ops:
            raise ValueError(f"plane {plane['name']}: no operation ran")
        merged = _union([(s, s + d) for _, s, d in ops])
        busy += sum(e - s for s, e in merged) / 1e9
        window += (merged[-1][1] - merged[0][0]) / 1e9
        for name, _, d in ops:
            short = op_name(name)
            if not short.startswith(CONTAINERS):
                ops_time[short] = ops_time.get(short, 0.0) + d / 1e9
        for name, _, d in mods:
            for role, prog in programs.items():
                if program_name(name) == prog:
                    per_role[role][0] += d / 1e9
                    per_role[role][1] += 1
        mod_starts = [m[1] for m in mods]
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            span = span_under(p, e0, s1) if span_under else None
            if span:
                label = "under " + span
            else:
                # the program whose first operation ended the gap: the last
                # module that had started by then
                i = bisect.bisect_right(mod_starts, s1) - 1
                nxt = program_name(mods[i][0]) if i >= 0 else "unknown"
                inside = i >= 0 and mods[i][1] < e0
                label = ("inside " if inside else "before ") + nxt
            gaps.append(((s1 - e0) / 1e9, label))
            gap_totals[label] = gap_totals.get(label, 0.0) + (s1 - e0) / 1e9
    n = len(planes)
    top_ops = sorted(ops_time.items(), key=lambda kv: -kv[1])[:10]
    longest = sorted(gaps, key=lambda g: -g[0])[:5]
    totals = sorted(gap_totals.items(), key=lambda kv: -kv[1])[:5]
    return {
        "busy_s": busy / n, "window_s": window / n,
        "roles": {r: {"seconds": v[0] / n, "count": v[1] / n}
                  for r, v in per_role.items()},
        "missing": [r for r, v in per_role.items() if v[1] == 0],
        "device_ops": [[k, v / n] for k, v in top_ops],
        "idle_gaps": ([["longest, " + lbl, sec] for sec, lbl in longest]
                      + [["total " + lbl, sec / n] for lbl, sec in totals]),
    }
