"""The scheduler's spans on the device clock.

Every `obs.trace.span` of the program is a `jax.profiler.TraceAnnotation`,
so a traced window's `.xplane.pb` holds them in the plane "/host:CPU", on
the line of the thread that emitted them, with their entry args as stats and
on the same clock as the device planes. `from_xplane` reads them beside the
two device lines `trace_reduce` uses, into the same plain data:

    {"planes": [{"name": "/device:TPU:0", "lines": [...]},
                {"name": "/host:CPU", "lines": [{"name": <thread>,
                  "events": [[name, start_ns, duration_ns, {stats}], ...]}]}]}

keeping of the host plane only events named `batch.*` (the fixture
`fixtures/trace_host_small.json` has this shape). Two reductions:
`dispatches` says what kind of dispatch each `jit_step` execution was,
`gaps` says what the host was doing while the device idled (and `gap_namer`
names one idle gap so, for the run's `breakdown`). The two clocks
are not quite one: `clock_offsets` finds the difference from the trace. A
program that emits no such spans (the parent of the PR that added them)
gives empty joins, and the readers return nothing.
"""

from __future__ import annotations

import bisect
import functools
import glob
import os
import statistics

from benchmark.trace_reduce import (MODULES_LINE, OPS_LINE, _line, _union,
                                    device_planes, program_name)

HOST_PLANE = "/host:CPU"
PREFIX = "batch."
STEP_PROGRAM = "jit_step"
# the spans that hold one synchronous `jit_step` dispatch, and its kind
DISPATCH_SPANS = {"batch.prefill": "prefill", "batch.mixed_step": "mixed",
                  "batch.single_step": "single"}
# every span under which the scheduler issues one program to the device
ISSUE_SPANS = {*DISPATCH_SPANS, "batch.super_step_issue", "batch.verify_issue"}
UNNAMED = "unnamed"


def from_xplane(log_dir: str) -> dict:
    """The device planes (modules and operations) and the host plane's
    `batch.*` events of the newest trace under `log_dir`."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    planes = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        lines = []
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name in (MODULES_LINE, OPS_LINE):
                    lines.append({"name": line.name, "events": [
                        [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                        for ev in line.events]})
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                events = [[ev.name, int(ev.start_ns), int(ev.duration_ns),
                           dict(ev.stats)]
                          for ev in line.events if ev.name.startswith(PREFIX)]
                if events:
                    lines.append({"name": line.name, "events": events})
        else:
            continue
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


@functools.lru_cache(maxsize=1)
def _window_trace(log_dir: str):
    try:
        return from_xplane(log_dir)
    except Exception as e:  # a reader never takes the run's other metrics down
        print(f"host_spans: no trace to read under {log_dir}: {e!r}",
              flush=True)
        return None


def window_trace(trace_dir):
    """The trace this process's traced window wrote under `trace_dir`, which
    a reader has from its `ctx.trace_dir` (`run.py` gives the profiler
    .bench_trace/<workload> of the checkout). Parsed once for the run and all
    readers; None (and one line) where there is none."""
    if not trace_dir:
        print("host_spans: this run kept no trace directory", flush=True)
        return None
    return _window_trace(trace_dir)


def scheduler_spans(trace: dict) -> list:
    """The `batch.*` events of the scheduler thread, by start: the host line
    that holds the most of them (clients' threads emit none; a second
    engine's scheduler would be another line)."""
    lines = [ln["events"] for p in trace["planes"] if p["name"] == HOST_PLANE
             for ln in p["lines"]]
    if not lines:
        return []
    return sorted(max(lines, key=len), key=lambda e: (e[1], -e[2]))


def _joined(trace: dict) -> tuple[list[tuple], int]:
    """(plane index, dispatch span, module event) for each `jit_step`
    execution and the dispatch span it overlaps most, if by more than half
    of itself; and the number of `jit_step` executions seen. The dispatch is
    synchronous, so the program runs between the span's `batch.launch` and
    the end of its `batch.fetch`; overlap and not containment, because the
    two planes' clocks differ by a millisecond or two (`clock_offsets`). The
    execution in flight when the profiler started has no span."""
    spans = [e for e in scheduler_spans(trace) if e[0] in DISPATCH_SPANS]
    starts = [e[1] for e in spans]
    pairs, seen = [], 0
    for p, plane in enumerate(device_planes(trace)):
        for mod in _line(plane, MODULES_LINE):
            name, start, dur = mod
            if program_name(name) != STEP_PROGRAM:
                continue
            seen += 1
            i = bisect.bisect_right(starts, start) - 1
            near = [spans[j] for j in (i - 1, i, i + 1) if 0 <= j < len(spans)]
            if not near:
                continue
            span = max(near, key=lambda e: min(start + dur, e[1] + e[2])
                       - max(start, e[1]))
            if (min(start + dur, span[1] + span[2])
                    - max(start, span[1])) * 2 > dur:
                pairs.append((p, span, mod))
    return pairs, seen


def clock_offsets(trace: dict) -> list[tuple[float, float, float]]:
    """Per device plane, what to add to its times to put them on the host
    plane's clock, with the bounds it lies between: (offset, lower, upper)
    in ns. The profiler aligns the two clocks only roughly (on a v5e the
    device's ran 0.8 to 2.1 ms behind the host's, another value each trace),
    and causality bounds the difference from both sides: no execution starts
    before its dispatch span does, none ends after its span does. The offset
    is the middle of the two bounds, which are some 0.5 to 1.5 ms apart: what
    a launch and a small fetch take at the least. (0, 0, 0) for a plane with
    no joined execution."""
    pairs, _ = _joined(trace)
    out = []
    for p in range(len(device_planes(trace))):
        mine = [(span, mod) for q, span, mod in pairs if q == p]
        if not mine:
            out.append((0.0, 0.0, 0.0))
            continue
        lower = max(span[1] - mod[1] for span, mod in mine)
        upper = min(span[1] + span[2] - mod[1] - mod[2] for span, mod in mine)
        out.append(((lower + upper) / 2, lower, upper))
    return out


def dispatches(trace: dict) -> tuple[list[dict], float]:
    """One row per `jit_step` execution that found its dispatch span: kind,
    chunk, riders, window, device_ns. And the share of the `jit_step`
    executions that did."""
    pairs, seen = _joined(trace)
    rows = []
    for _p, (span_name, _, _, stats), (_, _, dur) in pairs:
        kind = DISPATCH_SPANS[span_name]
        rows.append({
            "kind": kind,
            "chunk": int(stats.get("chunk", 1)),
            "riders": int(stats.get("rows" if kind == "single"
                                    else "riders", 0)),
            "window": int(stats.get("window", 0)),
            "device_ns": dur})
    return rows, (len(rows) / seen if seen else 0.0)


def by_kind(rows: list[dict]) -> list[tuple]:
    """(kind, chunk, window, count, total device ms, median device ms), most
    time first: the table a run prints, because a metric is one number. One
    program runs each (chunk, window), so a group's times hardly differ."""
    groups: dict[tuple, list[int]] = {}
    for r in rows:
        groups.setdefault((r["kind"], r["chunk"], r["window"]),
                          []).append(r["device_ns"])
    out = [(*key, len(ns), sum(ns) / 1e6, statistics.median(ns) / 1e6)
           for key, ns in groups.items()]
    return sorted(out, key=lambda g: -g[4])


def _innermost(spans: list) -> list[tuple[int, int, str]]:
    """Disjoint (start, end, name) segments, by start: each instant of the
    thread under the innermost span that covers it. `spans` is sorted by
    (start, -duration), so a parent comes before its children."""
    segs: list[tuple[int, int, str]] = []
    stack: list[tuple[int, str]] = []  # (end, name) of the open spans
    cursor = 0

    def emit(upto: int) -> None:
        nonlocal cursor
        if stack and upto > cursor:
            segs.append((cursor, upto, stack[-1][1]))
        cursor = max(cursor, upto)

    for name, start, dur, _stats in spans:
        while stack and stack[-1][0] <= start:
            emit(stack[-1][0])
            stack.pop()
        emit(start)
        stack.append((start + dur, name))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    return segs


def _split(segs: list, seg_starts: list, lo, hi) -> dict[str, float]:
    """The part of [lo, hi) under each span name of the disjoint `segs`."""
    parts: dict[str, float] = {}
    i = max(bisect.bisect_right(seg_starts, lo) - 1, 0)
    while i < len(segs) and segs[i][0] < hi:
        part = min(segs[i][1], hi) - max(segs[i][0], lo)
        if part > 0:
            parts[segs[i][2]] = parts.get(segs[i][2], 0) + part
        i += 1
    return parts


def gap_namer(trace: dict):
    """For `trace_reduce.reduce`: (device plane index, start, end of an idle
    gap on that plane's clock) -> the innermost scheduler span that covers
    more of it than any other and than none does, as `gaps` splits it; else
    None (a program without the spans: every gap)."""
    segs = _innermost(scheduler_spans(trace))
    seg_starts = [s[0] for s in segs]
    offsets = clock_offsets(trace)

    def name(plane: int, lo, hi):
        off = offsets[plane][0]
        parts = _split(segs, seg_starts, lo + off, hi + off)
        best = max(parts, key=parts.get, default=None)
        unnamed = hi - lo - sum(parts.values())
        return best if best and parts[best] >= unnamed else None
    return name


def gaps(trace: dict) -> dict:
    """The device's idle time inside the window (the holes in the union of
    "XLA Ops", as `trace_reduce.reduce` takes them), moved onto the host's
    clock (`clock_offsets`) and split among the innermost `batch.*` spans of
    the scheduler thread that cover each hole, with `unnamed` for what no
    span covers. Means over the device planes: {"idle_ns": {span name: ns},
    "total_ns"}; "dispatches", the scheduler's dispatch spans (a step, a scan
    or a verify block issued); "offsets", the clock offsets used."""
    spans = scheduler_spans(trace)
    segs = _innermost(spans)
    seg_starts = [s[0] for s in segs]
    planes = device_planes(trace)
    offsets = clock_offsets(trace)
    idle: dict[str, float] = {}
    total = 0
    for plane, (offset, _, _) in zip(planes, offsets):
        merged = _union([(s, s + d) for _, s, d in _line(plane, OPS_LINE)])
        for (_, lo), (hi, _) in zip(merged, merged[1:]):
            total += hi - lo
            lo, hi = lo + offset, hi + offset
            named = 0
            for span, part in _split(segs, seg_starts, lo, hi).items():
                idle[span] = idle.get(span, 0) + part
                named += part
            if hi - lo > named:
                idle[UNNAMED] = idle.get(UNNAMED, 0) + hi - lo - named
    n = max(len(planes), 1)
    return {"idle_ns": {k: v / n for k, v in idle.items()},
            "total_ns": total / n, "offsets": offsets,
            "dispatches": sum(1 for e in spans if e[0] in ISSUE_SPANS)}
