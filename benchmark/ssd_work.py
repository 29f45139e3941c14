"""What the SSD kernels (`ops/pallas_ssd.py`) are asked to do, in bytes and
FLOP, and which of a traced window's dispatches asked for it.

The work is counted from LIVE rows alone, whatever the kernels do with the
others (a dead row is copied through), from the sizes of a running matrix H:
heads x P x N float32 values a (slot, state layer).

- a STEP (`ssd_step`: one position of a live row, one layer): H read and
  written once, 2 x 4 heads P N bytes, and 5 FLOP a value of H (the decay, the
  outer product dt x B^T and its sum, the product with C and its sum);
- a CHUNK (`ssd_chunk`: T positions of ONE slot, one layer): H read and
  written once, and a head's dt x (T x P), B and C (T x N each) read and y
  (T x P) written; FLOP a head 2 T^2 P (the decay-masked scores against
  dt x), 2 T P N each for C against the incoming H and for the H out, and
  2 T^2 N once for C B^T.

The roofline's floor is the larger of bytes over 819 GB/s and FLOP over 197
TFLOP/s (one TPU v5e chip). `joined` sums the work of exactly the dispatches
whose executions lie in the device trace: a dispatch span's args carry
`ssm_rows` (live rows x layers through `ssd_step`) and `ssm_chunk` (chunk
tokens x layers through `ssd_chunk`), set in `runtime/batch_engine.py
_state_word`. A traced window is often CUT SHORT on the device's side (27.8 s
of 51, PERF.md "LEFT BY PR 41"), and whole-window counters over the trace's
seconds read up to 1.8 times too high.
"""

from __future__ import annotations

from benchmark import host_spans
from benchmark.trace_reduce import (MODULES_LINE, OPS_LINE, _line,
                                    device_planes, op_name, program_name)

HBM_BYTES_S, PEAK_FLOP_S = 819e9, 197e12  # TPU v5e, one chip
SCAN_PROGRAM, SCAN_SPAN = "jit_plain", "batch.super_step_issue"
SLACK_NS = 3_000_000  # the two planes' clocks differ by a millisecond or two


def sizes(cfg: dict) -> tuple[int, int, int]:
    """(heads, P, N) of the configuration's state-space mixer; the published
    file's where the configuration states none, which only a reader's
    arithmetic case meets (`test_benchmark_readers` runs every case under
    `mistral-7b`'s file): a RUN under another model's file finds no `ssd_*`
    operation in its profile and returns before it asks for sizes."""
    return (cfg.get("mamba_n_heads", 128), cfg.get("mamba_d_head", 64),
            cfg.get("mamba_d_state", 128))


def step_work(rows: float, heads: int, p: int, n: int) -> tuple[float, float]:
    """(bytes, FLOP) of `rows` live (row, layer) steps."""
    values = heads * p * n
    return 8.0 * values * rows, 5.0 * values * rows


def chunk_work(chunks: float, t: int, heads: int, p: int,
               n: int) -> tuple[float, float]:
    """(bytes, FLOP) of `chunks` (chunk, layer) calls of T positions."""
    bytes_ = 8.0 * heads * p * n + 4.0 * t * (2 * heads * p + 2 * n)
    flop = heads * (2.0 * t * t * p + 4.0 * t * p * n) + 2.0 * t * t * n
    return bytes_ * chunks, flop * chunks


KERNELS = ("ssd_chunk", "ssd_step")  # `ops/pallas_ssd.py`'s calls, by name
PROJECTIONS = ("q4_mm_ssm_in", "q4_mm_ssm_out")  # `_ssm_mixer`'s two


def op_seconds(trace: dict) -> dict[str, float]:
    """Device seconds, averaged over the planes, of the operations of
    `trace` (`host_spans.window_trace`: the profile as the run has parsed it
    already) whose RESULT's name holds one of `KERNELS` or `PROJECTIONS`
    (`%ssd_step.2`; an operation that merely reads such a result names it
    among its operands and is not counted): {mark: seconds}, in ONE pass
    over the window's millions of events for both readers. By name alone:
    the profiler's events carry no scope (PERF.md "LEFT BY PR 29" (1)), and
    reading every event's statistics for one costs a second parse of the
    whole profile, 30 s of a traced run."""
    if "ssd_op_seconds" not in trace:
        planes = device_planes(trace)
        sums = dict.fromkeys(KERNELS + PROJECTIONS, 0)
        for plane in planes:
            for name, _start, dur, *_ in _line(plane, OPS_LINE):
                # the whole text first: a substring search costs a tenth of
                # splitting the result's name off
                if "ssd_" in name or "q4_mm_ssm_" in name:
                    result = op_name(name)
                    for mark in sums:
                        if mark in result:
                            sums[mark] += dur
                            break
        trace["ssd_op_seconds"] = {
            m: v / 1e9 / max(len(planes), 1) for m, v in sums.items()}
    return trace["ssd_op_seconds"]


def _scan_pairs(trace: dict) -> list:
    """The K-step scan's executions with the issue span of each, in the
    order both were made (one device queue): an execution takes the oldest
    span not yet taken that began before it did."""
    spans = [e for e in host_spans.scheduler_spans(trace)
             if e[0] == SCAN_SPAN]
    pairs = []
    for plane in device_planes(trace):
        mods = sorted((m for m in _line(plane, MODULES_LINE)
                       if program_name(m[0]) == SCAN_PROGRAM),
                      key=lambda m: m[1])
        i = 0
        for mod in mods:
            if i < len(spans) and spans[i][1] <= mod[1] + SLACK_NS:
                pairs.append((spans[i], mod))
                i += 1
    return pairs


def joined(trace: dict, cfg: dict) -> tuple[float, float, int]:
    """(bytes, FLOP, dispatches) the SSD kernels were asked for by the
    dispatches of `trace` whose execution the device's side holds."""
    heads, p, n = sizes(cfg)
    stats = [span[3] for _p, span, _mod in host_spans._joined(trace)[0]]
    stats += [span[3] for span, _mod in _scan_pairs(trace)]
    bytes_ = flop = 0.0
    for st in stats:
        b0, f0 = step_work(float(st.get("ssm_rows", 0)), heads, p, n)
        t = int(st.get("chunk", 1))
        b1, f1 = chunk_work(float(st.get("ssm_chunk", 0)) / max(t, 1), t,
                            heads, p, n)
        bytes_, flop = bytes_ + b0 + b1, flop + f0 + f1
    return bytes_, flop, len(stats)
