"""What the KDA kernels (`ops/pallas_kda.py`) are asked to do, in bytes and
FLOP, and which of a traced window's dispatches asked for it.

The work is counted from LIVE rows, T, heads, K and V alone, whatever
implements it (a dead row is copied through; the kernel's tiling, its walk
over a chunk's columns and its selector products are its own business), from
the sizes of a running matrix S: heads x K x V float32 values a (slot, state
layer).

- a STEP (`kda_step`: one position of a live row, one layer): S read and
  written once, 2 x 4 heads K V bytes, and 7 FLOP a value of S (the decay by
  channel, S^T k and its sum, the outer product k u^T and its sum, S^T q and
  its sum);
- a CHUNK (`kda_chunk`: T positions of ONE slot, one layer): S read and
  written once, and a head's q, k, g (T x K each), v (T x V) and beta (T)
  read and o (T x V) written; FLOP a head, the chunk form's products:
  2 T^2 K each for the k-k and the q-k scores, 2 T K V each for k and q
  against the incoming S and for the S out, T^2 V for the triangular solve
  and 2 T^2 V for the scores against U.

The roofline's floor is the larger of bytes over 819 GB/s and FLOP over 197
TFLOP/s (one TPU v5e chip). `joined` sums the work of exactly the dispatches
whose executions lie in the device trace, as `benchmark/ssd_work.py` does
(whose pairing of the K-step scan's executions with their issue spans it
borrows), AND the kernels' device time inside those same executions: a
dispatch span's args carry `ssm_rows` (live rows x layers through the state
kind's step kernel, here `kda_step`) and `ssm_chunk` (chunk tokens x layers
through its chunk kernel, `kda_chunk`), set in `runtime/slot_cache.py
state_word` for whichever matrix-state kind the model has. Work and time
come from the SAME executions, so the share does not move with the part of
the `jit_step` executions that `host_spans._joined` pairs with a span (84 %
in this model's cell, PERF.md section 7): an unpaired execution gives
neither. `joined` returns that part too, and the reader prints it.
"""

from __future__ import annotations

import bisect
from typing import NamedTuple

from benchmark import cells, host_spans
from benchmark.ssd_work import (HBM_BYTES_S, PEAK_FLOP_S, SCAN_PROGRAM,
                                _scan_pairs)
from benchmark.trace_reduce import (MODULES_LINE, OPS_LINE, _line,
                                    device_planes, op_name, program_name)

__all__ = ["HBM_BYTES_S", "PEAK_FLOP_S", "KERNELS", "PROJECTIONS", "Joined",
           "sizes", "step_work", "chunk_work", "op_seconds", "joined"]


PUBLISHED = "kimi-linear-48b-a3b-l8"  # the configuration with such a mixer


def sizes(cfg: dict) -> tuple[int, int, int]:
    """(heads, K, V) of the configuration's delta-rule mixer. A configuration
    that states none is given `PUBLISHED`'s: only a reader's arithmetic case
    meets that (`test_benchmark_readers` runs every case under `mistral-7b`'s
    file); a RUN under another model's file finds no `kda_*` operation in
    its profile and returns before it asks for sizes."""
    lin = (cfg.get("linear_attn_config")
           or cells.load_config(PUBLISHED)["linear_attn_config"])
    return lin["num_heads"], lin["head_dim"], lin["head_dim"]


def step_work(rows: float, heads: int, k: int, v: int) -> tuple[float, float]:
    """(bytes, FLOP) of `rows` live (row, layer) steps."""
    values = heads * k * v
    return 8.0 * values * rows, 7.0 * values * rows


def chunk_work(chunks: float, t: int, heads: int, k: int,
               v: int) -> tuple[float, float]:
    """(bytes, FLOP) of `chunks` (chunk, layer) calls of T positions."""
    bytes_ = 8.0 * heads * k * v + 4.0 * t * heads * (3 * k + 2 * v + 1)
    flop = heads * (4.0 * t * t * k + 3.0 * t * t * v + 6.0 * t * k * v)
    return bytes_ * chunks, flop * chunks


KERNELS = ("kda_chunk", "kda_step")  # `ops/pallas_kda.py`'s calls, by name
# `_kda_mixer`'s named projections: in, the gates' first pair, out
PROJECTIONS = ("q4_mm_kda_in", "q4_mm_kda_lo", "q4_mm_kda_out")


def _marked(trace: dict) -> list[tuple[int, int, int, str]]:
    """(plane, start ns, duration ns, mark) of the operations of `trace`
    (`host_spans.window_trace`: the profile as the run has parsed it already)
    whose RESULT's name holds one of `KERNELS` or `PROJECTIONS`, in ONE pass
    for both readers, kept on the trace. By name alone, as
    `ssd_work.op_seconds`: the profiler's events carry no scope."""
    if "kda_ops" not in trace:
        marks = KERNELS + PROJECTIONS
        found = []
        for p, plane in enumerate(device_planes(trace)):
            for name, start, dur, *_ in _line(plane, OPS_LINE):
                if "kda_" in name:
                    result = op_name(name)
                    for mark in marks:
                        if mark in result:
                            found.append((p, start, dur, mark))
                            break
        trace["kda_ops"] = found
    return trace["kda_ops"]


def op_seconds(trace: dict) -> dict[str, float]:
    """Device seconds, averaged over the planes, of the WINDOW's operations
    under each mark: {mark: seconds}."""
    sums = dict.fromkeys(KERNELS + PROJECTIONS, 0)
    for _p, _start, dur, mark in _marked(trace):
        sums[mark] += dur
    planes = max(len(device_planes(trace)), 1)
    return {m: v / 1e9 / planes for m, v in sums.items()}


class Joined(NamedTuple):
    """What the executions paired with a span asked of the kernels, and what
    the kernels took inside them."""
    bytes: float
    flop: float
    dispatches: int
    kernel_s: float  # averaged over the planes, as `op_seconds`
    step_share: float  # of the `jit_step` executions, the part paired
    scan_share: float  # of the K-step scan's


def joined(trace: dict, cfg: dict) -> Joined:
    """The work of the dispatches of `trace` whose execution the device's
    side holds and `host_spans._joined` or `_scan_pairs` pairs with its
    span, and the KDA kernels' device time inside exactly those
    executions."""
    heads, k, v = sizes(cfg)
    planes = device_planes(trace)
    owner, scans_seen = {}, 0  # a module event's plane, by identity
    for p, plane in enumerate(planes):
        for mod in _line(plane, MODULES_LINE):
            owner[id(mod)] = p
            scans_seen += program_name(mod[0]) == SCAN_PROGRAM
    step_pairs, steps_seen = host_spans._joined(trace)
    scan_pairs = _scan_pairs(trace)
    pairs = [(span, mod) for _p, span, mod in step_pairs] + scan_pairs
    bytes_ = flop = 0.0
    inside: list[list[tuple[int, int]]] = [[] for _ in planes]
    for span, mod in pairs:
        st = span[3]
        b0, f0 = step_work(float(st.get("ssm_rows", 0)), heads, k, v)
        t = int(st.get("chunk", 1))
        b1, f1 = chunk_work(float(st.get("ssm_chunk", 0)) / max(t, 1), t,
                            heads, k, v)
        bytes_, flop = bytes_ + b0 + b1, flop + f0 + f1
        inside[owner[id(mod)]].append((mod[1], mod[1] + mod[2]))
    for windows in inside:
        windows.sort()
    kernel_ns = 0
    for p, start, dur, mark in _marked(trace):
        i = bisect.bisect_right(inside[p], (start, float("inf"))) - 1
        if mark in KERNELS and i >= 0 and start < inside[p][i][1]:
            kernel_ns += dur
    return Joined(bytes_, flop, len(pairs),
                  kernel_ns / 1e9 / max(len(planes), 1),
                  len(step_pairs) / steps_seen if steps_seen else 1.0,
                  len(scan_pairs) / scans_seen if scans_seen else 1.0)
