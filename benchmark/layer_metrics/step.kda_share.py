"""Share of the device's busy time that the delta-rule mixers take: the
device time of the KDA kernels (`kda_chunk*`, `kda_step*`) and of the
mixer's projections, which the program names for it (`q4_mm_kda_in`,
`q4_mm_kda_lo`, `q4_mm_kda_out`), over the union of all operations'
intervals in the window; the kernels' and the projections' parts are printed
apart. Six of the cell's eight layers are such layers: by the bytes of a
decode step at 8 rows, 0.13 GB of projections and 0.2 GB of running matrices
of some 2.2 GB, most of which is the experts'.

Counted by NAME in the profile the run has parsed already
(`kda_work.op_seconds`). The program also wraps the mixer in the scopes
`kda_mixer` and `kda_gate` (`models/forward.py _kda_mixer`: the convolution
over [q | k | v] and its three earlier rows, the unit lengths, the two
rank-128 second projections, the softplus, the head norm), but the
profiler's events carry no scope (PERF.md "LEFT BY PR 29" (1)), so those
fusions are NOT in this share, which is therefore a lower bound of the
mixer's. A program without the names (every model without such layers, and
the parent of the PR that added them) reads nothing."""
from benchmark import host_spans, kda_work

UNIT = "%"
LAYER = "step programs"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"


def read(ctx):
    if not ctx.trace_dir or not ctx.trace or not ctx.trace.get("busy_s"):
        return None
    trace = host_spans.window_trace(ctx.trace_dir)
    if trace is None:
        return None
    seconds = kda_work.op_seconds(trace)
    kernels = sum(seconds[k] for k in kda_work.KERNELS)
    mm = sum(seconds[k] for k in kda_work.PROJECTIONS)
    if kernels + mm == 0.0:
        print("step.kda_share: no operation of the window is a KDA kernel "
              "or a delta-rule layer's projection", flush=True)
        return None
    busy = ctx.trace["busy_s"]
    print(f"step.kda_share: delta-rule mixers {kernels + mm:.3f} s of "
          f"{busy:.3f} s busy, the KDA kernels {kernels:.3f} s and the "
          f"three projections {mm:.3f} s of it", flush=True)
    return 100.0 * (kernels + mm) / busy
