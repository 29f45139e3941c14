"""Share of the device's busy time that the state-space mixers take: the
device time of the SSD kernels (`ssd_chunk*`, `ssd_step*`) and of the
mixer's two projections, which the program names by kind (`q4_mm_ssm_in`,
`q4_mm_ssm_out`), over the union of all operations' intervals in the window;
the kernels' and the projections' parts are printed apart. Nine of the
cell's ten layers are such layers: by the bytes of a decode step at 8 rows,
0.52 GB of projections and 0.60 GB of running matrices of some 4 GB.

Counted by NAME in the profile the run has parsed already
(`ssd_work.op_seconds`). The program also wraps the mixer in the scope
`ssm_mixer` (`models/forward.py _ssm_mixer`: the convolution over u and its
three earlier rows, the softplus, the gated norm), but the profiler's events
carry no scope (PERF.md "LEFT BY PR 29" (1): three traced runs read 0.000 s
under it), so those fusions are NOT in this share; asking every event's
statistics for the scope was a second pass over the whole profile, 40 s of a
traced run. The commit of the new rows into the rings and the snapshots runs
behind the layer scan: `cache.ssm_state_mb` counts the matrices' bytes. A
program without the names (every model without such layers, and the parent
of the PR that added them) reads nothing."""
from benchmark import host_spans, ssd_work

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
    seconds = ssd_work.op_seconds(trace)
    kernels = sum(seconds[k] for k in ssd_work.KERNELS)
    mm = sum(seconds[k] for k in ssd_work.PROJECTIONS)
    if kernels + mm == 0.0:
        print("step.ssm_share: no operation of the window is an SSD kernel "
              "or a state-space layer's projection", flush=True)
        return None
    busy = ctx.trace["busy_s"]
    print(f"step.ssm_share: state-space mixers {kernels + mm:.3f} s of "
          f"{busy:.3f} s busy, the SSD kernels {kernels:.3f} s and the two "
          f"projections {mm:.3f} s of it", flush=True)
    return 100.0 * (kernels + mm) / busy
