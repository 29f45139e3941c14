"""Share of the device's idle time in the traced window that lies under a
`batch.*` span of the scheduler thread (`host_spans.gaps`): whether the spans
tile the scheduler's loop, so that an idle gap has an owner. The rest is
`unnamed`."""
from benchmark import host_spans

UNIT = "%"
LAYER = "scheduler"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"


def read(ctx):
    trace = host_spans.window_trace(ctx.trace_dir)
    if trace is None:
        return None
    g = host_spans.gaps(trace)
    if not g["total_ns"] or not g["dispatches"]:
        print("sched.gap_named_share: the trace holds no dispatch span of "
              "the scheduler, or no idle time: no reading", flush=True)
        return None
    unnamed = g["idle_ns"].get(host_spans.UNNAMED, 0.0)
    return 100.0 * (1.0 - unnamed / g["total_ns"])
