"""Device idle time of the traced window over the number of dispatches the
scheduler issued in it (its `batch.prefill`, `batch.mixed_step`,
`batch.single_step`, `batch.super_step_issue` and `batch.verify_issue`
spans): what the host adds, per dispatch, to the gap between two tokens. Idle
time is the holes in the union of the device's operations (as
`device.idle_share` takes it); `host_spans.gaps` splits it among the
scheduler's innermost `batch.*` spans, and the table of idle seconds per span
name is printed before the result line. A program without those spans gives
no reading."""
from benchmark import host_spans

UNIT = "ms"
LAYER = "scheduler"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"


def read(ctx):
    trace = host_spans.window_trace(ctx.trace_dir)
    if trace is None:
        return None
    g = host_spans.gaps(trace)
    if not g["dispatches"]:
        print("sched.gap_ms: the trace holds no dispatch span of the "
              "scheduler: no reading", flush=True)
        return None
    for i, (off, lo, hi) in enumerate(g["offsets"]):
        print(f"sched.gap_ms: device plane {i} is read {off / 1e6:.3f} ms "
              f"later, on the host's clock (bounds {lo / 1e6:.3f} and "
              f"{hi / 1e6:.3f} ms)", flush=True)
    print(f"sched.gap_ms: {g['total_ns'] / 1e9:.3f} s idle over "
          f"{g['dispatches']} dispatches, by the scheduler's span:",
          flush=True)
    for name, ns in sorted(g["idle_ns"].items(), key=lambda kv: -kv[1]):
        print(f"  {name:24s} {ns / 1e9:8.3f} s", flush=True)
    return g["total_ns"] / 1e6 / g["dispatches"]
