"""Share of the device's busy time that the GROUPED expert layer takes: the
device time of its kernels (`moe_grouped_q4_gu`, `moe_grouped_q4_down`), and
of every operation whose event names the program's `moe_route` or `moe_ffn`
scope, over the union of all operations' intervals in the window.

It is the expert layer's share only in a cell whose every dispatch takes the
grouped layer, and is listed for those cells alone: a dispatch that goes
through the all-experts scan (`models/forward.takes_the_scan`: Mixtral's
64-token chunks) spends its expert time in XLA fusions that nothing in the
profile tells from the rest of the block, and that time is NOT counted here.
What the profile can name (my chip run, PR 29): the kernels, by their own
names. The scopes are `jax.named_scope`s in `models/forward.py` and reach each
operation's HLO metadata, but this profiler's events carry a name, an offset
and a duration and no metadata, so the XLA operations around the kernels
(router matmul, softmax and top-k, two sorts, the gather of the rows and the
weighted sum back: 0.04 ms of a layer's 7 in a 64-token chunk, same run) are
counted only where a later profiler exposes the scope. A program with neither
the kernels nor the scopes (the parent of the PR that added them) reads
nothing."""
from benchmark import moe_trace

UNIT = "%"
LAYER = "step programs"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"


def read(ctx):
    planes = moe_trace.ops(ctx.trace_dir) if ctx.trace_dir else None
    if not planes or not ctx.trace or not ctx.trace.get("busy_s"):
        return None
    moe = moe_trace.seconds(planes, moe_trace.ROUTE, moe_trace.FFN,
                            moe_trace.KERNEL)
    if moe == 0.0:
        print("step.moe_share: no operation of the window is a grouped expert "
              "kernel or carries the moe_route or moe_ffn scope", flush=True)
        return None
    busy = ctx.trace["busy_s"]
    scoped = moe_trace.seconds(planes, moe_trace.ROUTE, moe_trace.FFN)
    print(f"step.moe_share: grouped expert layer {moe:.3f} s of {busy:.3f} s busy: "
          f"the grouped kernels {moe_trace.seconds(planes, moe_trace.KERNEL):.3f}"
          f" s, operations naming a scope {scoped:.3f} s", flush=True)
    return 100.0 * moe / busy
