"""Share of the device's busy time that the paged attention kernels take in a
model with kinds of attention layer: the device time of the operations named
`paged_attn_window*` and `paged_attn_full*` (the `name` of the `pallas_call`
in `ops/pallas_paged_attention.py`, which `models/forward.py` gives by the
layer's kind) over the union of all operations' intervals in the window,
printed apart by kind. The window keeps a sliding layer's kernel at five
128-key steps a row whatever the context, so its part stays level while the
full layers' grows with the rows' lengths. A program that names no kernel by
kind (every model of one kind of layer, and the parent of the PR that added
the kinds) reads nothing."""
from benchmark import moe_trace

UNIT = "%"
LAYER = "step programs"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"
WINDOW, FULL = "paged_attn_window", "paged_attn_full"


def read(ctx):
    planes = moe_trace.ops(ctx.trace_dir) if ctx.trace_dir else None
    if not planes or not ctx.trace or not ctx.trace.get("busy_s"):
        return None
    window = moe_trace.seconds(planes, WINDOW)
    full = moe_trace.seconds(planes, FULL)
    if window + full == 0.0:
        print("step.attn_share: no operation of the window is a paged "
              "attention kernel named by kind", flush=True)
        return None
    busy = ctx.trace["busy_s"]
    print(f"step.attn_share: window layers' kernel {window:.3f} s "
          f"({100 * window / busy:.2f} %), full layers' {full:.3f} s "
          f"({100 * full / busy:.2f} %) of {busy:.3f} s busy", flush=True)
    return 100.0 * (window + full) / busy
