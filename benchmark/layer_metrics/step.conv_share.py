"""Share of the device's busy time that the gated short convolutions take:
the device time of every operation whose event names the program's
`short_conv` scope (`models/forward.py _short_conv`: the gates, the three
taps over v and its two earlier rows, the reads of a slot's ring) or is one
of the convolution layers' two projections, which the program names by kind
(`q4_mm_conv_in`, `q4_mm_conv_out`, as it names `paged_attn_window`), over
the union of all operations' intervals in the window; the projections' part
is printed apart. 18 of LFM2's 24 layers are such layers, each 16.8 M
weights of a layer's 370 M: by the bytes about 4 % of a step. The commit of
the new rows into the rings and the blocks' snapshots runs behind the layer
scan and carries no scope of its own: `cache.state_write_kb` counts its
bytes. A program with neither the scope nor the names (every model without
such layers, and the parent of the PR that added them) reads nothing."""
from benchmark import moe_trace

UNIT = "%"
LAYER = "step programs"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"
SCOPE, PROJECTIONS = "short_conv", ("q4_mm_conv_in", "q4_mm_conv_out")


def read(ctx):
    planes = moe_trace.ops(ctx.trace_dir) if ctx.trace_dir else None
    if not planes or not ctx.trace or not ctx.trace.get("busy_s"):
        return None
    conv = moe_trace.seconds(planes, SCOPE, *PROJECTIONS)
    if conv == 0.0:
        print("step.conv_share: no operation of the window carries the "
              "short_conv scope or is a convolution layer's projection",
              flush=True)
        return None
    busy = ctx.trace["busy_s"]
    mm = moe_trace.seconds(planes, *PROJECTIONS)
    print(f"step.conv_share: convolution layers {conv:.3f} s of {busy:.3f} s "
          f"busy, their two projections {mm:.3f} s of it", flush=True)
    return 100.0 * conv / busy
