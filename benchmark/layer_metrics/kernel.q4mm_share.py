"""Share of the device's busy time that the fused Q40 dequant-matmul takes:
the device time of the operations named `q4_mm*` (the `name` of the
`pallas_call` in `ops/pallas_q4_mm.py`) over the union of all operations'
intervals in the window.

It says that the mechanism engages (a program whose matmuls at 2 to 512 rows
still go through XLA's dequantize-then-dot reads nothing here, as the parent
of the PR that made the kernel the default does), and what part of a
dispatch is one pass over the weights at the rows it was given: the part a
scheduler that fills its dispatches (fewer scratch rows among a 64-token
chunk's 512) can shrink. Lower is better at a given `itl_mean_ms`: the same
weights' pass in less of the time. The grouped expert kernels
(`moe_grouped_q4_*`, `step.moe_share`) are not counted."""
from benchmark import moe_trace

UNIT = "%"
LAYER = "kernels"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"
MARK = "q4_mm"


def read(ctx):
    planes = moe_trace.ops(ctx.trace_dir) if ctx.trace_dir else None
    if not planes or not ctx.trace or not ctx.trace.get("busy_s"):
        return None
    seconds = moe_trace.seconds(planes, MARK)
    if seconds == 0.0:
        print("kernel.q4mm_share: no operation of the window is a fused Q40 "
              "dequant-matmul", flush=True)
        return None
    busy = ctx.trace["busy_s"]
    print(f"kernel.q4mm_share: fused Q40 dequant-matmul {seconds:.3f} s of "
          f"{busy:.3f} s busy", flush=True)
    return 100.0 * seconds / busy
