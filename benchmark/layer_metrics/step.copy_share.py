"""Share of the device's busy time spent in XLA operations that only move
bytes: those whose NAME (`trace_reduce.op_name`: the result's name, not the
event's whole text, which also holds the operands' and the scopes') starts
with `copy` or holds `dynamic-slice`, over the union of all operations'
intervals in the window.

It says whether a step program still makes buffers of its own for what a
kernel could read in place. In the MoE cells it read a third of the busy
time while the layer scan sliced every layer's expert stack, touched or
not, out of (L, E, rows, K/2) before the grouped kernels or the
dequant-matmul might read it (`dynamic-slice_bitcast_fusion`,
`constant_dynamic-slice_fusion`); in the dense cell it is the K and V pool
re-laid into the step program's layout and back in every dispatch (`copy`).
Lower is better. A kernel's own traffic (a `custom-call`) and the fusions
that compute are not counted, whatever they slice inside."""
from benchmark import moe_trace
from benchmark.trace_reduce import op_name

UNIT = "%"
LAYER = "step programs"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"


def moves_bytes_only(name: str) -> bool:
    name = name.lstrip("%")
    return name.startswith("copy") or "dynamic-slice" in name


def read(ctx):
    planes = moe_trace.ops(ctx.trace_dir) if ctx.trace_dir else None
    if not planes or not ctx.trace or not ctx.trace.get("busy_s"):
        return None
    by_name = {}
    for plane in planes:
        for ev in plane:
            name = op_name(ev[0])
            if moves_bytes_only(name):
                by_name[name] = by_name.get(name, 0.0) + ev[2] / 1e9 / len(planes)
    seconds, busy = sum(by_name.values()), ctx.trace["busy_s"]
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:16]
    print(f"step.copy_share: {seconds:.3f} s of {busy:.3f} s busy in "
          f"{len(by_name)} operations that only move bytes: "
          + ", ".join(f"{n} {s:.3f}" for n, s in top), flush=True)
    return 100.0 * seconds / busy
