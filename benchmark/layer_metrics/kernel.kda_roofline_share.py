"""The KDA kernels' share of their roofline: the least time the chip could
take for the work the window's dispatches asked of them, over the device
time of the operations named `kda_chunk*` and `kda_step*` (the `name` of the
`pallas_call`s in `ops/pallas_kda.py`) in the same trace.

The work is `benchmark/kda_work.py`'s: bytes and FLOP of a step and of a
chunk from LIVE rows, T, heads, K and V alone (never from the kernels'
tiling, so whatever implements the delta rule is read against the same
work), summed from the span args (`ssm_rows`, `ssm_chunk`: the matrix-state
rows stepped and chunk tokens of whichever state kind the model has) of
exactly the dispatches JOINED to an execution in the trace, and NOT from
whole-window counters: a traced window is often cut short on the device's
side. The time is the kernels' INSIDE THOSE SAME EXECUTIONS: an execution
the join leaves without a span gives neither work nor time, so the share
does not move with the part joined, which is printed, with a warning under
the 95 % the other join-based readers ask for and no reading under half (too
few executions to stand for the window). A dead row's copy is in the time
and not in the work, so the share cannot pass 100 %. The floor is the larger
of bytes / 819 GB/s and FLOP / 197 TFLOP/s (one TPU v5e chip). A program
without the kernels or the span args (every other model's, and the parent of
the PR that added them) reads nothing."""
from benchmark import host_spans, kda_work

UNIT = "%"
LAYER = "kernels"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"
WARN_JOINED, MIN_JOINED = 0.95, 0.5


def read(ctx):
    trace = host_spans.window_trace(ctx.trace_dir) if ctx.trace_dir else None
    if trace is None:
        return None
    seconds = kda_work.op_seconds(trace)
    if not sum(seconds[k] for k in kda_work.KERNELS):
        print("kernel.kda_roofline_share: no kda_chunk or kda_step operation "
              "in the window", flush=True)
        return None
    j = kda_work.joined(trace, ctx.config)
    if not j.bytes or not j.kernel_s:
        print("kernel.kda_roofline_share: no dispatch span of the trace "
              "that carries ssm_rows or ssm_chunk is joined to an execution",
              flush=True)
        return None
    by_bytes = j.bytes / kda_work.HBM_BYTES_S
    by_flop = j.flop / kda_work.PEAK_FLOP_S
    print(f"kernel.kda_roofline_share: {j.dispatches} executions joined to "
          f"their span ({100 * j.step_share:.1f} % of the jit_step "
          f"executions, {100 * j.scan_share:.1f} % of the scan's); the "
          f"kernels inside them {j.kernel_s:.3f} s (of kda_step "
          f"{seconds['kda_step']:.3f} and kda_chunk "
          f"{seconds['kda_chunk']:.3f} in the window); floor "
          f"{max(by_bytes, by_flop):.4f} s (bytes {j.bytes / 1e9:.2f} GB = "
          f"{by_bytes:.4f} s, {j.flop / 1e12:.3f} TFLOP = {by_flop:.4f} s)",
          flush=True)
    if min(j.step_share, j.scan_share) < MIN_JOINED:
        print("kernel.kda_roofline_share: under half of the executions are "
              "joined to a span: no reading", flush=True)
        return None
    if min(j.step_share, j.scan_share) < WARN_JOINED:
        print("kernel.kda_roofline_share: WARNING: under 95 % of the "
              "executions are joined to a span (host_spans._joined asks an "
              "execution to overlap its span by half of itself); the share "
              "is that of the joined executions alone, work and time both",
              flush=True)
    return 100.0 * max(by_bytes, by_flop) / j.kernel_s
