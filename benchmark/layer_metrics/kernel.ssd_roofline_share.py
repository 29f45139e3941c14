"""The SSD kernels' share of their roofline: the least time the chip could
take for the work the window's dispatches asked of them, over the device
time of the operations named `ssd_chunk*` and `ssd_step*` (the `name` of the
`pallas_call`s in `ops/pallas_ssd.py`) in the same trace.

The work is `benchmark/ssd_work.py`'s: bytes and FLOP of a step and of a
chunk from LIVE rows, T, heads, P and N, summed from the span args
(`ssm_rows`, `ssm_chunk`) of exactly the dispatches JOINED to an execution in
the trace (`jit_step` executions to their dispatch spans by
`host_spans._joined`, the K-step scan's to `batch.super_step_issue` in
order), and NOT from whole-window counters: a traced window is often cut
short on the device's side, and counters over the trace's seconds read up to
1.8 times too high (PERF.md "LEFT BY PR 41"). An execution without a span
(the one in flight when the profiler started) adds time and no work, and a
dead row's copy is in the time and not in the work, so the share cannot pass
100 %. The floor is the larger of bytes / 819 GB/s and FLOP / 197 TFLOP/s
(one TPU v5e chip). A program without the kernels or the span args (every
other model's, and the parent of the PR that added them) reads nothing."""
from benchmark import host_spans, ssd_work

UNIT = "%"
LAYER = "kernels"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"


def read(ctx):
    trace = host_spans.window_trace(ctx.trace_dir) if ctx.trace_dir else None
    if trace is None:
        return None
    seconds = ssd_work.op_seconds(trace)
    kernel_s = sum(seconds[k] for k in ssd_work.KERNELS)
    if kernel_s == 0.0:
        print("kernel.ssd_roofline_share: no ssd_chunk or ssd_step operation "
              "in the window", flush=True)
        return None
    bytes_, flop, n = ssd_work.joined(trace, ctx.config)
    if not bytes_:
        print("kernel.ssd_roofline_share: no dispatch span of the trace "
              "carries ssm_rows or ssm_chunk", flush=True)
        return None
    by_bytes = bytes_ / ssd_work.HBM_BYTES_S
    by_flop = flop / ssd_work.PEAK_FLOP_S
    print(f"kernel.ssd_roofline_share: kernels {kernel_s:.3f} s over {n} "
          f"joined dispatches; floor {max(by_bytes, by_flop):.4f} s (bytes "
          f"{bytes_ / 1e9:.2f} GB = {by_bytes:.4f} s, {flop / 1e12:.3f} "
          f"TFLOP = {by_flop:.4f} s)", flush=True)
    return 100.0 * max(by_bytes, by_flop) / kernel_s
