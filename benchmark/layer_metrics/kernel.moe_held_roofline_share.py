"""The grouped expert kernels' share of their roofline where the chip holds a
SHARE of the router's experts: `kernel.moe_roofline_share`'s arithmetic (its
`work`, through the same `moe_trace` helper) at this configuration's expert
width, `moe_intermediate_size` (the accepted reader takes `intermediate_size`
where `moe_ffn_hidden_size` is missing, which in this family is the leading
dense layer's width). The counters are the same two, of the grouped
dispatches alone: the held experts a dispatch touched
(`batch_moe_grouped_experts_touched_total` x one expert's Q40 bytes, 24.8 MB
at 3 x 7168 x 2048) and the assignments that reached a held expert
(`batch_moe_grouped_assignments_total`); assignments to experts held
elsewhere are in neither the work nor the time. Padding rows and untouched
experts are in neither, so the share cannot pass 100 %. The two readers are a
`benchmark` PR's to merge (ROADMAP, Queue 2)."""
from benchmark import cells, moe_trace

UNIT = "%"
LAYER = "kernels"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"


def read(ctx):
    whole = cells.load_reader("kernel.moe_roofline_share")
    touched = ctx.counter_delta("batch_moe_grouped_experts_touched_total")
    real = ctx.counter_delta("batch_moe_grouped_assignments_total")
    planes = moe_trace.ops(ctx.trace_dir) if ctx.trace_dir else None
    if not touched or not real or not planes:
        return None
    kernel_s = moe_trace.seconds(planes, moe_trace.KERNEL)
    if kernel_s == 0.0:
        print("kernel.moe_held_roofline_share: no moe_grouped_q4 operation "
              "in the window", flush=True)
        return None
    width = (ctx.config.get("moe_intermediate_size")
             or ctx.config.get("moe_ffn_hidden_size")
             or ctx.config["intermediate_size"])
    bytes_, flop = whole.work({"hidden_size": ctx.config["hidden_size"],
                               "moe_ffn_hidden_size": width}, touched, real)
    by_bytes, by_flop = bytes_ / whole.HBM_BYTES_S, flop / whole.PEAK_FLOP_S
    print(f"kernel.moe_held_roofline_share: kernels {kernel_s:.3f} s; floor "
          f"{max(by_bytes, by_flop):.3f} s (bytes {bytes_ / 1e9:.1f} GB = "
          f"{by_bytes:.3f} s, {flop / 1e12:.1f} TFLOP = {by_flop:.3f} s)",
          flush=True)
    return 100.0 * max(by_bytes, by_flop) / kernel_s
