"""The grouped expert kernels' share of their roofline, for any routed
configuration: the least time the chip could take for the experts a window's
dispatches touched and the assignments they computed, over the device time of
the `moe_grouped_q4_*` operations in the same window.

The one general reader of this quantity. Its two twins differ only in where
they take an expert's width and which counters they read
(`kernel.moe_roofline_share`: `moe_ffn_hidden_size`, else `intermediate_size`;
`kernel.moe_held_roofline_share`: a share of the experts); here the width is
the configuration's `moe_intermediate_size` or `moe_ffn_hidden_size` and NEVER
`intermediate_size`, which in a model with a leading dense layer is that
layer's width (12288 beside experts of 1024 in Laguna-S-2.1: twelve times the
bytes, a share twelve times too high). A configuration that states neither key
has no routed experts of a stated width, and reads nothing.

The work is `kernel.moe_roofline_share`'s `work` at that width: every expert a
dispatch TOUCHED crosses HBM once (`batch_moe_grouped_experts_touched_total` x
3 matrices of hidden x width at 0.5625 bytes a weight), plus each assignment's
activations in bfloat16 (its input and output of hidden, its gated product
written and read, of width); FLOP 2 x `batch_moe_grouped_assignments_total` x
an expert's weights. Padding rows and untouched experts are in neither, so the
share cannot pass 100 %. The floor is the larger of bytes / 819 GB/s and FLOP /
197 TFLOP/s (one TPU v5e chip). A window with no such operation, or a program
without the counters, reads nothing."""
from benchmark import cells, moe_trace

UNIT = "%"
LAYER = "kernels"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"


def expert_width(cfg: dict):
    """The routed experts' hidden width, None where the file states none."""
    return cfg.get("moe_intermediate_size") or cfg.get("moe_ffn_hidden_size")


def read(ctx):
    width = expert_width(ctx.config)
    if not width:
        print("kernel.moe_expert_roofline_share: the configuration states no "
              "moe_intermediate_size nor moe_ffn_hidden_size", flush=True)
        return None
    touched = ctx.counter_delta("batch_moe_grouped_experts_touched_total")
    real = ctx.counter_delta("batch_moe_grouped_assignments_total")
    planes = moe_trace.ops(ctx.trace_dir) if ctx.trace_dir else None
    if not touched or not real or not planes:
        return None
    kernel_s = moe_trace.seconds(planes, moe_trace.KERNEL)
    if kernel_s == 0.0:
        print("kernel.moe_expert_roofline_share: no moe_grouped_q4 operation "
              "in the window", flush=True)
        return None
    twin = cells.load_reader("kernel.moe_roofline_share")
    bytes_, flop = twin.work({"hidden_size": ctx.config["hidden_size"],
                              "moe_ffn_hidden_size": width}, touched, real)
    by_bytes, by_flop = bytes_ / twin.HBM_BYTES_S, flop / twin.PEAK_FLOP_S
    print(f"kernel.moe_expert_roofline_share: experts of {width}: kernels "
          f"{kernel_s:.3f} s; floor {max(by_bytes, by_flop):.3f} s (bytes "
          f"{bytes_ / 1e9:.1f} GB = {by_bytes:.3f} s, {flop / 1e12:.1f} TFLOP "
          f"= {by_flop:.3f} s); {touched:.0f} experts touched, {real:.0f} "
          f"assignments, {real / touched:.2f} rows an expert", flush=True)
    return 100.0 * max(by_bytes, by_flop) / kernel_s
