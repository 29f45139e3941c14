"""The grouped expert kernels' share of their roofline: the least time the
chip could take for the work the window's counters report, over the device
time of the `moe_grouped_q4_*` operations in the same window.

The work, from the program's counters and the configuration's widths:
- bytes: every expert a dispatch TOUCHED crosses HBM once
  (`batch_moe_grouped_experts_touched_total` x the Q40 bytes of one expert, 0.5625 a
  weight, 3 matrices of hidden x expert width), plus each real row's
  activations in the engine's bfloat16: its input read and its output written
  (hidden each), its gated product written and read back (expert width each);
- FLOP: 2 x `batch_moe_grouped_assignments_total` x the weights of one expert.
Both counters hold the dispatches that went through the grouped layer alone:
the program sends a dispatch with 64 rows an expert and more through its
all-experts scan (a 64-token chunk in the Mixtral cell), whose time is not
under the kernels' names.
Padding rows and untouched experts are in neither, so the share cannot pass
100 %. The floor is the larger of bytes / 819 GB/s and FLOP / 197 TFLOP/s
(one TPU v5e chip, Google Cloud "TPU v5e": the only device this benchmark
runs on). A window with no such operation at all reads nothing."""
from benchmark import moe_trace

UNIT = "%"
LAYER = "kernels"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"
HBM_BYTES_S, PEAK_FLOP_S = 819e9, 197e12  # TPU v5e, one chip
Q40_BYTES = 0.5625
ACT_BYTES = 2  # bfloat16 rows


def expert_weights(cfg: dict) -> int:
    width = cfg.get("moe_ffn_hidden_size") or cfg["intermediate_size"]
    return 3 * cfg["hidden_size"] * width


def work(cfg: dict, touched: float, assignments: float):
    """(bytes, FLOP) of the touched experts and the real rows."""
    width = cfg.get("moe_ffn_hidden_size") or cfg["intermediate_size"]
    bytes_ = (touched * expert_weights(cfg) * Q40_BYTES
              + assignments * 2 * (cfg["hidden_size"] + width) * ACT_BYTES)
    return bytes_, 2.0 * assignments * expert_weights(cfg)


def read(ctx):
    touched = ctx.counter_delta("batch_moe_grouped_experts_touched_total")
    real = ctx.counter_delta("batch_moe_grouped_assignments_total")
    planes = moe_trace.ops(ctx.trace_dir) if ctx.trace_dir else None
    if not touched or not real or not planes:
        return None
    kernel_s = moe_trace.seconds(planes, moe_trace.KERNEL)
    if kernel_s == 0.0:
        print("kernel.moe_roofline_share: no moe_grouped_q4 operation in the "
              "window", flush=True)
        return None
    bytes_, flop = work(ctx.config, touched, real)
    by_bytes, by_flop = bytes_ / HBM_BYTES_S, flop / PEAK_FLOP_S
    print(f"kernel.moe_roofline_share: kernels {kernel_s:.3f} s; floor "
          f"{max(by_bytes, by_flop):.3f} s (bytes {bytes_ / 1e9:.1f} GB = "
          f"{by_bytes:.3f} s, {flop / 1e12:.1f} TFLOP = {by_flop:.3f} s)",
          flush=True)
    return 100.0 * max(by_bytes, by_flop) / kernel_s
