"""The latent attention kernel's share of its roofline: the least time the
chip could take for the work the window's counters report, over the device
time of the operations named `latent_paged_attention*` (the `name` of the
`pallas_call` in `ops/pallas_paged_attention.py`) in the same window.

The work asked for, whatever implements it (`work` below), from the program's
counters and gauge and the configuration's widths:
- bytes: every latent cache row under a dispatched row's committed length
  crosses HBM once a layer, all heads reading it once
  (`batch_latent_rows_read_total` x `kv_pool_row_bytes`, the bytes a token
  really holds a layer, the lanes' padding included), plus each query
  position's rows: its heads' queries read (heads x the row's bytes) and their
  outputs written (heads x the latent's width, float32)
  (`batch_latent_dispatch_rows_total`);
- FLOP: 2 x rows read x heads x (the row's width for the score + the latent's
  width for the weighted sum), at the published widths (576 + 512), not the
  padded ones.
The kernel's steps of 128 keys and its query blocks re-read rows, and the
chunk's own T x T fold is extra work: both are in the time and not in the
work, so the share cannot pass 100 %. The floor is the larger of bytes /
819 GB/s and FLOP / 197 TFLOP/s (one TPU v5e chip). A program without the
counters or the kernel (the parent of the PR that added them) reads nothing."""
from benchmark import moe_trace

UNIT = "%"
LAYER = "kernels"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"
HBM_BYTES_S, PEAK_FLOP_S = 819e9, 197e12  # TPU v5e, one chip
MARK = "latent_paged_attention"


def work(cfg: dict, rows_read: float, query_rows: float, row_bytes: float):
    """(bytes, FLOP) of reading `rows_read` cache rows of `row_bytes` for
    `query_rows` query positions of all heads."""
    heads = cfg["num_attention_heads"]
    latent = cfg.get("kv_lora_rank", 0)
    width = latent + cfg.get("qk_rope_head_dim", 0)
    bytes_ = (rows_read * row_bytes
              + query_rows * heads * (row_bytes + 4 * latent))
    return bytes_, 2.0 * rows_read * heads * (width + latent)


def read(ctx):
    rows = ctx.counter_delta("batch_latent_rows_read_total")
    queries = ctx.counter_delta("batch_latent_dispatch_rows_total")
    row_bytes = ctx._pick(ctx._after, "kv_pool_row_bytes", None)
    planes = moe_trace.ops(ctx.trace_dir) if ctx.trace_dir else None
    if not rows or not queries or not row_bytes or not planes:
        return None
    kernel_s = moe_trace.seconds(planes, MARK)
    if kernel_s == 0.0:
        print("kernel.latent_attn_roofline_share: no latent_paged_attention "
              "operation in the window", flush=True)
        return None
    bytes_, flop = work(ctx.config, rows, queries, row_bytes)
    by_bytes, by_flop = bytes_ / HBM_BYTES_S, flop / PEAK_FLOP_S
    print(f"kernel.latent_attn_roofline_share: kernel {kernel_s:.3f} s; floor "
          f"{max(by_bytes, by_flop):.4f} s (bytes {bytes_ / 1e9:.2f} GB = "
          f"{by_bytes:.4f} s, {flop / 1e12:.2f} TFLOP = {by_flop:.4f} s)",
          flush=True)
    return 100.0 * max(by_bytes, by_flop) / kernel_s
