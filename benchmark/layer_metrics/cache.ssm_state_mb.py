"""What the third kind of cached state costs a dispatch in HBM traffic:
`batch_ssm_state_bytes_total` (counted in `runtime/batch_engine.py
_state_word` from the shapes and the live rows: a running matrix H of heads
x P x N float32 values read and written for every live row a layer a step
and once for a chunk's slot a layer, and a snapshot's read and write where a
row ended a stride) over the window's dispatches of every kind (the
observations of `batch_dispatch_seconds`), in MB of 1e6 bytes. A K-step scan
of 8 live slots moves 8 x 8 x 9 x 2 x 4.19 MB = 4.8 GB; the keys and values
of the same scan are 64 tokens x 4 KB. A program without the counter, or a
model without state-space layers, reads nothing."""
from benchmark import dispatch_phases

UNIT = "MB/dispatch"
LAYER = "cache"
MOVES = "itl_mean_ms"
SOURCE = "program_counter"


def read(ctx):
    moved = ctx.counter_delta("batch_ssm_state_bytes_total")
    n = dispatch_phases.dispatched(ctx)
    if not moved or not n:
        print("cache.ssm_state_mb: the program counts no bytes of running "
              "matrices (no state-space layers), or the window delivered no "
              "dispatch", flush=True)
        return None
    rows = ctx.counter_delta("batch_ssm_rows_stepped_total") or 0.0
    toks = ctx.counter_delta("batch_ssm_chunk_tokens_total") or 0.0
    print(f"cache.ssm_state_mb: {moved / 1e9:.2f} GB of running matrices "
          f"over {n} dispatches; {rows:.0f} (row, layer) steps and "
          f"{toks:.0f} (token, layer) of chunks", flush=True)
    return moved / 1e6 / n
