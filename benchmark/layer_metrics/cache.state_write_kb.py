"""What the second kind of cached state costs a dispatch in HBM writes:
`batch_state_bytes_written_total` (counted in `runtime/batch_engine.py
_count_work` from the shapes: a row of `hidden_size` values for every row a
dispatch computes and every state layer, parked rows' scratch writes
included, and a block's snapshot, two such rows a layer, for every real
position that ends a pool block) over the window's dispatches of every kind
(the observations of `batch_dispatch_seconds`), in KB of 1000 bytes. A K-step
scan of 8 slots writes 8 x 8 x 18 x 4096 bytes = 4.7 MB; keys and values of
the same scan are 64 tokens x 12 KB = 0.8 MB. A program without the counter,
or a model without state layers, reads nothing."""
from benchmark import dispatch_phases

UNIT = "KB/dispatch"
LAYER = "cache"
MOVES = "itl_mean_ms"
SOURCE = "program_counter"


def read(ctx):
    written = ctx.counter_delta("batch_state_bytes_written_total")
    n = dispatch_phases.dispatched(ctx)
    if not written or not n:
        print("cache.state_write_kb: the program counts no state bytes (no "
              "state layers), or the window delivered no dispatch",
              flush=True)
        return None
    rows = ctx.counter_delta("batch_state_rows_advanced_total") or 0.0
    print(f"cache.state_write_kb: {written / 1e6:.1f} MB of ring rows and "
          f"snapshots over {n} dispatches; {rows:.0f} of the ring rows held "
          "a token of a request", flush=True)
    return written / 1e3 / n
