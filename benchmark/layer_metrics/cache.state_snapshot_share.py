"""Share of the pool's block ends whose state the program snapshot:
`batch_state_snapshots_total` (a real position that ends a pool block, a
state layer) over `batch_block_ends_total` (the same positions, counted for
every model) times the model's state layers, which is
`batch_state_rows_advanced_total` over `batch_positions_real_total`, all
four counted per dispatch in `runtime/batch_engine.py _count_work`. 100 where
every block the pool holds can seed a request (a prefix hit or a slot rewind
lands on a block end and continues from its snapshot); less where a later
change snapshots every n-th block or some layers alone, and rewinds land
that much further back. A program without the counters, or a model without
state layers, reads nothing."""
UNIT = "%"
LAYER = "cache"
MOVES = "itl_mean_ms"
SOURCE = "program_counter"


def read(ctx):
    snaps = ctx.counter_delta("batch_state_snapshots_total")
    ends = ctx.counter_delta("batch_block_ends_total")
    rows = ctx.counter_delta("batch_state_rows_advanced_total")
    real = ctx.counter_delta("batch_positions_real_total")
    if snaps is None or not ends or not rows or not real:
        print("cache.state_snapshot_share: the program counts no state "
              "snapshots (no state layers), or no block ended in the window",
              flush=True)
        return None
    layers = rows / real
    print(f"cache.state_snapshot_share: {snaps:.0f} snapshots of {ends:.0f} "
          f"block ends x {layers:.0f} state layers", flush=True)
    return 100.0 * snaps / (ends * layers)
