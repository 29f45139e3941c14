"""Share of the rows the grouped expert layer computed that were padding:
`batch_moe_rows_computed_total` (each chosen expert's run of rows rounded up
to the row tile, over all layers and dispatches of the window) against
`batch_moe_assignments_total` (dispatched rows x experts per token), both
counted on the device and handed out with each dispatch's results
(`runtime/batch_engine.py:_count_moe`). Also printed: the distinct experts a
dispatch read over those it could have (`batch_moe_experts_touched_total`
over `batch_moe_experts_offered_total`). Listed for the cells whose every
dispatch takes the grouped layer: a dispatch through the all-experts scan
counts its experts x rows as computed, so where some go that way the reading
is the share of computed rows that no assignment asked for, padding or not.
A program without the counters reads nothing."""
UNIT = "%"
LAYER = "step programs"
MOVES = "itl_mean_ms"
SOURCE = "program_counter"


def read(ctx):
    rows = ctx.counter_delta("batch_moe_rows_computed_total")
    real = ctx.counter_delta("batch_moe_assignments_total")
    if not rows or real is None:
        print("moe.padded_rows_share: the program counts no rows of an "
              "expert layer", flush=True)
        return None
    touched = ctx.counter_delta("batch_moe_experts_touched_total") or 0.0
    offered = ctx.counter_delta("batch_moe_experts_offered_total") or 0.0
    if offered:
        print(f"moe.padded_rows_share: experts touched {touched:.0f} of "
              f"{offered:.0f} offered ({100 * touched / offered:.1f} %); "
              f"{real:.0f} assignments in {rows:.0f} rows", flush=True)
    return 100.0 * (rows - real) / rows
