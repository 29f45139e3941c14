"""Share of the token positions the window's dispatches computed that held a
token of a request: `batch_positions_real_total` over
`batch_positions_dispatched_total` (`runtime/batch_engine.py`, counted per
dispatch from its shapes and live rows). A prefill dispatch computes slots x
chunk positions for one chunk and at most slots - 1 riders."""
UNIT = "%"
LAYER = "scheduler"
MOVES = "itl_mean_ms"
SOURCE = "program_counter"


def read(ctx):
    real = ctx.counter_delta("batch_positions_real_total")
    given = ctx.counter_delta("batch_positions_dispatched_total")
    if real is None or not given:
        print("sched.fill_share: the program counts no dispatched positions",
              flush=True)
        return None
    return 100.0 * real / given
