"""Share of the query-key pairs the attention kernel was asked for that it
computed: `batch_attn_pairs_visited_total` (for every row of a dispatch, T x
the keys of the 128-key steps that hold its committed length) over
`batch_attn_pairs_dispatched_total` (slots x T x the window bucket), both
counted per dispatch in `runtime/batch_engine.py`. The kernel runs no step
past a row's length, so this falls as rows are shorter than the bucket; a
program without the counter (one that runs every step) reads nothing."""
UNIT = "%"
LAYER = "kernels"
MOVES = "itl_mean_ms"
SOURCE = "program_counter"


def read(ctx):
    visited = ctx.counter_delta("batch_attn_pairs_visited_total")
    given = ctx.counter_delta("batch_attn_pairs_dispatched_total")
    if visited is None or not given:
        print("kernel.attn_visited_share: the program counts no visited "
              "attention pairs", flush=True)
        return None
    return 100.0 * visited / given
