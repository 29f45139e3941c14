"""Share of the window layers' key traffic that the sliding window leaves:
`batch_attn_window_pairs_visited_total` (for every row of a dispatch and every
layer with a window, T x the keys of the 128-key steps the paged kernel runs,
none wholly behind the row's first query's lower bound) over
`batch_attn_window_pairs_unwindowed_total` (what the same layers would have
visited with no window: every step up to the row's committed length), both
counted per dispatch in `runtime/batch_engine.py _count_work`.

Behind a window of 512 a row visits five steps of 128 keys at most (the one
that straddles the bound and four behind it) whatever its length, so in a cell
whose rows are 1k to 2.8k long this reads about a third; it reads 100 % where
the skip is lost, and falls as contexts grow. A program without the counters,
or a model no layer of which has a window, reads nothing."""
UNIT = "%"
LAYER = "kernels"
MOVES = "itl_mean_ms"
SOURCE = "program_counter"


def read(ctx):
    visited = ctx.counter_delta("batch_attn_window_pairs_visited_total")
    bare = ctx.counter_delta("batch_attn_window_pairs_unwindowed_total")
    if visited is None or not bare:
        print("kernel.attn_window_visited_share: the program counts no "
              "window layers' attention pairs", flush=True)
        return None
    return 100.0 * visited / bare
