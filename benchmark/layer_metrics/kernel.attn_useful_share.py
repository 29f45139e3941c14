"""Share of the query-key pairs the attention kernel was asked for that
causal attention needs: `batch_attn_pairs_real_total` (each real position's
position + 1) over `batch_attn_pairs_dispatched_total` (slots x T x the window
bucket), both counted per dispatch in `runtime/batch_engine.py`. The kernel
runs every row at the chunk's T against the whole bucket, parked rows and
scratch positions too."""
UNIT = "%"
LAYER = "kernels"
MOVES = "itl_mean_ms"
SOURCE = "program_counter"


def read(ctx):
    real = ctx.counter_delta("batch_attn_pairs_real_total")
    given = ctx.counter_delta("batch_attn_pairs_dispatched_total")
    if real is None or not given:
        print("kernel.attn_useful_share: the program counts no dispatched "
              "attention pairs", flush=True)
        return None
    return 100.0 * real / given
