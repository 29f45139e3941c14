"""Lower quartile of the SCAN BLOCKS' gaps (`e2e.gap_values`: a gap of 1 ms
or more followed by one token or more of its request within 1 ms, the wait
for a block the K-step scan `jit_plain` hands out at once): K steps of the
scan with no other dispatch between two blocks, the lowest of the bands a
block's gap falls in; a block that waited for a T = 1 or a chunk dispatch
lies in a higher one. The first reading of `jit_plain`; nothing where a
window holds no block of 2 or more. No bound: it leaves out every block that
waited (PERF.md, section 2)."""
UNIT = "ms"
LAYER = "clients"
MOVES = "itl_mean_ms"
SOURCE = "host_clock"
GAPS = ("itl_block_p25_ms",)  # what run.py has e2e.reduce work out for it


def read(ctx):
    return ctx.client.get("itl_block_p25_ms")
