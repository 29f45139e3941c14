"""Median over requests completed in the window of (last - first token) /
(output tokens - 1) (`e2e.reduce`): the mean of a request's gaps between
tokens; the bounded `itl_mean_ms` is the mean of all the window's gaps, of
every request together. No bound:
about ten requests complete in a window, and the median swings with the
schedule (PERF.md, PR 25)."""
UNIT = "ms"
LAYER = "clients"
MOVES = "itl_mean_ms"
SOURCE = "host_clock"


def read(ctx):
    return ctx.client.get("tpot_p50_ms")
