"""Median over requests completed in the window of (last - first token) /
(output tokens - 1) (`e2e.reduce`): the mean of a request's gaps between
tokens, whose 95th percentile over all requests is `itl_p95_ms`. No bound:
about ten requests complete in a window, and the median swings with the
schedule (PERF.md, PR 25)."""
UNIT = "ms"
LAYER = "clients"
MOVES = "itl_p95_ms"
SOURCE = "host_clock"


def read(ctx):
    return ctx.client.get("tpot_p50_ms")
