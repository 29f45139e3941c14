"""75th percentile of the RIDERS' gaps (`e2e.gap_values`: a gap of 1 ms or
more followed by no further token of its request within 1 ms, the wait of a
row that got ONE token from the dispatch that ended it): a rider's wait
across one dispatch that carries a 64-token prefill chunk, `step.mixed64_ms`
plus the host's time before it, on the client's side and from every such
dispatch of the window. It reads that band ONLY WHILE the band spans the
riders' 75th percentile: 70th to 90th in the dense cell, 70th to 79th and
69th to 79th in the MoE cells at PR 35 (PERF.md, section 2). A change that
shifts four points of the riders' mix moves it to another band with no change
in any latency, which is why it carries no bound: read it beside
`step.mixed64_ms`, and the shares in `gapstat.py --bands 1`."""
UNIT = "ms"
LAYER = "clients"
MOVES = "itl_mean_ms"
SOURCE = "host_clock"
GAPS = ("itl_rider_p75_ms",)  # what run.py has e2e.reduce work out for it


def read(ctx):
    return ctx.client.get("itl_rider_p75_ms")
