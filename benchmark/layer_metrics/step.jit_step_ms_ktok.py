"""Device time of every `jit_step` dispatch per 1000 prompt tokens prefilled
(`batch_prefill_tokens_total`), both over the traced window. `jit_step` is
one program for every chunk size: prefill chunks of 64, 8 and 1 with the
decoding rows that ride them, and the single T=1 decode step. So this is
what the step programs outside the K-step scan cost per unit of prompt, not
a price of prefill alone; the trace cannot tell a chunk's kind (PERF.md,
Open questions). A 64-token mixed dispatch, whose duration
`client.itl_rider_p75_ms` reads, takes about 64/1000 of it."""
UNIT = "ms/ktok"
LAYER = "step programs"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"
PROGRAMS = {"step": "jit_step"}


def read(ctx):
    role = ctx.trace["roles"]["step"]
    tokens = ctx.counter_delta("batch_prefill_tokens_total")
    if not role["count"] or not tokens:
        return None
    return role["seconds"] * 1e3 / (tokens / 1000.0)
