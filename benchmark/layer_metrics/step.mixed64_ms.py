"""Device time of the dispatch whose duration `client.itl_rider_p75_ms`
reads (a rider's gap across one 64-token chunk, with the host's time before
it): the median over
the `jit_step` executions inside a `batch.mixed_step` span with `chunk` 64 (a
64-token prefill chunk with decode rows riding it), at the widest attention
window among them. One program runs each (chunk, window bucket) and its time
grows with the bucket (on a v5e, Mistral-7B: 384 ms against 512 keys, 661 ms
against 1024), so the longest gaps between a rider's tokens are the widest
bucket's dispatches plus the idle time before the next one. The join of
device executions to the scheduler's spans is `host_spans.dispatches`; the
table of every (kind, chunk, window) is printed before the result line."""
import statistics

from benchmark import host_spans

UNIT = "ms"
LAYER = "step programs"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"
PROGRAMS = {"step": "jit_step"}
MIN_JOINED = 0.95


def read(ctx):
    trace = host_spans.window_trace(ctx.trace_dir)
    if trace is None:
        return None
    rows, joined = host_spans.dispatches(trace)
    print(f"step.mixed64_ms: {len(rows)} jit_step executions joined to a "
          f"dispatch span, {100 * joined:.1f} % of all", flush=True)
    for kind, chunk, window, n, total_ms, median_ms in host_spans.by_kind(
            rows):
        print(f"  {kind:8s} chunk {chunk:3d} window {window:5d}: {n:4d} "
              f"dispatches, {total_ms / 1e3:8.3f} s on the device, median "
              f"{median_ms:8.2f} ms", flush=True)
    if joined < MIN_JOINED:
        print("step.mixed64_ms: under 95 % of the jit_step executions lie in "
              "a dispatch span of the program: no reading", flush=True)
        return None
    mixed = [r for r in rows if r["kind"] == "mixed" and r["chunk"] == 64]
    if not mixed:
        print("step.mixed64_ms: no mixed dispatch with a 64-token chunk in "
              "the window", flush=True)
        return None
    widest = max(r["window"] for r in mixed)
    return statistics.median(r["device_ns"] for r in mixed
                             if r["window"] == widest) / 1e6
