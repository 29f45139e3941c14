"""The arithmetic of the end-to-end metrics, from the records of one window.

Every metric is taken over all the work and all the time of the window:
a rate counts every token by its time stamp, a median or a tail is that of
every request or gap the window holds. All times are the host's monotonic
clock (`host_clock`).
"""

from __future__ import annotations

import statistics


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100), linear between order statistics."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of nothing")
    k = (len(v) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


def reduce(records, open_t: float, close_t: float, chips: int = 1):
    """(metrics, samples, counts) of the window [open_t, close_t).

    tok_s_chip   every output token whose delivery falls in the window, plus
                 a request's prompt tokens credited at its first token, per
                 second per chip
    ttft_p50_ms  median over requests whose first token falls in the window
                 of first token minus submit
    tpot_p50_ms  median over requests completed in the window of
                 (last token - first token) / (output tokens - 1)
    itl_p95_ms   95th percentile of every gap between consecutive tokens of
                 one request that ends in the window
    """
    def inside(t):
        return open_t <= t < close_t

    tokens = 0
    ttft, tpot, gaps = [], [], []
    attempted = failed = succeeded = 0
    for r in records:
        live = (r.submit_t < close_t
                and (r.end_t is None or r.end_t >= open_t))
        if not live:
            continue
        attempted += 1
        done = (r.error is None and r.finish == "length"
                and len(r.token_t) == r.max_tokens)
        if r.error is not None or (r.end_t is not None and not done
                                   and not r.cancelled_by_driver):
            failed += 1
        tt = r.token_t
        tokens += sum(1 for t in tt if inside(t))
        if tt and inside(tt[0]):
            tokens += r.n_prompt
            ttft.append((tt[0] - r.submit_t) * 1e3)
        gaps.extend((b - a) * 1e3 for a, b in zip(tt, tt[1:]) if inside(b))
        if done and inside(tt[-1]):
            succeeded += 1
            if len(tt) > 1:
                tpot.append((tt[-1] - tt[0]) * 1e3 / (len(tt) - 1))
    seconds = close_t - open_t
    metrics = {"tok_s_chip": tokens / seconds / chips}
    if ttft:
        metrics["ttft_p50_ms"] = statistics.median(ttft)
    if tpot:
        metrics["tpot_p50_ms"] = statistics.median(tpot)
    if gaps:
        metrics["itl_p95_ms"] = percentile(gaps, 95)
    samples = {"tok_s_chip": tokens, "ttft_p50_ms": len(ttft),
               "tpot_p50_ms": len(tpot), "itl_p95_ms": len(gaps)}
    counts = {"attempted": attempted, "succeeded": succeeded,
              "failed": failed}
    return metrics, samples, counts
