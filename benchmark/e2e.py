"""The arithmetic of the end-to-end metrics, from the records of one window.

Every metric is taken over all the work and all the time of the window:
a rate counts every token by its time stamp, a median or a tail is that of
every request or gap the window holds. All times are the host's monotonic
clock (`host_clock`).
"""

from __future__ import annotations

import re
import statistics

# tokens of one request stamped within this of each other came out of one
# dispatch: a K-step scan hands its K tokens out at once (under 0.1 ms apart
# on the host's clock), no dispatch takes under 15 ms
BLOCK_MS = 1.0
GAP_COLUMNS = ("client", "index", "i", "end_ms", "gap_ms", "followed")
GAP_METRIC = re.compile(
    r"^itl_(?:(rider|block|blocktok)_)?(?:p(\d{1,2})|(mean))_ms$")


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100), linear between order statistics."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of nothing")
    k = (len(v) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


def gap_rows(records, open_t: float, close_t: float) -> list[tuple]:
    """Every gap between consecutive tokens of one request that ends in the
    window [open_t, close_t), as `GAP_COLUMNS`: the request (client, index),
    `i` the index in the reply of the token that ends the gap, `end_ms` its
    time after the window opened, `gap_ms`, and `followed`: how many tokens
    of that request arrived within `BLOCK_MS` after it. That count is what
    tells the kinds of gap apart on the client's side, with nothing taken
    from the program: a rider's token (one a dispatch) is followed by none,
    the first token of a scan block of K by K - 1."""
    rows = []
    for r in records:
        tt = r.token_t
        for i in range(1, len(tt)):
            if not open_t <= tt[i] < close_t:
                continue
            j = i + 1
            while j < len(tt) and (tt[j] - tt[i]) * 1e3 < BLOCK_MS:
                j += 1
            rows.append((r.client, r.index, i, (tt[i] - open_t) * 1e3,
                         (tt[i] - tt[i - 1]) * 1e3, j - i - 1))
    return rows


def gap_values(rows, kind: str | None) -> list[float]:
    """The gaps a statistic of `kind` is over, from `gap_rows`' rows.
    None: all of them. `rider`: a gap of `BLOCK_MS` or more followed by no
    further token, the wait of a row that got ONE token from the dispatch
    that ended it (a prefill chunk it rode, a single step). `block`: a gap
    of `BLOCK_MS` or more followed by one token or more, the wait for a
    scan block of 2 or more. `blocktok`: the same gaps, each over the
    block's tokens (followed + 1). A gap under `BLOCK_MS`, between two
    tokens of one block, is in `None` alone."""
    if kind is None:
        return [g for *_, g, _f in rows]
    if kind == "rider":
        return [g for *_, g, f in rows if g >= BLOCK_MS and f == 0]
    if kind == "block":
        return [g for *_, g, f in rows if g >= BLOCK_MS and f >= 1]
    if kind == "blocktok":
        return [g / (f + 1) for *_, g, f in rows if g >= BLOCK_MS and f >= 1]
    raise ValueError(f"unknown kind of gap {kind!r}")


def gap_metric(rows, name: str):
    """(value, samples) of the gap statistic `name` says:
    `itl_[<kind>_]p<q>_ms` is the q-th percentile over `gap_values(rows,
    kind)`, `itl_[<kind>_]mean_ms` their mean; the value is None where the
    window holds no such gap."""
    m = GAP_METRIC.match(name)
    if m is None:
        raise ValueError(f"{name!r} names no gap statistic")
    values = gap_values(rows, m.group(1))
    if not values:
        return None, 0
    if m.group(3):
        return sum(values) / len(values), len(values)
    return percentile(values, int(m.group(2))), len(values)


def reduce(records, open_t: float, close_t: float, chips: int = 1,
           gap_metrics=("itl_p95_ms",)):
    """(metrics, samples, counts) of the window [open_t, close_t).

    tok_s_chip   every output token whose delivery falls in the window, plus
                 a request's prompt tokens credited at its first token, per
                 second per chip
    ttft_p50_ms  median over requests whose first token falls in the window
                 of first token minus submit
    tpot_p50_ms  median over requests completed in the window of
                 (last token - first token) / (output tokens - 1)
    itl_p95_ms   95th percentile of every gap between consecutive tokens of
                 one request that ends in the window (logged, not bounded:
                 it stands on the step between two kinds of gap)
    and every other name in `gap_metrics`, by `gap_metric`: a percentile
    or the mean over all those gaps, over the riders' or over the scan
    blocks' (`gap_values`); absent where the window holds none of its kind.
    The one that BENCHMARK.json bounds since PR 35:
    itl_mean_ms  the mean over ALL of them, of every request: the gaps
                 inside a scan block (under 1 ms) count as they are, so it
                 is the window's time between tokens per token delivered.
                 A mean stands on no step between two kinds of gap: it moves
                 by the share of the gaps that move times how far they move
    and the two that per-layer readers hand on (`layer_metrics/client.itl_*`):
    itl_rider_p75_ms  75th percentile of the riders' gaps (1 ms or more,
                 followed by no token within 1 ms): it reads a rider's wait
                 across ONE dispatch that carries a 64-token prefill chunk
                 while that band spans the riders' 75th percentile
    itl_block_p25_ms  lower quartile of the scan blocks' gaps (1 ms or more,
                 followed by one token or more): the K-step scan `jit_plain`
                 with no prefill dispatch between two of its blocks
    """
    def inside(t):
        return open_t <= t < close_t

    tokens = 0
    ttft, tpot = [], []
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
    samples = {"tok_s_chip": tokens, "ttft_p50_ms": len(ttft),
               "tpot_p50_ms": len(tpot)}
    rows = gap_rows(records, open_t, close_t)
    for name in gap_metrics:
        value, samples[name] = gap_metric(rows, name)
        if value is not None:
            metrics[name] = value
    counts = {"attempted": attempted, "succeeded": succeeded,
              "failed": failed}
    return metrics, samples, counts
