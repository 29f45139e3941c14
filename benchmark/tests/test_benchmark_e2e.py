import glob
import os

import pytest

from benchmark import cells, e2e, gapstat
from benchmark.load import Record


def rec(submit, tokens, n_prompt=10, max_tokens=None, **kw):
    r = Record(0, 0, n_prompt, max_tokens or len(tokens), submit)
    r.token_t = list(tokens)
    r.end_t = tokens[-1] if tokens else None
    r.finish = "length"
    for k, v in kw.items():
        setattr(r, k, v)
    return r


def test_window_arithmetic_by_hand():
    records = [
        # wholly inside: first token at 1.0, four tokens
        rec(0.5, [1.0, 1.1, 1.2, 1.6], n_prompt=100),
        # first token before the window: its prompt is not credited, its
        # two tokens inside are, and it completes inside
        rec(-2.0, [-0.5, 0.2, 0.4], n_prompt=50),
        # cut by the close: three tokens inside, not completed
        rec(8.0, [9.0, 9.5, 9.9, 10.5], n_prompt=20, max_tokens=8,
            end_t=10.6, finish="cancelled", cancelled_by_driver=True),
    ]
    m, samples, counts = e2e.reduce(records, 0.0, 10.0, chips=1)
    assert samples["tok_s_chip"] == (4 + 100) + 2 + (3 + 20)
    assert m["tok_s_chip"] == pytest.approx(129 / 10.0)
    assert m["ttft_p50_ms"] == pytest.approx((500.0 + 1000.0) / 2)
    # completed in the window: (1.6-1.0)/3 and (0.4+0.5)/2
    assert m["tpot_p50_ms"] == pytest.approx((200.0 + 450.0) / 2)
    assert samples["itl_p95_ms"] == 3 + 2 + 2
    assert m["itl_p95_ms"] == pytest.approx(640.0)  # 100 100 200 400 400 500 700
    assert counts == {"attempted": 3, "succeeded": 2, "failed": 0}


@pytest.mark.parametrize("kw,failed", [
    (dict(error="boom"), 1),
    (dict(finish="error"), 1),
    (dict(finish="cancelled", cancelled_by_driver=True), 0),
])
def test_a_request_that_errors_is_failed(kw, failed):
    r = rec(1.0, [2.0, 2.5], max_tokens=4, **kw)
    _, _, counts = e2e.reduce([r], 0.0, 10.0)
    assert counts["failed"] == failed and counts["attempted"] == 1


def test_two_chips_halve_the_rate():
    r = rec(1.0, [2.0, 2.5], n_prompt=0)
    one, _, _ = e2e.reduce([r], 0.0, 10.0, chips=1)
    two, _, _ = e2e.reduce([r], 0.0, 10.0, chips=2)
    assert one["tok_s_chip"] == 2 * two["tok_s_chip"]


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 3.0), (95, 4.8), (100, 5.0)])
def test_percentile(q, want):
    assert e2e.percentile([5, 1, 4, 2, 3], q) == pytest.approx(want)


def block(at, k=8):
    """K tokens handed out at once: 50 us apart on the host's clock."""
    return [at + 5e-5 * j for j in range(k)]


BLOCKS = rec(0.5, [1.0] + block(1.2) + block(1.4), client=0)
RIDER = rec(1.5, [2.0, 2.03, 2.06, 2.13, 2.16], client=1)  # 2.13: a chunk
EDGES = rec(-1.0, [-0.1, 0.05, 10.2], client=2, max_tokens=9, end_t=None)
GAP_NAMES = ["itl_p95_ms", "itl_rider_p75_ms", "itl_block_p50_ms",
             "itl_blocktok_p50_ms", "itl_mean_ms", "itl_block_mean_ms"]


def test_which_gaps_each_statistic_is_over_by_hand():
    rows = e2e.gap_rows([BLOCKS, RIDER, EDGES], 0.0, 10.0)
    # the gap that ends at 10.2 is outside; the one from -0.1 to 0.05 inside
    assert len(rows) == 16 + 4 + 1
    by_req = {c: [r for r in rows if r[0] == c] for c in (0, 1, 2)}
    # the count after a gap: 7 behind a block's first token, down to 0
    assert [r[5] for r in by_req[0]] == [7, 6, 5, 4, 3, 2, 1, 0] * 2
    assert [r[2] for r in by_req[0]] == list(range(1, 17))
    assert [r[5] for r in by_req[1]] == [0, 0, 0, 0]
    assert by_req[2][0][3:] == pytest.approx((50.0, 150.0, 0))
    assert sorted(e2e.gap_values(rows, "rider")) == pytest.approx(
        [30, 30, 30, 70, 150])
    assert e2e.gap_values(rows, "block") == pytest.approx([200.0, 199.65])
    assert e2e.gap_values(rows, "blocktok") == pytest.approx(
        [200.0 / 8, 199.65 / 8])
    assert len(e2e.gap_values(rows, None)) == 21
    m, samples, _ = e2e.reduce([BLOCKS, RIDER, EDGES], 0.0, 10.0,
                               gap_metrics=GAP_NAMES)
    assert m["itl_rider_p75_ms"] == pytest.approx(70.0)  # the chunk's gap
    assert m["itl_block_p50_ms"] == pytest.approx(199.825)
    assert m["itl_blocktok_p50_ms"] == pytest.approx(199.825 / 8)
    assert m["itl_p95_ms"] == pytest.approx(199.65)  # 21 gaps: the 20th
    # the bounded one: ALL 21 gaps, a block's inner 0.05 ms ones as they are
    # (200 + 199.65 + 14 x 0.05 in the blocks, the rider's 160, 150)
    assert m["itl_mean_ms"] == pytest.approx((400.35 + 160 + 150) / 21)
    assert m["itl_block_mean_ms"] == pytest.approx(199.825)
    assert [samples[n] for n in GAP_NAMES] == [21, 5, 2, 2, 21, 2]


def test_a_window_with_no_scan_block_reports_no_block_metric():
    m, samples, _ = e2e.reduce([RIDER], 0.0, 10.0, gap_metrics=GAP_NAMES)
    assert "itl_block_p50_ms" not in m and "itl_blocktok_p50_ms" not in m
    assert samples["itl_block_p50_ms"] == 0
    assert m["itl_rider_p75_ms"] == pytest.approx(40.0)  # 30 30 30 70
    assert "itl_block_mean_ms" not in m
    assert m["itl_mean_ms"] == pytest.approx(40.0)  # (30 + 30 + 70 + 30) / 4
    with pytest.raises(ValueError):
        e2e.reduce([RIDER], 0.0, 10.0, gap_metrics=["itl_first_p50_ms"])


def test_gapstat_spread_and_room_on_three_recorded_runs():
    """`fixtures/gaps_run_{a,b,c}.json`: ten riders' gaps and two scan
    blocks a run; run c's schedule put four gaps in another band."""
    runs = [gapstat.load(p) for p in sorted(glob.glob(os.path.join(
        cells.HERE, "fixtures", "gaps_run_*.json")))]
    assert [len(r["rows"]) for r in runs] == [26, 26, 26]
    rider, blk, mean = gapstat.table(
        runs, ["itl_rider_p50_ms", "itl_block_p50_ms", "itl_mean_ms"])
    assert rider["values"] == pytest.approx([60.3, 60.9, 66.0])
    # run c is farthest from the median and left out: 0.6 over 60.6
    assert rider["spread"] == pytest.approx(0.6 / 60.6)
    assert rider["iqr"] == pytest.approx(5.7 / 60.9)
    assert blk["values"] == pytest.approx([205.0, 207.0, 242.0])
    assert blk["samples"] == [2, 2, 2]
    # the mean over all 26 gaps a run: 929.7, 936.7 and 1042.7 ms of gaps;
    # it stands at no quantile, so it has no room to read
    assert mean["values"] == pytest.approx([929.7 / 26, 936.7 / 26, 1042.7 / 26])
    assert mean["spread"] == pytest.approx((936.7 - 929.7) / 933.2)
    assert mean["room_below"] is None and mean["room_above"] is None
    # run a reads 60.3: 60 lies within 3 % under it, 60.6 to 61.8 above
    values = e2e.gap_values(runs[0]["rows"], "rider")
    assert gapstat.room(values, 60.3) == pytest.approx((10.0, 30.0))
    assert rider["room_below"] == 0.0  # run c stands between two bands
    assert gapstat.spread([100.0, 101.0]) == pytest.approx(1 / 100.5)
    shares = [round(s, 1) for s, _, _ in gapstat.bands(runs[0]["rows"])]
    assert shares == [15.4, 19.2, 3.8, 3.8, 3.8]


def test_a_window_with_no_completion_reports_no_tpot():
    m, _, _ = e2e.reduce([rec(1.0, [2.0], max_tokens=5, end_t=None)],
                         0.0, 10.0)
    assert "tpot_p50_ms" not in m and "itl_p95_ms" not in m
