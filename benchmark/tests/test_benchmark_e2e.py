import pytest

from benchmark import e2e
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


def test_a_window_with_no_completion_reports_no_tpot():
    m, _, _ = e2e.reduce([rec(1.0, [2.0], max_tokens=5, end_t=None)],
                         0.0, 10.0)
    assert "tpot_p50_ms" not in m and "itl_p95_ms" not in m
