import collections

import pytest

from benchmark import traffic

NAMES = ["chat-closed"]


def _take(plans, n):
    return [[p.next() for _ in range(n)] for p in plans]


@pytest.mark.parametrize("name", NAMES)
def test_same_seed_same_requests(name):
    t = traffic.load(name)
    a = _take(traffic.plan(t, 32000, 2**31 + 17), 6)
    b = _take(traffic.plan(t, 32000, 2**31 + 17), 6)
    assert a == b


@pytest.mark.parametrize("name", NAMES)
def test_every_seed_gets_the_same_lengths_and_other_tokens(name):
    t = traffic.load(name)

    def requests(seed):
        reqs = _take(traffic.plan(t, 32000, seed), t["cycle"] + 1)
        return [r for c in reqs for r in c[1:]]

    a, b = requests(1), requests(2)
    assert [(len(r.prompt), r.max_tokens) for r in a] == \
        [(len(r.prompt), r.max_tokens) for r in b]
    assert all(x.prompt != y.prompt for x, y in zip(a, b))
    assert len(collections.Counter(len(r.prompt) for r in a)) > t["clients"]
    lo, hi = t["prompt_tokens"]["min"], t["prompt_tokens"]["max"]
    assert all(lo <= len(r.prompt) <= hi for r in a)
    lo, hi = t["output_tokens"]["min"], t["output_tokens"]["max"]
    assert all(lo <= r.max_tokens <= hi for r in a)


@pytest.mark.parametrize("name", NAMES)
def test_prompts_are_distinct_and_cycle_repeats_lengths(name):
    t = traffic.load(name)
    plan = traffic.plan(t, 32000, 5)[0]
    reqs = [plan.next() for _ in range(2 * t["cycle"] + 1)]
    assert len({r.prompt for r in reqs}) == len(reqs)
    # request 0 is the warm-up: the shortest lengths of the file
    assert len(reqs[0].prompt) == t["prompt_tokens"]["min"]
    assert reqs[0].max_tokens == t["output_tokens"]["min"]
    assert [len(r.prompt) for r in reqs[1:1 + t["cycle"]]] == \
        [len(r.prompt) for r in reqs[1 + t["cycle"]:]]


def test_open_loop_is_in_the_schema_and_refused(tmp_path, monkeypatch):
    monkeypatch.setattr(traffic, "HERE", str(tmp_path))
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "x.json").write_text(
        '{"loop": "open", "rate_per_s": 2.0}')
    with pytest.raises(NotImplementedError):
        traffic.load("x")
