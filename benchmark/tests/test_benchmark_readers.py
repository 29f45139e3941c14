"""The readers of the per-layer metrics, on hand-made inputs. Every entry of
`BENCHMARK.json`'s `per_layer` is held to a reader file and to an arithmetic
case, `reader_cases/<metric>.json`: what the reader is given (the reduced
trace, the counters before and after the window, the clients' numbers, or a
trace fixture in the shape `host_spans.from_xplane` returns) and the number
it has to make of it. A metric that a later PR adds brings both files."""

import json
import os

import pytest

from benchmark import cells, host_spans
from benchmark.run import Ctx

BENCH = cells.benchmark_json()
CASES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "reader_cases")
TRACE = {"busy_s": 9.0, "window_s": 10.0,
         "roles": {"step": {"seconds": 5.0, "count": 20}}}
BEFORE = {"batch_prefill_tokens_total": 100.0}


def load_json(path):
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize("name", [m["name"] for m in BENCH["per_layer"]])
def test_every_entry_has_a_reader_and_an_arithmetic_case(monkeypatch, name):
    assert os.path.isfile(os.path.join(cells.HERE, "layer_metrics",
                                       name + ".py"))
    case = load_json(os.path.join(CASES, name + ".json"))
    trace_dir = None
    if "host_trace" in case:  # the fixture stands for the window's profile
        trace_dir = os.path.join(cells.HERE, "fixtures", case["host_trace"])
        monkeypatch.setattr(host_spans, "from_xplane", load_json)
        host_spans._window_trace.cache_clear()
    ctx = Ctx(cells.load_config("mistral-7b"), case.get("trace"),
              case.get("before", {}), case.get("after", {}),
              case.get("client", {}), trace_dir)
    try:
        assert ctx.metric(name) == pytest.approx(case["want"])
    finally:
        host_spans._window_trace.cache_clear()


@pytest.mark.parametrize("name", ["step.jit_step_ms_ktok",
                                  "client.tpot_p50_ms"])
def test_a_reader_that_finds_nothing_returns_nothing(name):
    trace = {**TRACE, "roles": {"step": {"seconds": 0.0, "count": 0}}}
    ctx = Ctx(cells.load_config("mistral-7b"), trace, BEFORE, BEFORE, {})
    assert ctx.metric(name) is None
