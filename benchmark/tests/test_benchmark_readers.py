"""The readers of the per-layer metrics, on hand-made inputs."""

import pytest

from benchmark import cells
from benchmark.run import Ctx

BENCH = cells.benchmark_json()
TRACE = {"busy_s": 9.0, "window_s": 10.0,
         "roles": {"step": {"seconds": 5.0, "count": 20}}}
BEFORE = {"batch_prefill_tokens_total": 100.0}
AFTER = {"batch_prefill_tokens_total": 600.0}


@pytest.mark.parametrize("name,want", [
    ("step.jit_step_ms_ktok", 5.0 * 1e3 / 0.5),
    ("device.idle_share", 10.0),
    ("client.tpot_p50_ms", 250.0),
])
def test_reader_arithmetic(name, want):
    ctx = Ctx(cells.load_config("mistral-7b"), TRACE, BEFORE, AFTER,
              {"tpot_p50_ms": 250.0})
    assert ctx.metric(name) == pytest.approx(want)


def test_every_entry_is_covered_above():
    assert {m["name"] for m in BENCH["per_layer"]} == {
        "step.jit_step_ms_ktok", "device.idle_share", "client.tpot_p50_ms"}


@pytest.mark.parametrize("name", ["step.jit_step_ms_ktok",
                                  "client.tpot_p50_ms"])
def test_a_reader_that_finds_nothing_returns_nothing(name):
    trace = {**TRACE, "roles": {"step": {"seconds": 0.0, "count": 0}}}
    ctx = Ctx(cells.load_config("mistral-7b"), trace, BEFORE, BEFORE, {})
    assert ctx.metric(name) is None
