import json
import os

import pytest

from benchmark import cells, trace_reduce

PROGRAMS = {"decode": "jit_plain", "prefill": "jit_step"}


@pytest.fixture(scope="module")
def trace():
    with open(os.path.join(cells.HERE, "fixtures", "trace_small.json")) as f:
        return json.load(f)


def test_busy_is_the_union_and_the_window_spans_the_operations(trace):
    r = trace_reduce.reduce(trace, PROGRAMS)
    # busy: [1000,5000) + [7000,15000) + [16000,16100) + [17000,20000)
    assert r["busy_s"] == pytest.approx((4000 + 8000 + 100 + 3000) / 1e9)
    assert r["window_s"] == pytest.approx(19000 / 1e9)


def test_programs_are_found_by_their_jitted_names(trace):
    r = trace_reduce.reduce(trace, PROGRAMS)
    assert r["roles"]["prefill"] == {"seconds": pytest.approx(7000 / 1e9),
                                     "count": 2}
    assert r["roles"]["decode"] == {"seconds": pytest.approx(8000 / 1e9),
                                    "count": 1}
    assert r["missing"] == []


def test_a_declared_program_without_an_event_is_reported(trace):
    r = trace_reduce.reduce(trace, {"verify": "jit_verify", **PROGRAMS})
    assert r["missing"] == ["verify"]


def test_operations_are_ranked_without_the_enclosing_while(trace):
    ops = dict(trace_reduce.reduce(trace, PROGRAMS)["device_ops"])
    assert "%while.4" not in ops
    assert ops["%fusion.1"] == pytest.approx(7500 / 1e9)
    assert ops["%paged_attention.8"] == pytest.approx(2500 / 1e9)
    assert list(ops)[0] == "%fusion.1"


def test_idle_gaps_under_no_span_are_named_by_the_program_that_ran_next(trace):
    gaps = trace_reduce.reduce(trace, PROGRAMS)["idle_gaps"]
    assert gaps[0] == ["longest, before jit_plain", pytest.approx(2000 / 1e9)]
    named = dict((k, v) for k, v in gaps if k.startswith("total "))
    assert named == {
        "total before jit_plain": pytest.approx(2000 / 1e9),
        "total before jit_convert_element_type": pytest.approx(1000 / 1e9),
        "total before jit_step": pytest.approx(900 / 1e9)}


def test_idle_gaps_are_named_by_the_scheduler_span_they_lie_under():
    """Labels only: busy time, window and roles are what they are without
    the spans."""
    from benchmark import host_spans

    with open(os.path.join(cells.HERE, "fixtures",
                           "trace_host_small.json")) as f:
        trace = json.load(f)
    programs = {"step": "jit_step"}
    plain = trace_reduce.reduce(trace, programs)
    named = trace_reduce.reduce(trace, programs, host_spans.gap_namer(trace))
    for key in ("busy_s", "window_s", "roles", "missing", "device_ops"):
        assert named[key] == plain[key]
    assert [g[1] for g in named["idle_gaps"][:3]] == [
        g[1] for g in plain["idle_gaps"][:3]]
    assert sorted(named["idle_gaps"]) == sorted([
        ["longest, under batch.build", pytest.approx(4000 / 1e9)],
        ["longest, under batch.fetch", pytest.approx(4000 / 1e9)],
        ["longest, before jit_step", pytest.approx(600 / 1e9)],
        ["total under batch.build", pytest.approx(4000 / 1e9)],
        ["total under batch.fetch", pytest.approx(4000 / 1e9)],
        ["total before jit_step", pytest.approx(600 / 1e9)]])


def test_a_trace_without_a_device_plane_is_an_error(trace):
    with pytest.raises(ValueError):
        trace_reduce.reduce({"planes": trace["planes"][:1]}, PROGRAMS)


@pytest.mark.parametrize("name,want", [
    ("jit_step(1783385353787555090)", "jit_step"),
    ("jit_plain(14906699236231347000)", "jit_plain")])
def test_program_name(name, want):
    assert trace_reduce.program_name(name) == want
