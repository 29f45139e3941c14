"""`host_spans`: the join of device executions to the scheduler's spans and
the attribution of idle time, on a hand-made trace, and the five readers that
came with them (their arithmetic: `reader_cases/`, `test_benchmark_readers`)."""

import json
import os

import pytest

from benchmark import cells, host_spans
from benchmark.run import Ctx

READERS = ["step.mixed64_ms", "sched.gap_ms", "sched.gap_named_share",
           "sched.fill_share", "kernel.attn_useful_share"]
BEFORE = {"batch_positions_real_total": 10.0,
          "batch_positions_dispatched_total": 100.0,
          "batch_attn_pairs_real_total": 1000.0,
          "batch_attn_pairs_dispatched_total": 50000.0}
AFTER = {"batch_positions_real_total": 10.0 + 71.0,
         "batch_positions_dispatched_total": 100.0 + 512.0,
         "batch_attn_pairs_real_total": 1000.0 + 24000.0,
         "batch_attn_pairs_dispatched_total": 50000.0 + 262144.0}


def fixture(name):
    with open(os.path.join(cells.HERE, "fixtures", name)) as f:
        return json.load(f)


@pytest.fixture()
def trace():
    return fixture("trace_host_small.json")


@pytest.fixture()
def joined_trace():
    """The same without the execution that has no span."""
    return fixture("trace_host_joined.json")


def ctx_for(monkeypatch, trace, before=BEFORE, after=AFTER):
    monkeypatch.setattr(host_spans, "window_trace", lambda trace_dir: trace)
    return Ctx(cells.load_config("mistral-7b"), None, before, after, {})


def test_the_scheduler_is_the_line_with_most_spans(trace):
    spans = host_spans.scheduler_spans(trace)
    assert len(spans) == 13
    assert all(e[0] != "batch.prefix_insert" for e in spans)
    # by start, a parent before its children
    assert [e[0] for e in spans[:3]] == ["batch.mixed_step", "batch.launch",
                                         "batch.fetch"]


def test_executions_are_joined_to_the_span_that_holds_their_start(trace):
    rows, joined = host_spans.dispatches(trace)
    assert joined == pytest.approx(3 / 4)  # the first one has no span
    assert rows == [
        {"kind": "mixed", "chunk": 64, "riders": 7, "window": 1024,
         "device_ns": 64000},
        {"kind": "mixed", "chunk": 64, "riders": 7, "window": 512,
         "device_ns": 62000},
        {"kind": "single", "chunk": 1, "riders": 7, "window": 512,
         "device_ns": 14000}]
    assert host_spans.by_kind(rows) == [
        ("mixed", 64, 1024, 1, pytest.approx(0.064), pytest.approx(0.064)),
        ("mixed", 64, 512, 1, pytest.approx(0.062), pytest.approx(0.062)),
        ("single", 1, 512, 1, pytest.approx(0.014), pytest.approx(0.014))]


def test_the_clock_offset_is_the_middle_of_its_causal_bounds(trace):
    # no execution starts before its span (the single step: 300), none ends
    # after it (the single step again: 300)
    assert host_spans.clock_offsets(trace) == [(300.0, 300, 300)]
    # a span that opens earlier and closes later loosens both bounds
    single = trace["planes"][1]["lines"][1]["events"][10]
    assert single[0] == "batch.single_step"
    single[1] -= 100
    single[2] += 300
    assert host_spans.clock_offsets(trace) == [(350.0, 200, 500)]
    trace["planes"] = trace["planes"][:1]
    assert host_spans.clock_offsets(trace) == [(0.0, 0.0, 0.0)]


def test_innermost_segments_tile_nested_spans():
    spans = [["a", 0, 100, {}], ["b", 10, 20, {}], ["c", 15, 5, {}],
             ["d", 200, 10, {}]]
    assert host_spans._innermost(spans) == [
        (0, 10, "a"), (10, 15, "b"), (15, 20, "c"), (20, 30, "b"),
        (30, 100, "a"), (200, 210, "d")]


def test_idle_time_is_split_among_the_innermost_spans(trace):
    g = host_spans.gaps(trace)
    assert g["total_ns"] == 600 + 4000 + 4000
    assert g["dispatches"] == 3  # spans; the first execution has none
    assert g["offsets"] == [(300.0, 300, 300)]
    assert g["idle_ns"] == {
        "unnamed": 500 + 2000,  # before the first span; half of the last hole
        "batch.fetch": 400 + 2000,
        "batch.mixed_step": 50 + 100 + 50,  # outside its launch and fetch
        "batch.launch": 50 + 150,
        "batch.deliver": 400, "batch.admit": 100, "batch.advance": 1000,
        "batch.build": 1800}
    assert sum(g["idle_ns"].values()) == g["total_ns"]


def test_a_trace_without_host_spans_gives_empty_joins(trace):
    trace["planes"] = trace["planes"][:1]  # what the parent's program writes
    assert host_spans.dispatches(trace) == ([], 0.0)
    g = host_spans.gaps(trace)
    assert g["idle_ns"] == {"unnamed": g["total_ns"]}
    assert g["dispatches"] == 0


def test_too_few_executions_joined_is_no_reading(monkeypatch, trace, capsys):
    assert ctx_for(monkeypatch, trace).metric("step.mixed64_ms") is None
    assert "under 95 %" in capsys.readouterr().out


def test_no_mixed_64_token_dispatch_is_no_reading(monkeypatch, joined_trace):
    for e in joined_trace["planes"][1]["lines"][1]["events"]:
        if e[0] == "batch.mixed_step":
            e[3]["chunk"] = 8
    ctx = ctx_for(monkeypatch, joined_trace)
    assert ctx.metric("step.mixed64_ms") is None


@pytest.mark.parametrize("name", READERS)
def test_a_reader_that_finds_nothing_returns_nothing(monkeypatch, trace, name):
    # no trace at all, and counters the program does not have
    assert ctx_for(monkeypatch, None, {}, {}).metric(name) is None
    # the parent's program: device planes, no span, counters that never moved
    trace["planes"] = trace["planes"][:1]
    assert ctx_for(monkeypatch, trace, BEFORE, BEFORE).metric(name) is None


def test_every_new_entry_has_its_reader_and_says_what_it_is():
    bench = {m["name"]: m for m in cells.benchmark_json()["per_layer"]}
    for name in READERS:
        reader = cells.load_reader(name)
        entry = bench[name]
        assert (reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE) == (
            entry["unit"], entry["layer"], entry["moves"], entry["source"])
        assert "workloads" not in entry  # both cells report it


def test_the_window_trace_is_read_where_the_run_says_and_parsed_once(
        monkeypatch, tmp_path, capsys):
    calls = []
    monkeypatch.setattr(host_spans, "from_xplane",
                        lambda d: calls.append(d) or {"planes": []})
    host_spans._window_trace.cache_clear()
    where = str(tmp_path / ".bench_trace" / "a.cell")
    ctx = Ctx({}, None, {}, {}, {}, where)
    assert host_spans.window_trace(ctx.trace_dir) == {"planes": []}
    assert host_spans.window_trace(ctx.trace_dir) == {"planes": []}
    assert calls == [where]
    host_spans._window_trace.cache_clear()
    # a run that traced nothing (a rehearsal, a test's bare Ctx)
    assert host_spans.window_trace(Ctx({}, None, {}, {}, {}).trace_dir) is None
    assert "no trace directory" in capsys.readouterr().out


def test_an_idle_gap_is_named_by_the_span_that_covers_most_of_it(trace):
    name = host_spans.gap_namer(trace)
    # on the device's clock, 300 ns behind: [1100,1700) lies 500 under
    # nothing and 100 under two spans; [65700,69700) is tiled, batch.build
    # 1800 of it; [131700,135700) is half batch.fetch, half nothing
    assert name(0, 1100, 1700) is None
    assert name(0, 65700, 69700) == "batch.build"
    assert name(0, 131700, 135700) == "batch.fetch"
    trace["planes"] = trace["planes"][:1]  # a program without the spans
    assert host_spans.gap_namer(trace)(0, 65700, 69700) is None


def test_a_missing_trace_file_is_a_line_and_no_exception(tmp_path, capsys):
    host_spans._window_trace.cache_clear()
    assert host_spans._window_trace(str(tmp_path)) is None
    assert "no trace to read" in capsys.readouterr().out
    host_spans._window_trace.cache_clear()


def test_the_host_plane_is_read_from_a_profile(tmp_path):
    """A CPU profiler session with `run.py`'s options: the annotation comes
    back as a `batch.*` event with its stats (no device plane on the CPU)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("batch.mixed_step", chunk=64,
                                          riders=3):
            with jax.profiler.TraceAnnotation("other.span"):
                pass
    finally:
        jax.profiler.stop_trace()
    t = host_spans.from_xplane(str(tmp_path))
    (plane,) = [p for p in t["planes"] if p["name"] == host_spans.HOST_PLANE]
    events = [e for ln in plane["lines"] for e in ln["events"]]
    assert [e[0] for e in events] == ["batch.mixed_step"]
    assert events[0][3] == {"chunk": 64, "riders": 3}
    assert events[0][2] > 0
