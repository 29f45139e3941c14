"""The granite-4.0-h-small family at its toy size
(`configs/tiny-granite-hybrid.json`): its own reference (the recurrence as a
scan over positions) agrees with the program within the toy's limits; the
same reference in fp8, or with the running matrices zeroed at every dispatch,
no decay, no skip term, the taps reversed, the gate behind the norm, the
residual multiplier or the stated attention scale lost, does not; what the
harness draws is mapped so that each of those mechanisms does something; its
stack adds up and a cut reads by its depth as the docstring says; the
published file holds the catalogue's keys; the work functions and the four
readers the cell brings do their arithmetic, read the same share of a trace
cut short and nothing of a program without the counters; and the new cell
rehearses on the CPU through `BatchEngine`."""

import copy
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import cells, host_spans, probe, ssd_work, traffic
from benchmark import weights as W
from benchmark.run import Ctx

SEED = 2**31 + 44
BENCH = cells.benchmark_json()
FAMILY = cells.load_family("granite_hybrid")
CONTROLS = ("fp8",) + FAMILY.MECHANISM_CONTROLS
NEW_CELL = "granite-4.0-h-small-l10.longctx-closed"
NEW_METRICS = ("kernel.ssd_roofline_share", "step.ssm_share",
               "cache.ssm_state_mb", "cache.ssm_snapshot_share")
FIXTURE = os.path.join(cells.HERE, "fixtures", "trace_ssd_ops.json")


@pytest.fixture(scope="module")
def ran():
    cfg = cells.load_config("tiny-granite-hybrid")
    weights = W.make_weights(cfg, SEED)
    be = probe.build_engine(cfg, weights)
    try:
        own = probe.check(cfg, weights, SEED, be, log=lambda m: None)
        held = be.kv_pool.snapshots.held()
        probes = probe.probe_tokens(cfg, SEED)
        arms = {c: {name: probe.judge(probe.pass_errors(
            cfg, weights, probes, cfg["check"][name],
            lambda cut, w, pr, c=c: probe.reference_rows(cfg, w, pr, c)[0]),
            cfg["check"][name]) for name in ("shallow", "full")}
            for c in CONTROLS}
    finally:
        be.close()
    return cfg, weights, own, arms, held


def test_its_own_reference_agrees_with_the_program(ran):
    cfg, _, own, _, held = ran
    assert own["correct"]
    assert own["shallow"]["max"] < 1e-3 and own["full"]["p90"] < 1e-3
    assert own["shallow"]["rows_judged"] == cfg["engine"]["slots"]
    # the probes cross a 64-, an 8- and 1-token chunks and the stride's end
    # at 255: those that passed it left a snapshot
    prompts = cfg["check"]["probe_prompts"]
    assert min(prompts) < 256 < max(prompts) and held >= 3


@pytest.mark.parametrize("control", CONTROLS)
def test_a_lower_precision_or_a_mechanism_changed_fails_a_limit(ran, control):
    cfg, _, _, arms, _ = ran
    arm = arms[control]
    assert not (arm["shallow"]["within"] and arm["full"]["within"])
    assert np.isfinite(arm["full"]["stat"])


def test_the_drawn_tensors_are_mapped_so_that_each_mechanism_acts(ran):
    cfg, weights, _, _, _ = ran
    m = FAMILY.mapped(weights)
    assert FAMILY.mapped(m) is m
    a = np.exp(m["blocks.ssm_a_log"])
    assert 0.9 < a[:, 0].mean() < 1.1 and 15 < a[:, -1].mean() < 17
    step = np.log1p(np.exp(m["blocks.ssm_dt_bias"]))
    assert step[:, 0].max() < 2e-3 and step[:, -1].min() > 5e-2
    taps = m["blocks.ssm_conv_w"].mean(axis=(0, 1))
    np.testing.assert_allclose(taps, FAMILY.TAPS, atol=0.02)
    assert np.abs(m["blocks.ssm_d"] - 1).max() > 0.5
    assert np.abs(m["blocks.ssm_conv_b"]).mean() > 0.05
    # the tie: the embedding is the head's dequantized values
    np.testing.assert_array_equal(  # (dequantized on the host, in slices)
        m["embedding"], np.asarray(W.dequantize(*weights["wcls"])))


def test_the_stack_adds_up_and_a_cut_reads_by_its_depth(ran):
    cfg, weights, _, _, _ = ran
    assert FAMILY.stacks(cfg) == [("blocks", 10)]
    cut = W.layer_cut(weights, [0, 5], cfg)
    assert FAMILY._types_held(cfg, cut) == ["mamba", "attention"]
    spec = FAMILY.model_spec({**cfg, "num_hidden_layers": 2})
    assert spec.layer_kinds == (0, 1) and spec.state_layers == (0,)
    params = FAMILY.program_params(cfg, cut)
    assert params["blocks"]["ssm_in"].shape[0] == 1
    assert params["blocks"]["wq"].shape[0] == 1
    assert params["blocks"]["router"].shape[0] == 2
    with pytest.raises(ValueError, match="a cut of 3"):
        FAMILY.model_spec({**cfg, "num_hidden_layers": 3})
    small = FAMILY.one_layer_a_stack(cfg, experts=2)
    assert FAMILY.stacks(small) == [("blocks", 2)]


def test_the_state_control_zeroes_the_matrices_at_dispatch_starts():
    starts = FAMILY.dispatch_starts(75, 96)
    # 64 + 8 + 1 + 1 + 1, then every forced token its own dispatch
    assert np.nonzero(starts[:75])[0].tolist() == [0, 64, 72, 73, 74]
    assert starts[75:].all()


def test_the_published_keys_are_the_catalogue_s():
    cfg = cells.load_config("granite-4.0-h-small-l10")
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "granite-4.0-h-small")
    for key, value in entry["config"].items():
        if key == "layer_types":  # the first period of the published forty
            assert cfg[key] == value[:10] and value == 4 * value[:10]
        elif key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert cfg["source"] == entry["source_url"]
    assert cfg["reduced"] == ["num_hidden_layers", "max_position_embeddings"]
    assert (cfg["num_hidden_layers"], cfg["published"]["num_hidden_layers"],
            cfg["max_position_embeddings"], cfg["context"]) == (
        10, 40, 4096, 4096)
    assert len(cfg["assumed"]) >= 8 and cfg["deployment"] and cfg["notes"]
    assert cfg["state_snapshots"] == 48
    prompts = cfg["check"]["probe_prompts"]
    assert prompts[-1] == 2304 and all(250 <= n <= 262 for n in prompts[:-1])
    assert cfg["check"]["shallow"]["cuts"] == [[0, 5]]


# ---- the work functions and the readers the cell brings ---------------------

def _ctx(before, after, trace=None, trace_dir=None):
    return Ctx(cells.load_config("granite-4.0-h-small-l10"), trace, before,
               after, {}, trace_dir)


def test_the_work_of_a_step_and_of_a_chunk_counts_live_rows_alone():
    cfg = cells.load_config("granite-4.0-h-small-l10")
    heads, p, n = ssd_work.sizes(cfg)
    assert (heads, p, n) == (128, 64, 128)
    bytes_, flop = ssd_work.step_work(72, heads, p, n)  # 8 live rows, 9 layers
    assert bytes_ == 72 * 2 * 4 * 2**20 and flop == 72 * 5 * 2**20
    nothing = ssd_work.step_work(0, heads, p, n)
    assert nothing == (0.0, 0.0)  # a dispatch of parked rows asks for nothing
    bytes_, flop = ssd_work.chunk_work(9, 64, heads, p, n)
    assert bytes_ == 9 * (8 * 2**20 + 4 * 64 * (2 * 8192 + 256))
    assert flop == 9 * (128 * (2 * 64 * 64 * 64 + 4 * 64 * 64 * 128)
                        + 2 * 64 * 64 * 128)


@pytest.fixture()
def traces(monkeypatch):
    """The fixture (its host side runs on behind the device side's end: a
    trace cut short) and the same window whole: the three later dispatches'
    executions and operations put back."""
    with open(FIXTURE) as f:
        cut = json.load(f)
    whole = copy.deepcopy(cut)
    dev = whole["planes"][0]["lines"]
    shift = 42_000_000  # the three dispatches once more, 42 ms later
    for line in dev:
        line["events"] += [[ev[0], ev[1] + shift, *ev[2:]]
                           for ev in line["events"]]
    store = {"cut": cut, "whole": whole}
    monkeypatch.setattr(host_spans, "from_xplane", lambda name: store[name])
    yield store
    host_spans._window_trace.cache_clear()


def test_the_roofline_share_of_a_trace_cut_short_is_the_whole_window_s(
        traces):
    """Work summed from the dispatches joined to an execution: 3 of the 6 in
    the cut trace, all 6 in the whole one, the same share; the window's
    counters over the cut trace's kernel seconds would read twice it."""
    got = {}
    for name in ("cut", "whole"):
        host_spans._window_trace.cache_clear()
        got[name] = _ctx({}, {}, None, name).metric(
            "kernel.ssd_roofline_share")
        bytes_, _, n = ssd_work.joined(traces[name], cells.load_config(
            "granite-4.0-h-small-l10"))
        assert n == (3 if name == "cut" else 6)
    assert got["cut"] == pytest.approx(got["whole"])
    assert got["cut"] == pytest.approx(57.98908836785334)
    assert 2 * got["cut"] > 105  # what a reader of counters would report


def test_the_mixers_share_counts_kernels_and_projections_by_name(traces):
    """By the RESULT's name, in the profile the run has parsed already: the
    fusion whose statistics alone name the `ssm_mixer` scope is not counted
    (a chip's profile carries no scope), nor an operation that reads a
    kernel's result and so names it among its operands."""
    host_spans._window_trace.cache_clear()
    ctx = _ctx({}, {}, {"busy_s": 0.04}, "cut")
    assert ctx.metric("step.ssm_share") == pytest.approx(66.75)
    trace = traces["cut"]
    seconds = ssd_work.op_seconds(trace)
    assert sum(seconds[k] for k in ssd_work.KERNELS) == pytest.approx(0.0217)
    assert sum(seconds[k] for k in ssd_work.PROJECTIONS) == pytest.approx(
        0.005)
    del trace["ssd_op_seconds"]  # counted once a trace: count it anew
    trace["planes"][0]["lines"][1]["events"].append(
        ["%fusion.9 = f32[8,8192]{1,0} fusion(f32[8,1,8192]{2,1,0} "
         "%ssd_step.5)", 60_000_000, 1_000_000])
    assert sum(ssd_work.op_seconds(trace)[k]
               for k in ssd_work.KERNELS) == pytest.approx(0.0217)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_without_the_counters_or_the_names_reads_nothing(
        monkeypatch, name):
    """The parent of this PR: no state-space counters, no SSD kernels, no
    span args (the Laguna fixture's operations, the small host trace)."""
    other = os.path.join(cells.HERE, "fixtures", "trace_laguna_ops.json")

    def load(path):
        with open(path) as f:
            return json.load(f)

    monkeypatch.setattr(host_spans, "from_xplane", load)
    host_spans._window_trace.cache_clear()
    ctx = _ctx({"batch_positions_real_total": 1.0},
               {"batch_positions_real_total": 9.0,
                "batch_dispatch_seconds": {
                    '{kind="super_step"}': {"count": 3, "sum": 1.0}}},
               {"busy_s": 0.008}, other)
    try:
        assert ctx.metric(name) is None
    finally:
        host_spans._window_trace.cache_clear()
    assert _ctx({}, {}, None, None).metric(name) is None


def test_each_new_metric_lists_the_new_cell_alone():
    for name in NEW_METRICS:
        m = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert m["workloads"] == [NEW_CELL]
        assert m["moves"] == "itl_mean_ms"
        reader = cells.load_reader(name)
        assert (reader.UNIT, reader.LAYER, reader.SOURCE) == (
            m["unit"], m["layer"], m["source"])
    assert os.path.getsize(os.path.join(cells.ROOT, "BENCHMARK.json")) < 65536


def test_the_new_cell_fits_its_configuration():
    cell = cells.cell(BENCH, NEW_CELL)
    cfg = cells.load_config(cell["config"])
    t = traffic.load(cell["traffic"])
    assert (cell["chips"], cell["traffic"]) == (1, "longctx-closed")
    assert t["clients"] <= cfg["engine"]["slots"]
    assert traffic.max_position(t) <= cfg["context"]
    blocks = cfg["engine"]["kv_pool_blocks"]
    assert blocks * cfg["engine"]["kv_block_tokens"] >= (
        t["clients"] * traffic.max_position(t))
    assert len(cell["why"]) <= 200


def test_the_new_cell_rehearses_on_the_cpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(cells.HERE, "run.py"), "--workload",
         NEW_CELL, "--seed", str(2**31 + 48), "--seconds", "4", "--trace", "1",
         "--rehearse", "1"], cwd=cells.ROOT, env=env, capture_output=True,
        text=True, timeout=1500)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["rehearsal"]
    assert all(k.startswith("rehearsal.") for k in line["metrics"])
    assert line["metrics"]["rehearsal.cache.ssm_state_mb"]["value"] > 0
