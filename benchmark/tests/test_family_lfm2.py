"""The LFM2 family at its toy size (`configs/tiny-lfm2.json`): its own
reference agrees with the program within the toy's limits; the same reference
in fp8, or with the convolution's state zeroed at every dispatch, the taps
reversed, the selection bias or QK-norm left out, does not; what the harness
draws is mapped so that each of those mechanisms does something (the taps
differ, the bias moves the chosen experts at a few tenths of the positions);
its stacks add up and a cut reads by its depth as the docstring says; the
published file holds the catalogue's keys; the three readers the cell brings
do their arithmetic and read nothing of a program without the counters; and
the new cell rehearses on the CPU through `BatchEngine`."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import cells, probe, traffic
from benchmark import weights as W
from benchmark.run import Ctx

SEED = 2**31 + 44
BENCH = cells.benchmark_json()
CONTROLS = ("fp8", "conv_state_off", "taps_reversed", "bias_off",
            "qknorm_off")
NEW_CELL = "lfm2-8b-a1b.chat-closed"
NEW_METRICS = ("step.conv_share", "cache.state_write_kb",
               "cache.state_snapshot_share")


@pytest.fixture(scope="module")
def ran():
    cfg = cells.load_config("tiny-lfm2")
    weights = W.make_weights(cfg, SEED)
    be = probe.build_engine(cfg, weights)
    try:
        own = probe.check(cfg, weights, SEED, be, log=lambda m: None)
        probes = probe.probe_tokens(cfg, SEED)
        arms = {c: {name: probe.judge(probe.pass_errors(
            cfg, weights, probes, cfg["check"][name],
            lambda cut, w, pr, c=c: probe.reference_rows(cfg, w, pr, c)[0]),
            cfg["check"][name]) for name in ("shallow", "full")}
            for c in CONTROLS}
    finally:
        be.close()
    return cfg, weights, own, arms


def test_its_own_reference_agrees_with_the_program(ran):
    cfg, _, own, _ = ran
    assert own["correct"]
    assert own["shallow"]["max"] < 1e-3 and own["full"]["p90"] < 1e-3
    assert own["shallow"]["rows_judged"] == cfg["engine"]["slots"]
    # every probe row crosses a 64-, an 8- and 1-token chunks
    assert all(n > 72 for n in cfg["check"]["probe_prompts"][1:])


@pytest.mark.parametrize("control", CONTROLS)
def test_a_lower_precision_or_a_mechanism_left_out_fails_a_limit(ran,
                                                                 control):
    """Each control moves the toy's logits past at least one of the toy's
    two limits (the full pass holds them all; the shallow cut, a dense
    convolution layer and an attention layer with experts, holds all but the
    state's, whose probes' recorded positions start dispatches there)."""
    cfg, _, _, arms = ran
    arm = arms[control]
    assert not (arm["shallow"]["within"] and arm["full"]["within"])
    assert not arm["full"]["within"]
    assert arm["full"]["stat"] > cfg["check"]["full"]["tol"]
    assert np.isfinite(arm["full"]["stat"])


def test_the_drawn_taps_and_bias_are_mapped_so_that_they_select(ran):
    cfg, weights, _, _ = ran
    fam = cells.load_family("lfm2")
    m = fam.mapped(weights)
    taps = m["blocks.conv_w"]
    # three clearly different magnitudes, the newest position's the largest
    mean = np.abs(taps).mean(axis=(0, 1))
    assert mean[2] > 1.9 * mean[1] > 3.6 * mean[0]
    assert (taps[..., 1] < 0).all() and (taps[..., 2] > 0).all()
    bias = m["blocks.router_bias"]
    assert abs(bias.mean()) < 0.03 and 0.02 < bias.std() < 0.1
    assert fam.mapped(m) is m  # mapped once
    # the tie: the embedding is the head's dequantized values
    np.testing.assert_array_equal(
        m["embedding"], np.asarray(W.dequantize(*weights["wcls"])))
    assert not np.array_equal(m["embedding"], weights["embedding"])
    # the bias changes the chosen experts at a few tenths of the positions:
    # in the cut whose expert layer is the last, a changed choice moves its
    # own position's logits and no other's
    cut = W.layer_cut(weights, cfg["check"]["shallow"]["cuts"][0], cfg)
    rng = np.random.default_rng(5)
    rows = [rng.integers(3, cfg["vocab_size"], 96).tolist()]
    at = [list(range(96))]
    own, _ = fam.logits_at(cfg, cut, rows, at)
    off, _ = fam.logits_at(cfg, cut, rows, at, "bias_off")
    moved = np.abs(own - off).max(axis=-1) > 1e-4
    assert 0.1 < moved.mean() < 0.95


def test_the_stacks_add_up_and_a_cut_reads_by_its_depth(ran):
    cfg, weights, _, _ = ran
    fam = cells.load_family("lfm2")
    assert fam.stacks(cfg) == [("lead", 1), ("blocks", 4)]
    assert W.stack_depths(weights, cfg) == {"lead": 1, "blocks": 4}
    whole = fam.program_params(cfg, weights)
    assert list(whole)[:2] == ["lead", "blocks"]
    # a layer is DRAWN with both mixers' tensors and handed its kind's
    assert weights["blocks.conv_in"][0].shape[0] == 4
    assert whole["blocks"]["conv_in"].shape[0] == 2
    assert whole["blocks"]["wq"].shape[0] == 2
    assert whole["blocks"]["moe_up"].shape[0] == 4
    # depth 2: the last leading layer and the first expert layer
    cut = cfg["check"]["shallow"]["cuts"][0]
    assert cut == [cfg["num_dense_layers"] - 1, cfg["num_dense_layers"]]
    w = W.layer_cut(weights, cut, cfg)
    spec = fam.model_spec({**cfg, "num_hidden_layers": 2})
    params = fam.program_params(cfg, w)
    assert (spec.layer_kinds, spec.lead_layers, spec.n_experts) == (
        (0, 1), 1, 8)
    assert "conv_in" in params["lead"] and "wq" in params["blocks"]
    assert "conv_in" not in params["blocks"]
    with pytest.raises(ValueError, match="not one this family can read"):
        fam.model_spec({**cfg, "num_hidden_layers": 3})
    big = cells.load_config("lfm2-8b-a1b")
    assert big["check"]["shallow"]["cuts"] == [[1, 2]]
    assert fam.stacks(big) == [("lead", 2), ("blocks", 22)]


def test_the_conv_state_control_zeroes_the_state_at_dispatch_starts():
    fam = cells.load_family("lfm2")
    starts = fam.dispatch_starts(75, 96)
    # 64 + 8 + 1 + 1 + 1, then every forced token its own dispatch
    assert np.nonzero(starts[:75])[0].tolist() == [0, 64, 72, 73, 74]
    assert starts[75:].all()


def test_the_published_keys_are_the_catalogue_s():
    cfg = cells.load_config("lfm2-8b-a1b")
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "LFM2-8B-A1B")
    for key, value in entry["config"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert cfg["source"] == entry["source_url"]
    assert cfg["published"] == {"max_position_embeddings": 128000}
    assert (cfg["max_position_embeddings"], cfg["context"]) == (4096, 4096)
    assert len(cfg["assumed"]) >= 5 and cfg["deployment"] and cfg["notes"]
    assert cfg["check"]["probe_prompts"][-1] == 2304
    assert all(72 <= n <= 78 for n in cfg["check"]["probe_prompts"][:-1])


# ---- the readers the cell brings --------------------------------------------

FIXTURE = os.path.join(cells.HERE, "fixtures", "trace_conv_ops.json")


def _ctx(before, after, trace=None, trace_dir=None):
    return Ctx(cells.load_config("lfm2-8b-a1b"), trace, before, after, {},
               trace_dir)


def test_the_convolution_share_counts_the_scope_and_the_projections_once():
    from benchmark import moe_trace

    ctx = _ctx({}, {}, {"busy_s": 0.008}, FIXTURE)
    assert ctx.metric("step.conv_share") == pytest.approx(10.0)
    reader = cells.load_reader("step.conv_share")
    planes = moe_trace.ops(FIXTURE)
    assert moe_trace.seconds(planes, *reader.PROJECTIONS) == pytest.approx(
        0.0007)
    assert moe_trace.seconds(planes, reader.SCOPE) == pytest.approx(0.0008)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_without_the_counters_or_the_names_reads_nothing(name):
    """The parent of this PR: no state counters, no short_conv scope (the
    Laguna fixture's operations)."""
    other = os.path.join(cells.HERE, "fixtures", "trace_laguna_ops.json")
    ctx = _ctx({"batch_positions_real_total": 1.0},
               {"batch_positions_real_total": 9.0,
                "batch_dispatch_seconds": {
                    '{kind="super_step"}': {"count": 3, "sum": 1.0}}},
               {"busy_s": 0.008}, other)
    assert ctx.metric(name) is None
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
    assert (cell["chips"], cell["traffic"]) == (1, "chat-closed")
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
    assert line["metrics"]["rehearsal.cache.state_snapshot_share"][
        "value"] == pytest.approx(100.0)
    assert line["metrics"]["rehearsal.cache.state_write_kb"]["value"] > 0
