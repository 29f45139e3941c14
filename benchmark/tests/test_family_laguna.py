"""The Laguna family at its toy size (`configs/tiny-laguna.json`): its own
reference agrees with the program within the toy's limits; the same reference
in fp8, or with the window, the gate or the full layers' rotation left out,
does not; its stacks add up and a cut reads by its depth as the docstring
says; rows of unequal length read as each row alone; the published file holds
the catalogue's keys; the three readers the cell brings do their arithmetic at
the file's widths and read nothing of a program without the counters; the two
traffic files deal every seed the same lengths; and both new cells rehearse on
the CPU."""

import collections
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import cells, probe, traffic
from benchmark import weights as W
from benchmark.run import Ctx

SEED = 2**31 + 43
BENCH = cells.benchmark_json()
CONTROLS = ("fp8", "window_off", "gate_off", "one_rope")
NEW_CELLS = ("laguna-s-2.1-l5.longctx-closed",)


@pytest.fixture(scope="module")
def ran():
    cfg = cells.load_config("tiny-laguna")
    weights = W.make_weights(cfg, SEED)
    be = probe.build_engine(cfg, weights)
    try:
        own = probe.check(cfg, weights, SEED, be, log=lambda m: None)
        probes = probe.probe_tokens(cfg, SEED)
        arms = {c: probe.judge(probe.pass_errors(
            cfg, weights, probes, cfg["check"]["shallow"],
            lambda cut, w, pr, c=c: probe.reference_rows(cfg, w, pr, c)[0]),
            cfg["check"]["shallow"]) for c in CONTROLS}
    finally:
        be.close()
    return cfg, weights, own, arms


def test_its_own_reference_agrees_with_the_program(ran):
    cfg, _, own, _ = ran
    assert own["correct"]
    assert own["shallow"]["max"] < 1e-3 and own["full"]["p90"] < 1e-3
    assert own["shallow"]["rows_judged"] == cfg["engine"]["slots"]
    # every probe row runs far past the toy's window and original context
    assert min(cfg["check"]["probe_prompts"]) > 8 * cfg["sliding_window"]
    assert min(cfg["check"]["probe_prompts"]) > cfg["rope_parameters"][
        "full_attention"]["original_max_position_embeddings"]


@pytest.mark.parametrize("control", CONTROLS)
def test_a_lower_precision_or_a_mechanism_left_out_fails_the_shallow_pass(
        ran, control):
    cfg, _, _, arms = ran
    arm = arms[control]
    assert not arm["within"]
    assert arm["stat"] > cfg["check"]["shallow"]["tol"]
    assert np.isfinite(arm["stat"])


def test_the_stacks_add_up_and_a_cut_reads_by_its_depth(ran):
    cfg, weights, _, _ = ran
    fam = cells.load_family("laguna")
    assert fam.stacks(cfg) == [("lead", 1), ("slide", 3), ("full", 1)]
    assert W.stack_depths(weights, cfg) == {"lead": 1, "slide": 3, "full": 1}
    assert sum(d for _, d in fam.stacks(cfg)) == cfg["num_hidden_layers"]
    whole = fam.program_params(cfg, weights)
    assert list(whole)[:3] == ["lead", "blocks", "blocks1"]
    assert "w1" in whole["lead"] and "moe_up" in whole["blocks"]
    assert whole["blocks"]["wq"].shape[:2] == (3, 18 * 32)
    assert whole["blocks1"]["wq"].shape[:2] == (1, 12 * 32)
    # two whole periods: the kinds alternate and the stacks' names count up
    two = {**cfg, "num_hidden_layers": 9, "layers_here": 9}
    period = cfg["layer_types"][:4]  # full, sliding, sliding, sliding
    assert fam.stacks({**two, "layer_types": period * 3,
                       "num_attention_heads_per_layer": [12, 18, 18, 18] * 3}
                      ) == [("lead", 1), ("slide", 3), ("full", 1),
                            ("slide1", 3), ("full1", 1)]
    # depth 1 is the leading layer, depth 2 the last sliding and the full one
    for cut, windows, experts, stacks in (
            ([0], (0,), 0, ["blocks"]),
            ([3, 4], (8, 0), 16, ["blocks", "blocks1"])):
        w = W.layer_cut(weights, cut, cfg)
        spec = fam.model_spec({**cfg, "num_hidden_layers": len(cut)})
        params = fam.program_params(cfg, w)
        assert [k for k, v in params.items() if isinstance(v, dict)] == stacks
        assert (spec.layer_window(), spec.n_experts, spec.lead_layers) == (
            windows, experts, 0)
    # a cut of [4] alone could not be told from [0] by its depth; 3 is refused
    with pytest.raises(ValueError, match="not one this family can read"):
        fam.model_spec({**cfg, "num_hidden_layers": 3})
    for name in ("tiny-laguna", "laguna-s-2.1-l5"):
        c = cells.load_config(name)
        assert c["check"]["shallow"]["cuts"] == [[0], [
            c["layers_here"] - 2, c["layers_here"] - 1]]


def test_rows_of_unequal_length_read_as_each_row_alone(ran):
    cfg, weights, _, _ = ran
    fam = cells.load_family("laguna")
    rng = np.random.default_rng(3)
    rows = [rng.integers(3, cfg["vocab_size"], n).tolist() for n in (19, 70)]
    at = [[5, 18], [0, 40, 69]]
    both, margins = fam.logits_at(cfg, weights, rows, at)
    assert both.shape == (5, cfg["vocab_size"]) and margins.shape == (5,)
    for i, off in ((0, 0), (1, 2)):
        alone, m = fam.logits_at(cfg, weights, [rows[i]], [at[i]])
        np.testing.assert_allclose(both[off:off + len(at[i])], alone,
                                   atol=1e-6, rtol=0)
        np.testing.assert_allclose(margins[off:off + len(at[i])], m,
                                   atol=1e-6)
    # a swapped expert moves its own position and, through the layers behind
    # it, later ones; never an earlier one
    flipped, _ = fam.logits_at(cfg, weights, rows, at, flip=(1, 1, 40))
    assert np.abs(flipped[:3] - both[:3]).max() < 1e-6
    assert np.abs(flipped[3] - both[3]).max() > 1e-4


def test_the_published_keys_are_the_catalogue_s():
    cfg = cells.load_config("laguna-s-2.1-l5")
    assert (cfg["num_hidden_layers"], cfg["max_position_embeddings"],
            cfg["context"]) == (5, 4096, 4096)
    assert cfg["published"] == {"num_hidden_layers": 48,
                                "max_position_embeddings": 1048576}
    assert cfg["reduced"] == ["num_hidden_layers", "max_position_embeddings"]
    # no width touched, the four per-layer lists whole
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"],
            cfg["shared_expert_intermediate_size"], cfg["head_dim"],
            cfg["num_experts"], cfg["num_experts_per_tok"],
            cfg["num_key_value_heads"], cfg["vocab_size"],
            cfg["sliding_window"]) == (3072, 12288, 1024, 1024, 128, 256, 10,
                                       8, 100352, 512)
    for key in ("layer_types", "mlp_layer_types", "gating_types",
                "num_attention_heads_per_layer"):
        assert len(cfg[key]) == 48
    assert cfg["num_attention_heads_per_layer"][:5] == [48, 72, 72, 72, 48]
    assert len(cfg["assumed"]) >= 6 and cfg["check_canary"] == "lead.wg"
    assert cfg["check"]["probe_prompts"][-1] == 2304


# ---- the readers the cell brings --------------------------------------------

FIXTURE = os.path.join(cells.HERE, "fixtures", "trace_laguna_ops.json")


def _ctx(before, after, trace=None, trace_dir=None, config="laguna-s-2.1-l5"):
    return Ctx(cells.load_config(config), trace, before, after, {}, trace_dir)


def test_the_expert_roofline_reader_at_the_file_s_widths():
    """70 experts of 3 x 3072 x 1024 weights touched by 80 assignments: 70 x
    9437184 x 0.5625 + 80 x 2 x (3072 + 1024) x 2 = 0.3729 GB over 819 GB/s
    = 0.4553 ms, against 2 ms of the two kernels in the fixture: 22.77 %. At
    `intermediate_size` (12288, the dense layer's) it would read 273 %."""
    after = {"batch_moe_grouped_experts_touched_total": 70.0,
             "batch_moe_grouped_assignments_total": 80.0}
    ctx = _ctx({}, after, {"busy_s": 0.008}, FIXTURE)
    want_bytes = 70 * 9437184 * 0.5625 + 80 * 2 * 4096 * 2
    assert ctx.metric("kernel.moe_expert_roofline_share") == pytest.approx(
        100 * want_bytes / 819e9 / 0.002)
    assert ctx.metric("kernel.moe_expert_roofline_share") == pytest.approx(
        22.77, abs=0.01)
    reader = cells.load_reader("kernel.moe_expert_roofline_share")
    assert reader.expert_width(cells.load_config("laguna-s-2.1-l5")) == 1024
    assert reader.expert_width(
        cells.load_config("smallthinker-21b-a3b")) == 768
    assert reader.expert_width(cells.load_config("mistral-7b")) is None
    # compute-bound work: many rows an expert
    many = {"batch_moe_grouped_experts_touched_total": 10.0,
            "batch_moe_grouped_assignments_total": 100000.0}
    flop = 2 * 100000 * 9437184
    assert _ctx({}, many, {"busy_s": 0.008}, FIXTURE).metric(
        "kernel.moe_expert_roofline_share") == pytest.approx(
        100 * flop / 197e12 / 0.002)


@pytest.mark.parametrize("name", ["kernel.moe_expert_roofline_share",
                                  "kernel.attn_window_visited_share",
                                  "step.attn_share"])
def test_a_program_without_the_counters_or_the_names_reads_nothing(name):
    """The parent of this PR: no window counters, no kernel named by kind
    (the A.X-K1 fixture's operations), no grouped counters."""
    other = os.path.join(cells.HERE, "fixtures", "trace_latent_ops.json")
    ctx = _ctx({"batch_prefill_tokens_total": 1.0},
               {"batch_prefill_tokens_total": 9.0}, {"busy_s": 0.008}, other)
    assert ctx.metric(name) is None
    assert _ctx({}, {}, None, None).metric(name) is None


def test_the_attention_share_by_kind():
    ctx = _ctx({}, {}, {"busy_s": 0.008}, FIXTURE)
    assert ctx.metric("step.attn_share") == pytest.approx(20.0)
    reader = cells.load_reader("step.attn_share")
    from benchmark import moe_trace
    planes = moe_trace.ops(FIXTURE)
    assert moe_trace.seconds(planes, reader.WINDOW) == pytest.approx(0.0006)
    assert moe_trace.seconds(planes, reader.FULL) == pytest.approx(0.001)


def test_each_new_metric_lists_the_new_cell_alone():
    for name in ("kernel.moe_expert_roofline_share",
                 "kernel.attn_window_visited_share", "step.attn_share"):
        m = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert m["workloads"] == ["laguna-s-2.1-l5.longctx-closed"]
        assert m["moves"] == "itl_mean_ms"
        reader = cells.load_reader(name)
        assert (reader.UNIT, reader.LAYER, reader.SOURCE) == (
            m["unit"], m["layer"], m["source"])
    assert os.path.getsize(os.path.join(cells.ROOT, "BENCHMARK.json")) < 65536


# ---- the traffic files the cells bring --------------------------------------

def _take(plans, n):
    return [[p.next() for _ in range(n)] for p in plans]


@pytest.mark.parametrize("name,clients,prompts,replies", [
    ("longctx-closed", 8, (1024, 2048), (384, 768))])
def test_the_traffic_deals_every_seed_the_same_lengths(name, clients, prompts,
                                                       replies):
    t = traffic.load(name)
    assert (t["clients"], t["cycle"], t["stagger_s"], t["think_s"]) == (
        clients, 4, 0.2, 0.05)
    assert traffic.max_position(t) == prompts[1] + replies[1]

    def requests(seed):
        reqs = _take(traffic.plan(t, 32000, seed), t["cycle"] + 1)
        return [r for c in reqs for r in c[1:]]

    a, b = requests(1), requests(2**31 + 5)
    assert [(len(r.prompt), r.max_tokens) for r in a] == \
        [(len(r.prompt), r.max_tokens) for r in b]
    assert all(x.prompt != y.prompt for x, y in zip(a, b))
    assert len(collections.Counter(len(r.prompt) for r in a)) > clients
    assert all(prompts[0] <= len(r.prompt) <= prompts[1] for r in a)
    assert all(replies[0] <= r.max_tokens <= replies[1] for r in a)
    # every decode position lies past the window: a prompt alone does
    window = cells.load_config("laguna-s-2.1-l5")["sliding_window"]
    assert min(len(r.prompt) for r in a) >= 2 * window


def test_the_new_cells_fit_their_configurations():
    for name in NEW_CELLS:
        cell = cells.cell(BENCH, name)
        cfg = cells.load_config(cell["config"])
        t = traffic.load(cell["traffic"])
        assert cell["chips"] == 1
        assert t["clients"] <= cfg["engine"]["slots"]
        assert traffic.max_position(t) <= cfg["context"]
        # the pool holds every client's longest request at once
        blocks = cfg["engine"]["kv_pool_blocks"]
        assert blocks == 0 or blocks * cfg["engine"]["kv_block_tokens"] >= (
            t["clients"] * traffic.max_position(t))
        toy = cells.load_config(cfg["toy"])
        scale = toy["context"] / cfg["context"]
        assert (int(t["prompt_tokens"]["max"] * scale)
                + t["output_tokens"]["max"]) <= toy["context"]


@pytest.mark.parametrize("cell", NEW_CELLS)
def test_the_new_cells_rehearse_on_the_cpu(cell):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(cells.HERE, "run.py"), "--workload",
         cell, "--seed", str(2**31 + 47), "--seconds", "4", "--trace", "1",
         "--rehearse", "1"], cwd=cells.ROOT, env=env, capture_output=True,
        text=True, timeout=1500)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["rehearsal"]
    assert all(k.startswith("rehearsal.") for k in line["metrics"])
    if cell.startswith("laguna"):
        # the gather path on the CPU reads the whole window: no skip to count
        assert "rehearsal.kernel.attn_window_visited_share" not in line[
            "metrics"]
