"""The Kimi-Linear family at its toy size (`configs/tiny-kimi-linear.json`):
its own reference (the delta rule as a scan over positions) agrees with the
program within the toy's limits and with the CHUNK FORM written out in
float64 numpy; the same reference in fp8, or with one mechanism changed (the
matrices zeroed at every dispatch, no decay, no delta term, no unit lengths,
the taps reversed, no output gate, the latent row's 64 values rotated, no
selection bias), does not; what the harness draws is mapped so that each of
those mechanisms does something; its stacks add up and a cut reads by its
depth as the docstring says; the published file holds the catalogue's keys
and its `notes` the sizes of the tensors drawn; the work functions and the
two readers the cell brings do their arithmetic, read the same share of a
trace cut short and nothing of a program without the names; and the new cell
rehearses on the CPU through `BatchEngine`."""

import copy
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from benchmark import cells, host_spans, kda_work, probe, traffic
from benchmark import weights as W
from benchmark.run import Ctx

SEED = 2**31 + 48
BENCH = cells.benchmark_json()
FAMILY = cells.load_family("kimi_linear")
CONTROLS = ("fp8",) + FAMILY.MECHANISM_CONTROLS
NEW_CONFIG = "kimi-linear-48b-a3b-l8"
NEW_CELL = NEW_CONFIG + ".longctx-closed"
NEW_METRICS = ("kernel.kda_roofline_share", "step.kda_share")
FIXTURE = os.path.join(cells.HERE, "fixtures", "trace_kda_ops.json")


@pytest.fixture(scope="module")
def ran():
    cfg = cells.load_config("tiny-kimi-linear")
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
    prompts = cfg["check"]["probe_prompts"]
    assert min(prompts) < 256 < max(prompts) and held >= 3


@pytest.mark.parametrize("control", CONTROLS)
def test_a_lower_precision_or_a_mechanism_changed_fails_a_limit(ran, control):
    """Every control of the check moves the logits past a limit."""
    cfg, _, _, arms, _ = ran
    arm = arms[control]
    assert not (arm["shallow"]["within"] and arm["full"]["within"])
    assert np.isfinite(arm["full"]["stat"])


def test_the_recurrence_equals_the_chunk_form_in_float64():
    """The family's position-by-position scan against the chunk form of the
    issue written out in float64 numpy (A, the triangular solve, o and S_T),
    at decays mild enough that float64 holds exp(-G): two statements of one
    function that share no line."""
    import jax.numpy as jnp

    r = np.random.RandomState(3)
    t, heads, kk, vv = 24, 3, 16, 8
    q, k = (r.randn(t, heads, kk) for _ in range(2))
    q /= np.sqrt((q * q).sum(-1, keepdims=True) + 1e-6)
    k /= np.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    v = r.randn(t, heads, vv)
    g = -r.uniform(0.0, 2.0, (t, heads, kk))
    beta = r.uniform(0, 1, (t, heads))
    got_o, got_s = FAMILY.kda_recurrence(*(jnp.asarray(a, jnp.float32)
                                           for a in (q, k, v, g, beta)))
    for h in range(heads):
        cum = np.cumsum(g[:, h], axis=0)  # G_t, a channel
        gam = np.exp(cum)
        kq, kk_ = k[:, h] * gam, k[:, h] / gam
        a = np.tril(beta[:, h, None] * (kq @ kk_.T), -1)
        u = np.linalg.solve(np.eye(t) + a, beta[:, h, None] * v[:, h])
        o = np.tril((q[:, h] * gam) @ kk_.T) @ u * kk ** -0.5
        s_t = (k[:, h] * np.exp(cum[-1] - cum)).T @ u
        np.testing.assert_allclose(got_o[:, h], o, atol=2e-5)
        np.testing.assert_allclose(got_s[h], s_t, atol=2e-5)


def test_the_drawn_tensors_are_mapped_so_that_each_mechanism_acts(ran):
    cfg, weights, _, _, _ = ran
    m = FAMILY.mapped(weights)
    assert FAMILY.mapped(m) is m
    a = np.exp(m["blocks.kda_a_log"])
    assert 0.9 < a[:, 0].mean() < 1.1 and 15 < a[:, -1].mean() < 17
    step = np.log1p(np.exp(m["blocks.kda_dt_bias"])).reshape(7, 4, 32)
    assert step[:, :, 0].max() < 2e-3 and step[:, :, -1].min() > 5e-2
    taps = m["blocks.kda_conv_w"].mean(axis=(0, 1))
    np.testing.assert_allclose(taps, FAMILY.TAPS, atol=0.02)
    assert np.abs(m["blocks.router_bias"]).mean() > 0.02
    # beta's logits spread by 2 whatever the width
    gain = (m["lead.kda_b"][1].astype(np.float32)
            / weights["lead.kda_b"][1].astype(np.float32))
    np.testing.assert_allclose(gain, FAMILY.beta_gain(128), rtol=2e-3)
    assert FAMILY.beta_gain(2304) == pytest.approx(2.0 / 0.96, rel=1e-3)
    # an MLA layer's scores spread enough for its softmax to select
    gain = (m["blocks.wq"][1].astype(np.float32)
            / weights["blocks.wq"][1].astype(np.float32))
    np.testing.assert_allclose(gain, FAMILY.Q_GAIN, rtol=2e-3)
    # the head is untied: the embedding stays the one drawn
    assert m["embedding"] is weights["embedding"]


def test_the_stacks_add_up_and_a_cut_reads_by_its_depth(ran):
    cfg, weights, _, _, _ = ran
    assert FAMILY.stacks(cfg) == [("lead", 1), ("blocks", 7)]
    cut = W.layer_cut(weights, [0, 3], cfg)
    assert FAMILY._types_held(cfg, cut) == ["kda", "mla"]
    spec = FAMILY.model_spec({**cfg, "num_hidden_layers": 2})
    assert spec.layer_kinds == (0, 1) and spec.state_layers == (0,)
    assert spec.lead_layers == 1 and spec.cache_layers == (1,)
    params = FAMILY.program_params(cfg, cut)
    assert params["lead"]["kda_in"].shape[0] == 1
    assert "kda_in" not in params["blocks"]
    assert params["blocks"]["wq"].shape[0] == 1
    assert params["blocks"]["router"].shape[0] == 1
    with pytest.raises(ValueError, match="a cut of 3"):
        FAMILY.model_spec({**cfg, "num_hidden_layers": 3})
    small = FAMILY.one_layer_a_stack(cfg, experts=2)
    assert FAMILY.stacks(small) == [("lead", 1), ("blocks", 3)]
    assert FAMILY.layer_types(small) == ["kda", "kda", "kda", "mla"]


def test_the_state_control_zeroes_the_matrices_at_dispatch_starts():
    starts = FAMILY.dispatch_starts(75, 96)
    assert np.nonzero(starts[:75])[0].tolist() == [0, 64, 72, 73, 74]
    assert starts[75:].all()


def test_the_published_keys_are_the_catalogue_s():
    cfg = cells.load_config(NEW_CONFIG)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "Kimi-Linear-48B-A3B-Instruct")
    for key, value in entry["config"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert cfg["source"] == entry["source_url"]
    assert cfg["reduced"] == ["num_hidden_layers", "max_position_embeddings"]
    assert (cfg["num_hidden_layers"], cfg["published"]["num_hidden_layers"],
            cfg["max_position_embeddings"], cfg["context"]) == (
        8, 27, 4096, 4096)
    assert len(cfg["assumed"]) >= 10 and cfg["deployment"] and cfg["notes"]
    assert cfg["state_snapshots"] == 24
    prompts = cfg["check"]["probe_prompts"]
    assert prompts[-1] == 2304 and all(250 <= n <= 262 for n in prompts[:-1])
    assert cfg["check"]["shallow"]["cuts"] == [[0, 3]]
    assert cfg["check_canary"] == "lead.kda_out"


def test_the_sizes_in_notes_equal_the_tensors_drawn():
    """`notes` is re-reckoned from `tensor_shapes`: the megabytes of a KDA
    expert layer, an MLA expert layer, layer 0, the head, the embedding and
    their sum, at 0.5625 bytes a Q40 weight and 4 a float32 value."""
    cfg = cells.load_config(NEW_CONFIG)
    shapes = FAMILY.tensor_shapes(cfg)

    def mb(names, prefix):
        total = 0.0
        for n in names:
            shape, q = shapes[f"{prefix}.{n}" if prefix else n]
            per = shape[1:] if prefix else shape
            total += float(np.prod(per)) * (0.5625 if q else 4.0)
        return total / 1e6

    both = [n.split(".", 1)[1] for n in shapes if n.startswith("blocks.")]
    ffn = [n for n in both if n not in FAMILY.KDA + FAMILY.MLA]
    kda_layer = mb(list(FAMILY.KDA) + ffn, "blocks")
    mla_layer = mb(list(FAMILY.MLA) + ffn, "blocks")
    lead = mb([n.split(".", 1)[1] for n in shapes if n.startswith("lead.")],
              "lead")
    head, emb = mb(["wcls"], ""), mb(["embedding"], "")
    stated = [float(x) for x in re.findall(
        r"A KDA expert layer [\d.]+ M = ([\d.]+) MB, an MLA expert layer "
        r"[\d.]+ M = ([\d.]+) MB, layer 0 \([^)]*\) [\d.]+ M = ([\d.]+) MB, "
        r"head [\d.]+ M = ([\d.]+) MB, embedding [^=]*= ([\d.]+) MB",
        cfg["notes"])[0]]
    np.testing.assert_allclose(stated, [kda_layer, mla_layer, lead, head, emb],
                               atol=0.35)
    total = lead + 5 * kda_layer + 2 * mla_layer + head + emb
    assert f"= {total / 1e3:.2f} GB of weights" in cfg["notes"]
    assert total / 1e3 / 15.75 > 0.25  # the floor of a deployment's share


# ---- the work functions and the readers the cell brings ---------------------

def _ctx(before, after, trace=None, trace_dir=None):
    return Ctx(cells.load_config(NEW_CONFIG), trace, before, after, {},
               trace_dir)


def test_the_work_of_a_step_and_of_a_chunk_counts_live_rows_alone():
    cfg = cells.load_config(NEW_CONFIG)
    heads, k, v = kda_work.sizes(cfg)
    assert (heads, k, v) == (32, 128, 128)
    bytes_, flop = kda_work.step_work(48, heads, k, v)  # 8 live rows, 6 layers
    assert bytes_ == 48 * 2 * 2 * 2**20 and flop == 48 * 7 * 2**19
    assert kda_work.step_work(0, heads, k, v) == (0.0, 0.0)
    bytes_, flop = kda_work.chunk_work(6, 64, heads, k, v)
    assert bytes_ == 6 * (4 * 2**20 + 4 * 64 * 32 * (3 * 128 + 2 * 128 + 1))
    assert flop == 6 * 32 * (4 * 64 * 64 * 128 + 3 * 64 * 64 * 128
                             + 6 * 64 * 128 * 128)
    # a configuration without such a mixer (a reader's arithmetic case runs
    # under mistral-7b's file) is given the published file's sizes
    assert kda_work.sizes(cells.load_config("mistral-7b")) == (heads, k, v)


@pytest.fixture()
def traces(monkeypatch):
    """The fixture (its host side runs on behind the device side's end: a
    trace cut short; its first execution, an 8-token chunk whose span opens
    over half of it late, is one `host_spans._joined` leaves without a span)
    and the same window whole."""
    with open(FIXTURE) as f:
        cut = json.load(f)
    whole = copy.deepcopy(cut)
    dev = whole["planes"][0]["lines"]
    shift = 42_000_000  # the three dispatches once more, 42 ms later
    for line in dev:
        line["events"] += [[ev[0], ev[1] + shift, *ev[2:]]
                           for ev in line["events"] if ev[1] >= 10_000_000]
    store = {"cut": cut, "whole": whole}
    monkeypatch.setattr(host_spans, "from_xplane", lambda name: store[name])
    yield store
    host_spans._window_trace.cache_clear()


def test_the_roofline_share_of_a_trace_cut_short_is_the_whole_window_s(
        traces):
    got = {}
    for name in ("cut", "whole"):
        host_spans._window_trace.cache_clear()
        got[name] = _ctx({}, {}, None, name).metric(
            "kernel.kda_roofline_share")
        j = kda_work.joined(traces[name], cells.load_config(NEW_CONFIG))
        assert j.dispatches == (3 if name == "cut" else 6)
        # the unjoined execution: one of two (of three) `jit_step`s, with
        # 0.7 ms of kernels that are in the window's time and not in these
        assert j.step_share == pytest.approx(1 / 2 if name == "cut" else 2 / 3)
        assert j.scan_share == 1.0
        assert j.kernel_s == pytest.approx(
            0.0074 if name == "cut" else 0.0148)
    assert got["cut"] == pytest.approx(got["whole"])
    assert got["cut"] == pytest.approx(56.99202217602217)
    assert 2 * got["cut"] > 105  # what a reader of counters would report


def test_the_roofline_share_does_not_move_with_the_part_joined(traces,
                                                               capsys):
    """The same window with the late span on time: the execution is joined,
    its work and its kernels' time come in together, and the share moves by
    what that one dispatch's own share differs, not by 0.7 ms of time
    without work."""
    config = cells.load_config(NEW_CONFIG)
    late = kda_work.joined(traces["cut"], config)
    for ev in traces["cut"]["planes"][1]["lines"][0]["events"]:
        if ev[0] == "batch.mixed_step" and ev[3]["chunk"] == 8:
            ev[1], ev[2] = 400_000, 6_500_000
    traces["cut"].pop("kda_ops")
    on_time = kda_work.joined(traces["cut"], config)
    assert on_time.step_share == 1.0 and on_time.dispatches == 4
    assert on_time.kernel_s == pytest.approx(late.kernel_s + 0.0007)
    assert on_time.bytes > late.bytes
    host_spans._window_trace.cache_clear()
    assert _ctx({}, {}, None, "cut").metric(
        "kernel.kda_roofline_share") == pytest.approx(
            100 * max(on_time.bytes / kda_work.HBM_BYTES_S,
                      on_time.flop / kda_work.PEAK_FLOP_S) / on_time.kernel_s)
    assert "WARNING" not in capsys.readouterr().out


def test_the_mixers_share_counts_kernels_and_projections_by_name(traces):
    host_spans._window_trace.cache_clear()
    ctx = _ctx({}, {}, {"busy_s": 0.04}, "cut")
    assert ctx.metric("step.kda_share") == pytest.approx(32.0)
    trace = traces["cut"]
    seconds = kda_work.op_seconds(trace)
    assert sum(seconds[k] for k in kda_work.KERNELS) == pytest.approx(0.0081)
    assert sum(seconds[k] for k in kda_work.PROJECTIONS) == pytest.approx(
        0.0047)
    del trace["kda_ops"]  # found once a trace: look anew
    trace["planes"][0]["lines"][1]["events"].append(
        ["%fusion.9 = f32[8,4096]{1,0} fusion(f32[8,32,128]{2,1,0} "
         "%kda_step.5)", 60_000_000, 1_000_000])
    assert sum(kda_work.op_seconds(trace)[k]
               for k in kda_work.KERNELS) == pytest.approx(0.0081)


@pytest.mark.parametrize("name", NEW_METRICS)
@pytest.mark.parametrize("other", ["trace_laguna_ops.json",
                                   "trace_ssd_ops.json"])
def test_a_program_without_the_names_or_the_span_args_reads_nothing(
        monkeypatch, name, other):
    """The parent of this PR (no KDA kernels, no span args), and Granite's
    program, whose state is a matrix a head too under other kernels."""
    path = os.path.join(cells.HERE, "fixtures", other)

    def load(p):
        with open(p) as f:
            return json.load(f)

    monkeypatch.setattr(host_spans, "from_xplane", load)
    host_spans._window_trace.cache_clear()
    ctx = _ctx({}, {}, {"busy_s": 0.008}, path)
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
    # the lists this PR may not put the cell on (ROADMAP M14) are as they were
    for name in ("cache.ssm_state_mb", "cache.ssm_snapshot_share",
                 "kernel.latent_attn_roofline_share",
                 "step.latent_attn_share", "kernel.moe_expert_roofline_share",
                 "sched.fetch_mb"):
        m = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert NEW_CELL not in m["workloads"]
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
         NEW_CELL, "--seed", str(2**31 + 52), "--seconds", "4", "--trace", "1",
         "--rehearse", "1"], cwd=cells.ROOT, env=env, capture_output=True,
        text=True, timeout=1500)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["rehearsal"]
    assert all(k.startswith("rehearsal.") for k in line["metrics"])
