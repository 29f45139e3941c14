"""The A.X-K1 family at its toy size (`configs/tiny-axk1.json`): its own
reference (the unabsorbed form) agrees with the program within the toy's
limits; the same reference in fp8, or given another share of the experts, does not; the traffic file the family's
cell brings deals every seed the same lengths; and both new cells rehearse on
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

SEED = 2**31 + 37
BENCH = cells.benchmark_json()


@pytest.fixture(scope="module")
def ran():
    cfg = cells.load_config("tiny-axk1")
    weights = W.make_weights(cfg, SEED)
    be = probe.build_engine(cfg, weights)
    try:
        own = probe.check(cfg, weights, SEED, be, log=lambda m: None)
        probes = probe.probe_tokens(cfg, SEED)
        low = probe.judge(probe.pass_errors(
            cfg, weights, probes, cfg["check"]["shallow"],
            lambda cut, w, pr: probe.reference_rows(cfg, w, pr, "fp8")[0]),
            cfg["check"]["shallow"])
        # the reference given another share of the experts (0 to 3, not 4 to
        # 7): what a program that took the offset for 0 would compute
        other = probe.judge(probe.pass_errors(
            cfg, weights, probes, cfg["check"]["shallow"],
            lambda cut, w, pr: probe.reference_rows(
                {**cfg, "expert_offset": 0}, w, pr)[0]),
            cfg["check"]["shallow"])
    finally:
        be.close()
    return cfg, weights, own, (low, other)


def test_its_own_reference_agrees_with_the_program(ran):
    cfg, _, own, _ = ran
    assert own["correct"]
    assert own["shallow"]["max"] < 1e-3 and own["full"]["p90"] < 1e-3
    assert own["shallow"]["rows_judged"] == cfg["engine"]["slots"]
    # every probe row runs past the toy's original context of 64
    assert min(cfg["check"]["probe_prompts"]) > cfg["rope_scaling"][
        "original_max_position_embeddings"]


def test_a_lower_precision_or_another_share_fails_the_shallow_pass(ran):
    cfg, _, _, arms = ran
    for arm in arms:  # the reference in fp8; the reference at offset 0
        assert not arm["within"]
        assert arm["stat"] > cfg["check"]["shallow"]["tol"]
        assert np.isfinite(arm["stat"])


def test_the_stacks_and_the_cuts_of_one_stack_each(ran):
    cfg, weights, _, _ = ran
    fam = cells.load_family("axk1")
    assert fam.stacks(cfg) == [("lead", 1), ("blocks", 2)]
    assert W.stack_depths(weights, cfg) == {"lead": 1, "blocks": 2}
    whole = fam.program_params(cfg, weights)
    assert set(whole) == {"embedding", "rms_final", "wcls", "lead", "blocks"}
    assert "w1" in whole["lead"] and "moe_up" in whole["blocks"]
    # a cut of one stack alone is the program's one stack, with its own spec
    for cut, kind, experts in (([0], "lead", 0), ([1, 2], "blocks", 4)):
        w = W.layer_cut(weights, cut, cfg)
        params = fam.program_params(cfg, w)
        assert "lead" not in params and ("w1" in params["blocks"]) == (
            kind == "lead")
        spec = fam.model_spec({**cfg, "num_hidden_layers": len(cut)})
        assert (spec.lead_layers, spec.n_experts, spec.n_layers) == (
            0, experts, len(cut))
    # the file's own cuts are of one stack each, as `_cut` needs
    for name in ("tiny-axk1", "ax-k1-ep4-l7"):
        c = cells.load_config(name)
        lead = c["first_k_dense_replace"]
        for cut in c["check"]["shallow"]["cuts"]:
            assert all(i < lead for i in cut) or all(i >= lead for i in cut)
            assert len(cut) != c["layers_here"]


def test_the_published_keys_are_the_catalogue_s():
    cfg = cells.load_config("ax-k1-ep4-l7")
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"], cfg["max_position_embeddings"]) == (
        7, 48, 40960, 8192)
    assert cfg["published"] == {"num_hidden_layers": 61,
                                "n_routed_experts": 192, "vocab_size": 163840,
                                "max_position_embeddings": 131072}
    assert (cfg["router_width"], cfg["expert_offset"]) == (192, 0)
    # no width touched
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["q_lora_rank"],
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"],
            cfg["num_experts_per_tok"]) == (7168, 18432, 2048, 1536, 512,
                                            128, 64, 128, 8)


# ---- the traffic file the cells bring --------------------------------------

def _take(plans, n):
    return [[p.next() for _ in range(n)] for p in plans]


def test_decode_closed_deals_every_seed_the_same_lengths():
    t = traffic.load("decode-closed")
    assert traffic.max_position(t) == 768

    def requests(seed):
        reqs = _take(traffic.plan(t, 40960, seed), t["cycle"] + 1)
        return [r for c in reqs for r in c[1:]]

    a, b = requests(1), requests(2**31 + 5)
    assert [(len(r.prompt), r.max_tokens) for r in a] == \
        [(len(r.prompt), r.max_tokens) for r in b]
    assert all(x.prompt != y.prompt for x, y in zip(a, b))
    assert len(collections.Counter(len(r.prompt) for r in a)) > t["clients"]
    assert all(64 <= len(r.prompt) <= 256 for r in a)
    assert all(256 <= r.max_tokens <= 512 for r in a)
    # every request carries a 64-token chunk: `step.mixed64_ms` finds one
    assert min(len(r.prompt) for r in a) >= 64
    same = _take(traffic.plan(t, 40960, 7), 3)
    assert same == _take(traffic.plan(t, 40960, 7), 3)


def test_the_new_cells_fit_their_configurations():
    t = traffic.load("decode-closed")
    for name in ("ax-k1-ep4-l7.decode-closed", "mistral-7b.decode-closed"):
        cell = cells.cell(BENCH, name)
        cfg = cells.load_config(cell["config"])
        assert cell["traffic"] == "decode-closed" and cell["chips"] == 1
        assert t["clients"] <= cfg["engine"]["slots"]
        assert traffic.max_position(t) <= cfg["context"]
        # the pool holds every client's longest request with room to spare
        blocks = cfg["engine"]["kv_pool_blocks"]
        assert blocks == 0 or blocks * cfg["engine"]["kv_block_tokens"] >= (
            t["clients"] * traffic.max_position(t))


@pytest.mark.parametrize("cell", ["ax-k1-ep4-l7.decode-closed",
                                  "mistral-7b.decode-closed"])
def test_the_new_cells_rehearse_on_the_cpu(cell):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(cells.HERE, "run.py"), "--workload",
         cell, "--seed", str(2**31 + 41), "--seconds", "4", "--trace", "1",
         "--rehearse", "1"], cwd=cells.ROOT, env=env, capture_output=True,
        text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["rehearsal"]
    assert all(k.startswith("rehearsal.") for k in line["metrics"])
    if cell.startswith("ax-k1"):
        assert 10 < line["metrics"]["rehearsal.moe.held_share"]["value"] < 50
