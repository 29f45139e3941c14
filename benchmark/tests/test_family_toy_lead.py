"""A family whose leading layer stands in a stack of its own
(`families/toy_lead.py`, `configs/tiny-lead.json`): what `weights.py` makes
of a layer index that is global over two stacks, and a whole run of
`run.py --rehearse 1` on it through `BatchEngine` on the CPU."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import cells, probe
from benchmark import weights as W

SEED = 2**31 + 37


@pytest.fixture(scope="module")
def drawn():
    cfg = cells.load_config("tiny-lead")
    return cfg, W.make_weights(cfg, SEED)


def test_depth_and_layers_are_global_over_the_stacks(drawn):
    cfg, w = drawn
    assert W.stack_depths(w, cfg) == {"lead": 1, "blocks": 1}
    assert W.depth(w, cfg) == 2
    first, second = W.layer(w, 0, cfg), W.layer(w, 1, cfg)
    assert set(first) == set(second) == {
        "wq", "wk", "wv", "wo", "w1", "w2", "w3", "rms_att", "rms_ffn"}
    assert np.array_equal(first["wq"][0], w["lead.wq"][0][0])
    assert np.array_equal(second["wq"][0], w["blocks.wq"][0][0])
    assert not np.array_equal(first["wq"][0], second["wq"][0])
    with pytest.raises(IndexError):
        W.layer(w, 2, cfg)
    # without the configuration nothing says how deep each stack is
    for fn in (W.depth, lambda x: W.layer(x, 0), lambda x: W.layer_cut(x, [0])):
        with pytest.raises(ValueError, match="lead"):
            fn(w)


def test_a_cut_goes_to_each_stack_by_its_own_share(drawn):
    cfg, w = drawn
    deep = {**cfg, "num_hidden_layers": 5, "lead_layers": 2,
            "block_layers": 3}
    w5 = W.make_weights(deep, SEED)
    cut = W.layer_cut(w5, [0, 3, 4], deep)
    assert W.stack_depths(cut, deep) == {"lead": 1, "blocks": 2}
    assert np.array_equal(cut["lead.wo"][1], w5["lead.wo"][1][[0]])
    assert np.array_equal(cut["blocks.rms_ffn"], w5["blocks.rms_ffn"][[1, 2]])
    assert cut["embedding"] is w5["embedding"]
    # a cut of a cut counts the layers that are left
    assert np.array_equal(W.layer(cut, 1, deep)["wo"][0], w5["blocks.wo"][0][1])
    only_blocks = W.layer_cut(w5, [2], deep)
    assert W.stack_depths(only_blocks, deep) == {"lead": 0, "blocks": 1}
    assert W.depth(only_blocks, deep) == 1
    with pytest.raises(IndexError):
        W.layer_cut(w5, [5], deep)


def test_stacks_that_do_not_add_up_fail_with_both_numbers(drawn):
    cfg, _ = drawn
    with pytest.raises(ValueError) as e:
        W.make_weights({**cfg, "num_hidden_layers": 7}, SEED)
    assert "hold 2 layers" in str(e.value) and "is 7" in str(e.value)


def test_the_program_is_handed_one_stack_and_the_check_passes(drawn):
    cfg, w = drawn
    params = W.to_program_params(w, cfg)
    assert set(params) == {"embedding", "blocks", "rms_final", "wcls"}
    assert params["blocks"]["rms_att"].shape[0] == 2
    assert cfg["check"]["shallow"]["cuts"] == [[0], [1], [0, 1]]
    be = probe.build_engine(cfg, w)
    try:
        out = probe.check(cfg, w, SEED, be, log=lambda m: None)
    finally:
        be.close()
    assert out["correct"]
    assert out["shallow"]["max"] < 1e-3 and out["full"]["max"] < 1e-3
    n = cfg["engine"]["slots"] * cfg["check"]["probe_decode"]
    assert out["shallow"]["positions"] == 3 * n


def test_the_canary_named_in_the_file_fails_the_cuts_that_hold_it(drawn):
    cfg, w = drawn
    spec = cfg["check"]["shallow"]
    bad = W.mis_scaled(w, cfg["check"]["canary"], 1.125)
    pe = probe.pass_errors(
        cfg, w, probe.probe_tokens(cfg, SEED), spec,
        lambda cut, cw, pr: probe.engine_logits(cfg)(
            cut, W.layer_cut(bad, cut, cfg), pr))
    assert not probe.judge(pe, spec)["within"]
    n = cfg["engine"]["slots"] * cfg["check"]["probe_decode"]
    lead, blocks, both = pe["err"][:n], pe["err"][n:2 * n], pe["err"][2 * n:]
    assert (lead < 1e-3).all()  # the mis-scaled matrix is not in this cut
    assert (blocks > spec["tol"]).all() and (both > spec["tol"]).all()


def test_a_rehearsal_drives_the_leading_stack_through_the_engine():
    """`run.py --rehearse 1` whole, on the CPU: the dense cell's `toy` is
    pointed at `tiny-lead` (no cell names it), nothing else is touched."""
    code = ("import sys\n"
            "from benchmark import cells, run\n"
            "real = cells.load_config\n"
            "cells.load_config = lambda n: ({**real(n), 'toy': 'tiny-lead'}"
            " if n == 'mistral-7b' else real(n))\n"
            "run.main(sys.argv[1:])\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, "-c", code, "--workload", "mistral-7b.chat-closed",
         "--seed", str(SEED), "--seconds", "3", "--trace", "0",
         "--rehearse", "1"],
        cwd=cells.ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    assert "configs/tiny-lead.json" in p.stdout
    assert p.stdout.count("check shallow:") == 1
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["rehearsal"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
