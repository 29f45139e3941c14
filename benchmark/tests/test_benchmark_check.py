"""The reference against the program (`models/forward.py` through
`BatchEngine`) at a toy size on the CPU for both block graphs, and the two
canaries of the output check."""

import numpy as np
import pytest

from benchmark import cells, probe
from benchmark import weights as W

SEED = 2**31 + 11


@pytest.fixture(scope="module", params=["tiny-dense", "tiny-moe"])
def setup(request):
    cfg = cells.load_config(request.param)
    weights = W.make_weights(cfg, SEED)
    probes = probe.probe_tokens(cfg, SEED)
    return cfg, weights, probes


@pytest.fixture(scope="module")
def moe():
    cfg = cells.load_config("tiny-moe")
    weights = W.make_weights(cfg, SEED + 1)
    probes = probe.probe_tokens(cfg, SEED + 1)
    pe0, _ = shallow(cfg, weights, probes,
                     lambda cut, w, pr: probe.reference_rows(cfg, w, pr)[0])
    return cfg, weights, probes, pe0


def shallow(cfg, weights, probes, got_of):
    """The shallow pass's verdict, by the code and the configuration's
    limits that a run's set-up uses."""
    spec = cfg["check"]["shallow"]
    pe = probe.pass_errors(cfg, weights, probes, spec, got_of)
    return pe, probe.judge(pe, spec)


def test_the_reference_agrees_with_the_program_at_both_depths(setup):
    cfg, weights, _ = setup
    be = probe.build_engine(cfg, weights)
    try:
        out = probe.check(cfg, weights, SEED, be, log=lambda m: None)
    finally:
        be.close()
    assert out["correct"]
    assert out["shallow"]["max"] < 1e-3 and out["full"]["max"] < 1e-3
    n = cfg["engine"]["slots"] * cfg["check"]["probe_decode"]
    assert out["full"]["positions"] == out["full"]["judged"] == n
    assert out["shallow"]["positions"] == n * len(cfg["check"]["shallow"]["cuts"])
    assert out["shallow"]["rows_judged"] == cfg["engine"]["slots"]


def test_scales_off_by_an_eighth_fail_the_shallow_pass(setup):
    cfg, weights, probes = setup
    bad = W.mis_scaled(weights, "wo", 1.125)
    pe, res = shallow(cfg, weights, probes,
                      lambda cut, w, pr: probe.engine_logits(cfg)(
                          cut, W.layer_cut(bad, cut), pr))
    assert not res["within"]
    # every position of the cut that holds layer 0 is over the limit
    first = cfg["check"]["probe_decode"] * cfg["engine"]["slots"]
    assert (pe["err"][:first] > cfg["check"]["shallow"]["tol"]).all()


def flipped(cfg, probes, pe, pick):
    """The reference with one routed expert swapped in layer 0, at the
    recorded position of the first cut that `pick` chooses by its margin."""
    decode = cfg["check"]["probe_decode"]
    i = int(pick(pe["gap"][:decode * len(probes)]))
    row, k = divmod(i, decode)
    t = len(probes[row][0]) - 1 + k

    def got(cut, w, pr):
        flip = (0, row, t) if cut == cfg["check"]["shallow"]["cuts"][0] else None
        return probe.reference_rows(cfg, w, pr, flip=flip)[0]
    return i, got


def test_another_last_expert_where_the_router_is_undecided_is_not_judged(moe):
    cfg, weights, probes, pe0 = moe
    spec = cfg["check"]["shallow"]
    i, got = flipped(cfg, probes, pe0, np.argmin)
    assert pe0["gap"][i] < spec["margin"]  # where a sound engine may differ
    pe, res = shallow(cfg, weights, probes, got)
    assert pe["err"][i] > spec["tol"]  # the position moved
    assert res["within"] and res["judged"] < res["positions"]


def test_one_stray_expert_is_counted_and_two_in_a_row_cannot_decide(moe):
    cfg, weights, probes, pe0 = moe
    i, got = flipped(cfg, probes, pe0, np.argmax)  # the router was decided
    pe, res = shallow(cfg, weights, probes, got)
    assert pe["err"][i] > cfg["check"]["shallow"]["tol"]
    assert res["over_tol_judged"] == 1 and res["within"]
    row = pe["row"] == pe["row"][i]
    pe["err"] = np.where(row, 1.0, pe["err"])  # the whole slot is a fault
    assert not probe.judge(pe, cfg["check"]["shallow"])["within"]


@pytest.mark.parametrize("precision,passes", [("float32", True),
                                              ("bfloat16", True),
                                              ("fp8", False)])
def test_the_control_in_a_lower_precision_fails(setup, precision, passes):
    cfg, weights, probes = setup
    _, res = shallow(cfg, weights, probes,
                     lambda cut, w, pr: probe.reference_rows(
                         cfg, w, pr, precision)[0])
    assert res["within"] is passes


def test_a_slot_that_returns_other_logits_fails_either_pass():
    pe = {"err": np.r_[np.full(112, 0.01), np.full(16, 1.4)],
          "row": np.repeat(np.arange(8), 16), "gap": np.full(128, np.inf)}
    for q in (0.5, 1.0):
        res = probe.judge(pe, {"quantile": q, "tol": 0.1})
        assert not res["within"] and res["worst_row"] == 7
    pe["gap"][112:] = 0.0  # a row with nothing to judge is not passed over
    assert not probe.judge(pe, {"quantile": 1.0, "tol": 0.1, "margin": 0.05}
                           )["within"]


def test_the_same_seed_gives_the_same_weights_and_a_large_seed_is_taken():
    cfg = cells.load_config("tiny-dense")
    a, b = W.make_weights(cfg, SEED), W.make_weights(cfg, SEED)
    c = W.make_weights(cfg, SEED + 1)
    assert np.array_equal(a["wq"][0], b["wq"][0])
    assert not np.array_equal(a["wq"][0], c["wq"][0])
    nib = np.concatenate([a["w1"][0] & 0x0F, a["w1"][0] >> 4])
    assert nib.min() == 1  # nibble 0 is remapped to 8: zero-mean weights
    assert abs(float(W.dequantize(*a["w1"]).mean())) < 1e-3
