"""The third toy: a family added by three files (`families/toy_gelu.py`,
`configs/tiny-gelu-moe.json`, this test). Its own reference agrees with the
program; the Mistral family's reference, put in its place, does not."""

import pytest

from benchmark import cells, probe
from benchmark import weights as W

SEED = 2**31 + 29


@pytest.fixture(scope="module")
def ran():
    """Both verdicts on one engine: the check by the configuration's own
    family, and the same logits judged by `families/mistral.py`."""
    cfg = cells.load_config("tiny-gelu-moe")
    weights = W.make_weights(cfg, SEED)
    be = probe.build_engine(cfg, weights)
    try:
        got_of = probe.engine_logits(cfg, be)  # the program, as cfg says
        own = probe.check(cfg, weights, SEED, be, log=lambda m: None,
                          got_of=got_of)
        other = probe.check({**cfg, "family": "mistral"}, weights, SEED, be,
                            log=lambda m: None, got_of=got_of)
    finally:
        be.close()
    return cfg, own, other


def test_the_family_file_is_found_and_its_check_passes(ran):
    cfg, own, _ = ran
    assert own["correct"]
    assert own["shallow"]["max"] < 1e-3 and own["full"]["max"] < 1e-3
    n = cfg["engine"]["slots"] * cfg["check"]["probe_decode"]
    assert own["full"]["positions"] == own["full"]["judged"] == n
    assert own["shallow"]["rows_judged"] == cfg["engine"]["slots"]
    assert len(set(cfg["check"]["probe_prompts"])) > 1  # rows of unequal length


def test_judged_by_another_family_s_reference_it_fails(ran):
    cfg, _, other = ran
    assert not other["correct"]
    assert not other["shallow"]["within"]
    assert other["shallow"]["stat"] > cfg["check"]["shallow"]["tol"]
