"""A model family is one file found by name: what the move of the Mistral
and Mixtral graphs into `families/mistral.py` must not have changed (the
weights a seed draws, the statistics the check reads), and what the harness
asks of any family's `logits_at`."""

import glob
import hashlib
import json
import os

import numpy as np
import pytest

from benchmark import cells, probe
from benchmark import weights as W

# sha256 over every tensor's name, dtype, shape and bytes in sorted order,
# read at the parent commit (a07ffef) by `weights.make_weights`
PARENT_WEIGHTS = {
    ("tiny-dense", 1):
        "60e4d94a562bdc4de2b97e130d9ea6db754da347efc999fc7d8b2cc255208609",
    ("tiny-dense", 3000000301):
        "b77c05be2adc4177d8173a44641df9dfeb0d9335b85ce83a882f6dc72ed6e1e2",
    ("tiny-moe", 1):
        "1f0429efa3cebc2a81e8505e8345ef6b84782d148f9564abdb147af2a8631349",
    ("tiny-moe", 3000000301):
        "54e1183cccc7261fd0028fc0969d42d7231111f7ed7caf467606962186149a7e",
}
# `probe.check` at the parent commit on the CPU: per pass (stat, judged of
# positions). The judged count moves if a router margin's scale does.
PARENT_CHECK = {
    ("tiny-dense", 1): ((4.2707668512775854e-07, 128, 128),
                        (5.223354264671798e-07, 128, 128)),
    ("tiny-dense", 2): ((4.617951958607591e-07, 128, 128),
                        (4.469012537811068e-07, 128, 128)),
    ("tiny-dense", 3): ((4.7164476768557506e-07, 128, 128),
                        (4.766825441038236e-07, 128, 128)),
    ("tiny-moe", 1): ((2.857687206869741e-07, 241, 256),
                      (3.745368317709108e-07, 128, 128)),
    ("tiny-moe", 2): ((2.8999909318372374e-07, 235, 256),
                      (3.659500507069424e-07, 128, 128)),
    ("tiny-moe", 3): ((2.8734682757658445e-07, 239, 256),
                      (3.678738806911497e-07, 128, 128)),
}


def digest(weights: dict) -> str:
    h = hashlib.sha256()
    for name in sorted(weights):
        t = weights[name]
        for a in (t if isinstance(t, tuple) else (t,)):
            a = np.ascontiguousarray(a)
            for part in (name, str(a.dtype), str(a.shape)):
                h.update(part.encode())
            h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name,seed", sorted(PARENT_WEIGHTS))
def test_a_seed_draws_the_weights_it_drew_before_the_move(name, seed):
    cfg = cells.load_config(name)
    assert digest(W.make_weights(cfg, seed)) == PARENT_WEIGHTS[name, seed]


@pytest.mark.parametrize("name,seed", sorted(PARENT_CHECK))
def test_the_check_reads_what_it_read_before_the_move(name, seed):
    cfg = cells.load_config(name)
    weights = W.make_weights(cfg, seed)
    be = probe.build_engine(cfg, weights)
    try:
        out = probe.check(cfg, weights, seed, be, log=lambda m: None)
    finally:
        be.close()
    for res, (stat, judged, positions) in zip(
            (out["shallow"], out["full"]), PARENT_CHECK[name, seed]):
        assert res["stat"] == pytest.approx(stat, abs=1e-6)
        assert (res["judged"], res["positions"]) == (judged, positions)


@pytest.mark.parametrize("name", ["tiny-dense", "tiny-moe"])
def test_rows_of_unequal_length_read_as_each_row_alone(name):
    """The harness hands a family rows of their own lengths and asks for
    some positions of each: the logits there are the row's own, whatever
    stood beside it."""
    cfg = cells.load_config(name)
    fam = cells.load_family(cfg["family"])
    weights = W.make_weights(cfg, 7)
    rng = np.random.default_rng(7)
    rows = [rng.integers(3, cfg["vocab_size"], size=n).tolist()
            for n in (5, 40, 17)]
    at = [[0, 4], range(30, 40), [16]]
    got, margin = fam.logits_at(cfg, weights, rows, at)
    assert got.shape == (13, cfg["vocab_size"]) and margin.shape == (13,)
    alone = np.concatenate([fam.logits_at(cfg, weights, [r], [a])[0]
                            for r, a in zip(rows, at)])
    np.testing.assert_allclose(got, alone, rtol=0, atol=2e-5)
    assert np.isinf(margin).all() == (name == "tiny-dense")


def test_a_family_with_no_file_fails_with_the_path_it_looked_for():
    cfg = {**cells.load_config("tiny-dense"), "family": "no-such-family"}
    want = os.path.join(cells.HERE, "families", "no-such-family.py")
    with pytest.raises(FileNotFoundError) as e:
        W.make_weights(cfg, 1)
    assert want in str(e.value)


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(
    cells.HERE, "configs", "*.json"))), ids=os.path.basename)
def test_every_configuration_names_a_family_a_toy_and_its_probes(path):
    with open(path) as f:
        cfg = json.load(f)
    fam = cells.load_family(cfg["family"])
    for fn in ("model_spec", "tensor_shapes", "logits_at"):
        assert callable(getattr(fam, fn))
    toy = cells.load_config(cfg["toy"])
    assert toy["toy"] == cfg["toy"] and toy["context"] <= cfg["context"]
    prompts, decode = cfg["check"]["probe_prompts"], cfg["check"]["probe_decode"]
    assert len(prompts) == cfg["engine"]["slots"] and decode >= 1
    assert max(prompts) + decode <= cfg["context"]
    shapes = fam.tensor_shapes(cfg)
    assert set(W.NOT_BLOCKS) <= set(shapes)
    # every stack's tensors are as deep as the family says the stack is (one
    # unnamed stack of every layer where it says nothing)
    declared = (dict(fam.stacks(cfg)) if hasattr(fam, "stacks")
                else {"": cfg["num_hidden_layers"]})
    assert sum(declared.values()) == cfg["num_hidden_layers"]
    in_stacks = {n: W.stack_of(n, declared) for n in shapes}
    assert set(declared) == set(in_stacks.values()) - {None}
    assert all(shapes[n][0][0] == declared[st]
               for n, st in in_stacks.items() if st is not None)
