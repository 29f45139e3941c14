import json
import os
import re

import pytest

from benchmark import cells, traffic

BENCH = cells.benchmark_json()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj|head).*size|_dim$|"
                   r"_rank$|num_experts_per_tok|expansion")


def test_exactly_the_contract_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(cells.ROOT, "BENCHMARK.json")) < 65536
    assert not any(w.startswith("/") or ".." in w for w in BENCH["command"])


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_every_workload_resolves_to_files(w):
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    cfg = cells.load_config(w["config"])
    assert w["config"] in [c["name"] for c in BENCH["configs"]]
    tr = traffic.load(w["traffic"])
    assert tr["clients"] <= cfg["engine"]["slots"]
    assert traffic.max_position(tr) <= cfg["context"]
    reported = (cells.metrics_of(BENCH, "end_to_end", w["name"])
                + cells.metrics_of(BENCH, "per_layer", w["name"]))
    assert "setup_s" in [m["name"] for m in reported] and len(reported) >= 3


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_every_config_file_states_its_cut(c):
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert c["file"].startswith("benchmark/") and NAME.match(c["name"])
    with open(os.path.join(cells.ROOT, c["file"])) as f:
        cfg = json.load(f)
    assert cfg["source"] == c["source"] and len(c["source"]) <= 200
    assert cfg["reduced"] == c["reduced"]
    assert not any(WIDTH.search(k) for k in c["reduced"])
    for key in ("engine", "check", "assumed", "context", "family", "toy"):
        assert key in cfg
    assert set(cfg["check"]) == {"shallow", "full", "reason", "probe_prompts",
                                 "probe_decode"}
    for name in ("shallow", "full"):
        spec = cfg["check"][name]
        assert {"quantile", "tol"} <= set(spec) <= {"quantile", "tol", "cuts",
                                                    "margin"}
    # the pass that carries the verdict on precision never rests on the
    # better half of a row
    assert 0.5 <= cfg["check"]["shallow"]["quantile"] <= 1.0
    assert 0.25 <= cfg["check"]["full"]["quantile"] <= 1.0
    depth = cfg["num_hidden_layers"]
    assert all(0 <= i < depth for cut in cfg["check"]["shallow"]["cuts"]
               for i in cut)
    assert "cuts" not in cfg["check"]["full"]  # the cell's own engine


@pytest.mark.parametrize(
    "m", BENCH["end_to_end"] + BENCH["per_layer"], ids=lambda m: m["name"])
def test_every_metric_is_well_formed(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("device_trace", "program_span", "program_counter",
                           "host_clock")
    for w in m.get("workloads", []):
        cells.cell(BENCH, w)


@pytest.mark.parametrize("m", BENCH["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_bounds(m):
    assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                      "source"}
    assert 0.01 <= m["bound"] <= 0.1
    assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_every_layer_metric_has_a_reader_that_agrees(m):
    assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                      "layer", "moves"}
    r = cells.load_reader(m["name"])
    assert (r.UNIT, r.LAYER, r.MOVES, r.SOURCE) == (
        m["unit"], m["layer"], m["moves"], m["source"])
    assert m["moves"] in [e["name"] for e in BENCH["end_to_end"]]
    assert callable(r.read)


def test_names_are_unique_and_paths_hold_only_named_characters():
    for group in ("configs", "workloads"):
        names = [x["name"] for x in BENCH[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(metrics) == len(set(metrics))
    for p in BENCH["paths"]:
        for d, _, files in os.walk(os.path.join(cells.ROOT, p)):
            if "__pycache__" in d or ".pytest_cache" in d:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), cells.ROOT)
                assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel
