"""Finding a cell's files by the names in BENCHMARK.json."""

from __future__ import annotations

import functools
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def benchmark_json() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_config(name: str) -> dict:
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def cell(bench: dict, workload: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")


def metrics_of(bench: dict, group: str, workload: str) -> list[dict]:
    """The metrics of `group` (end_to_end | per_layer) this cell reports."""
    return [m for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]


def _load(kind: str, directory: str, name: str):
    path = os.path.join(HERE, directory, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} {name!r}: looked for {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{directory}_" + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str):
    """The reader module of a per-layer metric: layer_metrics/<name>.py."""
    return _load("per-layer metric", "layer_metrics", name)


@functools.lru_cache(maxsize=None)
def load_family(name: str):
    """The module of a model family, families/<name>.py, named by a
    configuration file's `family`: `model_spec(cfg)`, `tensor_shapes(cfg)`
    and `logits_at(cfg, weights, rows, at, precision, flip)` (the plain
    float32 reference). One module a process: it keeps its compiled blocks."""
    return _load("model family", "families", name)

