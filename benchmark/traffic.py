"""The one general traffic generator: a pure function of a traffic file and
a seed.

A traffic file (`traffic/<name>.json`) carries the loop kind (`closed` |
`open`), the clients or the rate, the length distributions and the sharing.
`open` is in the schema and not implemented yet (PERF.md, Open questions).

Every seed gets the SAME work: the lengths are the `clients x cycle`
stratified quantiles of the file's distributions; prompt and output lengths
are paired, and the pairs dealt into one cycle per client, by one fixed
shuffle, so no seed draws an easier or a harder mix, or another order of
sizes. The seed draws every token id (and, in `weights.py`, the model): with
greedy replies of fixed length it changes no size and no timing, so runs on
different seeds are repeats of one workload (PERF.md, section 4). A client repeats its cycle with fresh token ids each time, so
nothing is shared between requests (sharing: none). `think_s` is the pause
between a reply's end and the client's next request: real callers have one,
and without it whether the next request is admitted before or after the
scheduler's next dispatch is a race of microseconds that sends two runs of
one seed down different schedules (PERF.md, PR 25).
"""

from __future__ import annotations

import json
import math
import os
from dataclasses import dataclass

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        t = json.load(f)
    if t["loop"] not in ("closed", "open"):
        raise ValueError(f"traffic {name}: loop {t['loop']!r}")
    if t["loop"] == "open":
        raise NotImplementedError(
            f"traffic {name}: the open loop is in the schema and not built yet")
    if t.get("sharing", "none") != "none":
        raise NotImplementedError(f"traffic {name}: sharing {t['sharing']!r}")
    return t


def _quantiles(dist: dict, n: int) -> list[int]:
    """n stratified lengths of a distribution, in increasing order."""
    if dist["dist"] != "loguniform":
        raise ValueError(f"unknown distribution {dist['dist']!r}")
    lo, hi = math.log(dist["min"]), math.log(dist["max"])
    return [int(round(math.exp(lo + (hi - lo) * (i + 0.5) / n)))
            for i in range(n)]


def max_position(t: dict) -> int:
    """The last cache position any request of this traffic can reach."""
    return t["prompt_tokens"]["max"] + t["output_tokens"]["max"]


@dataclass(frozen=True)
class Request:
    client: int
    index: int  # within its client, 0 is the warm-up request
    prompt: tuple[int, ...]
    max_tokens: int


class ClientPlan:
    """One client's endless sequence of requests."""

    def __init__(self, client: int, pairs, warmup, vocab: int, seed: int):
        self.client, self.pairs, self.vocab = client, pairs, vocab
        self.warmup = warmup
        self._rng = np.random.default_rng([seed, 0x7AFF1C, client])
        self._i = 0

    def next(self) -> Request:
        # request 0 is the warm-up, not timed: the shortest lengths of the
        # file, so that set-up stays short; the cycle starts at request 1
        n_prompt, n_out = (self.warmup if self._i == 0 else
                           self.pairs[(self._i - 1) % len(self.pairs)])
        toks = self._rng.integers(3, self.vocab, size=n_prompt)
        req = Request(self.client, self._i, tuple(int(x) for x in toks), n_out)
        self._i += 1
        return req


def plan(t: dict, vocab: int, seed: int) -> list[ClientPlan]:
    n = t["clients"] * t["cycle"]
    fixed = np.random.default_rng([0, 0x7AFF1C])
    prompts = np.asarray(_quantiles(t["prompt_tokens"], n))[fixed.permutation(n)]
    outs = np.asarray(_quantiles(t["output_tokens"], n))[fixed.permutation(n)]
    pairs = [(int(p), int(o)) for p, o in zip(prompts, outs)]
    c = t["cycle"]
    warmup = (t["prompt_tokens"]["min"], t["output_tokens"]["min"])
    return [ClientPlan(i, pairs[i * c:(i + 1) * c], warmup, vocab, seed)
            for i in range(t["clients"])]
