"""The output check: what decides `correct`.

A few seeded token sequences are teacher-forced through the SAME compiled
programs the timed window uses, and every recorded position's logits are
compared by value with the plain float32 reference, which the
configuration's family brings (`families/<family>.py`: `logits_at`), as it
brings the program's `ModelSpec` for the file. The sequences' lengths are
the file's: `check.probe_prompts`, the prompt tokens of each row, one row a
slot (72 to 79 in both cells: 64 + 8 + i x 1), and `check.probe_decode`,
the tokens forced after each (16). It runs
in set-up, after the programs are warm, before the profiler starts and before
the first client; nothing from the window enters it.

How the logits are reached without editing the program: each probe is an
ordinary `BatchEngine.submit` whose sampler is `ForcedSampler`. On the
host-sampled path the scheduler hands that sampler the logits of the row's
last position and ingests whatever token it returns, so the sampler records
the logits and returns the seeded token. The probes fill every slot, so each
row's prompt goes through chunked prefill (chunks of 64, 8 and 1) into the
paged cache while rows that are already decoding ride the same dispatches,
and every later token goes through a T=1 batched step that reads that
cache. For the probe alone the engine's `superstep` attribute is set to 1:
the K-step scan samples on the device and hands no logits out, so its
forward pass is not judged here (PERF.md, Open questions).

Two passes, each described by the configuration's `check` block. SHALLOW:
small engines on `cuts` of the same seeded weights (the first and the last
layer), under a tight limit that a lower precision has to fail. FULL: the
cell's own engine at its whole depth, under a loose one that tells "agrees"
from "uncorrelated" (a wrong layer index or cache offset).

The statistic is per position: rms of the logit difference over rms of the
reference's logits. A pass reads the worst row's `quantile` over that row's
judged positions (a row is a slot; quantile 1 is the maximum over all of
them), so no slot goes unjudged. Where the engine's bf16 router and the
float32 router pick another last expert, that position's logits move by
about their own scale in a run that is correct. Such a position is taken
out at the source and not by a low quantile: the reference knows its own
router's margin at every position (`logits_at` returns it), and a position whose
margin is under the pass's `margin` is counted and printed, not judged. That
only works where a flip stays at its own position, which is why the MoE
configuration cuts the shallow pass into ONE-layer engines: behind a second
layer's attention a flip reaches every later position of its row.
"""

from __future__ import annotations

import math

import numpy as np

from . import cells, weights as W


class ForcedSampler:
    """Records the logits it is shown and returns the seeded token."""

    temperature = 0.0  # read by the scheduler when it builds a device step
    topp = 0.9
    state = 0

    def __init__(self, forced):
        self.forced = [int(t) for t in forced]
        self.seen: list[np.ndarray] = []

    def sample(self, logits) -> int:
        self.seen.append(np.array(logits, np.float32).reshape(-1))
        return self.forced[len(self.seen) - 1]


def build_engine(cfg: dict, weights: dict, **overrides):
    """`BatchEngine` on `weights` with the configuration's engine settings."""
    from distributed_llama_tpu.runtime.batch_engine import BatchEngine

    eng = {k: v for k, v in cfg["engine"].items() if not k.startswith("_")}
    eng.update(overrides)
    kw = dict(slots=eng["slots"], superstep=eng["superstep"],
              pipeline=eng["pipeline"], paged_kv=eng["paged_kv"],
              kv_block_tokens=eng["kv_block_tokens"],
              kv_pool_blocks=eng["kv_pool_blocks"],
              prefix_cache=eng["prefix_cache"], tp=eng.get("tp", 1))
    spec = cells.load_family(cfg["family"]).model_spec(
        {**cfg, "num_hidden_layers": W.depth(weights, cfg)})
    return BatchEngine(spec, W.to_program_params(weights, cfg), None, **kw)


def probe_tokens(cfg: dict, seed: int):
    """Per row: (prompt, forced continuation), seeded and distinct, of the
    lengths the configuration's `check` block states; one row a slot."""
    prompts, decode = cfg["check"]["probe_prompts"], cfg["check"]["probe_decode"]
    if len(prompts) != cfg["engine"]["slots"]:
        raise ValueError(f"check.probe_prompts has {len(prompts)} rows for "
                         f"{cfg['engine']['slots']} slots")
    rng = np.random.default_rng([seed, 0xC4EC])
    out = []
    for n in prompts:
        toks = rng.integers(3, cfg["vocab_size"], size=n + decode)
        out.append((toks[:n].tolist(), toks[n:].tolist()))
    return out


def drive(be, probes, timeout: float = 600.0):
    """Every probe through `be` at once (one per slot); per row the logits
    the sampler was shown, (len(forced), vocab). The k-th row of that is
    the logits at position len(prompt) - 1 + k of prompt + forced."""
    saved = be.superstep
    be.superstep = 1  # host-sampled T=1 steps: see the module's docstring
    try:
        samplers = [ForcedSampler(forced) for _, forced in probes]
        reqs = [be.submit(prompt, len(forced), s)
                for (prompt, forced), s in zip(probes, samplers)]
        for r in reqs:
            r.wait(timeout)
    finally:
        be.superstep = saved
    for r, (_, forced) in zip(reqs, probes):
        if r.out != [int(t) for t in forced]:
            raise RuntimeError("probe: the engine did not ingest the forced "
                               f"tokens (finish {r.finish!r})")
    return [np.stack(s.seen) for s in samplers]


def position_errors(got: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per position: rms of the difference over rms of the reference."""
    num = np.sqrt(np.mean((got - ref) ** 2, axis=-1))
    den = np.sqrt(np.mean(ref ** 2, axis=-1))
    return num / np.maximum(den, 1e-30)


def reference_rows(cfg: dict, weights: dict, probes, precision="float32",
                   flip=None):
    """The reference at the positions `drive` records, in `drive`'s order:
    logits (recorded positions, vocab) and each position's smallest router
    margin over the layers of `weights` (recorded positions,). Each row goes
    to the family at its own length, and only those positions are asked for."""
    rows = [p + f[:-1] for p, f in probes]
    at = [range(len(p) - 1, len(p) - 1 + len(f)) for p, f in probes]
    return cells.load_family(cfg["family"]).logits_at(
        cfg, weights, rows, at, precision, flip)


def free_engine(be) -> None:
    """Stop an engine and give its device memory back now: the program keeps
    references to a closed engine (a gauge's callback among them), so waiting
    for the collector is not enough where two models do not fit together."""
    import gc

    import jax

    be.close()
    held = [vars(be)] + [vars(v) for v in vars(be).values()
                         if type(v).__name__ == "Engine"]
    for leaf in jax.tree_util.tree_leaves(held):
        if isinstance(leaf, jax.Array) and not leaf.is_deleted():
            leaf.delete()
    gc.collect()


def engine_logits(cfg: dict, full_engine=None):
    """What the program gives for one cut of the weights: the cell's own
    engine for the whole depth (cut None), else a small engine on the cut's
    layers, built here and freed."""
    def got(cut, w, probes):
        if cut is None:
            return np.concatenate(drive(full_engine, probes))
        be = build_engine(cfg, w)
        try:
            return np.concatenate(drive(be, probes))
        finally:
            free_engine(be)
    return got


def pass_errors(cfg: dict, weights: dict, probes, spec: dict, got_of,
                refs: dict | None = None) -> dict:
    """One pass, before its verdict: every recorded position's error, row and
    router margin, the pass's cuts one after another. `got_of(cut, w,
    probes)` stands in the program's place (`engine_logits`, or a control).
    `refs` keeps the reference of a cut for a second arm on the same seed."""
    refs = {} if refs is None else refs
    err, row, gap = [], [], []
    for cut in spec.get("cuts") or [None]:
        w = weights if cut is None else W.layer_cut(weights, cut, cfg)
        key = None if cut is None else tuple(cut)
        if key not in refs:
            refs[key] = reference_rows(cfg, w, probes)
        ref, gaps = refs[key]
        err.append(position_errors(got_of(cut, w, probes), ref))
        row.append(np.repeat(np.arange(len(probes)),
                             [len(f) for _, f in probes]))
        gap.append(gaps)
    return {"err": np.concatenate(err), "row": np.concatenate(row),
            "gap": np.concatenate(gap)}


def judge(pe: dict, spec: dict) -> dict:
    """The verdict of one pass: the worst row's `quantile` of the errors at
    its judged positions against `tol`."""
    err = np.asarray(pe["err"], np.float64)
    judged = np.asarray(pe["gap"]) >= (spec.get("margin") or 0.0)
    finite = bool(np.isfinite(err).all())
    rows = []
    for r in np.unique(pe["row"]):
        e = err[(pe["row"] == r) & judged]
        if e.size:
            rows.append((float(np.quantile(e, spec["quantile"]))
                         if finite else math.inf, int(r)))
    stat, worst_row = max(rows) if rows else (math.inf, -1)

    def q(x):
        return float(np.quantile(err, x)) if finite else math.inf

    return {"stat": stat, "worst_row": worst_row, "tol": spec["tol"],
            "quantile": spec["quantile"], "positions": int(err.size),
            "judged": int(judged.sum()), "rows_judged": len(rows),
            "over_tol": int(np.sum(~(err <= spec["tol"]))),
            "over_tol_judged": int(np.sum(~(err[judged] <= spec["tol"]))),
            "p50": q(0.5), "p90": q(0.9), "max": q(1.0),
            "within": (finite and stat <= spec["tol"]
                       and len(rows) == len(np.unique(pe["row"])))}


def check(cfg: dict, weights: dict, seed: int, full_engine, log=print,
          got_of=None) -> dict:
    """Both passes for `weights`. Prints each number compared beside its
    limit; returns the verdicts and `correct`."""
    got_of = got_of or engine_logits(cfg, full_engine)
    probes = probe_tokens(cfg, seed)
    out = {"correct": True}
    for name in ("shallow", "full"):
        spec = cfg["check"][name]
        res = judge(pass_errors(cfg, weights, probes, spec, got_of), spec)
        out[name] = res
        out["correct"] = out["correct"] and res["within"]
        unjudged = (f" (router margin under {spec['margin']:g}: counted, not "
                    "judged)" if spec.get("margin") else "")
        log(f"check {name}: worst row's q{res['quantile']:g} of the "
            f"per-position rms error {res['stat']:.5f} (row "
            f"{res['worst_row']}) against limit {res['tol']:g}; "
            f"{res['judged']} of {res['positions']} positions judged in "
            f"{res['rows_judged']} rows{unjudged}, {res['over_tol_judged']} "
            f"of them over the limit, {res['over_tol']} of all; all "
            f"positions p50 {res['p50']:.5f} p90 {res['p90']:.5f} max "
            f"{res['max']:.5f}")
    return out
