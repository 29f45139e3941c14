"""Logits parity between two ways of running one checkpoint on this device set.

    python -m distributed_llama_tpu.apps.parity --model m.m [--steps 16] [--tp N]

Loads the checkpoint once, builds two engines ONE AFTER THE OTHER (the first is
dropped before the second is placed, so one chip holds one model at a time),
teacher-forces the same seeded tokens one at a time through each engine's T=1
decode step, and compares the logits by value: random weights give flat logits,
so an argmax would compare noise.

    default   kernels on (a TPU's default policy) against use_pallas=False: the
              Q40 weights stay quantized, XLA dequantizes. This is the Pallas
              matvec and fused decode attention against plain XLA.
    --tp N    tp=1 on one device against tp=N over N devices, both at the default
              policy; also reports the sharded step's collective counts and what
              each device holds.

Prints one JSON line; exit code 1 when the logits disagree beyond the tolerance
(or, with --tp, the weights are not spread). chip_smoke.py runs it as its
`parity` phase; it is also the quickest by-hand check after touching a kernel.
"""

from __future__ import annotations

import argparse
import gc
import json
import statistics
import sys
import time

import numpy as np

# max |Δlogit| over max |logit| per step. What this can see is bounded by the
# noise floor of the arithmetic, not by the kernels: two CORRECT bf16 runs of
# one random-weight network that differ only in summation order (tp=1 against
# tp=4, same kernels, same Q80 quantization points) already sit 0.13 apart at
# Llama-3-8B on a v5e chip, and kernels against XLA dequant read the same
# 0.13 (rms 0.11 and 0.10; in f32 on the CPU the tp pair agrees to 6e-6).
# A wrong kernel or a mis-sharded weight gives uncorrelated logits, an error
# of order one, and that is what the tolerance separates. PERF.md, Findings.
TOLERANCE = 0.25


def _teacher_force(engine, tokens) -> tuple[np.ndarray, list[float]]:
    logits, ms = [], []
    for t in tokens:
        t0 = time.perf_counter()
        logits.append(np.asarray(engine.infer_chunk([int(t)]), np.float32))
        ms.append((time.perf_counter() - t0) * 1e3)
    return np.stack(logits), ms


def _fence_check(engine, token: int, n: int = 8) -> dict:
    """Is block_until_ready() a fence on this backend? Times n decode steps to
    block_until_ready(), then the logits' host copy on its own. If the wait
    returned before the device was done, the copy would absorb the step."""
    import jax.numpy as jnp

    wait_ms, copy_ms = [], []
    toks = jnp.full((engine.batch, 1), token, jnp.int32)
    for _ in range(n):
        step = engine._step_for(engine._window_for(engine.pos + 1))
        t0 = time.perf_counter()
        logits, engine.k_cache, engine.v_cache = step(
            engine.params, engine.rope, toks, engine.k_cache, engine.v_cache,
            engine._pos_arg(engine.pos))
        logits.block_until_ready()
        t1 = time.perf_counter()
        np.asarray(logits)
        t2 = time.perf_counter()
        engine.pos += 1
        wait_ms.append((t1 - t0) * 1e3)
        copy_ms.append((t2 - t1) * 1e3)
    wait, copy = statistics.median(wait_ms), statistics.median(copy_ms)
    return {"block_until_ready_ms": round(wait, 3),
            "host_copy_after_ms": round(copy, 3),
            "is_fence": copy < 0.5 * wait}


def _device_holdings(engine) -> list[dict]:
    """Per device: bytes of quantized weights its shards hold (from the arrays'
    own shard metadata) and what the backend says is in use."""
    import jax

    from ..quants import QTensor

    held = {d: 0 for d in engine.mesh.devices.flat}
    tensors = list(engine.params["blocks"].values()) + [engine.params["wcls"]]
    for t in tensors:
        if not isinstance(t, QTensor):
            continue
        for leaf in jax.tree_util.tree_leaves(t):
            for shard in leaf.addressable_shards:
                held[shard.device] += shard.data.nbytes
    out = []
    for d, nbytes in held.items():
        stats = d.memory_stats() or {}
        out.append({"id": d.id, "weight_bytes": nbytes,
                    "bytes_in_use": stats.get("bytes_in_use")})
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--model", required=True)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-seq-len", type=int, default=0)
    ap.add_argument("--tp", type=int, default=1,
                    help="compare tp=1 on one device against tp=N")
    args = ap.parse_args(argv)

    from ..platform_env import start

    device = start()
    import jax

    from .. import native
    from ..formats.mfile import load_model
    from ..ops.matmul import kernel_selections
    from ..runtime.engine import Engine

    t0 = time.perf_counter()
    spec, params = load_model(args.model, args.max_seq_len)
    load_s = time.perf_counter() - t0
    tokens = np.random.default_rng(args.seed).integers(
        3, spec.vocab_size, size=args.steps)

    if args.tp > 1:
        arms = [("tp1", dict(tp=1)), (f"tp{args.tp}", dict(tp=args.tp))]
    else:
        # use_pallas=True is the TPU default spelled out: off the chip it
        # needs the interpret request instead of quietly comparing XLA to XLA
        arms = [("kernels", dict(tp=1, use_pallas=True)),
                ("xla", dict(tp=1, use_pallas=False))]
    out: dict = {"phase": "parity", "device": device, "steps": args.steps,
                 "tolerance": TOLERANCE, "load_s": round(load_s, 1),
                 "loader": "native" if native.available() else "numpy",
                 "arms": {}}
    results = {}
    for name, kw in arms:
        t0 = time.perf_counter()
        engine = Engine(spec, params, **kw)
        build_s = time.perf_counter() - t0
        logits, ms = _teacher_force(engine, tokens)
        results[name] = logits
        arm = {"tp": engine.tp, "kernels": bool(engine.use_pallas),
               "dtype": np.dtype(engine.dtype).name,
               "build_s": round(build_s, 1),
               "first_step_ms": round(ms[0], 1),
               "step_ms_median": round(statistics.median(ms[1:] or ms), 3),
               "finite": bool(np.isfinite(logits).all()),
               "fence": _fence_check(engine, int(tokens[-1]))}
        if engine.tp > 1:
            traffic = engine.collective_stats()
            arm["collectives_per_step"] = dict(traffic.counts)
            arm["devices"] = _device_holdings(engine)
            share = [d["weight_bytes"] for d in arm["devices"]]
            arm["weights_spread"] = (
                len(share) == engine.tp
                and max(share) <= 1.05 * sum(share) / engine.tp)
        out["arms"][name] = arm
        # one model on the chip at a time: drop every reference the engine
        # and its compiled programs hold before the next arm places its own
        del engine
        jax.clear_caches()
        gc.collect()
    out["kernel_selections"] = kernel_selections()

    (_, a), (_, b) = results.items()
    scale = np.abs(b).max(axis=1)
    rel = np.abs(a - b).max(axis=1) / np.maximum(scale, 1e-9)
    out["max_rel_err"] = float(rel.max())
    out["rms_rel_err"] = float(np.sqrt(np.mean((a - b) ** 2))
                               / max(np.sqrt(np.mean(b ** 2)), 1e-9))
    out["logit_abs_max"] = float(scale.max())
    arms_ok = all(arm["finite"] and arm["fence"]["is_fence"]
                  and arm.get("weights_spread", True)
                  for arm in out["arms"].values())
    out["ok"] = bool(arms_ok and out["max_rel_err"] <= TOLERANCE)
    print(json.dumps(out), flush=True)
    sys.exit(0 if out["ok"] else 1)


if __name__ == "__main__":
    main()
