"""Logits parity between two ways of running one checkpoint on this device set.

    python -m distributed_llama_tpu.apps.parity --model m.m [--steps 16]
                                                [--tp N]

Loads the checkpoint once and builds engines ONE AFTER THE OTHER (each is
dropped before the next is placed, so one chip holds one model at a time). Every
engine is driven the same way: the same seeded tokens teacher-forced one at a
time through the T=1 decode step, then one 64-token prefill chunk, and the
logits of every position are compared by value: random weights give flat logits,
so an argmax would compare noise.

    default        kernels on (a TPU's default policy) against use_pallas=False:
                   the Q40 weights stay quantized, XLA dequantizes. This is the
                   Pallas matvec and fused decode attention (the T=1 steps) and
                   the fused dequant-matmul (the chunk) against plain XLA.
    --tp N         tp=1 on one device against tp=N over N devices, both at the
                   default policy; also reports the sharded step's collective
                   counts and what each device holds.

Each pair is compared twice, because one depth cannot do both jobs:

    shallow  the checkpoint cut to its first and last layer. Two layers leave
             rounding little room to grow, so the bounds are tight: this is the
             pass that sees a mis-scaled matrix, a dropped shard slice or a
             lower-precision path. A CANARY proves it on every run: the second
             arm again, with one matrix's scales off by an eighth, has to FAIL
             these bounds on this device at this width.
    full     the whole depth. bf16 rounding grows through 32 random-weight
             layers until two CORRECT runs sit a tenth of the logits' scale
             apart, so these bounds only separate "agrees" from "uncorrelated":
             a wrong layer index or cache offset, which the shallow pass
             cannot contain.

Prints one JSON line; exit code 1 when a pass is out of bounds, the canary went
unseen, an arm failed its own checks or (--tp) the weights are not spread.
chip_smoke.py runs it as its `parity` phase; it is also the quickest by-hand
check after touching a kernel.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import statistics
import sys
import time

import numpy as np

# Per pass: (max over positions of max|Δlogit| / max|logit|, rms Δ / rms logit).
# What a pass can see is bounded by the noise floor of the arithmetic, not by
# the kernels. Floors measured on v5e at Llama-3-8B widths (PERF.md, Findings,
# PR 21), and what a wrong result reads:
#   shallow  kernels against XLA dequant sit 0.034 (rms 0.021) apart: bf16
#            rounding plus the Q80 activations only the kernels quantize;
#            tp=1 sits 0.027 (rms 0.015) from tp=4. An eighth off on
#            one matrix of one layer reads 0.07 (rms 0.05) for wo or wv, the
#            weakest, up to 0.12 for wq, wk or w1 of the first layer; only wq
#            and wk of the LAST layer stay under the floor (0.034, rms 0.024
#            in f32 on the CPU). The bounds sit between floor and canary.
#   full     two correct bf16 runs that differ only in summation order (tp=1
#            against tp=4, same kernels, same Q80 quantization points) sit 0.13
#            (rms 0.08) apart, kernels against XLA dequant 0.13 (rms 0.09); in
#            f32 on the CPU the tp pair agrees to 6e-6. Uncorrelated logits
#            read about 1 (rms 1.4).
BOUNDS = {"shallow": (0.05, 0.035), "full": (0.25, 0.2)}
CHUNK = 64  # the largest prefill bucket (runtime/engine.py PREFILL_CHUNKS)
CANARY = ("wo", 1.125)  # matrix, and the factor on its first layer's scales


def shallow_cut(spec, params):
    """The same checkpoint with only its first and last layer."""
    import jax

    keep = np.array([0, spec.n_layers - 1])
    return (dataclasses.replace(spec, n_layers=2),
            {**params, "blocks": jax.tree.map(lambda a: a[keep],
                                              params["blocks"])})


def mis_scaled(params, name: str, factor: float):
    """`params` with the block scales of matrix `name` in layer 0 off by
    `factor`: what a kernel that decodes its scales wrongly would compute."""
    t = params["blocks"][name]
    scales = t.scales.copy()
    scales[0] = (scales[0].astype(np.float32) * factor).astype(scales.dtype)
    return {**params, "blocks": {**params["blocks"],
                                 name: dataclasses.replace(t, scales=scales)}}


def drive(engine, tokens, chunk) -> tuple[np.ndarray, list[float]]:
    """Every position's logits, (len(tokens) + len(chunk), vocab), and the
    T=1 steps' wall times."""
    rows, ms = [], []
    for t in tokens:
        t0 = time.perf_counter()
        rows.append(engine.infer_chunk([int(t)]))
        ms.append((time.perf_counter() - t0) * 1e3)
    rows.extend(engine.infer_chunk_logits(chunk))
    return np.asarray(rows, np.float32), ms


def compare(a: np.ndarray, b: np.ndarray, bounds) -> dict:
    scale = np.abs(b).max(axis=1)
    max_rel = float((np.abs(a - b).max(axis=1) / np.maximum(scale, 1e-9)).max())
    rms_rel = float(np.sqrt(np.mean((a - b) ** 2))
                    / max(np.sqrt(np.mean(b ** 2)), 1e-9))
    return {"max_rel_err": max_rel, "rms_rel_err": rms_rel,
            "logit_abs_max": float(scale.max()),
            "within": max_rel <= bounds[0] and rms_rel <= bounds[1]}


def _fence_check(engine, token: int, n: int = 8) -> dict:
    """Is block_until_ready() a fence on this backend? Times n decode steps to
    block_until_ready(), then the logits' host copy on its own. If the wait
    returned before the device was done, the copy would absorb the step."""
    wait_ms, copy_ms = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        logits = engine.dispatch(np.array([token], np.int32))
        logits.block_until_ready()
        t1 = time.perf_counter()
        np.asarray(logits)
        t2 = time.perf_counter()
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


def _run_arm(spec, params, kw, tokens, chunk, full: bool):
    """Build one engine, drive it, describe it, and drop it again."""
    import jax

    from ..runtime.engine import Engine

    t0 = time.perf_counter()
    engine = Engine(spec, params, **kw)
    build_s = time.perf_counter() - t0
    logits, ms = drive(engine, tokens, chunk)
    arm = {"tp": engine.tp, "kernels": str(engine.use_pallas),
           "dtype": np.dtype(engine.dtype).name, "build_s": round(build_s, 1),
           "first_step_ms": round(ms[0], 1),
           "step_ms_median": round(statistics.median(ms[1:] or ms), 3),
           "finite": bool(np.isfinite(logits).all())}
    if full:
        arm["fence"] = _fence_check(engine, int(tokens[-1]))
        if engine.tp > 1:
            arm["collectives_per_step"] = dict(engine.collective_stats().counts)
            arm["devices"] = _device_holdings(engine)
            share = [d["weight_bytes"] for d in arm["devices"]]
            arm["weights_spread"] = (
                len(share) == engine.tp
                and max(share) <= 1.05 * sum(share) / engine.tp)
    # one model on the chip at a time: drop every reference the engine and
    # its compiled programs hold before the next arm places its own
    del engine
    jax.clear_caches()
    gc.collect()
    return logits, arm


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
    from .. import native
    from ..formats.mfile import load_model
    from ..ops.matmul import kernel_selections

    t0 = time.perf_counter()
    spec, params = load_model(args.model, args.max_seq_len)
    load_s = time.perf_counter() - t0
    rng = np.random.default_rng(args.seed)
    tokens = rng.integers(3, spec.vocab_size, size=args.steps)
    chunk = rng.integers(3, spec.vocab_size, size=CHUNK)

    # use_pallas=True is the TPU default spelled out: off the chip it needs
    # the interpret request instead of quietly comparing XLA to XLA
    if args.tp > 1:
        arms = [("tp1", dict(tp=1)), (f"tp{args.tp}", dict(tp=args.tp))]
    else:
        arms = [("kernels", dict(tp=1, use_pallas=True)),
                ("xla", dict(tp=1, use_pallas=False))]
    out: dict = {"phase": "parity", "device": device, "steps": args.steps,
                 "chunk": CHUNK, "load_s": round(load_s, 1),
                 "loader": "native" if native.available() else "numpy",
                 "passes": {}}
    cut = shallow_cut(spec, params)
    ok = True
    for name, (pspec, pparams) in (("shallow", cut), ("full", (spec, params))):
        results = {}
        res = out["passes"][name] = {"bounds": BOUNDS[name], "arms": {}}
        for arm_name, kw in arms:
            results[arm_name], res["arms"][arm_name] = _run_arm(
                pspec, pparams, kw, tokens, chunk, full=name == "full")
        a, b = results.values()
        res.update(compare(a, b, BOUNDS[name]))
        ok = ok and res["within"] and all(
            arm["finite"] and arm.get("weights_spread", True)
            and arm.get("fence", {}).get("is_fence", True)
            for arm in res["arms"].values())
        if name == "shallow":
            # the bounds have to be able to fail: the second arm again on a
            # deliberately wrong matrix, against the clean first arm
            wrong, _ = _run_arm(pspec, mis_scaled(pparams, *CANARY),
                                arms[1][1], tokens, chunk, full=False)
            seen = compare(a, wrong, BOUNDS[name])
            res["canary"] = {"matrix": CANARY[0], "factor": CANARY[1],
                             "max_rel_err": seen["max_rel_err"],
                             "rms_rel_err": seen["rms_rel_err"],
                             "caught": not seen["within"]}
            ok = ok and res["canary"]["caught"]
    out["kernel_selections"] = kernel_selections()
    out["ok"] = bool(ok)
    print(json.dumps(out), flush=True)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
