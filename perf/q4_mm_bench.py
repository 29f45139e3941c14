#!/usr/bin/env python
"""Batched fused Q40 dequant-matmul microbench — the serving-shape evidence
for ops/pallas_q4_mm.py (decode, verify, drafter rows).

A fused dispatch should move only

    packed weights   n*(k/2) + 2*n*(k/32)     (0.5625 B/weight)
  + activations      m*k*2                    (bf16 rows)
  + output           m*n*4                    (f32 accumulator writeback)
  [+ residual        m*n*2                    (residual epilogue)]
  [+ second stream   n*(k/2) + 2*n*(k/32)     (gated silu·mul pair)]

per matmul — never a dequantized (n, k) bf16 image, which alone is 3.56x
the packed bytes. Sections time the kernels against the XLA dequant+dot
oracle at the M-row buckets the batched runtime actually dispatches
(decode M=B, verify M=B*(1+k), drafter M=B at the draft model's geometry)
and ALWAYS emit the analytic byte model, so the achieved-GB/s number can
be read against the theoretical floor. On CPU the kernels run in interpret
mode: timings are meaningless there (labeled backend="cpu"), but the byte
model and the bit-consistency section are backend-independent — the tier-1
smoke wrapper (tests/test_fused_matmul.py) asserts both without timing.

Each result prints as one JSON line (the microbench.py idiom).

Usage: python perf/q4_mm_bench.py [--section model|consistency|time] [--quick]
"""

import argparse
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from distributed_llama_tpu.quants import QK, FloatType, QTensor  # noqa: E402

# serving M-row buckets (runtime/batch_engine.py defaults): decode scans
# dispatch M=B rows, verify blocks M=B*(1+k) with k=4 drafts per row, the
# drafter free-runs M=B at its own (smaller) geometry. Shapes are the
# Llama-2-7B hot matmuls; the drafter rows use a TinyLlama-1.1B-class dim.
B, K_DRAFTS = 8, 4
TARGET_SHAPES = ((4096, 4096), (11008, 4096), (4096, 11008))
DRAFTER_SHAPES = ((2048, 2048), (5632, 2048), (2048, 5632))
BUCKETS = (
    ("decode", B, TARGET_SHAPES),
    ("verify", B * (1 + K_DRAFTS), TARGET_SHAPES),
    ("drafter", B, DRAFTER_SHAPES),
)
# small tileable shapes for the interpret-mode consistency pass (kh must
# admit a {512,256,128} K-tile: k % 256 == 0)
SMALL_SHAPES = ((8, 256, 512), (40, 512, 256), (8, 384, 256))


def fence(x):
    jax.block_until_ready(x)


def timed(fn, *args, reps=10):
    fence(fn(*args))  # compile + warm
    t0 = time.perf_counter()
    out = None
    for _ in range(reps):
        out = fn(*args)
    fence(out)
    return (time.perf_counter() - t0) / reps


def emit(**kw):
    print(json.dumps(kw))


def hbm_model(m: int, n: int, k: int, *, residual: bool = False,
              gated: bool = False) -> dict:
    """Analytic per-dispatch HBM traffic of the fused kernel family —
    every operand it reads or writes, and nothing else (the dequantized
    image never exists outside VMEM). `ratio` is total/packed: the
    fused-path acceptance bar is ratio <= 2.0 at every serving shape
    (weights dominate; a ratio blowing past 2 means the shape is
    activation-bound and the kernel is the wrong tool)."""
    packed = n * (k // 2) + 2 * n * (k // QK)  # nibbles + f16-bit scales
    weights = packed * (2 if gated else 1)
    total = weights + m * k * 2 + m * n * 4  # bf16 x rows, f32 out
    if residual:
        total += m * n * 2  # bf16 residual read folded into the epilogue
    return {"packed_bytes": weights, "total_bytes": total,
            "density": round(weights / (n * k * (2 if gated else 1)), 4),
            "ratio": round(total / weights, 3)}


def _rand_q40(n, k, seed=0):
    rng = np.random.RandomState(seed)
    return QTensor.from_float((rng.randn(n, k) * 0.05).astype(np.float32),
                              FloatType.Q40)


def _i4p(n, k, seed=0):
    return jax.tree_util.tree_map(
        jnp.asarray, _rand_q40(n, k, seed).to_i4p_layout())


def sec_model():
    """The analytic byte model at every serving bucket x op — no device
    work; this is the section the tier-1 smoke test replays."""
    for bucket, m, shapes in BUCKETS:
        for n, k in shapes:
            for op, kw in (("mm", {}), ("mm+res", {"residual": True}),
                           ("gated", {"gated": True})):
                rec = hbm_model(m, n, k, **kw)
                emit(section="model", bucket=bucket, op=op, m=m, n=n, k=k,
                     **rec)


def check_consistency(shapes=SMALL_SHAPES, seed=0) -> list[str]:
    """Interpret-mode kernels vs the XLA dequant+dot oracle on every fused
    variant: f32 closeness AND per-row argmax identity (the greedy-pick
    bar the serving identity suite holds end-to-end). Returns a list of
    failure strings — empty means consistent."""
    from distributed_llama_tpu.ops.pallas_q4_mm import (q4_gated_matmul,
                                                        q4_gated_supported,
                                                        q4_matmul,
                                                        q4_mm_supported)

    problems: list[str] = []
    for m, n, k in shapes:
        wl = _i4p(n, k, seed)
        w3 = _i4p(n, k, seed + 1)
        assert q4_mm_supported(wl, m) and q4_gated_supported(wl, w3, m), \
            (m, n, k)
        rng = np.random.RandomState(seed + 2)
        x = jnp.asarray(rng.randn(m, k) * 0.1, jnp.bfloat16)
        res = jnp.asarray(rng.randn(m, n) * 0.1, jnp.bfloat16)
        wd = np.asarray(wl.dequantize(dtype=jnp.float32))
        w3d = np.asarray(w3.dequantize(dtype=jnp.float32))
        xf = np.asarray(x, np.float32)

        def close(name, got, want):
            got = np.asarray(got, np.float32)
            if not np.allclose(got, want, atol=1e-2, rtol=5e-2):
                err = np.abs(got - want).max()
                problems.append(f"{name} m={m} n={n} k={k}: max err {err}")
            if not np.array_equal(got.argmax(-1), want.argmax(-1)):
                problems.append(f"{name} m={m} n={n} k={k}: argmax drift")

        close("mm", q4_matmul(x, wl, out_dtype=jnp.float32, interpret=True),
              xf @ wd.T)
        close("mm+res",
              q4_matmul(x, wl, out_dtype=jnp.float32, residual=res,
                        interpret=True),
              np.asarray(res, np.float32) + xf @ wd.T)
        h1, h3 = xf @ wd.T, xf @ w3d.T
        close("gated",
              q4_gated_matmul(x, wl, w3, act="silu", out_dtype=jnp.float32,
                              interpret=True),
              (h1 / (1.0 + np.exp(-h1))) * h3)
    return problems


def sec_consistency():
    problems = check_consistency()
    emit(section="consistency", shapes=len(SMALL_SHAPES), ok=not problems,
         problems=problems)


def sec_time(reps):
    """Kernel vs oracle wall time per bucket (TPU numbers are the real
    ones; CPU interpret timings are labeled and only prove liveness). On
    CPU the weight n is shrunk so interpret mode stays tractable."""
    from distributed_llama_tpu.ops.matmul import qmatmul
    from distributed_llama_tpu.ops.pallas_q4_mm import (q4_gated_matmul,
                                                        q4_gated_supported,
                                                        q4_matmul,
                                                        q4_mm_supported)

    on_tpu = jax.default_backend() == "tpu"
    for bucket, m, shapes in BUCKETS:
        for n, k in shapes:
            n_eff = n if on_tpu else min(n, 512)
            k_eff = k if on_tpu else min(k, 512)
            wl = _i4p(n_eff, k_eff)
            w3 = _i4p(n_eff, k_eff, seed=1)
            if not q4_mm_supported(wl, m):
                emit(section="time", bucket=bucket, m=m, n=n_eff, k=k_eff,
                     skipped="shape outside kernel support")
                continue
            x = jnp.ones((m, k_eff), jnp.bfloat16)
            res = jnp.ones((m, n_eff), jnp.bfloat16)
            packed = wl.data.nbytes + wl.scales.nbytes
            runs = (
                ("mm", functools.partial(q4_matmul, interpret=not on_tpu),
                 (x, wl), packed),
                ("mm+res", lambda x, wl, res: q4_matmul(
                    x, wl, residual=res, interpret=not on_tpu),
                 (x, wl, res), packed),
                ("gated", lambda x, wl, w3: q4_gated_matmul(
                    x, wl, w3, act="silu", interpret=not on_tpu),
                 (x, wl, w3), 2 * packed),
                ("xla", functools.partial(qmatmul, use_pallas=False),
                 (x, wl), packed),
            )
            for op, fn, args, weight_bytes in runs:
                if op == "gated" and not q4_gated_supported(wl, w3, m):
                    emit(section="time", bucket=bucket, op=op, m=m, n=n_eff,
                         k=k_eff, skipped="shape outside kernel support")
                    continue
                dt = timed(jax.jit(fn), *args, reps=reps)
                emit(section="time", backend=jax.default_backend(),
                     bucket=bucket, op=op, m=m, n=n_eff, k=k_eff,
                     ms=round(dt * 1e3, 3),
                     weight_gbps=round(weight_bytes / 1e9 / dt, 1))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--section", default=None,
                    choices=["model", "consistency", "time"])
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    reps = 3 if args.quick else 10
    emit(section="meta", backend=jax.default_backend(),
         device=str(jax.devices()[0]))
    if args.section in (None, "model"):
        sec_model()
    if args.section in (None, "consistency"):
        sec_consistency()
    if args.section in (None, "time"):
        sec_time(reps)


if __name__ == "__main__":
    main()
