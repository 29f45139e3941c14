#!/usr/bin/env python
"""The fused Q40 dequant-matmul alone (ops/pallas_q4_mm.py), beside XLA.

A call should move only

    packed weights   n*(k/2) + 2*n*(k/32)     (0.5625 B/weight)
  + activations      m*k*2                    (bf16 rows, once a call)
  + output           m*n*2                    (bf16)

never a dequantized (n, k) bf16 image, which alone is 3.56x the packed bytes.

`--cells` (the chip): every matmul shape of the benchmark's three
configurations at M = 8, 64 and 512 rows (a decode step or T = 1 dispatch, a
verify block, the rectangle a 64-token chunk was until PR 41) and at the 16
and 72 compact rows an 8-token and a 64-token chunk computes since
(`models/forward.compact_rows`; 80 beside 72: whole bf16 tiles), the kernel and the XLA
dequantize-then-dot oracle (`qmatmul(use_pallas=False)`), four calls on four
weights chained inside one jit so that a call's launch does not hide its
time: ms a call, beside the bytes' time at 819 GB/s and the FLOP's at 197
TFLOP/s (Google Cloud, "TPU v5e"), and ps a weight. It uses only `q4_matmul`
and `qmatmul`, so a copy of this file in another checkout times that
checkout's kernel.

`--section unit` (the chip; ROADMAP S10's first reading): what the MATRIX UNIT
alone asks of the same matrices at the same rows, a bf16 weight block already
in VMEM and no decode: ps a weight beside the 1.30 that four units taking 128
operand values a cycle at 1.5 GHz would read.

`--section model|consistency` run anywhere: the byte model, and the kernel
under the interpreter against the oracle (tests/test_fused_matmul.py replays
both without timing).

Each result prints as one JSON line (the microbench.py idiom).
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
from jax.experimental import pallas as pl

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from distributed_llama_tpu.quants import (QK, FloatType, QTensor,  # noqa: E402
                                          to_scale_plane)

HBM_BYTES_S, BF16_FLOP_S = 819e9, 197e12  # one v5e chip
ROWS = (8, 16, 64, 72, 80, 512)
# (configuration, matrix, out rows, in columns)
CELL_SHAPES = (
    ("mistral-7b", "wqkv", 6144, 4096),
    ("mistral-7b", "wo", 4096, 4096),
    ("mistral-7b", "w13 (Mixtral's expert [up|gate] slice)", 28672, 4096),
    ("mistral-7b", "w2 (Mixtral's expert down slice)", 4096, 14336),
    ("mistral-7b", "head", 32000, 4096),
    ("smallthinker-21b-a3b", "wqkv", 4608, 2560),
    ("smallthinker-21b-a3b", "wo", 2560, 3584),
    ("smallthinker-21b-a3b", "head", 151936, 2560),
)
# small shapes for the interpret-mode consistency pass: one block; a ragged
# second row block and two K chunks; rows no tile divides
SMALL_SHAPES = ((8, 256, 512), (40, 640, 2048), (8, 384, 256))
CHAIN = 4  # calls inside one jit


def emit(**kw):
    print(json.dumps(kw), flush=True)


def hbm_model(m: int, n: int, k: int) -> dict:
    """The bytes a call has to move: every operand once, and nothing else.
    `ratio` is total over packed weights: near 1 at 8 and 64 rows, up to 2.1
    where a 512-row chunk meets a small matrix (its rows and outputs then
    weigh as much as the weights)."""
    packed = n * (k // 2) + 2 * n * (k // QK)  # nibbles + f16-bit scales
    total = packed + m * k * 2 + m * n * 2
    return {"packed_bytes": packed, "total_bytes": total,
            "density": round(packed / (n * k), 4),
            "ratio": round(total / packed, 3)}


def _i4p(n, k, seed=0):
    rng = np.random.RandomState(seed)
    w = QTensor.from_float((rng.randn(n, k) * 0.05).astype(np.float32),
                           FloatType.Q40)
    return jax.tree_util.tree_map(jnp.asarray, w.to_i4p_layout())


def sec_model():
    for cfg, name, n, k in CELL_SHAPES:
        for m in ROWS:
            emit(section="model", config=cfg, matrix=name, m=m, n=n, k=k,
                 **hbm_model(m, n, k))


def check_consistency(shapes=SMALL_SHAPES, seed=0) -> list[str]:
    """The kernel under the interpreter against the XLA oracle over the same
    bf16 activations: float32 closeness (the decoded weights are the
    oracle's bit for bit, so only the order of the sums differs) AND the
    same argmax in every row. Returns the failures; empty means consistent."""
    from distributed_llama_tpu.ops.matmul import qmatmul
    from distributed_llama_tpu.ops.pallas_q4_mm import (q4_matmul,
                                                        q4_mm_supported)

    problems: list[str] = []
    for m, n, k in shapes:
        w = _i4p(n, k, seed)
        assert q4_mm_supported(w, m), (m, n, k)
        x = jnp.asarray(np.random.RandomState(seed + 2).randn(m, k) * 0.1,
                        jnp.bfloat16)
        got = np.asarray(q4_matmul(x, w, out_dtype=jnp.float32,
                                   interpret=True))
        want = np.asarray(qmatmul(x, w, use_pallas=False,
                                  out_dtype=jnp.float32))
        if not np.allclose(got, want, atol=1e-4, rtol=1e-4):
            problems.append(f"m={m} n={n} k={k}: max err "
                            f"{np.abs(got - want).max()}")
        if not np.array_equal(got.argmax(-1), want.argmax(-1)):
            problems.append(f"m={m} n={n} k={k}: argmax drift")
    return problems


def sec_consistency():
    problems = check_consistency()
    emit(section="consistency", shapes=len(SMALL_SHAPES), ok=not problems,
         problems=problems)


def _drawn(n, k, seed):
    """A Q40 weight drawn on the device: uniform nibbles, f16 scales."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    data = jax.random.bits(k1, (n, k // 2), jnp.uint8)
    scales = jax.random.uniform(k2, (n, k // QK), jnp.float32, 0.005,
                                0.02).astype(jnp.float16)
    return data, to_scale_plane(
        jax.lax.bitcast_convert_type(scales, jnp.int16))


def _chained(call, m, n):
    """CHAIN calls in one program, each on its own weight and on rows that
    depend on the call before, so none is elided or overlapped. The weights
    are ARGUMENTS: closed over, XLA folds their dequantization."""
    def f(x, *flat):
        acc = jnp.zeros((m, n), jnp.float32)
        for i in range(CHAIN):
            xi = x + (acc[:, :1] * 1e-9).astype(x.dtype)
            w = QTensor(FloatType.Q40, flat[2 * i], flat[2 * i + 1],
                        layout="i4p")
            acc = acc + call(xi, w).astype(jnp.float32)
        return acc
    return jax.jit(f)


def _ms_a_call(fn, *args, reps=5, calls=CHAIN):
    """ms a call of a program that chains `calls` of them."""
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps / calls * 1e3


def sec_cells():
    from distributed_llama_tpu.ops.matmul import qmatmul
    from distributed_llama_tpu.ops.pallas_q4_mm import (q4_matmul,
                                                        q4_mm_supported)

    if jax.default_backend() != "tpu":
        sys.exit("--cells times the chip's kernel: no TPU here "
                 f"({jax.default_backend()})")
    for cfg, name, n, k in CELL_SHAPES:
        flat = [a for i in range(CHAIN) for a in _drawn(n, k, i)]
        for m in ROWS:
            x = jax.random.normal(jax.random.PRNGKey(9), (m, k), jnp.bfloat16)
            model = hbm_model(m, n, k)
            rec = dict(section="cells", config=cfg, matrix=name, m=m, n=n, k=k,
                       bytes_ms=round(model["total_bytes"] / HBM_BYTES_S * 1e3, 4),
                       flop_ms=round(2 * m * n * k / BF16_FLOP_S * 1e3, 4))
            xla = _ms_a_call(_chained(
                lambda x, w: qmatmul(x, w, use_pallas=False), m, n), x, *flat)
            rec.update(xla_ms=round(xla, 4),
                       xla_ps_weight=round(xla * 1e9 / (n * k), 2))
            w0 = QTensor(FloatType.Q40, flat[0], flat[1], layout="i4p")
            if q4_mm_supported(w0, m):
                ms = _ms_a_call(_chained(q4_matmul, m, n), x, *flat)
                rec.update(kernel_ms=round(ms, 4),
                           kernel_ps_weight=round(ms * 1e9 / (n * k), 2))
            else:
                rec.update(kernel="declined by q4_mm_supported")
            emit(**rec)


UNIT_BLOCK = (512, 2048)  # the resident bf16 weight block: 2 MB of VMEM


def _unit_kernel(x_ref, w_ref, o_ref):
    @pl.when(pl.program_id(0) == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("steps", "interpret"))
def unit_passes(x, w, *, steps, interpret=False):
    """`steps` products of the m rows of x with ONE (bn, kc) bf16 weight
    block, rows, block and the float32 accumulator resident in VMEM (their
    block indices never change, so each is copied once): what is timed is
    the matrix unit taking bn x kc weight values a step, nothing else."""
    m, (bn, kc) = x.shape[0], w.shape
    return pl.pallas_call(
        _unit_kernel, grid=(steps,),
        in_specs=[pl.BlockSpec((m, kc), lambda i: (0, 0)),
                  pl.BlockSpec((bn, kc), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((m, bn), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((m, bn), jnp.float32),
        interpret=interpret, name="mxu_unit_passes")(x, w)


def sec_unit(rows=(8, 16, 72)):
    if jax.default_backend() != "tpu":
        sys.exit("--section unit times the chip's matrix unit: no TPU here "
                 f"({jax.default_backend()})")
    bn, kc = UNIT_BLOCK
    w = jax.random.normal(jax.random.PRNGKey(1), (bn, kc), jnp.bfloat16)
    for cfg, name, n, k in CELL_SHAPES:
        steps = -(-n // bn) * -(-k // kc)  # the matrix, a block a step
        for m in rows:
            x = jax.random.normal(jax.random.PRNGKey(9), (m, kc), jnp.bfloat16)
            ms = _ms_a_call(functools.partial(unit_passes, steps=steps), x, w,
                            calls=1)
            emit(section="unit", config=cfg, matrix=name, m=m, n=n, k=k,
                 steps=steps, ms=round(ms, 4),
                 ps_weight=round(ms * 1e9 / (steps * bn * kc), 3),
                 unit_floor_ps=1.30)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--section", default=None,
                    choices=["model", "consistency", "unit"])
    ap.add_argument("--cells", action="store_true")
    args = ap.parse_args()
    emit(section="meta", backend=jax.default_backend(),
         device=str(jax.devices()[0]))
    if args.cells:
        return sec_cells()
    if args.section == "unit":
        return sec_unit()
    if args.section in (None, "model"):
        sec_model()
    if args.section in (None, "consistency"):
        sec_consistency()


if __name__ == "__main__":
    main()
