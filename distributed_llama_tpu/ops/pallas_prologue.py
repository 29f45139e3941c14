"""Fused activation-prologue kernels: rmsnorm (+) Q80 quantization in one pass.

Every decode matvec quantizes its activation row to per-32-block int8 (the
reference's Q80 buffer discipline, src/tasks.cpp:96-135) before the weight kernel
runs. On the XLA path that costs, per layer, a handful of small fusions (rmsnorm
reduce, absmax, round/scale) plus — for the non-inline matvec variant — a
(K, nb) block-diagonal Xexp materialization through HBM. These kernels collapse
the whole prologue into ONE VPU pass per activation:

    rmsnorm_quantize_q80:  x (1,K) f32/bf16, w (K,)  ->  xq (1,K) i8, sx (1,nb) f32
    quantize_q80_row:      x (1,K)                   ->  xq (1,K) i8, sx (1,nb) f32

The outputs feed ops.matmul.qmatmul_q80, which routes into the inline-Xexp
matvec variants for BOTH layouts (scatter built in kernel scratch —
pallas_q4._matvec_kernel_inline / pallas_q8._matvec_kernel_inline), so the
quantized row is the only activation HBM traffic.

Numerics: the rmsnorm reduction runs in f32 with the same mean-square + eps
formula as ops.kernels.rmsnorm (reference funcs.cpp rms(), eps inside the mean);
the quantization IS pallas_q8._quantize_blocks (shared helper, pure jnp, usable
inside kernel bodies). Mosaic portability: the kernels see the row block-major,
(K/32, 32) with one quant block per row, a view taken in XLA where it is free:
splitting a (1, K) lane vector into blocks in-kernel is a shape cast the chip's
compiler refuses. All intermediates are f32 except the final i8 cast; no f16,
no narrow-int arithmetic.

Opt-in (Engine fused_prologue / bench --prologue) until a hardware A/B lands —
the round-4 lesson is not to ship never-executed kernels as defaults.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..platform_env import interpret_requested
from ..quants import QK


def _quantize_store(g, xq_ref, sx_ref):
    """Shared epilogue: per-block absmax quantize of g (nb, QK) f32 — one
    quant block per row — into the int8 blocks + f32 block scales. The math
    is pallas_q8._quantize_blocks itself: one source of truth for the Q80
    formula."""
    from .pallas_q8 import _quantize_blocks

    xq_ref[:], sx_ref[:] = _quantize_blocks(g)


def _rmsnorm_q80_kernel(x_ref, w_ref, xq_ref, sx_ref, *, eps: float):
    x = x_ref[:].astype(jnp.float32)  # (nb, QK)
    ms = jnp.sum(x * x) / x.size  # f32 reduction over the whole row
    inv = jnp.reciprocal(jnp.sqrt(ms + eps))
    _quantize_store(x * inv * w_ref[:].astype(jnp.float32), xq_ref, sx_ref)


def _quantize_kernel(x_ref, xq_ref, sx_ref):
    _quantize_store(x_ref[:].astype(jnp.float32), xq_ref, sx_ref)


def prologue_supported(k: int) -> bool:
    """Single-block VMEM kernel: the row (f32) plus outputs must be tiny. K up to
    64k (256 KB f32) is far under VMEM; require whole 32-blocks."""
    return k % QK == 0 and k <= (1 << 16)


def _row_call(kernel, nb: int, n_in: int, interpret: bool):
    """One-block pallas_call over the activation row viewed block-major as
    (nb, QK): Mosaic cannot split a (1, K) lane vector into quant blocks
    in-kernel (unsupported shape cast), so the view is taken in XLA where it
    is free. Outputs (nb, QK) int8 and (nb, 1) f32."""
    full = pl.BlockSpec((nb, QK), lambda: (0, 0), memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kernel,
        in_specs=[full] * n_in,
        out_specs=[full, pl.BlockSpec((nb, 1), lambda: (0, 0),
                                      memory_space=pltpu.VMEM)],
        out_shape=[jax.ShapeDtypeStruct((nb, QK), jnp.int8),
                   jax.ShapeDtypeStruct((nb, 1), jnp.float32)],
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _rmsnorm_q80(x, w, *, eps: float, interpret: bool):
    _, k = x.shape
    nb = k // QK
    xq, sx = _row_call(functools.partial(_rmsnorm_q80_kernel, eps=eps), nb, 2,
                       interpret)(x.reshape(nb, QK), w.reshape(nb, QK))
    return xq.reshape(1, k), sx.reshape(1, nb)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _quantize(x, *, interpret: bool):
    _, k = x.shape
    nb = k // QK
    xq, sx = _row_call(_quantize_kernel, nb, 1, interpret)(x.reshape(nb, QK))
    return xq.reshape(1, k), sx.reshape(1, nb)


def rmsnorm_quantize_q80(x: jax.Array, w: jax.Array, eps: float,
                         *, interpret: bool | None = None):
    """x (..., K) with leading dims multiplying to 1 -> (xq (1, K) i8,
    sx (1, nb) f32) of rmsnorm(x, w) quantized per 32-block."""
    k = x.shape[-1]
    if interpret is None:
        interpret = interpret_requested()
    return _rmsnorm_q80(x.reshape(1, k), w.reshape(1, k), eps=float(eps),
                        interpret=interpret)


def quantize_q80_row(x: jax.Array, *, interpret: bool | None = None):
    """x (..., K) with leading dims multiplying to 1 -> (xq (1, K) i8,
    sx (1, nb) f32)."""
    k = x.shape[-1]
    if interpret is None:
        interpret = interpret_requested()
    return _quantize(x.reshape(1, k), interpret=interpret)
