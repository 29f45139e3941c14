"""Fused int8-plane quantized matvec — the TPU descendant of matmulQ40vQ80.

The reference's hot loop (src/funcs.cpp:287-396) dot-products 4-bit weight blocks against
Q80-quantized activations with NEON `vdotq_s32`. A literal nibble-unpack kernel on TPU is
VPU-bound (~4 vector ops per weight swamp the MXU). Instead the load path expands Q40
nibbles once into **int8 planes** (`QTensor.to_i8_layout`): data int8 (out, K) holding
(nibble - 8), scales f32 (out, K/32). That costs 1 B/weight of HBM instead of 0.56, but
decode becomes pure MXU int8 work with zero per-weight VPU ops:

    y[n] = sum_b s[n,b] * sx[b] * P[n,b],   P = W8 @ Xexp   (int8 x int8 -> int32 MXU)

where Xexp (K, nb) is the activation vector quantized to int8 per 32-block (exactly the
reference's Q80 buffer semantics, src/tasks.cpp:96-135) and scattered block-diagonally:
Xexp[j, b] = xq[j] if j//32 == b else 0. A batch-1 matvec wastes 127/128 of every MXU pass
anyway; Xexp fills those wasted columns with the per-block partial sums, so the int8
matmul costs the same MXU passes as a plain matvec while making the per-block scale
structure a 32x-smaller (out, nb) elementwise epilogue instead of a per-weight multiply.

Decode (M=1) uses this kernel; prefill (M>1) amortizes a per-weight dequant over the
batch and goes through the XLA path in ops/matmul.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..platform_env import interpret_requested
from ..quants import QK, QTensor


def _matvec_kernel(xexp_ref, sx_ref, w_ref, s_ref, o_ref):
    # P[n, b] = sum_{j in block b} W8[n, j] * xq[j] — int8 x int8 -> int32 on the MXU
    p = jax.lax.dot_general(w_ref[:], xexp_ref[:], (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.int32)
    y = (s_ref[:] * sx_ref[:]) * p.astype(jnp.float32)  # (bn, nb) epilogue
    o_ref[:] = jnp.sum(y, axis=1, keepdims=True)


def _matvec_kernel_inline(xq_ref, sx_ref, w_ref, s_ref, o_ref, xexp_ref):
    """Inline-Xexp variant (the pallas_q4 pattern): the raw int8 activation row
    (K bytes of HBM instead of K*nb) is scattered block-diagonally into VMEM
    scratch at grid step 0 and reused by every row block."""
    _, nb = xexp_ref.shape

    @pl.when(pl.program_id(0) == 0)
    def _build():
        xexp_ref[:] = block_diag_scatter(xq_ref[0], nb)

    _matvec_kernel(xexp_ref, sx_ref, w_ref, s_ref, o_ref)


def _matvec_kernel_f32(xexp_ref, sx_ref, w_ref, s_ref, o_ref):
    # precise path: activations stay f32 (no Q80 step); weights convert once to f32.
    # Used by parity tests; decode perf path is the int8 kernel above.
    p = jax.lax.dot_general(w_ref[:].astype(jnp.float32), xexp_ref[:],
                            (((1,), (0,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    y = (s_ref[:] * sx_ref[:]) * p
    o_ref[:] = jnp.sum(y, axis=1, keepdims=True)


def _pick_bn(n: int, k: int, budget_bytes: int = 3 << 20) -> int:
    """Largest 128-multiple row-block whose (bn, K) int8 block fits the VMEM budget
    (double-buffered by Pallas). bn need not divide n: the grid is cdiv(n, bn) and
    Mosaic masks the trailing partial block. Tiny n uses the whole axis."""
    if n <= 128:
        return n
    cap = max(budget_bytes // max(k, 1), 128)
    return max(min(cap, n) // 128 * 128, 128)


# Above this VMEM footprint for the resident (K, nb) Xexp operand the kernel would not
# fit alongside the double-buffered weight blocks; callers (ops.matmul.qmatmul) fall back
# to the XLA dequant path. K=16384 (405B-class dim) stays comfortably under it.
_XEXP_VMEM_LIMIT = 9 << 20


def q8_shape_supported(n: int, k: int, precise: bool = False) -> bool:
    """Whether the fused matvec kernel can run a (n, k)-logical weight on TPU:
    the ONE-ROW matvec's bound on its resident Xexp operand (k <= 17378), true
    of this kernel and of `pallas_q4`'s matvec and asked at one row only. It
    decides the layout of the weights that kernel alone reads (int8 planes, the
    head, an expert stack); a split-plane Q40 matrix over it is still packed
    for the dequant-matmul's 2 to 512 rows (`models/params._kernel_convertible`)."""
    nb = k // QK
    esize = 4 if precise else 1
    return k * nb * esize <= _XEXP_VMEM_LIMIT


def q8_decode_supported(w: QTensor, precise: bool = False) -> bool:
    """Whether the fused matvec kernel can run this weight tensor on TPU."""
    if w.layout != "i8" or w.data.ndim != 2:
        return False
    return q8_shape_supported(*w.data.shape, precise=precise)


@functools.partial(jax.jit, static_argnames=("interpret", "precise"))
def _q8_matvec(xexp, sx, w8, scales, *, interpret: bool = False, precise: bool = False):
    """y (n, 1) f32 from block-diagonal Xexp (K, nb), sx (1, nb), int8 planes (n, K),
    scales (n, nb)."""
    k, nb = xexp.shape
    n, k2 = w8.shape
    assert k2 == k and scales.shape == (n, nb) and nb * QK == k, (
        xexp.shape, w8.shape, scales.shape)
    bn = _pick_bn(n, k)
    kernel = _matvec_kernel_f32 if precise else _matvec_kernel
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(n, bn),),
        in_specs=[
            pl.BlockSpec((k, nb), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, nb), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, k), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, nb), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((bn, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.float32),
        interpret=interpret,
    )(xexp, sx, w8, scales)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _q8_matvec_inline(xq, sx, w8, scales, *, interpret: bool = False):
    """Inline-Xexp variant: xq (1, K) int8 streamed to VMEM; the block-diagonal
    operand lives only in kernel scratch."""
    _, k = xq.shape
    n, k2 = w8.shape
    nb = k // QK
    assert k2 == k and scales.shape == (n, nb) and nb * QK == k, (
        xq.shape, w8.shape, scales.shape)
    bn = _pick_bn(n, k)
    return pl.pallas_call(
        _matvec_kernel_inline,
        grid=(pl.cdiv(n, bn),),
        in_specs=[
            pl.BlockSpec((1, k), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, nb), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, k), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, nb), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((bn, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((k, nb), jnp.int8)],
        interpret=interpret,
    )(xq, sx, w8, scales)


def _quantize_blocks(g: jax.Array):
    """Q80 quantization of g (nb, QK) f32, one quant block per row -> (int8
    (nb, QK), block scales (nb, 1) f32). Exactly the reference's Q80 buffer
    semantics (src/tasks.cpp:96-135). Pure jnp and free of reshapes."""
    absmax = jnp.max(jnp.abs(g), axis=-1, keepdims=True)
    inv = jnp.where(absmax > 0, 127.0 / absmax, 0.0)
    return jnp.round(g * inv).astype(jnp.int8), absmax / 127.0


def _quantize_row(x_row: jax.Array, nb: int):
    """Per-32-block Q80 quantization of one activation row (K,) -> (xq (K,) int8,
    sx (1, nb) f32)."""
    xq, sx = _quantize_blocks(x_row.reshape(nb, QK).astype(jnp.float32))
    return xq.reshape(x_row.shape[0]), sx.reshape(1, nb)


def block_diag_scatter(xq: jax.Array, nb: int) -> jax.Array:
    """Scatter a quantized row (K,) block-diagonally: Xexp[j, b] = xq[j] iff
    j // QK == b. Pure jnp — usable both in XLA and inside Pallas kernel bodies.

    Sub-32-bit dtypes broadcast through i32: Mosaic cannot insert a minor dim on
    narrow vectors ("Insertion of minor dim that is not a no-op only supported for
    32-bit types"), so the int8 path widens for the where and narrows after."""
    k = xq.shape[0]
    block_of = jax.lax.broadcasted_iota(jnp.int32, (k, nb), 0) // QK
    b_idx = jax.lax.broadcasted_iota(jnp.int32, (k, nb), 1)
    if xq.dtype.itemsize < 4:
        wide = jnp.where(block_of == b_idx, xq.astype(jnp.int32)[:, None], 0)
        return wide.astype(xq.dtype)
    return jnp.where(block_of == b_idx, xq[:, None], jnp.zeros((), xq.dtype))


def _expand_q80(x_row: jax.Array, nb: int):
    """Quantize one activation row (K,) to per-block int8 and scatter block-diagonally.

    Returns (Xexp (K, nb) int8, sx (1, nb) f32). Runs in XLA outside the kernel, where
    the quantize fuses with the producer.
    """
    xq, sx = _quantize_row(x_row, nb)
    return block_diag_scatter(xq, nb), sx


def _expand_f32(x_row: jax.Array, nb: int):
    """Precise-path variant: no activation quantization, unit block scales."""
    xexp = block_diag_scatter(x_row.astype(jnp.float32), nb)
    return xexp, jnp.ones((1, nb), jnp.float32)


def q8_matvec(x: jax.Array, w: QTensor, *, out_dtype=None,
              interpret: bool | None = None, precise: bool | None = None) -> jax.Array:
    """Decode-path matmul: x (..., K) with leading dims multiplying to 1, int8-layout
    QTensor (N, K) -> (..., N)."""
    if w.layout != "i8":
        raise ValueError(
            "q8_matvec needs i8-layout weights; run models.params.prepare_for_pallas "
            "(or QTensor.to_i8_layout) on the params first")
    assert w.data.ndim == 2, w.data.shape
    if interpret is None:
        interpret = interpret_requested()
    # precise (f32 activations, no Q80 step) is a parity-test tool, explicit opt-in only:
    # the production decode path quantizes activations to int8 exactly like the
    # reference's Q80 buffers regardless of the ambient compute dtype.
    precise = bool(precise)
    lead = x.shape[:-1]
    k = x.shape[-1]
    nb = k // QK
    x_row = x.reshape(k)
    if precise:
        xexp, sx = _expand_f32(x_row, nb)
    else:
        xexp, sx = _expand_q80(x_row, nb)
    y = _q8_matvec(xexp, sx, w.data, w.scales, interpret=interpret, precise=precise)
    return y.reshape(*lead, y.shape[0]).astype(out_dtype or x.dtype)
