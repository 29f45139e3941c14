"""Fused 4-bit split-plane quantized matvec — true-Q40-footprint decode kernel.

The int8-plane kernel (ops/pallas_q8.py) spends 1 B/weight of HBM; decode is
HBM-bandwidth-bound, so on a ~300 GB/s effective chip a 7B model costs ~25 ms/token in
weight traffic alone. This kernel keeps weights PACKED at 4 bits (0.5 B/weight + f16
scales = 0.5625 B/weight, the reference's own Q40 density, src/quants.hpp:17-20) and
unpacks in VMEM with zero cross-lane shuffles:

Layout "i4p" (split-plane packing, `QTensor.to_i4p_layout`):
    data   uint8 (out, K/2):  byte j = q[j] | (q[j + K/2] << 4),  q = nibble+8 in [0,16)
    scales int16 (out, C):    the reference's per-block f16 deltas as raw BIT PATTERNS
                              (bit-exact), K/32 of them a row with zero columns behind
                              up to C, whole 128-lane tiles (`quants.to_scale_plane`:
                              the form the chip keeps row-major). Mosaic on this toolchain
                              cannot lower f16 refs ("Unsupported type in mosaic
                              dialect: 'f16'"), so the kernel ships the bits as int16
                              and decodes f16->f32 in-kernel with exact integer math
                              (`_f16_bits_to_f32`).

Unpacking byte j's low nibble yields element j and the high nibble element j + K/2 —
both planes land in natural element order, so the unpack is 4 elementwise VPU ops per
byte (and/shift/two subs) and the per-block scale structure is untouched. The dot is the
same block-diagonal Xexp trick as pallas_q8 (P[n,b] = per-block int32 partial sums on
the MXU), split into the two K/2 halves:

    P = (lo - 8) @ Xexp[:K/2] + (hi - 8) @ Xexp[K/2:]
    y[n] = sum_b scales[n,b] * sx[b] * P[n,b]

This is the TPU descendant of matmulQ40vQ80 (src/funcs.cpp:287-396) at the reference's
exact storage density; the reference unpacks nibbles per dot-product on NEON the same
way, just 32 lanes at a time instead of 4096.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..platform_env import interpret_requested
from ..quants import QK, QTensor, scale_plane_cols


def _f16_bits_to_f32(h16):
    """Exact f16-bit-pattern (int16) -> f32 decode using only int ops + one bitcast.

    Mosaic cannot lower f16 refs, and the TPU VPU flushes subnormal f32 to zero, so
    the usual magic-multiply half->float trick silently zeroes subnormal deltas.
    Instead use  value = (m + (e>0)*1024) * 2^(max(e,1) - 25)  with the power of two
    built by bitcasting (k+127)<<23: every intermediate is a normal f32, making the
    decode bit-exact for all 65024 finite f16 patterns (verified exhaustively on a
    real v5e chip; f16 inf/nan decode wrong but Q40 deltas are always finite)."""
    h = h16.astype(jnp.int32) & 0xFFFF
    e = (h >> 10) & 0x1F
    mant = jnp.where(e > 0, (h & 0x3FF) + 1024, h & 0x3FF).astype(jnp.float32)
    p2 = jax.lax.bitcast_convert_type((jnp.maximum(e, 1) + 102) << 23, jnp.float32)
    f = mant * p2
    return jnp.where((h & 0x8000) != 0, -f, f)


def _unpack_dot_epilogue(xexp_ref, sx_ref, ssum_ref, wp_ref, s_ref, o_ref):
    """Shared kernel body: split-plane unpack, per-half MXU dots, scale epilogue.

    Mosaic on this toolchain cannot legalize elementwise subtract or logical shift on
    i8/u8 vectors (arith.subi / arith.shrui), so (a) the high nibble's shift widens
    through i32 (the only narrow-int ops Mosaic does lower are and/cast), and (b) the
    nibble's +8 offset is NOT removed per weight: the unsigned nibbles q in [0,16) go
    straight to the MXU and the offset folds into a per-block int32 correction:
    (q-8)·x = q·x - 8·Σ_block(x)  with Σ_block(x) = ssum_ref (the Q80 activation
    block sums, computed once per row outside the kernel). Same integer result
    bit-for-bit as subtracting 8 per weight."""
    wp = wp_ref[:]  # (bn, K/2) uint8
    lo = (wp & jnp.uint8(0x0F)).astype(jnp.int8)  # q of elements [0, K/2)
    hi = (wp.astype(jnp.int32) >> 4).astype(jnp.int8)  # q of elements [K/2, K)
    kh = wp.shape[1]
    # P[n, b] = sum_{j in block b} q[n, j] * xq[j] — int8 x int8 -> int32 on the MXU
    p = jax.lax.dot_general(lo, xexp_ref[:kh], (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.int32)
    p += jax.lax.dot_general(hi, xexp_ref[kh:], (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.int32)
    p -= ssum_ref[:] * 8  # remove the nibble offset per block (broadcast over rows)
    nb = sx_ref.shape[1]  # the plane's own columns, less its padding
    y = (_f16_bits_to_f32(s_ref[:, :nb]) * sx_ref[:]) * p.astype(jnp.float32)
    o_ref[:] = jnp.sum(y, axis=1, keepdims=True)


def _matvec_kernel(xexp_ref, sx_ref, ssum_ref, wp_ref, s_ref, o_ref):
    _unpack_dot_epilogue(xexp_ref, sx_ref, ssum_ref, wp_ref, s_ref, o_ref)


def _matvec_kernel_inline(xq_ref, sx_ref, ssum_ref, wp_ref, s_ref, o_ref, xexp_ref):
    """Variant generating the block-diagonal Xexp in VMEM scratch from the raw int8
    activation row (k bytes of HBM instead of k*nb): built once at grid step 0, reused
    by every row block."""
    _, nb = xexp_ref.shape

    @pl.when(pl.program_id(0) == 0)
    def _build():
        from .pallas_q8 import block_diag_scatter

        xexp_ref[:] = block_diag_scatter(xq_ref[0], nb)

    _unpack_dot_epilogue(xexp_ref, sx_ref, ssum_ref, wp_ref, s_ref, o_ref)


def _pick_bn(n: int, k: int, budget_bytes: int = 3 << 20) -> int:
    """Largest 128-multiple row-block whose (bn, K/2) packed block fits the VMEM budget
    (double-buffered by Pallas)."""
    if n <= 128:
        return n
    cap = max(budget_bytes // max(k // 2, 1), 128)
    return max(min(cap, n) // 128 * 128, 128)


_XEXP_VMEM_LIMIT = 9 << 20


def q4_shape_supported(n: int, k: int) -> bool:
    nb = k // QK
    return k % (2 * QK) == 0 and k * nb <= _XEXP_VMEM_LIMIT


def q4_decode_supported(w: QTensor) -> bool:
    """Whether the fused 4-bit matvec kernel can run this weight tensor on TPU."""
    if w.layout != "i4p" or w.data.ndim != 2:
        return False
    n, kh = w.data.shape
    return q4_shape_supported(n, kh * 2)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _q4_matvec(xexp, sx, wp, scales, *, interpret: bool = False):
    """y (n, 1) f32 from block-diagonal Xexp (K, nb) int8, sx (1, nb) f32,
    packed nibbles (n, K/2) uint8, the scales' plane (n, scale_plane_cols(nb))
    int16 f16-bit-patterns."""
    k, nb = xexp.shape
    n, kh = wp.shape
    cols = scale_plane_cols(nb)
    assert kh * 2 == k and scales.shape == (n, cols) and nb * QK == k, (
        xexp.shape, wp.shape, scales.shape)
    # activation block sums for the nibble-offset correction (colsum works because
    # Xexp's column b is exactly block b's xq values scattered along its rows)
    ssum = jnp.sum(xexp, axis=0, dtype=jnp.int32)[None, :]
    bn = _pick_bn(n, k)
    return pl.pallas_call(
        _matvec_kernel,
        grid=(pl.cdiv(n, bn),),
        in_specs=[
            pl.BlockSpec((k, nb), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, nb), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, nb), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, kh), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, cols), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((bn, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.float32),
        interpret=interpret,
    )(xexp, sx, ssum, wp, scales)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _q4_matvec_inline(xq, sx, wp, scales, *, interpret: bool = False):
    """Inline-Xexp variant: xq (1, K) int8 streamed to VMEM; the block-diagonal
    operand lives only in kernel scratch."""
    _, k = xq.shape
    n, kh = wp.shape
    nb = k // QK
    cols = scale_plane_cols(nb)
    assert kh * 2 == k and scales.shape == (n, cols), (
        xq.shape, wp.shape, scales.shape)
    ssum = jnp.sum(xq.reshape(nb, QK), axis=1, dtype=jnp.int32)[None, :]
    # the (k, nb) Xexp scratch (lanes padded to 128) shares the chip's 16 MiB
    # scoped VMEM with the double-buffered weight block and its two unpacked
    # planes: at K=14336 the default 3 MiB block overran it by 1.9 MiB
    xexp_bytes = k * pl.cdiv(nb, 128) * 128
    bn = _pick_bn(n, k, min(3 << 20, ((12 << 20) - xexp_bytes) // 4))
    return pl.pallas_call(
        _matvec_kernel_inline,
        grid=(pl.cdiv(n, bn),),
        in_specs=[
            pl.BlockSpec((1, k), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, nb), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, nb), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, kh), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, cols), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((bn, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((k, nb), jnp.int8)],
        interpret=interpret,
    )(xq, sx, ssum, wp, scales)


# flip after measuring on hardware (perf/microbench.py --section matvec compares both)
INLINE_XEXP_DEFAULT = False


def q4_matvec(x: jax.Array, w: QTensor, *, out_dtype=None,
              interpret: bool | None = None,
              inline_xexp: bool | None = None) -> jax.Array:
    """Decode-path matmul: x (..., K) with leading dims multiplying to 1, i4p-layout
    QTensor (N, K) -> (..., N)."""
    if w.layout != "i4p":
        raise ValueError("q4_matvec needs i4p-layout weights (QTensor.to_i4p_layout)")
    assert w.data.ndim == 2, w.data.shape
    if interpret is None:
        interpret = interpret_requested()
    if inline_xexp is None:
        inline_xexp = INLINE_XEXP_DEFAULT
    from .pallas_q8 import _expand_q80, _quantize_row

    lead = x.shape[:-1]
    k = x.shape[-1]
    nb = k // QK
    if inline_xexp:
        xq, sx = _quantize_row(x.reshape(k), nb)
        y = _q4_matvec_inline(xq[None, :], sx, w.data, w.scales, interpret=interpret)
    else:
        xexp, sx = _expand_q80(x.reshape(k), nb)
        y = _q4_matvec(xexp, sx, w.data, w.scales, interpret=interpret)
    return y.reshape(*lead, y.shape[0]).astype(out_dtype or x.dtype)
