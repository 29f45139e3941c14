"""Fused 4-bit dequant-matmul — the prefill / batched-decode counterpart of the
q4 matvec kernel.

The decode matvec (ops/pallas_q4.py) is a T=1 tool: its block-diagonal Xexp
trick needs one activation row. Prefill (T>1) and batched decode (B>1) run the
XLA dequant+dot path (ops/matmul.py), which dequantizes the i4p planes to bf16
operands that XLA may MATERIALIZE through HBM (~3.6x the packed bytes at 7B).
This kernel keeps the dequant in VMEM: each grid step loads a packed (bn, bkp)
nibble tile, picks its two scale tiles out of the row block's scales (decoded
once per row block into tile-major VMEM scratch, _split_scales), decodes to
bf16 in registers, and feeds the MXU — weights stream from HBM exactly once at
the file's own 0.5625 B/weight density regardless of M.

Split-plane addressing: i4p byte column c holds the LOW nibble of element c and
the HIGH nibble of element K/2 + c (QTensor.to_i4p_layout), so one packed tile
covers two disjoint K-ranges; the kernel takes the activation block TWICE with
block-index maps offset by K/2 (x_lo / x_hi views of the same array), and
scale tile j serves the low plane, tile j + gk the high plane.

Mosaic portability: nibble extraction widens through i32 (no narrow shifts),
the -8 offset and per-block scaling happen in f32 (no i8 subtract), scales
decode from f16 BIT PATTERNS with the integer-exact _f16_bits_to_f32, and the
dot is bf16xbf16->f32 on the MXU. No f16 refs anywhere. Two things the
interpreter accepts and the chip's compiler refuses shaped the scale path: a
(bn, bkp/32) scale block is narrower than a lane tile, and a (bn, bkp) ->
(bn, bkp/32, 32) reshape is an unsupported shape cast, so the scales arrive
as whole rows and widen with jnp.repeat (tests/test_tpu_compile.py holds the
family to the chip's compiler at Llama-3-8B shapes).

Opt-in (Engine prefill_kernel / DLT_PREFILL_KERNEL, bench --prefill-kernel)
until a hardware A/B lands — same policy as the prologue kernels. The batched
serving runtime opts in one level higher (Engine fused_matmul /
DLT_FUSED_MATMUL, --fused-matmul): the same kernel family with the legal
epilogues fused — residual add in the accumulator init (q4_matmul residual=)
and the silu·mul FFN gate pair as one kernel over the separate w1/w3 planes
(q4_gated_matmul) — serving decode M=B, verify M=B·(1+k), and drafter rows
(docs/SERVING.md "Kernel selection"; byte model computed by
perf/q4_mm_bench.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..platform_env import interpret_requested
from ..quants import QK, QTensor
from .pallas_q4 import _f16_bits_to_f32


# hot-path: traced
def _split_scales(s_ref, st_ref):
    """Decode one row-block's f16-bit scales (bn, K/32) once and lay them out
    tile-major in VMEM scratch (2*gk, bn, sb): entry t holds the sb block
    scales of packed-column tile t (low plane) or t - gk (high plane). The
    slices are static, so the K grid step can pick its tile with a leading-
    axis index — Mosaic has no unaligned dynamic lane slice."""
    sf = _f16_bits_to_f32(s_ref[:])
    sb = st_ref.shape[2]
    for t in range(st_ref.shape[0]):
        st_ref[t] = sf[:, t * sb:(t + 1) * sb]


# hot-path: traced
def _tile_partial(xlo_ref, xhi_ref, wp_ref, st_ref, *, gk):
    """One grid step's (M, bn) partial product: decode the packed (bn, bkp)
    nibble tile against its two scale tiles in VMEM and hit the MXU twice
    (low-plane and high-plane K-ranges of the split-plane layout)."""
    j = pl.program_id(1)
    wp = wp_ref[:]  # (bn, bkp) uint8 packed columns
    lo = (wp & jnp.uint8(0x0F)).astype(jnp.int32)  # elements [c, c+bkp)
    hi = wp.astype(jnp.int32) >> 4  # elements [K/2+c, K/2+c+bkp)

    def dequant(q_i32, s):
        # s (bn, bkp//QK): each block scale covers QK consecutive lanes
        qf = (q_i32.astype(jnp.float32) - 8.0) * jnp.repeat(s, QK, axis=1)
        return qf.astype(jnp.bfloat16)

    w_lo = dequant(lo, st_ref[j])
    w_hi = dequant(hi, st_ref[j + gk])
    acc = jax.lax.dot_general(
        xlo_ref[:].astype(jnp.bfloat16), w_lo, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)  # (M, bn)
    acc += jax.lax.dot_general(
        xhi_ref[:].astype(jnp.bfloat16), w_hi, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    return acc


# hot-path: traced
def _act_f32(a, act: str):
    """Epilogue activation on the f32 accumulator, formulas matching
    ops/kernels.py bit-for-bit in f32 (silu / tanh-approx GELU)."""
    if act == "silu":
        return a / (1.0 + jnp.exp(-a))
    c = 0.79788456080286535587989211986876  # sqrt(2/pi), as gelu_tanh
    return 0.5 * a * (1.0 + jnp.tanh(c * a * (1.0 + 0.044715 * a * a)))


def _mm_kernel(xlo_ref, xhi_ref, wp_ref, s_ref, o_ref, st_ref, *, gk):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        _split_scales(s_ref, st_ref)
        o_ref[:] = jnp.zeros_like(o_ref)

    o_ref[:] += _tile_partial(xlo_ref, xhi_ref, wp_ref, st_ref, gk=gk)


def _mm_res_kernel(xlo_ref, xhi_ref, wp_ref, s_ref, res_ref, o_ref, st_ref,
                   *, gk):
    """Residual-fused variant: the accumulator STARTS at the residual block
    (same (M, bn) tile the output covers), so `res + x @ w.T` costs zero extra
    HBM round-trips — the residual streams in once with the output tile."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        _split_scales(s_ref, st_ref)
        o_ref[:] = res_ref[:].astype(jnp.float32)

    o_ref[:] += _tile_partial(xlo_ref, xhi_ref, wp_ref, st_ref, gk=gk)


def _gated_mm_kernel(xlo_ref, xhi_ref, w1p_ref, s1_ref, w3p_ref, s3_ref,
                     o_ref, acc1_ref, acc3_ref, st1_ref, st3_ref, *, gk, act):
    """FFN gate-pair fusion: act(x @ w1.T) * (x @ w3.T) in ONE kernel. Both
    accumulators live in VMEM scratch across the sequential K grid; the
    silu/gelu·mul epilogue runs on the last K step, so the (M, hidden)
    intermediate activations never exist in HBM at all."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        _split_scales(s1_ref, st1_ref)
        _split_scales(s3_ref, st3_ref)
        acc1_ref[:] = jnp.zeros_like(acc1_ref)
        acc3_ref[:] = jnp.zeros_like(acc3_ref)

    acc1_ref[:] += _tile_partial(xlo_ref, xhi_ref, w1p_ref, st1_ref, gk=gk)
    acc3_ref[:] += _tile_partial(xlo_ref, xhi_ref, w3p_ref, st3_ref, gk=gk)

    @pl.when(j == gk - 1)
    def _epilogue():
        o_ref[:] = _act_f32(acc1_ref[:], act) * acc3_ref[:]


_BN = 256  # weight rows per grid step


def _pick_bkp(kh: int) -> int | None:
    """Packed columns per grid step: the largest lane-aligned tile width that
    divides the half-plane exactly (7B's w2 has kh=5504 -> 128; most dims take
    512). None = untileable (kh not a multiple of 128)."""
    for b in (512, 256, 128):
        if kh % b == 0:
            return b
    return None


# VMEM the tile-major scale scratch of one kernel may take. It sits beside the
# double-buffered operand tiles in the chip's 16 MiB of scoped VMEM: at 11 MiB
# (K=11008, the 7B w2 shape, one weight) the matmul still compiles at M=512;
# a gated pair at that K would need 21.5 MiB and is declined.
_SCALE_SCRATCH_LIMIT = 11 << 20


def _scale_scratch_bytes(kh: int) -> int:
    """Bytes of one weight's (2*gk, bn, sb) f32 scale scratch, each (bn, sb)
    tile padded to a 128-lane tile."""
    return 2 * (kh // _pick_bkp(kh)) * _BN * 128 * 4


def q4_mm_supported(w: QTensor, m: int) -> bool:
    """Whether the fused dequant-matmul can run this weight for M activation
    rows: i4p layout, self-contained pack (groups folded away by
    _localize_qtensors under TP), half-plane divisible into lane-aligned tiles,
    an (M, bn) f32 accumulator that stays tiny, and a scale scratch that
    fits VMEM."""
    if w.layout != "i4p" or w.groups != 1 or w.data.ndim != 2:
        return False
    kh = w.data.shape[1]  # K/2 packed columns
    return (_pick_bkp(kh) is not None and m <= 512
            and _scale_scratch_bytes(kh) <= _SCALE_SCRATCH_LIMIT)


def _grid_geom(x, wp, scales):
    """(bn, bkp, gk, sb) for one (M, K) x (N, K/2) dispatch, asserting the
    split-plane shapes line up."""
    m, k = x.shape
    n, kh = wp.shape
    nb = k // QK
    assert kh * 2 == k and scales.shape == (n, nb), (x.shape, wp.shape,
                                                     scales.shape)
    bkp = _pick_bkp(kh)
    assert bkp is not None, (kh, "half-plane not tileable; gate with "
                                 "q4_mm_supported")
    return min(_BN, n), bkp, kh // bkp, bkp // QK


def _x_specs(m, bkp, gk):
    # two views of x: the tile's low-plane and high-plane K-ranges
    return [
        pl.BlockSpec((m, bkp), lambda i, j: (0, j), memory_space=pltpu.VMEM),
        pl.BlockSpec((m, bkp), lambda i, j: (0, j + gk),
                     memory_space=pltpu.VMEM),
    ]


def _w_specs(bn, bkp, nb):
    # one packed-nibble tile per step; the row block's scales whole (their
    # block index does not move along K, so they are fetched once per row
    # block). A (bn, bkp/32) scale tile would be narrower than a lane tile,
    # which the TPU lowering refuses.
    return [
        pl.BlockSpec((bn, bkp), lambda i, j: (i, j), memory_space=pltpu.VMEM),
        pl.BlockSpec((bn, nb), lambda i, j: (i, 0), memory_space=pltpu.VMEM),
    ]


def _scale_scratch(bn, gk, sb):
    return pltpu.VMEM((2 * gk, bn, sb), jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _q4_matmul(x, wp, scales, *, interpret: bool = False):
    """x (M, K) -> (M, N) against packed nibbles (N, K/2) + int16 f16-bit scales
    (N, K/32)."""
    m = x.shape[0]
    n = wp.shape[0]
    bn, bkp, gk, sb = _grid_geom(x, wp, scales)
    return pl.pallas_call(
        functools.partial(_mm_kernel, gk=gk),
        grid=(pl.cdiv(n, bn), gk),
        in_specs=_x_specs(m, bkp, gk) + _w_specs(bn, bkp, scales.shape[1]),
        out_specs=pl.BlockSpec((m, bn), lambda i, j: (0, i),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[_scale_scratch(bn, gk, sb)],
        interpret=interpret,
    )(x, x, wp, scales)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _q4_matmul_res(x, wp, scales, res, *, interpret: bool = False):
    """x (M, K), res (M, N) -> res + x @ dequant(w).T, residual folded into
    the accumulator init (one extra streamed operand, no epilogue pass)."""
    m = x.shape[0]
    n = wp.shape[0]
    assert res.shape == (m, n), (res.shape, (m, n))
    bn, bkp, gk, sb = _grid_geom(x, wp, scales)
    return pl.pallas_call(
        functools.partial(_mm_res_kernel, gk=gk),
        grid=(pl.cdiv(n, bn), gk),
        in_specs=(_x_specs(m, bkp, gk) + _w_specs(bn, bkp, scales.shape[1]) + [
            pl.BlockSpec((m, bn), lambda i, j: (0, i),
                         memory_space=pltpu.VMEM),
        ]),
        out_specs=pl.BlockSpec((m, bn), lambda i, j: (0, i),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[_scale_scratch(bn, gk, sb)],
        interpret=interpret,
    )(x, x, wp, scales, res)


@functools.partial(jax.jit, static_argnames=("act", "interpret"))
def _q4_gated_matmul(x, w1p, s1, w3p, s3, *, act: str,
                     interpret: bool = False):
    """act(x @ w1.T) * (x @ w3.T) with both (M, N) accumulators in VMEM
    scratch — the FFN pair's intermediate activations never touch HBM."""
    m = x.shape[0]
    n = w1p.shape[0]
    assert w3p.shape == w1p.shape and s3.shape == s1.shape, (
        w1p.shape, w3p.shape, s1.shape, s3.shape)
    bn, bkp, gk, sb = _grid_geom(x, w1p, s1)
    w_specs = _w_specs(bn, bkp, s1.shape[1])
    return pl.pallas_call(
        functools.partial(_gated_mm_kernel, gk=gk, act=act),
        grid=(pl.cdiv(n, bn), gk),
        in_specs=_x_specs(m, bkp, gk) + w_specs + w_specs,
        out_specs=pl.BlockSpec((m, bn), lambda i, j: (0, i),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((m, bn), jnp.float32),
                        pltpu.VMEM((m, bn), jnp.float32),
                        _scale_scratch(bn, gk, sb),
                        _scale_scratch(bn, gk, sb)],
        interpret=interpret,
    )(x, x, w1p, s1, w3p, s3)


def _flatten_rows(x):
    m_total = 1
    for d in x.shape[:-1]:
        m_total *= d
    return m_total, x.shape[:-1]


def q4_matmul(x: jax.Array, w: QTensor, *, out_dtype=None,
              interpret: bool | None = None,
              residual: jax.Array | None = None) -> jax.Array:
    """Prefill/batched matmul: x (..., K) against an i4p QTensor (N, K) ->
    (..., N), weights streamed once at 4-bit density. With `residual`
    (shape (..., N)) the add is fused into the accumulator init."""
    m_total, lead = _flatten_rows(x)
    if not q4_mm_supported(w, m_total):
        raise ValueError(
            f"q4_matmul cannot run this weight (layout={w.layout}, "
            f"groups={w.groups}, shape={getattr(w.data, 'shape', None)}, "
            f"M={m_total}); gate with q4_mm_supported")
    if interpret is None:
        interpret = interpret_requested()
    k = x.shape[-1]
    if residual is None:
        y = _q4_matmul(x.reshape(m_total, k), w.data, w.scales,
                       interpret=interpret)
    else:
        y = _q4_matmul_res(x.reshape(m_total, k), w.data, w.scales,
                           residual.reshape(m_total, residual.shape[-1]),
                           interpret=interpret)
    return y.reshape(*lead, y.shape[-1]).astype(out_dtype or x.dtype)


def q4_gated_supported(w1: QTensor, w3: QTensor, m: int) -> bool:
    """Whether the fused FFN gate-pair kernel can serve act(x@w1.T) * (x@w3.T):
    both weights individually kernel-eligible and shape-identical (they tile
    on one grid), plus VMEM headroom for the two (M, bn) scratch
    accumulators and both scale scratches."""
    return (q4_mm_supported(w1, m) and q4_mm_supported(w3, m)
            and w1.data.shape == w3.data.shape
            and w1.scales.shape == w3.scales.shape
            and 2 * _scale_scratch_bytes(w1.data.shape[1])
            <= _SCALE_SCRATCH_LIMIT)


def q4_gated_matmul(x: jax.Array, w1: QTensor, w3: QTensor, *,
                    act: str = "silu", out_dtype=None,
                    interpret: bool | None = None) -> jax.Array:
    """FFN gate-pair: act(x @ w1.T) * (x @ w3.T) for x (..., K) against two
    i4p QTensors (N, K), one fused kernel — both weight streams at 4-bit
    density and ZERO HBM traffic for the (..., N) intermediates."""
    m_total, lead = _flatten_rows(x)
    if not q4_gated_supported(w1, w3, m_total):
        raise ValueError(
            f"q4_gated_matmul cannot run this pair (layouts={w1.layout}/"
            f"{w3.layout}, shapes={getattr(w1.data, 'shape', None)}/"
            f"{getattr(w3.data, 'shape', None)}, M={m_total}); gate with "
            f"q4_gated_supported")
    if act not in ("silu", "gelu_tanh"):
        raise ValueError(f"unsupported epilogue activation {act!r}")
    if interpret is None:
        interpret = interpret_requested()
    k = x.shape[-1]
    y = _q4_gated_matmul(x.reshape(m_total, k), w1.data, w1.scales,
                         w3.data, w3.scales, act=act, interpret=interpret)
    return y.reshape(*lead, y.shape[-1]).astype(out_dtype or x.dtype)
