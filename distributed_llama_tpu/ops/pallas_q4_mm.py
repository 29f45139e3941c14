"""Fused Q40 dequant-matmul for 2 to 512 activation rows: the weights decode in VMEM.

The decode matvec (ops/pallas_q4.py) is a one-row tool: its block-diagonal
Xexp trick needs a single activation row. Everything with more rows (prefill
chunks, the batched decode step, verify blocks, the all-experts scan's
per-expert slices) used to go through XLA's dequantize-then-dot, which wrote
the dequantized model to HBM every dispatch: the block scales spread to every
weight as an array, the packed planes copied to another layout, 7.8 GB of
temporaries for a 4 GB model (PERF.md section 5). Here the packed nibbles and
the f16-bit scales cross HBM once a call (the nibbles at the file's 0.5
bytes a weight; the scales as the plane they are stored in, below), become
bf16 in VMEM and go to the MXU with float32 accumulation. The decoded
weight is bit for bit XLA's `QTensor.dequantize(dtype=bf16)`:
bf16((q - 8) * bf16(scale)).

Split-plane addressing: i4p byte column c holds the LOW nibble of element c
and the HIGH nibble of element K/2 + c (QTensor.to_i4p_layout), so a packed
(bn, K/2) block covers all of K; the activations come in as two (M, K/2)
blocks of the same array, the planes' halves of K.

The operands: packed nibbles (..., N, K/2) uint8 and the scales' plane
(..., N, C) int16, C = K/32 rounded up to whole 128-lane tiles with zero
columns behind the real ones (`quants.to_scale_plane`, laid out once when the
weights are repacked). A block of it is (bn, C), read in place: the chip keeps
an array whose minor dimension is whole lane tiles row-major as stored. A
plane of K/32 columns (24, 80, 448 in the cells) it kept with the ROWS minor,
and XLA re-laid the whole stack to this very form at the head of every step
program, once a dispatch (PERF.md section 6, PR 46).

Blocks follow the shapes (`_pick_bn`): the rows' whole K is in every block,
so the activations cross HBM once a call and the grid has N / bn steps of up
to 512 KiB of packed weights; the body walks K in static chunks of at most 512
packed columns so that the decoded temporaries stay small and the compiler
can lay one chunk's decode (VPU) beside another's matmul (MXU).

What the chip's compiler refuses and the interpreter accepts shaped the
decode (tests/test_tpu_compile.py holds the family to it at the cells'
shapes): no shift or subtract on 8-bit vectors (the nibbles widen through
i32), no f16 refs (the scales arrive as int16 bit patterns and decode with
integer math, `_f16_bits_to_f32`), no (bn, bk) -> (bn, bk/32, 32) reshape (a
block's scale reaches its 32 lanes by a lane gather, `_spread`).

`scales_f32` and `partial_product` are the ONE decode this kernel and the
grouped expert kernels (ops/pallas_moe_grouped.py) share.

No epilogue. A residual add in the accumulator and a gated act(x Wgate^T) *
(x Wup^T) pair over the merged w13 stack were built and measured against
this plain kernel (PERF.md section 6, PR 30): 0.381 against 0.386 ms and
0.751 against 0.789 ms a call at 512 rows, nothing at 8 and 64, and in the
dense cell `itl_p95_ms` 110.92 against the plain kernel's 110.62; they went.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs import metrics
from ..platform_env import interpret_requested
from ..quants import QK, QTensor, scale_plane_cols
from .pallas_q4 import _f16_bits_to_f32

VMEM_LIMIT = 64 << 20  # of the chip's 128 MiB; the default scope is 16
_MM_BLOCK_BYTES = 1 << 19  # packed bytes of one weight block a grid step:
# the body is unrolled over a block, so its program's size follows the block,
# and every step program carries five of them (`setup_s` 85.4 against the parent's
# 77.8 at 1 MiB, PR 30); 512 KiB read the same time a call
_MAX_ROWS = 512  # activation rows a call: the (M, bn) accumulator and the
# resident (M, K) activations are sized for the widest dispatch the engines
# make (8 slots x a 64-token chunk)


def pick_bk(kh: int) -> int:
    """Packed columns a body chunk decodes at once: the largest lane-aligned
    width that divides the half-plane, the whole of it where none does (toy
    sizes under the interpreter)."""
    for b in (512, 256, 128):
        if kh % b == 0:
            return b
    return kh


def scales_f32(s_ref):
    """A block of the scales' plane, (bn, whole lane tiles) f16 bits, rounded
    to bf16, which is what XLA's `dequantize(dtype=bf16)` multiplies by, as
    the float32 the VPU computes in (`_spread` takes it a lane tile at a
    time; the plane's zero columns decode to zeros nobody reads). The kernels
    keep it in a VMEM scratch of `scales_shape`."""
    return _f16_bits_to_f32(s_ref[:]).astype(jnp.bfloat16).astype(jnp.float32)


def scales_shape(bn: int, cols: int):
    return pltpu.VMEM((bn, cols), jnp.float32)


_GATHER_LANES = jax.lax.GatherDimensionNumbers(
    offset_dims=(), collapsed_slice_dims=(1,), start_index_map=(1,),
    operand_batching_dims=(0,), start_indices_batching_dims=(0,))


# hot-path: traced
def _spread(tile, off, bk: int):
    """Scale columns off .. off + bk / 32 of a (bn, 128) lane tile of decoded
    scales, each over its block's 32 lanes: (bn, bk). A lane gather inside
    one vreg a lane tile of output (what `take_along_axis` lowers to, bound
    directly: its wrapper was three quarters of the time it took to trace
    this body), which costs the decode nothing measurable: the same kernel
    with no spread at all reads the same time. What it replaced (PERF.md
    section 6, PR 30, ps a weight at (28672, 4096), M = 8): a 0/1 matmul in
    one bf16 pass 2.3 against 1.8, the same at Precision.HIGHEST 8.8 (the
    grouped expert kernels' form until then), `jnp.repeat` 11.8."""
    if bk % 128:  # a toy width, its own columns: the interpreter alone
        return jnp.repeat(tile, QK, axis=1)
    lane = jax.lax.shift_right_logical(
        jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1), 5) + off
    tiles = [jax.lax.gather(
        tile, (lane + t * (128 // QK))[..., None], _GATHER_LANES, (1, 1),
        mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)
        for t in range(bk // 128)]
    return tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, axis=1)


# hot-path: traced
@jax.jit
def _chunk_product(xlo, xhi, wp, s_lo, off_lo, s_hi, off_hi):
    """One chunk of `partial_product`: bk packed columns of a (bn, K/2)
    block against the rows' two halves. A function of its own under `jit` so
    that it is traced once a process per (rows, bn, bk), not once for every
    chunk of every weight of every engine: the body is unrolled over K and a
    serving process brings up 45 kernels (five matrices at 8, 64 and 512
    rows, and again at each depth of the output check's engines), 4 to 14
    chunks each; Mosaic inlines the call. The nibbles widen through i32 (the
    chip's compiler has no shift or subtract on 8-bit vectors); a
    sign-extending or a bit-pattern decode of the nibble read the same time
    as this one."""
    bk = wp.shape[1]
    lo = (wp & jnp.uint8(0x0F)).astype(jnp.int32).astype(jnp.float32)
    hi = (wp.astype(jnp.int32) >> 4).astype(jnp.float32)
    w_lo = ((lo - 8.0) * _spread(s_lo, off_lo, bk)).astype(jnp.bfloat16)
    w_hi = ((hi - 8.0) * _spread(s_hi, off_hi, bk)).astype(jnp.bfloat16)
    contract = (((1,), (1,)), ((), ()))
    return (jax.lax.dot_general(xlo.astype(jnp.bfloat16), w_lo, contract,
                                preferred_element_type=jnp.float32)
            + jax.lax.dot_general(xhi.astype(jnp.bfloat16), w_hi, contract,
                                  preferred_element_type=jnp.float32))


def _scale_tile(s_ref, g0: int, bk: int):
    """The lane tile of s_ref (`scales_f32`) that holds scale columns
    g0 .. g0 + bk / 32, and where in it they start: a chunk's columns never
    straddle two tiles (bk divides the half-plane, bk / 32 divides 128)."""
    if bk % 128:
        return s_ref[:, g0:g0 + bk // QK], np.int32(0)
    w0 = g0 // 128 * 128
    return s_ref[:, w0:w0 + 128], np.int32(g0 - w0)


# hot-path: traced
def partial_product(xlo_ref, xhi_ref, wp_ref, s_ref, bk):
    """(rows, bn) f32: the rows' two K halves against one packed (bn, K/2)
    block with scales s_ref (`scales_f32`, in VMEM), the packed columns
    walked in static chunks of bk (`_chunk_product`), so that the compiler
    lays one chunk's decode (VPU) beside another's matmul (MXU): the same
    body as a `fori_loop` over pairs of 256-column chunks read 45 against 31
    ms a T = 1 dispatch of the dense cell (PERF.md section 6, PR 30)."""
    kh = wp_ref.shape[-1]
    acc = None
    for c in range(kh // bk):
        cols = slice(c * bk, (c + 1) * bk)
        part = _chunk_product(
            xlo_ref[:, cols], xhi_ref[:, cols], wp_ref[:, cols],
            *_scale_tile(s_ref, c * bk // QK, bk),
            *_scale_tile(s_ref, (kh + c * bk) // QK, bk))
        acc = part if acc is None else acc + part
    return acc


def _mm_kernel(at_ref, xlo_ref, xhi_ref, wp_ref, s_ref, o_ref, sf_ref, *, bk):
    sf_ref[:] = scales_f32(s_ref)
    o_ref[:] = partial_product(xlo_ref, xhi_ref, wp_ref, sf_ref,
                               bk).astype(o_ref.dtype)


_VMEM_BYTES = 128 << 20  # the chip's faster memory, all of it


def _pick_bn(n: int, kh: int, stacked: int = 1) -> int:
    """Weight rows a grid step: as many whole lane tiles as keep the packed
    (bn, K/2) block under _MM_BLOCK_BYTES, at most 512 (the accumulator is
    (M, bn) float32); n itself where it is smaller. The grid is cdiv(n, bn):
    a ragged last block (the 151936-row head) reads past the array and its
    surplus columns are never written. That is harmless in HBM and NOT where
    XLA keeps the whole stack (`stacked` matrices) in the faster memory
    space, which it does to a stack small enough (`S(1)` in the compiled
    text): a step program of granite-4.0-h-small's two-layer cut, whose one
    16768-row `ssm_in` lay there, never came back from the chip (PERF.md
    section 6, PR 46). A stack that fits that memory therefore gets the
    largest lane-aligned block that divides n, where there is one."""
    if n <= 128:
        return n
    bn = min(max(_MM_BLOCK_BYTES // kh // 128, 1) * 128, 512, n // 128 * 128)
    if n % bn and stacked * n * kh <= _VMEM_BYTES:
        bn = next((b for b in range(bn, 0, -128) if n % b == 0), bn)
    return bn


def q4_mm_reads(k: int) -> bool:
    """Whether the kernel reads a split-plane pack of k columns (a weight's
    K, or one column group's of it) at its 2 to `_MAX_ROWS` rows: a
    half-plane of whole lane tiles, whatever else the shape is. What
    `models/params.py` asks before it packs a matrix over the one-row
    matvec's bound."""
    return k % 256 == 0


def q4_mm_supported(w: QTensor, m: int, stacked: int = 0) -> bool:
    """Whether the fused dequant-matmul runs this weight for m activation
    rows: split-plane Q40 in one self-contained pack (`groups` folded away by
    _localize_qtensors under TP), (N, K/2) under `stacked` leading axes (a
    stack over layers, or over layers and experts), a half-plane of whole
    lane tiles, and no more rows than the resident activations are sized
    for. One row is the matvec kernel's."""
    if w.layout != "i4p" or w.groups != 1 or w.data.ndim != 2 + stacked:
        return False
    return q4_mm_reads(2 * w.data.shape[-1]) and 2 <= m <= _MAX_ROWS


@functools.partial(jax.jit,
                   static_argnames=("out_dtype", "interpret", "name"))
def _q4_matmul(x, wp, scales, at, *, out_dtype, interpret: bool = False,
               name: str = "q4_mm"):
    """x (M, K) -> (M, N) against the matrix at leading indices `at` (a tuple
    of traced scalars: the layer, or the layer and the expert) of packed
    nibbles (L, N, K/2) or (L, E, N, K/2) + int16 f16-bit scales of the same
    leading shape and (N, scale_plane_cols(K/32)): the plane as stored.

    The indices point into the WHOLE stack, prefetched as scalars and used
    by the weight blocks' index maps: a layer scan that handed the kernel
    its slice made XLA copy every layer's packed weights to a buffer of
    their own first (`dynamic-slice_bitcast_fusion`, `copy`: 7 s of the
    dense cell's 45 s busy, 9 ms of every dispatch; PERF.md section 6,
    PR 30), and with an expert axis every layer's experts, touched or not,
    and in the all-experts scan each expert once more (30 to 34 % of the MoE
    cells' busy time; PERF.md section 6, PR 33)."""
    m, k = x.shape
    lead = len(at)
    *_, n, kh = wp.shape
    assert kh * 2 == k and scales.shape == (
        *wp.shape[:lead], n, scale_plane_cols(k // QK)), (
        x.shape, wp.shape, scales.shape)
    bn = _pick_bn(n, kh, math.prod(wp.shape[:lead]))

    def block(i, a):
        return (*(a[j] for j in range(lead)), i, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # the leading indices
        grid=(pl.cdiv(n, bn),),
        in_specs=[pl.BlockSpec((m, kh), lambda i, a: (0, 0)),
                  pl.BlockSpec((m, kh), lambda i, a: (0, 1)),
                  pl.BlockSpec((*(None,) * lead, bn, kh), block),
                  pl.BlockSpec((*(None,) * lead, bn, scales.shape[-1]),
                               block)],
        out_specs=pl.BlockSpec((m, bn), lambda i, a: (0, i)),
        scratch_shapes=[scales_shape(bn, scales.shape[-1])])
    return pl.pallas_call(
        functools.partial(_mm_kernel, bk=pick_bk(kh)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        name=name,
        interpret=interpret,
    )(jnp.concatenate([jnp.reshape(a, (1,)) for a in at]).astype(jnp.int32),
      x, x, wp, scales)


_BODIES_LOWERED = metrics.counter(
    "q4_mm_bodies_lowered_total",
    "dequant-matmul call sites traced into a program, each of which lowers "
    "the kernel's body to Mosaic once: five a step program, so it follows "
    "the number of programs a process brings up (counted at trace time)")


def q4_matmul(x: jax.Array, w: QTensor, *, at=(), out_dtype=None,
              interpret: bool | None = None,
              name: str = "q4_mm") -> jax.Array:
    """x (..., K) against an i4p QTensor (N, K), or with `at` (traced
    leading indices: the layer, or the layer and the expert) against that
    matrix of one stacked (L, N, K) or (L, E, N, K) -> (..., N), the weights
    streamed once at 4-bit density and decoded in VMEM."""
    x2 = x.reshape(-1, x.shape[-1])
    if not q4_mm_supported(w, x2.shape[0], stacked=len(at)):
        raise ValueError(
            f"q4_matmul cannot run this weight (layout={w.layout}, "
            f"groups={w.groups}, shape={getattr(w.data, 'shape', None)}, "
            f"leading indices={len(at)}, M={x2.shape[0]}); gate with "
            f"q4_mm_supported")
    if interpret is None:
        interpret = interpret_requested()
    _BODIES_LOWERED.inc()
    wp, scales = w.data, w.scales
    if not at:  # a stack of one
        wp, scales, at = wp[None], scales[None], (0,)
    y = _q4_matmul(x2, wp, scales,
                   tuple(jnp.asarray(i, jnp.int32) for i in at),
                   out_dtype=jnp.dtype(out_dtype or x.dtype),
                   interpret=interpret, name=name)
    return y.reshape(*x.shape[:-1], y.shape[-1])
