"""Grouped Q40 matmuls for the routed expert layer: each row tile names its expert.

The rows of a dispatch's `N k` assignments arrive sorted by expert, every
expert's run padded to the row tile (ops/moe_grouped.py `plan`), so a tile
belongs to ONE expert. The grid is (out blocks, row tiles), row tiles
innermost, and the tile's expert rides in as a scalar-prefetch argument that
the weight BlockSpecs index with (`PrefetchScalarGridSpec`): consecutive tiles
of one expert keep the weight block's index, so the pipeline does not fetch it
again, and an expert nobody chose is never named. A chosen expert's packed
nibbles (0.5 bytes a weight) and its scales' plane cross HBM once a call;
an unchosen one costs no bytes and no FLOPs. Tiles past the last used one
repeat the last used tile's block indices (nothing moves) and skip their body.

The weights are the WHOLE stacks over layers, (L, E, rows, K/2) packed with
the scales' plane (L, E, rows, C), C = K/32 in whole 128-lane tiles as
`quants.to_scale_plane` stores it (the chip keeps that row-major and a block
reads it in place; see ops/pallas_q4_mm.py), and each weight's layer is one
more prefetched scalar beside the tile's expert (a `LayerOf` from the layer
scan; a layer's (E, rows, K/2) alone goes in as a stack of one). A layer
sliced out of the stack for the kernel was a copy of all its experts, touched
or not, two to three times the bytes the kernel then read (PERF.md section 6,
PR 33).

Two kernels. `gu`: act(x Wgate^T) * (x Wup^T) for a tile, both accumulators in
registers/VMEM, the (rows, hidden) pre-activations never in HBM; the merged
[up|gate] stack (models/params.py fuse_matvec_groups) is passed twice with the
gate's block index offset by hidden / bn. `down`: x Wdown^T. The contraction
axis is whole in every block (no K grid axis: an output block revisited after
other blocks would lose its accumulator), and the body walks it in static
chunks of at most 512 packed columns so the dequantized temporaries stay small.
The decode is ops/pallas_q4_mm.py's (`partial_product`): split-plane nibbles
through i32, the -8 and the bf16-rounded block scale in f32, bf16 operands to
the MXU, f32 accumulation.

Names: the two pallas_calls are `moe_grouped_q4_gu` and `moe_grouped_q4_down`
(the trace's readers look for `moe_grouped_q4`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..platform_env import interpret_requested
from ..quants import QTensor
from .matmul import LayerOf
from .pallas_q4_mm import (VMEM_LIMIT, partial_product, pick_bk, scales_f32,
                           scales_shape)

_BLOCK_BYTES = 1 << 20  # packed bytes of one weight block a grid step


def _pick_bn(n: int, kh: int) -> int | None:
    """Output columns a grid step: the largest lane-aligned divisor of n
    whose (bn, K/2) packed block stays under _BLOCK_BYTES (a grid step costs
    a fixed third of a microsecond and a tile's rows are few, so few large
    steps: 768 and 2560 at SmallThinker's widths, 512 and 128 at Mixtral's;
    worth 3 to 10 % against blocks of 256 rows, PERF.md section 6, PR 29);
    n itself where it has no such divisor and is small (a toy, a TP slice)."""
    for m in range(1, n // 128 + 1):
        if n % m == 0 and (n // m) % 128 == 0 and (n // m) * kh <= _BLOCK_BYTES:
            return n // m
    if n % 128 == 0:
        return 128
    return n if n <= 1024 else None


def grouped_supported(w, n_out: int, interpret: bool) -> bool:
    """Whether a stacked (E, rows, K) weight, or a `LayerOf` a layer of an
    (L, E, rows, K) one, can go through these kernels: split-plane Q40 in
    one self-contained pack, an output width that tiles, and on the chip
    lane-aligned blocks (the interpreter takes any)."""
    lead = 0
    if isinstance(w, LayerOf):
        w, lead = w.stack, len(w.at)
    if not isinstance(w, QTensor) or w.layout != "i4p" or w.groups != 1:
        return False
    if lead > 1 or w.data.ndim != 3 + lead:
        return False
    kh = w.data.shape[-1]
    if (bn := _pick_bn(n_out, kh)) is None:
        return False
    return interpret or (kh % 128 == 0 and bn % 128 == 0)


# hot-path: traced
def _act_f32(a, act: str):
    """Epilogue activation on the f32 accumulator, formulas matching
    ops/kernels.py bit-for-bit in f32 (silu / tanh-approx GELU / ReLU)."""
    if act == "silu":
        return a / (1.0 + jnp.exp(-a))
    if act == "relu":
        return jnp.maximum(a, 0.0)
    c = 0.79788456080286535587989211986876  # sqrt(2/pi), as gelu_tanh
    return 0.5 * a * (1.0 + jnp.tanh(c * a * (1.0 + 0.044715 * a * a)))


def _gu_kernel(te_ref, nu_ref, at_ref, xlo_ref, xhi_ref, up_ref, sup_ref,
               gate_ref, sgate_ref, o_ref, sfu_ref, sfg_ref, *, act, bk):
    @pl.when(pl.program_id(1) < nu_ref[0])
    def _():
        sfu_ref[:] = scales_f32(sup_ref)
        sfg_ref[:] = scales_f32(sgate_ref)
        up = partial_product(xlo_ref, xhi_ref, up_ref, sfu_ref, bk)
        gate = partial_product(xlo_ref, xhi_ref, gate_ref, sfg_ref, bk)
        o_ref[:] = (up * _act_f32(gate, act)).astype(o_ref.dtype)


def _down_kernel(te_ref, nu_ref, at_ref, xlo_ref, xhi_ref, w_ref, s_ref,
                 o_ref, sf_ref, *, bk):
    @pl.when(pl.program_id(1) < nu_ref[0])
    def _():
        sf_ref[:] = scales_f32(s_ref)
        o_ref[:] = partial_product(xlo_ref, xhi_ref, w_ref, sf_ref,
                                   bk).astype(o_ref.dtype)


def _row_block(i, nu_ref):
    # tiles past the last used one name its blocks again: nothing is fetched
    # for them, and the output block they leave alone is written back once
    return jnp.minimum(i, jnp.maximum(nu_ref[0] - 1, 0))


def _x_specs(tile, kh):
    return [pl.BlockSpec((tile, kh),
                         lambda n, i, te, nu, at: (_row_block(i, nu), 0)),
            pl.BlockSpec((tile, kh),
                         lambda n, i, te, nu, at: (_row_block(i, nu), 1))]


def _w_specs(bn, kh, cols, off, j):
    """One expert's (bn, K/2) packed block and the (bn, cols) block of its
    scales' plane (cols: K/32 in whole lane tiles, as stored) out of the
    (L, E, rows, ...) stack: the expert named by the tile, the layer by
    the call's j-th prefetched layer; `off` shifts the row block (the gate
    half of a merged [up|gate] stack)."""
    def block(n, i, te, nu, at):
        return (at[j], te[i], n + off, 0)

    return [pl.BlockSpec((None, None, bn, kh), block),
            pl.BlockSpec((None, None, bn, cols), block)]


def _call(kernel, name, x, operands, w_specs, n_out, bn, tile, out_dtype,
          tile_expert, n_used, at, interpret):
    cap, k = x.shape
    kh = k // 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # (tile_expert, n_used, the weights' layers)
        grid=(n_out // bn, cap // tile),
        in_specs=_x_specs(tile, kh) + w_specs,
        out_specs=pl.BlockSpec(
            (tile, bn), lambda n, i, te, nu, at: (_row_block(i, nu), n)),
        # a weight's decoded scales (pallas_q4_mm.scales_f32), one each
        scratch_shapes=[scales_shape(bn, s.shape[-1])
                        for s in operands[1::2]],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec, name=name,
        out_shape=jax.ShapeDtypeStruct((cap, n_out), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(tile_expert, n_used, at, x, x, *operands)


@functools.partial(jax.jit,
                   static_argnames=("tile", "act", "merged", "interpret"))
def _moe_grouped_q4(rows, tile_expert, n_used, layers, up, sup, gate, sgate,
                    down, sdown, *, tile, act, merged, interpret):
    """rows (C, d) sorted by expert -> (C, d): down(act(gate x) * up x) of
    each tile's expert. up/gate (L, E, h, d/2) packed with the scales'
    plane (L, E, h, d/32 in whole lane tiles) — the same array twice for a
    merged [up|gate] stack of 2h rows — and down (L, E, d, h/2); `layers`
    (3,): the layer to read of up, gate and down."""
    hidden = up.shape[2] // (2 if merged else 1)
    d_out = down.shape[2]
    kh = rows.shape[1] // 2
    bn_h, bn_d = _pick_bn(hidden, kh), _pick_bn(d_out, hidden // 2)
    nu = jnp.reshape(n_used, (1,)).astype(jnp.int32)
    te = tile_expert.astype(jnp.int32)
    h = _call(functools.partial(_gu_kernel, act=act, bk=pick_bk(kh)),
              "moe_grouped_q4_gu", rows, (up, sup, gate, sgate),
              _w_specs(bn_h, kh, sup.shape[-1], 0, 0)
              + _w_specs(bn_h, kh, sgate.shape[-1],
                         hidden // bn_h if merged else 0, 1),
              hidden, bn_h, tile, rows.dtype, te, nu, layers[:2], interpret)
    return _call(functools.partial(_down_kernel, bk=pick_bk(hidden // 2)),
                 "moe_grouped_q4_down", h, (down, sdown),
                 _w_specs(bn_d, hidden // 2, sdown.shape[-1], 0, 0),
                 d_out, bn_d, tile, rows.dtype, te, nu, layers[2:], interpret)


def _whole(w):
    """(packed stack, scales, layer) of a `LayerOf`; a layer's own (E, ...)
    stack is a stack of one."""
    if isinstance(w, LayerOf):
        return w.stack.data, w.stack.scales, w.at[0]
    return w.data[None], w.scales[None], 0


def moe_grouped_q4(rows, tile_expert, n_used, up, gate, down, *, tile: int,
                   act: str, interpret: bool | None = None):
    """The expert FFN of sorted, tile-padded rows (see the module docstring).
    up, gate, down: a `LayerOf` each (the stack over layers and the layer to
    read) or a QTensor (E, rows, K). `up is gate` says the stack is the
    merged [up|gate] one. Rows of tiles past `n_used` come back unwritten."""
    if interpret is None:
        interpret = interpret_requested()
    (u, su, lu), (g, sg, lg), (d, sd, ld) = map(_whole, (up, gate, down))
    layers = jnp.stack([jnp.asarray(i, jnp.int32) for i in (lu, lg, ld)])
    return _moe_grouped_q4(rows, tile_expert, n_used, layers, u, su, g, sg,
                           d, sd, tile=tile, act=act, merged=up is gate,
                           interpret=interpret)
