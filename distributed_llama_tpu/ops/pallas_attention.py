"""Fused decode attention — windowed cache read + GQA scores + softmax + AV in one
kernel, reading the cache window STRAIGHT out of the stacked (L, B, hk, S, hs)
buffers.

The XLA path (models/forward.py contiguous branch + ops/attention.py) materializes a
(B, hk, win, hs) dynamic-slice of each cache per layer before attention — at 7B /
window 256 that is ~134 MB/step of slice traffic plus separate softmax fusions (the
`dynamic-slice_bitcast_fusion` + `convert_reduce_fusion` lines in the round-4
profile, ~4-5 ms/step together). This kernel takes the FULL stacked caches as
operands and lets the Pallas pipeline DMA exactly the (layer_idx, 0, h, 0:win)
block per kv-head grid step — the layer index rides in as a scalar-prefetch
argument, so nothing is sliced or copied in XLA.

The reference's counterpart is the per-head attention loop at
src/llama2-tasks.cpp:54-94 (dot q·k over 0..pos, softmax, weighted v sum); the
windowed-read semantics match ops/attention.gqa_attention with models/forward.py's
key layout: window slots are valid iff slot < pos, and the current token's k/v
(not yet committed to the cache) attends from registers.

Decode-only by design: T = 1 query row, scalar pos (the host-loop/device-loop hot
path). Prefill and batched/per-row paths keep the XLA route, which amortizes fine
at T > 1.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..platform_env import interpret_requested

_NEG = -1e30  # f32 mask value; exp(_NEG - max) == 0 exactly in f32


def _kernel(pos_ref, q_ref, kn_ref, vn_ref, kw_ref, vw_ref, o_ref):
    """Grid step = one kv head. Blocks:
    q (1, g, hs) f32 | k_new/v_new (1, 1, hs) | kw/vw (1, 1, win, hs) cache dtype |
    out (1, g, hs) f32. pos is scalar-prefetched."""
    pos = pos_ref[0]
    q = q_ref[0]  # (g, hs) f32
    kw = kw_ref[0, 0].astype(jnp.float32)  # (win, hs)
    vw = vw_ref[0, 0].astype(jnp.float32)
    kn = kn_ref[0].astype(jnp.float32)  # (1, hs) current token
    vn = vn_ref[0].astype(jnp.float32)
    win = kw.shape[0]
    scale = jnp.float32(1.0 / math.sqrt(q.shape[-1]))

    s_old = jax.lax.dot_general(q, kw, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale  # (g, win)
    slot = jax.lax.broadcasted_iota(jnp.int32, s_old.shape, 1)
    s_old = jnp.where(slot < pos, s_old, _NEG)  # committed rows only
    s_new = jnp.sum(q * kn, axis=-1, keepdims=True) * scale  # (g, 1) current token

    m = jnp.maximum(jnp.max(s_old, axis=1, keepdims=True), s_new)  # (g, 1)
    p_old = jnp.exp(s_old - m)  # (g, win); masked slots exp(_NEG - m) == 0
    p_new = jnp.exp(s_new - m)  # (g, 1)
    denom = jnp.sum(p_old, axis=1, keepdims=True) + p_new
    out = jax.lax.dot_general(p_old, vw, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (g, hs)
    out = (out + p_new * vn) / denom
    o_ref[0] = out


def _kernel_tiled(pos_ref, q_ref, kn_ref, vn_ref, kw_ref, vw_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, wt, gw):
    """Window-tiled variant: grid (hk, gw); each step attends one (wt, hs) slice
    of the window with a flash-attention m/l/acc carry in VMEM scratch, so VMEM
    holds one tile regardless of the window (long-context decode keeps the fused
    kernel instead of falling back to the XLA path). The current token's k/v
    fold in at the last tile."""
    j = pl.program_id(1)
    pos = pos_ref[0]
    q = q_ref[0]  # (g, hs) f32
    scale = jnp.float32(1.0 / math.sqrt(q.shape[-1]))

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    kw = kw_ref[0, 0].astype(jnp.float32)  # (wt, hs)
    vw = vw_ref[0, 0].astype(jnp.float32)
    # a trailing partial tile's padded region holds UNSPECIFIED bits; the score
    # mask alone cannot save acc from 0*NaN, so zero the invalid V rows too
    row = jax.lax.broadcasted_iota(jnp.int32, (vw.shape[0], 1), 0) + j * wt
    vw = jnp.where(row < pos, vw, 0.0)
    s = jax.lax.dot_general(q, kw, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale  # (g, wt)
    slot = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * wt
    s = jnp.where(slot < pos, s, _NEG)  # committed rows only; masks tile padding
    # (NaN scores from padded K rows are replaced by _NEG here — jnp.where
    # selects the mask value regardless of NaN)
    m_new = jnp.maximum(m_ref[:], jnp.max(s, axis=1, keepdims=True))
    a = jnp.exp(m_ref[:] - m_new)
    p = jnp.exp(s - m_new)
    l_ref[:] = l_ref[:] * a + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[:] = acc_ref[:] * a + jax.lax.dot_general(
        p, vw, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[:] = m_new

    @pl.when(j == gw - 1)
    def _finalize():
        kn = kn_ref[0].astype(jnp.float32)  # (1, hs) current token
        vn = vn_ref[0].astype(jnp.float32)
        s_new = jnp.sum(q * kn, axis=-1, keepdims=True) * scale  # (g, 1)
        m_f = jnp.maximum(m_ref[:], s_new)
        a_f = jnp.exp(m_ref[:] - m_f)
        p_new = jnp.exp(s_new - m_f)
        denom = l_ref[:] * a_f + p_new
        o_ref[0] = (acc_ref[:] * a_f + p_new * vn) / denom


# per-operand VMEM budget for the single-block kernel; larger windows tile
_FUSED_ONE_BLOCK_LIMIT = 4 << 20
_WT = 2048  # window slots per tile in the tiled kernel


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def fused_decode_attention(q, kc, vc, k_new, v_new, layer_idx, pos, *,
                           window: int, interpret: bool | None = None):
    """One decode token's attention for one layer against the stacked caches.

    q: (hk, g, hs) f32/bf16 — query heads grouped per kv head.
    kc/vc: (L, B=1, hk, S, hs) FULL stacked caches (any dtype); only the
        (layer_idx, 0, h, 0:window) block is ever moved on-chip.
    k_new/v_new: (hk, 1, hs) — the current token's uncommitted k/v.
    layer_idx, pos: i32 scalars. window: static read bound (>= pos+1... the
        current token comes from k_new, so window >= pos suffices).
    Returns (hk, g, hs) f32.
    """
    if interpret is None:
        interpret = interpret_requested()
    hk, g, hs = q.shape
    l, b, hk2, s, hs2 = kc.shape
    assert b == 1 and hk2 == hk and hs2 == hs, (q.shape, kc.shape)
    assert k_new.shape == (hk, 1, hs), k_new.shape
    win = min(window, s)
    one_block = win * hs * jnp.dtype(kc.dtype).itemsize <= _FUSED_ONE_BLOCK_LIMIT

    if one_block:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # (layer_idx_arr, pos_arr)
            grid=(hk,),
            in_specs=[
                pl.BlockSpec((1, g, hs), lambda h, li, po: (h, 0, 0)),
                pl.BlockSpec((1, 1, hs), lambda h, li, po: (h, 0, 0)),
                pl.BlockSpec((1, 1, hs), lambda h, li, po: (h, 0, 0)),
                pl.BlockSpec((1, 1, win, hs), lambda h, li, po: (li[0], h, 0, 0)),
                pl.BlockSpec((1, 1, win, hs), lambda h, li, po: (li[0], h, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, g, hs), lambda h, li, po: (h, 0, 0)),
        )

        def kernel(li_ref, pos_ref, q_ref, kn_ref, vn_ref, kw_ref, vw_ref, o_ref):
            # li_ref is consumed by the BlockSpec index_maps only
            _kernel(pos_ref, q_ref, kn_ref, vn_ref, kw_ref, vw_ref, o_ref)

    else:
        # long-context form: tile the window axis with a flash-attention carry
        wt = min(_WT, win)
        gw = pl.cdiv(win, wt)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(hk, gw),
            in_specs=[
                pl.BlockSpec((1, g, hs), lambda h, j, li, po: (h, 0, 0)),
                pl.BlockSpec((1, 1, hs), lambda h, j, li, po: (h, 0, 0)),
                pl.BlockSpec((1, 1, hs), lambda h, j, li, po: (h, 0, 0)),
                pl.BlockSpec((1, 1, wt, hs),
                             lambda h, j, li, po: (li[0], h, j, 0)),
                pl.BlockSpec((1, 1, wt, hs),
                             lambda h, j, li, po: (li[0], h, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, g, hs), lambda h, j, li, po: (h, 0, 0)),
            scratch_shapes=[pltpu.VMEM((g, 1), jnp.float32),
                            pltpu.VMEM((g, 1), jnp.float32),
                            pltpu.VMEM((g, hs), jnp.float32)],
        )
        body = functools.partial(_kernel_tiled, wt=wt, gw=gw)

        def kernel(li_ref, pos_ref, q_ref, kn_ref, vn_ref, kw_ref, vw_ref,
                   o_ref, m_ref, l_ref, acc_ref):
            body(pos_ref, q_ref, kn_ref, vn_ref, kw_ref, vw_ref, o_ref,
                 m_ref, l_ref, acc_ref)


    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((hk, g, hs), jnp.float32),
        interpret=interpret,
    )(jnp.asarray([layer_idx], jnp.int32), jnp.asarray([pos], jnp.int32),
      q.astype(jnp.float32), k_new, v_new,
      kc.reshape(l, hk, s, hs), vc.reshape(l, hk, s, hs))
