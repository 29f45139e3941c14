"""Paged attention over the device block pool — decode AND speculative verify.

The production counterpart of the device-resident paged KV refactor
(docs/PAGED_KV.md): KV lives in a (L, N, hk, bt, hs) block pool and each
batch row's BLOCK TABLE maps virtual positions to pool blocks. Two readers
live here:

- `paged_gather_kv` — the XLA fallback: gather the table's blocks into a
  contiguous (B, hk, win, hs) buffer, exactly the dense deferred-write
  window layout. models/forward.py feeds it to the SAME gqa_attention code
  path as the dense cache, so on the CPU mesh the paged engine is
  bit-identical to the dense engine (the token-identity acceptance bar).

- `paged_attention` — the Pallas kernel: grid (B, hk, n_blocks); the block
  table rides in as a SCALAR-PREFETCH argument so each grid step's
  BlockSpec index_map DMAs exactly (layer, table[b, j], h) — no gather, no
  materialized window, the cache bytes move straight pool→VMEM. A
  flash-attention (m, l, acc) carry in VMEM scratch merges the blocks; the
  current chunk's uncommitted K/V (T = 1 for the decode scan, T = 1+k for
  the speculative verify dispatch) folds in at the last grid step with an
  in-chunk causal mask. f16 never appears (Mosaic cannot lower f16 refs):
  cache blocks load in their storage dtype and are cast to f32 in-kernel.

Numerics: the kernel's blockwise online softmax is mathematically exact but
not bit-identical to the one-shot XLA softmax; it is the TPU path
(`use_pallas` engines / DLT_PAGED_KERNEL=1), with interpret mode on CPU for
parity tests (perf/paged_attn_bench.py gates max|Δ|)."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..platform_env import interpret_requested

_NEG = -1e30  # f32 mask value; exp(_NEG - max) == 0 exactly in f32


def paged_gather_kv(kc, vc, layer_idx, tables, n_read: int):
    """Gather the first `n_read` table entries' blocks of one layer into
    contiguous (B, hk, n_read*bt, hs) K/V buffers (virtual-position order:
    table entry j supplies positions [j*bt, (j+1)*bt)).

    kc/vc: (L, N, hk, bt, hs) stacked pools; layer_idx: i32 scalar (traced —
    called inside the layer scan); tables: (B, W >= n_read) i32."""
    l, n, hk, bt, hs = kc.shape
    kl = jax.lax.dynamic_slice(kc, (layer_idx, 0, 0, 0, 0),
                               (1, n, hk, bt, hs))[0]
    vl = jax.lax.dynamic_slice(vc, (layer_idx, 0, 0, 0, 0),
                               (1, n, hk, bt, hs))[0]
    tbl = tables[:, :n_read]  # (B, n_read)
    b = tbl.shape[0]

    def grab(pool_layer):
        g = pool_layer[tbl]  # (B, n_read, hk, bt, hs)
        return jnp.transpose(g, (0, 2, 1, 3, 4)).reshape(
            b, hk, n_read * bt, hs)

    return grab(kl), grab(vl)


def _kernel(li_ref, tbl_ref, len_ref, q_ref, kn_ref, vn_ref, kb_ref, vb_ref,
            o_ref, m_ref, l_ref, acc_ref, *, bt, nb, t, g):
    """Grid step (b, h, j): one kv head's queries against table block j.

    Blocks: q (1, 1, t*g, hs) f32 | k_new/v_new (1, 1, t, hs) | kb/vb
    (1, 1, 1, bt, hs) cache dtype | out (1, 1, t*g, hs) f32. Scratch: the
    flash (m, l, acc) carry. li/tbl/len are scalar-prefetched (li and tbl
    are consumed by the BlockSpec index_maps; len masks in-body)."""
    b = pl.program_id(0)
    j = pl.program_id(2)
    q = q_ref[0, 0]  # (t*g, hs) f32
    scale = jnp.float32(1.0 / math.sqrt(q.shape[-1]))

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    kb = kb_ref[0, 0, 0].astype(jnp.float32)  # (bt, hs)
    vb = vb_ref[0, 0, 0].astype(jnp.float32)
    # virtual position of block row r is j*bt + r; rows at/after the row's
    # committed length are uncommitted garbage (scratch writes, CoW slack)
    pos = jax.lax.broadcasted_iota(jnp.int32, (bt, 1), 0) + j * bt
    live = pos < len_ref[b]
    vb = jnp.where(live, vb, 0.0)  # NaN guard: 0 * garbage stays finite
    s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(live.reshape(1, bt), s, _NEG)  # (t*g, bt)
    m_new = jnp.maximum(m_ref[:], jnp.max(s, axis=1, keepdims=True))
    a = jnp.exp(m_ref[:] - m_new)
    p = jnp.exp(s - m_new)
    l_ref[:] = l_ref[:] * a + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[:] = acc_ref[:] * a + jax.lax.dot_general(
        p, vb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[:] = m_new

    @pl.when(j == nb - 1)
    def _finalize():
        # fold the current chunk's uncommitted K/V: query row r (= ti*g+gi)
        # sits at position len+ti and may attend chunk key tau iff tau <= ti
        kn = kn_ref[0, 0].astype(jnp.float32)  # (t, hs)
        vn = vn_ref[0, 0].astype(jnp.float32)
        s_new = jax.lax.dot_general(q, kn, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
        ti = jax.lax.broadcasted_iota(jnp.int32, (t * g, t), 0) // g
        tau = jax.lax.broadcasted_iota(jnp.int32, (t * g, t), 1)
        s_new = jnp.where(tau <= ti, s_new, _NEG)
        m_f = jnp.maximum(m_ref[:], jnp.max(s_new, axis=1, keepdims=True))
        a_f = jnp.exp(m_ref[:] - m_f)
        p_new = jnp.exp(s_new - m_f)
        denom = l_ref[:] * a_f + jnp.sum(p_new, axis=1, keepdims=True)
        out = acc_ref[:] * a_f + jax.lax.dot_general(
            p_new, vn, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_ref[0, 0] = out / denom


@functools.partial(jax.jit,
                   static_argnames=("n_read", "interpret"))
def paged_attention(q, kc, vc, k_new, v_new, tables, lengths, layer_idx, *,
                    n_read: int, interpret: bool | None = None):
    """Paged attention of T chunk queries per row against block-table KV.

    q: (B, T, hq, hs) f32/bf16 — T = 1 (decode scan step) or 1+k (verify).
    kc/vc: (L, N, hk, bt, hs) FULL stacked pools (any dtype); only the
        (layer, tables[b, j], h) blocks are ever moved on-chip.
    k_new/v_new: (B, hk, T, hs) — the chunk's uncommitted K/V.
    tables: (B, W) i32 block table (first n_read entries are read).
    lengths: (B,) i32 committed length (row's start position).
    layer_idx: i32 scalar. n_read: static read-block count (the window
        bucket divided by bt — callers bucket it so shapes never vary per
        request, analysis/compile_audit.py).
    Returns (B, T, hq, hs) f32.
    """
    if interpret is None:
        interpret = interpret_requested()
    b, t, hq, hs = q.shape
    l, n, hk, bt, hs2 = kc.shape
    assert hs2 == hs and k_new.shape == (b, hk, t, hs), (q.shape, kc.shape,
                                                         k_new.shape)
    g = hq // hk
    nb = n_read
    qr = q.astype(jnp.float32).reshape(b, t, hk, g, hs)
    qr = jnp.transpose(qr, (0, 2, 1, 3, 4)).reshape(b, hk, t * g, hs)
    tbl_flat = tables[:, :nb].reshape(-1).astype(jnp.int32)  # (B*nb,)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # (layer_idx_arr, tbl_flat, lengths)
        grid=(b, hk, nb),
        in_specs=[
            pl.BlockSpec((1, 1, t * g, hs),
                         lambda bi, h, j, li, tb, ln: (bi, h, 0, 0)),
            pl.BlockSpec((1, 1, t, hs),
                         lambda bi, h, j, li, tb, ln: (bi, h, 0, 0)),
            pl.BlockSpec((1, 1, t, hs),
                         lambda bi, h, j, li, tb, ln: (bi, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, bt, hs),
                         lambda bi, h, j, li, tb, ln:
                         (li[0], tb[bi * nb + j], h, 0, 0)),
            pl.BlockSpec((1, 1, 1, bt, hs),
                         lambda bi, h, j, li, tb, ln:
                         (li[0], tb[bi * nb + j], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, t * g, hs),
                               lambda bi, h, j, li, tb, ln: (bi, h, 0, 0)),
        scratch_shapes=[pltpu.VMEM((t * g, 1), jnp.float32),
                        pltpu.VMEM((t * g, 1), jnp.float32),
                        pltpu.VMEM((t * g, hs), jnp.float32)],
    )
    body = functools.partial(_kernel, bt=bt, nb=nb, t=t, g=g)
    out = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hk, t * g, hs), jnp.float32),
        interpret=interpret,
    )(jnp.asarray([layer_idx], jnp.int32), tbl_flat,
      jnp.asarray(lengths, jnp.int32), qr, k_new, v_new, kc, vc)
    # (B, hk, t*g, hs) -> (B, T, hq, hs)
    out = out.reshape(b, hk, t, g, hs)
    return jnp.transpose(out, (0, 2, 1, 3, 4)).reshape(b, t, hq, hs)


def paged_attention_xla(q, kc, vc, k_new, v_new, tables, lengths, layer_idx,
                        *, n_read: int, virtual_len: int | None = None):
    """XLA reference for the kernel (and the bench oracle): gather the
    table's blocks into the dense window layout and run the SAME
    gqa_attention the dense cache path runs — bit-identical to a dense
    engine whose window equals n_read*bt. Shapes as paged_attention."""
    from .attention import gqa_attention

    b, t, hq, hs = q.shape
    bt = kc.shape[3]
    win = n_read * bt
    s_virtual = virtual_len if virtual_len is not None else win
    kw, vw = paged_gather_kv(kc, vc, layer_idx, tables, n_read)
    slot = jnp.arange(win)
    lengths = jnp.asarray(lengths, jnp.int32)
    slot_pos = jnp.where(slot[None, :] < lengths[:, None], slot[None, :],
                         s_virtual + 1)
    key_pos = jnp.concatenate(
        [slot_pos, lengths[:, None] + jnp.arange(t)[None, :]], axis=1)
    kfull = jnp.concatenate([kw, jnp.asarray(k_new, kw.dtype)], axis=2)
    vfull = jnp.concatenate([vw, jnp.asarray(v_new, vw.dtype)], axis=2)
    positions = lengths[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    out = gqa_attention(q.astype(jnp.float32), kfull, vfull, positions,
                        key_positions=key_pos)
    return out.reshape(b, t, hq, hs)
