"""Ring attention: causal GQA attention over a sequence-sharded KV cache.

Long-context sequence parallelism — absent from the reference, which keeps the FULL
seqLen KV slice resident per node and only shards heads (SURVEY.md §5: KvCacheSlice,
src/commands.cpp:97-102, per-head quadratic loop llama2-tasks.cpp:62-93). Here the cache's
sequence axis is sharded over the mesh's `sp` axis, so max context scales linearly with
devices; each device attends its local KV block, and the blocks rotate around the ring
with `ppermute` while a numerically stable online softmax (flash-attention-style
m/denominator carry) accumulates the output. Compute and ICI transfer overlap: while a
device contracts block r it can already be sending/receiving block r+1.

Every device holds the full Q (queries are small; KV is what grows with context), so the
output is replicated over sp and no final gather is needed. Combines with TP head
sharding orthogonally: cache is (B, hk/tp, S/sp, hs) on a (dp, sp, tp) mesh.

The sequence layout is STRIPED (device i's slot j holds position j*sp + i), which
spreads the live context evenly so static window buckets bound each rotation to
ceil(window/sp) columns — decode ICI/HBM then tracks the live context, not the
allocated seq_len (contiguous shards would concentrate the live prefix on the
low-index devices, and every rotation would move the FULL shard).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


def _block_attend(qg, k_blk, v_blk, positions, col_offset, col_stride=1,
                  live_end=None):
    """Masked scores + unnormalized accumulation for one KV block.

    qg: (B, hk, g, T, hs) f32; k_blk/v_blk: (B, hk, Sb, hs); positions: (T,) absolute
    query positions. Block column j sits at absolute position
    col_offset + col_stride*j — a striped shard is (owner, sp), the chunk's
    own block (chunk_start, 1). live_end, if given, additionally masks columns
    at positions >= live_end — cache blocks are attended only over COMMITTED
    rows (the current chunk arrives as its own register block instead).
    Returns (m (…, T), l (…, T), acc (…, T, hs)) partial softmax stats.
    """
    sb = k_blk.shape[2]
    hs = qg.shape[-1]
    scale = 1.0 / math.sqrt(hs)
    scores = jnp.einsum("bkgtd,bksd->bkgts", qg,
                        k_blk.astype(jnp.float32)) * scale  # (B, hk, g, T, Sb)
    col_pos = col_offset + col_stride * jnp.arange(sb)  # absolute column positions
    valid = col_pos[None, :] <= positions[:, None]  # (T, Sb) causal
    if live_end is not None:
        valid = valid & (col_pos[None, :] < live_end)
    scores = jnp.where(valid[None, None, None], scores, _NEG_INF)
    m = jnp.max(scores, axis=-1)  # (B, hk, g, T)
    # guard fully-masked blocks: exp(NEG_INF - NEG_INF) would be 1, so clamp m
    safe_m = jnp.maximum(m, _NEG_INF / 2)
    p = jnp.exp(scores - safe_m[..., None])
    p = jnp.where(valid[None, None, None], p, 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bkgts,bksd->bkgtd", p, v_blk.astype(jnp.float32))
    return m, l, acc


def _combine(m1, l1, acc1, m2, l2, acc2):
    """Merge two partial softmax accumulations (flash-attention combine)."""
    m = jnp.maximum(m1, m2)
    safe_m = jnp.maximum(m, _NEG_INF / 2)
    a1 = jnp.exp(m1 - safe_m)
    a2 = jnp.exp(m2 - safe_m)
    return m, l1 * a1 + l2 * a2, acc1 * a1[..., None] + acc2 * a2[..., None]


def ring_attention(q: jax.Array, k_shard: jax.Array, v_shard: jax.Array,
                   positions: jax.Array, *, axis_name: str, axis_size: int,
                   live_end: jax.Array | None = None,
                   chunk: tuple[jax.Array, jax.Array, jax.Array] | None = None,
                   window_slots: int | None = None) -> jax.Array:
    """Causal GQA attention of T query tokens against a sequence-sharded cache.

    q: (B, T, hq, hs) replicated over sp; k_shard/v_shard: (B, hk, S/sp, hs), the
    local sequence shard, striped: device i's local slot j holds absolute position
    j*axis_size + i. The live context occupies the first ceil(pos/sp) slots of
    EVERY shard, so with a static window bucket W covering pos, only
    window_slots = ceil(W/sp) slots participate — each ring rotation moves
    W/sp columns instead of S/sp, bounding both ICI and HBM per step by the
    LIVE context (the sp analog of the dense path's attn_window).

    The cache holds only COMMITTED rows (positions < live_end == start_pos); the
    current chunk's K/V ride in as `chunk=(k_c (B, hk, T, hs), v_c, chunk_start)`
    and are attended as one extra register block folded into the same online
    softmax — no cache write happens inside the step at all.

    Returns (B, T, hq*hs), replicated over sp.
    """
    b, t, hq, hs = q.shape
    _, hk, sb, _ = k_shard.shape
    g = hq // hk
    if window_slots is not None and window_slots < sb:
        k_shard = k_shard[:, :, :window_slots]
        v_shard = v_shard[:, :, :window_slots]
        sb = window_slots
    # (B, hk, g, T, hs) — block-attend subscripts are head-major
    qg = jnp.moveaxis(q.reshape(b, t, hk, g, hs), 1, 3).astype(jnp.float32)

    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i - 1) % axis_size) for i in range(axis_size)]  # send left, recv right

    m = jnp.full((b, hk, g, t), _NEG_INF, jnp.float32)
    l = jnp.zeros((b, hk, g, t), jnp.float32)
    acc = jnp.zeros((b, hk, g, t, hs), jnp.float32)
    k_blk, v_blk = k_shard, v_shard
    for r in range(axis_size):
        owner = (idx + r) % axis_size  # whose shard I currently hold
        mb, lb, ab = _block_attend(qg, k_blk, v_blk, positions, owner,
                                   axis_size, live_end=live_end)
        m, l, acc = _combine(m, l, acc, mb, lb, ab)
        if r + 1 < axis_size:
            k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
            v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
    if chunk is not None:
        k_c, v_c, chunk_start = chunk
        mb, lb, ab = _block_attend(qg, k_c, v_c, positions, chunk_start)
        m, l, acc = _combine(m, l, acc, mb, lb, ab)
    out = acc / jnp.maximum(l, 1e-30)[..., None]  # (B, hk, g, T, hs)
    out = jnp.moveaxis(out, 3, 1)  # (B, T, hk, g, hs)
    return out.reshape(b, t, hq * hs).astype(q.dtype)


def commit_kv_rows_sharded(k_cache: jax.Array, v_cache: jax.Array,
                           k_rows: jax.Array, v_rows: jax.Array,
                           start_pos: jax.Array, *, axis_name: str,
                           axis_size: int) -> tuple[jax.Array, jax.Array]:
    """The commit for sequence-sharded (striped) caches: write ALL layers' new
    rows in one tiny masked window write per cache.

    caches: (L, B, hk, Sb, hs) local shards; rows: (L, B, hk, T, hs) (every sp
    member computed identical rows — activations are sp-replicated). Member m's
    local slot j holds absolute position j*sp + m (see ring_attention), so m
    takes the chunk positions with p % sp == m, landing in a ceil(T/sp)(+1)
    slot window; a per-slot hit mask keeps what the window holds besides.
    Total write traffic is O(L·T) rows — the sp counterpart of forward()'s
    top-level dynamic_update_slice."""
    t = k_rows.shape[3]
    sb = k_cache.shape[3]
    idx = jax.lax.axis_index(axis_name)
    sp = axis_size
    wl = min((t - 1) // sp + 2, sb)  # slot-window width (static)
    j0 = jnp.clip(start_pos // sp, 0, sb - wl)
    slots = j0 + jnp.arange(wl)
    src = slots * sp + idx - start_pos  # which chunk token lands in each slot
    hit = (src >= 0) & (src < t)
    src_c = jnp.clip(src, 0, t - 1)

    def write(cache, rows):
        rows = rows.astype(cache.dtype)
        cur = jax.lax.dynamic_slice(
            cache, (0, 0, 0, j0, 0), (*cache.shape[:3], wl, cache.shape[4]))
        gathered = jnp.take(rows, src_c, axis=3)
        val = jnp.where(hit[None, None, None, :, None], gathered, cur)
        return jax.lax.dynamic_update_slice(cache, val, (0, 0, 0, j0, 0))

    return write(k_cache, k_rows), write(v_cache, v_rows)
