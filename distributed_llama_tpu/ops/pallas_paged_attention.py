"""Paged attention over the device block pool — decode, verify and prefill chunks.

The production counterpart of the device-resident paged KV refactor
(docs/PAGED_KV.md): KV lives in a (L, N, hk, bt, hs) block pool and each
batch row's BLOCK TABLE maps virtual positions to pool blocks. Two readers
live here:

- `paged_gather_kv` — the XLA fallback: gather the table's blocks into a
  contiguous (B, hk, win, hs) buffer, exactly the contiguous caches'
  window layout. models/forward.py feeds it to the SAME gqa_attention code
  path as the dense cache, so on the CPU mesh the paged engine is
  bit-identical to the dense engine (the token-identity acceptance bar).

- `paged_attention` — the Pallas kernel: grid (B,), one row a grid step,
  all hk heads, the rows in order on one core. The pools stay in HBM; the
  block table and the lengths ride in as SCALAR-PREFETCH arguments. A row
  walks its window in steps of 128 keys (P = 128 // bt table blocks, 8 at
  bt 16): the step's blocks are copied pool→VMEM by hand, a block's hk heads
  in one copy, into a double-buffered (hk, P*bt, hs) tile — no gather, no
  materialized window. A row runs ONLY the steps that hold committed keys
  (trip count ceil(length / 128), less the steps wholly behind a sliding
  window): past its length no copy is issued and nothing computed, so a row
  of length 0 runs only the in-chunk fold.
  WHAT PERSISTS ACROSS GRID STEPS (PR 50): the K/V double buffers, their
  DMA semaphores and one SMEM word, the buffer the call's next step lands
  in. While row b computes its last step it starts row b + 1's first copies
  (it reads that row's length and table from the prefetched scalars) into
  the buffer that step is not reading; a row without steps starts them at
  once. So only row 0 waits for a copy nothing hides, and a copy is started
  only where a step will wait for it: the pool's garbage past a length is
  still never moved.
  THE HEADS OF A STEP ARE ONE BATCHED COMPUTATION (PR 50): the scores of a
  group of kv heads are one `dot_general` with the heads as its batch
  dimension, (heads, T*g, 128), and mask, maximum, exp, sum, the product
  against V and the statistics' read and write are made once a group, not
  once a head: independent heads fill the MXU's and the VPU's pipelines
  where a loop over heads ran eight chains of dependent 4-row operations one
  after another (3.3 us a step of 0.5 MB read 0.85, against 0.64 for its
  bytes: `perf/paged_attn_bench.py --cells`). `head_group` takes all hk
  heads where the group's score block is small and a divisor of hk where
  T*g is large, from the call's shape alone. A flash-attention (m, l, acc)
  carry merges the steps; the current chunk's uncommitted K/V (T = 1 for
  the decode scan, 1+k for the speculative verify dispatch, 8 or 64 for a
  prefill chunk) folds in last with an in-chunk causal mask, batched the
  same way. Operands reach the MXU in the dtype they arrive in (bf16 x bf16
  products are exact in f32); statistics, p and the accumulator are f32.
  f16 never appears (Mosaic cannot lower f16 refs).

- `latent_paged_attention` (and `latent_paged_attention_xla`, its twin) — the
  same walk for a LATENT spec's pool, (L, N, 1, bt, W): one row a token that
  is key and value at once (the score is q . row over all W, the value the
  row's first `n_values` entries), every head reading it. Grid (B, T / tq):
  a step takes tq <= 8 chunk positions x all heads as one (tq*H, W) query
  block, so a 64-token chunk of 64 heads is eight blocks of 512 rows and a
  step's rows are copied once for all heads of a block.

Numerics: the kernel's blockwise online softmax is mathematically exact but
not bit-identical to the one-shot XLA softmax; it is the TPU path
(`use_pallas` engines; `paged_kernel=True`), with interpret mode on CPU for
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
_STEP_KEYS = 128  # keys a kernel step covers: one lane-full score tile


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


def pages_per_step(n_read: int, bt: int) -> int:
    """Pool blocks one kernel step covers: as many as make 128 keys (8 at
    bt 16), at most the n_read the window bucket holds, at least one."""
    return max(1, min(n_read, _STEP_KEYS // bt))


def visited_keys(length: int, n_read: int, bt: int, lo: int = 0) -> int:
    """Keys of the pool the kernel visits for a row of committed `length`
    in a window of `n_read` blocks: whole steps up to the one that holds
    the row's last committed key, none past it, never more than the
    window, and none wholly behind `lo`, the lowest key position the row's
    first query reads (a sliding window's lower bound; the step that
    straddles it is visited and masked). Host integers
    (runtime/batch_engine.py counts with it)."""
    step = pages_per_step(n_read, bt) * bt
    end = min(n_read * bt, -(-length // step) * step)
    return max(end - min(lo, length) // step * step, 0)


_GROUP_ROWS = 3072  # query rows (heads x T x g) one batched head group holds


def head_group(t: int, g: int, hk: int) -> int:
    """KV heads a step computes as ONE batched product: the largest divisor
    of hk whose score block stays under `_GROUP_ROWS` query rows, 1.5 MB a
    float32 temporary of (rows, 128), the most that compiles at every
    cell's shape within the default scoped VMEM (g 9 at a 64-token chunk,
    4608 rows for 8 heads, does not). All hk heads at T = 1, 8 and 1 + k
    and at a 64-token chunk up to g 6; four of eight at g 9. On the chip
    the larger group was the faster at every shape tried (a 64-token chunk
    at g 4 / 6 / 9: 0.043 / 0.134 / 0.113 ms a head at a time, 0.030 / 0.068
    / 0.056 by this rule; PERF.md section 6, PR 50). From the call's shape
    only."""
    fit = max(1, _GROUP_ROWS // (t * g))
    return max(d for d in range(1, hk + 1) if hk % d == 0 and d <= fit)


_QK = (((2,), (2,)), ((0,), (0,)))  # (h, r, d) x (h, k, d) -> (h, r, k)
_PV = (((2,), (1,)), ((0,), (0,)))  # (h, r, k) x (h, k, d) -> (h, r, d)


def _kernel(li_ref, tbl_ref, len_ref, win_ref, q_ref, kn_ref, vn_ref, k_hbm,
            v_hbm, o_ref, kbuf, vbuf, sem, m_ref, l_ref, turn_ref, *, bt, nb,
            pp, t, g, hg, windowed, head_size=None):
    """Grid step b: one row's queries, all hk kv heads, against the steps
    of pp pool blocks that hold its committed keys.

    Blocks: q (1, hk, t*g, hs) | k_new/v_new (1, hk, t, hs) | out
    (1, hk, t*g, hs) f32, the flash accumulator until the last line. k_hbm/
    v_hbm are the whole pools, left in HBM. Scratch, which PERSISTS from one
    grid step to the next (the grid is sequential): kbuf/vbuf (2, hk, pp*bt,
    hs) double buffers in the pool dtype, sem (2, 2) DMA semaphores (k|v,
    buffer), the flash (m, l) statistics (hk, t*g, 1), and turn (1,) in
    SMEM, the buffer the NEXT step of the call lands in: a step's buffer
    follows the steps the call has run, not the step's index in its row, so
    that row b can start row b + 1's first copies into the buffer its own
    last step is not reading and the two rows never meet in one. li/tbl/len
    are scalar-prefetched, and so is win, this layer's sliding window (0:
    none; compiled in only where the model has one, `windowed`): query ti of
    the row, at position len + ti, reads keys above len + ti - win, so the
    steps wholly under the FIRST query's bound are not run and the others
    mask. `hg` heads are computed at once (`head_group`)."""
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    hk, sk, hs = kbuf.shape[1:]
    li = li_ref[0]
    scale = jnp.float32(1.0 / math.sqrt(head_size or hs))
    if windowed:
        win = win_ref[0]
        # key position > qpos - win; no window: every key passes
        reach = jnp.where(win > 0, win, jnp.int32(1 << 30))

    def steps_of(length):
        """[first, end) of the steps a row of `length` runs: those that hold
        a committed key (a row of length 0 runs none), less the ones wholly
        behind its first query's window."""
        n_live = jnp.minimum((length + sk - 1) // sk, -(-nb // pp))
        if not windowed:
            return 0, n_live
        return (jnp.minimum(jnp.maximum(length - reach + 1, 0) // sk, n_live),
                n_live)

    length = len_ref[b]
    j0, n_live = steps_of(length)
    if windowed:
        # query row r = ti * g + gi sits at position length + r // g
        qpos = length + jax.lax.broadcasted_iota(jnp.int32, (t * g, 1), 0) // g
    # the MXU takes q and K as they arrive: bf16 x bf16 with f32 accumulation
    # gives the products an upcast would; any f32 operand makes the dot f32
    dt = jnp.promote_types(q_ref.dtype, kbuf.dtype)

    def copies(r, j, buf):
        """Step j of row r: its pp pages, all heads of a page in one copy,
        landing at 16-row offsets of one (pp*bt, hs) tile a head. A page past
        the window (nb not a multiple of pp) re-reads the window's last one;
        its keys sit past every length and mask out."""
        out = []
        for i in range(pp):
            page = tbl_ref[r * nb + jnp.minimum(j * pp + i, nb - 1)]
            rows = pl.ds(i * bt, bt)
            out.append(pltpu.make_async_copy(
                k_hbm.at[li, page], kbuf.at[buf, :, rows, :], sem.at[0, buf]))
            out.append(pltpu.make_async_copy(
                v_hbm.at[li, page], vbuf.at[buf, :, rows, :], sem.at[1, buf]))
        return out

    def start(r, j, buf):
        for c in copies(r, j, buf):
            c.start()

    @pl.when(b == 0)
    def _first_row():  # the only row that starts its own first copies
        turn_ref[0] = 0

        @pl.when(n_live > j0)
        def _():
            start(0, j0, 0)

    first = turn_ref[0]  # where this row's first step was (or would be) sent
    turn_ref[0] = (first + n_live - j0) % 2
    # the next row that exists: its first copies are started HERE, during
    # this row's last step (at once, if this row has no step), so that the
    # row itself only waits. A row without steps is sent nothing and passes
    # the duty on; nothing is started that no step waits for.
    nxt = jnp.minimum(b + 1, n_rows - 1)
    nj0, n_end = steps_of(len_ref[nxt])
    feeds_next = (b + 1 < n_rows) & (n_end > nj0)

    @pl.when(feeds_next & (n_live == j0))
    def _():
        start(nxt, nj0, first)

    m_ref[:] = jnp.full_like(m_ref, _NEG)
    l_ref[:] = jnp.zeros_like(l_ref)
    o_ref[:] = jnp.zeros_like(o_ref)

    def by_group(attend):
        """`attend(heads)` over the hk heads, hg at once."""
        if hg == hk:
            attend(slice(None))
            return

        def group(i, c):
            attend(pl.ds(i * hg, hg))
            return c

        jax.lax.fori_loop(0, hk // hg, group, 0)

    def step(j, carry):
        buf = (first + j - j0) % 2

        @pl.when(j + 1 < n_live)
        def _():
            start(b, j + 1, 1 - buf)

        @pl.when(feeds_next & (j + 1 == n_live))
        def _():
            start(nxt, nj0, 1 - buf)

        for c in copies(b, j, buf):
            c.wait()
        # keys at/after the row's committed length are uncommitted garbage
        # (scratch writes, CoW slack): only the last live step has any. One
        # iota a layout: reshaping the (sk, 1) mask to (1, sk) in the kernel
        # cost Mosaic 27 MB of VMEM and a minute of compile at T=64
        left = length - j * sk
        live = jax.lax.broadcasted_iota(jnp.int32, (1, sk), 1) < left
        live_v = jax.lax.broadcasted_iota(jnp.int32, (sk, 1), 0) < left
        ok = live
        if windowed:
            kpos = j * sk + jax.lax.broadcasted_iota(jnp.int32, (1, sk), 1)
            ok = live & (kpos > qpos - reach)  # (t*g, sk)

        def attend(heads):
            s = jax.lax.dot_general(
                q_ref[0, heads].astype(dt), kbuf[buf, heads].astype(dt), _QK,
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(ok, s, _NEG)  # (hg, t*g, sk)
            # NaN guard: 0 * garbage stays finite
            vb = jnp.where(live_v, vbuf[buf, heads].astype(jnp.float32), 0.0)
            m_old = m_ref[heads]
            m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
            a = jnp.exp(m_old - m_new)
            p = jnp.exp(s - m_new)
            if windowed:
                # a query whose window lies past this whole step has m_new
                # at _NEG, where exp(s - m_new) would read 1
                p = jnp.where(ok, p, 0.0)
            l_ref[heads] = l_ref[heads] * a + jnp.sum(p, axis=-1,
                                                      keepdims=True)
            o_ref[0, heads] = o_ref[0, heads] * a + jax.lax.dot_general(
                p, vb, _PV, preferred_element_type=jnp.float32)
            m_ref[heads] = m_new

        by_group(attend)
        return carry

    jax.lax.fori_loop(j0, n_live, step, 0)

    # fold the current chunk's uncommitted K/V: query row r (= ti*g+gi)
    # sits at position len+ti and may attend chunk key tau iff tau <= ti
    ti = jax.lax.broadcasted_iota(jnp.int32, (t * g, t), 0) // g
    tau = jax.lax.broadcasted_iota(jnp.int32, (t * g, t), 1)
    chunk_ok = tau <= ti
    if windowed:
        chunk_ok &= ti - tau < reach

    def fold(heads):
        q = q_ref[0, heads].astype(jnp.float32)
        kn = kn_ref[0, heads].astype(jnp.float32)  # (hg, t, hs)
        vn = vn_ref[0, heads].astype(jnp.float32)
        s_new = jax.lax.dot_general(
            q, kn, _QK, preferred_element_type=jnp.float32) * scale
        s_new = jnp.where(chunk_ok, s_new, _NEG)
        m_old = m_ref[heads]
        m_f = jnp.maximum(m_old, jnp.max(s_new, axis=-1, keepdims=True))
        a_f = jnp.exp(m_old - m_f)
        p_new = jnp.exp(s_new - m_f)
        denom = l_ref[heads] * a_f + jnp.sum(p_new, axis=-1, keepdims=True)
        out = o_ref[0, heads] * a_f + jax.lax.dot_general(
            p_new, vn, _PV, preferred_element_type=jnp.float32)
        o_ref[0, heads] = out / denom

    by_group(fold)


@functools.partial(jax.jit, static_argnames=("n_read", "interpret", "name",
                                             "head_size"))
def paged_attention(q, kc, vc, k_new, v_new, tables, lengths, layer_idx, *,
                    n_read: int, interpret: bool | None = None, window=None,
                    name: str | None = None, head_size: int | None = None):
    """Paged attention of T chunk queries per row against block-table KV.

    q: (B, T, hq, hs) in the activation dtype — T = 1 (decode scan step),
        1+k (verify) or a prefill chunk. A bf16 q against a bf16 pool goes
        to the MXU as bf16; any float32 operand makes the dot float32.
    kc/vc: (L, N, hk, bt, hs) FULL stacked pools (any dtype); only the
        (layer, tables[b, j]) blocks under the row's length are moved.
    k_new/v_new: (B, hk, T, hs) — the chunk's uncommitted K/V.
    tables: (B, W) i32 block table (first n_read entries are read).
    lengths: (B,) i32 committed length (row's start position).
    layer_idx: i32 scalar. n_read: static read-block count (the window
        bucket divided by bt — callers bucket it so shapes never vary per
        request, analysis/compile_audit.py).
    window: None, or this layer's sliding window as an i32 scalar (traced:
        it differs by layer inside one scan; 0 = this layer has none).
    name: the `pallas_call`'s name, which a device trace shows: a model with
        kinds of layer names the kernel by kind (`paged_attn_window`,
        `paged_attn_full`); None keeps the kernel's own.
    head_size: the values of a head that are real where hs is a head padded
        with zeros to whole lanes (Mosaic moves a pool block in tiles of 128
        lanes and refuses a slice of 64: heads of 64 lie in a pool 128 wide,
        which is what the chip's tiled memory holds of them anyway): the
        scores' scale is head_size^-0.5. None: hs.
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
    pp = pages_per_step(nb, bt)
    qr = q.reshape(b, t, hk, g, hs)
    qr = jnp.transpose(qr, (0, 2, 1, 3, 4)).reshape(b, hk, t * g, hs)
    tbl_flat = tables[:, :nb].reshape(-1).astype(jnp.int32)  # (B*nb,)

    def row(bi, li, tb, ln, wn):
        return (bi, 0, 0, 0)

    windowed = window is not None
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # (layer_idx_arr, tbl_flat, lengths, window)
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, hk, t * g, hs), row),
            pl.BlockSpec((1, hk, t, hs), row),
            pl.BlockSpec((1, hk, t, hs), row),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, hk, t * g, hs), row),
        scratch_shapes=[pltpu.VMEM((2, hk, pp * bt, hs), kc.dtype),
                        pltpu.VMEM((2, hk, pp * bt, hs), vc.dtype),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.VMEM((hk, t * g, 1), jnp.float32),
                        pltpu.VMEM((hk, t * g, 1), jnp.float32),
                        pltpu.SMEM((1,), jnp.int32)],
    )
    body = functools.partial(_kernel, bt=bt, nb=nb, pp=pp, t=t, g=g,
                             hg=head_group(t, g, hk), windowed=windowed,
                             head_size=head_size)
    out = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hk, t * g, hs), jnp.float32),
        # rows in order on one core: the scratch carries a row's prefetched
        # first copies into the next grid step
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name=name,
    )(jnp.asarray([layer_idx], jnp.int32), tbl_flat,
      jnp.asarray(lengths, jnp.int32),
      jnp.reshape(jnp.asarray(window if windowed else 0, jnp.int32), (1,)),
      qr, k_new, v_new, kc, vc)
    # (B, hk, t*g, hs) -> (B, T, hq, hs)
    out = out.reshape(b, hk, t, g, hs)
    return jnp.transpose(out, (0, 2, 1, 3, 4)).reshape(b, t, hq, hs)


def paged_attention_xla(q, kc, vc, k_new, v_new, tables, lengths, layer_idx,
                        *, n_read: int, virtual_len: int | None = None,
                        window: int = 0):
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
                        key_positions=key_pos,
                        key_lo=(jnp.maximum(positions - window + 1, 0)
                                if window else None))
    return out.reshape(b, t, hq, hs)


# ---- latent attention: ONE row a token, every head reads it -----------------

_LATENT_Q_TOKENS = 8  # chunk positions a grid step's query block holds


def _latent_q_tokens(t: int) -> int:
    """Positions of the chunk a grid step takes: the largest divisor of T up
    to 8, so that a 64-token chunk's 64 heads x 64 positions are eight blocks
    of 512 query rows, not one of 4096."""
    return max(d for d in range(1, min(t, _LATENT_Q_TOKENS) + 1) if t % d == 0)


def _latent_kernel(li_ref, tbl_ref, len_ref, q_ref, rn_ref, pool_hbm, o_ref,
                   buf, sem, m_ref, l_ref, *, bt, nb, pp, t, tq, h, nv, scale):
    """Grid step (b, qi): the query rows of positions qi*tq .. of row b, all
    h heads (row r of the block: position qi*tq + r // h, head r % h),
    against the steps of pp pool blocks that hold the row's committed rows.

    Blocks: q (1, tq*h, W) | rn (1, t, W), the chunk's own rows | out
    (1, tq*h, nv) f32, the flash accumulator until the last line. pool_hbm is
    the whole pool (L, N, 1, bt, W), left in HBM. A row of the pool is key
    and value at once: the score is q . row over all W, the value the row's
    first nv entries. Scratch: buf (2, pp*bt, W) double buffer, sem (2,),
    the flash (m, l) statistics (tq*h, 1)."""
    b, qi = pl.program_id(0), pl.program_id(1)
    sk = buf.shape[1]
    length = len_ref[b]
    li = li_ref[0]
    n_live = jnp.minimum((length + sk - 1) // sk, -(-nb // pp))
    dt = jnp.promote_types(q_ref.dtype, buf.dtype)

    def copies(j, slot):
        out = []
        for i in range(pp):
            page = tbl_ref[b * nb + jnp.minimum(j * pp + i, nb - 1)]
            out.append(pltpu.make_async_copy(
                pool_hbm.at[li, page, 0], buf.at[slot, pl.ds(i * bt, bt), :],
                sem.at[slot]))
        return out

    @pl.when(n_live > 0)
    def _first():
        for c in copies(0, 0):
            c.start()

    m_ref[:] = jnp.full_like(m_ref, _NEG)
    l_ref[:] = jnp.zeros_like(l_ref)
    o_ref[:] = jnp.zeros_like(o_ref)
    q = q_ref[0].astype(dt)  # (tq*h, W)

    def step(j, carry):
        slot = j % 2

        @pl.when(j + 1 < n_live)
        def _next():
            for c in copies(j + 1, 1 - slot):
                c.start()

        for c in copies(j, slot):
            c.wait()
        # every committed row lies before every query of the chunk: only the
        # rows at and past the committed length (garbage) are masked
        left = length - j * sk
        live = jax.lax.broadcasted_iota(jnp.int32, (1, sk), 1) < left
        live_v = jax.lax.broadcasted_iota(jnp.int32, (sk, 1), 0) < left
        rows = buf[slot]
        s = jax.lax.dot_general(q, rows.astype(dt), (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(live, s, _NEG)  # (tq*h, sk)
        vb = jnp.where(live_v, rows[:, :nv].astype(jnp.float32), 0.0)
        m_old = m_ref[:]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        a = jnp.exp(m_old - m_new)
        p = jnp.exp(s - m_new)
        l_ref[:] = l_ref[:] * a + jnp.sum(p, axis=1, keepdims=True)
        o_ref[0] = o_ref[0] * a + jax.lax.dot_general(
            p, vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new
        return carry

    jax.lax.fori_loop(0, n_live, step, 0)

    # fold the chunk's own rows: query row r sits at chunk position
    # qi*tq + r // h and may attend chunk row tau iff tau <= that
    ti = qi * tq + jax.lax.broadcasted_iota(jnp.int32, (tq * h, t), 0) // h
    tau = jax.lax.broadcasted_iota(jnp.int32, (tq * h, t), 1)
    rn = rn_ref[0].astype(jnp.float32)  # (t, W)
    s_new = jax.lax.dot_general(q_ref[0].astype(jnp.float32), rn,
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
    s_new = jnp.where(tau <= ti, s_new, _NEG)
    m_old = m_ref[:]
    m_f = jnp.maximum(m_old, jnp.max(s_new, axis=1, keepdims=True))
    a_f = jnp.exp(m_old - m_f)
    p_new = jnp.exp(s_new - m_f)
    denom = l_ref[:] * a_f + jnp.sum(p_new, axis=1, keepdims=True)
    out = o_ref[0] * a_f + jax.lax.dot_general(
        p_new, rn[:, :nv], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    o_ref[0] = out / denom


@functools.partial(jax.jit, static_argnames=("n_read", "n_values", "scale",
                                             "interpret"))
def latent_paged_attention(q, pool, rows_new, tables, lengths, layer_idx, *,
                           n_read: int, n_values: int, scale: float,
                           interpret: bool | None = None):
    """Latent attention (the absorbed form, models/forward.py
    `_latent_attention`) of T chunk queries a row against block-table rows.

    q: (B, T, H, W) in the activation dtype, a head's query as wide as a
        cache row. pool: (L, N, 1, bt, W) the FULL stacked pool, one row a
        token a layer; only the (layer, tables[b, j]) blocks under the row's
        length are moved, once for all H heads of a query block.
    rows_new: (B, T, W) the chunk's uncommitted rows. tables (B, W_t) i32,
    lengths (B,) i32, layer_idx i32 scalar, n_read static, as paged_attention.
    n_values: the leading entries of a row that are also its value (the
    latent); scale: what q . row is multiplied by.
    Returns (B, T, H, n_values) f32."""
    if interpret is None:
        interpret = interpret_requested()
    b, t, h, w = q.shape
    l, n, hk, bt, w2 = pool.shape
    assert hk == 1 and w2 == w and rows_new.shape == (b, t, w), (
        q.shape, pool.shape, rows_new.shape)
    nb = n_read
    pp = pages_per_step(nb, bt)
    tq = _latent_q_tokens(t)
    qr = q.reshape(b, t * h, w)
    tbl_flat = tables[:, :nb].reshape(-1).astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # (layer_idx_arr, tbl_flat, lengths)
        grid=(b, t // tq),
        in_specs=[
            pl.BlockSpec((1, tq * h, w), lambda bi, qi, *_: (bi, qi, 0)),
            pl.BlockSpec((1, t, w), lambda bi, qi, *_: (bi, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, tq * h, n_values),
                               lambda bi, qi, *_: (bi, qi, 0)),
        scratch_shapes=[pltpu.VMEM((2, pp * bt, w), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.VMEM((tq * h, 1), jnp.float32),
                        pltpu.VMEM((tq * h, 1), jnp.float32)],
    )
    body = functools.partial(_latent_kernel, bt=bt, nb=nb, pp=pp, t=t, tq=tq,
                             h=h, nv=n_values, scale=scale)
    out = pl.pallas_call(
        body, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, t * h, n_values), jnp.float32),
        interpret=interpret, name="latent_paged_attention",
    )(jnp.asarray([layer_idx], jnp.int32), tbl_flat,
      jnp.asarray(lengths, jnp.int32), qr, rows_new, pool)
    return out.reshape(b, t, h, n_values)


def latent_paged_attention_xla(q, pool, rows_new, tables, lengths, layer_idx,
                               *, n_read: int, n_values: int, scale: float):
    """XLA twin of `latent_paged_attention` (the CPU's path, and the kernel's
    oracle): gather the table's blocks into the window layout and run
    ops/attention.py `latent_attention`. Shapes as the kernel's."""
    from .attention import latent_attention

    b, t, h, w = q.shape
    l, n, hk, bt, _ = pool.shape
    pl_ = jax.lax.dynamic_slice(pool, (layer_idx, 0, 0, 0, 0),
                                (1, n, 1, bt, w))[0, :, 0]  # (N, bt, W)
    win = n_read * bt
    kw = pl_[tables[:, :n_read]].reshape(b, win, w)
    lengths = jnp.asarray(lengths, jnp.int32)
    slot = jnp.arange(win)
    slot_pos = jnp.where(slot[None, :] < lengths[:, None], slot[None, :],
                         jnp.int32(1 << 30))
    positions = lengths[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    key_pos = jnp.concatenate([slot_pos, positions], axis=1)
    return latent_attention(
        q, jnp.concatenate([kw, jnp.asarray(rows_new, kw.dtype)], axis=1),
        positions, key_pos, n_values, scale)
