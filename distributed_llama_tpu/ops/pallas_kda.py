"""The delta rule of a Kimi Delta Attention mixer (KDA) against a carried state.

A head holds a running MATRIX S (K, V) in float32 (K the key size, V the
value size) that is decayed BY CHANNEL of the key and corrected towards the
new value, a position t (q and k of unit length, a = exp(g) in (0, 1] a key
channel, beta in (0, 1) a head):

    S <- Diag(a_t) S
    u_t = beta_t (v_t - S^T k_t)
    S <- S + k_t u_t^T
    o_t = K^-1/2 S^T q_t

The transition is (I - beta k k^T) Diag(a), not a scalar times the identity,
so the SSD kernels (ops/pallas_ssd.py) cannot compute it. The states of all
slots and state layers are ONE array h (slots, layers, heads, K, V) that both
entry points update IN PLACE (`input_output_aliases`; the layer is a scalar
the kernel's index maps read), so a layer scan carries it without a copy:

- `kda_step`: one position of every slot (a T = 1 step, each step of the
  K-step scan, the riders of a chunk). A slot that is not `live` leaves its
  S bit for bit; a `fresh` slot (its position is 0) starts from zeros
  whatever the array holds.
- `kda_chunk`: T positions of ONE slot, a head a grid step. With
  G_t = sum_{s <= t} g_s (a channel) the chunk form is

      A[t, s] = beta_t sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c])     s < t
      U = (I + A)^-1 Diag(beta) (V - (K * exp(G)) S_0)
      o_t = K^-1/2 [(q_t * exp(G_t))^T S_0
                    + sum_{s <= t} (sum_c q_t[c] k_s[c] exp(G_t[c] - G_s[c])) u_s]
      S_T = Diag(exp(G_T)) S_0 + sum_s Diag(exp(G_T - G_s)) k_s u_s^T

  1 / exp(G_s) is unbounded inside a chunk (g of -1.5 a position over 64
  positions is e^96, past float32), so NO factor is ever a quotient of two
  exponentials: the kernel walks the chunk's columns s, forms
  exp(G_t - G_s) for the whole column from the difference (<= 0 wherever it
  is kept, every position its own reference point), and solves the
  triangular system by substitution in the same walk (column s of A times
  u_s leaves the rows behind it): nothing of (T, T) is inverted and nothing
  of (T, T, K) leaves the kernel.

Off the kernels (`use_pallas=False`: the CPU's float32 path) the same two
functions run the recurrence as written, position by position.

Where the bytes go (what `benchmark/kda_work.py` counts): a step reads and
writes K x V x 4 bytes a live (slot, head, layer) and nothing else of that
size; a chunk the same once for its slot.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..platform_env import interpret_requested

HEAD_BLOCK = 8  # heads a step's grid step holds: 8 x 128 x 128 x 4 = 512 KiB
_HI = jax.lax.Precision.HIGHEST


def _head_block(heads: int) -> int:
    return next(b for b in (HEAD_BLOCK, 4, 2, 1) if heads % b == 0)


# ---- the recurrence as written (XLA; the CPU path and the kernels' oracle) --

def _step_math(s, q, k, v, g, beta):
    """s (..., H, K, V) -> (o (..., H, V), new s): one position."""
    s = jnp.exp(g)[..., None] * s
    u = beta[..., None] * (v - jnp.einsum("...hkv,...hk->...hv", s, k,
                                          precision=_HI))
    s = s + k[..., None] * u[..., None, :]
    o = jnp.einsum("...hkv,...hk->...hv", s, q, precision=_HI)
    return o * k.shape[-1] ** -0.5, s


def kda_step_xla(h, layer, q, k, v, g, beta, live, fresh):
    q, k, v, g, beta = (a.astype(jnp.float32) for a in (q, k, v, g, beta))
    n = h.shape[0]
    old = jax.lax.dynamic_index_in_dim(h, layer, 1, keepdims=False)
    start = jnp.where(fresh.reshape(n, 1, 1, 1), 0.0, old)
    o, new = _step_math(start, q, k, v, g, beta)
    new = jnp.where(live.reshape(n, 1, 1, 1), new, old)
    return o, jax.lax.dynamic_update_slice(h, new[:, None],
                                           (0, layer, 0, 0, 0))


def kda_chunk_xla(h, layer, slot, q, k, v, g, beta, live, fresh):
    q, k, v, g, beta = (a.astype(jnp.float32) for a in (q, k, v, g, beta))
    old = jax.lax.dynamic_slice(
        h, (slot, layer, 0, 0, 0), (1, 1, *h.shape[2:]))[0, 0]
    start = jnp.where(fresh, 0.0, old)

    def pos(s, row):
        o, s = _step_math(s, *row)
        return s, o

    new, o = jax.lax.scan(pos, start, (q, k, v, g, beta))
    new = jnp.where(live, new, old)
    return o, jax.lax.dynamic_update_slice(h, new[None, None],
                                           (slot, layer, 0, 0, 0))


# ---- the kernels -----------------------------------------------------------

def _down_the_rows(rows8, j: int, width: int):
    """rows8 (8, K) -> (K, width): row j's values, one a ROW of the result,
    alike along the lanes. A transposed product against a selector of ones
    (the values times 1.0 and zeros at the highest precision): how a vector
    that lies along the lanes comes to scale the rows of a matrix."""
    pick = (jax.lax.broadcasted_iota(jnp.int32, (rows8.shape[0], width), 0)
            == j).astype(jnp.float32)
    return jax.lax.dot_general(rows8, pick, (((0,), (0,)), ((), ())),
                               precision=_HI,
                               preferred_element_type=jnp.float32)


def _step_kernel(ctl_ref, beta_ref, akq_ref, v_ref, h_ref, o_ref, out_ref, *,
                 hb: int):
    """Grid (slot s, head block j). ctl (2 + 2 S,) int32 in SMEM: the layer,
    the slots, then each slot's live and fresh; beta (S, H) float32 in SMEM.
    akq (1, hb, 8, K): a head's decay exp(g), k and q in rows 0, 1, 2; v and
    o (1, hb, V); h / out (1, 1, hb, K, V)."""
    s, j = pl.program_id(0), pl.program_id(1)
    n_slots = ctl_ref[1]
    live = ctl_ref[2 + s] > 0
    fresh = ctl_ref[2 + n_slots + s] > 0
    kk, vv = h_ref.shape[-2:]

    @pl.when(live)
    def _():
        for i in range(hb):
            rows8 = akq_ref[0, i]
            a, k, q = (_down_the_rows(rows8, r, vv) for r in range(3))
            st = a * jnp.where(fresh, 0.0, h_ref[0, 0, i])
            u = beta_ref[s, j * hb + i] * (
                v_ref[0, pl.ds(i, 1), :]
                - jnp.sum(k * st, axis=0, keepdims=True))
            st = st + k * u
            out_ref[0, 0, i] = st
            o_ref[0, pl.ds(i, 1), :] = jnp.sum(
                q * st, axis=0, keepdims=True) * kk ** -0.5

    @pl.when(jnp.logical_not(live))
    def _():
        out_ref[...] = h_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("interpret", "name"))
def _kda_step_pallas(h, layer, q, k, v, g, beta, live, fresh, *, interpret,
                     name):
    n, _, heads, kk, vv = h.shape
    hb = _head_block(heads)
    f32 = jnp.float32
    akq = jnp.stack([jnp.exp(g.astype(f32)), k.astype(f32), q.astype(f32)],
                    axis=2)
    akq = jnp.pad(akq, ((0, 0), (0, 0), (0, 5), (0, 0)))  # (S, H, 8, K)
    ctl = jnp.concatenate([
        jnp.stack([jnp.asarray(layer, jnp.int32), jnp.int32(n)]),
        live.astype(jnp.int32), fresh.astype(jnp.int32)])

    def at_h(si, j, ctl):
        return (si, ctl[0], j, 0, 0)

    o, h = pl.pallas_call(
        functools.partial(_step_kernel, hb=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n, heads // hb),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((1, hb, 8, kk), lambda si, j, ctl: (si, j, 0, 0)),
                pl.BlockSpec((1, hb, vv), lambda si, j, ctl: (si, j, 0)),
                pl.BlockSpec((1, 1, hb, kk, vv), at_h),
            ],
            out_specs=[
                pl.BlockSpec((1, hb, vv), lambda si, j, ctl: (si, j, 0)),
                pl.BlockSpec((1, 1, hb, kk, vv), at_h),
            ]),
        out_shape=[jax.ShapeDtypeStruct((n, heads, vv), f32),
                   jax.ShapeDtypeStruct(h.shape, h.dtype)],
        input_output_aliases={4: 1},  # ctl, beta, akq, v, h -> (o, h)
        interpret=interpret, name=name,
    )(ctl, beta.astype(f32), akq, v.astype(f32), h)
    return o, h


def _chunk_kernel(ctl_ref, q_ref, k_ref, kb_ref, g_ref, bv_ref, h_ref, o_ref,
                  out_ref, *, t: int):
    """Grid (head j). ctl (4,) int32 in SMEM: the slot, the layer, live,
    fresh. q, k (1, T, K); kb = beta k; g the running log decay G (1, T, K);
    bv = beta v (1, T, V); h / out (1, 1, 1, K, V). The walk over the
    chunk's columns s, UNROLLED (T copies of a dozen vector operations on
    values that stay in registers: as one `fori_loop` over rows sliced out of
    the blocks and a scratch the same walk took 5.6 times as long on the
    chip and saved nothing of a run's set-up; PERF.md section 6, PR 48):
    row s of `rest` is u_s once every earlier column has left it; column s
    of A and of the q-k products comes from exp(G - G_s), kept where the
    difference is <= 0."""
    live, fresh = ctl_ref[2] > 0, ctl_ref[3] > 0
    kk, vv = h_ref.shape[-2:]

    @pl.when(live)
    def _():
        q, k, kb, g = q_ref[0], k_ref[0], kb_ref[0], g_ref[0]
        s0 = jnp.where(fresh, 0.0, h_ref[0, 0, 0])  # (K, V)
        into = jnp.exp(g)  # the decay from the chunk's start: <= 1

        def dot(a, b):
            return jnp.dot(a, b, precision=_HI,
                           preferred_element_type=jnp.float32)

        rest = bv_ref[0] - dot(kb * into, s0)  # (T, V)
        out = dot(q * into, s0)
        at = jax.lax.broadcasted_iota(jnp.int32, (t, 1), 0)
        for s in range(t):
            kw = k[s:s + 1] * jnp.exp(jnp.minimum(g - g[s:s + 1], 0.0))
            u = rest[s:s + 1]  # (1, V): u_s
            qk = jnp.sum(q * kw, axis=-1, keepdims=True)  # (T, 1)
            out = out + jnp.where(at >= s, qk, 0.0) * u
            if s < t - 1:
                a = jnp.sum(kb * kw, axis=-1, keepdims=True)
                rest = rest - jnp.where(at > s, a, 0.0) * u
        o_ref[0] = out * kk ** -0.5
        total = g[t - 1:t]  # (1, K)
        total8 = jnp.broadcast_to(jnp.exp(total), (8, kk))
        out_ref[0, 0, 0] = (
            _down_the_rows(total8, 0, vv) * s0
            + jax.lax.dot_general(
                k * jnp.exp(total - g), rest, (((0,), (0,)), ((), ())),
                precision=_HI, preferred_element_type=jnp.float32))

    @pl.when(jnp.logical_not(live))
    def _():
        out_ref[...] = h_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("interpret", "name"))
def _kda_chunk_pallas(h, layer, slot, q, k, v, g, beta, live, fresh, *,
                      interpret, name):
    t, heads, kk = q.shape
    vv = v.shape[-1]
    f32 = jnp.float32

    def by_head(a):  # (T, H, w) -> (H, T, w)
        return jnp.swapaxes(a.astype(f32), 0, 1)

    beta = beta.astype(f32)[..., None]
    cum = jnp.cumsum(g.astype(f32), axis=0)
    ctl = jnp.stack([jnp.asarray(a, jnp.int32)
                     for a in (slot, layer, live, fresh)])

    def at_h(j, ctl):
        return (ctl[0], ctl[1], j, 0, 0)

    def rows(w):
        return pl.BlockSpec((1, t, w), lambda j, ctl: (j, 0, 0))

    o, h = pl.pallas_call(
        functools.partial(_chunk_kernel, t=t),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(heads,),
            in_specs=[rows(kk), rows(kk), rows(kk), rows(kk), rows(vv),
                      pl.BlockSpec((1, 1, 1, kk, vv), at_h)],
            out_specs=[rows(vv), pl.BlockSpec((1, 1, 1, kk, vv), at_h)]),
        out_shape=[jax.ShapeDtypeStruct((heads, t, vv), f32),
                   jax.ShapeDtypeStruct(h.shape, h.dtype)],
        input_output_aliases={6: 1},  # ctl, q, k, kb, g, bv, h -> (o, h)
        interpret=interpret, name=name,
    )(ctl, by_head(q), by_head(k), by_head(beta * k.astype(f32)),
      by_head(cum), by_head(beta * v.astype(f32)), h)
    return jnp.swapaxes(o, 0, 1), h


# ---- entry points ----------------------------------------------------------

def kda_step(h, layer, q, k, v, g, beta, live, fresh, *, use_pallas: bool,
             interpret: bool | None = None, name: str = "kda_step"):
    """One position of every slot. h (S, L, H, K, V) float32, updated in
    place at layer `layer`; q, k (S, H, K), each head's of unit length; v
    (S, H, V); g (S, H, K) the log decay, <= 0; beta (S, H); live, fresh
    (S,) bool. Returns (o (S, H, V) float32, h)."""
    if not use_pallas:
        return kda_step_xla(h, layer, q, k, v, g, beta, live, fresh)
    return _kda_step_pallas(
        h, layer, q, k, v, g, beta, live, fresh, name=name,
        interpret=interpret_requested() if interpret is None else interpret)


def kda_chunk(h, layer, slot, q, k, v, g, beta, live, fresh, *,
              use_pallas: bool, interpret: bool | None = None,
              name: str = "kda_chunk"):
    """T positions of slot `slot` against its carried S. q, k (T, H, K); v
    (T, H, V); g (T, H, K); beta (T, H); live, fresh scalars. Returns
    (o (T, H, V) float32, h with the slot's S after the chunk's last
    position)."""
    if not use_pallas:
        return kda_chunk_xla(h, layer, slot, q, k, v, g, beta, live, fresh)
    return _kda_chunk_pallas(
        h, layer, slot, q, k, v, g, beta, live, fresh, name=name,
        interpret=interpret_requested() if interpret is None else interpret)
