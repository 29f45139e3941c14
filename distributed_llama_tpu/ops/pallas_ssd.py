"""The state-space recurrence of a Mamba-2 mixer (SSD) against a carried state.

A head of P values holds a running MATRIX H (P, N) in float32 that sums every
earlier position of its sequence (N the state size; B and C, N wide, are
shared by all heads of the one group):

    H_p = exp(dt_p A) H_{p-1} + dt_p x_p B_p^T        A < 0 a scalar a head
    y_p = H_p C_p                                     (+ D x_p, the caller's)

The states of all slots and state layers are ONE array h (slots, layers,
heads, P, N) that both entry points update IN PLACE (`input_output_aliases`;
the layer is a scalar the kernel's index maps read), so a layer scan carries
it without a second copy:

- `ssd_step`: one position of every slot (a T = 1 step, each step of the
  K-step scan, the riders of a chunk). A slot that is not `live` (parked,
  padding, the chunk's own slot among the riders) leaves its H bit for bit;
  a `fresh` slot (its position is 0) starts from zeros whatever the array
  holds, which is how a reused slot's state is "zeroed".
- `ssd_chunk`: T positions of ONE slot in the dual form, a head at a time in
  VMEM: the decay-masked (C B^T) against dt x within the chunk, C against the
  incoming H, and the chunk's H out. Nothing of (T, T, heads) leaves the
  kernel.

Off the kernels (`use_pallas=False`: the CPU's float32 path) the same two
functions run the recurrence as written, position by position.

Where the bytes go (what `benchmark/` counts for the roofline): a step reads
and writes P x N x 4 bytes a live (slot, head, layer) and nothing else of
that size; a chunk the same once for its slot.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..platform_env import interpret_requested

HEAD_BLOCK = 16  # heads a grid step holds: 16 x 64 x 128 x 4 = 512 KiB of H
_HI = jax.lax.Precision.HIGHEST


def _head_block(heads: int) -> int:
    return next(b for b in (HEAD_BLOCK, 8, 4, 2, 1) if heads % b == 0)


# ---- the recurrence as written (XLA; the CPU path and the kernels' oracle) --

def _step_math(hl, x, dt, a, b, c):
    """hl (..., H, P, N) -> (y (..., H, P), new hl): one position."""
    decay = jnp.exp(dt * a)[..., None, None]
    new = decay * hl + (dt[..., None] * x)[..., None] * b[..., None, None, :]
    return jnp.einsum("...hpn,...n->...hp", new, c), new


def ssd_step_xla(h, layer, x, dt, a, b, c, live, fresh):
    x, dt, b, c = (v.astype(jnp.float32) for v in (x, dt, b, c))
    s = h.shape[0]
    old = jax.lax.dynamic_index_in_dim(h, layer, 1, keepdims=False)
    start = jnp.where(fresh.reshape(s, 1, 1, 1), 0.0, old)
    y, new = _step_math(start, x, dt, a.astype(jnp.float32), b, c)
    new = jnp.where(live.reshape(s, 1, 1, 1), new, old)
    return y, jax.lax.dynamic_update_slice(h, new[:, None],
                                           (0, layer, 0, 0, 0))


def ssd_chunk_xla(h, layer, slot, x, dt, a, b, c, live, fresh):
    x, dt, b, c = (v.astype(jnp.float32) for v in (x, dt, b, c))
    old = jax.lax.dynamic_slice(
        h, (slot, layer, 0, 0, 0), (1, 1, *h.shape[2:]))[0, 0]
    start = jnp.where(fresh, 0.0, old)

    def pos(hl, row):
        y, hl = _step_math(hl, *row[:2], a.astype(jnp.float32), *row[2:])
        return hl, y

    new, y = jax.lax.scan(pos, start, (x, dt, b, c))
    new = jnp.where(live, new, old)
    return y, jax.lax.dynamic_update_slice(h, new[None, None],
                                           (slot, layer, 0, 0, 0))


# ---- the kernels -----------------------------------------------------------

def _block_diag(row, hb: int, p: int):
    """row (1, hb x p) -> (hb, hb x p): row i keeps head i's p values."""
    head_of = jax.lax.broadcasted_iota(jnp.int32, (hb, hb * p), 1) // p
    mine = head_of == jax.lax.broadcasted_iota(jnp.int32, (hb, hb * p), 0)
    return jnp.where(mine, jnp.broadcast_to(row, (hb, hb * p)), 0.0)


def _step_kernel(ctl_ref, decay_ref, dx_ref, b_ref, c_ref, h_ref, y_ref,
                 o_ref, *, hb: int, p: int):
    """Grid (slot s, head block j). ctl (2 + 2 S,) int32 in SMEM: the layer,
    the slots, then each slot's live and fresh; decay (S, H) float32 in SMEM.
    dx (1, 1, hb P) the block's dt x, heads along the lanes; b, c (1, 8, N)
    a slot's B and C in eight equal rows; h / o (1, 1, hb, P, N)."""
    s, j = pl.program_id(0), pl.program_id(1)
    n_slots = ctl_ref[1]
    live = ctl_ref[2 + s] > 0
    fresh = ctl_ref[2 + n_slots + s] > 0
    n = h_ref.shape[-1]

    @pl.when(live)
    def _():
        # every head's outer product dt x B^T in one product: the block
        # diagonal of dx (hb, hb P), transposed, against B in hb equal rows
        outer = jax.lax.dot_general(
            _block_diag(dx_ref[0], hb, p),
            jnp.broadcast_to(b_ref[0, :1], (hb, n)),
            (((0,), (0,)), ((), ())), precision=_HI,
            preferred_element_type=jnp.float32)  # (hb P, N)
        for i in range(hb):
            old = jnp.where(fresh, 0.0, h_ref[0, 0, i])
            o_ref[0, 0, i] = (decay_ref[s, j * hb + i] * old
                              + outer[i * p:(i + 1) * p])
        new = o_ref[0, 0].reshape(hb * p, n)
        y = jax.lax.dot_general(c_ref[0], new, (((1,), (1,)), ((), ())),
                                precision=_HI,
                                preferred_element_type=jnp.float32)
        y_ref[0] = y[:1]

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = h_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


@functools.partial(jax.jit, static_argnames=("interpret", "name"))
def _ssd_step_pallas(h, layer, x, dt, a, b, c, live, fresh, *, interpret,
                     name):
    s, _, heads, p, n = h.shape
    hb = _head_block(heads)
    dt = dt.astype(jnp.float32)
    decay = jnp.exp(dt * a.astype(jnp.float32))  # (S, H)
    dx = (dt[..., None] * x.astype(jnp.float32)).reshape(s, 1, heads * p)
    ctl = jnp.concatenate([
        jnp.stack([jnp.asarray(layer, jnp.int32), jnp.int32(s)]),
        live.astype(jnp.int32), fresh.astype(jnp.int32)])

    def rows8(v):  # (S, N) -> (S, 8, N): whole sublanes for the products
        return jnp.broadcast_to(v.astype(jnp.float32)[:, None], (s, 8, n))

    def at_h(si, j, ctl):
        return (si, ctl[0], j, 0, 0)

    y, h = pl.pallas_call(
        functools.partial(_step_kernel, hb=hb, p=p),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(s, heads // hb),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((1, 1, hb * p), lambda si, j, ctl: (si, 0, j)),
                pl.BlockSpec((1, 8, n), lambda si, j, ctl: (si, 0, 0)),
                pl.BlockSpec((1, 8, n), lambda si, j, ctl: (si, 0, 0)),
                pl.BlockSpec((1, 1, hb, p, n), at_h),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, hb * p), lambda si, j, ctl: (si, 0, j)),
                pl.BlockSpec((1, 1, hb, p, n), at_h),
            ]),
        out_shape=[jax.ShapeDtypeStruct((s, 1, heads * p), jnp.float32),
                   jax.ShapeDtypeStruct(h.shape, h.dtype)],
        input_output_aliases={5: 1},  # ctl, decay, dx, b, c, h -> (y, h)
        interpret=interpret, name=name,
    )(ctl, decay, dx, rows8(b), rows8(c), h)
    return y.reshape(s, heads, p), h


def _chunk_kernel(ctl_ref, last_ref, dx_ref, col_ref, row_ref, b_ref, c_ref,
                  h_ref, y_ref, o_ref, *, hb: int, t: int):
    """Grid (head block j). ctl (4,) int32 in SMEM: the slot, the layer, live,
    fresh; last (H,) float32 in SMEM: a head's log decay over the whole
    chunk. dx (hb, T, P) dt x; col (hb, T, 128) the running log decay
    cum_t = sum_{s <= t} dt_s A along the rows, every lane alike; row
    (hb, 1, T) the same along the lanes; b, c (T, N); h / o (1, 1, hb, P,
    N). Per head: y = (C B^T * decay mask) dx + exp(cum) C H^T and
    H' = exp(cum_T) H + (exp(cum_T - cum) dx)^T B."""
    j = pl.program_id(0)
    live, fresh = ctl_ref[2] > 0, ctl_ref[3] > 0
    p = dx_ref.shape[-1]

    @pl.when(live)
    def _():
        b, c = b_ref[...], c_ref[...]
        g = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                precision=_HI,
                                preferred_element_type=jnp.float32)  # (T, T)
        causal = (jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
                  <= jax.lax.broadcasted_iota(jnp.int32, (t, t), 0))
        for i in range(hb):
            dx, col = dx_ref[i], col_ref[i]
            old = jnp.where(fresh, 0.0, h_ref[0, 0, i])  # (P, N)
            # exp of a difference that is <= 0 wherever it is kept
            decay = jnp.exp(jnp.where(causal, col[:, :t] - row_ref[i], 0.0))
            m = jnp.where(causal, g * decay, 0.0)
            y = jnp.dot(m, dx, precision=_HI,
                        preferred_element_type=jnp.float32)
            from_h = jax.lax.dot_general(
                c, old, (((1,), (1,)), ((), ())), precision=_HI,
                preferred_element_type=jnp.float32)  # (T, P)
            y_ref[i] = y + jnp.exp(col[:, :p]) * from_h
            total = last_ref[j * hb + i]
            o_ref[0, 0, i] = jnp.exp(total) * old + jax.lax.dot_general(
                jnp.exp(total - col[:, :p]) * dx, b,
                (((0,), (0,)), ((), ())), precision=_HI,
                preferred_element_type=jnp.float32)  # (P, N)

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = h_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


@functools.partial(jax.jit, static_argnames=("interpret", "name"))
def _ssd_chunk_pallas(h, layer, slot, x, dt, a, b, c, live, fresh, *,
                      interpret, name):
    t, heads, p = x.shape
    n = h.shape[-1]
    hb = _head_block(heads)
    dt = dt.astype(jnp.float32)
    cum = jnp.cumsum(dt * a.astype(jnp.float32), axis=0).T  # (H, T)
    dx = jnp.swapaxes(dt[..., None] * x.astype(jnp.float32), 0, 1)  # (H,T,P)
    ctl = jnp.stack([jnp.asarray(v, jnp.int32)
                     for v in (slot, layer, live, fresh)])

    def at_h(j, ctl):
        return (ctl[0], ctl[1], j, 0, 0)

    y, h = pl.pallas_call(
        functools.partial(_chunk_kernel, hb=hb, t=t),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(heads // hb,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((hb, t, p), lambda j, ctl: (j, 0, 0)),
                pl.BlockSpec((hb, t, 128), lambda j, ctl: (j, 0, 0)),
                pl.BlockSpec((hb, 1, t), lambda j, ctl: (j, 0, 0)),
                pl.BlockSpec((t, n), lambda j, ctl: (0, 0)),
                pl.BlockSpec((t, n), lambda j, ctl: (0, 0)),
                pl.BlockSpec((1, 1, hb, p, n), at_h),
            ],
            out_specs=[
                pl.BlockSpec((hb, t, p), lambda j, ctl: (j, 0, 0)),
                pl.BlockSpec((1, 1, hb, p, n), at_h),
            ]),
        out_shape=[jax.ShapeDtypeStruct((heads, t, p), jnp.float32),
                   jax.ShapeDtypeStruct(h.shape, h.dtype)],
        input_output_aliases={7: 1},  # ctl, last, dx, col, row, b, c, h
        interpret=interpret, name=name,
    )(ctl, cum[:, -1], dx,
      jnp.broadcast_to(cum[..., None], (heads, t, 128)), cum[:, None, :],
      b.astype(jnp.float32), c.astype(jnp.float32), h)
    return jnp.swapaxes(y, 0, 1), h


# ---- entry points ----------------------------------------------------------

def ssd_step(h, layer, x, dt, a, b, c, live, fresh, *, use_pallas: bool,
             interpret: bool | None = None, name: str = "ssd_step"):
    """One position of every slot. h (S, L, H, P, N) float32, updated in
    place at layer `layer`; x (S, H, P); dt (S, H), the step sizes behind
    their softplus; a (H,), negative; b, c (S, N); live, fresh (S,) bool.
    Returns (y (S, H, P) float32 without the D x term, h)."""
    if not use_pallas:
        return ssd_step_xla(h, layer, x, dt, a, b, c, live, fresh)
    return _ssd_step_pallas(
        h, layer, x, dt, a, b, c, live, fresh, name=name,
        interpret=interpret_requested() if interpret is None else interpret)


def ssd_chunk(h, layer, slot, x, dt, a, b, c, live, fresh, *,
              use_pallas: bool, interpret: bool | None = None,
              name: str = "ssd_chunk"):
    """T positions of slot `slot` against its carried H. x (T, H, P); dt
    (T, H); b, c (T, N); live, fresh scalars. Returns (y (T, H, P) float32
    without the D x term, h with the slot's H after the chunk's last
    position)."""
    if not use_pallas:
        return ssd_chunk_xla(h, layer, slot, x, dt, a, b, c, live, fresh)
    return _ssd_chunk_pallas(
        h, layer, slot, x, dt, a, b, c, live, fresh, name=name,
        interpret=interpret_requested() if interpret is None else interpret)
