"""The grouped expert layer: every (B x T) shape of a routed FFN, one way.

A dispatch of N = B x T rows makes N k assignments (row, expert, weight).
They are sorted by expert; each expert's run of rows is padded to the row
tile and laid into ONE buffer of static capacity N k + E (tile - 1), rounded
up to the tile: the worst case (every expert with one row over a tile's
multiple), so no assignment is ever dropped and there is no capacity factor.
Every tile then belongs to one expert, the grouped matmuls run gate/up with
the activation fused and then down, and the results go back to their rows
weighted by the router. An expert nobody chose gets no tile: it costs no
bytes and no FLOPs.

The stack it is given holds all experts or a contiguous share of them
(`offset`: the first expert held, under expert sharding): assignments to
experts held elsewhere get no row here, and the caller's psum adds the
shards' partial sums. Under hidden-axis slicing the stack holds every
expert's slice and the same psum merges.

Two ways through the tiles, one plan: the Pallas kernels
(ops/pallas_moe_grouped.py) where the stacks are split-plane Q40 and the
caller asked for kernels, else `lax.map` over the tiles in XLA, a tile's
expert dequantized where the tile is used. The row tile follows from shapes
alone: twice the mean run length N k / E, rounded up to a power of two,
between 16 (a bf16 sublane tile) and 256. Twice, because decoding an expert's
nibbles costs far more than multiplying a few more rows by them (the kernel
is bound by that decode, PERF.md section 5): a tile that holds nearly every
expert's whole run decodes each touched expert once.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..quants import QTensor
from .kernels import ACTS
from .matmul import LayerOf

MIN_TILE, MAX_TILE = 16, 256


def row_tile(assignments: int, experts: int) -> int:
    """Rows a tile holds, from the dispatch's shapes alone."""
    run = max(1, -(-2 * assignments // experts))
    return min(MAX_TILE, max(MIN_TILE, 1 << (run - 1).bit_length()))


def capacity(assignments: int, experts: int, tile: int) -> int:
    """Rows of the padded buffer: the worst case, a multiple of the tile."""
    return -(-(assignments + experts * (tile - 1)) // tile) * tile


def plan(top_i: jax.Array, el: int, offset, tile: int) -> dict:
    """Where each assignment's row goes. top_i (N, k): global expert ids;
    this stack holds experts [offset, offset + el).

    src (C,): the row of x each buffer row copies (N: none, a zero row);
    slot (N, k): the buffer row of each assignment (C: held elsewhere);
    tile_expert (C / tile,): local expert of each tile, tiles past the used
    ones repeating the last used tile's; n_used: tiles in use; counts (el,):
    assignments per held expert."""
    n, k = top_i.shape
    a = n * k
    cap = capacity(a, el, tile)
    e = top_i.reshape(a).astype(jnp.int32) - offset
    key = jnp.where((e >= 0) & (e < el), e, el)  # held elsewhere: sorts last
    order = jnp.argsort(key, stable=True)
    skey = key[order]
    counts = jnp.bincount(key, length=el + 1)[:el].astype(jnp.int32)
    padded = -(-counts // tile) * tile
    pad_end = jnp.cumsum(padded)
    run_start = jnp.cumsum(counts) - counts
    se = jnp.minimum(skey, el - 1)
    dest = jnp.where(skey < el,
                     (pad_end - padded)[se] + jnp.arange(a) - run_start[se],
                     cap)
    slot = jnp.zeros((a,), jnp.int32).at[order].set(dest.astype(jnp.int32))
    src = jnp.full((cap,), n, jnp.int32).at[dest].set(
        (order // k).astype(jnp.int32), mode="drop")
    n_used = (pad_end[-1] // tile).astype(jnp.int32)
    tiles = jnp.arange(cap // tile, dtype=jnp.int32)
    te = jnp.minimum(jnp.searchsorted(pad_end, tiles * tile, side="right"),
                     el - 1).astype(jnp.int32)
    te = jnp.where(tiles < n_used, te, te[jnp.maximum(n_used - 1, 0)])
    return {"src": src, "slot": slot.reshape(n, k), "tile_expert": te,
            "n_used": n_used, "counts": counts}


def _expert_of(stack, e):
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, e, 0, keepdims=False), stack)


def _grouped_xla(rows, p, up, gate, down, tile, act, merged):
    """The same tiles through XLA: one tile a step, its expert's matrices
    dequantized there; a tile past the used ones is skipped."""
    cap, d = rows.shape

    def dense(w, dtype):
        return w.dequantize(dtype=dtype) if isinstance(w, QTensor) else w

    def one(args):
        x, e, used = args

        def compute():
            def mm(v, w):
                return jax.lax.dot_general(
                    v, dense(_expert_of(w, e), v.dtype),
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32).astype(v.dtype)

            if merged:
                gu = mm(x, up)
                hl = gu.shape[-1] // 2
                h = gu[:, :hl] * act(gu[:, hl:])
            else:
                h = mm(x, up) * act(mm(x, gate))
            return mm(h, down)

        return jax.lax.cond(used, compute, lambda: jnp.zeros_like(x))

    tiles = cap // tile
    out = jax.lax.map(one, (rows.reshape(tiles, tile, d), p["tile_expert"],
                            jnp.arange(tiles) < p["n_used"]))
    return out.reshape(cap, d)


def grouped_expert_ffn(x, top_i, weights, bp, *, act_name: str, el: int,
                       offset=0, use_pallas=False, tile_experts=None):
    """sum_j weights[n, j] * down_e(act(gate_e x_n) * up_e x_n), e = top_i[n, j],
    over the experts this stack holds. x (N, d); top_i, weights (N, k); bp has
    `moe_gu` (the merged [up|gate] stack) or `moe_up` and `moe_gate`, and
    `moe_down`, with `el` experts on the leading axis, or each as a `LayerOf`
    a layer of the stack over layers: the kernels read that in place, XLA
    its slice. Returns ((N, d) in x.dtype, stats): stats int32 (3,) =
    assignments held here, rows computed (tiles in use x tile), experts
    touched. `tile_experts`: the experts the N k assignments spread over,
    where that is more than the `el` held (a share of a wider router): the
    row tile follows the mean run that reaches a held expert."""
    n, k = top_i.shape
    merged = "moe_gu" in bp
    up = bp["moe_gu"] if merged else bp["moe_up"]
    gate = up if merged else bp["moe_gate"]
    down = bp["moe_down"]
    tile = row_tile(n * k, tile_experts or el)
    p = plan(top_i, el, offset, tile)
    xz = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)])
    rows = xz[p["src"]]
    hidden = up.shape[1] // (2 if merged else 1)
    kernel = False
    if use_pallas:
        from ..platform_env import interpret_requested
        from .pallas_moe_grouped import grouped_supported

        interp = interpret_requested()
        kernel = (grouped_supported(up, hidden, interp)
                  and grouped_supported(gate, hidden, interp)
                  and grouped_supported(down, down.shape[1], interp))
    if kernel:
        from .pallas_moe_grouped import moe_grouped_q4

        out = moe_grouped_q4(rows, p["tile_expert"], p["n_used"], up, gate,
                             down, tile=tile, act=act_name)
    else:
        up, gate, down = (w.one() if isinstance(w, LayerOf) else w
                          for w in (up, gate, down))
        out = _grouped_xla(rows, p, up, gate, down, tile, ACTS[act_name],
                           merged)
    # an assignment held elsewhere has slot C: it reads a zero, not a row
    picked = jnp.take(out, p["slot"], axis=0, mode="fill", fill_value=0)
    y = jnp.einsum("nkd,nk->nd", picked.astype(jnp.float32),
                   weights.astype(jnp.float32)).astype(x.dtype)
    stats = jnp.stack([jnp.sum(p["counts"]), p["n_used"] * tile,
                       jnp.sum(p["counts"] > 0)]).astype(jnp.int32)
    return y, stats
