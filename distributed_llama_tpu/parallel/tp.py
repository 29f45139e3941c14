"""Tensor-parallel execution: shard params onto the mesh and build the SPMD step.

This layer replaces the reference's entire distribution machinery — weight streaming to
workers (transformer.cpp:432-451), per-layer broadcast/gather sync tasks (tasks.cpp:44-94),
and the root/worker role split (tasks.hpp:52-76). One shard_map'd program runs on every
device; `jax.device_put` with NamedShardings performs the "weight distribution"; XLA
lowers the psum/all_gather merge points to ICI/DCN collectives.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..models.forward import forward
from ..models.spec import ModelSpec
from ..ops.rope import RopeTables
from ..quants import QTensor
from .mesh import AXIS_SP, AXIS_TP
from .sharding import (check_divisibility, effective_kv_heads, kv_cache_pspec_for_mesh,
                       param_pspecs)


def _expand_pspec_tree(params: dict[str, Any], pspecs: dict[str, Any]):
    """Expand a per-tensor spec dict into a pytree congruent with params (QTensor nodes
    get their single spec broadcast to data+scales leaves, which line up by axis index)."""
    out = {}
    for k, v in params.items():
        if isinstance(v, dict):
            out[k] = _expand_pspec_tree(v, pspecs[k])
        elif isinstance(v, QTensor):
            spec = pspecs[k]
            out[k] = QTensor(v.ftype, spec, spec if v.scales is not None else None,
                             layout=v.layout, groups=v.groups,
                             row_groups=v.row_groups)
        else:
            out[k] = pspecs[k]
    return out


def _repeat_kv_rows(t: QTensor | Any, hk: int, rep: int) -> Any:
    """Replicate each KV head's row block `rep` times along the row (out) axis.

    Leaves are stacked (L, hk*hs, ...) arrays; rows stay whole-head-grouped so
    P('tp') on the row axis lands KV head j*hk//tp on shard j — exactly the head
    shard j's query slice attends with. Quant blocks run along the *in* axis, so
    row replication never splits a block.
    """
    import numpy as np

    def rep_leaf(a):
        if a is None:
            return None
        rows = a.shape[1]
        assert rows % hk == 0, (a.shape, hk)
        hs_g = rows // hk
        xp = np if isinstance(a, np.ndarray) else jax.numpy
        grouped = a.reshape(a.shape[0], hk, hs_g, *a.shape[2:])
        out = xp.repeat(grouped, rep, axis=1)
        return out.reshape(a.shape[0], hk * rep * hs_g, *a.shape[2:])

    if isinstance(t, QTensor):
        return QTensor(t.ftype, rep_leaf(t.data), rep_leaf(t.scales), layout=t.layout)
    return rep_leaf(t)


def shard_params(params: dict[str, Any], mesh: Mesh,
                 spec: ModelSpec | None = None,
                 moe_sharding: str = "slice") -> dict[str, Any]:
    """Place params on the mesh per param_pspecs — the TPU-native 'loadRoot' weight
    distribution (transformer.cpp:480-539) with device_put instead of socket writes.

    When tp > n_kv_heads, wk/wv rows are replicated per KV head (effective_kv_heads)
    before placement, lifting the reference's nSlices <= nKvHeads limit."""
    tp = mesh.shape[AXIS_TP]
    # fused matvec groups carry the TP-group count their rows were interleaved
    # with (models/params.py fuse_matvec_groups); placement on a mismatched
    # mesh/moe_sharding would silently scramble the member split — fail loudly
    from ..models.params import _FUSE_GROUPS, stack_names

    for name, t in [nt for st in stack_names(params)
                    for nt in params[st].items()]:
        if name not in _FUSE_GROUPS or not isinstance(t, QTensor):
            continue
        expected = 1 if (name == "moe_gu" and moe_sharding == "expert") else tp
        assert t.row_groups == expected, (
            f"{name} was fused with row interleave {t.row_groups}, but this "
            f"mesh shards it over {expected} group(s) (tp={tp}, "
            f"moe_sharding={moe_sharding}) — re-run prepare_for_pallas with "
            "the deployment's tp/moe_sharding")
    if spec is not None:
        check_divisibility(spec, tp, moe_sharding=moe_sharding)
        hk_eff = effective_kv_heads(spec, tp)
        # a latent row is whole on every shard: nothing to repeat
        if hk_eff != spec.n_kv_heads and not spec.latent:
            rep = hk_eff // spec.n_kv_heads
            params = dict(params)
            for st in stack_names(params):
                params[st] = dict(params[st])
                for name in ("wk", "wv"):
                    params[st][name] = _repeat_kv_rows(
                        params[st][name], spec.n_kv_heads, rep)
    pspec_tree = _expand_pspec_tree(params, param_pspecs(params, moe_sharding))

    def put(leaf, spec):
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map(put, params, pspec_tree,
                                  is_leaf=lambda x: isinstance(x, P))


def init_sharded_kv_cache(spec: ModelSpec, mesh: Mesh, batch: int = 1, dtype=None):
    """Zeroed KV caches with the head axis already expanded for KV-head replication
    and placed with the mesh's cache sharding. The one cache-construction path for
    every sharded entry point — callers can't forget effective_kv_heads."""
    import jax.numpy as jnp

    from ..models.forward import init_kv_cache

    dtype = dtype or jnp.float32
    hk = effective_kv_heads(spec, mesh.shape[AXIS_TP])
    kc, vc = init_kv_cache(spec, batch=batch, dtype=dtype, n_kv_heads=hk)
    sh = NamedSharding(mesh, kv_cache_pspec_for_mesh(mesh))
    return jax.device_put(kc, sh), jax.device_put(vc, sh)


def make_sharded_forward(spec: ModelSpec, mesh: Mesh, params: dict[str, Any], *,
                         dtype=None, use_pallas: bool = False,
                         compress_collectives: bool = False, donate_cache: bool = True,
                         attn_window: int | None = None,
                         moe_sharding: str = "slice",
                         kv_block_tokens: int = 0,
                         paged_kernel: bool = False,
                         moe_stats: bool = False):
    """Build the jitted SPMD forward step over the mesh's tp axis.

    moe_stats=True: the built fn returns a fourth value, the int32 vector of
    models.forward.forward (what the routed expert layers did).

    Returns fn(params, rope, tokens, k_cache, v_cache, start_pos) ->
    (logits, k_cache, v_cache). Cache buffers are donated (in-place update in HBM).

    THE TOKEN CARRY (docs/SERVING.md "Pipelined decode"). Every program also
    samples what can be sampled on the device: `tok`, int32 (B,), the arg-max
    (first index on ties, over the float32 logits, as np.argmax in
    runtime/sampler.Sampler) of each row's last returned position, which is
    the one position a row of a step or of a compact chunk. And it takes the
    `tok` of the dispatch before it: a row whose index-0 token is NEGATIVE
    holds no token yet and is given its carry entry inside the program, so
    the scheduler can issue a dispatch before the one that samples its input
    has been fetched. One executable either way: fn(...) with no `carry`
    runs it with a carry of zeros (no token may then be negative) and
    returns what it always did; fn(..., tables, carry) returns `tok` as one
    value more, last. jit keeps an executable a placement of its arguments:
    whoever hands a carry of its own making places it as a returned `tok`
    is, NamedSharding(mesh, P()) (rows sharded over dp: P('dp')).
    attn_window statically bounds the cache positions attention reads (see
    models.forward.forward); callers must keep start_pos + T <= attn_window.

    kv_block_tokens > 0 selects the device-resident paged KV layout
    (docs/PAGED_KV.md): the caches are a (L, N, hk, bt, hs) block pool and
    the returned fn takes a trailing per-row block-table argument —
    fn(params, rope, tokens, k_cache, v_cache, start_pos, tables).
    """
    import jax.numpy as jnp
    import numpy as np

    from .mesh import AXIS_DP

    tp = mesh.shape[AXIS_TP]
    sp = mesh.shape.get(AXIS_SP, 1)
    dp = mesh.shape.get(AXIS_DP, 1)
    check_divisibility(spec, tp, sp, moe_sharding=moe_sharding)
    dtype = dtype or jnp.float32

    param_specs = _expand_pspec_tree(params, param_pspecs(params, moe_sharding))
    kv_spec = kv_cache_pspec_for_mesh(mesh)
    # data parallelism: batch rows shard over dp (cache rows already carry AXIS_DP on
    # their batch axis); each dp group runs an independent replica of the tp/sp
    # program with zero cross-group traffic — the throughput axis the reference
    # lacks entirely (batch hard-wired to 1, funcs.cpp:424). start_pos must then be
    # per-row (B,), sharded alongside the rows.
    tok_spec = P(AXIS_DP) if dp > 1 else P()
    pos_spec = P(AXIS_DP) if dp > 1 else P()

    paged = kv_block_tokens > 0
    if paged:
        assert sp == 1 and dp == 1, "paged KV is tp-only (no sp/dp sharding)"
        # pool layout (L, N, hk, bt, hs): heads stay on tp, blocks replicated
        kv_spec = P(None, None, AXIS_TP)
    # a 1-member tp axis has nothing to reduce: drop the axis name so every
    # psum/all_gather elides. Compressed collectives keep the axis: the Q80
    # wire quantization is part of their numerics even over one member.
    tp_axis = AXIS_TP if (tp > 1 or compress_collectives) else None
    fwd = functools.partial(forward, spec=spec, dtype=dtype, axis_name=tp_axis,
                            sp_axis_name=AXIS_SP if sp > 1 else None, sp_size=sp,
                            use_pallas=use_pallas,
                            compress_collectives=compress_collectives,
                            attn_window=attn_window,
                            block_tokens=kv_block_tokens,
                            paged_kernel=paged_kernel, moe_stats=moe_stats)
    rope_type = spec.rope_type
    out_specs = ((tok_spec, kv_spec, kv_spec) + ((P(),) if moe_stats else ())
                 + (tok_spec,))

    def step(p, rope_cos, rope_sin, tokens, kc, vc, start_pos, carry,
             tables=None):
        rope = RopeTables(rope_cos, rope_sin, rope_type)
        first = tokens[:, 0]
        tokens = tokens.at[:, 0].set(jnp.where(first < 0, carry, first))
        more = {} if tables is None else {"block_tables": tables}
        out = fwd(p, rope=rope, tokens=tokens, k_cache=kc, v_cache=vc,
                  start_pos=start_pos, **more)
        return *out, jnp.argmax(out[0][:, -1], axis=-1).astype(jnp.int32)

    sharded = jax.shard_map(
        step, mesh=mesh,
        in_specs=(param_specs, P(), P(), tok_spec, kv_spec, kv_spec, pos_spec,
                  tok_spec) + ((P(),) if paged else ()),
        out_specs=out_specs,
        check_vma=False,
    )
    jitted = jax.jit(sharded, donate_argnums=(4, 5) if donate_cache else ())
    zeros = {}  # rows -> the carry of a caller that hands none

    def run(p, rope: RopeTables, tokens, kc, vc, start_pos, tables=None,
            carry=None):
        given = carry is not None
        rows = len(tokens)
        if not given and isinstance(tokens, jax.core.Tracer):
            carry = jnp.zeros((rows,), jnp.int32)  # traced: nothing to keep
        elif not given:
            if rows not in zeros:  # a host array put there: nothing compiles
                zeros[rows] = jax.device_put(
                    np.zeros((rows,), np.int32),
                    NamedSharding(mesh, tok_spec))
            carry = zeros[rows]
        out = jitted(p, rope.cos, rope.sin, tokens, kc, vc, start_pos, carry,
                     *((tables,) if paged else ()))
        return out if given else out[:-1]

    return run
