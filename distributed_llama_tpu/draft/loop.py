"""Drafter device programs: the catch-up + greedy-draft scan and the
chunked prefill step (docs/SERVING.md "Model-based drafting").

The drafter is a SECOND model sharded over the target engine's mesh, so its
programs mirror the target's idioms (runtime/device_loop.py) at the
drafter's own ModelSpec. Two programs live here:

- make_draft_loop: ONE `lax.scan` per proposal turn. Each row first
  force-ingests its catch-up tokens (target-delivered tokens the drafter has
  not yet seen — typically the correction/bonus token of the previous verify
  turn), then free-runs greedy argmax for k steps, feeding each draft back
  as the next input. Both phases share the scan body: step j of row r takes
  catchup[r, j] while j < ncatch[r], its own previous argmax afterwards, and
  parks (clamped scratch write, masked reads) past budget[r] = ncatch[r] +
  k[r] - 1. The host slices row r's drafts from the returned (S, B) argmax
  block at [ncatch[r]-1, ncatch[r]-1+k[r]). Scan lengths are bucketed
  (speculative.verify_block_bucket) so compile count stays O(log k).

- make_draft_step: the plain (B, T) forward for chunked catch-up prefill
  when a row's pending history exceeds what a scan should carry (fresh
  attach with a long prompt). A thin factory around
  parallel.tp.make_sharded_forward under its own name so the compile
  manifest (analysis/compile_audit.py) tracks drafter programs apart from
  the target's.

Drafting is greedy-only by design: drafts are PROPOSALS — the target's
verify samples with the request's real temperature/topp and the usual
acceptance identity holds for any proposal content, so the drafter never
needs the xorshift* machinery.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..models.forward import forward
from ..models.spec import ModelSpec
from ..ops.rope import RopeTables
from ..parallel.mesh import AXIS_SP
from ..parallel.sharding import kv_cache_pspec_for_mesh, param_pspecs
from ..parallel.tp import _expand_pspec_tree
from ..resilience import faults


def make_draft_step(spec: ModelSpec, mesh, params, **kw):
    """Chunked drafter forward — fn(params, rope, tokens (B, T), kc, vc,
    start_pos (B,)) -> (logits, kc, vc). Same contract as
    make_sharded_forward; a separate factory name so drafter prefill
    programs get their own compile-manifest key."""
    from ..parallel.tp import make_sharded_forward

    return make_sharded_forward(spec, mesh, params, **kw)


def make_draft_loop(spec: ModelSpec, mesh, params, steps: int, *,
                    dtype=None, use_pallas: bool = False,
                    compress_collectives: bool = False,
                    donate_cache: bool = True,
                    moe_sharding: str = "slice"):
    """Build the drafter's catch-up + draft scan.

    fn(params, rope, catchup (B, S), kc, vc, start_pos (B,), ncatch (B,),
    budget (B,)) -> (toks (S, B), pos (B,), kc, vc).

    Per row r: steps j < ncatch[r] force-ingest catchup[r, j] at position
    start_pos[r] + j; steps ncatch[r] <= j < budget[r] ingest the previous
    argmax (free-running draft). toks[j, r] is the argmax after step j's
    ingestion, so row r's k drafts are toks[ncatch[r]-1 : ncatch[r]-1+k, r].
    Rows with budget 0 park: their scratch writes land clamped inside the
    cache on masked slots (the free-rollback discipline — the row's next
    real catch-up overwrites them). KV advances budget[r] positions for
    live rows; drafted-token KV beyond the confirmed frontier is adopted by
    the drafter exactly when the target later delivers the same token
    (draft/drafter.py push).
    """
    from ..parallel.mesh import AXIS_DP

    dtype = dtype or jnp.float32
    assert steps >= 1
    assert mesh.shape.get(AXIS_SP, 1) == 1 and \
        mesh.shape.get(AXIS_DP, 1) == 1, "the drafter is tp-only"
    param_specs = _expand_pspec_tree(params, param_pspecs(params, moe_sharding))
    kv_spec = kv_cache_pspec_for_mesh(mesh)
    rope_type = spec.rope_type
    seq_len = spec.seq_len

    from ..runtime.device_loop import _tp_axis

    fwd = functools.partial(forward, spec=spec, dtype=dtype,
                            axis_name=_tp_axis(mesh, compress_collectives),
                            sp_axis_name=None, sp_size=1,
                            use_pallas=use_pallas,
                            compress_collectives=compress_collectives,
                            attn_window=None)

    # hot-path: traced
    def loop(p, rope_cos, rope_sin, catchup, kc, vc, start_pos, ncatch,
             budget):
        rope = RopeTables(rope_cos, rope_sin, rope_type)

        def step(carry, j):
            tok, pos, kc, vc = carry
            live = j < budget  # (B,)
            forced = jax.lax.dynamic_index_in_dim(
                catchup, jnp.minimum(j, catchup.shape[1] - 1), axis=1,
                keepdims=False)  # (B,)
            inp = jnp.where(j < ncatch, forced, tok)
            step_pos = jnp.where(live, pos, jnp.minimum(pos, seq_len - 1))
            logits, kc, vc = fwd(p, rope=rope, tokens=inp[:, None],
                                 k_cache=kc, v_cache=vc, start_pos=step_pos)
            nxt = jnp.argmax(logits[:, -1].astype(jnp.float32),
                             axis=-1).astype(jnp.int32)
            tok = jnp.where(live, nxt, tok)
            pos = jnp.where(live, pos + 1, pos)
            return (tok, pos, kc, vc), nxt

        tok0 = catchup[:, 0]
        (tok, pos, kc, vc), toks = jax.lax.scan(
            step, (tok0, start_pos, kc, vc),
            jnp.arange(steps, dtype=jnp.int32))
        return toks, pos, kc, vc

    sharded = jax.shard_map(
        loop, mesh=mesh,
        in_specs=(param_specs, P(), P(), P(), kv_spec, kv_spec, P(), P(),
                  P()),
        out_specs=(P(), P(), kv_spec, kv_spec),
        check_vma=False,
    )
    donate = (4, 5) if donate_cache else ()
    jitted = jax.jit(sharded, donate_argnums=donate)

    # hot-path
    def run(p, rope: RopeTables, catchup, kc, vc, start_pos, ncatch, budget):
        faults.fire("draft.dispatch", steps=steps)
        return jitted(p, rope.cos, rope.sin,
                      jnp.asarray(catchup, jnp.int32), kc, vc,
                      jnp.asarray(start_pos, jnp.int32),
                      jnp.asarray(ncatch, jnp.int32),
                      jnp.asarray(budget, jnp.int32))

    return run
