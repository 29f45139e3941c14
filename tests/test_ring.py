"""Ring attention / sequence parallelism tests (8-device CPU mesh).

The reference has NO sequence parallelism (SURVEY.md §5); these tests hold the new
capability to the same standard as its TP tests: sharded execution must equal unsharded
execution (the commands-test pattern, src/commands-test.cpp:6-79)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from distributed_llama_tpu.models.forward import forward, init_kv_cache
from distributed_llama_tpu.models.params import init_random_params
from distributed_llama_tpu.models.spec import ArchType, ModelSpec, RopeType
from distributed_llama_tpu.ops.attention import gqa_attention
from distributed_llama_tpu.ops.ring_attention import ring_attention
from distributed_llama_tpu.ops.rope import RopeTables
from distributed_llama_tpu.parallel.mesh import make_mesh
from distributed_llama_tpu.parallel.tp import (init_sharded_kv_cache, make_sharded_forward,
                                                shard_params)
from distributed_llama_tpu.quants import FloatType
from distributed_llama_tpu.runtime.engine import Engine
from distributed_llama_tpu.runtime.sampler import Sampler


@pytest.mark.parametrize("sp", [2, 4])
@pytest.mark.parametrize("t", [1, 5])
def test_ring_attention_equals_full(sp, t):
    """Ring attention over sp sequence shards == plain attention over the full
    cache. The shards are striped: member m's slot j holds position j*sp + m."""
    rng = np.random.RandomState(0)
    b, hq, hk, s, hs = 1, 8, 4, 32, 16
    pos0 = 11  # queries at positions 11..11+t
    q = jnp.asarray(rng.randn(b, t, hq, hs).astype(np.float32))
    kc = jnp.asarray(rng.randn(b, hk, s, hs).astype(np.float32))
    vc = jnp.asarray(rng.randn(b, hk, s, hs).astype(np.float32))
    positions = pos0 + jnp.arange(t, dtype=jnp.int32)

    want = np.asarray(gqa_attention(q, kc, vc, positions))

    mesh = make_mesh(sp=sp, tp=1)

    def stripe(c):  # global index m*Sb + j <- position j*sp + m
        return c.reshape(b, hk, s // sp, sp, hs).swapaxes(2, 3).reshape(c.shape)

    def f(q, kc, vc):
        return ring_attention(q, kc, vc, positions, axis_name="sp", axis_size=sp)

    sharded = jax.jit(jax.shard_map(
        f, mesh=mesh,
        in_specs=(P(), P(None, None, "sp", None), P(None, None, "sp", None)),
        out_specs=P(), check_vma=False))
    got = np.asarray(sharded(q, stripe(kc), stripe(vc)))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)


def _tiny_spec():
    return ModelSpec(arch_type=ArchType.LLAMA, dim=64, hidden_dim=128, n_layers=2,
                     n_heads=4, n_kv_heads=4, vocab_size=256, seq_len=32,
                     rope_type=RopeType.LLAMA).resolved()


def test_forward_sp_tp_equals_unsharded():
    """Full model on a 2x2 (sp x tp) mesh == single-device forward: prefill then a
    decode step continuing from the sharded cache, which stays loop-invariant in
    the layer scan and is committed via the masked window write
    (commit_kv_rows_sharded)."""
    spec = _tiny_spec()
    params = init_random_params(spec, FloatType.F32, seed=3)
    rope = RopeTables.create(spec)
    tokens = jnp.asarray([[1, 7, 23, 5, 2, 9, 11, 4]])

    kc, vc = init_kv_cache(spec)
    want, wkc, wvc = forward(params, spec, rope, tokens, kc, vc, jnp.int32(0))
    want2, _, _ = forward(params, spec, rope, jnp.asarray([[3]]), wkc, wvc,
                          jnp.int32(8))

    mesh = make_mesh(sp=2, tp=2)
    sparams = shard_params(params, mesh, spec)
    step = make_sharded_forward(spec, mesh, sparams, donate_cache=False)
    kc, vc = init_sharded_kv_cache(spec, mesh)
    got, gkc, gvc = step(sparams, rope, tokens, kc, vc, jnp.int32(0))
    got2, _, _ = step(sparams, rope, jnp.asarray([[3]]), gkc, gvc, jnp.int32(8))

    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(got2), np.asarray(want2), atol=2e-4,
                               rtol=1e-3)


def _destripe(cache: np.ndarray, sp: int) -> np.ndarray:
    """Undo the striped sp layout: member m's local slot j holds position
    j*sp + m (ops/ring_attention.py); the GLOBAL array concatenates members'
    shards, so array index m*Sb + j -> position j*sp + m."""
    L, B, hk, S, hs = cache.shape
    sb = S // sp
    out = np.zeros_like(cache)
    for m in range(sp):
        for j in range(sb):
            out[:, :, :, j * sp + m] = cache[:, :, :, m * sb + j]
    return out


def test_sp_striped_cache_state_matches_unsharded():
    """After prefill + a chunk that straddles the contiguous shard boundary + a
    decode step, the striped cache must hold the unsharded run's committed rows
    once the stripe permutation is undone."""
    spec = _tiny_spec()  # seq_len=32, sp=2 -> shard size 16
    params = init_random_params(spec, FloatType.F32, seed=9)
    rope = RopeTables.create(spec)
    mesh = make_mesh(sp=2, tp=2)
    sparams = shard_params(params, mesh, spec)
    step = make_sharded_forward(spec, mesh, sparams, donate_cache=False)

    def run(step, params, kc, vc):
        # prefill 12, then a 8-token chunk at 12..20 (across position 16),
        # then a decode step at 20
        for toks, pos in ((list(range(1, 13)), 0), (list(range(20, 28)), 12),
                          ([3], 20)):
            _, kc, vc = step(params, rope, jnp.asarray([toks]), kc, vc,
                             jnp.int32(pos))
        return np.asarray(kc), np.asarray(vc)

    kw, vw = run(lambda p, *a: forward(p, spec, *a), params,
                 *init_kv_cache(spec))
    kg, vg = run(step, sparams, *init_sharded_kv_cache(spec, mesh))
    # committed region [0, 21) must agree; beyond it is unwritten scratch
    np.testing.assert_allclose(_destripe(kg, sp=2)[:, :, :, :21],
                               kw[:, :, :, :21], atol=1e-5)
    np.testing.assert_allclose(_destripe(vg, sp=2)[:, :, :, :21],
                               vw[:, :, :, :21], atol=1e-5)


def test_sp_chunk_wider_than_shard():
    """sp=4 on seq_len=32 gives 8-slot shards; a 16-token prefill chunk is wider
    than a shard — the commit must spread it over every member's slot window
    (regression: a window write that only handled t <= shard size)."""
    spec = _tiny_spec()  # seq_len=32 -> sb=8 at sp=4
    params = init_random_params(spec, FloatType.F32, seed=4)
    rope = RopeTables.create(spec)
    tokens = jnp.asarray([[(i % 200) + 1 for i in range(16)]])

    kc, vc = init_kv_cache(spec)
    want, wkc, wvc = forward(params, spec, rope, tokens, kc, vc, jnp.int32(0))
    want2, _, _ = forward(params, spec, rope, jnp.asarray([[3]]), wkc, wvc,
                          jnp.int32(16))

    mesh = make_mesh(sp=4, tp=2)
    sparams = shard_params(params, mesh, spec)
    step = make_sharded_forward(spec, mesh, sparams, donate_cache=False)
    kc, vc = init_sharded_kv_cache(spec, mesh)
    got, gkc, gvc = step(sparams, rope, tokens, kc, vc, jnp.int32(0))
    got2, _, _ = step(sparams, rope, jnp.asarray([[3]]), gkc, gvc, jnp.int32(16))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4,
                               rtol=1e-3)
    np.testing.assert_allclose(np.asarray(got2), np.asarray(want2), atol=2e-4,
                               rtol=1e-3)


def test_sp_windowed_ring_matches_full():
    """Striped windowed ring: with attn_window=32 on a seq_len=64 cache, only
    ceil(32/sp)=16 slots per member rotate, and results must equal the
    unsharded forward while every live position is inside the window."""
    spec = ModelSpec(arch_type=ArchType.LLAMA, dim=64, hidden_dim=128, n_layers=2,
                     n_heads=4, n_kv_heads=4, vocab_size=256, seq_len=64,
                     rope_type=RopeType.LLAMA).resolved()
    params = init_random_params(spec, FloatType.F32, seed=6)
    rope = RopeTables.create(spec)
    tokens = jnp.asarray([[1, 7, 23, 5, 2, 9, 11, 4]])

    kc, vc = init_kv_cache(spec)
    want, wkc, wvc = forward(params, spec, rope, tokens, kc, vc, jnp.int32(0))
    want2, _, _ = forward(params, spec, rope, jnp.asarray([[3]]), wkc, wvc,
                          jnp.int32(8))

    mesh = make_mesh(sp=2, tp=2)
    sparams = shard_params(params, mesh, spec)
    step = make_sharded_forward(spec, mesh, sparams, donate_cache=False,
                                attn_window=32)
    kc, vc = init_sharded_kv_cache(spec, mesh)
    got, gkc, gvc = step(sparams, rope, tokens, kc, vc, jnp.int32(0))
    got2, _, _ = step(sparams, rope, jnp.asarray([[3]]), gkc, gvc, jnp.int32(8))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4,
                               rtol=1e-3)
    np.testing.assert_allclose(np.asarray(got2), np.asarray(want2), atol=2e-4,
                               rtol=1e-3)


def test_engine_generate_with_sp():
    """End-to-end greedy generation with sequence parallelism == tp-only engine."""
    spec = _tiny_spec()
    params = init_random_params(spec, FloatType.Q40, seed=5)
    sampler = Sampler(spec.vocab_size, temperature=0.0)
    prompt = [1, 9, 4]

    ref = Engine(spec, params, tp=1)
    want, _ = ref.generate(list(prompt), 10, sampler)

    eng = Engine(spec, params, tp=2, sp=2)
    got, _ = eng.generate(list(prompt), 10, sampler)
    assert got == want

    eng.reset()
    got2, _ = eng.generate_chunked(list(prompt), 10, sampler, chunk=4)
    assert got2 == want
