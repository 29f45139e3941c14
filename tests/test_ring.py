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
from distributed_llama_tpu.ops.attention import gqa_attention, update_kv_cache
from distributed_llama_tpu.ops.ring_attention import (
    ring_attention,
    update_kv_cache_sharded,
)
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
    """Ring attention over sp sequence shards == plain attention over the full cache."""
    rng = np.random.RandomState(0)
    b, hq, hk, s, hs = 1, 8, 4, 32, 16
    pos0 = 11  # queries at positions 11..11+t
    q = jnp.asarray(rng.randn(b, t, hq, hs).astype(np.float32))
    kc = jnp.asarray(rng.randn(b, hk, s, hs).astype(np.float32))
    vc = jnp.asarray(rng.randn(b, hk, s, hs).astype(np.float32))
    positions = pos0 + jnp.arange(t, dtype=jnp.int32)

    want = np.asarray(gqa_attention(q, kc, vc, positions))

    mesh = make_mesh(sp=sp, tp=1)

    def f(q, kc, vc):
        return ring_attention(q, kc, vc, positions, axis_name="sp", axis_size=sp)

    sharded = jax.jit(jax.shard_map(
        f, mesh=mesh,
        in_specs=(P(), P(None, None, "sp", None), P(None, None, "sp", None)),
        out_specs=P(), check_vma=False))
    got = np.asarray(sharded(q, kc, vc))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("t,start", [(1, 0), (1, 17), (8, 12), (8, 16)])
def test_update_kv_cache_sharded_matches_full(t, start):
    """Sharded cache writes (incl. chunks straddling a shard boundary) == full-cache
    update then manual sharding."""
    rng = np.random.RandomState(1)
    b, hk, s, hs, sp = 1, 2, 32, 8, 4
    kc = jnp.asarray(rng.randn(b, hk, s, hs).astype(np.float32))
    vc = jnp.asarray(rng.randn(b, hk, s, hs).astype(np.float32))
    k_new = jnp.asarray(rng.randn(b, t, hk, hs).astype(np.float32))
    v_new = jnp.asarray(rng.randn(b, t, hk, hs).astype(np.float32))

    kw, vw = update_kv_cache(kc, vc, k_new, v_new, jnp.int32(start))

    mesh = make_mesh(sp=sp, tp=1)
    kvp = P(None, None, "sp", None)

    def f(kc, vc, k_new, v_new):
        return update_kv_cache_sharded(kc, vc, k_new, v_new, jnp.int32(start),
                                       axis_name="sp")

    sharded = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=(kvp, kvp, P(), P()),
        out_specs=(kvp, kvp), check_vma=False))
    kg, vg = sharded(kc, vc, k_new, v_new)
    np.testing.assert_allclose(np.asarray(kg), np.asarray(kw), atol=1e-6)
    np.testing.assert_allclose(np.asarray(vg), np.asarray(vw), atol=1e-6)


def _tiny_spec():
    return ModelSpec(arch_type=ArchType.LLAMA, dim=64, hidden_dim=128, n_layers=2,
                     n_heads=4, n_kv_heads=4, vocab_size=256, seq_len=32,
                     rope_type=RopeType.LLAMA).resolved()


@pytest.mark.parametrize("cache_write", ["inscan", "deferred"])
def test_forward_sp_tp_equals_unsharded(cache_write):
    """Full model on a 2x2 (sp x tp) mesh == single-device forward: prefill then a
    decode step continuing from the sharded cache. Both cache disciplines — the
    deferred form keeps the sequence-sharded caches loop-invariant and commits via
    the masked window write (commit_kv_rows_sharded)."""
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
    step = make_sharded_forward(spec, mesh, sparams, donate_cache=False,
                                cache_write=cache_write)
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


def test_sp_deferred_cache_state_matches_inscan():
    """After prefill + a boundary-straddling chunk + a decode step, the deferred
    (striped) cache must hold the same committed rows as inscan once the stripe
    permutation is undone."""
    spec = _tiny_spec()  # seq_len=32, sp=2 -> shard size 16
    params = init_random_params(spec, FloatType.F32, seed=9)
    rope = RopeTables.create(spec)
    mesh = make_mesh(sp=2, tp=2)
    sparams = shard_params(params, mesh, spec)

    caches = {}
    for cw in ("inscan", "deferred"):
        step = make_sharded_forward(spec, mesh, sparams, donate_cache=False,
                                    cache_write=cw)
        kc, vc = init_sharded_kv_cache(spec, mesh)
        # prefill 12, then a 8-token chunk at 12..20 (straddles the shard
        # boundary at 16), then a decode step at 20
        _, kc, vc = step(sparams, rope, jnp.asarray([list(range(1, 13))]), kc, vc,
                         jnp.int32(0))
        _, kc, vc = step(sparams, rope, jnp.asarray([list(range(20, 28))]), kc, vc,
                         jnp.int32(12))
        _, kc, vc = step(sparams, rope, jnp.asarray([[3]]), kc, vc, jnp.int32(20))
        caches[cw] = (np.asarray(kc), np.asarray(vc))

    kd = _destripe(caches["deferred"][0], sp=2)
    vd = _destripe(caches["deferred"][1], sp=2)
    # committed region [0, 21) must agree exactly; beyond it is unwritten scratch
    np.testing.assert_allclose(kd[:, :, :, :21],
                               caches["inscan"][0][:, :, :, :21], atol=1e-6)
    np.testing.assert_allclose(vd[:, :, :, :21],
                               caches["inscan"][1][:, :, :, :21], atol=1e-6)


def test_sp_deferred_chunk_wider_than_shard():
    """sp=4 on seq_len=32 gives 8-slot shards; a 16-token prefill chunk is wider
    than a shard — the deferred commit must scatter it across multiple shards
    (regression: the windowed write only handles t <= shard size)."""
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
    step = make_sharded_forward(spec, mesh, sparams, donate_cache=False,
                                cache_write="deferred")
    kc, vc = init_sharded_kv_cache(spec, mesh)
    got, gkc, gvc = step(sparams, rope, tokens, kc, vc, jnp.int32(0))
    got2, _, _ = step(sparams, rope, jnp.asarray([[3]]), gkc, gvc, jnp.int32(16))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4,
                               rtol=1e-3)
    np.testing.assert_allclose(np.asarray(got2), np.asarray(want2), atol=2e-4,
                               rtol=1e-3)


def test_sp_deferred_windowed_ring_matches_full():
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
                                cache_write="deferred", attn_window=32)
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
