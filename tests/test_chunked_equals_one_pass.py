"""Chunked equals one pass: a prefill of n tokens followed by one decode step
gives the logits and the committed cache rows of ONE pass over the n + 1 tokens.

That is what the cache discipline of models/forward.py has to deliver: the
caches are read-only in the layer scan (committed rows + the chunk's own k/v
through explicit key positions) and all layers' new rows are committed by one
write per cache after it. Stated for both cache kinds a dispatch can be handed,
the contiguous (L, B, hk, S, hs) caches and the block pool behind per-row
tables (the layout every cell runs, docs/PAGED_KV.md); the fused decode kernel
reads contiguous caches only. Chunked and one-pass differ by float
reassociation alone (the key axis is [window ++ chunk], not one run of rows),
hence ulp-scale tolerances.
"""

import numpy as np
import pytest
import jax.numpy as jnp

from distributed_llama_tpu.models.forward import forward, init_kv_cache
from distributed_llama_tpu.models.params import init_random_params
from distributed_llama_tpu.models.spec import ArchType, ModelSpec, RopeType
from distributed_llama_tpu.ops.rope import RopeTables
from distributed_llama_tpu.quants import FloatType

BT = 8  # tokens a pool block
KINDS = ["contiguous", "pool"]


def _spec(arch=ArchType.LLAMA, **kw):
    base = dict(arch_type=arch, dim=64, hidden_dim=96, n_layers=3, n_heads=4,
                n_kv_heads=2, vocab_size=128, seq_len=64, rope_type=RopeType.LLAMA)
    base.update(kw)
    return ModelSpec(**base).resolved()


class Cache:
    """Empty caches of one kind for `batch` rows, the keywords forward() takes
    with them, and how a position is spelled and a row read back."""

    def __init__(self, spec, kind, batch=1, per_row=False):
        self.kind, self.batch = kind, batch
        self.per_row = per_row or kind == "pool"  # the pool takes (B,) positions
        if kind == "pool":
            w = spec.seq_len // BT
            # block 0 is scratch; row b owns blocks 1 + b*w .. (b+1)*w, handed
            # out back to front so that a table is not the identity
            self.tables = jnp.asarray(
                1 + np.arange(batch * w)[::-1].reshape(batch, w), jnp.int32)
            shape = (spec.n_layers, batch * w + 1, spec.n_kv_heads, BT,
                     spec.head_size)
            self.k, self.v = jnp.zeros(shape), jnp.zeros(shape)
            self.kw = dict(block_tables=self.tables, block_tokens=BT)
        else:
            self.k, self.v = init_kv_cache(spec, batch=batch)
            self.kw = {}

    def pos(self, p):
        if not self.per_row:
            return jnp.int32(p)
        return jnp.broadcast_to(jnp.asarray(p, jnp.int32), (self.batch,))

    def rows(self, row, n):
        """The first n committed positions of `row`: (k, v), (L, hk, n, hs)."""
        if self.kind == "pool":
            p = np.arange(n)
            blk, off = np.asarray(self.tables)[row, p // BT], p % BT
            return tuple(np.asarray(c)[:, blk, :, off].transpose(1, 2, 0, 3)
                         for c in (self.k, self.v))
        return tuple(np.asarray(c)[:, row, :, :n] for c in (self.k, self.v))

    def step(self, fwd, tokens, p, **kw):
        logits, self.k, self.v = fwd(jnp.asarray(tokens), self.k, self.v,
                                     self.pos(p), **self.kw, **kw)
        return np.asarray(logits)


def _check(chunked, row, one, n, got, want):
    """Last-position logits agree, and the n committed rows of `row` of the
    chunked run with those of the one-row one-pass run."""
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    assert np.argmax(got) == np.argmax(want)
    for c, o in zip(chunked.rows(row, n), one.rows(0, n)):
        np.testing.assert_allclose(c, o, atol=1e-6, rtol=1e-4)


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("window", [None, 16])
def test_prefill_then_decode_equals_one_pass(window, kind):
    spec = _spec()
    params = init_random_params(spec, FloatType.F32, seed=11)
    rope = RopeTables.create(spec)
    row = [3, 9, 27, 81, 7, 42]

    def fwd(*a, **kw):
        return forward(params, spec, rope, *a, attn_window=window, **kw)

    one, chunked = Cache(spec, kind), Cache(spec, kind)
    want = one.step(fwd, [row], 0)
    chunked.step(fwd, [row[:5]], 0)
    got = chunked.step(fwd, [row[5:]], 5)
    _check(chunked, 0, one, 6, got[0, -1], want[0, -1])


@pytest.mark.parametrize("kind", KINDS)
def test_prefill_then_decode_equals_one_pass_with_state_layers(kind):
    """Layers whose mixer is a gated short convolution (LFM2): a prefill in
    chunks of 64, 8 and 1 and a decode step against ONE pass over the 74
    tokens. A chunk's first positions read the v rows behind them from the
    slot's ring, which the chunk before committed; the pool also gets each
    block end's snapshot, which is that state."""
    from distributed_llama_tpu.models.forward import (STATE_RING, StateCache,
                                                      init_state)
    from distributed_llama_tpu.models.spec import LayerKind, RouterScore

    spec = _spec(ArchType.MIXTRAL, n_layers=5, seq_len=128, head_dim=16,
                 rope_type=RopeType.FALCON, n_experts=4, n_active_experts=2,
                 hidden_dim=32, qk_norm=True, router_bias=True,
                 router_score=RouterScore.SIGMOID, lead_layers=1,
                 lead_hidden_dim=96,
                 kinds=(LayerKind("conv", 4, conv_kernel=3),
                        LayerKind("full", 4)), layer_kinds=(0, 1, 0, 0, 1))
    params = init_random_params(spec, FloatType.F32, seed=13)
    rope = RopeTables.create(spec)
    row = np.random.default_rng(2).integers(3, 128, 74).tolist()

    def caches():
        c = Cache(spec, kind)
        if kind == "pool":
            shape = (2,) + c.k.shape[1:]  # the two attention layers own rows
            c.k, v = jnp.zeros(shape), jnp.zeros(shape)
            c.v = StateCache(v, *init_state(spec, 1, shape[1], jnp.float32))
        assert isinstance(c.v, StateCache) and c.k.shape[0] == 2
        return c

    def fwd(*a, **kw):
        return forward(params, spec, rope, *a, **kw)

    one, chunked = caches(), caches()
    want = one.step(fwd, [row], 0)
    got, at = [], 0
    for n in (64, 8, 1, 1):
        got.append(chunked.step(fwd, [row[at:at + n]], at))
        at += n
    np.testing.assert_allclose(np.concatenate(got, axis=1), want, atol=1e-5,
                               rtol=1e-5)
    # the ring: v at the last STATE_RING positions, the same either way
    live = [p % STATE_RING for p in range(74 - STATE_RING, 74)]
    np.testing.assert_allclose(np.asarray(chunked.v.ring)[0, live, :3],
                               np.asarray(one.v.ring)[0, live, :3], atol=1e-6)
    np.testing.assert_allclose(np.asarray(chunked.k), np.asarray(one.k),
                               atol=1e-6)
    if kind == "pool":
        # blocks of 8: the snapshots of the nine finished blocks agree, and
        # block 8's (positions 64..71) is the ring's rows 70 and 71
        snaps_c, snaps_1 = (np.asarray(c.v.snaps)[0] for c in (chunked, one))
        np.testing.assert_allclose(snaps_c, snaps_1, atol=1e-6)
        blk = int(np.asarray(chunked.tables)[0, 8])
        ring = np.asarray(chunked.v.ring)[0]
        np.testing.assert_allclose(
            snaps_c[blk, :6].reshape(2, 3, -1),
            np.stack([ring[70 % STATE_RING, :3], ring[71 % STATE_RING, :3]]),
            atol=1e-6)


@pytest.mark.parametrize("kind", KINDS)
def test_prefill_then_decode_equals_one_pass_with_state_space_layers(kind):
    """Layers whose mixer is a state-space recurrence (granite-4.0-h-small's
    Mamba-2 layers): a prefill in chunks of 64, 8 and 1 and a decode step
    against ONE pass over the 74 tokens. Each chunk continues the slot's
    running matrices from where the one before left them and reads the
    convolution's three earlier u rows from the slot's ring; the logits, the
    matrices and the ring agree, and so do the multipliers and the stated
    attention scale, which both runs go through."""
    from distributed_llama_tpu.models.forward import (STATE_RING, StateCache,
                                                      init_state)
    from distributed_llama_tpu.models.spec import LayerKind

    spec = _spec(ArchType.MIXTRAL, n_layers=4, seq_len=128, head_dim=16,
                 n_experts=4, n_active_experts=2, hidden_dim=32,
                 shared_hidden_dim=64, embedding_multiplier=12.0,
                 residual_multiplier=0.22, logits_scaling=16.0,
                 attn_multiplier=0.5, state_snapshots=3,
                 kinds=(LayerKind("mamba", 4, conv_kernel=4, ssm_heads=4,
                                  ssm_head_dim=32, ssm_state=16),
                        LayerKind("attention", 4, rope_type=RopeType.NONE)),
                 layer_kinds=(0, 0, 1, 0))
    params = init_random_params(spec, FloatType.F32, seed=17)
    rope = RopeTables.create(spec)
    row = np.random.default_rng(2).integers(3, 128, 74).tolist()

    def caches():
        c = Cache(spec, kind)
        if kind == "pool":
            shape = (1,) + c.k.shape[1:]  # the one attention layer owns rows
            c.k, v = jnp.zeros(shape), jnp.zeros(shape)
            c.v = StateCache(v, *init_state(spec, 1, shape[1], jnp.float32))
            assert c.v.snap_h.shape[0] == 4 and c.v.ctl.shape == (2, 1, 1)
        assert isinstance(c.v, StateCache) and c.k.shape[0] == 1
        assert c.v.h.shape == (1, 3, 4, 32, 16)
        return c

    def fwd(*a, **kw):
        return forward(params, spec, rope, *a, **kw)

    one, chunked = caches(), caches()
    want = one.step(fwd, [row], 0)
    got, at = [], 0
    for n in (64, 8, 1, 1):
        got.append(chunked.step(fwd, [row[at:at + n]], at))
        at += n
    np.testing.assert_allclose(np.concatenate(got, axis=1), want, atol=2e-6,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(chunked.v.h), np.asarray(one.v.h),
                               atol=1e-5)
    assert np.abs(np.asarray(one.v.h)).max() > 0
    live = [p % STATE_RING for p in range(74 - STATE_RING, 74)]
    np.testing.assert_allclose(np.asarray(chunked.v.ring)[0, live, :3],
                               np.asarray(one.v.ring)[0, live, :3], atol=1e-6)
    np.testing.assert_allclose(np.asarray(chunked.k), np.asarray(one.k),
                               atol=1e-6)


def test_fused_decode_kernel_equals_one_pass():
    """The one-row decode glue: use_pallas + T = 1 + a scalar position routes
    through the fused decode-attention kernel (interpret off-TPU). Pins the
    q.reshape head grouping, k_t[0] shapes, window wiring and dtype casts of
    that branch twice: tightly against the same dispatch with its position
    spelled per row, which takes the XLA reader with the same matvec kernels,
    and against one XLA pass over all four tokens at the scale of the Q80
    activation quantization only the kernels do."""
    spec = _spec(dim=64, hidden_dim=96)
    params = init_random_params(spec, FloatType.Q40, seed=9)
    rope = RopeTables.create(spec)
    from distributed_llama_tpu.models.params import prepare_for_pallas

    pp = prepare_for_pallas(params)
    row = [1, 2, 3, 7]

    kc, vc = init_kv_cache(spec)
    one, okc, ovc = forward(params, spec, rope, jnp.asarray([row]), kc, vc,
                            jnp.int32(0))
    _, kc, vc = forward(params, spec, rope, jnp.asarray([row[:3]]), kc, vc,
                        jnp.int32(0))
    tok = jnp.asarray([row[3:]])
    xla, _, _ = forward(pp, spec, rope, tok, kc, vc, jnp.asarray([3], jnp.int32),
                        use_pallas=True, attn_window=16)
    got, gkc, gvc = forward(pp, spec, rope, tok, kc, vc, jnp.int32(3),
                            use_pallas=True, attn_window=16)
    got, xla, one = np.asarray(got), np.asarray(xla), np.asarray(one)[:, -1:]
    rel = np.abs(got - xla).max() / (np.abs(xla).max() + 1e-9)
    assert rel < 1e-4, rel
    rel = np.abs(got - one).max() / (np.abs(one).max() + 1e-9)
    assert rel < 0.03, rel
    assert np.argmax(got, -1).tolist() == np.argmax(xla, -1).tolist()
    # layer 0's new row sees no attention: only the activation quantization
    np.testing.assert_allclose(np.asarray(gkc)[0, :, :, :4],
                               np.asarray(okc)[0, :, :, :4], atol=0.03)


@pytest.mark.parametrize("kind", KINDS)
def test_rows_at_their_own_positions_equal_one_pass_each(kind):
    """Continuous-batching shape: per-row start_pos, batch 2, rows at DIFFERENT
    depths. The per-row slot masking and the per-row commit must each honor
    its own offset (identical offsets would be indistinguishable from the
    scalar path), and what a row holds past its committed length (here the
    stale seed rows 2..4 of row 1) must stay masked."""
    spec = _spec()
    params = init_random_params(spec, FloatType.F32, seed=5)
    rope = RopeTables.create(spec)

    def fwd(*a, **kw):
        return forward(params, spec, rope, *a, **kw)

    seed = [[1, 2, 3, 11, 12], [4, 5, 6, 13, 14]]
    chunked = Cache(spec, kind, batch=2, per_row=True)
    chunked.step(fwd, seed, 0)
    got = chunked.step(fwd, [[7], [8]], [5, 2])
    for r, row in enumerate((seed[0] + [7], seed[1][:2] + [8])):
        one = Cache(spec, kind)
        want = one.step(fwd, [row], 0)
        _check(chunked, r, one, len(row), got[r, -1], want[0, -1])


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("arch,kw", [
    (ArchType.MIXTRAL, dict(n_experts=4, n_active_experts=2,
                            rope_type=RopeType.FALCON)),
    (ArchType.GROK1, dict(n_experts=4, n_active_experts=2,
                          rope_type=RopeType.FALCON)),
])
def test_prefill_then_decode_equals_one_pass_moe(arch, kw, kind):
    spec = _spec(arch, **kw)
    params = init_random_params(spec, FloatType.F32, seed=2)
    rope = RopeTables.create(spec)
    row = [3, 9, 27, 42]

    def fwd(*a, **kw):
        return forward(params, spec, rope, *a, **kw)

    one, chunked = Cache(spec, kind), Cache(spec, kind)
    want = one.step(fwd, [row], 0)
    chunked.step(fwd, [row[:3]], 0)
    got = chunked.step(fwd, [row[3:]], 3)
    _check(chunked, 0, one, 4, got[0, -1], want[0, -1])


def test_kv_replicated_mesh_decode_equals_one_pass():
    """tp=8 > n_kv_heads=2 (the 405B-class GQA shape): prefill and a use_pallas
    decode step over the KV-replicated mesh must match one pass of the
    replicated single-device model."""
    from distributed_llama_tpu.models.params import prepare_for_pallas
    from distributed_llama_tpu.parallel.mesh import make_mesh
    from distributed_llama_tpu.parallel.tp import (init_sharded_kv_cache,
                                                   make_sharded_forward, shard_params)

    spec = _spec(dim=256, hidden_dim=256, n_layers=2, n_heads=8, n_kv_heads=2,
                 vocab_size=128, seq_len=32)
    params = init_random_params(spec, FloatType.Q40, seed=8)
    rope = RopeTables.create(spec)
    kc, vc = init_kv_cache(spec)
    want, _, _ = forward(params, spec, rope, jnp.asarray([[1, 2, 5]]), kc, vc,
                         jnp.int32(0))

    mesh = make_mesh(tp=8)
    pp = shard_params(prepare_for_pallas(params, tp=8), mesh, spec)
    step = make_sharded_forward(spec, mesh, pp, donate_cache=False,
                                use_pallas=True)
    kc8, vc8 = init_sharded_kv_cache(spec, mesh)
    _, kc8, vc8 = step(pp, rope, jnp.asarray([[1, 2]]), kc8, vc8, jnp.int32(0))
    got, _, _ = step(pp, rope, jnp.asarray([[5]]), kc8, vc8, jnp.int32(2))
    got, want = np.asarray(got), np.asarray(want)[:, -1:]
    rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
    assert rel < 0.03, rel  # Q80 activation-quantization error scale
    assert np.argmax(got, -1).tolist() == np.argmax(want, -1).tolist()


@pytest.mark.parametrize("kind", KINDS)
def test_sharded_step_prefill_then_decode_equals_one_pass(kind):
    """tp=2 shard_map: the same property of the step built over the mesh."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_llama_tpu.parallel.mesh import AXIS_TP, make_mesh
    from distributed_llama_tpu.parallel.tp import (init_sharded_kv_cache,
                                                   make_sharded_forward, shard_params)

    spec = _spec(dim=64, hidden_dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
                 vocab_size=128, seq_len=32)
    params = init_random_params(spec, FloatType.Q40, seed=3)
    mesh = make_mesh(tp=2)
    rope = RopeTables.create(spec)
    base = shard_params(params, mesh, spec)
    step = make_sharded_forward(spec, mesh, base, donate_cache=False,
                                kv_block_tokens=BT if kind == "pool" else 0)

    def fwd(tokens, kc, vc, pos, block_tables=None, block_tokens=0):
        tables = () if block_tables is None else (block_tables,)
        return step(base, rope, tokens, kc, vc, pos, *tables)

    def cache():
        c = Cache(spec, kind)
        if kind == "pool":
            sh = NamedSharding(mesh, P(None, None, AXIS_TP))
            c.k, c.v = jax.device_put(c.k, sh), jax.device_put(c.v, sh)
        else:
            c.k, c.v = init_sharded_kv_cache(spec, mesh)
        return c

    row = [1, 2, 3, 9]
    one, chunked = cache(), cache()
    want = one.step(fwd, [row], 0)
    chunked.step(fwd, [row[:3]], 0)
    got = chunked.step(fwd, [row[3:]], 3)
    _check(chunked, 0, one, 4, got[0, -1], want[0, -1])
