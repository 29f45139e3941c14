"""Shared-prefix KV cache tests (ISSUE 3, cache/ + BatchEngine/api integration).

Layers under test:
- radix.py against a brute-force longest-prefix oracle (random insert/match/
  evict with refcount invariants — the property test the satellite demands);
- block_pool.py hot/Q80 tiers (bit-exact hot round-trip, near-lossless cold);
- BatchEngine end-to-end: greedy AND seeded-stochastic outputs token-identical
  with the prefix cache enabled vs disabled, cross-slot reuse actually skips
  prefill, clamped-park truncation releases the radix reservation (regression
  for the _park_positions interaction);
- SingleSlotCache (api_server --batch 1 path): cross-conversation reuse after
  the resident conversation was displaced.
"""

import random
import time

import numpy as np
import pytest

from distributed_llama_tpu.cache import PrefixCache
from distributed_llama_tpu.cache.radix import RadixIndex
from distributed_llama_tpu.cache.block_pool import KVBlockPool
from distributed_llama_tpu.models.params import init_random_params
from distributed_llama_tpu.models.spec import ArchType, ModelSpec, RopeType
from distributed_llama_tpu.quants import FloatType
from distributed_llama_tpu.runtime.sampler import Sampler


def _spec(seq_len=128, dim=64):
    return ModelSpec(arch_type=ArchType.LLAMA, dim=dim, hidden_dim=128,
                     n_layers=2, n_heads=4, n_kv_heads=4, vocab_size=256,
                     seq_len=seq_len, rope_type=RopeType.LLAMA).resolved()


# ---------------------------------------------------------------------------
# radix.py: property test vs a brute-force oracle
# ---------------------------------------------------------------------------


class _Oracle:
    """Brute-force model of the index: a prefix-closed set of block-chains."""

    def __init__(self, bt):
        self.bt = bt
        self.chains: set[tuple] = set()  # each element: tuple of block-tuples

    def blocks(self, toks):
        return tuple(tuple(toks[i:i + self.bt])
                     for i in range(0, len(toks) - self.bt + 1, self.bt))

    def insert(self, toks, landed):
        blks = self.blocks(toks)[:landed]
        for i in range(1, len(blks) + 1):
            self.chains.add(blks[:i])

    def match_len(self, toks):
        blks = self.blocks(toks)
        n = 0
        while n < len(blks) and blks[:n + 1] in self.chains:
            n += 1
        return n


def test_radix_property_vs_oracle():
    rng = random.Random(1234)
    bt = 4
    tree = RadixIndex(block_tokens=bt)
    oracle = _Oracle(bt)
    handles = iter(range(10 ** 9))
    node_of = {}  # chain -> node (for targeted acquire/release bookkeeping)
    acquired = []  # list of chains currently acquired (via match+acquire)

    def rand_tokens():
        # draw from a small alphabet so prefixes actually collide
        return [rng.randrange(1, 6) for _ in range(rng.randrange(0, 20))]

    for step in range(3000):
        op = rng.random()
        toks = rand_tokens()
        if op < 0.4:  # insert
            chain = tree.insert(toks, lambda i: next(handles))
            oracle.insert(toks, len(chain))
            for i, node in enumerate(chain):
                node_of[oracle.blocks(toks)[:i + 1]] = node
        elif op < 0.7:  # match
            got = tree.match(toks)
            assert len(got) == oracle.match_len(toks), (step, toks)
        elif op < 0.85:  # acquire a random cached chain (pins it)
            got = tree.match(toks)
            if got:
                keep = rng.randrange(1, len(got) + 1)
                tree.acquire(got[:keep])
                acquired.append(got[:keep])
        elif acquired and op < 0.95:  # release one acquired chain
            tree.release(acquired.pop(rng.randrange(len(acquired))))
        else:  # evict
            n = rng.randrange(1, 5)
            freed = set(tree.evict(n))
            assert len(freed) <= n
            # oracle removal: chains whose leaf handle was freed
            gone = {c for c, nd in node_of.items() if nd.handle in freed}
            for c in gone:
                oracle.chains.discard(c)
                del node_of[c]
        # global invariants after every op
        assert tree.nodes == len(oracle.chains), step
        assert set(tree.chains()) == oracle.chains, step
        pinned = sum(len(c) for c in acquired)
        assert tree.total_refs() == pinned, step
    for c in acquired:
        tree.release(c)
    assert tree.total_refs() == 0


def test_radix_eviction_respects_refs_and_lru():
    tree = RadixIndex(block_tokens=2)
    h = iter(range(100))
    tree.insert([1, 1, 2, 2], lambda i: next(h))      # chain A (2 blocks)
    tree.insert([9, 9], lambda i: next(h))            # chain B (1 block)
    a = tree.match([1, 1, 2, 2])
    tree.acquire(a)
    # A is pinned: only B is evictable, however much we ask for
    freed = tree.evict(10)
    assert len(freed) == 1 and tree.nodes == 2
    tree.release(a)
    tree.insert([9, 9], lambda i: next(h))  # recreate B, LRU-newer than A
    # A released: eviction cascades leaf -> parent, oldest first
    freed = tree.evict(2)
    assert len(freed) == 2 and tree.nodes == 1
    assert tree.match([1, 1, 2, 2]) == []
    assert len(tree.match([9, 9])) == 1


# ---------------------------------------------------------------------------
# block_pool.py: tiers
# ---------------------------------------------------------------------------


def test_pool_hot_roundtrip_bit_exact_and_capacity():
    pool = KVBlockPool(max_blocks=2)
    k = np.random.default_rng(0).normal(size=(2, 4, 8, 16)).astype(np.float32)
    v = 2 * k + 1
    h = pool.put(k, v)
    k2, v2 = pool.get(h)
    assert k2.dtype == np.float32
    assert np.array_equal(k2, k) and np.array_equal(v2, v)
    assert pool.put(k, v) is not None
    assert pool.put(k, v) is None  # full: pool never evicts on its own
    pool.free(h)
    assert pool.put(k, v) is not None


def test_pool_q80_tier_demotes_lru_and_dequantizes_close():
    pool = KVBlockPool(max_blocks=4, hot_blocks=1, q80=True)
    rng = np.random.default_rng(1)
    blocks = [rng.normal(size=(2, 4, 8, 16)).astype(np.float32)
              for _ in range(3)]
    hs = [pool.put(b, b + 0.25) for b in blocks]
    # hot budget 1: the two LRU blocks were demoted to Q80
    assert pool.is_cold(hs[0]) and pool.is_cold(hs[1]) and not pool.is_cold(hs[2])
    assert pool.hot_count() == 1 and pool.demoted_blocks == 2
    # Q80 is per-32-block absmax/127: reconstruction within ~1% of the range
    k0, v0 = pool.get(hs[0])
    assert k0.shape == blocks[0].shape and k0.dtype == np.float32
    tol = np.abs(blocks[0]).max() / 127 * 1.01
    assert np.abs(k0 - blocks[0]).max() <= tol
    assert np.abs(v0 - (blocks[0] + 0.25)).max() <= tol
    # cold tier is genuinely denser than f32
    assert pool.nbytes() < sum(2 * b.nbytes for b in blocks)


def test_prefix_cache_lookup_fetch_roundtrip():
    """lookup() hands out a lease only; fetch() gathers exactly the requested
    row span — including a skip that starts mid-block."""
    pc = PrefixCache(max_blocks=16, block_tokens=4)
    L, hk, hs = 2, 2, 8
    K = np.arange(L * hk * 12 * hs, dtype=np.float32).reshape(L, hk, 12, hs)
    V = K + 0.5
    toks = list(range(1, 13))
    pc.insert(toks, lambda a, b: (K[:, :, a:b], V[:, :, a:b]))
    lease = pc.lookup(toks + [99])
    assert lease is not None and lease.tokens == 12
    k, v = pc.fetch(lease)
    assert np.array_equal(k, K) and np.array_equal(v, V)
    k5, v5 = pc.fetch(lease, skip=5)  # mid-block skip
    assert np.array_equal(k5, K[:, :, 5:12]) and np.array_equal(v5, V[:, :, 5:12])
    pc.mark_seeded(lease, 12)
    pc.release(lease)
    # a second release must be a no-op (take-and-clear), not an underflow
    pc.release(lease)
    assert pc.total_refs() == 0
    st = pc.stats()
    assert st["hits"] == 1 and st["hit_tokens"] == 12


# ---------------------------------------------------------------------------
# BatchEngine end-to-end: cache on == cache off, cross-slot reuse, eviction
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def engines():
    from distributed_llama_tpu.runtime.batch_engine import BatchEngine

    spec = _spec()
    params = init_random_params(spec, FloatType.Q40, seed=17)
    be_off = BatchEngine(spec, params, slots=2, tp=1, prefix_cache=False)
    be_on = BatchEngine(spec, params, slots=2, tp=1, prefix_cache=True,
                        prefix_block_tokens=8)
    yield spec, be_off, be_on
    be_on.close()
    be_off.close()


SHARED = [1] + [10 + (i * 7) % 90 for i in range(33)]  # 34 tokens, 4 blocks of 8


def _run(be, prompt, n, temperature=0.0, seed=0, vocab=256):
    return be.submit(list(prompt),
                     n, Sampler(vocab, temperature=temperature,
                                seed=seed)).wait(timeout=180)


def _settle(pred, timeout=10):
    """wait() returns at done.set(); the scheduler thread harvests the slot
    into the pool just after — poll for the post-finish state."""
    t0 = time.time()
    while not pred() and time.time() - t0 < timeout:
        time.sleep(0.01)
    assert pred()


def test_cache_on_off_token_identical_greedy_and_stochastic(engines):
    spec, be_off, be_on = engines
    prompts = [SHARED + [200 + i] for i in range(3)] + [[1, 99, 98]]
    plans = [(0.0, 0), (0.8, 7), (0.8, 7), (0.0, 0)]  # greedy AND stochastic
    wants = [_run(be_off, p, 8, t, s) for p, (t, s) in zip(prompts, plans)]

    base = be_on.prefilled_tokens
    got = [_run(be_on, prompts[0], 8, *plans[0])]     # warms the radix
    got_unrel = _run(be_on, prompts[3], 8, *plans[3])  # dirties both slots' histories
    mid = be_on.prefilled_tokens
    got.append(_run(be_on, prompts[1], 8, *plans[1]))  # must seed from the pool
    seeded_prefill = be_on.prefilled_tokens - mid
    got.append(_run(be_on, prompts[2], 8, *plans[2]))
    got.append(got_unrel)

    assert got == wants
    # the seeded request prefilled only its uncached suffix: 35-token prompt,
    # 32 tokens (4 full blocks) seeded from the pool
    assert seeded_prefill <= len(prompts[1]) - 32
    st = be_on.prefix_cache.stats()
    # apply-time accounting: prompts[1] seeded from the pool (hit); prompts[2]
    # found its prefix on the slot prompts[1] vacated, so its lookup matched
    # but the copy-free rewind served it (unused_hit, NOT a pool hit)
    assert st["hits"] >= 1 and st["hit_tokens"] >= 30
    assert st["unused_hits"] >= 1
    _settle(lambda: be_on.prefix_cache.total_refs() == 0)  # every lease released


def test_a_prefix_hit_on_a_model_with_state_layers_equals_cold_prefill():
    """Layers that hold a state (LFM2's convolutions): a hit of n whole
    blocks continues from the n-th block's snapshot and gives the tokens a
    cold prefill gives, greedy and seeded-stochastic; the hit is whole blocks
    alone (32 of the 34 shared tokens), and the engine without the paged
    pool refuses the model."""
    import jax.numpy as jnp

    from distributed_llama_tpu.models.spec import LayerKind, RouterScore
    from distributed_llama_tpu.runtime.batch_engine import BatchEngine

    spec = ModelSpec(
        arch_type=ArchType.MIXTRAL, dim=64, hidden_dim=32, n_layers=4,
        n_heads=4, n_kv_heads=2, vocab_size=256, seq_len=128, n_experts=4,
        n_active_experts=2, head_dim=16, rope_type=RopeType.FALCON,
        qk_norm=True, router_bias=True, router_score=RouterScore.SIGMOID,
        kinds=(LayerKind("conv", 4, conv_kernel=3), LayerKind("full", 4)),
        layer_kinds=(0, 1, 0, 0)).resolved()
    params = init_random_params(spec, FloatType.Q40, seed=19)
    kw = dict(slots=2, tp=1, kv_block_tokens=8, dtype=jnp.float32)
    with pytest.raises(ValueError, match="dense per-slot caches"):
        BatchEngine(spec, params, paged_kv=False, **kw)
    be_off = BatchEngine(spec, params, prefix_cache=False, **kw)
    be_on = BatchEngine(spec, params, prefix_cache=True, **kw)
    try:
        prompts = [SHARED + [200 + i] for i in range(3)] + [[1, 99, 98]]
        plans = [(0.0, 0), (0.8, 7), (0.8, 7), (0.0, 0)]
        wants = [_run(be_off, p, 8, t, s) for p, (t, s) in zip(prompts, plans)]
        got = [_run(be_on, prompts[0], 8, *plans[0])]
        unrelated = _run(be_on, prompts[3], 8, *plans[3])
        _run(be_on, [7, 8, 9, 10], 4)  # dirties the other slot's history too
        mid = be_on.prefilled_tokens
        got.append(_run(be_on, prompts[1], 8, *plans[1]))
        assert be_on.prefilled_tokens - mid == len(prompts[1]) - 32
        got.append(_run(be_on, prompts[2], 8, *plans[2]))
        assert got + [unrelated] == wants
        assert be_on.prefix_cache.stats()["hit_tokens"] >= 32
    finally:
        be_on.close()
        be_off.close()


def test_a_prefix_hit_on_a_state_space_model_lands_on_a_stride_snapshot():
    """Layers whose state is a matrix a head (granite-4.0-h-small's Mamba-2
    layers): which blocks carry a snapshot is the cache manager's decision,
    one every 256 positions. A hit on 300 shared tokens continues from the
    block that ends at 255 (256 tokens, not the 296 of whole blocks) and
    gives the tokens a cold prefill gives, greedy and seeded-stochastic; a
    hit on 200 shared tokens has no snapshot under it and prefills from 0."""
    import jax.numpy as jnp

    from distributed_llama_tpu.models.spec import LayerKind
    from distributed_llama_tpu.runtime.batch_engine import BatchEngine

    spec = ModelSpec(
        arch_type=ArchType.MIXTRAL, dim=64, hidden_dim=32, n_layers=4,
        n_heads=4, n_kv_heads=2, vocab_size=256, seq_len=512, n_experts=4,
        n_active_experts=2, head_dim=16, rope_type=RopeType.FALCON,
        shared_hidden_dim=64, embedding_multiplier=12.0,
        residual_multiplier=0.22, logits_scaling=16.0, attn_multiplier=0.5,
        state_snapshots=4,
        kinds=(LayerKind("mamba", 4, conv_kernel=4, ssm_heads=4,
                         ssm_head_dim=32, ssm_state=16),
               LayerKind("attention", 4, rope_type=RopeType.NONE)),
        layer_kinds=(0, 1, 0, 0)).resolved()
    params = init_random_params(spec, FloatType.Q40, seed=23)
    kw = dict(slots=2, tp=1, kv_block_tokens=8, dtype=jnp.float32)
    shared = np.random.default_rng(6).integers(3, 256, 300).tolist()
    be_off = BatchEngine(spec, params, prefix_cache=False, **kw)
    be_on = BatchEngine(spec, params, prefix_cache=True, **kw)
    try:
        prompts = [shared + [200 + i] for i in range(3)] + [
            shared[:200] + [7, 7]]
        plans = [(0.0, 0), (0.8, 7), (0.8, 7), (0.0, 0)]
        wants = [_run(be_off, p, 8, t, s) for p, (t, s) in zip(prompts, plans)]
        got = [_run(be_on, prompts[0], 8, *plans[0])]
        _run(be_on, [7, 8, 9, 10], 4)  # dirties the other slot's history too
        mid = be_on.prefilled_tokens
        got.append(_run(be_on, prompts[1], 8, *plans[1]))
        assert be_on.prefilled_tokens - mid == len(prompts[1]) - 256
        got.append(_run(be_on, prompts[2], 8, *plans[2]))
        mid = be_on.prefilled_tokens
        got.append(_run(be_on, prompts[3], 8, *plans[3]))
        assert be_on.prefilled_tokens - mid == len(prompts[3])
        assert got == wants
        assert be_on.prefix_cache.stats()["hit_tokens"] >= 256
        assert be_on.kv_pool.snapshots.held() >= 1
    finally:
        be_on.close()
        be_off.close()


def test_concurrent_shared_prefix_requests_identical(engines):
    spec, be_off, be_on = engines
    prompts = [SHARED + [150 + i] for i in range(4)]
    wants = [_run(be_off, p, 6) for p in prompts]
    _run(be_on, prompts[0], 6)  # warm the cache
    reqs = [be_on.submit(list(p), 6, Sampler(spec.vocab_size, temperature=0.0))
            for p in prompts]
    outs = [r.wait(timeout=180) for r in reqs]
    assert outs == wants
    _settle(lambda: be_on.prefix_cache.total_refs() == 0)


def test_eviction_under_tiny_pool_keeps_outputs_identical(engines):
    """A pool far smaller than the working set must still be correct — every
    miss just prefills (the cache is an optimization, never a correctness
    gate) and eviction churns without corrupting the tree."""
    from distributed_llama_tpu.runtime.batch_engine import BatchEngine

    spec, be_off, _ = engines
    params = init_random_params(spec, FloatType.Q40, seed=17)
    # paged_kv=False: this pins the DENSE host pool's eviction semantics
    # (the --no-paged-kv path); the paged analog lives in test_paged_kv.py
    be = BatchEngine(spec, params, slots=2, tp=1, prefix_cache=True,
                     prefix_block_tokens=8, prefix_cache_blocks=3,
                     paged_kv=False)
    try:
        prompts = [SHARED + [140 + i] for i in range(2)] + [[1, 77] + [30 + i for i in range(20)]]
        wants = [_run(be_off, p, 6) for p in prompts]
        got = [_run(be, p, 6) for p in prompts]
        got2 = [_run(be, p, 6) for p in prompts]  # second pass: churned pool
        assert got == wants and got2 == wants
        _settle(lambda: be.prefix_cache.total_refs() == 0)
        assert len(be.prefix_cache.pool) <= 3
    finally:
        be.close()


def test_context_end_with_cache_matches_off():
    """Drive rows to the context end (exercises the clamped-park and
    super-step history-truncation paths) with the cache enabled; outputs must
    match the cache-off engine exactly."""
    from distributed_llama_tpu.runtime.batch_engine import BatchEngine

    spec = _spec(seq_len=32)
    params = init_random_params(spec, FloatType.Q40, seed=5)
    prompts = [[1, 2, 3, 4, 5, 6, 7, 8, 9, 10], [1, 2, 3, 4, 5, 6, 7, 8, 11]]
    outs = {}
    for on in (False, True):
        be = BatchEngine(spec, params, slots=2, tp=1, prefix_cache=on,
                         prefix_block_tokens=4)
        try:
            if on:
                _run(be, prompts[0], 30)  # warm + insert near-full context
            reqs = [be.submit(list(p), 30, Sampler(spec.vocab_size,
                                                   temperature=0.0))
                    for p in prompts]
            outs[on] = [r.wait(timeout=180) for r in reqs]
            for r in reqs:
                assert r.finish == "length"
            if on:
                _settle(lambda: be.prefix_cache.total_refs() == 0)
                # the clamped super-step destroyed row s-1 mid-scan; the
                # finish harvest must have truncated BEFORE inserting, so no
                # chain may cover the full [0, s) range (block_tokens=4,
                # s=32: max depth 7 blocks = 28 tokens, never 8)
                chains = be.prefix_cache.radix.chains()
                assert chains and max(len(c) for c in chains) <= 7, (
                    max(len(c) for c in chains))
        finally:
            be.close()
    assert outs[True] == outs[False]


def test_clamped_park_releases_radix_reservation():
    """Regression (ISSUE 3 satellite): when a clamped park truncates
    slot.history below a lease's seeded length, the radix reservation must
    shrink with it — the tree must not stay pinned for rows the slot no
    longer holds (a stale pin blocks eviction and misstates what the slot
    can re-insert)."""
    from distributed_llama_tpu.runtime.batch_engine import BatchEngine

    spec = _spec(seq_len=32)
    params = init_random_params(spec, FloatType.Q40, seed=5)
    # paged_kv=False: white-box test of the DENSE lease-shrink machinery
    # (slot.history/lease poking); paged leases shrink through the same
    # manager's `truncate` path and are covered by test_paged_kv.py
    be = BatchEngine(spec, params, slots=2, tp=1, prefix_cache=True,
                     prefix_block_tokens=4, paged_kv=False)
    try:
        prompt = [1] + list(range(2, 26))  # 25 tokens -> 6 full blocks
        _run(be, prompt, 1)
        pc = be.prefix_cache
        _settle(lambda: pc.radix.nodes >= 6)  # harvest lands post-finish
        # simulate a seeded in-flight slot (as _assign leaves it)
        slot = be._slots[0]
        lease = pc.lookup(prompt)
        assert lease is not None and lease.tokens == 24
        slot.lease = lease
        slot.history = list(prompt[:24])
        slot.pos = 24
        # a 20-wide dispatch parks this row clamped at 32-20=12: rows >= 12
        # are overwritten, history truncates, and the lease MUST follow
        starts = be._park_positions(20)
        assert starts[0] == 12 and slot.history == prompt[:12]
        assert slot.lease.tokens == 12 and len(slot.lease.nodes) == 3
        # exactly the surviving 3 blocks stay pinned
        assert pc.radix.total_refs() == 3
        # the released tail is evictable again; the pinned prefix is not
        freed = pc.radix.evict(100)
        assert len(freed) == 3
        pc.release(slot.lease)
        slot.lease = None
        assert pc.total_refs() == 0
        slot.history, slot.pos = [], 0
    finally:
        be.close()


def test_seeding_into_dp_sharded_cache_matches():
    """dp=2 x tp=2: the seed scatter indexes the dp-SHARDED batch axis and the
    harvest gathers from it — outputs must still match the cache-off engine."""
    from distributed_llama_tpu.runtime.batch_engine import BatchEngine

    spec = _spec()
    params = init_random_params(spec, FloatType.Q40, seed=17)
    prompts = [SHARED + [230 + i] for i in range(3)]
    outs = {}
    for on in (False, True):
        be = BatchEngine(spec, params, slots=4, tp=2, dp=2, prefix_cache=on,
                         prefix_block_tokens=8)
        try:
            outs[on] = [_run(be, prompts[0], 6)]  # warm (inserts when on)
            reqs = [be.submit(list(p), 6, Sampler(spec.vocab_size,
                                                  temperature=0.0))
                    for p in prompts[1:]]
            outs[on] += [r.wait(timeout=180) for r in reqs]
            if on:
                _settle(lambda: be.prefix_cache.total_refs() == 0)
                assert be.prefix_cache.hit_tokens >= 32
        finally:
            be.close()
    assert outs[True] == outs[False]


# ---------------------------------------------------------------------------
# SingleSlotCache (api_server --batch 1 path)
# ---------------------------------------------------------------------------


def test_single_slot_cross_conversation_reuse():
    from distributed_llama_tpu.cache import PrefixCache, SingleSlotCache
    from distributed_llama_tpu.runtime.engine import Engine

    spec = _spec()
    params = init_random_params(spec, FloatType.Q40, seed=9)
    eng = Engine(spec, params, tp=1)
    ssc = SingleSlotCache(eng, PrefixCache(max_blocks=64, block_tokens=8))
    smp = lambda: Sampler(spec.vocab_size, temperature=0.0)

    conv_a = SHARED + [201]
    conv_b = [1, 60, 61, 62]

    def run(conv):
        reuse = ssc.begin(conv)
        out, _ = eng.generate(conv[reuse:], 6, smp())
        ssc.end((conv + out)[:eng.pos])
        return out, reuse

    want_a, r0 = run(conv_a)
    assert r0 == 0
    run(conv_b)  # displaces the resident conversation
    # return to A: the resident KV holds B, but the radix pool holds A's
    # blocks — reuse must come from the pool, not a fresh prefill
    got_a, reuse = run(conv_a)
    assert reuse >= 32  # 4 full 8-token blocks seeded
    assert got_a == want_a
    # a new conversation sharing only the system prompt also hits
    conv_c = SHARED + [222]
    got_c, reuse_c = run(conv_c)
    assert reuse_c >= 32
    eng.reset()
    cold = Engine(spec, params, tp=1)
    want_c, _ = cold.generate(list(conv_c), 6, smp())
    assert got_c == want_c
    assert ssc.cache.radix.total_refs() == 0


def test_single_slot_invalidate_recovers():
    from distributed_llama_tpu.cache import PrefixCache, SingleSlotCache
    from distributed_llama_tpu.runtime.engine import Engine

    spec = _spec()
    params = init_random_params(spec, FloatType.Q40, seed=9)
    eng = Engine(spec, params, tp=1)
    ssc = SingleSlotCache(eng, PrefixCache(max_blocks=64, block_tokens=8))
    prompt = SHARED + [205]
    reuse = ssc.begin(prompt)
    assert reuse == 0
    ssc.invalidate()  # as the api error path would
    assert ssc.resident == [] and ssc.cache.radix.total_refs() == 0
    out, _ = eng.generate(list(prompt), 4, Sampler(spec.vocab_size,
                                                   temperature=0.0))
    ssc.end((prompt + out)[:eng.pos])
    assert ssc.cache.radix.nodes >= 4
