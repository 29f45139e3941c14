"""Device-resident paged KV tests (ISSUE 12, docs/PAGED_KV.md).

Load-bearing properties:
- pool refcount/alloc/CoW metadata vs a brute-force oracle;
- directory remap/demote/promote lifecycle (zero-copy hits, cold uploads);
- token identity PAGED vs DENSE on the CPU mesh — greedy AND
  seeded-stochastic, speculative verify, pipelined chains — resting on the
  gather path's bit-exactness with the dense window computation;
- durable-resume admissions over remapped blocks;
- clamped parks copy-on-write instead of corrupting directory blocks;
- pool exhaustion fails only the starving request (scheduler survives);
- the Pallas kernel (interpret mode) serves the same tokens;
- the perf/paged_attn_bench.py parity gate (tier-1 smoke).
"""

import os
import sys
import time

import numpy as np
import pytest

from distributed_llama_tpu.cache.block_pool import PendingRows
from distributed_llama_tpu.cache.device_pool import (DeviceKVPool,
                                                     KVPoolExhausted,
                                                     PagedPrefixCache,
                                                     SCRATCH_BLOCK)
from distributed_llama_tpu.models.params import init_random_params
from distributed_llama_tpu.models.spec import ArchType, ModelSpec, RopeType
from distributed_llama_tpu.quants import FloatType
from distributed_llama_tpu.runtime.batch_engine import BatchEngine
from distributed_llama_tpu.runtime.sampler import Sampler

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "perf"))


def _spec(seq_len=128):
    return ModelSpec(arch_type=ArchType.LLAMA, dim=64, hidden_dim=128,
                     n_layers=2, n_heads=4, n_kv_heads=4, vocab_size=256,
                     seq_len=seq_len, rope_type=RopeType.LLAMA).resolved()


def _settle(pred, timeout=10):
    t0 = time.time()
    while not pred() and time.time() - t0 < timeout:
        time.sleep(0.01)
    assert pred()


# ------------------------------------------------------------------ pool


def test_pool_refcount_property_vs_oracle():
    """Random alloc/incref/decref interleavings against a dict oracle:
    conservation (allocated + free == capacity - scratch), refcount
    equality, no double-free, scratch never allocated."""
    rng = np.random.default_rng(7)
    pool = DeviceKVPool(24, 8)
    oracle: dict[int, int] = {}  # bid -> refs
    for _ in range(2000):
        op = rng.integers(0, 3)
        if op == 0:
            n = int(rng.integers(1, 4))
            ids = pool.alloc(n)
            if 24 - 1 - len(oracle) < n:
                assert ids is None
            else:
                assert ids is not None and len(ids) == n
                for b in ids:
                    assert b != SCRATCH_BLOCK and b not in oracle
                    oracle[b] = 1
        elif op == 1 and oracle:
            b = int(rng.choice(list(oracle)))
            pool.incref([b])
            oracle[b] += 1
        elif op == 2 and oracle:
            b = int(rng.choice(list(oracle)))
            pool.decref([b])
            oracle[b] -= 1
            if oracle[b] == 0:
                del oracle[b]
        refs = pool.refcounts()
        assert refs[SCRATCH_BLOCK] == 1
        for b, r in oracle.items():
            assert refs[b] == r, (b, refs[b], r)
        assert pool.free_blocks() == 24 - 1 - len(oracle)
        for b in range(1, 24):
            assert pool.shared(b) == (oracle.get(b, 0) > 1)
    if oracle:
        pool.decref([b for b, r in oracle.items() for _ in range(r)])
    assert pool.free_blocks() == 23


def test_directory_remap_demote_promote_roundtrip():
    """Insert-by-reference, lookup leases, demotion to the cold tier under
    reclaim, and promotion back on a later hit — block DATA round-trips
    through the host tier exactly (q80 off)."""
    pool = DeviceKVPool(16, 4)
    pc = PagedPrefixCache(pool, 4, cold_blocks=8, q80=False)
    store = {}  # bid -> (k, v) the fake device pool

    def read_block(bid):
        return store[bid]

    toks = list(range(1, 13))  # 3 full blocks of 4
    ids = pool.alloc(3)
    for i, b in enumerate(ids):
        store[b] = (np.full((2, 2, 4, 8), 10.0 + i, np.float32),
                    np.full((2, 2, 4, 8), 20.0 + i, np.float32))
    created = pc.insert_blocks(toks, ids)
    assert created == 3 and pc.radix.nodes == 3
    refs = pool.refcounts()
    assert all(refs[b] == 2 for b in ids)  # slot ref + directory ref

    # zero-copy hit: the lease resolves to the SAME device blocks
    lease = pc.lookup(toks + [99])
    assert lease is not None and lease.tokens == 12
    assert [n.handle for n in lease.nodes] == [("dev", b) for b in ids]
    pc.mark_seeded(lease, 12)
    pc.release(lease)

    # the "slot" releases its refs; reclaim demotes all three to the cold
    # tier and frees the device blocks
    pool.decref(ids)
    freed = pc.reclaim(3, read_block)
    assert freed == 3 and pool.free_blocks() == 15
    st = pc.stats()
    assert st["cold_blocks"] == 3 and st["dev_blocks"] == 0
    assert st["demoted_blocks"] == 3

    # a later hit still matches; promotion restores the exact rows
    lease = pc.lookup(toks + [99])
    assert lease is not None and lease.tokens == 12
    for i, node in enumerate(lease.nodes):
        tier, h = node.handle
        assert tier == "cold"
        k, v = pc.fetch_cold(h)
        assert np.array_equal(k, store[ids[i]][0])
        assert np.array_equal(v, store[ids[i]][1])
        nb = pool.alloc(1)[0]
        pc.promote(node, nb)
        assert node.handle == ("dev", nb)
    assert pc.stats()["dev_blocks"] == 3
    pc.release(lease)
    assert pc.total_refs() == 0


def test_cold_subtree_eviction_releases_dev_descendants():
    """Review regression: when a FULL cold tier forces _evict_cold_locked
    to drop a cold subtree, any dev-tier descendants dropped with it must
    surrender their pool refs — and the demotion loop must not double-count
    a victim that rode out with the dropped subtree."""
    pool = DeviceKVPool(8, 4)
    pc = PagedPrefixCache(pool, 4, cold_blocks=1, q80=False)
    store = {}

    def read_block(bid):
        return store[bid]

    toks = list(range(1, 9))  # 2 full blocks of 4
    ids = pool.alloc(2)
    for b in ids:
        store[b] = (np.full((1, 1, 4, 8), float(b), np.float32),
                    np.full((1, 1, 4, 8), float(b) + 0.5, np.float32))
    pc.insert_blocks(toks, ids)
    pool.decref(ids)  # directory-only refs remain
    pc.reclaim(1, read_block)   # parent demotes; cold tier now FULL
    assert pc.stats()["cold_blocks"] == 1
    pc.reclaim(1, read_block)   # child's demotion must evict the cold
    # subtree (which contains the child itself) exactly once
    assert pool.free_blocks() == 7, pool.refcounts()
    assert pc.radix.nodes == 0


def test_reclaim_spares_the_excluded_slot():
    """Review regression: the adopting slot looks idle (req bound only
    after the manager's `admit` returns) — reclaim must never release the slot the
    allocation is being performed FOR."""
    spec = _spec(seq_len=64)
    params = init_random_params(spec, FloatType.Q40, seed=3)
    be = BatchEngine(spec, params, slots=2, tp=1, superstep=4,
                     prefix_cache=False, kv_block_tokens=8)
    try:
        slot = be._slots[0]
        be.slot_cache.cover(slot, 16)
        assert len(slot.blocks) == 2 and slot.req is None
        be.slot_cache.reclaim(10 ** 6, exclude=slot)  # cannot be satisfied
        assert len(slot.blocks) == 2  # the excluded slot kept its table
        be.slot_cache.reclaim(10 ** 6)    # unshielded: idle stock IS reclaimed
        assert slot.blocks == []
    finally:
        be.close()


# --------------------------------------------------- engine token identity


@pytest.fixture(scope="module")
def engines():
    spec = _spec()
    params = init_random_params(spec, FloatType.Q40, seed=23)
    dense = BatchEngine(spec, params, slots=2, tp=1, superstep=4,
                        paged_kv=False, prefix_cache=False)
    paged = BatchEngine(spec, params, slots=2, tp=1, superstep=4,
                        kv_block_tokens=8)
    yield spec, params, dense, paged
    paged.close()
    dense.close()


def _run(be, prompt, n, temperature=0.0, seed=0, vocab=256):
    return be.submit(list(prompt), n,
                     Sampler(vocab, temperature=temperature,
                             seed=seed)).wait(timeout=240)


SHARED = [1] + [10 + (i * 7) % 90 for i in range(33)]


def test_paged_vs_dense_token_identity(engines):
    """ISSUE 12 acceptance: greedy AND seeded-stochastic outputs are
    byte-identical paged-vs-dense, including cross-slot directory remaps
    mid-sequence."""
    spec, params, dense, paged = engines
    prompts = [SHARED + [200 + i] for i in range(3)] + [[1, 99, 98]]
    plans = [(0.0, 0), (0.8, 7), (0.8, 11), (0.0, 0)]
    wants = [_run(dense, p, 9, t, s) for p, (t, s) in zip(prompts, plans)]
    # concurrent co-batched mix: pipelined chains, shared radix, remaps
    # mid-run — every row must still match its dense sequential reference
    reqs = [paged.submit(list(p), 9, Sampler(spec.vocab_size, temperature=t,
                                             seed=s))
            for p, (t, s) in zip(prompts, plans)]
    outs = [r.wait(timeout=240) for r in reqs]
    assert outs == wants
    _settle(lambda: paged.prefix_cache.total_refs() == 0)


def test_paged_vs_dense_speculative_identity():
    """Speculative verify dispatches ride the paged pool byte-identically
    (repetitive prompts engage real (B, 1+k) verify blocks)."""
    spec = _spec()
    params = init_random_params(spec, FloatType.Q40, seed=5)
    rep = [9, 21, 33] * 6
    outs = {}
    for paged in (False, True):
        be = BatchEngine(spec, params, slots=2, tp=1, superstep=4,
                         speculative=4, paged_kv=paged, prefix_cache=paged)
        try:
            a = be.submit(list(rep), 16, Sampler(spec.vocab_size))
            b = be.submit(list(rep[2:]), 16,
                          Sampler(spec.vocab_size, temperature=0.8, seed=3))
            outs[paged] = (a.wait(240), b.wait(240))
            if paged:
                assert be.verify_steps >= 1  # the verify path really ran
        finally:
            be.close()
    assert outs[True] == outs[False]


def test_cache_on_off_identical_and_zero_seed_bytes():
    """Within the paged engine: directory on vs off is token-identical, the
    warm resubmit is a REMAP (blocks reused, zero host→device KV bytes),
    and the prefill skip is real."""
    spec = _spec()
    params = init_random_params(spec, FloatType.Q40, seed=17)
    prompts = [SHARED + [210 + i] for i in range(3)]
    outs = {}
    for on in (False, True):
        be = BatchEngine(spec, params, slots=2, tp=1, superstep=4,
                         prefix_cache=on, kv_block_tokens=8)
        try:
            outs[on] = [_run(be, prompts[0], 8)]
            _run(be, [1, 77, 78], 8)  # dirty both slots
            time.sleep(0.2)
            base = be.prefilled_tokens
            outs[on].append(_run(be, prompts[1], 8))
            if on:
                # 34 shared tokens -> 4 full 8-token blocks remapped (the
                # slot's own 1-token rewind overlap counts as resident)
                assert be.prefilled_tokens - base <= len(prompts[1]) - 32
                st = be.prefix_cache.stats()
                assert st["hit_tokens"] + st["resident_tokens"] >= 32
                assert st["hit_tokens"] >= 31
                assert be.seed_bytes == 0, be.seed_bytes
                _settle(lambda: be.prefix_cache.total_refs() == 0)
            outs[on].append(_run(be, prompts[2], 8))
        finally:
            be.close()
    assert outs[True] == outs[False]


def test_resume_over_remapped_blocks_byte_identical():
    """Durable-resume construction (prompt ⊕ delivered, fast-forwarded
    sampler) admitted over a DIRECTORY REMAP: the resumed stream must be
    byte-identical to the uninterrupted run, greedy and stochastic."""
    spec = _spec()
    params = init_random_params(spec, FloatType.Q40, seed=29)
    be = BatchEngine(spec, params, slots=2, tp=1, superstep=4,
                     kv_block_tokens=8)
    prompt = SHARED[:17]
    try:
        for temperature, seed in ((0.0, 0), (0.8, 13)):
            smp = Sampler(spec.vocab_size, temperature=temperature, seed=seed)
            full = be.submit(list(prompt), 16, smp).wait(240)
            # dirty BOTH slots so the resume MUST come from the directory
            ra = be.submit([1, 3, 5], 6, Sampler(spec.vocab_size))
            rb = be.submit([1, 4, 6], 6, Sampler(spec.vocab_size))
            ra.wait(240), rb.wait(240)
            time.sleep(0.2)
            k = 7
            smp2 = Sampler(spec.vocab_size, temperature=temperature,
                           seed=seed)
            smp2.fast_forward(k)
            req = be.submit(prompt + full[:k], 16 - k, smp2,
                            resume_tokens=k)
            rest = req.wait(240)
            assert full[:k] + rest == full, (temperature, rest)
            assert req.stats.reused_tokens >= 8  # at least one block remap
    finally:
        be.close()


def test_cold_promotion_does_not_leak_pool_blocks():
    """Review regression (confirmed leak): the admission's cold promotion
    allocates a device block, promote() takes the directory's ref, and the
    ALLOCATION ref must be dropped — or every demote→promote cycle orphans
    one block until the pool starves. Cycle the same prefix through the
    cold tier and pin used-block conservation."""
    spec = _spec(seq_len=64)
    params = init_random_params(spec, FloatType.Q40, seed=7)
    be = BatchEngine(spec, params, slots=2, tp=1, superstep=4,
                     kv_block_tokens=8)
    prompt = SHARED[:17]
    try:
        _run(be, prompt, 4)
        time.sleep(0.2)
        used = []
        for i in range(3):
            be.slot_cache.reclaim(be.kv_pool.n_blocks)  # demote to cold
            out = _run(be, prompt + [240 + i], 4)   # promote + remap
            assert len(out) == 4
            time.sleep(0.2)
            used.append(be.kv_pool.used_blocks())
        assert used[2] <= used[0], used  # conservation: no orphaned refs
        assert be.prefix_cache.stats()["promoted_blocks"] >= 2
    finally:
        be.close()


def test_context_end_clamp_does_not_corrupt_directory():
    """Clamped parks (rows near seq_len) overwrite their own tail rows; in
    paged mode those rows may back DIRECTORY blocks — copy-on-write must
    keep the shared copies intact, so a later remap still reproduces the
    dense outputs, and lease pins shrink back to zero."""
    spec = _spec(seq_len=32)
    params = init_random_params(spec, FloatType.Q40, seed=5)
    prompts = [[1, 2, 3, 4, 5, 6, 7, 8, 9, 10], [1, 2, 3, 4, 5, 6, 7, 8, 11]]
    outs = {}
    for paged in (False, True):
        be = BatchEngine(spec, params, slots=2, tp=1, superstep=4,
                         prefix_cache=paged, paged_kv=paged,
                         kv_block_tokens=4)
        try:
            if paged:
                _run(be, prompts[0], 30)  # warm: harvest + clamp at the wall
            reqs = [be.submit(list(p), 30, Sampler(spec.vocab_size))
                    for p in prompts]
            outs[paged] = [r.wait(240) for r in reqs]
            for r in reqs:
                assert r.finish == "length"
            if paged:
                # the re-run of prompts[0] after the clamp must have REUSED
                # directory blocks and still produced the dense tokens
                assert be.prefix_cache.stats()["hit_tokens"] > 0
                _settle(lambda: be.prefix_cache.total_refs() == 0)
        finally:
            be.close()
    assert outs[True] == outs[False]


def test_pool_exhaustion_fails_only_the_starving_request():
    """A pool sized for ~one context cannot serve two concurrent long
    requests: one fails with the typed KVPoolExhausted (request scope), the
    other completes, the scheduler survives and keeps serving."""
    spec = _spec(seq_len=64)
    params = init_random_params(spec, FloatType.Q40, seed=3)
    w = 64 // 8
    be = BatchEngine(spec, params, slots=2, tp=1, superstep=4,
                     kv_block_tokens=8, kv_pool_blocks=w + 2,
                     prefix_cache=False)
    try:
        a = be.submit([1, 2, 3], 56, Sampler(spec.vocab_size))
        b = be.submit([1, 2, 4], 56, Sampler(spec.vocab_size))
        res = []
        for r in (a, b):
            try:
                r.wait(timeout=240)
                res.append(("ok", r))
            except KVPoolExhausted:
                res.append(("exhausted", r))
        kinds = sorted(k for k, _ in res)
        assert kinds in (["exhausted", "ok"], ["ok", "ok"]), kinds
        assert be.scheduler_alive()
        # the engine still serves after the pressure event
        out = be.submit([1, 9, 9], 6, Sampler(spec.vocab_size)).wait(240)
        assert len(out) == 6
    finally:
        be.close()


def test_interpret_kernel_serves_identical_greedy_tokens():
    """The Pallas paged-attention kernel (interpret mode on CPU) plugged
    into the full engine serves the same greedy tokens as the XLA gather
    path — the deterministic end-to-end smoke for the TPU kernel route."""
    spec = _spec(seq_len=64)  # small W keeps the interpreted grid cheap
    params = init_random_params(spec, FloatType.Q40, seed=11)
    prompt = SHARED[:12]
    outs = {}
    for kernel in (False, True):
        be = BatchEngine(spec, params, slots=2, tp=1, superstep=4,
                         kv_block_tokens=8, paged_kernel=kernel)
        try:
            assert be._eng.paged_kernel == kernel
            outs[kernel] = _run(be, prompt, 8)
        finally:
            be.close()
    assert outs[True] == outs[False]


def test_paged_attn_bench_parity_gate():
    """Tier-1 smoke for perf/paged_attn_bench.py: XLA-vs-dense bit
    exactness, kernel max|Δ| under tolerance, greedy-pick agreement — the
    decode (T=1) and verify (T=5) shapes."""
    import paged_attn_bench

    rows = paged_attn_bench.run(small=True)
    assert {r["shape"] for r in rows} == {"decode_t1", "verify_t5"}
    for r in rows:
        assert r["xla_vs_dense_bit_exact"]
        assert r["kernel_max_abs_err"] < 2e-5
        assert r["greedy_pick_agree"]


# ------------------------------------------- the deferred demotion (ISSUE 39)
#
# A reclaim's victims are read by ONE gather that nobody waits for; the cold
# tier holds the pending read and it is settled where the scheduler only
# waits. Held here to the per-block path this replaced, which is kept below
# as the reference: WHICH blocks are demoted, in which order, when a node
# turns cold and what rows a later hit uploads must not have changed.

TOYS = ("tiny-dense", "tiny-axk1", "tiny-laguna", "tiny-lfm2")


@pytest.fixture(scope="module", params=TOYS)
def toy(request):
    """(name, spec, params) of a toy configuration: dense keys and values,
    a latent row with an empty second side, two kinds of layer, and layers
    whose state rides the blocks as a third, typed payload."""
    from benchmark import cells
    from benchmark import weights as W

    # (a context of 256: the pool's floor is a whole context of blocks)
    cfg = {**cells.load_config(request.param), "context": 256}
    weights = W.make_weights(cfg, 2**31 + 39)
    spec = cells.load_family(cfg["family"]).model_spec(cfg)
    assert spec.seq_len == 256
    return request.param, spec, W.to_program_params(weights, cfg)


def _engine(toy, **kw):
    import jax.numpy as jnp

    _, spec, params = toy
    kw = {"slots": 2, "superstep": 4, "kv_block_tokens": 16,
          "kv_pool_blocks": 20, "prefix_cache_blocks": 5, "tp": 1,
          "dtype": jnp.float32, **kw}
    return BatchEngine(spec, params, None, **kw)


def _sides(eng, bid):
    """What the pool holds of block `bid`, a host array a side: keys, values
    and, of a model with state layers, the block's state snapshot."""
    from distributed_llama_tpu.runtime.slot_cache import pool_sides

    return tuple(np.asarray(c[:, bid]) for c in pool_sides(eng))


def _reference_reclaim(pc, n_blocks, read_block):
    """PagedPrefixCache.reclaim as it was before ISSUE 39, kept as the
    reference: walk and sort the whole tree, read a block, put it, and on a
    full cold tier evict one cold subtree and read the block AGAIN."""
    from distributed_llama_tpu.cache.device_pool import _DEMOTED

    def walk(tier):
        out, stack = [], [pc.radix.root]
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            if (node is not pc.radix.root and node.refs == 0
                    and node.handle[0] == tier):
                out.append(node)
        out.sort(key=lambda v: v.stamp)
        return out

    def evict_cold(n):
        dev_ids = []
        for node in walk("cold")[:n]:
            if node.handle[0] == "cold":
                dev_ids.extend(pc._drop_subtree_locked(node))
        return dev_ids

    with pc._lock:
        released = []
        for node in walk("dev"):
            if len(released) >= n_blocks:
                break
            if node.handle[0] != "dev":
                continue
            bid = node.handle[1]
            if pc.cold is not None:
                h = pc.cold.put(*read_block(bid))
                if h is None and len(pc.cold) > 0:
                    released.extend(evict_cold(1))
                    if node.handle[0] != "dev":
                        continue
                    h = pc.cold.put(*read_block(bid))
                if h is not None:
                    node.handle = ("cold", h)
                    pc.demoted += 1
                    _DEMOTED.inc()
                    released.append(bid)
                    continue
            released.extend(pc._drop_subtree_locked(node))
    return pc.pool.decref(released) if released else 0


def _as_reference(be, reads):
    """Put the per-block path into `be`: the old reclaim over the old
    synchronous reader, two slices and two copies a block."""
    eng, pc = be._eng, be.prefix_cache

    def read_block(bid):
        reads.append(bid)
        return _sides(eng, bid)

    be.slot_cache.demote = lambda deficit: _reference_reclaim(pc, deficit, read_block)


def _directory(pc):
    """Every node in LRU order: (chain of keys, tier, stamp, rows)."""
    out, stack = [], [(pc.radix.root, ())]
    while stack:
        node, chain = stack.pop()
        for key, child in node.children.items():
            stack.append((child, chain + (key,)))
        if node is not pc.radix.root:
            tier, h = node.handle
            rows = pc.cold.get(h) if tier == "cold" else None
            out.append((node.stamp, node.depth, chain, tier, rows))
    out.sort(key=lambda e: e[:2])
    return out


def _traffic(vocab):
    """A seeded sequence that fills a pool of 20 blocks several times over:
    distinct prompts of 3 to 6 blocks, and repeats of two earlier ones so
    that demoted blocks are hit and promoted."""
    rng = np.random.default_rng(39)
    prompts = [rng.integers(3, vocab, int(n)).tolist()
               for n in rng.integers(50, 100, 8)]
    order = [0, 1, 2, 3, 0, 4, 5, 1, 6, 7, 4]
    return [prompts[i] + [5 + j] for j, i in enumerate(order)]


def test_demotion_leaves_the_reference_directory(toy):
    """(a) The same requests through the deferred path and through the
    per-block reference leave the same tokens, the same pool and the same
    directory: every node's tier and LRU place, every cold block's rows bit
    for bit the device rows the reference read synchronously."""
    from distributed_llama_tpu.obs import metrics

    outs, dirs, refs, reads = [], [], [], []
    for reference in (False, True):
        be = _engine(toy)
        if reference:
            _as_reference(be, reads)
        before = metrics.snapshot()
        try:
            outs.append([_run(be, p, 6, vocab=toy[1].vocab_size)
                         for p in _traffic(toy[1].vocab_size)])
            _settle(lambda: be.prefix_cache.total_refs() == 0)
            dirs.append(_directory(be.prefix_cache))
            refs.append(be.kv_pool.refcounts())
            st = be.prefix_cache.stats()
        finally:
            be.close()
        moved = {k: v - before.get(k, 0) for k, v in metrics.snapshot().items()
                 if k.startswith("paged_kv_demote")}
        if not reference:
            assert st["demoted_blocks"] >= 20 and st["promoted_blocks"] >= 3
            # one read a reclaim: fewer reads than blocks, never more
            assert 0 < moved["paged_kv_demote_reads_total"] \
                < moved["paged_kv_demoted_blocks_total"], moved
    assert outs[0] == outs[1]
    assert np.array_equal(refs[0], refs[1])
    assert len(dirs[0]) == len(dirs[1]) > 0
    for got, want in zip(dirs[0], dirs[1]):
        assert got[:4] == want[:4]
        if want[4] is not None:
            for g, w in zip(got[4], want[4]):
                assert g.dtype == w.dtype and np.array_equal(g, w)
    # the reference read a victim twice whenever the cold tier was full
    assert len(reads) > len(set(reads))


def test_hit_on_a_pending_payload_promotes_the_right_rows(toy):
    """(b) A prompt whose blocks were demoted and NOT yet settled: the hit
    settles them, uploads the rows the device held, and the tokens are
    those of a run without the cache."""
    vocab = toy[1].vocab_size
    prompt = np.random.default_rng(7).integers(3, vocab, 70).tolist()
    plain = _engine(toy, prefix_cache=False)
    try:
        want = [_run(plain, prompt + [9, 8], 6, vocab=vocab)]
    finally:
        plain.close()
    be = _engine(toy, prefix_cache_blocks=8)
    try:
        _run(be, prompt, 4, vocab=vocab)
        _settle(lambda: be.prefix_cache.total_refs() == 0)
        pc, eng = be.prefix_cache, be._eng
        lease = pc.lookup(prompt + [9])
        held = {n.handle[1]: None for n in lease.nodes}
        pc.release(lease)
        assert len(held) == 4
        for bid in held:
            held[bid] = _sides(eng, bid)
        be.slot_cache.settle = lambda force=False: None  # nobody settles
        be.slot_cache.reclaim(be.kv_pool.n_blocks)
        assert pc.unsettled == 4 and pc.stats()["cold_blocks"] == 4
        got = [_run(be, prompt + [9, 8], 6, vocab=vocab)]
        assert got == want
        assert pc.stats()["promoted_blocks"] == 4
        lease = pc.lookup(prompt + [9])
        for node, rows in zip(lease.nodes, held.values()):
            tier, bid = node.handle
            assert tier == "dev"
            for got, want_rows in zip(_sides(eng, bid), rows, strict=True):
                assert np.array_equal(got, want_rows)
        pc.release(lease)
    finally:
        be.close()


def _fill_directory(be, n_nodes, seed=0):
    """n_nodes unreferenced device-tier nodes, one chain of one block each,
    over freshly written pool blocks."""
    import jax.numpy as jnp

    eng, bt = be._eng, be.slot_cache.block_tokens
    ids = be.kv_pool.alloc(n_nodes)
    rng = np.random.default_rng(seed)
    eng.k_cache = eng.k_cache.at[:, np.asarray(ids)].set(jnp.asarray(
        rng.standard_normal((eng.k_cache.shape[0], n_nodes)
                            + eng.k_cache.shape[2:]), eng.k_cache.dtype))
    for i, b in enumerate(ids):
        be.prefix_cache.insert_blocks([1000 * (i + 1) + j
                                       for j in range(bt)], [b])
    be.kv_pool.decref(ids)
    return ids


def test_no_program_compiles_for_reclaims_of_1_to_8_blocks(toy):
    """(e) After the constructor and `read_block(0)`, which is what the
    benchmark's warm-up calls, a reclaim of any size compiles nothing
    (counted as benchmark/run.py counts)."""
    import jax

    be = _engine(toy, kv_pool_blocks=64, prefix_cache_blocks=64)
    compiled = []

    def on(event, seconds, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(seconds)

    try:
        _fill_directory(be, 50)
        be.slot_cache.read_block(0)
        jax.monitoring.register_event_duration_secs_listener(on)
        for n in range(1, 9):
            free = be.kv_pool.free_blocks()
            be.slot_cache.demote(n)
            assert be.kv_pool.free_blocks() == free + n
        be.slot_cache.demote(11)  # more than the largest size: two gathers
        assert be.prefix_cache.settle(force=True)[0] == 47
        assert not compiled
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
        be.close()


def test_reset_and_close_with_payloads_pending(toy):
    """(g) close() leaves the cold tier holding host arrays alone, equal to
    the device rows; reset() forgets pending payloads with the directory."""
    be = _engine(toy, kv_pool_blocks=32, prefix_cache_blocks=16)
    try:
        ids = _fill_directory(be, 6)
        pc, eng = be.prefix_cache, be._eng
        want = {b: _sides(eng, b) for b in ids}
        nodes = {n.handle[1]: n for n in pc.radix.root.children.values()}
        be.slot_cache.demote(6)
        assert pc.unsettled == 6
        be.close()
        assert pc.unsettled == 0
        for b, node in nodes.items():
            tier, h = node.handle
            assert tier == "cold" and pc.cold.pending(h) is None
            for got, rows in zip(pc.fetch_cold(h), want[b], strict=True):
                assert np.array_equal(got, rows) and got.shape == rows.shape
    finally:
        be.close()
    be = _engine(toy, kv_pool_blocks=32, prefix_cache_blocks=16)
    try:
        _fill_directory(be, 6)
        be.slot_cache.demote(4)
        assert be.prefix_cache.unsettled == 4
        be.kv_pool.reset()
        be.prefix_cache.reset()
        pc = be.prefix_cache
        assert pc.unsettled == 0 and len(pc.cold) == 0 and pc.radix.nodes == 0
        assert pc.settle(force=True) == (0, 0)
        assert be.kv_pool.free_blocks() == 31
    finally:
        be.close()


class _Rows(PendingRows):
    """A block's pending rows as the engine's reader would hand them out."""

    def __init__(self, k, v, fail=False):
        self.k, self.v, self.fail = k, v, fail
        self.shape, self.dtype = k.shape, k.dtype
        self.nbytes = k.nbytes + v.nbytes
        self.settled = 0

    def ready(self):
        return True

    def settle(self):
        self.settled += 1
        if self.fail:
            raise OSError("the device read failed")
        return self.k, self.v


def _chains(pc, pool, n_chains, depth, bt=4):
    """n_chains x depth unreferenced device nodes; returns {bid: chain}."""
    where = {}
    for c in range(n_chains):
        ids = pool.alloc(depth)
        toks = [c * 100000 + i for i in range(depth * bt)]
        pc.insert_blocks(toks, ids)
        pool.decref(ids)
        where.update({b: c for b in ids})
    return where


def test_full_cold_tier_reads_each_victim_once():
    """(c) With the cold tier full, room is made BEFORE a victim is read:
    one read a demoted block (the per-block path read it, was refused,
    evicted and read it again)."""
    pool = DeviceKVPool(64, 4)
    pc = PagedPrefixCache(pool, 4, cold_blocks=3, q80=False)
    _chains(pc, pool, 10, 2)
    reads = []

    def read_block(bid):
        reads.append(bid)
        return _Rows(np.full((1, 1, 4, 8), float(bid), np.float32),
                     np.full((1, 1, 4, 8), -float(bid), np.float32))

    for _ in range(6):
        assert pc.reclaim(2, read_block) >= 2
    st = pc.stats()
    assert st["demoted_blocks"] == len(reads) == len(set(reads)) == 12
    # (an evicted cold root takes its cold child with it: 2 or 3 are left)
    left = st["cold_blocks"]
    assert 2 <= left == len(pc.cold) == pc.unsettled <= 3
    assert pc.settle() == (left, 0) and pc.unsettled == 0
    for h in list(pc.cold._blocks):
        k, v = pc.cold.get(h)
        assert k[0, 0, 0, 0] == -v[0, 0, 0, 0] and k[0, 0, 0, 0] in reads
    # device blocks: used + free conserved, nothing leaked
    assert pool.used_blocks() + pool.free_blocks() == 63
    assert pool.used_blocks() == st["dev_blocks"] == 20 - 12
    assert st["evicted_blocks"] == 12 - left


def test_failed_read_at_settle_evicts_the_subtree_and_leaks_nothing():
    """(d) A read that raises when it is settled drops its node and the
    subtree under it, device-tier descendants included, as the eviction it
    stood in for would have; a lease on the subtree only postpones it."""
    pool = DeviceKVPool(32, 4)
    pc = PagedPrefixCache(pool, 4, cold_blocks=8, q80=False)
    where = _chains(pc, pool, 2, 3)
    made = {}

    def read_block(bid):
        made[bid] = _Rows(np.zeros((1, 1, 4, 8), np.float32),
                          np.zeros((1, 1, 4, 8), np.float32),
                          fail=where[bid] == 0)
        return made[bid]

    # LRU order is chain 0 root-first, then chain 1: demote the two roots'
    # worth (chain 0's first block, whose read will fail, and its child)
    assert pc.reclaim(2, read_block) == 2
    assert [where[b] for b in made] == [0, 0] and pc.unsettled == 2
    lease = pc.lookup(list(range(12)) + [0])  # pins the chain: the drop waits
    assert [n.handle[0] for n in lease.nodes] == ["cold", "cold", "dev"]
    assert pc.settle() == (0, 0) and pc.unsettled == 2 and pc.radix.nodes == 6
    with pytest.raises(OSError):
        pc.fetch_cold(lease.nodes[1].handle[1])  # the hit falls back to prefill
    pc.mark_unused(lease)
    assert pc.settle() == (0, 0)
    assert pc.unsettled == 0 and pc.radix.nodes == 3 and len(pc.cold) == 0
    assert pool.used_blocks() == 3 and pool.free_blocks() == 31 - 3
    assert pc.reclaim(3, read_block) == 3 and pc.settle() == (3, 0)
    assert pool.used_blocks() == 0 and pc.stats()["cold_blocks"] == 3


def test_reclaim_of_one_does_not_walk_the_tree():
    """(f) Victims come off an LRU kept as nodes change: reclaim(1) on a
    tree of 5000 nodes enumerates no node's children (counted, not timed),
    and still takes the least recently used block."""
    pool = DeviceKVPool(5002, 4)
    pc = PagedPrefixCache(pool, 4, cold_blocks=64, q80=False)
    where = _chains(pc, pool, 50, 100)
    assert pc.radix.nodes == 5000
    visits = []

    class Counting(dict):
        def values(self):
            visits.append(1)
            return dict.values(self)

    stack = [pc.radix.root]
    while stack:
        node = stack.pop()
        stack.extend(node.children.values())
        node.children = Counting(node.children)
    taken = []

    def read_block(bid):
        taken.append(bid)
        return (np.zeros((1, 1, 4, 8), np.float32),) * 2

    for _ in range(20):
        assert pc.reclaim(1, read_block) == 1
    assert not visits
    # chain 0 is the oldest touch; root first within it
    first = [b for b, c in where.items() if c == 0][:20]
    assert taken == first
    # a lookup of chain 0 makes chain 1 the oldest; the heaps stay bounded
    for _ in range(200):
        pc.release(pc.lookup([i for i in range(400)] + [7]))
    assert pc.reclaim(1, read_block) == 1 and where[taken[-1]] == 1
    assert not visits
    assert all(len(h) <= 64 + 4 * pc.radix.nodes for h in pc._lru.values())


def test_q80_tier_settles_a_pending_block_before_it_compresses_it():
    """The Q80 tier compresses at put(): a pending block it picks is settled
    first, and a block whose read FAILED is passed over and dropped by the
    next settle()."""
    pool = DeviceKVPool(16, 4)
    pc = PagedPrefixCache(pool, 4, cold_blocks=8, q80=True)  # 2 stay hot
    _chains(pc, pool, 5, 1)
    made = []

    def read_block(bid):
        rng = np.random.default_rng(bid)
        made.append(_Rows(rng.standard_normal((1, 2, 4, 16), np.float32),
                          rng.standard_normal((1, 2, 4, 16), np.float32),
                          fail=len(made) == 1))
        return made[-1]

    assert pc.reclaim(5, read_block) == 5
    # five puts over a hot budget of two: the three oldest were picked, the
    # second of them failed its read and stays pending
    assert [r.settled for r in made] == [1, 2, 1, 0, 0]
    assert pc.cold.demoted_blocks == 2 and pc.cold.hot_count() == 3
    handles = sorted(pc.cold._blocks)
    assert [pc.cold.pending(h) is not None for h in handles] == [
        False, True, False, True, True]
    k, v = pc.cold.get(handles[0])
    assert np.allclose(k, made[0].k, atol=0.05) and not np.array_equal(k, made[0].k)
    assert pc.settle() == (4, 0)  # the failed one is dropped, not settled
    assert pc.unsettled == 0 and pc.radix.nodes == 4 and len(pc.cold) == 4
    k, v = pc.cold.get(handles[4])
    assert np.array_equal(k, made[4].k) and np.array_equal(v, made[4].v)
    assert pool.used_blocks() == 0


def test_demotion_with_the_pool_sharded_over_kv_heads():
    """tp = 2: the pool is sharded over hk and so is the gather's result;
    a hit on the pending payload uploads the rows the device held."""
    spec = _spec(seq_len=128)
    params = init_random_params(spec, FloatType.Q40, seed=11)
    prompt = SHARED[:33]
    plain = BatchEngine(spec, params, slots=2, tp=2, superstep=4,
                        kv_block_tokens=8, prefix_cache=False)
    try:
        want = _run(plain, prompt + [77], 6)
    finally:
        plain.close()
    be = BatchEngine(spec, params, slots=2, tp=2, superstep=4,
                     kv_block_tokens=8, kv_pool_blocks=20,
                     prefix_cache_blocks=8)
    try:
        _run(be, prompt, 4)
        _settle(lambda: be.prefix_cache.total_refs() == 0)
        pc, eng = be.prefix_cache, be._eng
        assert len(eng.k_cache.sharding.device_set) == 2
        lease = pc.lookup(prompt + [77])
        held = [(np.asarray(eng.k_cache[:, n.handle[1]]),
                 np.asarray(eng.v_cache[:, n.handle[1]])) for n in lease.nodes]
        pc.release(lease)
        be.slot_cache.settle = lambda force=False: None
        be.slot_cache.reclaim(be.kv_pool.n_blocks)
        assert pc.unsettled == len(held) == 4
        assert _run(be, prompt + [77], 6) == want
        lease = pc.lookup(prompt + [77])
        for node, (k, v) in zip(lease.nodes, held):
            assert node.handle[0] == "dev"
            assert np.array_equal(np.asarray(eng.k_cache[:, node.handle[1]]), k)
            assert np.array_equal(np.asarray(eng.v_cache[:, node.handle[1]]), v)
        pc.release(lease)
    finally:
        be.close()


def test_a_demoted_block_of_a_state_space_model_gives_its_snapshot_up():
    """granite-4.0-h-small's toy: a snapshot lies in a pool of its own, by
    stride, and is FREED with its block (38.7 MB a snapshot at the published
    widths would be most of a demotion's bytes): two sides travel to the
    host tier, the demoted blocks' entries are gone, and the same prompt
    again finds its keys and values cold but no snapshot under them,
    prefills from 0 and gives the same tokens."""
    import jax.numpy as jnp

    from benchmark import cells
    from benchmark import weights as W
    from distributed_llama_tpu.runtime.slot_cache import pool_sides

    cfg = {**cells.load_config("tiny-granite-hybrid"), "context": 512}
    weights = W.make_weights(cfg, 2**31 + 39)
    spec = cells.load_family(cfg["family"]).model_spec(cfg)
    be = BatchEngine(spec, W.to_program_params(weights, cfg), None, slots=2,
                     superstep=4, kv_block_tokens=16, kv_pool_blocks=80,
                     tp=1, dtype=jnp.float32)
    try:
        assert len(pool_sides(be._eng)) == 2  # keys and values alone
        prompt = np.random.default_rng(9).integers(3, 512, 300).tolist()
        want = _run(be, prompt, 6, vocab=512)
        _settle(lambda: be.prefix_cache.total_refs() == 0)
        snaps = be.kv_pool.snapshots
        assert snaps.held() == 1  # the block that ends at position 255
        again = be.submit(list(prompt), 6, Sampler(512, temperature=0.0))
        assert again.wait(timeout=180) == want
        assert again.stats.reused_tokens == 256
        _settle(lambda: be.prefix_cache.total_refs() == 0)
        for sl in be._slots:
            be.slot_cache.release(sl)
        be.slot_cache.demote(be.prefix_cache.stats()["dev_blocks"])
        be.slot_cache.settle(force=True)
        assert be.prefix_cache.stats()["cold_blocks"] >= 16
        assert snaps.held() == 0
        cold = be.submit(list(prompt), 6, Sampler(512, temperature=0.0))
        assert cold.wait(timeout=180) == want
        assert cold.stats.reused_tokens == 0
    finally:
        be.close()
