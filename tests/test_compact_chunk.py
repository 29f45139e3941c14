"""A prefill chunk's step program runs its weights over the rows that hold a
token (ISSUE 41): `models/forward.py RowMap`.

The scheduler tells the program which row prefills (one entry behind the
rows' positions); the residual stream is then compact, chunk + one row a
slot. Since ISSUE 45 a block pool is read over those rows too, the lead's
chunk in one call of the reader and one query a slot in a second
(`RowMap.attend`), and only the commit sees the (slots, chunk) rectangle.
Held here, on toys of every family, to the rectangular program the same
`forward()` is without that entry: the sampled rows' logits and the K/V
committed at every real position are the same numbers.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_tpu.models import forward as F
from distributed_llama_tpu.runtime.batch_engine import BatchEngine

CONTEXT = 256
HISTORY = 19  # tokens a rider holds before the mixed dispatch


@functools.lru_cache(maxsize=2)
def _toy(name):
    from benchmark import cells
    from benchmark import weights as W

    cfg = {**cells.load_config(name), "context": CONTEXT}
    weights = W.make_weights(cfg, 2**31 + 41)
    spec = cells.load_family(cfg["family"]).model_spec(cfg)
    return spec, W.to_program_params(weights, cfg)


def _engine(name, slots, tp, paged, kernels):
    spec, params = _toy(name)
    return BatchEngine(spec, params, None, slots=slots, superstep=4, tp=tp,
                       paged_kv=paged, kv_block_tokens=16, prefix_cache=False,
                       dtype=jnp.float32, use_pallas=kernels)


def _rows_at(be, cache, tables, row, lo, n):
    """What `cache` holds of `row` at positions [lo, lo + n), layers first."""
    cache = np.asarray(cache)
    if tables is None:  # (L, B, hk, S, w)
        return cache[:, row, :, lo:lo + n]
    bt = be.slot_cache.block_tokens
    return np.stack([cache[:, tables[row, p // bt], :, p % bt]
                     for p in range(lo, lo + n)], axis=2)


# (toy, chunk, riders, slots, tp, block pool, kernels interpreted ("scan":
# and every expert dispatch through the all-experts scan), a parked row one
# chunk from the context's end[, the prefilling slot: the middle one
# where none is given[, the pool's rows padded to this many lanes]])
CASES = [
    # every family: a 64-token chunk, every other slot riding
    *[(toy, 64, 3, 4, 1, True, False, False) for toy in (
        "tiny-dense", "tiny-moe", "tiny-gelu-moe", "tiny-smallthinker",
        "tiny-lead", "tiny-axk1", "tiny-laguna", "tiny-lfm2")],
    # chunks of 64 and 8 with 0, 1 and slots - 1 riders, at the cells' 8 slots
    ("tiny-dense", 64, 0, 8, 1, True, False, False),
    ("tiny-dense", 64, 1, 8, 1, True, False, False),
    ("tiny-dense", 8, 0, 8, 1, True, False, False),
    ("tiny-dense", 8, 1, 8, 1, True, False, False),
    ("tiny-dense", 8, 7, 8, 1, True, False, False),
    ("tiny-smallthinker", 8, 3, 4, 1, True, False, False),
    # a chunk the scheduler shrank because a parked row sits near the end
    ("tiny-dense", 5, 1, 4, 1, True, False, True),
    ("tiny-dense", 5, 1, 4, 1, False, False, True),
    ("tiny-axk1", 64, 1, 4, 1, True, False, True),
    ("tiny-lfm2", 64, 1, 4, 1, True, False, True),
    # layers with a state: a chunk of 8 with every other slot riding
    ("tiny-lfm2", 8, 3, 4, 1, True, False, False),
    # the dense per-row cache
    ("tiny-dense", 64, 3, 4, 1, False, False, False),
    ("tiny-moe", 8, 1, 4, 1, False, False, False),
    ("tiny-axk1", 8, 3, 4, 1, False, False, False),
    ("tiny-laguna", 64, 1, 4, 1, False, False, False),
    # tp 2 on the CPU mesh, both cache kinds
    ("tiny-dense", 64, 3, 4, 2, True, False, False),
    ("tiny-dense", 8, 1, 4, 2, False, False, False),
    ("tiny-moe", 8, 3, 4, 2, True, False, False),
    ("tiny-axk1", 8, 1, 4, 2, True, False, False),
    ("tiny-laguna", 8, 3, 4, 2, True, False, False),
    # the kernels (interpreted): dequant-matmul, grouped experts, paged reads.
    # (The dequant-matmul rounds its rows to bfloat16: where XLA's float32
    # sums differ in their last bit between 16 and 32 rows a value may round
    # the other way, 8e-5 of a cache row; `tiny-axk1` at chunk 8 with three
    # riders and the lead in slot 0 does, before ISSUE 45 as after.)
    ("tiny-dense", 8, 3, 4, 1, True, True, False),
    ("tiny-moe", 8, 1, 4, 1, True, True, False),
    ("tiny-axk1", 8, 1, 4, 1, True, True, False),
    ("tiny-lfm2", 8, 1, 4, 1, True, True, False),
    # the pool read in two calls (ISSUE 45): the lead's table taken at the
    # LAST slot and at slot 0, with the riders on either side of it
    ("tiny-dense", 64, 3, 4, 1, True, False, False, 3),
    ("tiny-dense", 64, 3, 4, 1, True, False, False, 0),
    ("tiny-dense", 8, 7, 8, 1, True, True, False, 7),
    ("tiny-axk1", 8, 3, 4, 1, True, True, False, 3),
    # a parked row beside riders, through the gather and through the kernel
    ("tiny-dense", 64, 2, 4, 1, True, False, True),
    ("tiny-dense", 8, 2, 4, 1, True, True, True),
    # kinds of layer by name, the window a traced scalar of the kernel
    ("tiny-laguna", 64, 3, 4, 1, True, "scan", False),
    # the latent kernel: a chunk of 64 is eight query blocks, a rider one
    ("tiny-axk1", 64, 3, 4, 1, True, True, False),
    # heads narrower than the pool's rows (heads of 64 in lanes of 128 on
    # the chip; the toy's 32 here): scale and slices by `head_size`
    ("tiny-lfm2", 64, 3, 4, 1, True, "scan", False, None, 128),
    ("tiny-dense", 64, 1, 4, 1, True, False, False, 0, 128),
]


def _widened(cache, lanes):
    """The pool with zeros behind every row up to `lanes` values, as
    `runtime/engine.py` holds heads narrower than a lane tile on the chip."""
    def pad(a):
        if a.size == 0:  # a latent spec's empty second side
            return a
        return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, lanes - a.shape[-1])])

    if isinstance(cache, F.StateCache):
        return cache._replace(rows=pad(cache.rows))
    return pad(cache)


@pytest.mark.parametrize("case", CASES, ids=lambda c: "-".join(map(str, c)))
def test_a_compact_chunk_is_the_rectangle_at_every_real_position(
        case, monkeypatch):
    toy, chunk, riders, slots, tp, paged, kernels, near_end, *more = case
    lead, lanes = (more + [None, None])[:2]
    if kernels == "scan":
        # the expert path goes by the rows a dispatch computes (`forward.
        # takes_the_scan`), 72 compact against 256 of the rectangle, and
        # the grouped kernels round otherwise than the scan (0.04 to 0.1 of
        # a logit on these toys, before ISSUE 45 as after): one path on
        # both sides, so that the bar below stays the attention's
        monkeypatch.setattr(F, "SCAN_FROM_MEAN_RUN", 0)
        kernels = True
    be = _engine(toy, slots, tp, paged, kernels)
    try:
        eng = be._eng
        assert bool(eng.use_pallas) == kernels
        rng = np.random.default_rng([41, chunk, riders, slots])
        vocab = be.spec.vocab_size
        if lead is None:
            lead = slots // 2  # the prefilling slot is neither first nor last
        ride = [b for b in range(slots) if b != lead][:riders]
        tables_np = tables = None
        if paged:
            for sl in be._slots:
                be.slot_cache.cover(sl, CONTEXT)
            tables_np, tables = be.slot_cache.tables_np.copy(), be.slot_cache.table()[0]
        step = eng._step_for(None)

        def run(tokens, starts, kc, vc):
            args = (eng.params, eng.rope, jnp.asarray(tokens, jnp.int32),
                    jnp.copy(kc), jax.tree.map(jnp.copy, vc),
                    jnp.asarray(starts, jnp.int32))
            logits, kc, vc, *_ = step(*args, *(() if tables is None
                                               else (tables,)))
            return np.asarray(logits), kc, vc

        # every row's history first: the riders read it, the lead appends
        hist = rng.integers(3, vocab, size=(slots, HISTORY))
        kc0, vc0 = eng.k_cache, eng.v_cache
        if lanes:
            kc0, vc0 = _widened(kc0, lanes), _widened(vc0, lanes)
        _, kc0, vc0 = run(hist, [0] * slots, kc0, vc0)
        starts = [HISTORY] * slots
        if near_end:  # a parked row whose scratch just fits the context
            parked = next(b for b in range(slots)
                          if b != lead and b not in ride)
            starts[parked] = CONTEXT - chunk
        tokens = np.zeros((slots, chunk), np.int64)
        tokens[lead] = rng.integers(3, vocab, size=chunk)
        tokens[ride, 0] = rng.integers(3, vocab, size=len(ride))

        want, kc_r, vc_r = run(tokens, starts, kc0, vc0)
        got, kc_c, vc_c = run(tokens, starts + [lead], kc0, vc0)
        assert want.shape == (slots, chunk, vocab)
        assert got.shape == (slots, 1, vocab)
        tol = dict(rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(got[lead, 0], want[lead, -1], **tol)
        for b in ride:
            np.testing.assert_allclose(got[b, 0], want[b, 0], **tol)
        if isinstance(vc_r, F.StateCache):
            # layers with a state: the ring rows every real position wrote
            # and the snapshots of the block ends the chunk crossed are the
            # rectangle's; what lay below a row's start is untouched
            _same_state(be, vc_c, vc_r, vc0, tables_np, lead, ride, chunk,
                        slots, tol)
            vc_c, vc_r, vc0 = vc_c.rows, vc_r.rows, vc0.rows
        for c_got, c_want in ((kc_c, kc_r), (vc_c, vc_r)):
            if c_want.size == 0:  # a latent spec's empty second side
                continue
            for b, n in [(lead, chunk)] + [(b, 1) for b in ride]:
                np.testing.assert_allclose(
                    _rows_at(be, c_got, tables_np, b, HISTORY, n),
                    _rows_at(be, c_want, tables_np, b, HISTORY, n), **tol)
            # and what lay below every row's start is untouched
            for b in range(slots):
                np.testing.assert_array_equal(
                    _rows_at(be, c_got, tables_np, b, 0, HISTORY),
                    _rows_at(be, kc0 if c_want is kc_r else vc0, tables_np,
                             b, 0, HISTORY))
    finally:
        be.close()


def _same_state(be, got, want, before, tables_np, lead, ride, chunk, slots,
                tol):
    n = len(be.spec.state_layers)
    ring_g, ring_w, ring_0 = (np.asarray(c.ring) for c in (got, want, before))
    w = ring_g.shape[1]
    for b, m in [(lead, chunk)] + [(b, 1) for b in ride]:
        at = [(HISTORY + i) % w for i in range(m)][-w:]
        np.testing.assert_allclose(ring_g[b, at, :n], ring_w[b, at, :n], **tol)
    for b in range(slots):
        if b == lead and chunk + 2 > w:
            continue  # the lead's chunk went once round its ring
        at = [p % w for p in range(max(HISTORY - 2, 0), HISTORY)]
        np.testing.assert_array_equal(ring_g[b, at], ring_0[b, at])
    snaps_g, snaps_w = np.asarray(got.snaps)[0], np.asarray(want.snaps)[0]
    bt = be.slot_cache.block_tokens
    ends = [p for p in range(HISTORY, HISTORY + chunk) if (p + 1) % bt == 0]
    assert chunk < bt or ends
    for p in ends:
        blk = tables_np[lead, p // bt]
        assert np.abs(snaps_w[blk]).max() > 0
        np.testing.assert_allclose(snaps_g[blk], snaps_w[blk], **tol)


def test_programs_without_a_lead_row_hold_no_row_map(monkeypatch):
    """The T = 1 step, the K-step scan and a verify block are traced without
    ever building a `RowMap`: they are the programs they were. (Their call
    signatures are pinned in perf/compile_manifest.json.)"""
    def refuse(*a, **k):
        raise AssertionError("a program without a lead row built a RowMap")

    monkeypatch.setattr(F.RowMap, "of", classmethod(refuse))
    monkeypatch.setattr(F.RowMap, "attend", refuse)
    be = _engine("tiny-dense", 2, 1, True, False)
    try:
        from distributed_llama_tpu.runtime.sampler import Sampler

        be.spec_k = 0
        r = be.submit([5], 12, Sampler(be.spec.vocab_size, temperature=0.0))
        r.wait(120)
        assert len(r.out) == 12 and be.decode_steps + be.super_steps > 0
        # a verify block is a (slots, T) rectangle of real positions
        eng = be._eng
        tok = jnp.zeros((2, 8), jnp.int32)
        jax.eval_shape(
            lambda *a: eng._step_for(None)(*a), eng.params, eng.rope, tok,
            eng.k_cache, eng.v_cache, jnp.zeros((2,), jnp.int32),
            be.slot_cache.table()[0])
        with pytest.raises(AssertionError, match="built a RowMap"):
            jax.eval_shape(
                lambda *a: eng._step_for(None)(*a), eng.params, eng.rope,
                tok, eng.k_cache, eng.v_cache, jnp.zeros((3,), jnp.int32),
                be.slot_cache.table()[0])
    finally:
        be.close()


def _lowered_chunk(be, chunk, lead):
    """The lowered text of the engine's step program at (slots, chunk), told
    which row prefills or (`lead` False) not."""
    eng = be._eng
    slots = be.slots_n
    return jax.jit(eng._step_for(None)).lower(
        eng.params, eng.rope, jnp.zeros((slots, chunk), jnp.int32),
        eng.k_cache, eng.v_cache, jnp.zeros((slots + lead,), jnp.int32),
        be.slot_cache.table()[0]).as_text()


@pytest.mark.parametrize("toy", ["tiny-dense", "tiny-laguna", "tiny-axk1"])
def test_a_chunk_program_reads_the_pool_twice_and_builds_no_rectangle_of_q(
        toy, monkeypatch):
    """Under a row map the pool's reader is called twice a run of like
    layers (a scan's body is traced once), with the lead slot's one table
    and then with every slot's, and no array of the rectangle's q shape
    (slots, chunk, heads, head size) is left in the lowered text; the
    rectangular program (no lead named) calls it once and holds that q."""
    from distributed_llama_tpu.ops import pallas_paged_attention as P

    slots, chunk = 4, 64
    be = _engine(toy, slots, 1, True, False)
    try:
        spec = be.spec
        name = ("latent_paged_attention_xla" if spec.latent
                else "paged_gather_kv")
        seen = []
        reader = getattr(P, name)

        def spy(*a, **k):
            seen.append(tuple(a[3].shape))  # the block tables it is given
            return reader(*a, **k)

        monkeypatch.setattr(P, name, spy)
        runs, width = len(spec.runs()), be.slot_cache.tables_np.shape[1]
        text = _lowered_chunk(be, chunk, lead=True)
        assert seen == [(1, width), (slots, width)] * runs
        seen.clear()
        rect = _lowered_chunk(be, chunk, lead=False)
        assert seen == [(slots, width)] * runs
        if spec.latent:  # q' of every head, as wide as a cache row
            heads = {(spec.n_heads, be._eng.k_cache.shape[-1])}
        else:  # a kind of layer has its own head count
            heads = {(k.n_heads, k.head_size) for k in (
                map(spec.of_kind, range(len(spec.kinds))) if spec.kinds
                else [spec])}
        for h, w in heads:
            q = f"tensor<{slots}x{chunk}x{h}x{w}xf32>"
            assert q in rect, (q, "the rectangular program's own q")
            assert q not in text, q
            # the compact rows' q stands in its place
            assert (f"tensor<1x{F.compact_rows(chunk, slots)}x{h}x{w}xf32>"
                    in text)
    finally:
        be.close()


@pytest.mark.parametrize("toy,paged", [("tiny-moe", True), ("tiny-axk1", True),
                                       ("tiny-moe", False)])
def test_a_prefill_dispatch_counts_the_rows_it_computes(toy, paged):
    """`batch_positions_dispatched_total` and `batch_moe_routed_total` count
    the compact rows of a chunk; attention's pairs and a latent model's
    dispatched rows count the chunk's queries and one a slot where a block
    pool is read (ISSUE 45), the rectangle where the cache is contiguous."""
    from distributed_llama_tpu.obs import metrics
    from distributed_llama_tpu.runtime.sampler import Sampler

    def snap():
        return {n: metrics.REGISTRY.snapshot().get(n, 0.0) for n in (
            "batch_positions_dispatched_total", "batch_positions_real_total",
            "batch_attn_pairs_dispatched_total", "batch_moe_routed_total",
            "batch_attn_pairs_visited_total", "batch_attn_pairs_real_total",
            "batch_latent_dispatch_rows_total", "batch_latent_rows_read_total")}

    be = _engine(toy, 4, 1, paged, False)
    try:
        before = snap()
        be.submit(list(range(3, 3 + 64)), 0,
                  Sampler(be.spec.vocab_size, temperature=0.0)).wait(120)
        moved = {n: v - before[n] for n, v in snap().items()}
        spec = be.spec
    finally:
        be.close()
    rows = F.compact_rows(64, 4)
    assert rows == 72 and F.compact_rows(8, 8) == 16
    assert moved["batch_positions_dispatched_total"] == rows
    assert moved["batch_positions_real_total"] == 64
    queries = 64 + 4 if paged else 4 * 64
    assert moved["batch_attn_pairs_dispatched_total"] == queries * CONTEXT
    # off the kernel the whole window is read for every query asked for
    assert moved["batch_attn_pairs_visited_total"] == queries * CONTEXT
    assert moved["batch_attn_pairs_real_total"] == 64 * 65 // 2
    assert moved["batch_moe_routed_total"] == (
        rows * spec.n_active_experts * spec.block_layers)
    latent_rows = (64 + 4) * spec.n_layers if spec.latent else 0
    assert moved["batch_latent_dispatch_rows_total"] == latent_rows
    assert moved["batch_latent_rows_read_total"] == 0  # every row starts at 0


def test_a_chunk_counts_the_keys_its_two_reads_visit():
    """With the kernel, a dispatch with a lead visits the lead's committed
    keys once a chunk position and every slot's once (the lead's own among
    them); a latent model reads the lead's rows in both calls."""
    from distributed_llama_tpu.obs import metrics

    names = ("batch_attn_pairs_dispatched_total",
             "batch_attn_pairs_visited_total",
             "batch_latent_dispatch_rows_total",
             "batch_latent_rows_read_total")
    be = _engine("tiny-axk1", 4, 1, True, True)
    try:
        before = metrics.snapshot()
        # slot 2 prefills 64 tokens from 130; riders at 10 and 300; a parked
        # row at 0: steps of 128 keys, so 256, 128, 384 and 0 keys
        starts = [10, 300, 130, 0]
        be._count_work(64, 512, [(130, 64), (10, 1), (300, 1)], starts,
                       lead=2)
        d = [metrics.snapshot()[k] - before.get(k, 0) for k in names]
        assert d[0] == (64 + 4) * 512
        assert d[1] == 64 * 256 + (128 + 384 + 256 + 0)
        layers = be.spec.n_layers
        assert d[2] == (64 + 4) * layers
        assert d[3] == (sum(starts) + 130) * layers
        # without a lead the same dispatch is the rectangle's
        before = metrics.snapshot()
        be._count_work(64, 512, [(130, 64), (10, 1), (300, 1)], starts)
        d = [metrics.snapshot()[k] - before.get(k, 0) for k in names]
        assert d == [4 * 64 * 512, 64 * (128 + 384 + 256 + 0),
                     4 * 64 * layers, sum(starts) * layers]
    finally:
        be.close()
