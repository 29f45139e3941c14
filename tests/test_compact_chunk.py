"""A prefill chunk's step program runs its weights over the rows that hold a
token (ISSUE 41): `models/forward.py RowMap`.

The scheduler tells the program which row prefills (one entry behind the
rows' positions); the residual stream is then compact, chunk + one row a
slot, and only attention and the commit see the (slots, chunk) rectangle.
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
    bt = be._kv_bt
    return np.stack([cache[:, tables[row, p // bt], :, p % bt]
                     for p in range(lo, lo + n)], axis=2)


# (toy, chunk, riders, slots, tp, block pool, kernels interpreted, a parked
# row one chunk from the context's end)
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
    # the kernels (interpreted): dequant-matmul, grouped experts, paged reads
    ("tiny-dense", 8, 3, 4, 1, True, True, False),
    ("tiny-moe", 8, 1, 4, 1, True, True, False),
    ("tiny-axk1", 8, 1, 4, 1, True, True, False),
    ("tiny-lfm2", 8, 1, 4, 1, True, True, False),
]


@pytest.mark.parametrize(
    "toy,chunk,riders,slots,tp,paged,kernels,near_end", CASES,
    ids=lambda v: str(v))
def test_a_compact_chunk_is_the_rectangle_at_every_real_position(
        toy, chunk, riders, slots, tp, paged, kernels, near_end):
    be = _engine(toy, slots, tp, paged, kernels)
    try:
        eng = be._eng
        assert bool(eng.use_pallas) == kernels
        rng = np.random.default_rng([41, chunk, riders, slots])
        vocab = be.spec.vocab_size
        lead = slots // 2  # the prefilling slot is neither first nor last
        ride = [b for b in range(slots) if b != lead][:riders]
        tables_np = tables = None
        if paged:
            for sl in be._slots:
                be._paged_ensure(sl, CONTEXT)
            tables_np, tables = be._tables_np.copy(), be._tables()
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
        _, kc0, vc0 = run(hist, [0] * slots, eng.k_cache, eng.v_cache)
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
    bt = be._kv_bt
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
            be._tables())
        with pytest.raises(AssertionError, match="built a RowMap"):
            jax.eval_shape(
                lambda *a: eng._step_for(None)(*a), eng.params, eng.rope,
                tok, eng.k_cache, eng.v_cache, jnp.zeros((3,), jnp.int32),
                be._tables())
    finally:
        be.close()


def test_a_prefill_dispatch_counts_the_rows_it_computes():
    """`batch_positions_dispatched_total` and `batch_moe_routed_total` count
    the compact rows of a chunk; attention's pairs stay the rectangle's."""
    from distributed_llama_tpu.obs import metrics
    from distributed_llama_tpu.runtime.sampler import Sampler

    def snap():
        return {n: metrics.REGISTRY.snapshot().get(n, 0.0) for n in (
            "batch_positions_dispatched_total", "batch_positions_real_total",
            "batch_attn_pairs_dispatched_total", "batch_moe_routed_total")}

    be = _engine("tiny-moe", 4, 1, True, False)
    try:
        before = snap()
        be.submit(list(range(3, 3 + 64)), 0,
                  Sampler(be.spec.vocab_size, temperature=0.0)).wait(120)
        moved = {n: v - before[n] for n, v in snap().items()}
    finally:
        be.close()
    rows = F.compact_rows(64, 4)
    assert rows == 72 and F.compact_rows(8, 8) == 16
    assert moved["batch_positions_dispatched_total"] == rows
    assert moved["batch_positions_real_total"] == 64
    assert moved["batch_attn_pairs_dispatched_total"] == 4 * 64 * CONTEXT
    assert moved["batch_moe_routed_total"] == (
        rows * be.spec.n_active_experts * be.spec.block_layers)
