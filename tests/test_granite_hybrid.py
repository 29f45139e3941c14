"""granite-4.0-h-small's graph at a toy size on the CPU: the program against
the family's plain reference (`benchmark/families/granite_hybrid.py`, the
state-space recurrence as a scan over positions) on seeded weights.

What the configuration forces and these tests hold: a state layer whose state
is a MATRIX a head (`StateCache.h`), which no ring holds and which therefore
has to be CARRIED: through parked rows, an over-decoded row, a flushed
chained super-step, a step issued ahead, a preempted slot and a reused one;
snapshots taken every `STATE_STRIDE` positions into a pool of a few entries
that blocks are given and lose, on which a prefix hit, a rewind and a resume
land; the SSD kernels (`ops/pallas_ssd.py`) interpreted against the
recurrence as written; the multipliers and the stated attention scale; and
each refusal. LFM2, whose snapshot lies in every block, is held to what it
did (`test_lfm2.py`, and one case here).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells, probe
from benchmark import weights as W
from distributed_llama_tpu.cache.device_pool import DeviceKVPool, SnapshotPool
from distributed_llama_tpu.models import forward as F
from distributed_llama_tpu.models.params import (block_tensor_shapes,
                                                 init_random_params,
                                                 run_tensor_shapes)
from distributed_llama_tpu.models.spec import RopeType
from distributed_llama_tpu.obs import metrics
from distributed_llama_tpu.ops import pallas_ssd as S
from distributed_llama_tpu.ops.rope import RopeTables
from distributed_llama_tpu.quants import FloatType
from distributed_llama_tpu.runtime.sampler import Sampler

SEED = 2**31 + 42
LOGITS_TOL = 2e-6  # the logits are divided by 16: their scale is 0.015
KERNEL_TOL = 1e-3  # the Q40 x Q80 kernels' rounding, as in test_lfm2.py
CONTEXT = 512
BT = 16
STRIDE = F.STATE_STRIDE


@pytest.fixture(scope="module")
def toy():
    cfg = {**cells.load_config("tiny-granite-hybrid"), "context": CONTEXT}
    fam = cells.load_family("granite_hybrid")
    weights = W.make_weights(cfg, SEED)
    return (cfg, fam, weights, fam.model_spec(cfg),
            W.to_program_params(weights, cfg))


def _engine(toy, manual=False, **kw):
    from distributed_llama_tpu.runtime.batch_engine import BatchEngine

    cfg, _, weights, spec, _ = toy
    args = dict(slots=4, superstep=8, pipeline=True, paged_kv=True,
                kv_block_tokens=BT, prefix_cache=True, dtype=jnp.float32,
                tp=1)
    args.update(kw)
    be = BatchEngine(spec, W.to_program_params(weights, cfg), None, **args)
    if manual:  # the test's thread is the scheduler
        be._ensure_thread = lambda: None
    return be


def _greedy(toy, seq, n):
    """The reference's argmax chain: n tokens behind `seq`."""
    cfg, fam, weights, _, _ = toy
    seq, out = list(seq), []
    for _ in range(n):
        ref, _ = fam.logits_at(cfg, weights, [seq], [[len(seq) - 1]])
        out.append(int(np.argmax(ref[0])))
        seq.append(out[-1])
    return out


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(3, 512, n).tolist()


def _greedy_sampler():
    return Sampler(512, temperature=0.0)


def _count(name):
    return metrics.snapshot().get(name, 0)


# ---- the model as data ------------------------------------------------------

def test_the_spec_carries_the_model_as_data(toy):
    cfg, _, _, spec, params = toy
    mamba, attn = spec.kinds
    assert (mamba.conv_kernel, mamba.ssm_heads, mamba.ssm_head_dim,
            mamba.ssm_state) == (4, 8, 32, 16)
    assert attn.rope_type == RopeType.NONE and not attn.conv_kernel
    assert spec.mixed and spec.ssm and len(spec.runs()) == 1
    assert spec.state_layers == (0, 1, 2, 3, 4, 6, 7, 8, 9)
    assert spec.cache_layers == (5,)
    assert spec.state_rows == 3 and spec.state_width == 256 + 2 * 16
    assert spec.state_matrix == (8, 32, 16) and spec.ssm_inner == 256
    assert spec.state_block_bytes(4) == 9 * (3 * 288 * 4 + 8 * 32 * 16 * 4)
    assert (spec.embedding_multiplier, spec.residual_multiplier,
            spec.logits_scaling, spec.attn_scale) == (12.0, 0.22, 16.0,
                                                      8.0)
    # (the toy states a scale of 8 where the file states 1/128: at a hidden
    # size of 128 the drawn q . k are a tenth of the published widths')
    assert spec.shared_hidden_dim == 96 and spec.n_experts == 8
    assert spec.state_snapshots == cfg["state_snapshots"]
    own = run_tensor_shapes(spec, spec.runs()[0])
    assert own["ssm_in"][0] == (9, 256 + 288 + 8, 128)
    assert own["wq"][0][0] == 1 and own["router"][0][0] == 10
    assert params["blocks"]["ssm_a_log"].shape == (9, 8)
    assert "ssm_in" in block_tensor_shapes(spec.of_kind(0))
    assert "wq" in block_tensor_shapes(spec.of_kind(1))


def test_the_published_file_gives_the_published_model():
    cfg = cells.load_config("granite-4.0-h-small-l10")
    spec = cells.load_family("granite_hybrid").model_spec(cfg)
    assert (spec.dim, spec.hidden_dim, spec.shared_hidden_dim) == (
        4096, 768, 1536)
    assert (spec.n_experts, spec.n_active_experts) == (72, 10)
    assert spec.state_matrix == (128, 64, 128) and spec.state_width == 8448
    assert spec.layer_kinds == (0, 0, 0, 0, 0, 1, 0, 0, 0, 0)
    assert (spec.n_heads, spec.n_kv_heads, spec.head_size) == (32, 8, 128)
    assert spec.attn_scale == 1 / 128 and spec.vocab_size == 100352
    # one snapshot: nine layers' matrices in float32 and three rows of 8448
    assert spec.state_block_bytes(2) == 9 * (4 * 2**20 + 3 * 8448 * 2)
    assert spec.state_snapshots == 48


def _mixed(toy, **over):
    return dataclasses.replace(toy[3], **over)


@pytest.mark.parametrize("over,why", [
    (dict(state_snapshots=0), "states its snapshot"),
    (dict(kinds=(dataclasses.replace(
        cells.load_family("granite_hybrid").model_spec(
            {**cells.load_config("tiny-granite-hybrid")}).kinds[0],
        ssm_groups=2),) + cells.load_family("granite_hybrid").model_spec(
            {**cells.load_config("tiny-granite-hybrid")}).kinds[1:]),
     "one group"),
])
def test_resolved_holds_a_state_space_spec_to_what_the_program_runs(
        toy, over, why):
    with pytest.raises(AssertionError, match=why):
        _mixed(toy, **over).resolved()


# ---- the kernels against the recurrence as written ---------------------------

def _recurrence(h, x, dt, a, b, c):
    """One slot's H (H, P, N) through T positions, in float64."""
    h = np.asarray(h, np.float64)
    ys = []
    for t in range(x.shape[0]):
        h = (np.exp(dt[t] * a)[:, None, None] * h
             + (dt[t][:, None] * x[t])[:, :, None] * b[t][None, None, :])
        ys.append(np.einsum("hpn,n->hp", h, c[t]))
    return np.stack(ys), h


@pytest.mark.parametrize("t", [1, 8, 64])
def test_ssd_chunk_interpreted_equals_the_sequential_recurrence(t):
    """T positions of ONE slot against a non-zero incoming H: y and the H
    left behind, the kernel interpreted; a slot that is not live keeps its H
    bit for bit, a fresh one starts from zeros whatever it holds, and no
    other slot or layer is touched."""
    r = np.random.RandomState(t)
    s, layers, heads, p, n = 3, 2, 4, 8, 16
    h0 = r.randn(s, layers, heads, p, n).astype(np.float32)
    a = -np.linspace(1, 16, heads).astype(np.float32)
    x = r.randn(t, heads, p).astype(np.float32)
    dt = (np.abs(r.randn(t, heads)) * 0.1).astype(np.float32)
    b, c = (r.randn(t, n).astype(np.float32) for _ in range(2))
    for fresh in (False, True):
        want_y, want_h = _recurrence(0 * h0[2, 1] if fresh else h0[2, 1], x,
                                     dt, a, b, c)
        y, h = S.ssd_chunk(jnp.asarray(h0), 1, 2, x, dt, a, b, c, True,
                           fresh, use_pallas=True, interpret=True)
        np.testing.assert_allclose(y, want_y, atol=2e-5)
        np.testing.assert_allclose(h[2, 1], want_h, atol=2e-5)
        rest = np.asarray(h).copy()
        rest[2, 1] = h0[2, 1]
        np.testing.assert_array_equal(rest, h0)
    _, h = S.ssd_chunk(jnp.asarray(h0), 1, 2, x, dt, a, b, c, False, False,
                       use_pallas=True, interpret=True)
    np.testing.assert_array_equal(h, h0)


def test_ssd_step_interpreted_equals_the_recurrence_and_skips_a_dead_row():
    r = np.random.RandomState(7)
    s, layers, heads, p, n = 4, 3, 4, 8, 16
    h0 = r.randn(s, layers, heads, p, n).astype(np.float32)
    a = -np.linspace(1, 16, heads).astype(np.float32)
    x = r.randn(s, heads, p).astype(np.float32)
    dt = (np.abs(r.randn(s, heads)) * 0.1).astype(np.float32)
    b, c = (r.randn(s, n).astype(np.float32) for _ in range(2))
    live = np.asarray([True, False, True, True])
    fresh = np.asarray([False, False, True, False])
    for kernel in (True, False):
        y, h = S.ssd_step(jnp.asarray(h0), 2, x, dt, a, b, c,
                          jnp.asarray(live), jnp.asarray(fresh),
                          use_pallas=kernel, interpret=True)
        for i in range(s):
            if not live[i]:
                continue
            want_y, want_h = _recurrence(
                0 * h0[i, 2] if fresh[i] else h0[i, 2], x[i:i + 1],
                dt[i:i + 1], a, b[i:i + 1], c[i:i + 1])
            np.testing.assert_allclose(y[i], want_y[0], atol=2e-5)
            np.testing.assert_allclose(h[i, 2], want_h, atol=2e-5)
        np.testing.assert_array_equal(h[1], h0[1])  # the dead row, bit for bit
        np.testing.assert_array_equal(np.asarray(h)[:, :2], h0[:, :2])


# ---- the program against the reference ---------------------------------------

def test_engine_prefill_and_decode_match_the_reference(toy):
    """`Engine` (one sequence, contiguous cache): chunks of 64, 8 and 1, then
    single steps; a rewind that is not to 0 says it cannot; a sequence begun
    anew at 0 starts from a zero state whatever the cache held."""
    from distributed_llama_tpu.runtime.engine import Engine

    cfg, fam, weights, spec, params = toy
    row = _prompt(90, 5)
    ref, _ = fam.logits_at(cfg, weights, [row], [range(len(row))])
    eng = Engine(spec, params, None, tp=1, dtype=jnp.float32,
                 use_pallas=False)
    assert isinstance(eng.v_cache, F.StateCache)
    assert eng.v_cache.h.shape == (1, 9, 8, 32, 16)
    assert eng.v_cache.snap_h is None and eng.v_cache.ctl is None
    got = [eng.infer_chunk_logits(row[:64]), eng.infer_chunk_logits(row[64:72]),
           *[eng.infer_chunk_logits([t]) for t in row[72:]]]
    np.testing.assert_allclose(np.concatenate(got), ref, atol=LOGITS_TOL,
                               rtol=0)
    with pytest.raises(ValueError, match="keeps no snapshot"):
        eng.seek(85)
    eng.seek(0)
    np.testing.assert_allclose(eng.infer_chunk_logits(row[:20]), ref[:20],
                               atol=LOGITS_TOL, rtol=0)


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "kernels"])
def test_batch_engine_chunked_prefill_and_decode_match_the_reference(
        toy, kernels):
    """Rows that end before, on and behind the stride's end at 255 through
    chunks of 64, 8 and 1 into the paged pool, decode rows riding the longer
    rows' chunks, then T = 1 steps: BatchEngine as the cell builds it (device
    pool, prefix cache on, pipelined, K = 8), the kernels interpreted and
    without."""
    cfg, fam, weights, _, _ = toy
    be = _engine(toy, use_pallas=kernels)
    try:
        assert bool(be._eng.paged_kernel) == kernels
        rng = np.random.default_rng(11)
        probes = []
        for n in (72, 250, 255, 262):
            toks = rng.integers(3, cfg["vocab_size"], n + 6)
            probes.append((toks[:n].tolist(), toks[n:].tolist()))
        got = np.concatenate(probe.drive(be, probes))
        ref, _ = probe.reference_rows(cfg, weights, probes)
        np.testing.assert_allclose(got, ref, rtol=0,
                                   atol=KERNEL_TOL if kernels else LOGITS_TOL)
        # the rows of 255 and 262 crossed position 255: two snapshots kept
        assert be.kv_pool.snapshots.held() == 2
    finally:
        be.close()


@pytest.mark.parametrize("control", ["ssm_state_off", "decay_off",
                                     "dskip_off", "taps_reversed",
                                     "gate_after_norm", "resid_mult_off",
                                     "attn_scale_sqrt"])
def test_each_mechanism_moves_the_reference(toy, control):
    """What the family maps the drawn tensors for: the reference with one
    mechanism changed reads far from the reference, at a prompt of 150 that
    ends in chunks of 64, 8 and 1."""
    cfg, fam, weights, _, _ = toy
    row = _prompt(160, 9)
    at = [range(149, 159)]
    ref, _ = fam.logits_at(cfg, weights, [row], at)
    off, _ = fam.logits_at(cfg, weights, [row], at, precision=control)
    err = probe.position_errors(off, ref)
    assert err.min() > 20 * LOGITS_TOL / 0.015, err


@pytest.mark.parametrize("field,value", [
    ("embedding_multiplier", 1.0), ("residual_multiplier", 1.0),
    ("logits_scaling", 1.0), ("attn_multiplier", 0.0)])
def test_each_multiplier_moves_the_programs_logits(toy, field, value):
    _, _, _, spec, params = toy
    toks = jnp.asarray([_prompt(40, 3)])

    def logits(spec):
        kc, vc = F.init_kv_cache(spec, 1, jnp.float32)
        return np.asarray(F.forward(params, spec, RopeTables.create(spec),
                                    toks, kc, vc, jnp.int32(0))[0])

    want, got = logits(spec), logits(dataclasses.replace(spec,
                                                         **{field: value}))
    assert np.abs(got - want).max() > 1e-4 * np.abs(want).max()


# ---- the carry ---------------------------------------------------------------

def _warm_slots(be, hist):
    """Every slot prefilled with `hist` (slots, T) through one rectangle;
    returns (step, tables, kc, vc)."""
    eng = be._eng
    for sl in be._slots:
        be.slot_cache.cover(sl, CONTEXT)
    tables = be.slot_cache.table()[0]
    step = eng._step_for(None)
    _, kc, vc, _ = step(eng.params, eng.rope, jnp.asarray(hist),
                        eng.k_cache, eng.v_cache,
                        jnp.zeros(hist.shape[0], jnp.int32), tables)
    return step, tables, kc, vc


def _word(vc, live, entry=None):
    word = np.zeros((2, len(live), 1), np.int32)
    word[0, :, 0] = live
    if entry is not None:
        word[1, :, 0] = entry
    return vc._replace(ctl=jnp.asarray(word))


def test_a_k_step_scan_equals_k_single_steps_and_parks_rows(toy):
    """The scan's eight steps against eight T = 1 steps of the same program
    on copies of the same caches: the same tokens and the same matrices; a
    row whose budget is 0 keeps its H bit for bit, one whose budget ends
    mid-scan keeps the H of its last step; the scan hands back, as `held`,
    the matrices it found."""
    be = _engine(toy, prefix_cache=False)
    try:
        eng = be._eng
        hist = np.random.default_rng(3).integers(3, 512, size=(4, 27))
        step, tables, kc, vc = _warm_slots(be, hist)
        copy = lambda t: jax.tree.map(jnp.copy, t)  # noqa: E731
        logits = step(eng.params, eng.rope, jnp.asarray(hist[:, -1:]),
                      copy(kc), copy(vc), jnp.full(4, 26, jnp.int32),
                      tables)[0]
        tok0 = np.argmax(np.asarray(logits)[:, -1], -1).astype(np.int32)
        starts = np.full(4, 27, np.int32)
        budget = np.asarray([8, 8, 0, 5], np.int32)  # row 2 parked throughout
        loop = be._batched_loop(8, "greedy", None)
        toks, _, pos, _, kc_s, vc_s, _ = loop(
            eng.params, eng.rope, tok0, copy(kc), copy(vc), starts,
            np.zeros((4, 2), np.uint32), np.zeros(4, np.float32),
            np.full(4, 0.9, np.float32), budget, tables)
        toks = np.asarray(toks)
        assert np.asarray(pos).tolist() == [35, 35, 27, 32]
        np.testing.assert_array_equal(vc_s.held, vc.h)
        kc_1, vc_1, tok, at = copy(kc), copy(vc), tok0.copy(), starts.copy()
        for i in range(8):
            live = i < budget
            logits, kc_1, vc_1, _ = step(
                eng.params, eng.rope, jnp.asarray(tok[:, None]), kc_1,
                _word(vc_1, live), jnp.asarray(at), tables)
            nxt = np.argmax(np.asarray(logits)[:, 0], -1).astype(np.int32)
            np.testing.assert_array_equal(toks[i][live], nxt[live])
            tok = np.where(live, nxt, tok)
            at = at + live
        np.testing.assert_allclose(vc_s.h, vc_1.h, atol=1e-6)
        np.testing.assert_array_equal(vc_s.h[2], vc.h[2])
        assert np.abs(np.asarray(vc_s.h[0]) - np.asarray(vc.h[0])).max() > 0
    finally:
        be.close()


def test_a_parked_rows_matrices_are_untouched_by_a_mixed_dispatch(toy):
    """A prefill chunk with one rider and two parked rows: the parked rows'
    H are bit for bit what they were; the lead's is the H a fresh one-pass
    forward of its whole sequence leaves; the rider's moved."""
    be = _engine(toy, prefix_cache=False)
    try:
        eng = be._eng
        hist = np.random.default_rng(4).integers(3, 512, size=(4, 40))
        step, tables, kc, vc = _warm_slots(be, hist)
        before = np.asarray(vc.h)
        tokens = np.zeros((4, 64), np.int64)
        tokens[1] = np.random.default_rng(5).integers(3, 512, 64)  # the lead
        tokens[0, 0] = 17  # a rider; rows 2 and 3 are parked
        _, _, vc2, _ = step(eng.params, eng.rope, jnp.asarray(tokens), kc,
                            _word(vc, [1, 1, 0, 0]),
                            jnp.asarray([40, 40, 40, 40, 1], jnp.int32),
                            tables)
        after = np.asarray(vc2.h)
        np.testing.assert_array_equal(after[2:], before[2:])
        assert np.abs(after[0] - before[0]).max() > 0
        # one pass over the lead's 104 tokens, alone, from zeros
        whole = np.concatenate([hist[1], tokens[1]])[None]
        kc0, vc0 = F.init_kv_cache(be.spec, 1, jnp.float32)
        _, _, one = F.forward(eng.params, be.spec, eng.rope,
                              jnp.asarray(whole), kc0, vc0, jnp.int32(0))
        np.testing.assert_allclose(after[1], np.asarray(one.h)[0], atol=1e-5)
    finally:
        be.close()


@pytest.mark.parametrize("pipeline", [True, False], ids=["chained", "plain"])
def test_greedy_requests_through_scans_give_the_references_tokens(
        toy, pipeline):
    """Four requests of different lengths through prefill and K-step scans:
    replies end mid-block by length (the row's budget ends inside the scan
    and its matrices stay at its last step) while the other rows go on,
    chained or not; every row's tokens are the reference's argmax chain."""
    be = _engine(toy, pipeline=pipeline)
    try:
        prompts = [_prompt(n, 20 + n) for n in (70, 33, 90, 17)]
        lens = [21, 9, 30, 14]
        reqs = [be.submit(p, n, _greedy_sampler())
                for p, n in zip(prompts, lens)]
        outs = [r.wait(300) for r in reqs]
        assert be.super_steps > 0
        for p, n, out in zip(prompts, lens, outs):
            assert out == _greedy(toy, p, n)
    finally:
        be.close()


def test_a_flushed_super_step_leaves_the_survivors_as_unpipelined(toy):
    """Rows that stop on the HOST's word (a stop check the device cannot
    know), mid-block: the device over-decodes them, and chained, the
    super-step already in flight is flushed and the matrices swapped back.
    The survivors' tokens are an unpipelined run's, and the reference's."""
    prompts = [_prompt(n, 50 + n) for n in (40, 41, 42, 43)]
    stops = [37, 11, 29, 19]  # none a multiple of 8: every end is mid-block

    def stop_after(n):
        seen = []
        return lambda tok: seen.append(tok) or len(seen) >= n

    def flushes():
        v = metrics.snapshot().get("batch_pipeline_flushes_total", {})
        return sum(v.values()) if isinstance(v, dict) else v

    outs = {}
    for pipeline in (True, False):
        be = _engine(toy, pipeline=pipeline, prefix_cache=False)
        try:
            flushed = flushes()
            reqs = [be.submit(p, 60, _greedy_sampler(),
                              stop_check=stop_after(n))
                    for p, n in zip(prompts, stops)]
            outs[pipeline] = [r.wait(300) for r in reqs]
            if pipeline:
                assert flushes() > flushed
        finally:
            be.close()
    assert [len(o) for o in outs[True]] == stops
    assert outs[True] == outs[False]
    assert outs[True][0] == _greedy(toy, prompts[0], stops[0])


def test_steps_issued_ahead_deliver_the_references_tokens(toy):
    """Prefill chunks with riders, issued ahead of their predecessor's
    delivery (PR 43): a long prompt arrives while two rows decode, so every
    chunk of it carries riders and is planned from the step in flight; all
    three give the reference's tokens."""
    be = _engine(toy, manual=True, slots=3, prefix_cache=False)
    try:
        early = [be.submit(_prompt(20 + i, 60 + i), 24, _greedy_sampler())
                 for i in range(2)]
        n = 0
        while min(len(r.out) for r in early) < 2:
            be._loop_once()
            n += 1
            assert n < 300
        late = be.submit(_prompt(150, 66), 5, _greedy_sampler())
        ahead = 0
        while (not all(r.done.is_set() for r in early + [late])
               or be._inflight is not None):
            be._loop_once()
            ahead += be._inflight is not None and be._inflight.kind == "step"
            n += 1
            assert n < 3000
        assert ahead > 0
        assert late.out == _greedy(toy, late.prompt, 5)
        assert early[0].out == _greedy(toy, early[0].prompt, 24)
    finally:
        be.close()


def test_the_same_slot_reused_starts_from_a_zero_state(toy):
    """One slot, two unrelated requests one after the other: the second
    starts at position 0 of a slot whose matrices hold the first one's
    state, and gives the reference's tokens."""
    be = _engine(toy, slots=1)
    try:
        first = be.submit(_prompt(80, 1), 12, _greedy_sampler())
        first.wait(300)
        assert np.abs(np.asarray(be._eng.v_cache.h)).max() > 0
        second = be.submit(_prompt(50, 2), 12, _greedy_sampler())
        assert second.wait(300) == _greedy(toy, second.prompt, 12)
        assert second.stats.reused_tokens == 0
    finally:
        be.close()


# ---- snapshots by stride -----------------------------------------------------

def test_a_prefix_hit_and_a_slot_rewind_land_on_a_stride_snapshot(toy):
    """The same prompt of 300 three times: cold; then on the SAME slot (a
    rewind: it lands on 256, the newest stride end under the prompt's end,
    and seeds the matrices and the tails from that block's entry); then,
    with the first slot busy, on ANOTHER slot (a directory hit: a remap, and
    the same seed). A prompt that shares 200 tokens has no snapshot under
    them and prefills from 0."""
    be = _engine(toy, slots=2)
    try:
        prompt = _prompt(300, 77)
        want = _greedy(toy, prompt, 8)
        c0 = _count("paged_kv_state_restores_total")
        cold = be.submit(prompt, 8, _greedy_sampler())
        assert cold.wait(300) == want
        assert _count("paged_kv_state_restores_total") == c0
        assert be.kv_pool.snapshots.held() == 1
        again = be.submit(prompt, 8, _greedy_sampler())
        assert again.wait(300) == want
        assert again.stats.reused_tokens == STRIDE  # not 299, nor 288
        assert _count("paged_kv_state_restores_total") == c0 + 1
        busy = be.submit(prompt[:50] + _prompt(30, 78), 40, _greedy_sampler())
        hit = be.submit(prompt + [5, 6, 7], 6, _greedy_sampler())
        assert hit.wait(300) == _greedy(toy, prompt + [5, 6, 7], 6)
        busy.wait(300)
        assert hit.stats.reused_tokens == STRIDE
        short = be.submit(prompt[:200] + [9, 9], 4, _greedy_sampler())
        assert short.wait(300) == _greedy(toy, prompt[:200] + [9, 9], 4)
        assert short.stats.reused_tokens == 0
    finally:
        be.close()


def test_a_preempted_slot_resumes_from_its_newest_snapshot(toy):
    """A batch request preempted for an interactive one behind position 256
    resumes byte-identical to an uninterrupted run, re-prefilling from the
    stride's snapshot and not from 0."""
    be = _engine(toy, slots=1, manual=True)
    try:
        prompt = _prompt(250, 31)
        ref = be.submit(list(prompt), 40, _greedy_sampler(), klass="batch")
        while not ref.done.is_set() or be._inflight is not None:
            be._loop_once()
        victim = be.submit(_prompt(250, 32), 40, _greedy_sampler(),
                           klass="batch")
        n = 0
        while len(victim.out) < 20:
            be._loop_once()
            n += 1
            assert n < 2000
        inter = be.submit([1, 2, 3], 4, _greedy_sampler(),
                          klass="interactive")
        while (not (victim.done.is_set() and inter.done.is_set())
               or be._inflight is not None):
            be._loop_once()
            n += 1
            assert n < 4000
        assert victim.preemptions >= 1, "the preemption never engaged"
        assert victim.out == _greedy(toy, victim.prompt, 40)
        assert victim.stats.reused_tokens >= STRIDE
    finally:
        be.close()


def test_the_snapshot_pool_allots_confirms_evicts_and_frees():
    pool = DeviceKVPool(16, BT)
    snaps = pool.snapshots = SnapshotPool(2, entry_bytes=100)
    a, b, c = pool.alloc(3)
    ea, sa = snaps.allot(a)
    eb, sb = snaps.allot(b)
    assert {ea, eb} == {1, 2} and snaps.held() == 2
    assert snaps.entry(a) is None  # not confirmed yet: nothing lands on it
    # both entries are being written: a third block gets the scratch entry
    assert snaps.allot(c) == (0, 0)
    snaps.settle(a, sa, True)
    snaps.settle(b, sb, True)
    assert snaps.entry(a) == ea and snaps.entry(b) == eb
    assert metrics.snapshot()["kv_pool_ssm_snapshot_bytes"] == 200
    # none free: the least recently wanted confirmed one (a) is given up
    snaps.entry(b)
    snaps.entry(a)
    snaps.entry(b)
    evicted = _count("paged_kv_ssm_snapshot_evictions_total")
    ec, sc = snaps.allot(c)
    assert ec == ea and snaps.entry(a) is None and snaps.evictions == 1
    assert _count("paged_kv_ssm_snapshot_evictions_total") == evicted + 1
    # a dispatch whose tokens were not accepted gives its entry back; a
    # settlement of an older allotment of the same block changes nothing
    ec2, sc2 = snaps.allot(c)
    snaps.settle(c, sc, False)
    assert ec2 == ec and snaps.held() == 2
    snaps.settle(c, sc2, False)
    assert snaps.held() == 1 and snaps.entry(c) is None
    # freed with the block
    pool.decref([b])
    assert snaps.held() == 0 and snaps.entry(b) is None


def test_an_evicted_snapshot_sends_the_hit_to_an_older_one_or_to_zero(toy):
    """A pool of ONE entry: the second stride end of a prompt takes the
    entry of the first, and a repeat lands on 512's... no: on the newest
    that is left; with that one given up too, on 0."""
    cfg, fam, weights, spec, _ = toy
    be = _engine(toy, slots=1)
    try:
        be.kv_pool.snapshots = SnapshotPool(1, 1)
        prompt = _prompt(300, 88)
        want = _greedy(toy, prompt, 4)
        assert be.submit(prompt, 4, _greedy_sampler()).wait(300) == want
        # another sequence crosses 255 and takes the only entry
        other = _prompt(280, 89)
        assert be.submit(other, 4, _greedy_sampler()).wait(300) == _greedy(
            toy, other, 4)
        assert be.kv_pool.snapshots.evictions >= 1
        again = be.submit(prompt, 4, _greedy_sampler())
        assert again.wait(300) == want
        assert again.stats.reused_tokens == 0
    finally:
        be.close()


def test_lfm2_keeps_a_snapshot_in_every_block():
    """The convolution model's stride is the pool's block: its snapshots
    lie at the block's id, a rewind lands on the last block end, and the
    engine has no snapshot pool."""
    from distributed_llama_tpu.runtime.batch_engine import BatchEngine

    cfg = {**cells.load_config("tiny-lfm2"), "context": 256}
    weights = W.make_weights(cfg, SEED)
    spec = cells.load_family("lfm2").model_spec(cfg)
    be = BatchEngine(spec, W.to_program_params(weights, cfg), None, slots=2,
                     superstep=8, pipeline=True, paged_kv=True,
                     kv_block_tokens=BT, prefix_cache=True,
                     dtype=jnp.float32, tp=1)
    try:
        assert be.slot_cache.stride == BT and be.kv_pool.snapshots is None
        assert be._eng.v_cache.h is None and be._eng.v_cache.ctl is None
        assert be._eng.v_cache.snaps.shape[1] == be.kv_pool.n_blocks
        prompt = _prompt(100, 77)
        cold = be.submit(prompt, 6, _greedy_sampler()).wait(300)
        again = be.submit(prompt, 6, _greedy_sampler())
        assert again.wait(300) == cold
        assert again.stats.reused_tokens == 96
        assert be.slot_cache.state_landing(99, lambda i: None) == 96
    finally:
        be.close()


# ---- counters, refusals, files -----------------------------------------------

def test_the_state_space_counters_and_the_span_args_of_a_dispatch(toy):
    be = _engine(toy, prefix_cache=False)
    try:
        names = ("batch_ssm_rows_stepped_total",
                 "batch_ssm_chunk_tokens_total",
                 "batch_ssm_state_bytes_total",
                 "batch_ssm_stride_ends_total", "batch_ssm_snapshots_total")
        for sl in be._slots:
            be.slot_cache.cover(sl, CONTEXT)
        rows = [(be._slots[i], None) for i in (0, 1, 3)]
        before = metrics.snapshot()
        # slot 1 prefills 64 tokens from 192 (its last ends the stride at
        # 255), slot 0 rides at 255 (it ends one too), slot 3 rides at 30
        snaps, args = be.slot_cache.state_word(rows, [255, 192, 0, 30], [1, 64, 0, 1],
                                     chunk=64)
        after = metrics.snapshot()
        d = [after[k] - before.get(k, 0) for k in names]
        matrix = 9 * 8 * 32 * 16 * 4
        assert d == [9 * 2, 9 * 64, 2 * matrix * (2 + 1 + 2), 2, 2]
        assert args == {"ssm_rows": 18, "ssm_chunk": 576, "ssm_bytes": d[2]}
        word = np.asarray(be._eng.v_cache.ctl)
        assert word[0, :, 0].tolist() == [1, 1, 0, 1]
        assert sorted(word[1, :2, 0].tolist()) == [1, 2] and not word[1, 2:].any()
        assert [(s.index, last) for s, _, _, _, last in snaps] == [
            (0, 255), (1, 255)]
        # a K-step scan of 8: every step of every live row through ssd_step
        _, args = be.slot_cache.state_word(rows, [40, 50, 0, 60], [8, 8, 0, 3])
        assert args["ssm_rows"] == 9 * 19 and args["ssm_chunk"] == 0
    finally:
        be.close()


def test_a_chunk_is_cut_at_a_stride_end(toy):
    """A prompt that starts off the stride's grid (it never does in
    practice: every landing is a multiple of the stride) is cut so that no
    chunk runs past a stride's last position."""
    be = _engine(toy, manual=True, slots=1, prefix_cache=False)
    try:
        sl = be._slots[0]
        req = be.submit(_prompt(200, 5), 2, _greedy_sampler())
        be._loop_once()  # admitted, the first chunk of 64 dispatched
        while sl.req is None:
            be._loop_once()
        sl.pos, sl.ahead = 250, 0  # as if it stood at 250 with 64 to go
        sl.pending = _prompt(64, 6)
        be.slot_cache.cover(sl, 320)
        fl, _, _ = be._plan_chunk(sl, [], 0.0)
        assert fl.k == 1  # 6 positions to the stride's end: chunks of 1
        sl.pos = 192
        fl, _, _ = be._plan_chunk(sl, [], 0.0)
        assert fl.k == 64
        req.cancel()
    finally:
        be.close()


@pytest.mark.parametrize("kw,why", [
    (dict(paged_kv=False), "dense per-slot caches"),
    (dict(speculative=4), "speculative verify"),
    (dict(prefix_cache_q80=True), "Q80 cold tier"),
    (dict(superstep=40), "superstep 40"),
    (dict(kv_cache_storage="host", kv_cache_resident=64),
     "host-spill ring does not support layers that hold a state"),
    (dict(tp=2), "runs whole on one chip"),
])
def test_what_cannot_carry_the_state_refuses_at_construction(toy, kw, why):
    with pytest.raises(ValueError, match=why):
        _engine(toy, **kw)


def test_the_engine_and_the_stream_of_blocks_refuse_too(toy):
    from distributed_llama_tpu.runtime.engine import Engine

    _, _, _, spec, params = toy
    with pytest.raises(ValueError, match="sequence-sharded"):
        Engine(spec, params, None, tp=1, sp=2, dtype=jnp.float32)
    be = _engine(toy)
    try:
        with pytest.raises(ValueError, match="KV-block streaming"):
            be.submit(_prompt(20, 1), 2, _greedy_sampler(), export_kv=True)
    finally:
        be.close()


def test_random_params_of_a_state_space_spec_run(toy):
    """`init_random_params` draws decays, steps and taps that do something,
    and the program runs on them in float32 and in Q40."""
    _, _, _, spec, _ = toy
    rope = RopeTables.create(spec)
    toks = jnp.asarray([_prompt(24, 8)])
    for ftype in (FloatType.F32, FloatType.Q40):
        params = init_random_params(spec, ftype, seed=3)
        kc, vc = F.init_kv_cache(spec, 1, jnp.float32)
        logits, _, vc = F.forward(params, spec, rope, toks, kc, vc,
                                  jnp.int32(0))
        assert np.isfinite(np.asarray(logits)).all()
        assert np.abs(np.asarray(vc.h)).max() > 0
