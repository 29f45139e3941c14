"""Kimi-Linear-48B-A3B-Instruct's graph at a toy size on the CPU: the program
against the family's plain reference (`benchmark/families/kimi_linear.py`,
the delta rule as a scan over positions) on seeded weights.

What the configuration forces and these tests hold: a THIRD state kind whose
matrix a head is decayed by channel and corrected by the delta rule
(`ops/pallas_kda.py`, interpreted against the recurrence at a decay of e^-5
a position), carried as Granite's is (parked rows, over-decode, a flush, a
step issued ahead, a reused slot, snapshots by stride); latent attention as
a KIND of layer beside it, without a rotation and with q through one
projection, its one row a token in the same pool as the rings and the
matrices' snapshots; a leading dense layer whose mixer holds a matrix; the
header keys and tensors of all of it; and each refusal. Granite and LFM2 are
held to what they did by their own files.
"""

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells, probe
from benchmark import weights as W
from distributed_llama_tpu.cache.device_pool import SnapshotPool
from distributed_llama_tpu.formats.mfile import (load_model,
                                                 params_file_order,
                                                 write_model)
from distributed_llama_tpu.models import forward as F
from distributed_llama_tpu.models.params import (block_tensor_shapes,
                                                 hold_dense,
                                                 init_random_params,
                                                 run_tensor_shapes)
from distributed_llama_tpu.models.spec import (ArchType, LayerKind,
                                               ModelSpec, RopeType)
from distributed_llama_tpu.obs import metrics
from distributed_llama_tpu.ops import pallas_kda as K
from distributed_llama_tpu.ops.rope import RopeTables
from distributed_llama_tpu.quants import FloatType
from distributed_llama_tpu.runtime.sampler import Sampler

SEED = 2**31 + 48
LOGITS_TOL = 2e-5  # float32 against float32 at logits of about 1
KERNEL_TOL = 5e-3  # the Q40 x Q80 kernels' rounding, as in test_lfm2.py
CONTEXT = 512
BT = 16
STRIDE = F.STATE_STRIDE


@pytest.fixture(scope="module")
def toy():
    cfg = {**cells.load_config("tiny-kimi-linear"), "context": CONTEXT}
    fam = cells.load_family("kimi_linear")
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


def _settle(pred, timeout=10):
    t0 = time.time()
    while not pred() and time.time() - t0 < timeout:
        time.sleep(0.01)
    assert pred()


# ---- the model as data ------------------------------------------------------

def test_the_spec_carries_the_model_as_data(toy):
    cfg, _, _, spec, params = toy
    kda, mla = spec.kinds
    assert (kda.conv_kernel, kda.kda_heads, kda.kda_key_dim,
            kda.kda_value_dim, kda.kda_rank) == (4, 4, 32, 32, 32)
    assert (mla.kv_lora_rank, mla.qk_nope_head_dim, mla.qk_rope_head_dim,
            mla.v_head_dim, mla.q_lora_rank) == (64, 32, 32, 32, 0)
    assert mla.rope_type == RopeType.NONE and not mla.conv_kernel
    assert spec.mixed and spec.ssm and spec.latent and spec.lead_layers == 1
    assert [r.name for r in spec.runs()] == ["lead", "blocks"]
    assert spec.state_layers == (0, 1, 2, 4, 5, 6)
    assert spec.cache_layers == (3, 7)
    assert spec.state_rows == 3 and spec.state_width == 4 * 96
    assert spec.state_matrix == (4, 32, 32)
    assert spec.cache_widths == (128, 0) and spec.n_kv_heads == 1
    assert spec.head_size == 64 and spec.attn_scale == 64 ** -0.5
    assert spec.state_block_bytes(4) == 6 * (3 * 384 * 4 + 4 * 32 * 32 * 4)
    assert (spec.n_experts, spec.n_active_experts, spec.router_scale,
            spec.shared_hidden_dim) == (8, 2, 2.446, 64)
    assert spec.state_snapshots == cfg["state_snapshots"]
    lead, blocks = (run_tensor_shapes(spec, r) for r in spec.runs())
    assert lead["kda_in"][0] == (1, 384, 128) and "wq" not in lead
    assert lead["w1"][0] == (1, 256, 128) and "router" not in lead
    assert blocks["kda_in"][0][0] == 5 and blocks["wq"][0] == (2, 256, 128)
    assert blocks["rms_kv"][0] == (2, 64) and "rms_q" not in blocks
    assert blocks["router"][0][0] == 7
    assert params["blocks"]["kda_a_log"].shape == (5, 4)
    assert "kda_lo" in block_tensor_shapes(spec.of_kind(0))
    assert "wkv_a" in block_tensor_shapes(spec.of_kind(1))
    assert spec.of_kind(1).latent and not spec.of_kind(0).latent


def test_the_published_file_gives_the_published_model():
    cfg = cells.load_config("kimi-linear-48b-a3b-l8")
    fam = cells.load_family("kimi_linear")
    spec = fam.model_spec(cfg)
    assert (spec.dim, spec.hidden_dim, spec.lead_hidden_dim,
            spec.shared_hidden_dim) == (2304, 1024, 9216, 1024)
    assert (spec.n_experts, spec.n_active_experts) == (256, 8)
    assert spec.state_matrix == (32, 128, 128) and spec.state_width == 12288
    assert spec.layer_kinds == (0, 0, 0, 1, 0, 0, 0, 1)
    assert spec.cache_widths == (640, 0) and spec.head_size == 192
    assert spec.vocab_size == 163840 and spec.router_scale == 2.446
    # one snapshot: six layers' matrices in float32 and three rows of 12288
    assert spec.state_block_bytes(2) == 6 * (2 * 2**20 + 3 * 12288 * 2)
    assert fam.layer_types(cfg) == ["kda"] * 3 + ["mla"] + ["kda"] * 3 + [
        "mla"]
    assert cfg["reduced"] == ["num_hidden_layers", "max_position_embeddings"]


def _mixed(toy, **over):
    return dataclasses.replace(toy[3], **over)


@pytest.mark.parametrize("over,why", [
    (lambda s: dict(kinds=(dataclasses.replace(s.kinds[0], kda_rank=0),
                           s.kinds[1])), "a delta-rule kind states"),
    (lambda s: dict(kinds=(dataclasses.replace(s.kinds[0], ssm_state=16,
                                               ssm_heads=4, ssm_head_dim=8),
                           s.kinds[1])), "is no state-space kind"),
    (lambda s: dict(kinds=(s.kinds[0], dataclasses.replace(
        s.kinds[1], sliding_window=8))), "a latent kind states"),
    (lambda s: dict(kv_lora_rank=64), "kinds of layer state their own"),
    (lambda s: dict(state_snapshots=0), "states its snapshot pool"),
    (lambda s: dict(kinds=s.kinds + (LayerKind(
        "conv", 4, rope_type=RopeType.NONE, conv_kernel=3),)),
     "one state kind .* and one attention kind"),
    (lambda s: dict(n_kv_heads=2), "a latent row is one kv head"),
])
def test_resolved_holds_a_delta_rule_spec_to_what_the_program_runs(
        toy, over, why):
    with pytest.raises(AssertionError, match=why):
        _mixed(toy, **over(toy[3])).resolved()


def test_latent_attention_is_a_kind_only_beside_state_layers(toy):
    """The sentences `ModelSpec.resolved` says since latent attention can be
    a kind: a latent kind beside an ordinary attention kind (no state layer)
    is refused, and so is a latent row with a window."""
    spec = toy[3]
    mla = spec.kinds[1]
    full = LayerKind("full", 4, rope_type=RopeType.FALCON)
    with pytest.raises(AssertionError,
                       match="a kind only beside state layers"):
        dataclasses.replace(spec, kinds=(full, mla), state_snapshots=0,
                            layer_kinds=(0, 0, 0, 1, 0, 0, 0, 1),
                            lead_layers=0).resolved()
    with pytest.raises(AssertionError, match="no window and no 0/1"):
        ModelSpec(arch_type=ArchType.MIXTRAL, dim=64, hidden_dim=32,
                  n_layers=2, n_heads=4, n_kv_heads=1, vocab_size=64,
                  seq_len=64, kv_lora_rank=32, q_lora_rank=32,
                  qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                  rope_type=RopeType.FALCON, sliding_window=8).resolved()


# ---- the kernels against the recurrence --------------------------------------

def _draw(r, t, heads, kk, vv, g_min):
    q, k = (r.randn(t, heads, kk).astype(np.float32) for _ in range(2))
    q /= np.sqrt((q * q).sum(-1, keepdims=True) + 1e-6)
    k /= np.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    v = r.randn(t, heads, vv).astype(np.float32)
    g = -r.uniform(0.0, -g_min, (t, heads, kk)).astype(np.float32)
    g[:, 0] = g_min  # a head that forgets by e^-5 every position
    beta = r.uniform(0, 1, (t, heads)).astype(np.float32)
    beta[:, 0], beta[:, 1] = 1.0, 0.0
    return q, k, v, g, beta


def _recurrence(s, q, k, v, g, beta):
    """The delta rule in float64 numpy, a position at a time."""
    s = s.astype(np.float64)
    out = []
    for t in range(q.shape[0]):
        s = np.exp(g[t].astype(np.float64))[..., None] * s
        u = beta[t][:, None] * (v[t] - np.einsum("hkv,hk->hv", s, k[t]))
        s = s + k[t][..., None] * u[:, None, :]
        out.append(np.einsum("hkv,hk->hv", s, q[t]) * q.shape[-1] ** -0.5)
    return np.stack(out), s


@pytest.mark.parametrize("t", [1, 8, 64])
def test_kda_chunk_interpreted_equals_the_sequential_recurrence(t):
    """At g down to -5 a position over 64 positions (1 / exp(G) would be
    e^320), beta at 0 and at 1: no overflow, no NaN, the recurrence's
    numbers; a dead chunk leaves the matrices bit for bit; a fresh one
    starts from zeros."""
    r = np.random.RandomState(t)
    slots, layers, heads, kk, vv = 3, 2, 4, 32, 16
    h0 = r.randn(slots, layers, heads, kk, vv).astype(np.float32)
    q, k, v, g, beta = _draw(r, t, heads, kk, vv, -5.0)
    for kernel in (True, False):
        for live, fresh in ((True, False), (True, True), (False, False)):
            o, h = K.kda_chunk(jnp.asarray(h0), 1, 2, q, k, v, g, beta,
                               jnp.asarray(live), jnp.asarray(fresh),
                               use_pallas=kernel, interpret=True)
            assert np.isfinite(np.asarray(o)).all()
            if not live:
                np.testing.assert_array_equal(h, h0)
                continue
            want_o, want_s = _recurrence(
                0 * h0[2, 1] if fresh else h0[2, 1], q, k, v, g, beta)
            np.testing.assert_allclose(o, want_o, atol=2e-5)
            np.testing.assert_allclose(h[2, 1], want_s, atol=2e-5)
            np.testing.assert_array_equal(np.asarray(h)[:2], h0[:2])
            np.testing.assert_array_equal(h[2, 0], h0[2, 0])


def test_kda_step_interpreted_equals_the_recurrence_and_skips_a_dead_row():
    r = np.random.RandomState(7)
    slots, layers, heads, kk, vv = 4, 3, 4, 32, 16
    h0 = r.randn(slots, layers, heads, kk, vv).astype(np.float32)
    q, k, v, g, beta = _draw(r, slots, heads, kk, vv, -5.0)
    live = np.asarray([True, False, True, True])
    fresh = np.asarray([False, False, True, False])
    for kernel in (True, False):
        o, h = K.kda_step(jnp.asarray(h0), 2, q, k, v, g, beta,
                          jnp.asarray(live), jnp.asarray(fresh),
                          use_pallas=kernel, interpret=True)
        for i in range(slots):
            if not live[i]:
                continue
            want_o, want_s = _recurrence(
                0 * h0[i, 2] if fresh[i] else h0[i, 2], q[i:i + 1],
                k[i:i + 1], v[i:i + 1], g[i:i + 1], beta[i:i + 1])
            np.testing.assert_allclose(o[i], want_o[0], atol=2e-5)
            np.testing.assert_allclose(h[i, 2], want_s, atol=2e-5)
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
    assert eng.v_cache.h.shape == (1, 6, 4, 32, 32)
    assert eng.k_cache.shape[0] == 2 and eng.k_cache.shape[-1] == 128
    assert eng.v_cache.rows.shape[-1] == 0  # a latent row has one side
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
    without. Logits, not tokens."""
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


def test_eight_slots_of_mixed_lengths_match_the_reference(toy):
    """The cell's eight slots, every one with a row of another length, some
    under a chunk, some over a stride: every recorded position's logits."""
    cfg, fam, weights, _, _ = toy
    be = _engine(toy, slots=8)
    try:
        rng = np.random.default_rng(12)
        probes = []
        for n in (5, 17, 64, 71, 130, 256, 257, 300):
            toks = rng.integers(3, cfg["vocab_size"], n + 4)
            probes.append((toks[:n].tolist(), toks[n:].tolist()))
        got = np.concatenate(probe.drive(be, probes))
        ref, _ = probe.reference_rows(cfg, weights, probes)
        np.testing.assert_allclose(got, ref, rtol=0, atol=LOGITS_TOL)
    finally:
        be.close()


@pytest.mark.parametrize("control", [
    "kda_state_off", "decay_off", "delta_off", "qk_norm_off",
    "taps_reversed", "out_gate_off", "pe_rotated", "router_bias_off"])
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
    assert err.max() > 50 * LOGITS_TOL, err


def test_an_mla_layers_output_does_not_know_its_position(toy):
    """No rotation (`mla_use_nope`): shifting every position by a constant
    changes nothing an MLA layer computes. Positions reach the layer in two
    ways: the causal ORDER, which a constant shift keeps, and the rotation
    table's rows, which it moves. So the table is the whole of the matter:
    with rows of garbage in it (what any shift of the positions would read)
    the program's logits are bit for bit the same, and the family's
    reference WITH the rotation the model does not state reads elsewhere."""
    _, _, _, spec, params = toy
    params = hold_dense(params, jnp.float32)  # w_uk, w_uv: as the engine
    rope = RopeTables.create(spec)
    toks = jnp.asarray([_prompt(40, 3)])

    def logits(rope):
        kc, vc = F.init_kv_cache(spec, 1, jnp.float32)
        return np.asarray(F.forward(params, spec, rope, toks, kc, vc,
                                    jnp.int32(0))[0])

    garbage = RopeTables(rope.cos * 0 + 0.3, rope.sin * 0 - 0.7,
                         rope.rope_type)
    np.testing.assert_array_equal(logits(rope), logits(garbage))
    # and the family's reference WITH a rotation reads elsewhere
    cfg, fam, weights = toy[:3]
    row = _prompt(60, 4)
    ref, _ = fam.logits_at(cfg, weights, [row], [[59]])
    rot, _ = fam.logits_at(cfg, weights, [row], [[59]],
                           precision="pe_rotated")
    assert np.abs(rot - ref).max() > 50 * LOGITS_TOL


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
    row whose budget is 0 keeps its S bit for bit, one whose budget ends
    mid-scan keeps the S of its last step; the scan hands back, as `held`,
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
    S are bit for bit what they were; the lead's is the S a fresh one-pass
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
    replies end mid-block by length while the other rows go on, chained or
    not; every row's tokens are the reference's argmax chain."""
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
    """Rows that stop on the HOST's word mid-block: the device over-decodes
    them, and chained, the super-step already in flight is flushed and the
    matrices swapped back (`held`). The survivors' tokens are an unpipelined
    run's, and the reference's."""
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
    delivery: a long prompt arrives while two rows decode; all three give
    the reference's tokens."""
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


# ---- snapshots by stride, in one pool with the latent rows -------------------

def test_latent_rows_rings_and_matrices_stand_in_one_pool(toy):
    """What one cache manager holds for this model: the pool's row is a
    latent row (one kv head, 128 values at the toy's 64 + 32, an empty
    second side) over the TWO attention layers, beside the slots' rings at
    the delta-rule kind's width, the running matrices, `held`, and the
    snapshot pool's entries with their tails."""
    from distributed_llama_tpu.runtime.slot_cache import pool_sides

    be = _engine(toy)
    try:
        eng, spec = be._eng, be.spec
        n = be.kv_pool.n_blocks
        assert eng.k_cache.shape == (2, n, 1, BT, 128)
        vc = eng.v_cache
        assert vc.rows.shape == (2, n, 1, BT, 0)
        assert vc.ring.shape == (4, F.STATE_RING, 16, 384)
        assert vc.h.shape == vc.held.shape == (4, 6, 4, 32, 32)
        assert vc.snap_h.shape == (spec.state_snapshots + 1, 6, 4, 32, 32)
        assert vc.snaps.shape == (1, spec.state_snapshots + 1, 32, 384)
        assert vc.ctl.shape == (2, 4, 1)
        assert len(pool_sides(eng)) == 2  # the rows' two sides: no snapshot
        assert be.slot_cache.stride == STRIDE
        assert isinstance(be.kv_pool.snapshots, SnapshotPool)
        assert metrics.snapshot()["batch_state_matrix_bytes"] == 4 * 32 * 32 * 4
    finally:
        be.close()


def test_a_prefix_hit_and_a_slot_rewind_land_on_a_stride_snapshot(toy):
    """The same prompt of 300 three times: cold; then on the SAME slot (a
    rewind: it lands on 256 and seeds the matrices and the tails from that
    block's entry); then, with the first slot busy, on ANOTHER slot (a
    directory hit). A prompt that shares 200 tokens has no snapshot under
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


def test_a_demoted_block_gives_its_snapshot_up(toy):
    """A snapshot lies in a pool of its own, by stride, and is FREED with
    its block: the latent rows travel to the host tier, the demoted blocks'
    entries are gone, and the same prompt again finds its rows cold but no
    snapshot under them, prefills from 0 and gives the same tokens."""
    be = _engine(toy, slots=2, superstep=4, kv_pool_blocks=80)
    try:
        prompt = _prompt(300, 9)
        want = be.submit(list(prompt), 6, _greedy_sampler()).wait(180)
        assert want == _greedy(toy, prompt, 6)
        _settle(lambda: be.prefix_cache.total_refs() == 0)
        snaps = be.kv_pool.snapshots
        assert snaps.held() == 1  # the block that ends at position 255
        again = be.submit(list(prompt), 6, _greedy_sampler())
        assert again.wait(180) == want
        assert again.stats.reused_tokens == STRIDE
        _settle(lambda: be.prefix_cache.total_refs() == 0)
        for sl in be._slots:
            be.slot_cache.release(sl)
        be.slot_cache.demote(be.prefix_cache.stats()["dev_blocks"])
        be.slot_cache.settle(force=True)
        assert be.prefix_cache.stats()["cold_blocks"] >= 16
        assert snaps.held() == 0
        cold = be.submit(list(prompt), 6, _greedy_sampler())
        assert cold.wait(180) == want
        assert cold.stats.reused_tokens == 0
    finally:
        be.close()


# ---- counters, refusals, files -----------------------------------------------

def test_the_matrix_state_counters_and_the_span_args_of_a_dispatch(toy):
    """The counters PR 44 added count a KDA layer's matrices under the names
    they have, and the dispatch span carries the KDA rows stepped and the
    chunk tokens under the args' own names (`ssm_rows`, `ssm_chunk` name the
    state's shape, a matrix a head: the readers' source)."""
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
        snaps, args = be.slot_cache.state_word(
            rows, [255, 192, 0, 30], [1, 64, 0, 1], chunk=64)
        after = metrics.snapshot()
        d = [after[k] - before.get(k, 0) for k in names]
        matrix = 6 * 4 * 32 * 32 * 4
        assert d == [6 * 2, 6 * 64, 2 * matrix * (2 + 1 + 2), 2, 2]
        assert args == {"ssm_rows": 12, "ssm_chunk": 384, "ssm_bytes": d[2]}
        _, args = be.slot_cache.state_word(rows, [40, 50, 0, 60],
                                           [8, 8, 0, 3])
        assert args["ssm_rows"] == 6 * 19 and args["ssm_chunk"] == 0
    finally:
        be.close()


def test_a_chunk_is_cut_at_a_stride_end(toy):
    be = _engine(toy, manual=True, slots=1, prefix_cache=False)
    try:
        sl = be._slots[0]
        req = be.submit(_prompt(200, 5), 2, _greedy_sampler())
        be._loop_once()
        while sl.req is None:
            be._loop_once()
        sl.pos, sl.ahead = 250, 0
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
     "host-spill ring does not support a latent cache row"),
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


@pytest.mark.parametrize("ftype", [FloatType.F32, FloatType.Q40])
def test_mfile_roundtrip_of_the_new_header_keys_and_tensors(tmp_path, toy,
                                                            ftype):
    """A delta-rule kind's heads, key and value sizes and gate rank, a latent
    kind's row and head widths with q_lora_rank 0 (one `wq`, no `rms_q`), a
    leading dense layer whose mixer holds a matrix, the snapshot pool's
    entries: written, read back, and the tensors the same."""
    spec = toy[3]
    params = init_random_params(spec, ftype, seed=5)
    path = str(tmp_path / "kimi.m")
    write_model(path, spec, params_file_order(spec, params), ftype)
    spec2, params2 = load_model(path)
    kda, mla = spec2.kinds
    assert (kda.conv_kernel, kda.kda_heads, kda.kda_key_dim,
            kda.kda_value_dim, kda.kda_rank) == (4, 4, 32, 32, 32)
    assert (mla.q_lora_rank, mla.kv_lora_rank, mla.qk_nope_head_dim,
            mla.qk_rope_head_dim, mla.v_head_dim) == (0, 64, 32, 32, 32)
    assert mla.rope_type == kda.rope_type == RopeType.NONE
    assert spec2.state_matrix == (4, 32, 32) and spec2.state_width == 384
    assert spec2.layer_kinds == spec.layer_kinds and spec2.lead_layers == 1
    assert spec2.latent and spec2.cache_widths == (128, 0)
    assert (spec2.state_snapshots, spec2.router_scale,
            spec2.router_bias) == (spec.state_snapshots, 2.446, True)
    for st in ("lead", "blocks"):
        assert set(params2[st]) == set(params[st])
        for name in params[st]:
            a, b = params[st][name], params2[st][name]
            a = a.to_numpy() if hasattr(a, "to_numpy") else np.asarray(a)
            b = b.to_numpy() if hasattr(b, "to_numpy") else np.asarray(b)
            assert a.shape == b.shape, name
            np.testing.assert_allclose(a, b, atol=1e-6, err_msg=name)


def test_random_params_of_a_delta_rule_spec_run(toy):
    """`init_random_params` draws decays, steps and taps that do something,
    and the program runs on them in float32 and in Q40."""
    _, _, _, spec, _ = toy
    rope = RopeTables.create(spec)
    toks = jnp.asarray([_prompt(24, 8)])
    for ftype in (FloatType.F32, FloatType.Q40):
        params = hold_dense(init_random_params(spec, ftype, seed=3),
                            jnp.float32)
        kc, vc = F.init_kv_cache(spec, 1, jnp.float32)
        logits, _, vc = F.forward(params, spec, rope, toks, kc, vc,
                                  jnp.int32(0))
        assert np.isfinite(np.asarray(logits)).all()
        assert np.abs(np.asarray(vc.h)).max() > 0
