"""LFM2-8B-A1B's block graph at the toy size, against its plain reference.

What the model forces is data on `ModelSpec`: a KIND of layer whose mixer is a
gated short convolution (`LayerKind.conv_kernel`), 3 of the toy's 5 layers,
beside attention layers with QK-norm (`qk_norm`); a sigmoid router that picks
over score + bias and weighs by the score (`router_bias`); a leading dense
layer. A convolution layer holds a STATE, its last two rows of v = B * u,
which is not a list of positions: it rides in the second cache's place as a
`StateCache` (a ring of v rows a slot, and a snapshot a pool block), and the
tests below hold every way the batched engine moves it (a chunk continuing a
slot's state, riders, parked rows, the K-step scan, a flushed chained
super-step, a prefix hit, demotion and promotion, a slot rewind) to the
reference, which is the benchmark's own (`benchmark/families/lfm2.py`): plain
float32, the whole sequence at once, no cache, no state.

Tolerances. LOGITS_TOL 2e-4 (absolute, logits of rms about 0.3): both sides
are float32 and differ by the order of sums, which reads 1e-6 here. The same
reference with the state zeroed at every dispatch, the taps reversed, the bias
or QK-norm left out reads above 1e-2 (benchmark/tests/test_family_lfm2.py),
so the tolerance tells the model from each model that loses a mechanism.
KERNEL_TOL 2e-3 where the engine runs the Q40 kernels (bf16 operands).
"""

import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from benchmark import cells, probe
from benchmark import weights as W
from distributed_llama_tpu.formats.mfile import (load_model, params_file_order,
                                                 read_spec, write_model)
from distributed_llama_tpu.models import forward as F
from distributed_llama_tpu.models.params import (block_tensor_shapes,
                                                 init_random_params,
                                                 run_tensor_shapes,
                                                 stack_names)
from distributed_llama_tpu.models.spec import (ArchType, LayerKind, ModelSpec,
                                               RopeType, RouterScore)
from distributed_llama_tpu.ops.rope import RopeTables
from distributed_llama_tpu.quants import FloatType
from distributed_llama_tpu.runtime.sampler import Sampler

SEED = 2**31 + 42
LOGITS_TOL = 2e-4
KERNEL_TOL = 2e-3
CONTEXT = 256
BT = 16


@pytest.fixture(scope="module")
def toy():
    cfg = {**cells.load_config("tiny-lfm2"), "context": CONTEXT}
    fam = cells.load_family("lfm2")
    weights = W.make_weights(cfg, SEED)
    return (cfg, fam, weights, fam.model_spec(cfg),
            W.to_program_params(weights, cfg))


def _engine(toy, **kw):
    from distributed_llama_tpu.runtime.batch_engine import BatchEngine

    cfg, _, weights, spec, _ = toy
    args = dict(slots=4, superstep=8, pipeline=True, paged_kv=True,
                kv_block_tokens=BT, prefix_cache=True, dtype=jnp.float32,
                tp=1)
    args.update(kw)
    return BatchEngine(spec, W.to_program_params(weights, cfg), None, **args)


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


def test_the_spec_carries_the_model_as_data(toy):
    cfg, _, _, spec, params = toy
    conv, full = spec.kinds
    assert (conv.name, conv.conv_kernel, full.name, full.conv_kernel) == (
        "conv", 3, "full", 0)
    assert spec.layer_kinds == (0, 1, 0, 0, 1) and spec.mixed
    assert spec.state_layers == (0, 2, 3) and spec.cache_layers == (1, 4)
    assert spec.state_rows == 2 and spec.qk_norm and spec.router_bias
    assert spec.router_score == RouterScore.SIGMOID and spec.lead_layers == 1
    assert full.rope_type == RopeType.FALCON and full.rope_theta == 1e6
    assert spec.state_block_bytes(4) == 3 * 2 * 128 * 4
    # TWO runs whatever the kinds: the scan's body picks the mixer
    assert [(r.name, r.first, r.depth, r.kind, r.lead) for r in spec.runs()] == [
        ("lead", 0, 1, None, True), ("blocks", 1, 4, None, False)]
    assert stack_names(params) == ["lead", "blocks"]
    # a mixer's tensors are as deep as its kind has layers in the run
    assert params["lead"]["conv_in"].shape == (1, 3 * 128, 128)
    assert "wq" not in params["lead"] and "w1" in params["lead"]
    assert params["blocks"]["conv_in"].shape == (2, 3 * 128, 128)
    assert params["blocks"]["conv_w"].shape == (2, 128, 3)
    assert params["blocks"]["wq"].shape == (2, 128, 128)
    assert params["blocks"]["rms_qh"].shape == (2, 32)
    assert params["blocks"]["router_bias"].shape == (4, 8)
    assert params["blocks"]["moe_up"].shape[:2] == (4, 8)
    shapes = run_tensor_shapes(spec, spec.runs()[1])
    assert {n: s[0] for n, (s, _) in shapes.items()} == {
        n: t.shape[0] for n, t in params["blocks"].items()}
    assert set(block_tensor_shapes(spec.of_kind(0))) >= {
        "conv_in", "conv_w", "conv_out"}
    assert "wq" not in block_tensor_shapes(spec.of_kind(0))


def test_the_published_file_gives_the_published_model():
    """Every width is the published one: 2048, 32 and 8 heads of 64, 7168, 32
    experts of 1792 of which 4, 24 layers in the published order, 65536."""
    cfg = cells.load_config("lfm2-8b-a1b")
    spec = cells.load_family("lfm2").model_spec(cfg)
    assert (spec.dim, spec.n_heads, spec.n_kv_heads, spec.head_size) == (
        2048, 32, 8, 64)
    assert (spec.lead_hidden_dim, spec.hidden_dim, spec.n_experts,
            spec.n_active_experts, spec.vocab_size) == (7168, 1792, 32, 4,
                                                        65536)
    assert spec.n_layers == 24 and spec.lead_layers == 2
    assert spec.cache_layers == (2, 6, 10, 14, 18, 21)
    assert len(spec.state_layers) == 18
    assert [r.depth for r in spec.runs()] == [2, 22]
    assert spec.attn_scale == 1 / 8
    # 12 KB of keys and values a token, 147 KB of snapshot a block
    assert len(spec.cache_layers) * spec.cache_row_bytes(2) == 12288
    assert spec.state_block_bytes(2) == 18 * 2 * 2048 * 2
    assert cfg["reduced"] == ["max_position_embeddings"]
    assert cfg["layer_types"].count("conv") == 18


def _mixed(**over):
    base = dict(arch_type=ArchType.MIXTRAL, dim=64, hidden_dim=32, n_layers=4,
                n_heads=4, n_kv_heads=2, vocab_size=64, seq_len=128,
                n_experts=4, n_active_experts=2, head_dim=16,
                rope_type=RopeType.FALCON, qk_norm=True, router_bias=True,
                router_score=RouterScore.SIGMOID,
                kinds=(LayerKind("conv", 4, conv_kernel=3),
                       LayerKind("full", 4)),
                layer_kinds=(0, 1, 0, 0))
    return ModelSpec(**{**base, **over})


@pytest.mark.parametrize("over,why", [
    (dict(), None),
    (dict(lead_layers=1, lead_hidden_dim=64), None),
    (dict(kinds=(LayerKind("conv", 4, conv_kernel=3),
                 LayerKind("conv4", 4, conv_kernel=4), LayerKind("full", 4)),
          layer_kinds=(0, 1, 2, 0)), "one state kind"),
    (dict(layer_kinds=(0, 0, 0, 0)), "an attention layer too"),
    (dict(attn_gate=True), "per-head gate"),
    (dict(layer_kinds=(0, 0, 0)), "layer_kinds"),
])
def test_resolved_holds_a_mixed_spec_to_what_the_program_runs(over, why):
    if why is None:
        assert _mixed(**over).resolved().mixed
    else:
        with pytest.raises(AssertionError, match=why):
            _mixed(**over).resolved()


def test_route_with_a_bias_picks_by_score_plus_bias_and_weighs_by_score():
    spec = _mixed(router_renorm=False).resolved()
    logits = jnp.asarray([[[2.0, 1.0, 0.0, -1.0]]])
    s = np.asarray(jax.nn.sigmoid(logits))[0, 0]
    top_i, w = F._route(logits, 2, spec)
    assert sorted(np.asarray(top_i)[0, 0].tolist()) == [0, 1]
    bias = jnp.asarray([-1.0, 0.0, 0.0, 1.0])
    top_i, w = F._route(logits, 2, spec, bias)
    got = dict(zip(np.asarray(top_i)[0, 0].tolist(),
                   np.asarray(w)[0, 0].tolist()))
    # expert 3 is lifted in and expert 0 pushed out; the weights are the
    # scores, the bias is not in them
    assert sorted(got) == [1, 3]
    assert got[1] == pytest.approx(s[1]) and got[3] == pytest.approx(s[3])
    renorm = dataclasses.replace(spec, router_renorm=True)
    _, w = F._route(logits, 2, renorm, bias)
    assert float(np.asarray(w).sum()) == pytest.approx(1.0)


def test_engine_prefill_and_decode_match_the_reference(toy):
    """`Engine` (one sequence, contiguous cache: the pool's own reference):
    chunks of 64, 8 and 1, then single steps."""
    from distributed_llama_tpu.runtime.engine import Engine

    cfg, fam, weights, spec, params = toy
    row = _prompt(90, 5)
    ref, _ = fam.logits_at(cfg, weights, [row], [range(len(row))])
    eng = Engine(spec, params, None, tp=1, dtype=jnp.float32,
                 use_pallas=False)
    assert isinstance(eng.v_cache, F.StateCache)
    assert eng.k_cache.shape[0] == 2 and eng.v_cache.snaps.shape[1] == 0
    got = [eng.infer_chunk_logits(row[:64]), eng.infer_chunk_logits(row[64:72]),
           *[eng.infer_chunk_logits([t]) for t in row[72:]]]
    np.testing.assert_allclose(np.concatenate(got), ref, atol=LOGITS_TOL,
                               rtol=0)
    # a short rewind finds its state in the ring; a long one says it cannot
    eng.seek(85)
    np.testing.assert_allclose(eng.infer_chunk_logits(row[85:]), ref[85:],
                               atol=LOGITS_TOL, rtol=0)
    with pytest.raises(ValueError, match="keeps no snapshot"):
        eng.seek(10)
    eng.seek(0)
    np.testing.assert_allclose(eng.infer_chunk_logits(row[:20]), ref[:20],
                               atol=LOGITS_TOL, rtol=0)


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "kernels"])
def test_batch_engine_chunked_prefill_and_decode_match_the_reference(
        toy, kernels):
    """Rows of 72 to 75 tokens and one of 140 through chunks of 64, 8 and 1
    into the paged pool, decode rows riding the long row's chunks, then T = 1
    steps: BatchEngine as the cell builds it (device pool, prefix cache on,
    pipelined, K = 8), with the kernels interpreted and without."""
    cfg, fam, weights, _, _ = toy
    be = _engine(toy, use_pallas=kernels)
    try:
        assert bool(be._eng.paged_kernel) == kernels
        rng = np.random.default_rng(11)
        probes = []
        for n in (72, 73, 74, 140):
            toks = rng.integers(3, cfg["vocab_size"], n + 6)
            probes.append((toks[:n].tolist(), toks[n:].tolist()))
        got = np.concatenate(probe.drive(be, probes))
        ref, _ = probe.reference_rows(cfg, weights, probes)
        np.testing.assert_allclose(got, ref, rtol=0,
                                   atol=KERNEL_TOL if kernels else LOGITS_TOL)
    finally:
        be.close()


def _state_of(vc, slot, pos, n):
    """What slot `slot` would continue from at `pos`: (2, n, dim)."""
    ring = np.asarray(vc.ring)
    w = ring.shape[1]
    return np.stack([ring[slot, (pos - j) % w, :n] for j in (2, 1)])


def test_a_k_step_scan_equals_k_single_steps_and_parks_rows(toy):
    """The scan's eight steps against eight T = 1 steps of the same program
    on copies of the same caches: the same tokens, the same keys, values,
    ring and snapshots; a row whose budget is 0 keeps its state bit for bit."""
    be = _engine(toy, prefix_cache=False)
    try:
        eng, spec = be._eng, be.spec
        n = len(spec.state_layers)
        for sl in be._slots:
            be.slot_cache.cover(sl, CONTEXT)
        tables = be.slot_cache.table()[0]
        hist = np.random.default_rng(3).integers(3, 512, size=(4, 27))
        step = eng._step_for(None)
        copy = lambda t: jax.tree.map(jnp.copy, t)  # noqa: E731
        logits, kc, vc, _ = step(eng.params, eng.rope, jnp.asarray(hist),
                                 copy(eng.k_cache), copy(eng.v_cache),
                                 jnp.zeros(4, jnp.int32), tables)
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
        # the same by single steps, each row advancing while it has budget
        kc_1, vc_1, tok, at = copy(kc), copy(vc), tok0.copy(), starts.copy()
        for i in range(8):
            logits, kc_1, vc_1, _ = step(eng.params, eng.rope,
                                         jnp.asarray(tok[:, None]), kc_1, vc_1,
                                         jnp.asarray(at), tables)
            nxt = np.argmax(np.asarray(logits)[:, 0], -1).astype(np.int32)
            live = i < budget
            np.testing.assert_array_equal(toks[i][live], nxt[live])
            tok = np.where(live, nxt, tok)
            at = at + live
        for b, p in enumerate([35, 35, 27, 32]):
            np.testing.assert_allclose(_state_of(vc_s, b, p, n),
                                       _state_of(vc_1, b, p, n), atol=1e-6)
        np.testing.assert_array_equal(_state_of(vc_s, 2, 27, n),
                                      _state_of(vc, 2, 27, n))
        # block 1 of rows 0 and 1 ended at position 31: snapshot = the state
        snaps = np.asarray(vc_s.snaps)[0]
        for b in (0, 1, 3):
            blk = int(be.slot_cache.tables_np[b, 1])
            want = _state_of(vc_s, b, 32, n).reshape(2 * n, -1)
            np.testing.assert_array_equal(snaps[blk, :2 * n], want)
        np.testing.assert_allclose(np.asarray(kc_s), np.asarray(kc_1),
                                   atol=1e-6)
    finally:
        be.close()


def test_a_parked_rows_state_is_untouched_by_a_mixed_dispatch(toy):
    """A prefill chunk with one rider and one parked row: the parked row's
    scratch write lands AT its frontier, so the two rows it would continue
    from are bit for bit what they were, and so is every finished block's
    snapshot."""
    be = _engine(toy, prefix_cache=False)
    try:
        eng, n = be._eng, len(be.spec.state_layers)
        for sl in be._slots:
            be.slot_cache.cover(sl, CONTEXT)
        tables = be.slot_cache.table()[0]
        step = eng._step_for(None)
        hist = np.random.default_rng(4).integers(3, 512, size=(4, 40))
        _, kc, vc, _ = step(eng.params, eng.rope, jnp.asarray(hist),
                            eng.k_cache, eng.v_cache,
                            jnp.zeros(4, jnp.int32), tables)
        before = jax.tree.map(np.asarray, vc)
        tokens = np.zeros((4, 64), np.int64)
        tokens[1] = np.random.default_rng(5).integers(3, 512, 64)  # the lead
        tokens[0, 0] = 17  # a rider; rows 2 and 3 are parked
        _, _, vc2, _ = step(eng.params, eng.rope, jnp.asarray(tokens), kc, vc,
                            jnp.asarray([40, 40, 40, 40, 1], jnp.int32),
                            tables)
        for b in (2, 3):
            np.testing.assert_array_equal(_state_of(vc2, b, 40, n),
                                          _state_of(before, b, 40, n))
        snaps0, snaps1 = before.snaps[0], np.asarray(vc2.snaps)[0]
        for b in range(4):  # blocks 0 and 1 of every row were finished
            for j in (0, 1):
                blk = int(be.slot_cache.tables_np[b, j])
                np.testing.assert_array_equal(snaps1[blk], snaps0[blk])
        # the lead crossed the block ends at 47, 63, 79, 95: four snapshots
        for j in (2, 3, 4, 5):
            blk = int(be.slot_cache.tables_np[1, j])
            assert np.abs(snaps1[blk, :2 * n]).max() > 0
            np.testing.assert_array_equal(
                snaps1[blk, :2 * n],
                _state_of(vc2, 1, 16 * (j + 1), n).reshape(2 * n, -1)
                if 16 * (j + 1) > 104 - F.STATE_RING else snaps1[blk, :2 * n])
    finally:
        be.close()


@pytest.mark.parametrize("pipeline", [True, False], ids=["chained", "plain"])
def test_greedy_requests_through_scans_give_the_references_tokens(
        toy, pipeline):
    """Four requests of different lengths through prefill and K-step scans:
    replies end mid-block and, chained, FLUSH the super-step in flight, whose
    survivors go on from the state they had before it; every row's tokens
    are the reference's argmax chain, pipelined or not."""
    be = _engine(toy, pipeline=pipeline)
    try:
        prompts = [_prompt(n, 20 + n) for n in (70, 33, 90, 17)]
        lens = [21, 9, 30, 14]
        reqs = [be.submit(p, n, Sampler(512, temperature=0.0))
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
    super-step already in flight is flushed, its writes to every surviving
    row's ring and snapshots lying at or past the row's frontier. The
    survivors' tokens are an unpipelined run's, and the reference's."""
    from distributed_llama_tpu.obs import metrics

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
            rolled = metrics.snapshot().get("batch_rollback_tokens_total", 0)
            flushed = flushes()
            reqs = [be.submit(p, 60, Sampler(512, temperature=0.0),
                              stop_check=stop_after(n))
                    for p, n in zip(prompts, stops)]
            outs[pipeline] = [r.wait(300) for r in reqs]
            assert metrics.snapshot()["batch_rollback_tokens_total"] > rolled
            if pipeline:
                assert flushes() > flushed
        finally:
            be.close()
    assert [len(o) for o in outs[True]] == stops
    assert outs[True] == outs[False]
    assert outs[True][0] == _greedy(toy, prompts[0], stops[0])


def test_a_prefix_hit_and_a_slot_rewind_equal_a_cold_prefill(toy):
    """The same long prompt three times: cold; then on the SAME slot (a
    rewind: it lands on the last block end below the prompt's end and seeds
    the ring from that block's snapshot); then, with the first slot busy, on
    ANOTHER slot (a directory hit: a remap, and the same seed). All three
    give the reference's tokens, and the restores are counted."""
    from distributed_llama_tpu.obs import metrics

    be = _engine(toy, slots=2)
    try:
        prompt = _prompt(100, 77)
        want = _greedy(toy, prompt, 10)
        count = lambda: metrics.snapshot().get(  # noqa: E731
            "paged_kv_state_restores_total", 0)
        c0 = count()
        cold = be.submit(prompt, 10, Sampler(512, temperature=0.0))
        assert cold.wait(300) == want and count() == c0
        again = be.submit(prompt, 10, Sampler(512, temperature=0.0))
        assert again.wait(300) == want
        assert again.stats.reused_tokens == 96  # 6 blocks, not 99 tokens
        assert count() == c0 + 1
        # a request that keeps slot 0 busy, then the prompt once more
        busy = be.submit(prompt[:50] + _prompt(30, 78), 40,
                         Sampler(512, temperature=0.0))
        hit = be.submit(prompt + [5, 6, 7], 6, Sampler(512, temperature=0.0))
        assert hit.wait(300) == _greedy(toy, prompt + [5, 6, 7], 6)
        busy.wait(300)
        assert hit.stats.reused_tokens == 96
        assert be.prefix_cache.stats()["hit_tokens"] >= 96
    finally:
        be.close()


def test_a_demoted_and_promoted_block_brings_its_snapshot_back(toy):
    """A directory block demoted to the host tier carries its state snapshot
    beside its keys and values, and a hit on it promotes all three: the
    tokens are the cold prefill's."""
    be = _engine(toy, slots=2, kv_pool_blocks=40)
    try:
        prompt = _prompt(100, 91)
        want = _greedy(toy, prompt, 8)
        assert be.submit(prompt, 8, Sampler(512, temperature=0.0)
                         ).wait(300) == want
        pc = be.prefix_cache
        assert pc.stats()["dev_blocks"] >= 6
        snaps = np.asarray(be._eng.v_cache.snaps)[0].copy()
        # drop the slots' own tables, then demote every directory block
        for sl in be._slots:
            be.slot_cache.release(sl)
        be.slot_cache.demote(pc.stats()["dev_blocks"])
        be.slot_cache.settle(force=True)
        st = pc.stats()
        assert st["dev_blocks"] == 0 and st["cold_blocks"] >= 6
        # the host tier holds the typed payload: (k, v, state)
        node = pc.radix.match(prompt)[0]
        rows = pc.fetch_cold(node.handle[1])
        assert len(rows) == 3 and rows[2].shape == (1, *snaps.shape[1:])
        assert np.abs(rows[2]).max() > 0
        # scribble over the whole device side: a hit must not depend on it
        eng = be._eng
        eng.v_cache = eng.v_cache._replace(
            snaps=jnp.full_like(eng.v_cache.snaps, 7.0),
            ring=jnp.full_like(eng.v_cache.ring, 7.0))
        hit = be.submit(prompt, 8, Sampler(512, temperature=0.0))
        assert hit.wait(300) == want
        assert hit.stats.reused_tokens == 96
        assert pc.stats()["promoted_blocks"] >= 6
    finally:
        be.close()


def test_close_and_a_pool_reclaim_free_both_kinds_together(toy):
    """A block's snapshot lives at the block's id: nothing of the second
    kind of state is allocated or freed apart from the block."""
    be = _engine(toy, slots=2, kv_pool_blocks=40)
    try:
        be.submit(_prompt(100, 93), 4, Sampler(512, temperature=0.0)).wait(300)
        assert be._eng.v_cache.snaps.shape[1] == be.kv_pool.n_blocks
        assert be.slot_cache.block_bytes == sum(
            c.nbytes // c.shape[1]
            for c in (be._eng.k_cache, be._eng.v_cache.rows,
                      be._eng.v_cache.snaps))
        used = be.kv_pool.used_blocks()
        assert used > 0
        for sl in be._slots:
            be.slot_cache.release(sl)
        be.prefix_cache.reclaim(used, lambda bid: (_ for _ in ()).throw(
            RuntimeError("evict")))
        assert be.kv_pool.used_blocks() == 0
    finally:
        be.close()


def test_the_state_counters_of_one_dispatch(toy):
    from distributed_llama_tpu.obs import metrics

    be = _engine(toy, prefix_cache=False)
    try:
        names = ("batch_state_rows_advanced_total",
                 "batch_state_snapshots_total", "batch_block_ends_total",
                 "batch_state_bytes_written_total")
        before = metrics.snapshot()
        # a chunk of 64 from position 8 (block ends 15, 31, 47, 63) and two
        # riders at 30 and 31 (31 ends a block): 66 real positions, 5 ends
        be._count_work(64, 256, [(8, 64), (30, 1), (31, 1)], [8, 30, 31, 0],
                       lead=0)
        after = metrics.snapshot()
        d = [after[k] - before.get(k, 0) for k in names]
        assert d[:3] == [3 * 66, 3 * 5, 5]
        assert d[3] == 3 * 128 * 4 * (72 + 2 * 5)
        assert after["kv_pool_state_block_bytes"] == 3 * 2 * 128 * 4
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
    from distributed_llama_tpu.resilience.errors import InvalidRequest
    from distributed_llama_tpu.runtime.engine import Engine

    _, _, _, spec, params = toy
    with pytest.raises(ValueError, match="sequence-sharded .* does not "
                                         "support layers that hold a state"):
        Engine(spec, params, None, tp=1, sp=2)
    be = _engine(toy)
    try:
        with pytest.raises(InvalidRequest, match="KV-block streaming"):
            be.submit([1, 2, 3], 2, Sampler(512, temperature=0.0),
                      export_kv=True)
        with pytest.raises(ValueError, match="KV-block streaming"):
            be.import_kv_blocks(list(range(16)), [(None, None)])
    finally:
        be.close()


def test_a_model_file_round_trip_of_the_new_header_keys_and_tensors(
        toy, tmp_path):
    """The repo's writer, then its loader: the same spec (the convolution
    kind, QK-norm, the selection bias), the same two stacks with each
    mixer's tensors over its own layers, and `Engine` on the file gives the
    reference's logits."""
    from distributed_llama_tpu.runtime.engine import Engine

    cfg, fam, weights, spec, params = toy
    path = str(tmp_path / "lfm2.m")
    write_model(path, spec, params_file_order(spec, params, as_stored=True),
                FloatType.Q40)
    spec2, wft, _ = read_spec(path)
    assert wft == FloatType.Q40
    named = dataclasses.replace(
        spec2, orig_seq_len=spec.orig_seq_len, kinds=tuple(
            dataclasses.replace(k, name=o.name)
            for k, o in zip(spec2.kinds, spec.kinds)))
    assert named == spec
    assert spec2.kinds[0].conv_kernel == 3 and spec2.qk_norm
    assert spec2.router_bias
    _, loaded = load_model(path)
    assert stack_names(loaded) == stack_names(params)
    for st in stack_names(params):
        assert set(loaded[st]) == set(params[st])
        for name, t in params[st].items():
            a, b = loaded[st][name], t
            np.testing.assert_array_equal(
                a.to_numpy() if hasattr(a, "to_numpy") else np.asarray(a),
                b.to_numpy() if hasattr(b, "to_numpy") else np.asarray(b))
    row = _prompt(50, 8)
    ref, _ = fam.logits_at(cfg, weights, [row], [range(len(row))])
    eng = Engine(spec2, loaded, None, tp=1, dtype=jnp.float32,
                 use_pallas=False)
    logits = eng.prefill(row[:41])
    np.testing.assert_allclose(np.asarray(logits).reshape(-1), ref[40],
                               atol=LOGITS_TOL, rtol=0)
    np.testing.assert_allclose(eng.infer_chunk_logits(row[41:]), ref[41:],
                               atol=LOGITS_TOL, rtol=0)


def test_random_params_of_a_mixed_spec_run(toy):
    """`init_random_params` draws a mixed spec's stacks by `run_tensor_shapes`
    and forward() runs them: taps and a bias that do something."""
    spec = _mixed(lead_layers=1, lead_hidden_dim=64).resolved()
    params = init_random_params(spec, FloatType.F32, seed=3)
    assert params["blocks"]["conv_in"].shape[0] == 2
    assert params["blocks"]["wq"].shape[0] == 1
    rope = RopeTables.create(spec)
    kc, vc = F.init_kv_cache(spec)
    row = jnp.asarray([_prompt(20, 1)]) % 64
    one, _, _ = F.forward(params, spec, rope, row, kc, vc, jnp.int32(0))
    a, kc, vc = F.forward(params, spec, rope, row[:, :13], kc, vc,
                          jnp.int32(0))
    b, _, _ = F.forward(params, spec, rope, row[:, 13:], kc, vc,
                        jnp.int32(13))
    np.testing.assert_allclose(np.concatenate([a, b], axis=1), one,
                               atol=1e-5, rtol=1e-5)
