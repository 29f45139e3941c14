"""Laguna-S-2.1's block graph at the toy size, against its plain reference.

What the model forces is data on `ModelSpec`: two KINDS of attention layer
(`ModelSpec.kinds`: sliding layers of 18 query heads behind a window of 8, all
of a head rotated; full layers of 12, half a head rotated with YaRN's
frequencies and a factor on the tables), each run of like layers a stack and a
scan of its own behind a leading dense layer, a per-head sigmoid gate on the
attention output, 4 of 16 softmax-routed experts beside a shared one. The
reference is the benchmark's own (`benchmark/families/laguna.py`): plain
float32, the whole sequence at once, no cache. Everything here compares LOGITS
of prefill plus cached decode with that full forward pass, on rows whose
positions lie several windows past the toy's window of 8.

Tolerances. LOGITS_TOL 2e-4 (absolute, logits of rms about 0.3): both sides
are float32; the program splits the softmax differently (the paged kernel's
online softmax) and sums the experts in another order, which reads 1e-6 here.
The same reference computed in bfloat16, or with the window, the gate or the
full layers' rotation ignored, reads above 1e-3 (asserted below), so the
tolerance tells the model from each model that leaves a mechanism out.
KERNEL_TOL 2e-3 where the engine runs the Q40 kernels: they hand the MXU bf16
operands (float32 accumulation); the reference in fp8, the precision under
that, reads above 1e-2 (asserted below).
"""

import dataclasses
import math

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from benchmark import cells, probe
from benchmark import weights as W
from distributed_llama_tpu.formats.mfile import (load_model, params_file_order,
                                                 read_spec, write_model)
from distributed_llama_tpu.models.forward import forward, init_kv_cache
from distributed_llama_tpu.models.params import (block_tensor_shapes,
                                                 stack_names)
from distributed_llama_tpu.models.spec import (ArchType, LayerKind, ModelSpec,
                                               RopeType, RouterScore)
from distributed_llama_tpu.ops.pallas_paged_attention import (
    paged_attention, paged_attention_xla, visited_keys)
from distributed_llama_tpu.ops.rope import RopeTables, apply_rope
from distributed_llama_tpu.quants import FloatType

SEED = 2**31 + 13
LOGITS_TOL = 2e-4
KERNEL_TOL = 2e-3
PROMPT, DECODE = 41, 11  # 52 positions: over six windows of 8


@pytest.fixture(scope="module")
def toy():
    cfg = cells.load_config("tiny-laguna")
    fam = cells.load_family("laguna")
    weights = W.make_weights(cfg, SEED)
    return (cfg, fam, weights, fam.model_spec(cfg),
            W.to_program_params(weights, cfg))


@pytest.fixture(scope="module")
def sequence(toy):
    cfg, fam, weights, _, _ = toy
    row = np.random.default_rng(5).integers(3, cfg["vocab_size"],
                                            PROMPT + DECODE).tolist()
    ref, _ = fam.logits_at(cfg, weights, [row], [range(len(row))])
    return row, ref


def test_the_spec_carries_the_model_as_data(toy):
    cfg, _, _, spec, params = toy
    assert [k.name for k in spec.kinds] == ["full", "slide"]
    full, slide = spec.kinds
    assert (full.n_heads, slide.n_heads) == (12, 18)
    assert (full.sliding_window, slide.sliding_window) == (0, 8)
    assert full.rope_type == RopeType.YARN_NEOX and full.rotary_dim == 16
    assert slide.rope_type == RopeType.FALCON and slide.rotary_dim == 32
    assert spec.layer_kinds == (0, 1, 1, 1, 0)
    assert spec.layer_window() == (0, 8, 8, 8, 0)
    assert spec.attn_gate and spec.lead_layers == 1
    assert spec.router_score == RouterScore.SOFTMAX and spec.router_scale == 2.5
    assert PROMPT + DECODE >= 6 * cfg["sliding_window"]
    # a run of like layers a stack: the leading dense layer, three sliding
    # layers, the full layer; each at its own head count, none padded
    assert [(r.name, r.first, r.depth, r.lead) for r in spec.runs()] == [
        ("lead", 0, 1, True), ("blocks", 1, 3, False), ("blocks1", 4, 1, False)]
    assert stack_names(params) == ["lead", "blocks", "blocks1"]
    assert params["lead"]["wq"].shape == (1, 12 * 32, 128)
    assert params["blocks"]["wq"].shape == (3, 18 * 32, 128)
    assert params["blocks"]["wo"].shape == (3, 128, 18 * 32)
    assert params["blocks"]["wg"].shape == (3, 18, 128)
    assert params["blocks1"]["wg"].shape == (1, 12, 128)
    assert "w1" in params["lead"] and "router" not in params["lead"]
    assert params["blocks1"]["moe_up"].shape[:2] == (1, 16)
    # the kind's spec is what a layer's tensors take their shapes from
    assert block_tensor_shapes(spec.of_kind(1))["wq"][0] == (18 * 32, 128)
    with pytest.raises(AssertionError, match="of_kind"):
        block_tensor_shapes(spec)


def test_the_published_file_gives_the_published_kinds():
    """At the published widths: 72 and 48 heads of 128 over 8 kv heads (groups
    of 9 and 6), a window of 512, 64 of a full layer's 128 values rotated,
    256 experts of which every one is held."""
    spec = cells.load_family("laguna").model_spec(
        cells.load_config("laguna-s-2.1-l5"))
    full, slide = spec.kinds
    assert (slide.n_heads, full.n_heads, spec.n_kv_heads) == (72, 48, 8)
    assert spec.of_kind(1).q_group == 9 and spec.of_kind(0).q_group == 6
    assert (slide.sliding_window, full.rotary_dim, slide.rotary_dim) == (
        512, 64, 128)
    assert full.rope_table_scale == pytest.approx(0.1 * math.log(128) + 1)
    assert (spec.n_experts, spec.n_router, spec.n_active_experts) == (
        256, 256, 10)
    assert spec.cache_row_bytes(2) == 4096
    assert [(r.depth, r.kind) for r in spec.runs()] == [(1, 0), (3, 1), (1, 0)]


def _kinds(**over):
    base = dict(arch_type=ArchType.MIXTRAL, dim=64, hidden_dim=32, n_layers=3,
                n_heads=4, n_kv_heads=2, vocab_size=64, seq_len=32,
                n_experts=4, n_active_experts=2, head_dim=32,
                kinds=(LayerKind("full", 4), LayerKind("slide", 6, 8)),
                layer_kinds=(0, 1, 0))
    return ModelSpec(**{**base, **over})


@pytest.mark.parametrize("over,why", [
    (dict(), None),
    (dict(lead_layers=1, lead_hidden_dim=64), None),  # a leading stack too
    (dict(layer_kinds=(0, 1)), "layer_kinds"),
    (dict(layer_kinds=(0, 2, 0)), "layer_kinds"),
    (dict(sliding_window=8), "kinds of layer state their own"),
    (dict(rope_layers=(1, 0, 1)), "kinds of layer state their own"),
    (dict(head_dim=0), "head size"),
    (dict(kinds=(LayerKind("full", 4), LayerKind("slide", 5, 8))), "LayerKind"),
    (dict(kinds=(LayerKind("full", 4, rotary_dim=33),
                 LayerKind("slide", 6, 8))), "LayerKind"),
    (dict(kinds=(), layer_kinds=(0, 0, 0)), "layer_kinds without kinds"),
    (dict(kv_lora_rank=16, n_kv_heads=1), "kinds|latent"),
    # the 0/1 switches of ONE stack still do not ride behind a leading stack
    (dict(kinds=(), layer_kinds=(), sliding_window=8, lead_layers=1,
          lead_hidden_dim=64), "two kinds stand in one stack"),
], ids=["kinds", "lead+kinds", "short", "unknown-kind", "window-beside-kinds",
        "switches-beside-kinds", "no-head-size", "heads-not-a-group",
        "odd-rotary-width", "layer-kinds-alone", "latent", "switches-behind-lead"])
def test_resolved_takes_kinds_and_refuses_what_it_cannot_run(over, why):
    if why is None:
        spec = _kinds(**over).resolved()
        assert len(spec.runs()) == 3 and spec.layer_window() == (0, 8, 0)
    else:
        with pytest.raises(AssertionError, match=why):
            _kinds(**over).resolved()


def test_rotation_tables_against_a_float64_reckoning():
    """The published file's two rotations in closed form. Full layers: 32
    pairs over a rotary width of 64, theta 500000, factor 128 over 8192, betas
    32 and 1: the correction range is 64 ln(8192 / (beta 2 pi)) / (2 ln
    500000) = 9.04 and 17.49, so pairs 0 to 9 keep their frequency, pairs 18 to
    31 are divided by 128, the ramp runs (i - 9) / 9 between; cos and sin times
    1.4852030263919618. Sliding layers: 64 pairs, theta 10000, no scaling."""
    cfg = cells.load_config("laguna-s-2.1-l5")
    fam = cells.load_family("laguna")
    spec = dataclasses.replace(fam.model_spec(cfg), seq_len=2048)
    f = np.asarray([500000.0 ** (-i / 32) for i in range(32)])
    assert 64 * math.log(8192 / (32 * 2 * math.pi)) / (
        2 * math.log(500000)) == pytest.approx(9.04, abs=0.01)
    assert 64 * math.log(8192 / (2 * math.pi)) / (
        2 * math.log(500000)) == pytest.approx(17.49, abs=0.01)
    ramp = np.clip((np.arange(32) - 9) / 9, 0, 1)
    want = f / 128 * ramp + f * (1 - ramp)
    assert want[5] == f[5] and want[20] == f[20] / 128
    assert want[12] == pytest.approx(f[12] * (1 / 128 / 3 + 2 / 3))
    got, factor = fam.inv_freq(cfg["rope_parameters"]["full_attention"], 128)
    np.testing.assert_allclose(got, want, rtol=1e-12)
    assert factor == 1.4852030263919618
    tables = RopeTables.create(spec)
    assert tables.cos.shape == (2, 2048, 64)  # a table a kind, the widest wide
    full, slide = tables.of_kind(spec, 0), tables.of_kind(spec, 1)
    assert full.cos.shape == (2048, 32) and slide.cos.shape == (2048, 64)
    assert full.rope_type == RopeType.YARN_NEOX
    for pos in (1, 700, 2047):
        np.testing.assert_allclose(np.asarray(full.cos[pos]),
                                   np.cos(pos * want) * factor, atol=2e-6)
        np.testing.assert_allclose(np.asarray(full.sin[pos]),
                                   np.sin(pos * want) * factor, atol=2e-6)
        plain = 10000.0 ** (-np.arange(64) / 64)
        np.testing.assert_allclose(np.asarray(slide.sin[pos]),
                                   np.sin(pos * plain), atol=2e-6)


def test_a_narrow_table_rotates_the_first_values_and_passes_the_rest():
    """Half-split pairs (j, j + r/2) within the first r values, by hand."""
    freqs = np.asarray([0.5, 0.25])
    pos = 3
    tables = RopeTables(jnp.asarray(np.cos(np.outer(np.arange(8), freqs)),
                                    jnp.float32),
                        jnp.asarray(np.sin(np.outer(np.arange(8), freqs)),
                                    jnp.float32), RopeType.FALCON)
    x = np.arange(1.0, 9.0, dtype=np.float32)  # one head of 8, r = 4
    got = np.asarray(apply_rope(jnp.asarray(x)[None, None, :], tables,
                                jnp.asarray([pos])))[0, 0]
    c, s = np.cos(pos * freqs), np.sin(pos * freqs)
    want = np.concatenate([x[0:2] * c - x[2:4] * s, x[0:2] * s + x[2:4] * c,
                           x[4:]])
    np.testing.assert_allclose(got, want, rtol=1e-6)


def _paged_cache(spec, bt=8):
    """A pool and one row's block table: block 0 is scratch."""
    w = spec.seq_len // bt
    return (jnp.zeros((spec.n_layers, w + 1, spec.n_kv_heads, bt,
                       spec.head_size), jnp.float32),
            jnp.zeros((spec.n_layers, w + 1, spec.n_kv_heads, bt,
                       spec.head_size), jnp.float32),
            jnp.arange(1, w + 1, dtype=jnp.int32)[None], bt)


def _chunks_then_decode(spec, params, row, path, chunks):
    """Logits of `row` through forward(): the prompt in `chunks`, then one
    token at a time, through the cache kind `path`."""
    rope = RopeTables.create(spec)
    kw, pos = {}, (lambda p: jnp.int32(p))
    if path.startswith("paged"):
        kc, vc, tables, bt = _paged_cache(spec)
        kw = dict(block_tables=tables, block_tokens=bt,
                  paged_kernel=path == "paged-kernel")
        pos = lambda p: jnp.asarray([p], jnp.int32)  # noqa: E731
    else:
        kc, vc = init_kv_cache(spec)
        if path == "dense-window":
            kw = dict(attn_window=spec.seq_len // 2)
    got, p = [], 0
    step = jax.jit(forward, static_argnums=(1,), static_argnames=(
        "block_tokens", "paged_kernel", "attn_window"))
    for n in chunks + (1,) * (len(row) - sum(chunks)):
        logits, kc, vc = step(params, spec, rope,
                              jnp.asarray([row[p:p + n]]), kc, vc, pos(p),
                              **kw)
        got.append(np.asarray(logits)[0])
        p += n
    return np.concatenate(got)


@pytest.mark.parametrize("path", ["dense", "dense-window", "paged-gather",
                                  "paged-kernel"])
def test_prefill_then_cached_decode_matches_the_full_forward_pass(
        toy, sequence, path):
    """Three stacks and two rotations through every cache kind the model
    has, against the reference's one pass: chunks of 16 and 25 (two and three
    windows each), then T = 1 from position 41 on, five windows in."""
    _, _, _, spec, params = toy
    row, ref = sequence
    got = _chunks_then_decode(spec, params, row, path, (16, PROMPT - 16))
    np.testing.assert_allclose(got, ref, atol=LOGITS_TOL, rtol=0)


@pytest.mark.parametrize("control", ["window_off", "gate_off", "one_rope",
                                     "bfloat16", "q80"])
def test_a_model_without_a_mechanism_fails_the_tolerance(toy, sequence,
                                                         control):
    """So that the test above could fail: the reference with the window, the
    gate or the full layers' own rotation left out, or in a lower precision,
    is further from itself than the program may be."""
    cfg, fam, weights, _, _ = toy
    row, ref = sequence
    other, _ = fam.logits_at(cfg, weights, [row], [range(len(row))], control)
    assert np.max(np.abs(other - ref)) > 5 * LOGITS_TOL
    if control == "bfloat16":
        fp8, _ = fam.logits_at(cfg, weights, [row], [range(len(row))], "fp8")
        assert np.max(np.abs(fp8 - ref)) > 5 * KERNEL_TOL


@pytest.mark.parametrize("cut", [[0], [3, 4]],
                         ids=["dense-full-layer", "slide-and-full-expert"])
def test_the_check_s_cuts_read_by_depth(toy, sequence, cut):
    """The two shallow cuts of the output check, which the harness hands the
    family as a depth: 1 is the leading layer (a dense model of one kind), 2
    the last sliding and the last full layer (two stacks of one)."""
    cfg, fam, weights, _, _ = toy
    row, _ = sequence
    w = W.layer_cut(weights, cut, cfg)
    spec = fam.model_spec({**cfg, "num_hidden_layers": len(cut)})
    params = W.to_program_params(w, cfg)
    if cut == [0]:
        assert spec.arch_type == ArchType.LLAMA and not spec.is_moe
        assert stack_names(params) == ["blocks"] and "w1" in params["blocks"]
        assert spec.layer_window() == (0,)
    else:
        assert stack_names(params) == ["blocks", "blocks1"]
        assert spec.layer_window() == (8, 0) and spec.lead_layers == 0
    ref, _ = fam.logits_at(cfg, w, [row], [range(len(row))])
    got = _chunks_then_decode(spec, params, row, "paged-gather", (24,))
    np.testing.assert_allclose(got, ref, atol=LOGITS_TOL, rtol=0)


@pytest.mark.parametrize("t", [1, 8, 64])
@pytest.mark.parametrize("g,window", [(6, 0), (9, 40), (9, 200), (6, 130)])
def test_the_paged_kernel_at_groups_of_6_and_9_with_a_window(g, window, t):
    """The kernel (interpreted) against its XLA twin at the model's groups,
    rows whose lengths straddle the window's lower bound: behind a window of
    200 a row of 420 keys skips its first step of 128, behind 130 its first
    two; a row shorter than the window skips nothing."""
    rng = np.random.default_rng(g * 1000 + window + t)
    layers, hk, hs, bt, layer = 2, 2, 32, 16, 1
    lens = [0, 5, 127, 300, 420, 511]
    nb = 36  # 576 keys: four steps of 128 and a short one
    b, n = len(lens), len(lens) * nb + 1
    mk = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    kc, vc = mk(layers, n, hk, bt, hs), mk(layers, n, hk, bt, hs)
    tables = jnp.asarray(rng.permutation(np.arange(1, n)).reshape(b, nb),
                         jnp.int32)
    q, kn, vn = mk(b, t, hk * g, hs), mk(b, hk, t, hs), mk(b, hk, t, hs)
    lengths = jnp.asarray(lens, jnp.int32)
    out = paged_attention(q, kc, vc, kn, vn, tables, lengths, layer, n_read=nb,
                          interpret=True,
                          window=jnp.int32(window) if window else None,
                          name="paged_attn_window" if window else None)
    ref = paged_attention_xla(q, kc, vc, kn, vn, tables, lengths, layer,
                              n_read=nb, window=window)
    assert np.isfinite(np.asarray(out)).all()
    assert np.abs(np.asarray(out) - np.asarray(ref)).max() < 2e-5
    if window == 200:  # what the counters say of these rows
        assert visited_keys(420, nb, bt, 420 - 200 + 1) == 512 - 128
        assert visited_keys(300, nb, bt, 300 - 200 + 1) == 384


@pytest.fixture(scope="module")
def engine(toy):
    """BatchEngine as the cell builds it, on the kernels (interpret mode):
    the paged-attention kernel by kind and the grouped Q40 kernels."""
    from distributed_llama_tpu.runtime.batch_engine import BatchEngine

    cfg, _, weights, spec, _ = toy
    be = BatchEngine(spec, W.to_program_params(weights, cfg), None, slots=4,
                     superstep=4, paged_kv=True, kv_block_tokens=16,
                     prefix_cache=True, use_pallas=True, dtype=jnp.float32,
                     tp=1)
    assert be._eng.paged_kernel and be._eng.moe_stats
    yield be
    be.close()


def test_batch_engine_chunked_prefill_and_decode_match_the_reference(
        toy, engine):
    """Rows of 72 to 75 tokens and one of 300 through chunks of 64, 8 and 1
    into the paged pool, decode rows riding the long row's chunks, then T = 1
    steps: every position past the window, the long row's two 128-key steps
    behind it skipped by the sliding layers' kernel."""
    cfg, fam, weights, _, _ = toy
    rng = np.random.default_rng(11)
    probes = []
    for n in (72, 73, 74, 300):
        toks = rng.integers(3, cfg["vocab_size"], n + 6)
        probes.append((toks[:n].tolist(), toks[n:].tolist()))
    got = np.concatenate(probe.drive(engine, probes))
    ref, _ = probe.reference_rows(cfg, weights, probes)
    np.testing.assert_allclose(got, ref, atol=KERNEL_TOL, rtol=0)
    off, _ = probe.reference_rows(cfg, weights, probes, "window_off")
    assert np.max(np.abs(off - ref)) > 5 * KERNEL_TOL


def test_batch_engine_scan_tokens_and_counters(toy, engine):
    """A greedy request through prefill and K-step scans: the tokens are the
    reference's argmax chain; the window layers' counters move, the visited
    one by less than the unwindowed one once a row is a step past the
    window."""
    from distributed_llama_tpu.obs import metrics
    from distributed_llama_tpu.runtime.sampler import Sampler

    cfg, fam, weights, spec, _ = toy
    prompt = np.random.default_rng(13).integers(3, cfg["vocab_size"],
                                                150).tolist()
    before = metrics.snapshot()
    out, _ = engine.generate(prompt, 6,
                             Sampler(spec.vocab_size, temperature=0.0))
    after = metrics.snapshot()
    seq = list(prompt)
    for tok in out:
        ref, _ = fam.logits_at(cfg, weights, [seq], [[len(seq) - 1]])
        assert int(np.argmax(ref[0])) == tok
        seq.append(tok)
    moved = {k: after[k] - before.get(k, 0) for k in after
             if k.startswith("batch_") and not isinstance(after[k], dict)}
    positions = moved["batch_positions_dispatched_total"]
    assert moved["batch_moe_routed_total"] == (
        positions * spec.n_active_experts * spec.block_layers)
    seen = moved["batch_attn_window_pairs_visited_total"]
    bare = moved["batch_attn_window_pairs_unwindowed_total"]
    assert 0 < seen < bare
    assert after["batch_attn_heads"] == {'{kind="window"}': 18.0,
                                         '{kind="full"}': 12.0}


def test_the_window_counters_arithmetic(toy, engine):
    """One T = 1 dispatch of four rows at lengths 300, 10, 0 and 200 in a
    bucket of 512 keys (32 blocks of 16, steps of 128): the three sliding
    layers visit, behind a window of 8, the step that holds the row's last
    key alone (128, 128, 0, 128) where they would have visited every step up
    to it (384, 128, 0, 256); the averaged pair counts both kinds of layer."""
    from distributed_llama_tpu.obs import metrics

    _, _, _, spec, _ = toy
    lens = [300, 10, 0, 200]
    before = metrics.snapshot()
    engine._count_work(1, 512, [], lens)
    after = metrics.snapshot()
    d = {k: after[k] - before.get(k, 0) for k in (
        "batch_attn_window_pairs_visited_total",
        "batch_attn_window_pairs_unwindowed_total",
        "batch_attn_pairs_visited_total")}
    assert d["batch_attn_window_pairs_visited_total"] == 3 * (128 + 128 + 0 + 128)
    assert d["batch_attn_window_pairs_unwindowed_total"] == 3 * (384 + 128 + 0 + 256)
    # three layers of five behind the window, two read everything
    assert d["batch_attn_pairs_visited_total"] == pytest.approx(
        0.6 * 384 + 0.4 * 768)
    # a K-step scan of 2 steps: the first row's budget of 1 stops it growing
    before = after
    engine._count_work(2, 512, [], [127, 127], budget=[2, 1])
    after = metrics.snapshot()
    grown = (after["batch_attn_window_pairs_unwindowed_total"]
             - before["batch_attn_window_pairs_unwindowed_total"])
    # lengths 127, 128 (row 0) and 127, 128 (row 1: min(i, 1)): a step each
    assert grown == 3 * 4 * 128
    assert visited_keys(300, 32, 16, 300 - 8 + 1) == 128


def test_a_model_file_round_trip_of_the_new_header_keys(toy, sequence,
                                                        tmp_path):
    """The repo's writer, then its loader: the same spec (the kinds with
    every field, each layer's kind, the gate, the rotary widths), the same
    three stacks, and the single-sequence engine (`apps/dllama.py`'s) on the
    file gives the reference's logits through its contiguous cache."""
    from distributed_llama_tpu.runtime.engine import Engine

    cfg, fam, weights, spec, params = toy
    path = str(tmp_path / "laguna.m")
    write_model(path, spec, params_file_order(spec, params, as_stored=True),
                FloatType.Q40)
    spec2, wft, _ = read_spec(path)
    assert wft == FloatType.Q40
    # a header holds integers: a kind's name is not stored, and a factor
    # rides in millionths
    named = dataclasses.replace(spec2, orig_seq_len=spec.orig_seq_len, kinds=tuple(
        dataclasses.replace(k, name=o.name, rope_table_scale=o.rope_table_scale)
        for k, o in zip(spec2.kinds, spec.kinds)))
    assert named == spec
    assert spec2.kinds[0].rope_table_scale == pytest.approx(
        spec.kinds[0].rope_table_scale, abs=1e-6)
    assert [k.name for k in spec2.kinds] == ["kind0", "kind1"]
    _, loaded = load_model(path)
    assert stack_names(loaded) == stack_names(params)
    for st in stack_names(params):
        assert set(loaded[st]) == set(params[st])
        for name, t in params[st].items():
            a, b = loaded[st][name], t
            np.testing.assert_array_equal(
                a.to_numpy() if hasattr(a, "to_numpy") else np.asarray(a),
                b.to_numpy() if hasattr(b, "to_numpy") else np.asarray(b))
    row, ref = sequence
    eng = Engine(spec2, loaded, None, tp=1, dtype=jnp.float32,
                 use_pallas=False)
    logits = eng.prefill(row[:PROMPT])  # chunks of 64 / 8 / 1: contiguous cache
    np.testing.assert_allclose(np.asarray(logits).reshape(-1),
                               ref[PROMPT - 1], atol=LOGITS_TOL, rtol=0)
    nxt = eng.infer_chunk_logits(row[PROMPT:PROMPT + 2])
    np.testing.assert_allclose(nxt, ref[PROMPT:PROMPT + 2], atol=LOGITS_TOL,
                               rtol=0)


def test_a_file_without_the_new_keys_reads_as_before(tmp_path):
    """A model of one kind writes none of the new keys."""
    from distributed_llama_tpu.formats.mfile import write_header
    from distributed_llama_tpu.models.spec import HeaderKey

    plain = cells.load_family("mistral").model_spec(
        cells.load_config("tiny-dense"))
    path = tmp_path / "h.m"
    with open(path, "wb") as f:
        write_header(f, plain, FloatType.Q40)
    ints = np.frombuffer(path.read_bytes()[8:], "<i4")
    assert not set(ints[::2].tolist()) & {
        int(HeaderKey.N_KINDS), int(HeaderKey.ATTN_GATE),
        int(HeaderKey.ROTARY_DIM), int(HeaderKey.ROPE_TABLE_SCALE_E6)}


def test_tp2_equals_tp1(toy, sequence):
    """Each kind's heads, their gates, the FFNs' hidden axes and the
    vocabulary sliced over two shards."""
    from distributed_llama_tpu.parallel.mesh import make_mesh
    from distributed_llama_tpu.parallel.tp import (init_sharded_kv_cache,
                                                   make_sharded_forward,
                                                   shard_params)

    _, _, _, spec, params = toy
    row, ref = sequence
    rope = RopeTables.create(spec)
    mesh = make_mesh(tp=2)
    sharded = shard_params(params, mesh, spec)
    step = make_sharded_forward(spec, mesh, sharded, donate_cache=False)
    kc, vc = init_sharded_kv_cache(spec, mesh)
    got0, kc, vc = step(sharded, rope, jnp.asarray([row[:PROMPT]]), kc, vc,
                        jnp.int32(0))
    got1, _, _ = step(sharded, rope, jnp.asarray([row[PROMPT:PROMPT + 1]]),
                      kc, vc, jnp.int32(PROMPT))
    got = np.concatenate([np.asarray(got0)[0], np.asarray(got1)[0]])
    np.testing.assert_allclose(got, ref[:PROMPT + 1], atol=LOGITS_TOL, rtol=0)


def test_every_cache_kind_that_cannot_run_kinds_of_layer_says_so(toy):
    from distributed_llama_tpu.runtime.engine import Engine

    _, _, _, spec, params = toy
    with pytest.raises(ValueError, match="host-spill ring does not support "
                                         "kinds of attention layer"):
        Engine(spec, params, None, kv_cache_storage="host",
               kv_cache_resident=64, tp=1)
    with pytest.raises(ValueError, match="sequence-sharded .* does not "
                                         "support kinds of attention layer"):
        Engine(spec, params, None, tp=1, sp=2)
