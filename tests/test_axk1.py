"""A.X-K1's block graph (the DeepSeek-V3 graph) at the toy size, against its
plain reference.

What the model forces is data on `ModelSpec`: latent attention (one cache row
a token a layer, read in the absorbed form), a leading dense layer in a stack
and a scan of its own, a sigmoid router over 16 experts of which this
checkpoint holds 4 (from expert 4), a shared expert, YaRN. The reference is
the benchmark's own (`benchmark/families/axk1.py`): plain float32, the
UNabsorbed form with per-head keys and values, the whole sequence at once, no
cache. Everything here compares LOGITS of prefill plus cached decode with
that full forward pass.

Tolerances. LOGITS_TOL 2e-4 (absolute, logits of rms about 0.3): both sides
are float32; the program multiplies the queries through w_uk where the
reference forms the keys, splits the softmax differently (the paged kernel's
online softmax) and sums the experts in another order, which reads 1e-6 to
2e-5 here. The same reference computed in bfloat16 reads above 1e-3
(asserted below), so the tolerance tells float32 from the precision under
it. KERNEL_TOL 2e-3 where the engine runs the Q40 kernels: they hand the MXU
bf16 operands (float32 accumulation); the reference in fp8, the precision
under that, reads above 1e-2 (asserted below).
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
from distributed_llama_tpu.models.forward import (_route, forward,
                                                  init_kv_cache)
from distributed_llama_tpu.models.params import hold_dense
from distributed_llama_tpu.models.spec import RopeType, RouterScore
from distributed_llama_tpu.ops.rope import RopeTables
from distributed_llama_tpu.quants import FloatType

SEED = 2**31 + 11
LOGITS_TOL = 2e-4
KERNEL_TOL = 2e-3
PROMPT, DECODE = 41, 11


@pytest.fixture(scope="module")
def toy():
    cfg = cells.load_config("tiny-axk1")
    fam = cells.load_family("axk1")
    weights = W.make_weights(cfg, SEED)
    params = hold_dense(W.to_program_params(weights, cfg), jnp.float32)
    return cfg, fam, weights, fam.model_spec(cfg), params


@pytest.fixture(scope="module")
def sequence(toy):
    cfg, fam, weights, _, _ = toy
    row = np.random.default_rng(5).integers(3, cfg["vocab_size"],
                                            PROMPT + DECODE).tolist()
    ref, _ = fam.logits_at(cfg, weights, [row], [range(len(row))])
    return row, ref


def test_the_spec_carries_the_model_as_data(toy):
    _, _, _, spec, params = toy
    assert spec.latent and spec.n_kv_heads == 1
    assert spec.head_size == 40 and spec.rope_width == 8 and spec.o_dim == 128
    assert spec.lead_layers == 1 and spec.block_layers == 2
    assert (spec.n_experts, spec.n_router, spec.expert_offset) == (4, 16, 4)
    assert spec.router_score == RouterScore.SIGMOID and spec.router_scale == 2.5
    assert spec.shared_hidden_dim == 64 and spec.rope_type == RopeType.YARN
    # two stacks: no expert tensor under the dense layer, no dense FFN under
    # an expert layer
    assert set(params["lead"]) & {"router", "moe_up", "sh_up"} == set()
    assert "w1" in params["lead"] and "w1" not in params["blocks"]
    assert params["blocks"]["moe_up"].shape[:2] == (2, 4)
    assert params["blocks"]["router"].shape[:2] == (2, 16)


def test_the_cache_holds_one_latent_row_a_token():
    """At the published widths: [c (512) ; k_pe (64)] padded to 640 values,
    1280 bytes in bfloat16, and no second side; keys and values of 64 heads
    would be 40960."""
    spec = cells.load_family("axk1").model_spec(
        cells.load_config("ax-k1-ep4-l7"))
    assert spec.cache_widths == (640, 0)
    assert spec.cache_row_bytes(2) == 1280
    assert spec.n_heads * (spec.head_size + spec.v_head_dim) * 2 == 40960
    kc, vc = init_kv_cache(dataclasses.replace(spec, seq_len=16, n_layers=2,
                                               lead_layers=1))
    assert kc.shape == (2, 1, 1, 16, 640) and vc.shape == (2, 1, 1, 16, 0)
    # 48 of 192 held, the router 192 wide; the attention scale with YaRN's
    assert (spec.n_experts, spec.n_router, spec.lead_layers) == (48, 192, 1)
    m = 0.1 * math.log(32) + 1
    assert spec.attn_scale == pytest.approx(192 ** -0.5 * m * m)
    assert spec.attn_scale == pytest.approx(192 ** -0.5 * 1.8133, rel=1e-4)


def test_yarn_frequencies_against_numbers_worked_by_hand():
    """theta 10000 over 64 rotary values, factor 32 over 4096, betas 32 and
    1: the correction range is pairs 10 to 23 (64 ln(4096 / (beta 2 pi)) /
    (2 ln 10000) = 10.47 and 22.51)."""
    fam = cells.load_family("axk1")
    cfg = cells.load_config("ax-k1-ep4-l7")
    spec = fam.model_spec(cfg)
    f = [10000.0 ** (-i / 32) for i in range(32)]
    want = ([f[i] for i in range(11)]  # untouched up to pair 10
            + [f[i] / 32 * ((i - 10) / 13) + f[i] * (1 - (i - 10) / 13)
               for i in range(11, 23)]
            + [f[i] / 32 for i in range(23, 32)])
    assert want[16] == pytest.approx(0.01 * (1 / 32 * 6 / 13 + 7 / 13))
    np.testing.assert_allclose(fam.yarn_inv_freq(cfg), want, rtol=1e-12)
    tables = RopeTables.create(dataclasses.replace(spec, seq_len=8))
    assert tables.cos.shape == (8, 32)  # the rotary part alone
    # position 1 turns by the frequency itself; the tables' factor is 1
    np.testing.assert_allclose(np.asarray(tables.cos[1]), np.cos(want),
                               atol=1e-7)
    np.testing.assert_allclose(np.asarray(tables.sin[5]),
                               np.sin(5 * np.asarray(want)), atol=1e-6)


def test_sigmoid_routing_against_a_hand_made_case(toy):
    _, _, _, spec, _ = toy
    logits = jnp.asarray([[[0.0, math.log(3.0), -1.0, math.log(1 / 3), 5.0,
                            -5.0]]])
    # sigmoid: 0.5, 0.75, 0.269, 0.25, 0.9933, 0.0067: the two largest are
    # experts 4 and 1; renormalised over the two, times 2.5
    top_i, w = _route(logits, 2, spec)
    assert top_i.tolist() == [[[4, 1]]]
    s4 = 1 / (1 + math.exp(-5.0))
    np.testing.assert_allclose(
        np.asarray(w)[0, 0], [2.5 * s4 / (s4 + 0.75), 2.5 * 0.75 / (s4 + 0.75)],
        rtol=1e-6)
    # not renormalised and unscaled: the scores themselves
    _, raw = _route(logits, 2, dataclasses.replace(
        spec, router_renorm=False, router_scale=1.0))
    np.testing.assert_allclose(np.asarray(raw)[0, 0], [s4, 0.75], rtol=1e-6)
    # a spec that says nothing: the softmax over all, renormalised
    plain = cells.load_family("mistral").model_spec(
        cells.load_config("tiny-moe"))
    assert plain.router_score == RouterScore.SOFTMAX and plain.router_renorm
    top_i, soft = _route(logits, 2, plain)
    assert top_i.tolist() == [[[4, 1]]]
    assert float(jnp.sum(soft)) == pytest.approx(1.0)


def _paged_cache(spec, bt=8):
    """A pool and one row's block table: block 0 is scratch."""
    w = spec.seq_len // bt
    kw, vw = spec.cache_widths
    return (jnp.zeros((spec.n_layers, w + 1, 1, bt, kw), jnp.float32),
            jnp.zeros((spec.n_layers, w + 1, 1, bt, vw), jnp.float32),
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
    """The absorbed program through every cache kind a latent spec has,
    against the unabsorbed reference's one pass: chunks of 16 and 25 (the
    paged kernel's query blocks of 8 and of 5), then T = 1."""
    _, _, _, spec, params = toy
    row, ref = sequence
    got = _chunks_then_decode(spec, params, row, path, (16, PROMPT - 16))
    np.testing.assert_allclose(got, ref, atol=LOGITS_TOL, rtol=0)


def test_a_lower_precision_fails_the_tolerance(toy, sequence):
    cfg, fam, weights, _, _ = toy
    row, ref = sequence
    for control in ("bfloat16", "q80"):
        other, _ = fam.logits_at(cfg, weights, [row], [range(len(row))],
                                 control)
        assert np.max(np.abs(other - ref)) > 5 * LOGITS_TOL, control
    fp8, _ = fam.logits_at(cfg, weights, [row], [range(len(row))], "fp8")
    assert np.max(np.abs(fp8 - ref)) > 5 * KERNEL_TOL


@pytest.mark.parametrize("cut", [[0], [1]], ids=["dense-layer", "expert-layer"])
def test_one_layer_absorbed_against_unabsorbed(toy, sequence, cut):
    """One layer alone, so that nothing averages out behind it: the leading
    dense layer (latent attention and a plain FFN) and one expert layer."""
    cfg, fam, weights, _, _ = toy
    row, _ = sequence
    w = W.layer_cut(weights, cut, cfg)
    spec = fam.model_spec({**cfg, "num_hidden_layers": 1})
    if cut == [1]:  # a one-layer cut reads as the leading stack: say which
        spec = dataclasses.replace(
            fam.model_spec({**cfg, "num_hidden_layers": 2}), n_layers=1)
    params = hold_dense(W.to_program_params(w, cfg), jnp.float32)
    ref, _ = fam.logits_at(cfg, w, [row], [range(len(row))])
    got = _chunks_then_decode(spec, params, row, "paged-gather", (24,))
    np.testing.assert_allclose(got, ref, atol=LOGITS_TOL, rtol=0)


@pytest.fixture(scope="module")
def uncut():
    """One expert layer with ALL 16 experts held, and the four shares of it
    (experts 0-3, 4-7, 8-11, 12-15), the router 16 wide in each."""
    cfg = cells.load_config("tiny-axk1")
    whole = {**cfg, "n_routed_experts": 16, "expert_offset": 0,
             "num_hidden_layers": 2, "layers_here": 2}
    weights = W.layer_cut(W.make_weights(whole, SEED + 1), [1], whole)

    def share(off):
        part = dict(weights)
        for name in ("moe_up", "moe_gate", "moe_down"):
            part["blocks." + name] = tuple(
                a[:, off:off + 4] for a in weights["blocks." + name])
        return {**whole, "n_routed_experts": 4, "expert_offset": off}, part

    return whole, weights, share


def test_the_shares_add_up_to_the_uncut_layer(uncut):
    """THE SHARES' TEST. The routed parts that offsets 0, 4, 8 and 12 give,
    plus the shared expert counted once, are the uncut reference's layer:
    with `none` the layer whose routed experts add nothing (down's scales
    zero: the residual stream, attention and the shared expert),
    sum_off (share_off - none) + none = uncut. Seen at the layer's output
    through the final norm's input, which the family hands out as logits of
    an identity-free head, so compared on the hidden state: the family's
    `_layer` itself."""
    import jax

    fam = cells.load_family("axk1")
    whole, weights, share = uncut
    row = np.random.default_rng(3).integers(3, 512, 40)
    x = jnp.asarray(weights["embedding"][row])

    def layer_out(cfg, w):
        with jax.default_matmul_precision("highest"):
            lw = W.layer(w, 0, cfg)
            return np.asarray(fam._layer(fam._sizes(cfg), "float32", x, lw,
                                         None)[0])

    full = layer_out(whole, weights)
    cfg0, w0 = share(0)
    packed, scales = w0["blocks.moe_down"]
    none = layer_out(cfg0, {**w0, "blocks.moe_down":
                            (packed, np.zeros_like(scales))})
    parts = [layer_out(*share(off)) - none for off in (0, 4, 8, 12)]
    # every share routes the same 2 of 16 and renormalises over both
    np.testing.assert_allclose(sum(parts) + none, full, atol=2e-6, rtol=0)
    assert all(np.abs(p).max() > 1e-4 for p in parts)  # each holds some


@pytest.mark.parametrize("off", [0, 4, 8, 12])
def test_the_program_computes_each_share_as_the_reference_does(uncut, off):
    """Each of the four shares through the program (the grouped layer at
    T = 1 and at a chunk, `offset` from the spec) against the reference
    given the same share: with the test above, the program's shares add up
    to the uncut layer too."""
    fam = cells.load_family("axk1")
    _, _, share = uncut
    cfg, w = share(off)
    row = np.random.default_rng(3).integers(3, 512, 40).tolist()
    spec = dataclasses.replace(
        fam.model_spec({**cfg, "num_hidden_layers": 2, "layers_here": 3}),
        n_layers=1)
    assert spec.expert_offset == off and spec.lead_layers == 0
    params = hold_dense(W.to_program_params(w, cfg), jnp.float32)
    ref, _ = fam.logits_at(cfg, w, [row], [range(len(row))])
    got = _chunks_then_decode(spec, params, row, "paged-gather", (32,))
    np.testing.assert_allclose(got, ref, atol=LOGITS_TOL, rtol=0)


@pytest.fixture(scope="module")
def engine(toy):
    """BatchEngine as the cell builds it, on the kernels (interpret mode):
    the latent paged-attention kernel and the grouped Q40 kernels."""
    from distributed_llama_tpu.runtime.batch_engine import BatchEngine

    _, _, _, spec, _ = toy
    cfg, _, weights, _, _ = toy
    be = BatchEngine(spec, W.to_program_params(weights, cfg), None, slots=4,
                     superstep=4, paged_kv=True, kv_block_tokens=16,
                     prefix_cache=True, use_pallas=True, dtype=jnp.float32,
                     tp=1)
    assert be._eng.paged_kernel and be._eng.moe_stats
    yield be
    be.close()


def test_the_pool_holds_rows_and_no_keys_or_values(toy, engine):
    from distributed_llama_tpu.obs import metrics

    _, _, _, spec, _ = toy
    kc, vc = engine._eng.k_cache, engine._eng.v_cache
    assert kc.shape[2:] == (1, 16, 128) and vc.shape[2:] == (1, 16, 0)
    assert kc.shape[0] == spec.n_layers == 3
    # 32 + 8 values padded to 128, float32 here
    assert metrics.snapshot()["kv_pool_row_bytes"] == 128 * 4
    assert "lead" in engine._eng.params


def test_batch_engine_chunks_of_64_8_1_and_decode_match_the_reference(
        toy, engine):
    cfg, fam, weights, _, _ = toy
    rng = np.random.default_rng(11)
    probes = []
    for n in (72, 73, 74, 75):
        toks = rng.integers(3, cfg["vocab_size"], n + 6)
        probes.append((toks[:n].tolist(), toks[n:].tolist()))
    got = np.concatenate(probe.drive(engine, probes))
    ref, _ = probe.reference_rows(cfg, weights, probes)
    np.testing.assert_allclose(got, ref, atol=KERNEL_TOL, rtol=0)


def test_batch_engine_scan_tokens_and_counters(toy, engine):
    """A greedy request through prefill and K-step scans: the tokens are the
    reference's argmax chain; the routers' assignments are counted whole and
    the held ones apart; the latent counters move."""
    from distributed_llama_tpu.obs import metrics
    from distributed_llama_tpu.runtime.sampler import Sampler

    cfg, fam, weights, spec, _ = toy
    prompt = np.random.default_rng(13).integers(3, cfg["vocab_size"],
                                                20).tolist()
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
    routed, held = (moved["batch_moe_routed_total"],
                    moved["batch_moe_assignments_total"])
    positions = moved["batch_positions_dispatched_total"]
    assert routed == positions * spec.n_active_experts * spec.block_layers
    assert 0 < held < routed  # 4 of 16 held: about a quarter
    # the weights ran over the compact rows of the prompt's two chunks of 8
    # (20 tokens: 8 + 8 + 4 x 1), attention over each chunk's 8 queries and
    # one a slot (ISSUE 45: no longer their (slots, 8) rectangles)
    from distributed_llama_tpu.models.forward import compact_rows

    slots = len(engine._slots)
    queries = positions + 2 * (8 + slots - compact_rows(8, slots))
    assert moved["batch_latent_dispatch_rows_total"] == queries * spec.n_layers
    assert moved["batch_latent_rows_read_total"] > 0
    assert moved["batch_attn_pairs_real_total"] > 0


def test_a_prefix_cache_hit_on_latent_blocks_gives_the_same_logits(
        toy, engine):
    """The same prompt again after every slot has served another one: the
    admission finds the prompt's pool blocks (latent rows) in the radix
    directory, remaps them into the slot's table and prefills the tail alone;
    the logits it is shown are the first run's."""
    from distributed_llama_tpu.obs import metrics

    cfg, _, _, _, _ = toy
    rng = np.random.default_rng(17)

    def probe_row(n):
        toks = rng.integers(3, cfg["vocab_size"], n + 5)
        return (toks[:n].tolist(), toks[n:].tolist())

    shared = probe_row(70)
    first = probe.drive(engine, [shared])[0]
    # every slot's own history is overwritten: no slot can rewind to it
    probe.drive(engine, [probe_row(40 + i) for i in range(engine.slots_n)])
    before = metrics.snapshot()
    again = probe.drive(engine, [shared])[0]
    after = metrics.snapshot()
    assert (after["paged_kv_remapped_blocks_total"]
            > before.get("paged_kv_remapped_blocks_total", 0))
    assert (after["prefix_cache_hits_total"]
            > before.get("prefix_cache_hits_total", 0))
    np.testing.assert_allclose(again, first, atol=KERNEL_TOL, rtol=0)


def test_every_cache_kind_that_cannot_hold_a_latent_row_says_so(toy):
    from distributed_llama_tpu.runtime.batch_engine import BatchEngine
    from distributed_llama_tpu.runtime.engine import Engine

    cfg, _, weights, spec, _ = toy
    params = W.to_program_params(weights, cfg)
    with pytest.raises(ValueError, match="host-spill ring does not support "
                                         "a latent cache row"):
        Engine(spec, params, None, kv_cache_storage="host",
               kv_cache_resident=64, tp=1)
    with pytest.raises(ValueError, match="sequence-sharded .* does not "
                                         "support a latent cache row"):
        Engine(spec, params, None, tp=1, sp=2)
    with pytest.raises(ValueError, match="Q80 cold tier"):
        BatchEngine(spec, params, None, slots=2, prefix_cache=True,
                    prefix_cache_q80=True, tp=1)
    with pytest.raises(ValueError, match="dense host prefix cache"):
        BatchEngine(spec, params, None, slots=2, prefix_cache=True,
                    paged_kv=False, tp=1)


def test_a_model_file_round_trip_of_the_new_header_keys(toy, sequence, tmp_path):
    """The repo's writer, then its loader: the same spec (every new key), the
    same two stacks, and the single-sequence engine (`apps/dllama.py`'s) on
    the file decodes the reference's tokens."""
    from distributed_llama_tpu.runtime.engine import Engine

    cfg, fam, weights, spec, _ = toy
    params = W.to_program_params(weights, cfg)
    path = str(tmp_path / "axk1.m")
    write_model(path, spec, params_file_order(spec, params, as_stored=True),
                FloatType.Q40)
    spec2, wft, _ = read_spec(path)
    assert wft == FloatType.Q40
    assert spec2 == dataclasses.replace(spec, orig_seq_len=spec2.orig_seq_len)
    _, loaded = load_model(path)
    assert set(loaded) == set(params)
    for st in ("lead", "blocks"):
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


def test_tp2_with_expert_sharding_equals_tp1(toy, sequence):
    """Heads, the shared expert's hidden axis and the vocabulary sliced over
    two shards, WHOLE experts sharded (2 of the 4 held on each, the offset
    the spec's plus the shard's), the latent row whole on both."""
    from distributed_llama_tpu.parallel.mesh import make_mesh
    from distributed_llama_tpu.parallel.tp import (init_sharded_kv_cache,
                                                   make_sharded_forward,
                                                   shard_params)

    _, _, _, spec, params = toy
    row, ref = sequence
    rope = RopeTables.create(spec)
    mesh = make_mesh(tp=2)
    sharded = shard_params(params, mesh, spec, moe_sharding="expert")
    step = make_sharded_forward(spec, mesh, sharded, donate_cache=False,
                                moe_sharding="expert")
    kc, vc = init_sharded_kv_cache(spec, mesh)
    assert kc.shape[2] == 2  # the row replicated: one copy a shard
    got0, kc, vc = step(sharded, rope, jnp.asarray([row[:PROMPT]]), kc, vc,
                        jnp.int32(0))
    got1, _, _ = step(sharded, rope, jnp.asarray([row[PROMPT:PROMPT + 1]]),
                      kc, vc, jnp.int32(PROMPT))
    got = np.concatenate([np.asarray(got0)[0], np.asarray(got1)[0]])
    np.testing.assert_allclose(got, ref[:PROMPT + 1], atol=LOGITS_TOL, rtol=0)
