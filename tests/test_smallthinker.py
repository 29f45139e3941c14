"""SmallThinker's block graph at the toy size, against its plain reference.

The model is data on the routed block graph (models/spec.py): a stated head
size (6 heads of 32 beside a hidden size of 128), ReLU-gated experts (3 of 8),
a router fed from the block's input, and per layer whether q and k rotate and
whether attention is windowed (16 keys here, so every sequence below passes
the window several times). The reference is the benchmark's own
(`benchmark/families/smallthinker.py`, the one copy in the repo): plain
float32, the whole sequence at once, no cache. Everything here compares
LOGITS of prefill plus cached decode with that full forward pass.

Tolerances. LOGITS_TOL 2e-4 (absolute, logits of rms about 0.3): both sides
are float32; the program's cached path splits the softmax differently (the
paged kernel's online softmax) and sums the experts in another order, which
reads 1e-6 to 3e-5 here. The same reference computed in bfloat16 reads above
1e-3 (asserted below), so the tolerance tells float32 from the precision
under it. KERNEL_TOL 2e-3 where the engine runs the grouped Q40 kernels: they
hand the MXU bf16 operands (float32 accumulation), which reads up to 6e-4 in
the logits here; the reference in fp8, the precision under that, reads above
1e-2 (asserted below).
"""

import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from benchmark import cells, probe
from benchmark import weights as W
from distributed_llama_tpu.formats.mfile import (load_model, params_file_order,
                                                 write_model)
from distributed_llama_tpu.models.forward import forward, init_kv_cache
from distributed_llama_tpu.models.params import (_concat_rows_grouped,
                                                 expected_experts_touched,
                                                 prepare_for_pallas)
from distributed_llama_tpu.models.spec import HiddenAct, RouterInput
from distributed_llama_tpu.ops.moe_grouped import (ACTS, capacity,
                                                   grouped_expert_ffn, row_tile)
from distributed_llama_tpu.ops.pallas_paged_attention import visited_keys
from distributed_llama_tpu.ops.rope import RopeTables
from distributed_llama_tpu.quants import FloatType, QTensor

SEED = 2**31 + 7
LOGITS_TOL = 2e-4
KERNEL_TOL = 2e-3
PROMPT, DECODE = 41, 11  # 52 positions: over three windows of 16


@pytest.fixture(scope="module")
def toy():
    cfg = cells.load_config("tiny-smallthinker")
    fam = cells.load_family("smallthinker")
    weights = W.make_weights(cfg, SEED)
    return cfg, fam, weights, fam.model_spec(cfg), W.to_program_params(weights)


@pytest.fixture(scope="module")
def sequence(toy):
    """One seeded sequence and the reference's logits at every position."""
    cfg, fam, weights, _, _ = toy
    row = np.random.default_rng(5).integers(3, cfg["vocab_size"],
                                            PROMPT + DECODE).tolist()
    ref, _ = fam.logits_at(cfg, weights, [row], [range(len(row))])
    return row, ref


def test_the_spec_carries_the_model_as_data(toy):
    cfg, _, _, spec, _ = toy
    assert spec.head_size == 32 and spec.q_dim == 192 != spec.dim
    assert spec.kv_dim == 64 and spec.hidden_act == HiddenAct.RELU
    assert spec.router_input == RouterInput.BLOCK_INPUT
    assert spec.layer_rope() == (0, 1, 1, 1, 0, 1, 1, 1)
    assert spec.layer_window() == (0, 16, 16, 16, 0, 16, 16, 16)
    assert PROMPT + DECODE >= 3 * cfg["sliding_window_size"]


def _paged_cache(spec, bt=8):
    """A pool and one row's block table: block 0 is scratch."""
    w = spec.seq_len // bt
    shape = (spec.n_layers, w + 1, spec.n_kv_heads, bt, spec.head_size)
    return (jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32),
            jnp.arange(1, w + 1, dtype=jnp.int32)[None], bt)


@pytest.mark.parametrize("path", ["dense", "dense-window", "paged-gather",
                                  "paged-kernel"])
def test_prefill_then_cached_decode_matches_the_full_forward_pass(
        toy, sequence, path):
    _, _, _, spec, params = toy
    row, ref = sequence
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
            # a static bucket over the cache read, past every position here
            kw = dict(attn_window=spec.seq_len // 2)
    got, p = [], 0
    # the prompt in chunks of 17 and 24, then one token at a time
    for n in (17, PROMPT - 17) + (1,) * DECODE:
        logits, kc, vc = forward(params, spec, rope,
                                 jnp.asarray([row[p:p + n]]), kc, vc, pos(p),
                                 **kw)
        got.append(np.asarray(logits)[0])
        p += n
    np.testing.assert_allclose(np.concatenate(got), ref, atol=LOGITS_TOL,
                               rtol=0)


def test_a_lower_precision_or_no_window_fails_the_tolerance(toy, sequence):
    cfg, fam, weights, _, _ = toy
    row, ref = sequence
    for control in ("bfloat16", "q80", "window_off"):
        other, _ = fam.logits_at(cfg, weights, [row], [range(len(row))],
                                 control)
        assert np.max(np.abs(other - ref)) > 5 * LOGITS_TOL, control
    fp8, _ = fam.logits_at(cfg, weights, [row], [range(len(row))], "fp8")
    assert np.max(np.abs(fp8 - ref)) > 5 * KERNEL_TOL
    # the windows engage only past 16 keys: before that, off equals on
    np.testing.assert_allclose(other[:16], ref[:16], atol=1e-6)


@pytest.fixture(scope="module")
def engine(toy):
    """BatchEngine as the cell builds it, on the kernels (interpret mode):
    the paged-attention kernel with its lower bound and the grouped Q40
    kernels."""
    from distributed_llama_tpu.runtime.batch_engine import BatchEngine

    _, _, _, spec, params = toy
    be = BatchEngine(spec, params, None, slots=4, superstep=4, paged_kv=True,
                     kv_block_tokens=16, prefix_cache=True, use_pallas=True,
                     dtype=jnp.float32, tp=1)
    assert be._eng.paged_kernel and be._eng.moe_stats
    yield be
    be.close()


def test_batch_engine_chunks_of_64_8_1_and_decode_match_the_reference(
        toy, engine):
    """Four probe rows at once: prompts of 64 + 8 + i through mixed chunks of
    64, 8 and 1 with the decoding rows riding them, then T=1 steps."""
    cfg, fam, weights, _, _ = toy
    rng = np.random.default_rng(11)
    probes = []
    for n in (72, 73, 74, 75):
        toks = rng.integers(3, cfg["vocab_size"], n + 6)
        probes.append((toks[:n].tolist(), toks[n:].tolist()))
    got = np.concatenate(probe.drive(engine, probes))
    ref, _ = probe.reference_rows(cfg, weights, probes)
    np.testing.assert_allclose(got, ref, atol=KERNEL_TOL, rtol=0)


def test_batch_engine_scan_tokens_and_moe_counters(toy, engine):
    """A greedy request through prefill, the host-sampled first token and
    K-step scans: the tokens are the reference's argmax chain, and the
    expert layer's counters moved as its shapes say. A chunk's block of
    logits reaches the host as its first and last position only."""
    from distributed_llama_tpu.obs import metrics
    from distributed_llama_tpu.runtime.sampler import Sampler

    cfg, fam, weights, spec, _ = toy
    prompt = np.random.default_rng(13).integers(3, cfg["vocab_size"], 20).tolist()
    before = metrics.snapshot()
    out, _ = engine.generate(prompt, 6,
                             Sampler(spec.vocab_size, temperature=0.0))
    after = metrics.snapshot()
    seq = list(prompt)
    for tok in out:  # teacher-forced: each token is the reference's argmax
        ref, _ = fam.logits_at(cfg, weights, [seq], [[len(seq) - 1]])
        assert int(np.argmax(ref[0])) == tok
        seq.append(tok)
    moved = {k: after[k] - before.get(k, 0) for k in after
             if k.startswith("batch_moe_")}
    a, rows, touched, offered = (moved[f"batch_moe_{n}_total"] for n in (
        "assignments", "rows_computed", "experts_touched", "experts_offered"))
    assert a > 0 and a % (spec.n_active_experts * spec.n_layers) == 0
    assert rows >= a and offered % (spec.n_experts * spec.n_layers) == 0
    assert 0 < touched <= offered


def _expert_case(n, act, kernel, merged, seed=0):
    """N rows, 8 experts of which 2 a row: expert 2 everybody's, expert 5
    nobody's. Returns what grouped_expert_ffn takes and the per-token loop."""
    e, k, d, h = 8, 2, 64, 64
    rng = np.random.default_rng([seed, n])
    stack = {nm: QTensor.from_float(
        rng.standard_normal((e, *shp)).astype(np.float32) * 0.1, FloatType.Q40)
        for nm, shp in (("moe_up", (h, d)), ("moe_gate", (h, d)),
                        ("moe_down", (d, h)))}
    x = rng.standard_normal((n, d)).astype(np.float32)
    others = np.array([0, 1, 3, 4, 6, 7])
    top_i = np.stack([np.full(n, 2), rng.choice(others, n)], axis=1)
    wts = rng.random((n, k)).astype(np.float32)
    up, gate, down = (stack[nm].to_numpy() for nm in
                      ("moe_up", "moe_gate", "moe_down"))
    want = np.zeros((n, d), np.float64)
    for i in range(n):
        for j in range(k):
            ex = top_i[i, j]
            hb = (up[ex] @ x[i]) * np.asarray(ACTS[act](jnp.asarray(gate[ex] @ x[i])))
            want[i] += wts[i, j] * (down[ex] @ hb)
    bp = dict(stack)
    if merged:
        bp = {"moe_gu": _concat_rows_grouped(
            [stack["moe_up"], stack["moe_gate"]], 1, row_axis=1),
            "moe_down": stack["moe_down"]}
    if kernel:
        bp = {nm: t.to_i4p_layout() for nm, t in bp.items()}
    return bp, jnp.asarray(x), jnp.asarray(top_i), jnp.asarray(wts), want


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
@pytest.mark.parametrize("act", ["relu", "silu", "gelu_tanh"])
@pytest.mark.parametrize("held", ["all", "halves"])
@pytest.mark.parametrize("n", [3, 4, 40], ids=["below-E", "at-E", "above-E"])
def test_grouped_expert_layer_against_a_per_token_loop(n, held, act, kernel):
    """N k below, at and above E; an expert nobody chose and one everybody
    chose; all experts held, or two halves that add up to the whole."""
    bp, x, top_i, wts, want = _expert_case(n, act, kernel, merged=kernel)
    if held == "all":
        got, st = grouped_expert_ffn(x, top_i, wts, bp, act_name=act, el=8,
                                     use_pallas=kernel)
        tile = row_tile(n * 2, 8)
        assert int(st[0]) == n * 2 and int(st[2]) == len(np.unique(top_i))
        assert int(st[1]) % tile == 0 and n * 2 <= int(st[1]) <= capacity(
            n * 2, 8, tile)
    else:
        got, a = 0, 0
        for off in (0, 4):
            half = jax.tree_util.tree_map(lambda t: t[off:off + 4], bp)
            part, st = grouped_expert_ffn(x, top_i, wts, half, act_name=act,
                                          el=4, offset=off, use_pallas=kernel)
            got, a = got + part, a + int(st[0])
        assert a == n * 2  # every assignment is computed by exactly one half
    # float32 against float64, bf16 operands inside the kernel's MXU dots
    np.testing.assert_allclose(np.asarray(got), want,
                               atol=2e-2 if kernel else 2e-5, rtol=0)


def test_row_tile_and_capacity_follow_from_shapes():
    # 8 slots x 6 of 64: a decode step, an 8- and a 64-token chunk
    assert [row_tile(n * 6, 64) for n in (8, 64, 512)] == [16, 16, 128]
    assert capacity(48, 64, 16) == 1008 and capacity(3072, 64, 128) == 11264
    # Mixtral's 2 of 8 at the same dispatches
    assert [row_tile(n * 2, 8) for n in (8, 64, 512)] == [16, 32, 256]
    assert expected_experts_touched(64, 6, 8) == pytest.approx(34.88, abs=0.01)
    assert expected_experts_touched(8, 2, 1) == pytest.approx(2.0)


@pytest.mark.parametrize("moe_sharding", ["slice", "expert"])
def test_tp2_matches_the_single_device_program(toy, sequence, moe_sharding):
    from distributed_llama_tpu.parallel.mesh import make_mesh
    from distributed_llama_tpu.parallel.tp import (init_sharded_kv_cache,
                                                   make_sharded_forward,
                                                   shard_params)

    _, _, _, spec, params = toy
    row, ref = sequence
    rope = RopeTables.create(spec)
    mesh = make_mesh(tp=2)
    sharded = shard_params(params, mesh, spec, moe_sharding=moe_sharding)
    step = make_sharded_forward(spec, mesh, sharded, donate_cache=False,
                                moe_sharding=moe_sharding, moe_stats=True)
    kc, vc = init_sharded_kv_cache(spec, mesh)
    kc1, vc1 = init_kv_cache(spec)
    got, p = [], 0
    for n in (PROMPT, 1, 1, 1):
        toks = jnp.asarray([row[p:p + n]])
        logits, kc, vc, stats = step(sharded, rope, toks, kc, vc, jnp.int32(p))
        got.append(np.asarray(logits)[0])
        # the counters are the whole layer's, not one shard's view of it
        _, kc1, vc1, want = forward(params, spec, rope, toks, kc1, vc1,
                                    jnp.int32(p), moe_stats=True)
        want = [int(v) for v in want]
        assert [int(v) for v in stats][:1] + [int(v) for v in stats][3:5] == (
            want[:1] + want[3:5])  # assignments, offered, grouped assignments
        if moe_sharding == "slice":  # same tiles on every shard
            assert [int(v) for v in stats] == want
        else:  # a shard pads its own runs: touched agrees, rows need not
            assert int(stats[2]) == want[2] and int(stats[5]) == want[5]
        p += n
    np.testing.assert_allclose(np.concatenate(got), ref[:p], atol=LOGITS_TOL,
                               rtol=0)


def test_tp2_kernel_layouts_shard(toy):
    """prepare_for_pallas at tp 2: 28/4-style heads (here 6/2 of 32) and the
    experts' hidden slice keep their quant blocks whole."""
    from distributed_llama_tpu.parallel.sharding import check_divisibility

    _, _, _, spec, params = toy
    check_divisibility(spec, 2)
    pp = prepare_for_pallas(params, tp=2, spec=spec)
    # wo's in-axis is q_dim = 192: 96 a shard, whole quant blocks
    assert pp["blocks"]["wo"].layout != "planar"
    assert pp["blocks"]["wo"].shape == (spec.n_layers, 128, 192)


@pytest.mark.parametrize("ftype", [FloatType.F32, FloatType.Q40])
def test_m_file_round_trip_keeps_the_new_header_keys(toy, tmp_path, ftype):
    _, _, _, spec, params = toy
    path = str(tmp_path / "toy.m")
    write_model(path, spec, params_file_order(spec, params), ftype)
    spec2, params2 = load_model(path)
    for field in ("head_dim", "sliding_window", "rope_layers", "window_layers",
                  "router_input", "hidden_act", "norm_eps", "rope_type",
                  "n_experts", "n_active_experts", "hidden_dim", "dim"):
        assert getattr(spec2, field) == getattr(spec, field), field
    assert params2["blocks"]["wq"].shape == (spec.n_layers, 192, 128)
    assert params2["blocks"]["wo"].shape == (spec.n_layers, 128, 192)
    if ftype == FloatType.F32:  # the dequantized values come back exactly
        np.testing.assert_array_equal(params2["blocks"]["moe_down"].to_numpy(),
                                      params["blocks"]["moe_down"].to_numpy())


def test_a_header_without_the_new_keys_reads_as_before(tmp_path):
    from distributed_llama_tpu.models.params import init_random_params
    from distributed_llama_tpu.models.spec import ArchType, ModelSpec

    spec = ModelSpec(arch_type=ArchType.LLAMA, dim=64, hidden_dim=128,
                     n_layers=1, n_heads=4, n_kv_heads=2, vocab_size=64,
                     seq_len=32).resolved()
    params = init_random_params(spec, FloatType.F32)
    path = str(tmp_path / "old.m")
    write_model(path, spec, params_file_order(spec, params), FloatType.F32)
    with open(path, "rb") as f:
        f.seek(4)
        header = int.from_bytes(f.read(4), "little")
    assert header == 8 + 8 * 15  # the reference's fifteen keys, none of ours
    spec2, _ = load_model(path)
    assert spec2.head_dim == 0 and spec2.head_size == 16
    assert spec2.sliding_window == 0 and spec2.rope_layers == ()
    assert spec2.layer_rope() == (1,) and spec2.layer_window() == (0,)


@pytest.mark.parametrize("length,n_read,lo,want", [
    (300, 64, 0, 384),      # no bound: the three steps that hold 300 keys
    (300, 64, 127, 384),    # a bound inside the first step: still visited
    (300, 64, 128, 256),    # the first step wholly behind it: skipped
    (5000, 512, 905, 4224),  # a 4096 window at 5000: steps 7 to 39 of 40
    (100, 64, 400, 128),    # a bound past the length never skips a live step
    (0, 64, 0, 0),
])
def test_visited_keys_with_a_lower_bound(length, n_read, lo, want):
    assert visited_keys(length, n_read, 16, lo) == want
    assert visited_keys(length, n_read, 16) >= want


@pytest.mark.parametrize("hidden_act", [HiddenAct.SILU, HiddenAct.GELU])
def test_many_rows_an_expert_take_the_scan_and_agree_with_the_grouped_layer(
        monkeypatch, hidden_act):
    """The rule is shapes alone: at a mean of SCAN_FROM_MEAN_RUN rows an
    expert the dispatch goes through the all-experts scan, under it through
    the grouped layer; both give the same logits and count their work."""
    from distributed_llama_tpu.models import forward as F
    from distributed_llama_tpu.models.params import init_random_params
    from distributed_llama_tpu.models.spec import ArchType, ModelSpec, RopeType

    spec = ModelSpec(arch_type=ArchType.MIXTRAL, dim=64, hidden_dim=64,
                     n_layers=2, n_heads=4, n_kv_heads=2, vocab_size=96,
                     seq_len=160, n_experts=4, n_active_experts=2,
                     hidden_act=hidden_act, rope_type=RopeType.FALCON).resolved()
    params = init_random_params(spec, FloatType.F32, seed=3)
    rope = RopeTables.create(spec)
    toks = jnp.asarray(np.random.default_rng(2).integers(3, 96, (1, 128)))
    assert F.takes_the_scan(128, 2, spec.n_experts)

    def run():
        kc, vc = init_kv_cache(spec)
        logits, _, _, stats = forward(params, spec, rope, toks, kc, vc,
                                      jnp.int32(0), moe_stats=True)
        return np.asarray(logits), [int(v) for v in stats]

    scan_logits, scan = run()
    monkeypatch.setattr(F, "SCAN_FROM_MEAN_RUN", 1 << 20)
    grouped_logits, grouped = run()
    np.testing.assert_allclose(scan_logits, grouped_logits, atol=2e-5, rtol=0)
    # assignments and experts offered agree; the scan computes every row for
    # every expert and reports nothing through the grouped layer
    assert scan[0] == grouped[0] == 128 * 2 * 2 and scan[3] == grouped[3] == 8
    assert scan[1] == 4 * 128 * 2 and scan[2] == 8 and scan[4:] == [0, 0]
    assert grouped[4] == grouped[0] and grouped[5] == grouped[2] <= 8


def test_the_scan_holds_a_share_of_the_experts_under_expert_sharding():
    """Whole experts over tp 2, a dispatch on the scan's side of the rule:
    each shard scans the experts it holds and the psum adds the shares."""
    from distributed_llama_tpu.models.params import init_random_params
    from distributed_llama_tpu.models.spec import ArchType, ModelSpec, RopeType
    from distributed_llama_tpu.parallel.mesh import make_mesh
    from distributed_llama_tpu.parallel.tp import (init_sharded_kv_cache,
                                                   make_sharded_forward,
                                                   shard_params)

    spec = ModelSpec(arch_type=ArchType.MIXTRAL, dim=64, hidden_dim=64,
                     n_layers=2, n_heads=4, n_kv_heads=2, vocab_size=96,
                     seq_len=160, n_experts=4, n_active_experts=2,
                     rope_type=RopeType.FALCON).resolved()
    params = init_random_params(spec, FloatType.F32, seed=4)
    rope = RopeTables.create(spec)
    toks = jnp.asarray(np.random.default_rng(3).integers(3, 96, (1, 128)))
    kc, vc = init_kv_cache(spec)
    want, _, _ = forward(params, spec, rope, toks, kc, vc, jnp.int32(0))
    mesh = make_mesh(tp=2)
    sharded = shard_params(params, mesh, spec, moe_sharding="expert")
    step = make_sharded_forward(spec, mesh, sharded, donate_cache=False,
                                moe_sharding="expert")
    kc, vc = init_sharded_kv_cache(spec, mesh)
    got, _, _ = step(sharded, rope, toks, kc, vc, jnp.int32(0))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4,
                               rtol=0)


# ---- the expert stacks read in place: (L, E, rows, K/2) at prefetched indices


def _layer_stack(layers, merged, seed=0):
    """`layers` layers of _expert_case's experts as one stack over layers
    in the kernels' layout, and the rows, routing and tile of one dispatch."""
    cases = [_expert_case(40, "relu", True, merged, seed=seed + i)
             for i in range(layers)]
    stack = {nm: jax.tree_util.tree_map(lambda *a: jnp.stack(a),
                                        *(c[0][nm] for c in cases))
             for nm in cases[0][0]}
    return stack, cases[0][1], cases[0][2], cases[0][3]


@pytest.mark.parametrize("act", ["relu", "silu"])
@pytest.mark.parametrize("merged", [True, False], ids=["merged", "unmerged"])
@pytest.mark.parametrize("layer", [0, 1, 2], ids=["first", "middle", "last"])
def test_grouped_kernels_on_a_layer_of_the_stack_equal_the_slice_bit_for_bit(
        layer, merged, act):
    """The same Mosaic body on the same blocks at another address: layer l
    of (L, E, rows, K/2) through the prefetched layer index against the
    (E, rows, K/2) slice handed in alone, with tiles left unused."""
    from distributed_llama_tpu.ops.matmul import LayerOf
    from distributed_llama_tpu.ops.moe_grouped import plan
    from distributed_llama_tpu.ops.pallas_moe_grouped import (
        grouped_supported, moe_grouped_q4)

    stack, x, top_i, _ = _layer_stack(3, merged)
    tile = row_tile(40 * 2, 8)
    p = plan(top_i, 8, 0, tile)
    assert int(p["n_used"]) < p["tile_expert"].shape[0]  # unused tiles
    rows = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)])[p["src"]]
    names = ("moe_gu", "moe_gu") if merged else ("moe_up", "moe_gate")

    def call(of):
        up = of(stack[names[0]])
        gate = up if merged else of(stack[names[1]])
        down = of(stack["moe_down"])
        assert all(grouped_supported(w, 64, True) for w in (up, gate, down))
        out = moe_grouped_q4(rows, p["tile_expert"], p["n_used"], up, gate,
                             down, tile=tile, act=act, interpret=True)
        return np.asarray(out)[:int(p["n_used"]) * tile]

    whole = call(lambda w: LayerOf(w, (jnp.int32(layer),)))
    sliced = call(lambda w: jax.tree_util.tree_map(lambda a: a[layer], w))
    np.testing.assert_array_equal(whole, sliced)
    other = call(lambda w: LayerOf(w, (jnp.int32((layer + 1) % 3),)))
    assert np.abs(whole - other).max() > 1e-3  # the index is what is read


@pytest.mark.parametrize("k,nb", [(768, 24), (1792, 56), (2560, 80),
                                  (3584, 112), (14336, 448), (4096, 128)])
def test_grouped_kernels_decode_the_stored_planes_bit_for_bit(k, nb):
    """Both grouped kernels on (L, E, rows, K/2) stacks whose scales are the
    stored plane, (L, E, rows, K/32 in whole lane tiles): one-hot rows read
    the decoded weights of (layer 1, expert 2) back through the MXU, and
    they are `dequantize(dtype=bf16)` bit for bit. `down` gives the weights
    themselves; `gu` with the same stack as up and gate gives w relu(w),
    float32 products of bf16 values, which are exact."""
    from distributed_llama_tpu.ops import pallas_moe_grouped as G
    from distributed_llama_tpu.ops.pallas_q4_mm import pick_bk
    from distributed_llama_tpu.quants import scale_plane_cols

    rng = np.random.default_rng(nb)
    n, tile = 256, 16
    w = QTensor.from_float(rng.standard_normal((2, 3, n, k)).astype(
        np.float32) * 0.02, FloatType.Q40).to_i4p_layout()
    cols = scale_plane_cols(nb)
    assert w.scales.shape == (2, 3, n, cols)
    assert w.block_scales().shape == (2, 3, n, nb)
    # the first and last quant block of each half-plane: 64 rows, 4 tiles
    at = np.r_[0:16, k // 2 - 16:k // 2 + 16, k - 16:k]
    x = np.zeros((len(at), k), np.float32)
    x[np.arange(len(at)), at] = 1.0
    te = jnp.full((len(at) // tile,), 2, jnp.int32)
    nu = jnp.asarray([len(at) // tile], jnp.int32)
    kh, bn = k // 2, G._pick_bn(n, k // 2)
    want = np.asarray(w.dequantize(dtype=jnp.bfloat16).astype(
        jnp.float32))[1, 2][:, at].T

    def call(kernel, name, operands, specs, layers):
        return np.asarray(G._call(
            functools.partial(kernel, bk=pick_bk(kh)), name, jnp.asarray(x),
            operands, specs, n, bn, tile, jnp.float32, te, nu,
            jnp.asarray(layers, jnp.int32), True))

    got = call(G._down_kernel, "moe_grouped_q4_down", (w.data, w.scales),
               G._w_specs(bn, kh, cols, 0, 0), [1])
    np.testing.assert_array_equal(got, want)
    got = call(functools.partial(G._gu_kernel, act="relu"),
               "moe_grouped_q4_gu", (w.data, w.scales) * 2,
               G._w_specs(bn, kh, cols, 0, 0) + G._w_specs(bn, kh, cols, 0, 1),
               [1, 1])
    np.testing.assert_array_equal(got, want * np.maximum(want, 0.0))


@functools.lru_cache(maxsize=None)
def _wide_toy(name, hidden=256, seed=SEED):
    """A toy MoE configuration at widths whose half-planes are whole lane
    tiles (hidden 256, experts of `hidden`), which is what the rule that keeps
    a stack whole asks for; at the toys' own 128 every stack is sliced.
    Returns (spec, params in the kernels' layouts, planar params)."""
    cfg = dict(cells.load_config(name), hidden_size=256)
    if cfg["family"] == "smallthinker":
        cfg.update(moe_ffn_hidden_size=hidden, num_hidden_layers=4,
                   rope_layout=[0, 1, 1, 1], sliding_window_layout=[0, 1, 1, 1])
    else:
        cfg.update(intermediate_size=hidden, num_attention_heads=8,
                   num_key_value_heads=4)
    spec = cells.load_family(cfg["family"]).model_spec(cfg)
    params = W.to_program_params(W.make_weights(cfg, seed))
    return spec, prepare_for_pallas(params, spec=spec), params


def _expert_stack_reads(spec, params, b, t):
    """Of forward() traced with kernels on at (b, t): the shapes of the layer
    scan's sliced operands (its xs), and of every operand of a dynamic_slice
    outside the kernels' bodies."""
    rope = RopeTables.create(spec)
    kc, vc = init_kv_cache(spec, batch=b)
    jaxpr = jax.make_jaxpr(lambda p, toks, kc, vc: forward(
        p, spec, rope, toks, kc, vc, jnp.zeros((b,), jnp.int32),
        use_pallas=True))(params, jnp.zeros((b, t), jnp.int32), kc, vc)
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1  # the layer scan
    skip = scans[0].params["num_consts"] + scans[0].params["num_carry"]
    xs = [v.aval.shape for v in scans[0].invars[skip:]]
    sliced = []

    def walk(j):
        for e in j.eqns:
            if e.primitive.name == "dynamic_slice":
                sliced.append(e.invars[0].aval.shape)
            if e.primitive.name != "pallas_call":
                for sub in jax.core.jaxprs_in_params(e.params):
                    walk(sub)

    walk(jaxpr.jaxpr)
    return xs, sliced


# 3 of 8 and 2 of 4 experts a row: 512 rows (8 slots x a 64-token chunk) take
# the all-experts scan, 64 and 8 rows the grouped layer
@pytest.mark.parametrize("name,b,t", [
    ("tiny-smallthinker", 8, 64), ("tiny-smallthinker", 8, 8),
    ("tiny-smallthinker", 8, 1), ("tiny-moe", 8, 64), ("tiny-moe", 8, 8),
    ("tiny-moe", 8, 1), ("tiny-smallthinker", 1, 1), ("tiny-moe", 1, 1)])
def test_no_step_program_slices_an_expert_stack_for_a_kernel(name, b, t):
    """With kernels on at 2 to 512 rows the layer scan's xs hold no expert
    stack and nothing dynamic-slices one, whole or a layer of it: the
    kernels index it. One row (the matvec branch) slices as it always did."""
    from distributed_llama_tpu.models import forward as F

    spec, params, _ = _wide_toy(name)
    experts = {n: w for n, w in params["blocks"].items()
               if n.startswith("moe_")}
    assert set(experts) == {"moe_gu", "moe_down"}
    whole = {a.shape for w in experts.values() for a in (w.data, w.scales)}
    layer = {s[1:] for s in whole}
    assert F.takes_the_scan(b * t, spec.n_active_experts,
                            spec.n_experts) == (b * t == 512)
    xs, sliced = _expert_stack_reads(spec, params, b, t)
    if b * t == 1:
        assert whole <= set(xs) and layer & set(sliced)
    else:
        assert not whole & set(xs)
        assert not (whole | layer) & set(sliced)


@pytest.mark.parametrize("name,t", [("tiny-smallthinker", 8),
                                    ("tiny-moe", 64), ("tiny-moe", 8)])
def test_the_stack_read_in_place_gives_the_sliced_programs_logits(
        monkeypatch, name, t):
    """Grouped layer and all-experts scan: the program that indexes the
    whole stacks equals, bit for bit, the one that slices every layer (the
    rule answering no), and both stand by XLA's dequantize-then-dot."""
    from distributed_llama_tpu.models import forward as F

    spec, params, _ = _wide_toy(name)
    rope = RopeTables.create(spec)
    toks = jnp.asarray(np.random.default_rng(3).integers(
        3, spec.vocab_size, (8, t)))

    def run(**kw):
        kc, vc = init_kv_cache(spec, batch=8)
        return np.asarray(forward(params, spec, rope, toks, kc, vc,
                                  jnp.zeros((8,), jnp.int32), **kw)[0])

    whole = run(use_pallas=True)
    monkeypatch.setattr(F, "reads_the_stack", lambda w, m, use_pallas: False)
    np.testing.assert_array_equal(whole, run(use_pallas=True))
    # bf16 operands in the kernels; a row whose router is undecided may pick
    # another expert under them, so the bulk of the positions is held
    err = np.abs(whole - run(use_pallas=False)).max(axis=-1)
    assert np.quantile(err, 0.9) < 10 * KERNEL_TOL, np.quantile(err, 0.9)


@pytest.mark.parametrize("moe_sharding", ["slice", "expert"])
def test_tp2_with_the_stacks_read_in_place_matches_the_single_device_program(
        moe_sharding):
    """Kernels on under tp 2: the shard's (L, E / 2, ...) stack at the local
    expert index, or every expert's hidden slice, read in place per shard,
    against the single-device program on the kernels."""
    from distributed_llama_tpu.parallel.mesh import make_mesh
    from distributed_llama_tpu.parallel.tp import (init_sharded_kv_cache,
                                                   make_sharded_forward,
                                                   shard_params)

    # experts of 512: the hidden slice's half-plane is still a lane tile
    spec, pp1, params = _wide_toy("tiny-smallthinker", hidden=512)
    rope = RopeTables.create(spec)
    mesh = make_mesh(tp=2)
    pp = shard_params(prepare_for_pallas(params, tp=2, spec=spec,
                                         moe_sharding=moe_sharding),
                      mesh, spec, moe_sharding=moe_sharding)
    local = {n: tuple(s // (2 if ax == "tp" else 1) for s, ax in zip(
        w.data.shape, w.data.sharding.spec + (None,) * 4))
        for n, w in pp["blocks"].items() if n.startswith("moe_")}
    assert all(s[-1] % 128 == 0 for s in local.values()), local
    step = make_sharded_forward(spec, mesh, pp, use_pallas=True,
                                donate_cache=False, moe_sharding=moe_sharding)
    toks = jnp.asarray(np.random.default_rng(5).integers(
        3, spec.vocab_size, (1, 16)))
    kc, vc = init_sharded_kv_cache(spec, mesh)
    got, _, _ = step(pp, rope, toks, kc, vc, jnp.int32(0))
    kc1, vc1 = init_kv_cache(spec)
    want, _, _ = forward(pp1, spec, rope, toks, kc1, vc1, jnp.int32(0),
                         use_pallas=True)
    err = np.abs(np.asarray(got) - np.asarray(want)).max(axis=-1)
    assert np.quantile(err, 0.9) < KERNEL_TOL, err
