"""Fused 4-bit dequant-matmul (ops/pallas_q4_mm.py), interpret mode.

The kernel decodes i4p blocks in VMEM to the bf16 weights XLA's
`dequantize(dtype=bf16)` makes, bit for bit, and feeds the MXU: it must match
dequantize-then-dot to float32 summation order, the split-plane addressing
(one packed block covers both halves of K) must survive several K chunks, a
ragged last row block and TP sharding, and every caller reaches it through
`qmatmul(use_pallas=True)` with `use_pallas=False` as the oracle."""

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_tpu.models.forward import forward, init_kv_cache
from distributed_llama_tpu.models.params import (init_random_params,
                                                 prepare_for_pallas)
from distributed_llama_tpu.models.spec import ArchType, ModelSpec, RopeType
from distributed_llama_tpu.ops.matmul import (kernel_selections, qmatmul,
                                              reset_kernel_selections)
from distributed_llama_tpu.ops.pallas_q4_mm import (_pick_bn, pick_bk,
                                                    q4_matmul,
                                                    q4_mm_supported)
from distributed_llama_tpu.ops.rope import RopeTables
from distributed_llama_tpu.quants import FloatType, QTensor, scale_plane_cols


def _w(n, k, seed=0):
    rng = np.random.RandomState(seed)
    return QTensor.from_float(rng.randn(n, k).astype(np.float32) * 0.02,
                              FloatType.Q40).to_i4p_layout()


# (8, 96, 1024): one block, one K chunk; (3, 300, 2048): rows no tile
# divides, two chunks; (40, 640, 256): a ragged second row block (bn 512 of
# 640) and a chunk of 128; (4, 64, 4096): four chunks of 512; (8, 128,
# 2560): five chunks of 256, whose scales' lane tiles end in padding
@pytest.mark.parametrize("m,n,k", [(8, 96, 1024), (3, 300, 2048),
                                   (40, 640, 256), (4, 64, 4096),
                                   (8, 128, 2560)])
def test_q4_matmul_matches_dequant_dot(m, n, k):
    w = _w(n, k)
    assert q4_mm_supported(w, m)
    x = jnp.asarray(np.random.RandomState(1).randn(m, k).astype(np.float32))
    want = qmatmul(x.astype(jnp.bfloat16), w, use_pallas=False,
                   out_dtype=jnp.float32)
    got = q4_matmul(x, w, out_dtype=jnp.float32, interpret=True)
    # the same bf16 weights and products, summed in another order
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_decoded_weights_are_xlas_bf16_weights_bit_for_bit():
    """Each row of an identity matrix reads one column of the decoded
    weights back: they have to BE `dequantize(dtype=bf16)`, whose scales are
    rounded to bf16 before the product."""
    w = _w(192, 256, seed=3)
    got = q4_matmul(jnp.eye(256, dtype=jnp.bfloat16), w,
                    out_dtype=jnp.float32, interpret=True)
    want = np.asarray(w.dequantize(dtype=jnp.bfloat16).astype(jnp.float32))
    np.testing.assert_array_equal(np.asarray(got), want.T)


def _columns(k, n=96, seed=0):
    """Columns of K to read back: the first and the last quant block of each
    half-plane (where a scale's lane tile begins and where the plane's
    padding begins) and a draw of the rest."""
    edge = np.r_[0:32, k // 2 - 32:k // 2 + 32, k - 32:k]
    rest = np.random.RandomState(seed).choice(k, n, replace=False)
    return np.unique(np.r_[edge, rest])


def _decoded(w, cols, **kw):
    """Columns `cols` of the weights the kernel decodes, (len(cols), N): a
    one-hot row reads one column back through the MXU unchanged."""
    k = w.shape[-1]
    x = np.zeros((len(cols), k), np.float32)
    x[np.arange(len(cols)), cols] = 1.0
    return np.asarray(q4_matmul(jnp.asarray(x, jnp.bfloat16), w,
                                out_dtype=jnp.float32, interpret=True, **kw))


# K/32, the file's scales a row, at the cells' widths: an expert's down
# (K 768), LFM2's and SmallThinker's (1792, 2560, 3584), w2's 448; the plane
# the kernel reads holds them in whole lane tiles, zero columns behind them.
# 4096 / 32 is a lane tile as it is
@pytest.mark.parametrize("k,nb,cols", [(768, 24, 128), (1792, 56, 128),
                                       (2560, 80, 128), (3584, 112, 128),
                                       (14336, 448, 512), (4096, 128, 128)])
def test_decode_is_xlas_bit_for_bit_at_every_plane_width(k, nb, cols):
    """The scales are stored as the plane the kernel's block reads in place
    (`quants.to_scale_plane`): what it decodes is still `dequantize(dtype=
    bf16)` bit for bit, over a ragged second row block too."""
    w = _w(160, k, seed=nb)
    assert w.scales.shape == (160, cols) == (160, scale_plane_cols(nb))
    assert w.block_scales().shape == (160, nb)
    assert not np.asarray(w.scales)[:, nb:].any()  # the padding is zeros
    at = _columns(k, seed=nb)
    want = np.asarray(w.dequantize(dtype=jnp.bfloat16).astype(jnp.float32))
    np.testing.assert_array_equal(_decoded(w, at), want[:, at].T)


def test_decode_of_a_ragged_head_bit_for_bit():
    """SmallThinker's head cut small: 1187 rows of K 2560 in blocks of 384,
    the last one 35 rows that end before the block does."""
    w = _w(1187, 2560, seed=11)
    assert _pick_bn(1187, 1280) == 384 and w.scales.shape == (1187, 128)
    at = _columns(2560, n=32, seed=2)
    want = np.asarray(w.dequantize(dtype=jnp.bfloat16).astype(jnp.float32))
    np.testing.assert_array_equal(_decoded(w, at), want[:, at].T)


def test_decode_at_a_layer_and_an_expert_of_a_padded_stack_bit_for_bit():
    """(layer 1, expert 2) of a (2, 3, 256, 768) stack, whose planes are
    (2, 3, 256, 128) for 24 scales a row: the block index maps name the
    same matrix in the nibbles and in the plane."""
    rng = np.random.RandomState(9)
    w = QTensor.from_float(rng.randn(2, 3, 256, 768).astype(np.float32)
                           * 0.02, FloatType.Q40).to_i4p_layout()
    assert w.scales.shape == (2, 3, 256, 128)
    at = _columns(768, n=32, seed=3)
    want = np.asarray(w.dequantize(dtype=jnp.bfloat16).astype(jnp.float32))
    got = _decoded(w, at, at=(jnp.int32(1), jnp.int32(2)))
    np.testing.assert_array_equal(got, want[1, 2][:, at].T)
    assert np.abs(got - want[0, 2][:, at].T).max() > 1e-3


@pytest.mark.parametrize("at", [(0, 0), (1, 2), (2, 3), (1,)])
def test_q4_matmul_at_layer_and_expert_of_the_stack_equals_the_slices(at):
    """(layer, expert) of an (L, E, N, K/2) stack, or the layer of an
    (L, N, K/2) one, read in place through the prefetched indices against
    the slice handed in alone: the same blocks at another address, so bit
    for bit; `qmatmul` takes the same way from a `LayerOf`."""
    from distributed_llama_tpu.ops.matmul import LayerOf

    shape = (3, 4)[:len(at)]
    rng = np.random.RandomState(5)
    w = QTensor.from_float(rng.randn(*shape, 300, 512).astype(np.float32)
                           * 0.02, FloatType.Q40).to_i4p_layout()
    assert w.data.shape == (*shape, 300, 256)
    x = jnp.asarray(rng.randn(8, 512).astype(np.float32))
    one = LayerOf(w, tuple(jnp.int32(i) for i in at)).one()
    assert one.data.shape == (300, 256)
    assert q4_mm_supported(w, 8, stacked=len(at))
    assert not q4_mm_supported(w, 8, stacked=len(at) - 1)
    want = np.asarray(q4_matmul(x, one, out_dtype=jnp.float32,
                                interpret=True))
    got = q4_matmul(x, w, at=tuple(jnp.int32(i) for i in at),
                    out_dtype=jnp.float32, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), want)
    reset_kernel_selections()
    got = qmatmul(x, LayerOf(w, tuple(jnp.int32(i) for i in at)),
                  use_pallas=True, out_dtype=jnp.float32)
    assert set(kernel_selections().values()) == {"q4_mm"}
    np.testing.assert_array_equal(np.asarray(got), want)
    # one row is the matvec kernel's: it takes the slice
    y1 = qmatmul(x[:1], LayerOf(w, tuple(jnp.int32(i) for i in at)),
                 use_pallas=False, out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(y1), want[:1], atol=2e-2)


def test_q4_mm_supported_gates():
    w = _w(64, 1024, seed=1)
    assert q4_mm_supported(w, 2) and q4_mm_supported(w, 512)
    assert not q4_mm_supported(w, 1)  # one row is the matvec kernel's
    assert not q4_mm_supported(w, 513)  # more rows than the blocks hold
    assert not q4_mm_supported(_w(64, 576), 8)  # K/2 = 288: no lane tile
    w8 = QTensor.from_float(np.zeros((64, 1024), np.float32),
                            FloatType.Q80).to_i8_layout()
    assert not q4_mm_supported(w8, 8)  # another layout
    stacked = QTensor(FloatType.Q40, np.zeros((2, 64, 512), np.uint8),
                      np.zeros((2, 64, 128), np.int16), layout="i4p")
    assert not q4_mm_supported(stacked, 8)  # the grouped kernels' stacks
    with pytest.raises(ValueError, match="q4_mm_supported"):
        q4_matmul(jnp.ones((1, 1024), jnp.bfloat16), w, interpret=True)


# what `prepare_for_pallas` makes of a weight whose K (1024) is over the
# one-row matvec's bound, lowered to 512 (conftest.py): name -> (the file's
# type, the spec's shape, the tensor of the prepared params, its layout)
OVER_THE_BOUND = {
    # split-plane Q40 of a layer: the dequant-matmul reads the pack
    "w2": (FloatType.Q40, "dense", lambda p: p["blocks"]["w2"], "i4p"),
    # under the bound nothing changed: merged and packed
    "w13": (FloatType.Q40, "dense", lambda p: p["blocks"]["w13"], "i4p"),
    # int8 planes are the matvec's alone: a Q80 weight over its bound stays
    "w2-q80": (FloatType.Q80, "dense", lambda p: p["blocks"]["w2"], "planar"),
    "w13-q80": (FloatType.Q80, "dense", lambda p: p["blocks"]["w13"], "i8"),
    # the head and an expert stack keep the matvec's gate
    "head": (FloatType.Q40, "wide", lambda p: p["wcls"], "planar"),
    "experts": (FloatType.Q40, "moe", lambda p: p["blocks"]["moe_down"],
                "planar"),
    # a K the split-plane pack takes (64 | 1088) in no whole lane tiles
    # (256 does not divide it): no kernel reads it at any number of rows
    "w2-unaligned": (FloatType.Q40, "unaligned",
                     lambda p: p["blocks"]["w2"], "planar"),
}


@pytest.mark.parametrize("case", list(OVER_THE_BOUND))
def test_the_pack_asks_the_kernels_that_read_it(matvec_bound_under_1024,
                                                case):
    """`models/params._kernel_convertible`: a Q40 matrix of the layers is
    packed where ANY kernel reads the pack at some number of rows (the
    dequant-matmul at 2 to 512 whatever K is, `q4_mm_reads`), and the
    one-row matvec's bound decides only what that kernel alone reads."""
    ftype, shape, pick, layout = OVER_THE_BOUND[case]
    kw = dict(arch_type=ArchType.LLAMA, dim=512, hidden_dim=1024, n_layers=1,
              n_heads=4, n_kv_heads=4, vocab_size=64, seq_len=32,
              rope_type=RopeType.LLAMA)
    if shape == "wide":
        kw.update(dim=1024, hidden_dim=512)
    elif shape == "unaligned":
        kw.update(hidden_dim=1088)
    elif shape == "moe":
        kw.update(arch_type=ArchType.MIXTRAL, n_experts=2,
                  n_active_experts=1)
    spec = ModelSpec(**kw).resolved()
    params = init_random_params(spec, ftype, seed=3)
    got = pick(prepare_for_pallas(params, spec=spec))
    assert got.layout == layout, (case, got.layout, got.shape)
    assert got.shape[-1] == (1088 if shape == "unaligned" else
                             512 if case.startswith("w13") else 1024)


@pytest.mark.parametrize("n,k,bn,bk", [
    (4096, 4096, 256, 512),      # Mistral wo: 512 KiB blocks of 256 rows
    (4096, 14336, 128, 512),     # w2: 7168 packed columns a row, 14 chunks
    (151936, 2560, 384, 256),    # SmallThinker's head: a ragged last block
    (2560, 3584, 256, 256),      # its wo: 1792 packed columns, 7 chunks
    (2048, 768, 512, 128),       # an expert's down: 384 packed columns
    (64, 1024, 64, 512)])        # fewer rows than a lane tile
def test_blocks_follow_the_shapes(n, k, bn, bk):
    assert (_pick_bn(n, k // 2), pick_bk(k // 2)) == (bn, bk)
    assert bn * (k // 2) <= 1 << 20


def test_a_stack_that_fits_the_faster_memory_gets_no_ragged_block():
    """granite-4.0-h-small's `ssm_in` (16768 rows, K/2 2048): nine layers
    of it are 309 MB and keep blocks of 256 with a ragged 66th; the check's
    one-layer cut is 34 MB, which XLA may keep in VMEM, where a block that
    ends past the array is not safe: 128 rows, 131 whole blocks. A.X-K1's
    576-row `wkv_a` has no lane-aligned divisor and stays as it was."""
    assert _pick_bn(16768, 2048, 9) == 256 and 16768 % 256
    assert _pick_bn(16768, 2048, 1) == 128
    assert _pick_bn(151936, 1280) == 384  # SmallThinker's head: 194 MB
    assert _pick_bn(576, 3584, 6) == 128 and _pick_bn(4096, 2048, 1) == 256
    w = _w(384, 8192, seed=4)  # bn 128 of 384 either way
    x = jnp.asarray(np.random.RandomState(1).randn(8, 8192).astype(np.float32))
    want = qmatmul(x.astype(jnp.bfloat16), w, use_pallas=False,
                   out_dtype=jnp.float32)
    got = q4_matmul(x, w, out_dtype=jnp.float32, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def _spec():
    return ModelSpec(arch_type=ArchType.LLAMA, dim=1024, hidden_dim=1024,
                     n_layers=2, n_heads=8, n_kv_heads=8, vocab_size=256,
                     seq_len=32, rope_type=RopeType.LLAMA).resolved()


def test_prefill_forward_kernel_matches_xla_path():
    """T=8 prefill through use_pallas=True (the dequant-matmul at M=8) == the
    XLA dequant path over the same prepared weights, which off the chip
    multiplies float32 activations by float32 weights: bf16's distance."""
    spec = _spec()
    params = init_random_params(spec, FloatType.Q40, seed=7)
    rope = RopeTables.create(spec)
    pp = prepare_for_pallas(params, spec=spec)

    tokens = jnp.asarray([[1, 5, 9, 2, 7, 4, 3, 8]])
    kc, vc = init_kv_cache(spec)
    want, _, _ = forward(pp, spec, rope, tokens, kc, vc, jnp.int32(0),
                         use_pallas=False)
    reset_kernel_selections()
    kc, vc = init_kv_cache(spec)
    got, _, _ = forward(pp, spec, rope, tokens, kc, vc, jnp.int32(0),
                        use_pallas=True)
    assert set(kernel_selections().values()) == {"q4_mm"}
    got, want = np.asarray(got), np.asarray(want)
    rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
    assert rel < 0.02, rel


def test_prefill_kernel_sharded_matches():
    """tp=2 shard_map prefill with the kernel (col-sharded wo/w2 localize to
    groups=1 self-contained packs) == the planar sharded step. The localized
    shard widths must actually take the kernel, or this test would pass
    vacuously through XLA."""
    from distributed_llama_tpu.parallel.mesh import make_mesh
    from distributed_llama_tpu.parallel.tp import (init_sharded_kv_cache,
                                                   make_sharded_forward,
                                                   shard_params)

    spec = _spec()
    # col-sharded wo/w2 local half-plane width (K/tp)/2: whole lane tiles
    assert spec.dim // 2 // 2 % 128 == 0 and spec.hidden_dim // 2 // 2 % 128 == 0
    params = init_random_params(spec, FloatType.Q40, seed=3)
    mesh = make_mesh(tp=2)
    tokens = jnp.asarray([[1, 5, 9, 2]])
    rope = RopeTables.create(spec)

    base = shard_params(params, mesh, spec)
    step = make_sharded_forward(spec, mesh, base, donate_cache=False)
    kc, vc = init_sharded_kv_cache(spec, mesh)
    want, _, _ = step(base, rope, tokens, kc, vc, jnp.int32(0))

    pp = shard_params(prepare_for_pallas(params, tp=2, spec=spec), mesh, spec)
    reset_kernel_selections()
    stepp = make_sharded_forward(spec, mesh, pp, use_pallas=True,
                                 donate_cache=False)
    kc, vc = init_sharded_kv_cache(spec, mesh)
    got, _, _ = stepp(pp, rope, tokens, kc, vc, jnp.int32(0))
    assert set(kernel_selections().values()) == {"q4_mm"}
    got, want = np.asarray(got), np.asarray(want)
    rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
    assert rel < 0.02, rel


def test_engine_prefill_goes_through_the_kernel():
    """End-to-end: Engine(use_pallas=True) prefills its chunk through the
    dequant-matmul and decodes through the matvec; the prompt's greedy
    continuation is the kernel-off engine's."""
    from distributed_llama_tpu.runtime.engine import Engine
    from distributed_llama_tpu.runtime.sampler import Sampler

    spec = _spec()
    params = init_random_params(spec, FloatType.Q40, seed=13)
    base = Engine(spec, params, tp=1, use_pallas=False)
    prompt = [1, 7, 3, 9, 2, 11, 4, 6, 5]  # a chunk of 8 rows and a step
    want, _ = base.generate(prompt, 6,
                            Sampler(spec.vocab_size, temperature=0.0))
    reset_kernel_selections()
    eng = Engine(spec, params, tp=1, use_pallas=True)
    got, _ = eng.generate(prompt, 6,
                          Sampler(spec.vocab_size, temperature=0.0))
    assert {"q4_mm", "q4_matvec"} <= set(kernel_selections().values())
    assert got == want


def test_batch_engine_with_the_kernel_matches():
    """Batched decode (B=2 slots) engages the dequant-matmul at m=B>1; tokens
    must match the kernel-off batched engine exactly."""
    from distributed_llama_tpu.runtime.batch_engine import BatchEngine
    from distributed_llama_tpu.runtime.sampler import Sampler

    spec = _spec()
    params = init_random_params(spec, FloatType.Q40, seed=5)
    prompts = [[1, 7, 23, 5], [1, 9, 2]]

    def run(**kw):
        be = BatchEngine(spec, params, slots=2, tp=2, **kw)
        try:
            reqs = [be.submit(list(p), 6, Sampler(spec.vocab_size, temperature=0.0))
                    for p in prompts]
            return [r.wait(timeout=180) for r in reqs]
        finally:
            be.close()

    want = run(use_pallas=False)
    reset_kernel_selections()
    got = run(use_pallas=True)
    assert "q4_mm" in set(kernel_selections().values())
    assert got == want


def test_a_second_step_program_traces_no_chunk_again(monkeypatch):
    """The body is unrolled over K, and what it unrolls is one jitted chunk
    (`_chunk_product`), traced once a process per (rows, bn, bk): a step of
    ANOTHER attention window (another program, the same matmuls) and an
    engine of ANOTHER depth (the output check's cuts) lower their call
    sites (`q4_mm_bodies_lowered_total` counts them) and trace no chunk."""
    from distributed_llama_tpu.ops import pallas_q4_mm as mm
    from distributed_llama_tpu.parallel.mesh import make_mesh
    from distributed_llama_tpu.parallel.tp import (init_sharded_kv_cache,
                                                   make_sharded_forward,
                                                   shard_params)

    mesh = make_mesh(tp=1)
    rope = RopeTables.create(_spec())
    tokens = jnp.asarray([[1, 5, 9, 2, 7]])  # five rows: no other test's

    def run(window, n_layers=2):
        import dataclasses

        spec = dataclasses.replace(_spec(), n_layers=n_layers)
        params = init_random_params(spec, FloatType.Q40, seed=17)
        pp = shard_params(prepare_for_pallas(params, spec=spec, mesh=mesh),
                          mesh, spec)
        step = make_sharded_forward(spec, mesh, pp, use_pallas=True,
                                    donate_cache=False, attn_window=window)
        kc, vc = init_sharded_kv_cache(spec, mesh)
        return np.asarray(step(pp, rope, tokens, kc, vc, jnp.int32(0))[0])

    spreads = []  # `_spread` runs only while a chunk is being traced

    def counted(*a, spread=mm._spread):
        spreads.append(a[0].shape)
        return spread(*a)

    monkeypatch.setattr(mm, "_spread", counted)
    first = run(16)
    sites, chunks = mm._BODIES_LOWERED.value, len(spreads)
    assert chunks >= 2  # a chunk's two planes
    second = run(32)
    run(16, n_layers=3)
    assert mm._BODIES_LOWERED.value - sites >= 2 * 4  # wqkv, wo, w13, w2
    assert len(spreads) == chunks
    np.testing.assert_array_equal(first, second)
