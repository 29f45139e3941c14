"""Pallas q4 (split-plane packed nibble) kernel tests — interpret mode on CPU.

The i4p layout keeps the reference's exact Q40 HBM density (src/quants.hpp:17-20);
these tests pin (a) the layout round-trip, (b) the column-group packing that makes
in-axis TP slices self-contained, (c) kernel-vs-oracle numerics, and (d) the windowed
forward being exactly equivalent to the full-cache forward.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from distributed_llama_tpu.models.forward import forward, init_kv_cache
from distributed_llama_tpu.models.params import init_random_params, prepare_for_pallas
from distributed_llama_tpu.models.spec import ArchType, ModelSpec, RopeType
from distributed_llama_tpu.ops.pallas_q4 import q4_matvec
from distributed_llama_tpu.ops.rope import RopeTables
from distributed_llama_tpu.quants import QK, FloatType, QTensor, scale_plane_cols


def _to_jnp(t: QTensor) -> QTensor:
    return jax.tree_util.tree_map(jnp.asarray, t)


def test_f16_bits_decode_exhaustive():
    """The in-kernel f16-bits->f32 decode (_f16_bits_to_f32) must be bit-exact for
    EVERY finite f16 pattern — including subnormals and signed zeros — because the
    i4p layout ships the reference's Q40 deltas as raw int16 bit patterns. (The
    magic-multiply half->float trick fails this on TPU hardware: the VPU flushes
    subnormal f32 intermediates; the integer-math decode keeps every intermediate
    normal. Verified on a real v5e in round 4; this pins the math in interpret.)"""
    from distributed_llama_tpu.ops.pallas_q4 import _f16_bits_to_f32

    allbits = np.arange(65536, dtype=np.uint16)
    finite = ((allbits >> 10) & 0x1F) != 31  # exclude inf/nan (never valid deltas)
    got = np.asarray(jax.jit(_f16_bits_to_f32)(jnp.asarray(allbits.view(np.int16))))
    want = allbits.view(np.float16).astype(np.float32)
    np.testing.assert_array_equal(got[finite], want[finite])


def test_i4p_roundtrip_exact():
    rng = np.random.RandomState(3)
    w = QTensor.from_float(rng.randn(64, 256).astype(np.float32), FloatType.Q40)
    wi = w.to_i4p_layout()
    assert wi.data.shape == (64, 128) and wi.scales.dtype == np.int16
    np.testing.assert_array_equal(wi.to_numpy(), w.to_numpy())
    np.testing.assert_allclose(np.asarray(wi.dequantize(jnp.float32)), w.to_numpy(),
                               atol=1e-6)


def test_i4p_col_groups_make_shards_self_contained():
    """Slicing a col_groups=G i4p tensor along the packed axis into G parts must give
    each shard the exact i4p pack of its own natural column slice — the property that
    lets device_put shard in-axis (ColMatmulSlice) weights without repacking."""
    rng = np.random.RandomState(4)
    n, k, g = 16, 512, 4
    w = QTensor.from_float(rng.randn(n, k).astype(np.float32), FloatType.Q40)
    grouped = w.to_i4p_layout(col_groups=g)
    full = w.to_numpy()
    # a shard's part of the scales' plane: its own K/32 columns in whole
    # lane tiles (quants.to_scale_plane pads within each column group)
    kl, khl, cols = k // g, k // (2 * g), grouped.scales.shape[1] // g
    assert cols == scale_plane_cols(k // QK // g) == 128
    for s in range(g):
        shard = QTensor(grouped.ftype, grouped.data[:, s * khl:(s + 1) * khl],
                        grouped.scales[:, s * cols:(s + 1) * cols], layout="i4p")
        np.testing.assert_array_equal(shard.to_numpy(), full[:, s * kl:(s + 1) * kl])


def test_q4_matvec_matches_oracle():
    rng = np.random.RandomState(7)
    n, k = 128, 512
    w = QTensor.from_float((rng.randn(n, k) * 0.05).astype(np.float32), FloatType.Q40)
    wi = _to_jnp(w.to_i4p_layout())
    x = jnp.asarray(rng.randn(1, k).astype(np.float32)).astype(jnp.bfloat16)
    want = np.asarray(x, np.float32) @ w.to_numpy().T
    got = np.asarray(q4_matvec(x, wi, interpret=True), np.float32)
    rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
    assert rel < 0.02, rel  # Q80 activation quantization error scale


@pytest.mark.parametrize("inline", [False, True], ids=["xexp", "inline"])
@pytest.mark.parametrize("k,nb", [(768, 24), (1792, 56), (2560, 80),
                                  (3584, 112), (14336, 448), (4096, 128)])
def test_q4_matvec_reads_the_planes_own_columns_bit_for_bit(k, nb, inline):
    """The one-row kernel on the scales' plane as stored, K/32 columns in
    whole lane tiles: an activation row that lives in ONE quant block leaves
    one term a weight row, scale x activation scale x integer dot in
    float32, which NumPy gives bit for bit from the file's own scales; the
    first and last block of each half-plane, where the plane's padding and
    the halves meet. 160 rows: a ragged second row block."""
    from distributed_llama_tpu.ops.pallas_q8 import _quantize_row

    rng = np.random.RandomState(nb)
    w = QTensor.from_float((rng.randn(160, k) * 0.05).astype(np.float32),
                           FloatType.Q40)
    wi = _to_jnp(w.to_i4p_layout())
    assert wi.scales.shape == (160, scale_plane_cols(nb))
    q = np.concatenate([(w.data & 0x0F), (w.data >> 4)], axis=-1).astype(
        np.int32).reshape(160, k) - 8  # the file's nibbles, natural order
    for b in (0, nb // 2 - 1, nb // 2, nb - 1):
        x = np.zeros((1, k), np.float32)
        x[0, b * QK:(b + 1) * QK] = rng.randn(QK)
        xq, sx = _quantize_row(jnp.asarray(x[0]), nb)
        p = q[:, b * QK:(b + 1) * QK] @ np.asarray(xq, np.int32)[
            b * QK:(b + 1) * QK]
        want = (w.scales[:, b].astype(np.float32) * np.asarray(sx)[0, b]
                ) * p.astype(np.float32)
        got = q4_matvec(jnp.asarray(x), wi, out_dtype=jnp.float32,
                        interpret=True, inline_xexp=inline)
        np.testing.assert_array_equal(np.asarray(got)[0], want)


def test_q4_matvec_agrees_with_q8_kernel():
    """Same weights through the 4-bit packed kernel and the int8-plane kernel must be
    bit-identical modulo f16-vs-f32 scale precision (both quantize activations to the
    same Q80 blocks)."""
    from distributed_llama_tpu.ops.pallas_q8 import q8_matvec

    rng = np.random.RandomState(9)
    n, k = 64, 256
    w = QTensor.from_float((rng.randn(n, k) * 0.05).astype(np.float32), FloatType.Q40)
    x = jnp.asarray(rng.randn(1, k).astype(np.float32)).astype(jnp.bfloat16)
    y4 = np.asarray(q4_matvec(x, _to_jnp(w.to_i4p_layout()), interpret=True), np.float32)
    y8 = np.asarray(q8_matvec(x, _to_jnp(w.to_i8_layout()), interpret=True), np.float32)
    np.testing.assert_allclose(y4, y8, rtol=2e-3, atol=1e-5)


def test_q4_matvec_requires_i4p_layout():
    w = QTensor.from_float(np.ones((8, 64), np.float32), FloatType.Q40)
    with pytest.raises(ValueError, match="i4p"):
        q4_matvec(jnp.ones((1, 64)), w, interpret=True)


def test_prepare_for_pallas_picks_i4p_for_q40():
    spec = ModelSpec(arch_type=ArchType.LLAMA, dim=64, hidden_dim=128, n_layers=2,
                     n_heads=4, n_kv_heads=2, vocab_size=128, seq_len=16,
                     rope_type=RopeType.LLAMA).resolved()
    params = init_random_params(spec, FloatType.Q40, seed=7)
    pp = prepare_for_pallas(params, tp=2, spec=spec)
    # QKV and gate/up merge into single row-concatenated tensors (fuse_matvec_groups)
    assert pp["blocks"]["wqkv"].layout == "i4p" and pp["blocks"]["wqkv"].groups == 1
    assert pp["blocks"]["wqkv"].shape[1] == spec.dim + 2 * spec.kv_dim
    assert pp["blocks"]["w13"].shape[1] == 2 * spec.hidden_dim
    assert "wq" not in pp["blocks"] and "w1" not in pp["blocks"]
    assert pp["blocks"]["w2"].layout == "i4p" and pp["blocks"]["w2"].groups == 2
    assert pp["wcls"].layout == "i4p"
    # Q80 weights keep the int8-plane layout (no 4-bit repack possible)
    p80 = prepare_for_pallas(init_random_params(spec, FloatType.Q80, seed=7), tp=1)
    assert p80["blocks"]["wqkv"].layout == "i8"


def test_sharded_forward_with_i4p_params():
    """tp=2 shard_map over grouped-i4p params (the col-sharded w2/wo carry groups=tp in
    their pytree aux): shard_params + the jitted step must run and match the planar
    TP step. Regression test for the groups-aux pytree mismatch."""
    from distributed_llama_tpu.parallel.mesh import make_mesh
    from distributed_llama_tpu.parallel.tp import (init_sharded_kv_cache,
                                                   make_sharded_forward, shard_params)

    spec = ModelSpec(arch_type=ArchType.LLAMA, dim=64, hidden_dim=128, n_layers=2,
                     n_heads=4, n_kv_heads=2, vocab_size=128, seq_len=16,
                     rope_type=RopeType.LLAMA).resolved()
    params = init_random_params(spec, FloatType.Q40, seed=3)
    mesh = make_mesh(tp=2)
    tokens = jnp.asarray([[1, 2, 3]])

    base = shard_params(params, mesh, spec)
    step = make_sharded_forward(spec, mesh, base, donate_cache=False)
    kc, vc = init_sharded_kv_cache(spec, mesh)
    want, _, _ = step(base, RopeTables.create(spec), tokens, kc, vc, jnp.int32(0))

    pp = shard_params(prepare_for_pallas(params, tp=2), mesh, spec)
    assert pp["blocks"]["w2"].groups == 2
    stepp = make_sharded_forward(spec, mesh, pp, donate_cache=False)
    kc, vc = init_sharded_kv_cache(spec, mesh)
    got, _, _ = stepp(pp, RopeTables.create(spec), tokens, kc, vc, jnp.int32(0))
    # prefill goes through the XLA dequant path; i4p dequant must match planar exactly
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4, rtol=2e-4)


def test_moe_decode_kernel_path_matches_planar():
    """Mixtral decode with i4p expert stacks (the kernel path slices each active
    expert's packed planes with dynamic_slice) must match the planar gather path at
    Q80 activation-quantization error scale."""
    spec = ModelSpec(arch_type=ArchType.MIXTRAL, dim=64, hidden_dim=128, n_layers=2,
                     n_heads=4, n_kv_heads=2, vocab_size=128, seq_len=16,
                     n_experts=4, n_active_experts=2,
                     rope_type=RopeType.FALCON).resolved()
    params = init_random_params(spec, FloatType.Q40, seed=13)
    rope = RopeTables.create(spec)
    pp = prepare_for_pallas(params)
    # up+gate merge into the moe_gu stack (fuse_matvec_groups)
    assert pp["blocks"]["moe_gu"].layout == "i4p"
    assert pp["blocks"]["moe_gu"].shape[-2] == 2 * spec.hidden_dim

    tok = jnp.asarray([[5]])
    kc, vc = init_kv_cache(spec)
    want, _, _ = forward(params, spec, rope, tok, kc, vc, jnp.int32(0))
    kc, vc = init_kv_cache(spec)
    got, _, _ = forward(pp, spec, rope, tok, kc, vc, jnp.int32(0), use_pallas=True)
    got, want = np.asarray(got), np.asarray(want)
    rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
    assert rel < 0.03, rel


def test_windowed_forward_equals_full():
    """attn_window >= pos+T must give EXACTLY the full-cache forward's logits — the
    positions mask already hides everything past pos, the window only trims dead reads."""
    spec = ModelSpec(arch_type=ArchType.LLAMA, dim=64, hidden_dim=96, n_layers=2,
                     n_heads=4, n_kv_heads=2, vocab_size=128, seq_len=64,
                     rope_type=RopeType.LLAMA).resolved()
    params = init_random_params(spec, FloatType.F32, seed=5)
    rope = RopeTables.create(spec)
    tokens = jnp.asarray([[9, 2, 17, 4, 31]])

    kc, vc = init_kv_cache(spec)
    want, kcf, vcf = forward(params, spec, rope, tokens, kc, vc, jnp.int32(0))
    kc, vc = init_kv_cache(spec)
    got, kcw, vcw = forward(params, spec, rope, tokens, kc, vc, jnp.int32(0),
                            attn_window=16)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the cache itself is identical (same writes, windowing only affects reads)
    np.testing.assert_array_equal(np.asarray(kcw), np.asarray(kcf))

    # decode continuation at pos=5 with a window still matches
    tok = jnp.asarray([[7]])
    want2, _, _ = forward(params, spec, rope, tok, kcf, vcf, jnp.int32(5))
    got2, _, _ = forward(params, spec, rope, tok, kcw, vcw, jnp.int32(5),
                         attn_window=16)
    np.testing.assert_array_equal(np.asarray(got2), np.asarray(want2))


def test_q4_inline_xexp_matches_standard(monkeypatch):
    """The scratch-built Xexp variant must produce bit-identical results to the
    HBM-materialized one (same int8 quantization, same dots) — across a MULTI-step
    grid, so the build-at-step-0/reuse-later scratch mechanism is actually exercised."""
    import distributed_llama_tpu.ops.pallas_q4 as pq4

    monkeypatch.setattr(pq4, "_pick_bn", lambda n, k, budget_bytes=0: 128)
    rng = np.random.RandomState(21)
    n, k = 512, 512  # grid = 4 row blocks
    w = QTensor.from_float((rng.randn(n, k) * 0.05).astype(np.float32), FloatType.Q40)
    wi = _to_jnp(w.to_i4p_layout())
    x = jnp.asarray(rng.randn(1, k).astype(np.float32)).astype(jnp.bfloat16)
    y0 = np.asarray(q4_matvec(x, wi, interpret=True, inline_xexp=False))
    y1 = np.asarray(q4_matvec(x, wi, interpret=True, inline_xexp=True))
    np.testing.assert_array_equal(y0, y1)
