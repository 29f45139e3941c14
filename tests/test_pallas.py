"""Pallas q8 kernel tests (interpret mode on the CPU mesh).

The fused int8-plane matvec must agree with the planar jnp path (which the golden tests
tie to the numpy oracle): i8 layout round-trip, TP slicing of the layout along both axes,
the matvec against the dequant oracle, and the full forward pass with prepared params.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from distributed_llama_tpu.models.forward import forward, init_kv_cache
from distributed_llama_tpu.models.params import init_random_params, prepare_for_pallas
from distributed_llama_tpu.models.spec import ArchType, ModelSpec, RopeType
from distributed_llama_tpu.ops.pallas_q8 import q8_matvec
from distributed_llama_tpu.ops.rope import RopeTables
from distributed_llama_tpu.quants import QK, FloatType, QTensor


def _to_jnp(t: QTensor) -> QTensor:
    return jax.tree_util.tree_map(jnp.asarray, t)


@pytest.mark.parametrize("ftype", [FloatType.Q40, FloatType.Q80])
def test_i8_layout_roundtrip(ftype):
    rng = np.random.RandomState(3)
    w = QTensor.from_float(rng.randn(64, 256).astype(np.float32), ftype)
    wi = w.to_i8_layout()
    np.testing.assert_allclose(wi.to_numpy(), w.to_numpy(), atol=1e-7)
    np.testing.assert_allclose(np.asarray(wi.dequantize(jnp.float32)), w.to_numpy(),
                               atol=1e-6)


def test_i8_layout_slices_both_axes():
    """Row (out) and col (in) slices of the i8 layout dequantize to the matching slices
    of the full tensor — the property TP sharding relies on (no per-shard segmenting)."""
    rng = np.random.RandomState(4)
    n, k, shards = 16, 512, 4
    w = QTensor.from_float(rng.randn(n, k).astype(np.float32), FloatType.Q40)
    wi = w.to_i8_layout()
    full = w.to_numpy()
    for s in range(shards):
        row = QTensor(wi.ftype, wi.data[s * (n // shards):(s + 1) * (n // shards)],
                      wi.scales[s * (n // shards):(s + 1) * (n // shards)], layout="i8")
        np.testing.assert_allclose(row.to_numpy(),
                                   full[s * (n // shards):(s + 1) * (n // shards)],
                                   atol=1e-7)
        kl, nbl = k // shards, (k // QK) // shards
        col = QTensor(wi.ftype, wi.data[:, s * kl:(s + 1) * kl],
                      wi.scales[:, s * nbl:(s + 1) * nbl], layout="i8")
        np.testing.assert_allclose(col.to_numpy(), full[:, s * kl:(s + 1) * kl],
                                   atol=1e-7)


def test_q8_matvec_precise_interpret():
    """f32 activations take the precise path: must match the dequant-matmul oracle."""
    rng = np.random.RandomState(6)
    n, k = 128, 512
    w = QTensor.from_float((rng.randn(n, k) * 0.05).astype(np.float32), FloatType.Q40)
    wi = _to_jnp(w.to_i8_layout())
    x = jnp.asarray(rng.randn(1, k).astype(np.float32))
    want = np.asarray(x) @ w.to_numpy().T
    got = np.asarray(q8_matvec(x, wi, interpret=True, precise=True))
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


def test_q8_matvec_int8_interpret():
    """bf16 activations take the Q80-quantized int8 MXU path: same numerics as the
    reference's Q40xQ80 kernel (activations rounded per-32-block to int8)."""
    rng = np.random.RandomState(7)
    n, k = 128, 512
    w = QTensor.from_float((rng.randn(n, k) * 0.05).astype(np.float32), FloatType.Q40)
    wi = _to_jnp(w.to_i8_layout())
    x = jnp.asarray(rng.randn(1, k).astype(np.float32)).astype(jnp.bfloat16)
    want = np.asarray(x, np.float32) @ w.to_numpy().T
    got = np.asarray(q8_matvec(x, wi, interpret=True), np.float32)
    rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
    assert rel < 0.02, rel  # Q80 activation quantization error


def test_q8_matvec_requires_i8_layout():
    w = QTensor.from_float(np.ones((8, 64), np.float32), FloatType.Q40)
    with pytest.raises(ValueError, match="i8-layout"):
        q8_matvec(jnp.ones((1, 64)), w, interpret=True)


def test_forward_with_pallas_params():
    """Full dense forward with prepare_for_pallas'd weights (interpret mode). T=1 decode
    exercises the kernel (int8 Q80-quantized activations, so compare at Q80 error
    scale); the T=3 prefill goes through the XLA dequant path and matches tightly."""
    spec = ModelSpec(arch_type=ArchType.LLAMA, dim=64, hidden_dim=96, n_layers=2,
                     n_heads=4, n_kv_heads=2, vocab_size=128, seq_len=16,
                     rope_type=RopeType.LLAMA).resolved()
    params = init_random_params(spec, FloatType.Q40, seed=7)
    rope = RopeTables.create(spec)
    pparams = prepare_for_pallas(params)

    for tokens, rel_tol in ((jnp.asarray([[1, 2, 3]]), 1e-5), (jnp.asarray([[5]]), 0.03)):
        kc, vc = init_kv_cache(spec)
        want, _, _ = forward(params, spec, rope, tokens, kc, vc, jnp.int32(0))
        kc, vc = init_kv_cache(spec)
        got, _, _ = forward(pparams, spec, rope, tokens, kc, vc, jnp.int32(0),
                            use_pallas=True)
        got, want = np.asarray(got), np.asarray(want)
        rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
        assert rel < rel_tol, rel


def test_q8_inline_matvec_matches_xexp_variant():
    """The i8 inline-Xexp matvec (scratch scatter) must reproduce the
    Xexp-materializing variant exactly — same int8 dot, same epilogue."""
    from distributed_llama_tpu.ops.pallas_q8 import (_q8_matvec,
                                                     _q8_matvec_inline,
                                                     block_diag_scatter)

    rng = np.random.RandomState(3)
    n, k = 48, 256
    nb = k // QK
    xq = jnp.asarray(rng.randint(-127, 128, (1, k)).astype(np.int8))
    sx = jnp.asarray(rng.rand(1, nb).astype(np.float32) * 0.01)
    w8 = jnp.asarray(rng.randint(-8, 8, (n, k)).astype(np.int8))
    scales = jnp.asarray(rng.rand(n, nb).astype(np.float32) * 0.01)

    xexp = block_diag_scatter(xq.reshape(k), nb)
    want = _q8_matvec(xexp, sx, w8, scales, interpret=True)
    got = _q8_matvec_inline(xq, sx, w8, scales, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
