"""Fused 4-bit dequant-matmul (ops/pallas_q4_mm.py), interpret mode.

The prefill / batched-decode kernel dequantizes i4p tiles in VMEM and feeds the
MXU in bf16 — it must match dequantize-to-bf16-then-dot to float tolerance, and
the split-plane dual-view addressing (one packed tile covers two disjoint
K-ranges) must survive multi-tile K grids and TP sharding."""

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_tpu.models.forward import forward, init_kv_cache
from distributed_llama_tpu.models.params import (init_random_params,
                                                 prepare_for_pallas)
from distributed_llama_tpu.models.spec import ArchType, ModelSpec, RopeType
from distributed_llama_tpu.ops.pallas_q4_mm import q4_matmul, q4_mm_supported
from distributed_llama_tpu.ops.rope import RopeTables
from distributed_llama_tpu.quants import FloatType, QTensor


@pytest.mark.parametrize("m,n,k", [(8, 96, 1024), (3, 300, 2048), (1, 64, 1024)])
def test_q4_matmul_matches_dequant_dot(m, n, k):
    rng = np.random.RandomState(0)
    w = QTensor.from_float(rng.randn(n, k).astype(np.float32) * 0.02,
                           FloatType.Q40).to_i4p_layout()
    assert q4_mm_supported(w, m)
    x = jnp.asarray(rng.randn(m, k).astype(np.float32))

    wd = w.dequantize(dtype=jnp.bfloat16)
    want = (x.astype(jnp.bfloat16) @ wd.T).astype(np.float32)
    got = q4_matmul(x, w, out_dtype=jnp.float32, interpret=True)
    # per-tile f32 accumulation vs one full-K bf16 dot: order differences at
    # bf16 product granularity
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-2, rtol=3e-2)


def test_q4_mm_supported_gates():
    rng = np.random.RandomState(1)
    w = QTensor.from_float(rng.randn(64, 1024).astype(np.float32),
                           FloatType.Q40).to_i4p_layout()
    assert q4_mm_supported(w, 64)
    assert not q4_mm_supported(w, 1024)  # M cap
    w_odd = QTensor.from_float(rng.randn(64, 576).astype(np.float32),
                               FloatType.Q40).to_i4p_layout()
    assert not q4_mm_supported(w_odd, 8)  # K/2=288 not tileable by 512
    w8 = QTensor.from_float(rng.randn(64, 1024).astype(np.float32),
                            FloatType.Q80).to_i8_layout()
    assert not q4_mm_supported(w8, 8)  # i8 layout unsupported


def test_scale_scratch_gate_declines_what_vmem_cannot_hold():
    """The tile-major scale scratch is lane-padded f32, so a half-plane that
    only tiles by 128 columns costs 32x its scales: at K=11008 one weight
    still fits beside the operand tiles (11 MiB; it compiles for v5e at
    M=512), a gated pair would need 21.5 MiB of the chip's 16 MiB scoped
    VMEM, which its compiler refuses. The gates read shapes only."""
    from distributed_llama_tpu.ops.pallas_q4_mm import q4_gated_supported

    def weight(n, k):
        return QTensor(FloatType.Q40, np.zeros((n, k // 2), np.uint8),
                       np.zeros((n, k // 32), np.int16), layout="i4p")

    assert q4_mm_supported(weight(256, 11008), 512)
    assert not q4_gated_supported(weight(256, 11008), weight(256, 11008), 8)
    assert q4_gated_supported(weight(256, 14336), weight(256, 14336), 128)
    assert not q4_gated_supported(weight(256, 28672), weight(256, 28672), 8)
    assert not q4_mm_supported(weight(256, 2 * 125 * 128), 8)  # 31 MiB


def _spec():
    # dim 1024 so K/2=512 tiles exactly (q4_mm_supported needs kh % 512 == 0)
    return ModelSpec(arch_type=ArchType.LLAMA, dim=1024, hidden_dim=1024,
                     n_layers=2, n_heads=8, n_kv_heads=8, vocab_size=256,
                     seq_len=32, rope_type=RopeType.LLAMA).resolved()


def test_prefill_forward_kernel_matches_xla_path():
    """T=8 prefill through use_pallas='all' (the dequant-matmul kernel) == the
    XLA dequant path at bf16-accumulation tolerance."""
    spec = _spec()
    params = init_random_params(spec, FloatType.Q40, seed=7)
    rope = RopeTables.create(spec)
    pp = prepare_for_pallas(params, spec=spec)

    tokens = jnp.asarray([[1, 5, 9, 2, 7, 4, 3, 8]])
    kc, vc = init_kv_cache(spec)
    want, _, _ = forward(pp, spec, rope, tokens, kc, vc, jnp.int32(0),
                         use_pallas=True)
    kc, vc = init_kv_cache(spec)
    got, _, _ = forward(pp, spec, rope, tokens, kc, vc, jnp.int32(0),
                        use_pallas="all")
    got, want = np.asarray(got), np.asarray(want)
    rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
    assert rel < 0.02, rel


def test_prefill_kernel_sharded_matches():
    """tp=2 shard_map prefill with the kernel (col-sharded wo/w2 localize to
    groups=1 self-contained packs) == the planar sharded step. The localized
    shard widths must actually take the kernel (adaptive tile width), or this
    test would pass vacuously through the XLA fallback."""
    from distributed_llama_tpu.ops.pallas_q4_mm import _pick_bkp
    from distributed_llama_tpu.parallel.mesh import make_mesh
    from distributed_llama_tpu.parallel.tp import (init_sharded_kv_cache,
                                                   make_sharded_forward,
                                                   shard_params)

    spec = _spec()
    # col-sharded wo/w2 local half-plane width: (K/tp)/2 — must be tileable
    assert _pick_bkp(spec.dim // 2 // 2) is not None
    assert _pick_bkp(spec.hidden_dim // 2 // 2) is not None
    params = init_random_params(spec, FloatType.Q40, seed=3)
    mesh = make_mesh(tp=2)
    tokens = jnp.asarray([[1, 5, 9, 2]])
    rope = RopeTables.create(spec)

    base = shard_params(params, mesh, spec)
    step = make_sharded_forward(spec, mesh, base, donate_cache=False)
    kc, vc = init_sharded_kv_cache(spec, mesh)
    want, _, _ = step(base, rope, tokens, kc, vc, jnp.int32(0))

    pp = shard_params(prepare_for_pallas(params, tp=2, spec=spec), mesh, spec)
    stepp = make_sharded_forward(spec, mesh, pp, use_pallas="all",
                                 donate_cache=False)
    kc, vc = init_sharded_kv_cache(spec, mesh)
    got, _, _ = stepp(pp, rope, tokens, kc, vc, jnp.int32(0))
    got, want = np.asarray(got), np.asarray(want)
    rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
    assert rel < 0.02, rel


def test_engine_prefill_kernel_generation_matches():
    """End-to-end: Engine(prefill_kernel=True) greedy tokens == baseline (the
    kernel only changes where dequant happens; decode path identical)."""
    from distributed_llama_tpu.runtime.engine import Engine
    from distributed_llama_tpu.runtime.sampler import Sampler

    spec = _spec()
    params = init_random_params(spec, FloatType.Q40, seed=13)
    base = Engine(spec, params, tp=1, use_pallas=True)
    want, _ = base.generate([1, 7, 3, 9, 2], 6,
                            Sampler(spec.vocab_size, temperature=0.0))

    eng = Engine(spec, params, tp=1, use_pallas=True, prefill_kernel=True)
    assert eng.use_pallas == "all"
    got, _ = eng.generate([1, 7, 3, 9, 2], 6,
                          Sampler(spec.vocab_size, temperature=0.0))
    assert got == want


def test_batch_engine_with_prefill_kernel_matches():
    """Batched decode (B=2 slots) engages the dequant-matmul at m=B>1; tokens
    must match the non-kernel batched engine exactly."""
    from distributed_llama_tpu.runtime.batch_engine import BatchEngine
    from distributed_llama_tpu.runtime.sampler import Sampler

    spec = _spec()
    params = init_random_params(spec, FloatType.Q40, seed=5)
    prompts = [[1, 7, 23, 5], [1, 9, 2]]

    def run(**kw):
        be = BatchEngine(spec, params, slots=2, tp=2, use_pallas=True, **kw)
        try:
            reqs = [be.submit(list(p), 6, Sampler(spec.vocab_size, temperature=0.0))
                    for p in prompts]
            return [r.wait(timeout=180) for r in reqs]
        finally:
            be.close()

    want = run()
    got = run(prefill_kernel=True)
    assert got == want


def test_pick_bkp_baseline_arch_coverage():
    """Pin exactly which BASELINE widths take the kernel and which fall back:
    all single-chip (tp=1) in-widths are tileable — the adaptive width exists
    because 7B's w2 half-plane (5504) is not a multiple of 512 — while the odd
    TP-local slices of 11008-class hidden dims (2752 at tp=4, 1376 at tp=8)
    are KNOWN fallbacks (half-plane not a multiple of 128). A new arch whose
    hot width lands in the fallback set should move it to the tileable list or
    widen the ladder."""
    from distributed_llama_tpu.ops.pallas_q4_mm import _pick_bkp

    # tp=1 in-widths of every BASELINE arch (dim and hidden): all tileable
    for k in (4096, 11008, 2048, 5632, 14336, 6144, 32768):
        assert _pick_bkp(k // 2) is not None, k
    assert _pick_bkp(5504) == 128  # 7B w2, the reason the ladder exists
    assert _pick_bkp(2048) == 512
    # known XLA fallbacks: odd TP-local slices of 11008/5632-class hidden dims
    for k in (2752, 1376, 704, 1408):
        assert _pick_bkp(k // 2) is None, k
    assert _pick_bkp(288) is None  # K=576: untileable, gated out
