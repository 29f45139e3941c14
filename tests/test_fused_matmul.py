"""The fused Q40 dequant-matmul in the batched serving runtime.

Three layers of assurance, all interpret-mode on CPU, every case the
surviving path (`use_pallas=True`: one rule from shapes, ops/matmul.py)
against the kernel-off oracle (`use_pallas=False`):

- unit: `qmatmul` at the rows the batched runtime dispatches (decode M=B,
  verify M=B(1+k), a prefill chunk's M=512) picks `q4_mm` and matches the
  oracle; a shape the gate declines and an injected selection fault take the
  XLA lowering and say so in the registry;
- analytic: a call's HBM byte model stays within twice the packed weights at
  every cell shape, and the kernel is consistent with the XLA oracle, argmax
  included (perf/q4_mm_bench.py);
- end-to-end: a BatchEngine with the kernels on (pipelined + speculative +
  model drafter) and the T-bucket verify programs emit the kernel-off
  engine's tokens for greedy rows; a seeded-stochastic row draws as the
  kernel-off engine does and differs only by the kernel's bf16 rounding,
  which is held by the logits.

The residual-add and gated epilogues this file used to test lost to the
plain kernel on the chip and went (PERF.md section 6, PR 30).
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_tpu.models.params import (init_random_params,
                                                 prepare_for_pallas)
from distributed_llama_tpu.models.spec import ArchType, ModelSpec, RopeType
from distributed_llama_tpu.ops import pallas_q4_mm
from distributed_llama_tpu.ops.matmul import (kernel_selections, qmatmul,
                                              reset_kernel_selections)
from distributed_llama_tpu.quants import FloatType, QTensor

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "perf"))

import q4_mm_bench  # noqa: E402


def _w(n, k, seed=0):
    rng = np.random.RandomState(seed)
    qt = QTensor.from_float(rng.randn(n, k).astype(np.float32) * 0.02,
                            FloatType.Q40).to_i4p_layout()
    return jax.tree_util.tree_map(jnp.asarray, qt)


# the rows the batched runtime dispatches: decode M=B, verify M=B(1+k) with
# leading (B, T) dims, and the widest chunk (8 slots x 64 tokens)
@pytest.mark.parametrize("lead", [(8,), (8, 5), (512,)])
def test_qmatmul_picks_the_kernel_and_matches_the_oracle(lead):
    w = _w(256, 1024)
    x = jnp.asarray(np.random.RandomState(1).randn(*lead, 1024) * 0.1,
                    jnp.bfloat16)
    want = qmatmul(x, w, use_pallas=False, out_dtype=jnp.float32)
    reset_kernel_selections()
    got = qmatmul(x, w, use_pallas=True, out_dtype=jnp.float32)
    m = int(np.prod(lead))
    assert kernel_selections() == {
        f"m={m},n=256,k=1024,layout=i4p,op=mm": "q4_mm"}
    assert got.shape == (*lead, 256) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("out_dtype", [jnp.bfloat16, jnp.float32])
def test_kernel_writes_the_output_dtype_itself(out_dtype):
    """The head asks for float32 logits, the blocks for bf16: the kernel
    rounds its float32 accumulator once, as the oracle does."""
    w = _w(384, 512, seed=2)
    x = jnp.asarray(np.random.RandomState(3).randn(16, 512) * 0.1,
                    jnp.bfloat16)
    got = qmatmul(x, w, use_pallas=True, out_dtype=out_dtype)
    want = qmatmul(x, w, use_pallas=False, out_dtype=out_dtype)
    assert got.dtype == out_dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-2 if out_dtype == jnp.bfloat16 else 1e-4,
                               rtol=1e-2 if out_dtype == jnp.bfloat16 else 1e-4)


def test_declined_shapes_and_injected_faults_take_xla_by_name():
    """A half-plane that is not whole lane tiles is declined by the gate
    (`xla`); an injected `matmul.kernel_select` fault degrades the call site
    (`xla-fallback`); both give the oracle's result bit for bit."""
    from distributed_llama_tpu.resilience import faults

    x = jnp.ones((8, 576), jnp.bfloat16)
    w_odd = _w(64, 576, seed=4)
    reset_kernel_selections()
    got = qmatmul(x, w_odd, use_pallas=True)
    assert set(kernel_selections().values()) == {"xla"}
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(
        qmatmul(x, w_odd, use_pallas=False), np.float32))

    w = _w(64, 1024, seed=5)
    x = jnp.ones((8, 1024), jnp.bfloat16)
    reset_kernel_selections()
    with faults.active(faults.FaultSpec("matmul.kernel_select")) as plan:
        got = qmatmul(x, w, use_pallas=True)
    assert plan.fired() > 0
    assert set(kernel_selections().values()) == {"xla-fallback"}
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(
        qmatmul(x, w, use_pallas=False), np.float32))


@pytest.mark.parametrize("rows", [1, 2, 8, 72])
def test_a_weight_over_the_matvecs_bound_reaches_the_kernel(
        matvec_bound_under_1024, rows):
    """A Q40 matrix of the layers whose K is over the one-row matvec's bound
    is packed all the same (`prepare_for_pallas` asks the kernels that read
    the pack, not the matvec alone), and `qmatmul` reads it out of the stack
    with the dequant-matmul at 2, 8 and 72 rows, within that kernel's
    distance of dequantize-then-dot on the planar blocks. At ONE row neither
    kernel takes it and XLA dequantizes the pack: the planar weight's result
    bit for bit. (Until PR 49 such a weight stayed planar and XLA dequantized
    it whole at every number of rows, every step: 10 % of A.X-K1's window.)"""
    from distributed_llama_tpu.ops.matmul import LayerOf, _qmatmul_xla

    spec = ModelSpec(arch_type=ArchType.LLAMA, dim=512, hidden_dim=1024,
                     n_layers=2, n_heads=4, n_kv_heads=4, vocab_size=64,
                     seq_len=32, rope_type=RopeType.LLAMA).resolved()
    params = init_random_params(spec, FloatType.Q40, seed=11)
    w2 = prepare_for_pallas(params, spec=spec)["blocks"]["w2"]
    assert (w2.layout, w2.shape) == ("i4p", (2, 512, 1024))
    planar = jax.tree.map(lambda a: jnp.asarray(a[1]), params["blocks"]["w2"])
    x = jnp.asarray(np.random.RandomState(rows).randn(rows, 1024) * 0.1,
                    jnp.bfloat16)
    want = _qmatmul_xla(x, planar, out_dtype=jnp.float32)
    reset_kernel_selections()
    got = qmatmul(x, LayerOf(w2, (jnp.int32(1),)), use_pallas=True,
                  out_dtype=jnp.float32)
    assert kernel_selections() == {
        f"m={rows},n=512,k=1024,layout=i4p,op=mm":
        "xla" if rows == 1 else "q4_mm"}
    if rows == 1:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4, rtol=1e-4)


def test_bench_byte_model_within_packed_density():
    """Satellite smoke: at EVERY cell shape a call's HBM traffic is at most
    1.3 times the packed weights at 8 and 64 rows and, rows and outputs of a
    512-row chunk included, under the 3.56x that the dequantized bf16 image
    alone would be; the weight stream is exactly Q40's packed density
    (0.5625 B/weight)."""
    for _cfg, _name, n, k in q4_mm_bench.CELL_SHAPES:
        for m in q4_mm_bench.ROWS:
            rec = q4_mm_bench.hbm_model(m, n, k)
            assert rec["ratio"] <= (1.3 if m <= 64 else 3.56), (m, n, k, rec)
            assert rec["density"] == 0.5625, rec


def test_bench_kernels_consistent_with_xla_oracle():
    """Satellite smoke: the interpret-mode kernel against the XLA oracle,
    close in f32 AND the same argmax in every row."""
    problems = q4_mm_bench.check_consistency()
    assert problems == [], "\n".join(problems)


def _spec():
    # dim 1024: a half-plane of 512 packed columns, whole lane tiles, so the
    # kernel serves; the registry assertions below guard a vacuous pass
    return ModelSpec(arch_type=ArchType.LLAMA, dim=1024, hidden_dim=1024,
                     n_layers=2, n_heads=8, n_kv_heads=8, vocab_size=256,
                     seq_len=32, rope_type=RopeType.LLAMA).resolved()


REP = [7, 31, 5, 102] * 4  # n-gram-dense: engages the verify path


def _run_batch(spec, params, reqs, *, draft=False, **kw):
    from distributed_llama_tpu.runtime.batch_engine import BatchEngine
    from distributed_llama_tpu.runtime.sampler import Sampler

    V = spec.vocab_size
    if draft:
        kw["draft_model"] = (spec, params)
    be = BatchEngine(spec, params, slots=2, tp=1, superstep=4, pipeline=True,
                     speculative=4, spec_min_draft=1, **kw)
    try:
        subs = [be.submit(list(p), gen, Sampler(V, temperature=temp,
                                                seed=seed))
                for p, gen, temp, seed in reqs]
        return [r.wait(timeout=300) for r in subs]
    finally:
        be.close()


def test_batch_engine_fused_token_identity(monkeypatch):
    """The acceptance gate: a BatchEngine with the kernels on (pipelined +
    speculative, with the co-resident model drafter so its k-step scan runs
    the kernel too) emits the kernel-off engine's tokens for greedy rows,
    and the registry proves the dequant-matmul served.

    The seeded-stochastic row is judged otherwise, because it is the
    kernel's rounding that moves its sampled tokens and not the engine's
    draws (found in PR 30): off the chip the oracle multiplies float32
    activations by float32 weights, the kernel bf16 by bf16, and an
    inverse-CDF draw over 256 near-flat probabilities turns on the fourth
    digit. So (a) with the kernel's gate closed the SAME engine (kernel
    layout, merged groups, paged kernel, drafter) must emit the kernel-off
    engine's sampled tokens exactly: it seeds and orders its draws alike;
    and (b) the kernel's logits for that row's prompt stay within 2 % of the
    logits' scale of the oracle's."""
    spec = _spec()
    params = init_random_params(spec, FloatType.Q40, seed=5)
    reqs = [(REP, 8, 0.0, 0),               # greedy, verify-engaging
            ([1, 9, 2, 7], 8, 0.0, 0),      # greedy, scan path
            (REP, 6, 0.8, 11)]              # seeded stochastic
    want = _run_batch(spec, params, reqs, draft=True)
    reset_kernel_selections()
    got = _run_batch(spec, params, reqs, draft=True, use_pallas=True)
    assert got[:2] == want[:2]
    assert "q4_mm" in set(kernel_selections().values())
    assert len(got[2]) == len(want[2]) == 6

    monkeypatch.setattr(pallas_q4_mm, "q4_mm_supported",
                        lambda w, m, stacked=False: False)
    reset_kernel_selections()
    closed = _run_batch(spec, params, reqs, draft=True, use_pallas=True)
    assert "q4_mm" not in set(kernel_selections().values())
    assert closed == want
    monkeypatch.undo()

    from distributed_llama_tpu.models.forward import forward, init_kv_cache
    from distributed_llama_tpu.ops.rope import RopeTables

    pp = prepare_for_pallas(params, spec=spec)
    rope = RopeTables.create(spec)
    logits = []
    for up in (False, True):
        kc, vc = init_kv_cache(spec)
        out, _, _ = forward(pp, spec, rope, jnp.asarray([REP]), kc, vc,
                            jnp.int32(0), use_pallas=up)
        logits.append(np.asarray(out, np.float32))
    err = np.abs(logits[1] - logits[0]).max() / np.abs(logits[0]).max()
    assert 0 < err < 0.02, err


@pytest.mark.parametrize("t", [2, 3, 5, 9])
def test_verify_bucket_kernel_matches_dense(t):
    """Verify-bucket sweep: the (B, T) verify program with the kernels on
    returns the same targets/accepts/frontier as the dense XLA reference at
    every reachable T bucket."""
    from distributed_llama_tpu.ops.rope import RopeTables
    from distributed_llama_tpu.parallel.mesh import make_mesh
    from distributed_llama_tpu.parallel.tp import (init_sharded_kv_cache,
                                                   shard_params)
    from distributed_llama_tpu.runtime.device_loop import \
        make_batched_verify_loop

    spec = _spec()
    params = init_random_params(spec, FloatType.Q40, seed=9)
    mesh = make_mesh(tp=1)
    rope = RopeTables.create(spec)
    b = 2
    rng = np.random.RandomState(t)
    proposals = rng.randint(0, spec.vocab_size, size=(b, t)).astype(np.int32)
    start = np.zeros((b,), np.int32)
    rstate = np.ones((b, 2), np.uint32)
    temp = np.zeros((b,), np.float32)
    topp = np.ones((b,), np.float32)
    ndraft = np.full((b,), t - 1, np.int32)

    def run(p, up):
        loop = make_batched_verify_loop(spec, mesh, p, t, mode="greedy",
                                        use_pallas=up, donate_cache=False)
        kc, vc = init_sharded_kv_cache(spec, mesh, batch=b)
        toks, acc, tok, pos, _rng, _kc, _vc = loop(
            p, rope, proposals, kc, vc, start, rstate, temp, topp, ndraft)
        return (np.asarray(toks).tolist(), np.asarray(acc).tolist(),
                np.asarray(tok).tolist(), np.asarray(pos).tolist())

    base = shard_params(params, mesh, spec)
    want = run(base, False)
    pp = shard_params(prepare_for_pallas(params, spec=spec), mesh, spec)
    reset_kernel_selections()
    got = run(pp, True)
    assert "q4_mm" in set(kernel_selections().values())
    assert got == want
