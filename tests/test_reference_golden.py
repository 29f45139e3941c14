"""Golden block tests ported from the REFERENCE's own test suite.

Round 1 validated numerics against an independently written numpy oracle — good, but
self-referential (both sides share one author's reading of the reference). These tests
anchor to the reference's *recorded outputs* instead:

- Llama: the 4096-float golden table from /root/reference/src/llama2-tasks-test.cpp:12-525
  (extracted verbatim into tests/data/llama2_block_golden.npy), produced by a 1-layer
  dim-4096 block forward over xorshift*-seeded F32 weights (state 800000010, each draw
  / 120.0; llama2-tasks-test.cpp:527-608).
- Grok-1: the spot windows at [0:4), [256:260), [5012:5016) from
  /root/reference/src/grok1-tasks-test.cpp:13-15 (1-layer dim-6144 8-expert MoE block,
  state 123456789, draws / 100.0, input additionally / 78.38367176906169f).

Weight streams are regenerated bit-exactly with the native xorshift* port
(native.xorshift_f32_fill). Stream order follows the reference tests' fill order, which
for Llama is rms vectors FIRST then matmul weights (the test writes rmsData before
mmData from one stream, llama2-tasks-test.cpp:561-566), while Grok fills the block
region sequentially in .m tensor order (wq,wk,wv,wo,router,[up,gate,down]xE,norms;
transformer.cpp:498-523).

Both reference tests run at pos=0 with the final-norm/logits tasks skipped, so these
call the per-layer block function directly. Tolerances are the reference's own
(1e-5 / 3.5e-5, "Optimization may cause some differences").
"""

import functools
import os

import numpy as np
import pytest
import jax.numpy as jnp

from distributed_llama_tpu import native
from distributed_llama_tpu.models.forward import _block
from distributed_llama_tpu.models.spec import ArchType, HiddenAct, ModelSpec, RopeType
from distributed_llama_tpu.ops.rope import RopeTables
from distributed_llama_tpu.quants import FloatType, QTensor

DATA = os.path.join(os.path.dirname(__file__), "data")

needs_native = pytest.mark.skipif(
    not native.available(),
    reason="native xorshift stream unavailable (sequential 200M-draw stream)")


class Stream:
    """Sequential view over the reference tests' single xorshift* draw stream."""

    def __init__(self, state: int, div: float):
        self.state = state
        self.div = div

    def take(self, *shape) -> np.ndarray:
        n = int(np.prod(shape))
        vals, self.state = native.xorshift_f32_fill(self.state, n, self.div)
        return vals.reshape(shape)


def run_block(spec: ModelSpec, bp: dict, x: np.ndarray) -> np.ndarray:
    rope = RopeTables.create(spec)
    kc = jnp.zeros((1, 1, spec.n_kv_heads, spec.seq_len, spec.head_size), jnp.float32)
    vc = jnp.zeros_like(kc)
    block = functools.partial(
        _block, spec=spec, rope=rope, start_pos=jnp.int32(0),
        positions=jnp.zeros((1,), jnp.int32), axis_name=None, sp_axis_name=None,
        sp_size=1, use_pallas=False, compress=False, window=None, kc=kc, vc=vc)
    bp = {k: (v if isinstance(v, QTensor) else jnp.asarray(v)) for k, v in bp.items()}
    x_out, _ = block(jnp.asarray(x)[None, None, :], (bp, jnp.int32(0)))
    return np.asarray(x_out)[0, 0]


@needs_native
def test_llama_block_matches_reference_golden():
    spec = ModelSpec(arch_type=ArchType.LLAMA, dim=4096, hidden_dim=11008, n_layers=1,
                     n_heads=32, n_kv_heads=32, vocab_size=32000, seq_len=2048,
                     rope_type=RopeType.LLAMA, rope_theta=10000.0).resolved()
    s = Stream(800000010, 120.0)
    bp = {}
    # the reference test fills the trailing rms region first, then the matmul region
    # (llama2-tasks-test.cpp:561-566), so the draw order is norms -> weights
    bp["rms_att"] = s.take(spec.dim)
    bp["rms_ffn"] = s.take(spec.dim)
    for name, out_dim, in_dim in (
            ("wq", spec.dim, spec.dim), ("wk", spec.kv_dim, spec.dim),
            ("wv", spec.kv_dim, spec.dim), ("wo", spec.dim, spec.dim),
            ("w1", spec.hidden_dim, spec.dim), ("w2", spec.dim, spec.hidden_dim),
            ("w3", spec.hidden_dim, spec.dim)):
        bp[name] = QTensor.from_float(s.take(out_dim, in_dim), FloatType.F32)
    x = s.take(spec.dim)

    got = run_block(spec, bp, x)
    want = np.load(os.path.join(DATA, "llama2_block_golden.npy"))
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


# grok1-tasks-test.cpp:13-15 — the reference's recorded spot windows
GROK_GOLDEN = {
    0: [0.00940248929, 0.0191232786, 0.0147766126, 0.0102868658],
    256: [0.0191071425, 0.0134582901, 0.0146755828, 0.019181719],
    5012: [0.0126675405, 0.0169415697, 0.0183475353, 0.0182626117],
}


@needs_native
def test_grok1_block_matches_reference_golden():
    spec = ModelSpec(arch_type=ArchType.GROK1, dim=6144, hidden_dim=1024, n_layers=1,
                     n_heads=48, n_kv_heads=8, vocab_size=1024, seq_len=8192,
                     n_experts=8, n_active_experts=2, hidden_act=HiddenAct.GELU,
                     rope_type=RopeType.FALCON, rope_theta=10000.0).resolved()
    s = Stream(123456789, 100.0)
    bp = {}
    bp["wq"] = QTensor.from_float(s.take(spec.dim, spec.dim), FloatType.F32)
    bp["wk"] = QTensor.from_float(s.take(spec.kv_dim, spec.dim), FloatType.F32)
    bp["wv"] = QTensor.from_float(s.take(spec.kv_dim, spec.dim), FloatType.F32)
    bp["wo"] = QTensor.from_float(s.take(spec.dim, spec.dim), FloatType.F32)
    bp["router"] = QTensor.from_float(s.take(spec.n_experts, spec.dim), FloatType.F32)
    ups, gates, downs = [], [], []
    for _ in range(spec.n_experts):
        ups.append(s.take(spec.hidden_dim, spec.dim))
        gates.append(s.take(spec.hidden_dim, spec.dim))
        downs.append(s.take(spec.dim, spec.hidden_dim))
    bp["moe_up"] = QTensor.from_float(np.stack(ups), FloatType.F32)
    bp["moe_gate"] = QTensor.from_float(np.stack(gates), FloatType.F32)
    bp["moe_down"] = QTensor.from_float(np.stack(downs), FloatType.F32)
    bp["rms_att"] = s.take(spec.dim)
    bp["rms_ffn"] = s.take(spec.dim)
    bp["rms_moe"] = s.take(spec.dim)
    bp["rms_ffn2"] = s.take(spec.dim)
    # the reference test divides x by the embedding scale, which grokMulInput then
    # multiplies back (grok1-tasks-test.cpp:73); net block input is the raw /100 draw —
    # _block runs post-embedding-scale, so feed the raw draws directly. The /78.38f
    # round trip is f32-exact to well below the 3.5e-5 tolerance.
    x = s.take(spec.dim)

    got = run_block(spec, bp, x)
    for off, want in GROK_GOLDEN.items():
        np.testing.assert_allclose(got[off:off + 4], np.asarray(want, np.float32),
                                   atol=3.5e-5, rtol=0)
