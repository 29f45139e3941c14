"""AOT compiles for a described TPU v5e: what interpret mode cannot show.

Every other kernel test in this suite runs the Pallas interpreter on the CPU,
which accepts block shapes, reshapes and VMEM footprints that the chip's
compiler (Mosaic) refuses. The TPU compiler is installed here and compiles for
a chip that is described, not attached (`jax.experimental.topologies`), so each
case lowers one kernel at a Llama-3-8B shape (dim 4096, hidden 14336, vocab
128256, 32/8 heads of 128), single-chip or the tp=4 shard of it, and compiles
it with `interpret=False`. Nothing runs: a pass says the compiler takes the
kernel, not that its result is right (chip_smoke.py's parity phase does that).

The cases marked "repaired" were refused before this file existed: the fused
dequant-matmul family for a (256, 16) scale block and an in-kernel lane-split
reshape, the inline-Xexp matvec at K=14336 for 1.9 MiB too much VMEM, and the
prologue kernels for the same reshape.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from distributed_llama_tpu.ops.pallas_attention import fused_decode_attention
from distributed_llama_tpu.ops.pallas_paged_attention import paged_attention
from distributed_llama_tpu.ops.pallas_prologue import _quantize, _rmsnorm_q80
from distributed_llama_tpu.ops.pallas_q4 import _q4_matvec, _q4_matvec_inline
from distributed_llama_tpu.ops.pallas_q4_mm import (_q4_gated_matmul,
                                                    _q4_matmul, _q4_matmul_res)

DIM, HIDDEN, VOCAB, HS, LAYERS = 4096, 14336, 128256, 128, 32
BF16, F32, I8, U8, I16, I32 = (jnp.bfloat16, jnp.float32, jnp.int8, jnp.uint8,
                               jnp.int16, jnp.int32)


@pytest.fixture(scope="module")
def chip():
    """One described v5e chip to give every shape its sharding."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _as_the_entry_points_compile():
    """Two process-wide settings must not leak into these compiles. A compile
    for a described chip is written to the persistent cache but cannot be
    read back without one, so the cache is off around them. And conftest.py
    pins matmul precision to "highest" for the f32 golden tests, which no
    entry point does: Mosaic refuses an int8 dot asked for at fp32 precision."""
    from jax.experimental.compilation_cache import compilation_cache

    cache = jax.config.jax_enable_compilation_cache
    precision = jax.config.jax_default_matmul_precision
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_default_matmul_precision", None)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", cache)
    jax.config.update("jax_default_matmul_precision", precision)
    compilation_cache.reset_cache()


def _q4_weight(n, k):
    return [((n, k // 2), U8), ((n, k // 32), I16)]


def matvec(n, k):
    """The default decode matmul: block-diagonal Xexp operand."""
    nb = k // 32
    return _q4_matvec, [((k, nb), I8), ((1, nb), F32), *_q4_weight(n, k)], {}


def matvec_inline(n, k):
    """--prologue's feed: Xexp built in VMEM scratch."""
    nb = k // 32
    return (_q4_matvec_inline,
            [((1, k), I8), ((1, nb), F32), *_q4_weight(n, k)], {})


def matmul(m, n, k):
    return _q4_matmul, [((m, k), BF16), *_q4_weight(n, k)], {}


def matmul_res(m, n, k):
    return (_q4_matmul_res,
            [((m, k), BF16), *_q4_weight(n, k), ((m, n), BF16)], {})


def gated(m, n, k):
    return (_q4_gated_matmul,
            [((m, k), BF16), *_q4_weight(n, k), *_q4_weight(n, k)],
            {"act": "silu"})


def paged(b, t, hq, hk, n_read, q=F32):
    """B rows x T chunk queries against a (L, N, hk, 16, 128) bf16 pool."""
    pool = ((LAYERS, 517, hk, 16, HS), BF16)
    new = ((b, hk, t, HS), BF16)
    return (paged_attention,
            [((b, t, hq, HS), q), pool, pool, new, new, ((b, 256), I32),
             ((b,), I32), ((), I32)], {"n_read": n_read})


def decode_attention(hk, window):
    cache = ((LAYERS, 1, hk, 2048, HS), BF16)
    new = ((hk, 1, HS), BF16)
    return (fused_decode_attention,
            [((hk, 4, HS), F32), cache, cache, new, new, ((), I32), ((), I32)],
            {"window": window})


CASES = {
    # q4_matvec, single chip: wq/wo, w1/w3, w2, wcls, merged wqkv
    "matvec-wq": matvec(DIM, DIM),
    "matvec-w1": matvec(HIDDEN, DIM),
    "matvec-w2": matvec(DIM, HIDDEN),
    "matvec-wcls": matvec(VOCAB, DIM),
    "matvec-wqkv": matvec(DIM + 2 * 1024, DIM),
    # q4_matvec, tp=4 shards of the same
    "matvec-tp4-wq": matvec(DIM // 4, DIM),
    "matvec-tp4-w1": matvec(HIDDEN // 4, DIM),
    "matvec-tp4-w2": matvec(DIM, HIDDEN // 4),
    "matvec-tp4-wcls": matvec(VOCAB // 4, DIM),
    # paged attention: decode and verify blocks, single chip and tp=4 heads
    "paged-b8-t1": paged(8, 1, 32, 8, 16),
    "paged-b8-t5": paged(8, 5, 32, 8, 16),
    "paged-tp4-b4-t1": paged(4, 1, 8, 2, 128),
    # the benchmark cells' dispatches, bf16 q as the engine passes it: a
    # 64-token chunk, an 8-token chunk and the decode step at the 1024, 512
    # and 256 buckets; the tp=4 head shard; Grok-1's g = 6 (T*g = 6 rows);
    # a window the 8-block step does not divide
    "paged-b8-t64-w1024": paged(8, 64, 32, 8, 64, BF16),
    "paged-b8-t8-w512": paged(8, 8, 32, 8, 32, BF16),
    "paged-b8-t1-w1024": paged(8, 1, 32, 8, 64, BF16),
    "paged-b8-t64-w256": paged(8, 64, 32, 8, 16, BF16),
    "paged-tp4-b8-t64": paged(8, 64, 8, 2, 64, BF16),
    "paged-g6-b8-t1": paged(8, 1, 48, 8, 64, BF16),
    "paged-g6-b8-t64": paged(8, 64, 48, 8, 64, BF16),
    "paged-b8-t5-w304": paged(8, 5, 32, 8, 19, BF16),
    "paged-b8-t1-w4096": paged(8, 1, 32, 8, 256, BF16),
    # fused decode attention: one-block and full windows
    "decode-attn-w256": decode_attention(8, 256),
    "decode-attn-w2048": decode_attention(8, 2048),
    "decode-attn-tp4-w256": decode_attention(2, 256),
    # repaired: the fused dequant-matmul family (--prefill-kernel,
    # --fused-matmul) at the serving buckets M = B, B*(1+k), prefill chunk
    "repaired-matmul-m8-w2": matmul(8, DIM, HIDDEN),
    "repaired-matmul-tp4-m40-w2": matmul(40, DIM, HIDDEN // 4),
    "repaired-matmul-res-m8-wo": matmul_res(8, DIM, DIM),
    "repaired-gated-m8-w13": gated(8, HIDDEN, DIM),
    # repaired: inline matvec VMEM at K=14336, and the prologue kernels
    "repaired-matvec-inline-w2": matvec_inline(DIM, HIDDEN),
    "repaired-prologue-rmsnorm-dim": (
        _rmsnorm_q80, [((1, DIM), BF16), ((1, DIM), F32)], {"eps": 1e-5}),
    "repaired-prologue-quantize-hidden": (
        _quantize, [((1, HIDDEN), BF16)], {}),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_compiles_for_v5e(chip, case):
    fn, shapes, static = CASES[case]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
            for shape, dtype in shapes]
    compiled = fn.lower(*args, interpret=False, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()
