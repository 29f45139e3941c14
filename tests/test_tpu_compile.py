"""AOT compiles for a described TPU v5e: what interpret mode cannot show.

Every other kernel test in this suite runs the Pallas interpreter on the CPU,
which accepts block shapes, reshapes and VMEM footprints that the chip's
compiler (Mosaic) refuses. The TPU compiler is installed here and compiles for
a chip that is described, not attached (`jax.experimental.topologies`), so each
case lowers one kernel at a Llama-3-8B shape (dim 4096, hidden 14336, vocab
128256, 32/8 heads of 128), single-chip or the tp=4 shard of it, and compiles
it with `interpret=False`. Nothing runs: a pass says the compiler takes the
kernel, not that its result is right (chip_smoke.py's parity phase does that).

The fused dequant-matmul is held to the benchmark cells' real shapes at 8,
64 and 512 rows (`CELL_MATMULS`): what `qmatmul` hands it in a decode step,
an 8-token and a 64-token chunk at 8 slots. Its gate declines none of them.

The case marked "repaired" was refused before this file existed: the
inline-Xexp matvec at K=14336 for 1.9 MiB too much VMEM.

Whether XLA puts a copy AROUND a kernel or a scatter shows only in a whole
step program: `STEP_POOLS` x `STEP_PROGRAMS` compile `forward()` as
`perf/aot_step.py` does (four layers drawn at the cells' widths and re-shaped
to their depth, 256 blocks of the pool, the pools donated)
and hold the compiled text to updating the KV pool in place.
"""

import functools
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from distributed_llama_tpu.ops.moe_grouped import capacity, row_tile
from distributed_llama_tpu.ops.pallas_attention import fused_decode_attention
from distributed_llama_tpu.ops.pallas_moe_grouped import _moe_grouped_q4
from distributed_llama_tpu.ops.pallas_paged_attention import (
    head_group, latent_paged_attention, paged_attention)
from distributed_llama_tpu.ops.pallas_q4 import _q4_matvec, _q4_matvec_inline
from distributed_llama_tpu.ops.pallas_q4_mm import q4_matmul, q4_mm_supported
from distributed_llama_tpu.quants import FloatType, QTensor, scale_plane_cols

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "perf"))

import aot_step  # noqa: E402

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


def _plane(k):
    """Columns of the scales' plane as the weights are stored: K/32 in whole
    lane tiles (`quants.to_scale_plane`)."""
    return scale_plane_cols(k // 32)


def _q4_weight(n, k):
    return [((n, k // 2), U8), ((n, _plane(k)), I16)]


def matvec(n, k):
    """The default decode matmul: block-diagonal Xexp operand."""
    nb = k // 32
    return _q4_matvec, [((k, nb), I8), ((1, nb), F32), *_q4_weight(n, k)], {}


def matvec_inline(n, k):
    """The matvec with Xexp built in VMEM scratch (`inline_xexp`)."""
    nb = k // 32
    return (_q4_matvec_inline,
            [((1, k), I8), ((1, nb), F32), *_q4_weight(n, k)], {})


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def _q4_matmul(x, wp, scales, *at, out_dtype, interpret):
    return q4_matmul(x, QTensor(FloatType.Q40, wp, scales, layout="i4p"),
                     at=at, out_dtype=out_dtype, interpret=interpret)


def matmul(m, n, k, out=BF16, lead=(2,)):
    """The fused dequant-matmul as `qmatmul` calls it from the layer scan:
    a layer of the whole stack (`lead`: the stack's leading axes, layers or
    layers and experts, each indexed by a traced scalar). The gate has to
    admit the shape (a case `q4_mm_supported` declined would be asserted
    so)."""
    stack = [((*lead, n, k // 2), U8), ((*lead, n, _plane(k)), I16)]
    w = QTensor(FloatType.Q40, *(jax.ShapeDtypeStruct(*a) for a in stack),
                layout="i4p")
    assert q4_mm_supported(w, m, stacked=len(lead)), (m, n, k)
    return (_q4_matmul, [((m, k), BF16), *stack, *[((), I32)] * len(lead)],
            {"out_dtype": jnp.dtype(out)})


TRACED_I32 = object()  # a keyword argument that is a traced i32 scalar


def paged(b, t, hq, hk, n_read, q=F32, window=False, head_size=None):
    """B rows x T chunk queries against a (L, N, hk, 16, 128) bf16 pool;
    `window`: the layer's sliding window rides in as a traced scalar;
    `head_size`: the real values of heads padded to the pool's 128 lanes."""
    pool = ((LAYERS, 517, hk, 16, HS), BF16)
    new = ((b, hk, t, HS), BF16)
    static = {"n_read": n_read, **({"window": TRACED_I32} if window else {}),
              **({"head_size": head_size} if head_size else {})}
    return (paged_attention,
            [((b, t, hq, HS), q), pool, pool, new, new, ((b, 512), I32),
             ((b,), I32), ((), I32)], static)


def latent(b, t, n_read):
    """The latent attention kernel at A.X-K1's widths: 64 heads against one
    row of 640 values a token (512 + 64, whole lanes), the first 512 of
    which are the values; the cell's pool of 7 layers x 2048 blocks of 16."""
    w = 640
    return (latent_paged_attention,
            [((b, t, 64, w), BF16), ((7, 2048, 1, 16, w), BF16),
             ((b, t, w), BF16), ((b, 512), I32), ((b,), I32), ((), I32)],
            {"n_read": n_read, "n_values": 512, "scale": 0.13})


def grouped(rows, k, experts, hidden, dim, merged=True, act="relu", layers=1):
    """The grouped expert layer's two kernels for `rows` x `k` assignments
    over `experts` experts of width `hidden`, at the tile and capacity the
    shapes give (ops/moe_grouped.py), reading a layer of stacks over
    `layers` layers."""
    tile = row_tile(rows * k, experts)
    cap = capacity(rows * k, experts, tile)
    gu_rows = 2 * hidden if merged else hidden
    lead = (layers, experts)
    up = [((*lead, gu_rows, dim // 2), U8), ((*lead, gu_rows, _plane(dim)), I16)]
    down = [((*lead, dim, hidden // 2), U8), ((*lead, dim, _plane(hidden)), I16)]
    return (_moe_grouped_q4,
            [((cap, dim), BF16), ((cap // tile,), I32), ((), I32), ((3,), I32),
             *up, *up, *down], {"tile": tile, "act": act, "merged": merged})


def grouped_share(rows, k, held, width, hidden, dim, layers=1):
    """The same two kernels where the stack holds `held` of the `width`
    experts the router scores: the row tile follows the mean run over the
    router's width, the capacity the worst case over the held."""
    tile = row_tile(rows * k, width)
    cap = capacity(rows * k, held, tile)
    lead = (layers, held)
    up = [((*lead, 2 * hidden, dim // 2), U8),
          ((*lead, 2 * hidden, _plane(dim)), I16)]
    down = [((*lead, dim, hidden // 2), U8), ((*lead, dim, _plane(hidden)), I16)]
    return (_moe_grouped_q4,
            [((cap, dim), BF16), ((cap // tile,), I32), ((), I32), ((3,), I32),
             *up, *up, *down], {"tile": tile, "act": "silu", "merged": True})


def kda(t):
    """The delta-rule kernels at Kimi-Linear's published sizes (8 slots, 6
    state layers, 32 heads of 128 x 128): `kda_step` (t = 0), one position of
    every slot, and `kda_chunk`, t positions of one slot; the matrices
    donated to the call by the step program, updated in place here."""
    from distributed_llama_tpu.ops.pallas_kda import (_kda_chunk_pallas,
                                                      _kda_step_pallas)

    h = ((8, 6, 32, 128, 128), F32)
    n = t or 8
    rows = [((n, 32, 128), F32)] * 4  # q, k, v, g
    if not t:
        return (_kda_step_pallas,
                [h, ((), I32), *rows, ((8, 32), F32), ((8,), jnp.bool_),
                 ((8,), jnp.bool_)], {"name": "kda_step"})
    return (_kda_chunk_pallas,
            [h, ((), I32), ((), I32), *rows, ((t, 32), F32),
             ((), jnp.bool_), ((), jnp.bool_)], {"name": "kda_chunk"})


def decode_attention(hk, window):
    cache = ((LAYERS, 1, hk, 2048, HS), BF16)
    new = ((hk, 1, HS), BF16)
    return (fused_decode_attention,
            [((hk, 4, HS), F32), cache, cache, new, new, ((), I32), ((), I32)],
            {"window": window})


# the benchmark cells' matmuls (out rows, in columns): Mistral-7B's merged
# wqkv, wo, merged w13 (Mixtral's expert scan slices its [up|gate] stack to
# the same shape), w2 (and the expert's down slice) and head at 8, 64 and 512
# rows; SmallThinker's merged wqkv, wo and ragged 151936-row head at 512
_MISTRAL = {"wqkv": (6144, 4096), "wo": (4096, 4096), "w13": (28672, 4096),
            "w2": (4096, 14336), "head": (32000, 4096)}
_SMALLTHINKER = {"wqkv": (4608, 2560), "wo": (2560, 3584),
                 "head": (151936, 2560)}
CELL_MATMULS = {
    **{f"matmul-mistral-m{m}-{name}": matmul(m, n, k)
       for m in (8, 16, 64, 72, 512) for name, (n, k) in _MISTRAL.items()},
    **{f"matmul-smallthinker-m512-{name}": matmul(512, n, k)
       for name, (n, k) in _SMALLTHINKER.items()},
    # A.X-K1's leading dense w2, a stack of one: K 18432 is over the one-row
    # matvec's bound (17378) and packed since PR 49; 9216 packed columns a
    # row (18 chunks of 512), a scale plane of 576 columns stored as 640
    **{f"matmul-axk1-m{m}-lead-w2": matmul(m, 7168, 18432, lead=(1,))
       for m in (8, 72)},
}

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
    # layers of two kinds in one scan: the window is a traced scalar. 28/4
    # heads (g = 7), a 64-token chunk and the decode step at the 1024 bucket
    # and a long row's 8192
    "paged-window-g7-b8-t64-w1024": paged(8, 64, 28, 4, 64, BF16, True),
    "paged-window-g7-b8-t1-w1024": paged(8, 1, 28, 4, 64, BF16, True),
    "paged-window-g7-b8-t64-w8192": paged(8, 64, 28, 4, 512, BF16, True),
    "paged-window-b8-t8-w512": paged(8, 8, 32, 8, 32, BF16, True),
    # the two calls a dispatch makes since PR 45, heads batched since PR 50
    # (`PAGED_HEAD_GROUPS` below): the lead's chunk alone, B 1 x T 8 and T 64,
    # beside the riders' B 8 x T 1 above; Laguna's kinds at the largest score
    # block (its full layers' g 6 and its window layers' g 9, a long row's
    # 2048 keys);
    # SmallThinker's lead; heads of 64 padded to the pool's 128 lanes (LFM2)
    "paged-lead-b1-t8-w1024": paged(1, 8, 32, 8, 64, BF16),
    "paged-lead-b1-t64-w1024": paged(1, 64, 32, 8, 64, BF16),
    "paged-window-g7-b1-t64-w1024": paged(1, 64, 28, 4, 64, BF16, True),
    "paged-window-g6-b1-t64-w2048": paged(1, 64, 48, 8, 128, BF16, True),
    "paged-window-g9-b1-t64-w2048": paged(1, 64, 72, 8, 128, BF16, True),
    "paged-window-g9-b8-t64-w1024": paged(8, 64, 72, 8, 64, BF16, True),
    "paged-window-g9-b8-t1-w2048": paged(8, 1, 72, 8, 128, BF16, True),
    "paged-padded-heads-b8-t1-w1024": paged(8, 1, 32, 8, 64, BF16,
                                            head_size=64),
    "paged-padded-heads-b1-t64-w1024": paged(1, 64, 32, 8, 64, BF16,
                                             head_size=64),
    # latent attention: the decode step, an 8-token and a 64-token chunk
    # (eight query blocks of 512 rows) at the 1024 bucket, and a long row's
    # 8192
    "latent-b8-t1-w1024": latent(8, 1, 64),
    "latent-b8-t8-w1024": latent(8, 8, 64),
    "latent-b8-t64-w1024": latent(8, 64, 64),
    "latent-b8-t1-w8192": latent(8, 1, 512),
    "latent-b8-t64-w8192": latent(8, 64, 512),
    # A.X-K1's share of the experts: 48 held of 2048 x 7168, the tile from
    # the router's width of 192 (a decode step and a 64-token chunk)
    "grouped-l6-e48-t1": grouped_share(8, 8, 48, 192, 2048, 7168, layers=6),
    "grouped-l6-e48-t64": grouped_share(512, 8, 48, 192, 2048, 7168,
                                        layers=6),
    # the grouped expert layer: 64 ReGLU experts of 768 at 8 slots (decode
    # step, 8-token and 64-token chunks), Mixtral's 8 experts of 14336
    # (merged [up|gate] stack, SiLU), and the tp=4 hidden slice of those
    "grouped-e64-t1": grouped(8, 6, 64, 768, 2560),
    "grouped-e64-t8": grouped(64, 6, 64, 768, 2560),
    "grouped-e64-t64": grouped(512, 6, 64, 768, 2560),
    "grouped-e64-t64-unmerged": grouped(512, 6, 64, 768, 2560, merged=False),
    "grouped-e8-t1": grouped(8, 2, 8, 14336, 4096, act="silu"),
    "grouped-e8-t64": grouped(512, 2, 8, 14336, 4096, act="silu"),
    "grouped-tp4-e8-t64": grouped(512, 2, 8, 14336 // 4, 4096, act="silu"),
    # the same out of the cells' whole stacks, the layer a prefetched scalar:
    # SmallThinker's 24 layers of 64 experts at 8, 64 and 512 rows, Mixtral's
    # 8 layers of 8 at 8 and 64 (its 512 rows take the all-experts scan)
    "grouped-l24-e64-t1": grouped(8, 6, 64, 768, 2560, layers=24),
    "grouped-l24-e64-t8": grouped(64, 6, 64, 768, 2560, layers=24),
    "grouped-l24-e64-t64": grouped(512, 6, 64, 768, 2560, layers=24),
    "grouped-l8-e8-t1": grouped(8, 2, 8, 14336, 4096, act="silu", layers=8),
    "grouped-l8-e8-t8": grouped(64, 2, 8, 14336, 4096, act="silu", layers=8),
    # a prefill chunk's compact rows since PR 41 (`forward.compact_rows`): 72
    # of a 64-token chunk, 16 of an 8-token one; Mixtral's 72 x 2 of 8 take
    # the grouped layer where 512 rows took the all-experts scan
    "grouped-l24-e64-r72": grouped(72, 6, 64, 768, 2560, layers=24),
    "grouped-l24-e64-r16": grouped(16, 6, 64, 768, 2560, layers=24),
    "grouped-l8-e8-r72": grouped(72, 2, 8, 14336, 4096, act="silu", layers=8),
    "grouped-l8-e8-r16": grouped(16, 2, 8, 14336, 4096, act="silu", layers=8),
    # fused decode attention: one-block and full windows
    "decode-attn-w256": decode_attention(8, 256),
    "decode-attn-w2048": decode_attention(8, 2048),
    "decode-attn-tp4-w256": decode_attention(2, 256),
    # the fused dequant-matmul at Llama-3-8B widths: a verify block's
    # B(1+k) = 40 rows on the tp=4 slice of w2, two rows, the head in f32
    "matmul-tp4-m40-w2": matmul(40, DIM, HIDDEN // 4),
    "matmul-m2-wo": matmul(2, DIM, DIM),
    "matmul-m8-wcls-f32": matmul(8, VOCAB, DIM, out=F32),
    # the all-experts scan's step in Mixtral's 64-token chunk: (layer,
    # expert) of the (8, 8, rows, K/2) stacks, two prefetched indices
    "matmul-l8-e8-m512-gu": matmul(512, 28672, 4096, lead=(8, 8)),
    "matmul-l8-e8-m512-down": matmul(512, 4096, 14336, lead=(8, 8)),
    **CELL_MATMULS,
    # repaired: inline matvec VMEM at K=14336
    "repaired-matvec-inline-w2": matvec_inline(DIM, HIDDEN),
    # the delta-rule kernels at Kimi-Linear's sizes: a step of 8 slots, an
    # 8-token and a 64-token chunk of one
    "kda-step": kda(0),
    "kda-chunk-t8": kda(8),
    "kda-chunk-t64": kda(64),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_compiles_for_v5e(chip, case):
    fn, shapes, static = CASES[case]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
            for shape, dtype in shapes]
    static = {k: (jax.ShapeDtypeStruct((), I32, sharding=chip)
                  if v is TRACED_I32 else v) for k, v in static.items()}
    compiled = fn.lower(*args, interpret=False, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()


# kv heads a step of the paged-attention kernel takes as one batched product
# (`head_group`), by case: all of them up to 3072 query rows a group, which is
# every case but a 64-token chunk at g 9 (four of eight, 2304 rows)
PAGED_HEAD_GROUPS = {
    "paged-b8-t1": 8, "paged-b8-t5": 8, "paged-tp4-b4-t1": 2,
    "paged-b8-t64-w1024": 8, "paged-b8-t8-w512": 8, "paged-b8-t1-w1024": 8,
    "paged-b8-t64-w256": 8, "paged-tp4-b8-t64": 2, "paged-g6-b8-t1": 8,
    "paged-g6-b8-t64": 8, "paged-b8-t5-w304": 8, "paged-b8-t1-w4096": 8,
    "paged-window-g7-b8-t64-w1024": 4, "paged-window-g7-b8-t1-w1024": 4,
    "paged-window-g7-b8-t64-w8192": 4, "paged-window-b8-t8-w512": 8,
    "paged-lead-b1-t8-w1024": 8, "paged-lead-b1-t64-w1024": 8,
    "paged-window-g7-b1-t64-w1024": 4, "paged-window-g6-b1-t64-w2048": 8,
    "paged-window-g9-b1-t64-w2048": 4, "paged-window-g9-b8-t64-w1024": 4,
    "paged-window-g9-b8-t1-w2048": 8,
    "paged-padded-heads-b8-t1-w1024": 8, "paged-padded-heads-b1-t64-w1024": 8,
}


def test_paged_cases_take_their_heads_in_the_groups_stated():
    """What the head-group rule chose at every paged case compiled above."""
    chosen = {}
    for case, (fn, shapes, _) in CASES.items():
        if fn is paged_attention:
            (_, t, hq, _), (_, _, hk, _, _) = shapes[0][0], shapes[1][0]
            chosen[case] = head_group(t, hq // hk, hk)
    assert chosen == PAGED_HEAD_GROUPS


# the kinds of block pool the cells hold (and a fourth model on the first), each at its configuration's
# widths and depth (fewer experts than the files hold: an expert's width is
# kept, and the commit never sees their count)
STEP_POOLS = {
    "hk8": ("mistral-7b", {}),
    "hk4": ("smallthinker-21b-a3b", {"moe_num_primary_experts": 8}),
    "latent": ("ax-k1-ep4-l7", {"n_routed_experts": 8}),
    # three stacks of two kinds of layer: groups of 9 behind a window of 512
    # and of 6 without, each kernel under its kind's name, the gate's
    # (72, 3072) and (48, 3072) matrices through the dequant-matmul
    "kinds": ("laguna-s-2.1-l5", {"num_experts": 16}),
    # layers that hold a state and no keys (LFM2): ONE scan whose body picks
    # the mixer, a pool of 6 of 24 layers with heads of 64 in lanes of 128,
    # and beside it the slots' rings and the blocks' state snapshots
    "state": ("lfm2-8b-a1b", {"num_experts": 8}),
}
# every configuration of the benchmark, for what holds of all their weights:
# the pools' five and the two whose pools are of a kind already there
# (Mixtral's four experts keep a 64-token chunk's 72 rows x 2 in the grouped
# layer, as its eight do)
STEP_MODELS = {
    **STEP_POOLS,
    "e8": ("mixtral-8x7b-l8", {"num_local_experts": 4}),
    "ssm": ("granite-4.0-h-small-l10", {"num_local_experts": 16}),
    # a delta-rule kind beside a latent kind, a leading dense layer
    "kda": ("kimi-linear-48b-a3b-l8", {"num_experts": 16}),
}
# `jit_step` at T = 1 and at a 64-token chunk as the scheduler dispatches it
# (told which row prefills: 72 compact rows, `forward.RowMap`), and a 2-step
# decode scan with the pools in its carry (`make_batched_decode_loop`'s form)
STEP_PROGRAMS = {"t1": {"chunk": 1}, "t64": {"chunk": 64}, "scan2": {"scan": 2}}


@pytest.fixture(scope="module")
def step_model(chip):
    """(spec, parameter shapes, configuration) of a `STEP_MODELS` entry,
    drawn once a module."""
    return functools.cache(lambda pool: aot_step.model_shapes(
        STEP_MODELS[pool][0], chip, **STEP_MODELS[pool][1]))


# the programs the tests below read for more than one thing: the decode
# step, an 8-token and a 64-token chunk as the scheduler dispatches them, the
# K-step scan
STEP_KINDS = {"t1": {"chunk": 1}, "t8": {"chunk": 8}, "t64": {"chunk": 64},
              "scan8": {"scan": 8}}


@pytest.fixture(scope="module")
def step_text(chip, step_model):
    """The compiled text of a `STEP_MODELS` entry's `STEP_KINDS` program,
    compiled once a module (with `DLT_PALLAS_INTERPRET` taken out by the
    test that asks: the chip's programs hold kernels)."""
    def text(model, program):
        assert "DLT_PALLAS_INTERPRET" not in os.environ
        spec, shapes, cfg = step_model(model)
        return aot_step.compile_step(spec, shapes, cfg, chip,
                                     **STEP_KINDS[program]).as_text()

    return functools.cache(text)


@pytest.mark.parametrize("program", list(STEP_PROGRAMS))
@pytest.mark.parametrize("pool", list(STEP_POOLS))
def test_step_program_updates_the_pool_in_place(chip, step_model, pool,
                                                program, monkeypatch):
    """No instruction of the compiled step program results in an array of
    the pool's shape in another layout than the donated parameter's, and
    none copies one: the commit writes the new rows where the pool lies
    (until PR 37 a scatter made XLA re-lay the whole K and V pool before
    and after it, four copies a dispatch and two more a scan step)."""
    spec, shapes, cfg = step_model(pool)
    # conftest.py asks for interpret mode; the chip's programs hold kernels
    monkeypatch.delenv("DLT_PALLAS_INTERPRET")
    text = aot_step.compile_step(spec, shapes, cfg, chip,
                                 **STEP_PROGRAMS[program]).as_text()
    assert "tpu_custom_call" in text
    for side in aot_step.held_pools(spec, cfg):
        assert aot_step.pool_relayouts(text, side) == []
    if spec.mixed:
        # the second kind of state is updated where it lies too: with the
        # layers as the arrays' leading axis, or 18 of them unpadded on the
        # second-minor one, XLA re-laid ring and snapshots a program (302 MB
        # in and out; PERF.md section 6, PR 42). A scan may keep the ring in
        # the faster memory space for its steps (a copy in and out a scan)
        from distributed_llama_tpu.models.forward import init_state

        ring, snaps = jax.eval_shape(lambda: init_state(
            spec, 8, cfg["engine"]["kv_pool_blocks"], jnp.bfloat16))
        assert aot_step.pool_relayouts(text, snaps.shape) == []
        if "scan" not in STEP_PROGRAMS[program]:
            assert aot_step.pool_relayouts(text, ring.shape) == []
        assert text.count("tpu_custom_call") <= 16  # one scan, not thirteen
    chunk = STEP_PROGRAMS[program].get("chunk", 1)
    if chunk > 1:  # the head ran at the one sampled position a row
        assert aot_step.logits_blocks(text, 8, chunk, cfg["vocab_size"]) == []


@pytest.mark.parametrize("program", list(STEP_KINDS))
@pytest.mark.parametrize("model", list(STEP_MODELS))
def test_step_program_reads_the_scale_planes_as_stored(step_text, model,
                                                       program, monkeypatch):
    """No step program of any configuration (the decode step, an 8-token
    and a 64-token chunk as the scheduler dispatches them, the K-step scan)
    copies or re-lays a Q40 weight's scale plane: the weights' repack stores
    it in whole lane tiles (`quants.to_scale_plane`), which the chip keeps
    row-major and the kernels' blocks read in place. Until PR 46 a plane of
    K/32 = 24, 80 or 448 columns lay with its rows minor and every program
    copied each stack (`copy -> s16[24,64,1536,80]`, 1.0 to 1.7 GB of
    temporaries a program in three configurations) before its layer scan.

    What may remain is named (`aot_step.PLANE_PREFETCH`): a `copy-done`
    whose result lies exactly as the plane is stored, row-major in
    (8,128)(2,1) tiles, but in the faster memory space `S(1)`. That is XLA's
    prefetch of a plane small enough for that space (the 512-row head of
    these compiles, `s16[8,4096,128]` in Mixtral, the dense layers' planes
    of Laguna and Granite), overlapped with the work before its use; it pads
    and transposes nothing, and every program but a few has one. A `copy`,
    a fusion, or a `copy-done` in any other layout is a re-layout."""
    monkeypatch.delenv("DLT_PALLAS_INTERPRET")
    text = step_text(model, program)
    assert "tpu_custom_call" in text and "s16[" in text
    assert aot_step.scale_relayouts(text) == []
    # the reader sees what it is there to see: the same plane, rows minor
    assert aot_step.scale_relayouts(
        "  %copy.61 = s16[24,64,1536,80]{3,2,1,0:T(8,128)(2,1)} copy(%p.1)\n"
        "  %copy-done.9 = s16[512,80]{0,1:T(8,128)(2,1)S(1)} copy-done(%c)"
    ) == ["copy -> s16[24,64,1536,80]{3,2,1,0:T(8,128)(2,1)}",
          "copy-done -> s16[512,80]{0,1:T(8,128)(2,1)S(1)}"]


@pytest.mark.parametrize("program", ["t1", "t64", "scan8"])
@pytest.mark.parametrize("model", list(STEP_MODELS))
def test_step_program_converts_no_whole_weight(step_model, step_text, model,
                                               program, monkeypatch):
    """No step program of any configuration computes anything from a weight
    alone (`aot_step.weight_conversions`): every block-quantized matrix of
    the layers is read packed by a kernel, so none appears by blocks in a
    float dtype, and the embedding table is gathered in the dtype the
    program computes in, so no instruction results in an array of its
    shape. Until PR 49 A.X-K1's leading dense `w2` (7168 x 18432, K over the
    one-row matvec's bound) stayed planar and XLA dequantized it whole in
    every program, three fusions and 264 MB written a step, and the K-step
    scan of every configuration but Granite's cast the float32 table once a
    block (XLA moves the rows' cast in front of the gather and out of the
    loop): together 10 % of A.X-K1's window (PERF.md section 6, PR 49)."""
    _, shapes, _ = step_model(model)
    monkeypatch.delenv("DLT_PALLAS_INTERPRET")
    text = step_text(model, program)
    assert shapes["embedding"].dtype == BF16  # as the engine holds it
    assert aot_step.weight_conversions(text, shapes) == []
    # the reader sees what it is there to see: the parent's lines, at this
    # model's own shapes (a matrix of its layers by blocks, its table)
    t = next(t for st in aot_step.stack_names(shapes)
             for name, t in shapes[st].items()
             if name != "router" and getattr(t, "scales", None) is not None
             and t.shape[-2] * t.shape[-1] >= 1 << 20)
    n, k = t.shape[-2:]
    v, d = shapes["embedding"].shape
    assert aot_step.weight_conversions(
        f"  %convert_multiply_fusion.7 = bf16[{n},{k // 32},32]"
        "{0,2,1:T(8,128)(2,1)} fusion(%get-tuple-element.1216, %g.2), "
        "kind=kLoop, calls=%fused_computation.146\n"
        f"  %mul.9 = f32[{n},{k // 32},32]{{2,1,0}} multiply(%c.1, %b.2)\n"
        f"  %convert.55 = bf16[{v},{d}]{{1,0:T(8,128)(2,1)}} "
        "convert(%p__embedding__.1)\n"
        f"  %p__embedding__.1 = f32[{v},{d}]{{1,0}} parameter(3)\n"
        f"  %gte.5 = bf16[{v},{d}]{{1,0}} get-tuple-element(%w), index=4\n"
        f"  %x.3 = bf16[72,{k // 32},32]{{2,1,0}} multiply(%a, %b)", shapes
    ) == [f"convert_multiply_fusion -> bf16[{n},{k // 32},32]",
          f"mul -> f32[{n},{k // 32},32]", f"convert -> bf16[{v},{d}]"]


@pytest.mark.parametrize("pool", ["hk8", "kinds"])
def test_chunk_program_builds_no_rectangle_of_q(chip, step_model, pool,
                                                monkeypatch):
    """A 64-token chunk as the scheduler dispatches it reads the pool over
    the compact rows, the lead slot's 64 queries in one call of the kernel
    and one query a slot in a second (`forward.RowMap.attend`; ISSUE 45):
    no instruction of the compiled program results in an array of the
    rectangle's q, as the projections leave it (8, 64, heads, 128) or as
    the kernel takes it (8, kv heads, 64 x group, 128), so none is copied
    either; the program of a block whose every position is real holds it,
    and one kernel a run of like layers fewer."""
    import re

    spec, shapes, cfg = step_model(pool)
    monkeypatch.delenv("DLT_PALLAS_INTERPRET")
    text, rect = (aot_step.compile_step(
        spec, shapes, cfg, chip, chunk=64, rectangle=r).as_text()
        for r in (False, True))
    kinds = ([spec.of_kind(i) for i in range(len(spec.kinds))]
             if spec.kinds else [spec])
    for kind in kinds:
        hq, hk, hs = kind.n_heads, kind.n_kv_heads, kind.head_size
        g = hq // hk
        q = re.compile(rf"= \(?(?:bf16|f32)\[8,(?:64,{hq}|{hk},{64 * g}|"
                       rf"64,{hk},{g}),{hs}\]")
        assert q.search(rect), "the rectangular program's own q"
        found = [ln.strip()[:120] for ln in text.splitlines() if q.search(ln)]
        assert found == [], found[:3]
        # the two calls' q in its place: (1, hk, 64 x g, hs), (8, hk, g, hs)
        for b, rows in ((1, 64 * g), (8, g)):
            assert re.search(rf"bf16\[{b},{hk},{rows},{hs}\]", text)
    runs = len(spec.runs())
    assert (text.count("tpu_custom_call") - rect.count("tpu_custom_call")
            == runs)


def test_step_program_carries_the_running_matrices_in_place(chip, monkeypatch):
    """granite-4.0-h-small's T = 1 step for a described v5e: the SSD kernels
    are in it under their names, the running matrices (8 slots x 9 layers x
    128 heads x 64 x 128 float32, 302 MB) ride the layer scan and its
    `lax.cond` without a copy, the snapshot pool is written in place, and
    the slots' rings of u rows (8448 wide) are neither copied nor re-laid: a
    commit that read the ring as the dispatch found it while another loop
    wrote it made XLA copy 138 MB in and out (PERF.md section 6, PR 44)."""
    import re

    spec, shapes, cfg = aot_step.model_shapes(
        "granite-4.0-h-small-l10", chip, num_local_experts=16)
    monkeypatch.delenv("DLT_PALLAS_INTERPRET")
    text = aot_step.compile_step(spec, shapes, cfg, chip, chunk=1).as_text()
    assert "ssd_step" in text and text.count("tpu_custom_call") <= 16
    for side in aot_step.held_pools(spec, cfg):
        assert aot_step.pool_relayouts(text, side) == []
    from distributed_llama_tpu.models.forward import init_state

    ring, snaps, h, _, snap_h, _ = jax.eval_shape(lambda: init_state(
        spec, 8, cfg["engine"]["kv_pool_blocks"], jnp.bfloat16))
    assert h.shape == (8, 9, 128, 64, 128) and snap_h.shape[0] == 49
    assert aot_step.pool_relayouts(text, ring.shape) == []
    assert aot_step.pool_relayouts(text, snaps.shape) == []
    for a in (h, snap_h):
        shape = "f32[" + ",".join(str(d) for d in a.shape) + "]"
        copies = [ln for ln in text.splitlines()
                  if re.search(r"= " + re.escape(shape) + r"\S* copy\(", ln)]
        assert copies == [], copies[:2]


@pytest.mark.parametrize("program", list(STEP_KINDS))
def test_step_program_carries_the_delta_rule_matrices_in_place(
        step_model, step_text, program, monkeypatch):
    """Kimi-Linear's four step programs for a described v5e: the KDA kernels
    are in them under their names (`kda_step`; a chunk's `kda_chunk` too),
    beside the latent attention kernel; the running matrices (8 slots x 6
    layers x 32 heads x 128 x 128 float32, 101 MB) ride BOTH layer scans
    (the leading dense layer's and the expert layers') and the `lax.cond`
    without a copy, the snapshot pool is written in place, and the slots'
    rings of [q | k | v] rows (12288 wide), the snapshots' tails and the
    pool's latent rows are neither copied nor re-laid."""
    import re

    from distributed_llama_tpu.models.forward import init_state

    spec, shapes, cfg = step_model("kda")
    monkeypatch.delenv("DLT_PALLAS_INTERPRET")
    text = step_text("kda", program)
    assert "kda_step" in text and "latent_paged_attention" in text
    assert ("kda_chunk" in text) == (STEP_KINDS[program].get("chunk", 1) > 1)
    assert len(spec.runs()) == 2 and text.count("tpu_custom_call") <= 26
    for side in aot_step.held_pools(spec, cfg):
        assert aot_step.pool_relayouts(text, side) == []
    ring, snaps, h, _, snap_h, _ = jax.eval_shape(lambda: init_state(
        spec, 8, cfg["engine"]["kv_pool_blocks"], jnp.bfloat16))
    assert h.shape == (8, 6, 32, 128, 128) and snap_h.shape[0] == 25
    assert ring.shape == (8, 64, 16, 12288)
    assert aot_step.pool_relayouts(text, ring.shape) == []
    assert aot_step.pool_relayouts(text, snaps.shape) == []
    for a in (h, snap_h):
        shape = "f32[" + ",".join(str(d) for d in a.shape) + "]"
        copies = [ln for ln in text.splitlines()
                  if re.search(r"= " + re.escape(shape) + r"\S* copy\(", ln)]
        assert copies == [], copies[:2]
