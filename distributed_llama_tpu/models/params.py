"""Parameter pytrees for all supported architectures.

Layout convention: every per-layer tensor is STACKED along a leading n_layers axis so the
forward pass can `lax.scan` over layers (one compiled block program instead of the
reference's hand-unrolled 25-tasks-per-layer lists, llama2-tasks.cpp:246-276).

Weight matrices keep the reference's (out, in) row-major orientation with quantization
blocks along `in`. Tensor inventory mirrors the `.m` file exactly
(transformer.cpp:494-529):

    embedding (vocab, dim) f32 as a loader gives it and a file holds it; the
       engine holds it in ITS dtype (`hold_dense`)
    wcls (vocab, dim) [weights ftype]
    per layer: wq (q_dim, dim), wk (kv_dim, dim), wv (kv_dim, dim), wo (dim, q_dim)
       (q_dim = n_heads x head_size, which is dim unless the header states head_dim),
       dense: w1/gate (hidden, dim), w2/down (dim, hidden), w3/up (hidden, dim)
       moe:   router (n_experts, dim), moe_up/moe_gate (E, hidden, dim),
              moe_down (E, dim, hidden)
       norms: rms_att (dim,), rms_ffn (dim,) [+ grok1: rms_moe, rms_ffn2]
       latent attention and the shared expert: `block_tensor_shapes`
    rms_final (dim,) f32

A model with leading dense layers (ModelSpec.lead_layers) has TWO stacks,
`params["lead"]` and `params["blocks"]`, each stacked over its own layers: the
tensors of the two kinds differ. A model with kinds of attention layer
(ModelSpec.kinds: another head count, so another shape of wq and wo) has one
stack a RUN of like layers, under the names `ModelSpec.runs()` gives them.
`stack_names(params)` lists the stacks a params dict holds, in layer order.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import numpy as np

from ..obs import metrics
from ..quants import QK, FloatType, QTensor, scale_plane_cols
from .spec import ArchType, ModelSpec

Params = dict[str, Any]


def block_tensor_shapes(spec: ModelSpec, lead: bool = False
                        ) -> dict[str, tuple[tuple[int, ...], bool]]:
    """Per-layer tensor name -> (shape-without-layer-axis, is_quantized_matmul).

    Order matters: it is the `.m` file tensor order within a layer
    (transformer.cpp:498-523). `lead`: the tensors of a leading dense layer
    (spec.lead_layers), which stand in `params["lead"]`.

    Latent attention (spec.latent) has wq_a (q_lora_rank, dim), wq_b (q_dim,
    q_lora_rank), wkv_a (kv_lora_rank + qk_rope_head_dim, dim), the published
    kv_b projection as its two halves by head, w_uk (H, qk_nope_head_dim,
    kv_lora_rank) and w_uv (H, v_head_dim, kv_lora_rank), and the norms rms_q
    and rms_kv; a routed block with a shared expert has sh_gate, sh_down,
    sh_up beside its stacks.
    A spec with kinds of layer is asked for one kind's shapes:
    `block_tensor_shapes(spec.of_kind(run.kind), run.lead)`. The per-head
    output gate (spec.attn_gate) is `wg` (n_heads, dim), behind wo.
    A convolution kind (spec.conv_kernel > 0) has conv_in (3 dim, dim: the
    gates B and C and the input u, in that order), the taps conv_w (dim,
    conv_kernel) and conv_out (dim, dim) in attention's place (`MIXER`);
    a state-space kind (spec.ssm_state > 0; Mamba-2) has ssm_in (inner +
    state_width + heads, dim: the gate z, the convolution's input [x | B | C]
    and dt, in that order), the taps ssm_conv_w (state_width, conv_kernel)
    with their bias ssm_conv_b, a head's ssm_dt_bias, ssm_a_log and ssm_d,
    the gated norm's weight ssm_norm (inner,) and ssm_out (dim, inner);
    a delta-rule kind (spec.kda_heads > 0; Kimi Delta Attention) has kda_in
    (state_width, dim: the convolution's input [q | k | v]), the taps
    kda_conv_w (state_width, conv_kernel), kda_lo (2 rank, dim: the decay's
    and the output gate's first projections [f_a | g_a]), their second
    projections kda_fb (heads x key, rank) and kda_gb (heads x value, rank),
    kda_b (heads, dim: beta), a channel's kda_dt_bias, a head's kda_a_log,
    the head norm's weight kda_norm (value,) and kda_out (dim, heads x value).
    A latent kind with q_lora_rank 0 has ONE wq (q_dim, dim) and no rms_q.
    QK-norm (spec.qk_norm) adds rms_qh and rms_kh (head_size,), a selection
    bias (spec.router_bias) router_bias (n_router,) behind the router.
    """
    assert not spec.kinds, "ask with spec.of_kind(kind): shapes differ by kind"
    d, h, kv, e = spec.dim, spec.hidden_dim, spec.kv_dim, spec.n_experts
    qd = spec.q_dim  # n_heads x head_size: dim unless the header states head_dim
    shapes: dict[str, tuple[tuple[int, ...], bool]]
    if spec.kda_heads:
        nh, cw, rank = spec.kda_heads, spec.state_width, spec.kda_rank
        shapes = {
            "kda_in": ((cw, d), True),
            "kda_conv_w": ((cw, spec.conv_kernel), False),
            "kda_lo": ((2 * rank, d), True),
            "kda_fb": ((nh * spec.kda_key_dim, rank), True),
            "kda_gb": ((nh * spec.kda_value_dim, rank), True),
            "kda_b": ((nh, d), True),
            "kda_dt_bias": ((nh * spec.kda_key_dim,), False),
            "kda_a_log": ((nh,), False),
            "kda_norm": ((spec.kda_value_dim,), False),
            "kda_out": ((d, nh * spec.kda_value_dim), True),
        }
    elif spec.ssm_state:
        inner, cw = spec.ssm_inner, spec.state_width
        nh = spec.ssm_heads
        shapes = {
            "ssm_in": ((inner + cw + nh, d), True),
            "ssm_conv_w": ((cw, spec.conv_kernel), False),
            "ssm_conv_b": ((cw,), False),
            "ssm_dt_bias": ((nh,), False),
            "ssm_a_log": ((nh,), False),
            "ssm_d": ((nh,), False),
            "ssm_norm": ((inner,), False),
            "ssm_out": ((d, inner), True),
        }
    elif spec.conv_kernel:
        shapes = {
            "conv_in": ((3 * d, d), True),
            "conv_w": ((d, spec.conv_kernel), False),
            "conv_out": ((d, d), True),
        }
    elif spec.latent:
        r, nh = spec.kv_lora_rank, spec.n_heads
        shapes = {
            **({"wq_a": ((spec.q_lora_rank, d), True),
                "wq_b": ((qd, spec.q_lora_rank), True)}
               if spec.q_lora_rank else {"wq": ((qd, d), True)}),
            "wkv_a": ((r + spec.qk_rope_head_dim, d), True),
            "w_uk": ((nh, spec.qk_nope_head_dim, r), True),
            "w_uv": ((nh, spec.v_head_dim, r), True),
            "wo": ((d, spec.o_dim), True),
        }
    else:
        shapes = {
            "wq": ((qd, d), True),
            "wk": ((kv, d), True),
            "wv": ((kv, d), True),
            "wo": ((d, qd), True),
        }
    if spec.attn_gate:
        shapes["wg"] = ((spec.n_heads, d), True)
    if spec.qk_norm and not spec.conv_kernel:
        shapes["rms_qh"] = ((spec.head_size,), False)
        shapes["rms_kh"] = ((spec.head_size,), False)
    if spec.is_moe and not lead:
        shapes["router"] = ((spec.n_router, d), True)
        if spec.router_bias:
            shapes["router_bias"] = ((spec.n_router,), False)
        shapes["moe_up"] = ((e, h, d), True)
        shapes["moe_gate"] = ((e, h, d), True)
        shapes["moe_down"] = ((e, d, h), True)
        if spec.shared_hidden_dim:
            sh = spec.shared_hidden_dim
            shapes["sh_gate"] = ((sh, d), True)
            shapes["sh_down"] = ((d, sh), True)
            shapes["sh_up"] = ((sh, d), True)
    else:
        if lead:
            h = spec.lead_hidden_dim
        shapes["w1"] = ((h, d), True)
        shapes["w2"] = ((d, h), True)
        shapes["w3"] = ((h, d), True)
    shapes["rms_att"] = ((d,), False)
    shapes["rms_ffn"] = ((d,), False)
    if spec.latent:
        if spec.q_lora_rank:
            shapes["rms_q"] = ((spec.q_lora_rank,), False)
        shapes["rms_kv"] = ((spec.kv_lora_rank,), False)
    if spec.arch_type == ArchType.GROK1:
        shapes["rms_moe"] = ((d,), False)
        shapes["rms_ffn2"] = ((d,), False)
    return shapes


# a layer's tensors that belong to its MIXER (attention, or a convolution):
# in a run of layers of several kinds (`ModelSpec.mixed`) each kind's stand
# stacked over THAT kind's layers of the run, every other tensor over all of
# the run's (`run_tensor_shapes`)
SSM = ("ssm_in", "ssm_conv_w", "ssm_conv_b", "ssm_dt_bias", "ssm_a_log",
       "ssm_d", "ssm_norm", "ssm_out")
KDA = ("kda_in", "kda_conv_w", "kda_lo", "kda_fb", "kda_gb", "kda_b",
       "kda_dt_bias", "kda_a_log", "kda_norm", "kda_out")
LATENT = ("wq_a", "wq_b", "wkv_a", "w_uk", "w_uv", "rms_q", "rms_kv")
MIXER = frozenset({"wq", "wk", "wv", "wqkv", "wo", "rms_qh", "rms_kh",
                   "conv_in", "conv_w", "conv_out", *SSM, *KDA, *LATENT})


def is_state_tensor(name: str) -> bool:
    """Whether a MIXER tensor is the state kind's (a convolution's, a
    state-space mixer's, a delta-rule mixer's) and not attention's."""
    return name.startswith(("conv_", "ssm_", "kda_"))


def run_tensor_shapes(spec: ModelSpec, run) -> dict[
        str, tuple[tuple[int, ...], bool]]:
    """One run's tensors as `params[run.name]` holds them: name -> (shape
    WITH the leading stack axis, quantized). A run of one kind: every tensor
    `run.depth` deep. A run of a model with convolution layers: the mixers'
    tensors as deep as their kind has layers in the run (none of a kind that
    has none), everything else `run.depth` deep."""
    if not spec.mixed:
        return {n: ((run.depth, *shape), q) for n, (shape, q) in
                block_tensor_shapes(spec.of_kind(run.kind), run.lead).items()}
    out: dict[str, tuple[tuple[int, ...], bool]] = {}
    of_run = spec.layer_kinds[run.first:run.first + run.depth]
    for k in sorted(set(of_run)):
        for n, (shape, q) in block_tensor_shapes(spec.of_kind(k),
                                                 run.lead).items():
            depth = of_run.count(k) if n in MIXER else run.depth
            out.setdefault(n, ((depth, *shape), q))
    return out


def layer_tensor_shapes(spec: ModelSpec, layer: int) -> dict[
        str, tuple[tuple[int, ...], bool]]:
    """Layer `layer`'s own tensors in `.m` file order, without a stack axis:
    `block_tensor_shapes` of the layer's kind."""
    kind = spec.layer_kinds[layer] if spec.kinds else None
    return block_tensor_shapes(spec.of_kind(kind), layer < spec.lead_layers)


# what a state-space mixer's unquantized tensors are drawn AROUND, as
# (layer's width, last axis) -> values: four different taps with the newest
# the largest, a bias, steps of 0.001 to 0.1 ahead of the softplus, A between
# 1 and 16 over the heads, a skip of 1
_SSM_DRAWN = {
    "ssm_conv_w": lambda n, k: np.asarray([0.25, -0.5, 0.75, 1.0][-k:]),
    "ssm_conv_b": lambda n, k: 0.1,
    "ssm_dt_bias": lambda n, k: np.log(np.expm1(np.geomspace(1e-3, 1e-1, n))),
    "ssm_a_log": lambda n, k: np.log(np.linspace(1.0, 16.0, n)),
    "ssm_d": lambda n, k: 1.0,
}
# a delta-rule mixer's: the same taps (no bias), a channel's step of 0.001 to
# 0.1 ahead of the softplus, A between 1 and 16 over the heads
_SSM_DRAWN.update({"kda_" + n: _SSM_DRAWN["ssm_" + n]
                   for n in ("conv_w", "dt_bias", "a_log")})


def init_random_params(spec: ModelSpec, weights_ftype: FloatType = FloatType.F32,
                       seed: int = 0, scale: float = 0.02) -> Params:
    """Random-weight model for tests/benchmarks (the reference's golden-test pattern:
    seeded random weights, llama2-tasks-test.cpp:527-608)."""
    rng = np.random.RandomState(seed)

    def randn(*shape):
        return (rng.randn(*shape) * scale).astype(np.float32)

    def stack(run) -> Params:
        blocks: Params = {}
        for name, (shape, quantized) in run_tensor_shapes(spec, run).items():
            full = randn(*shape)
            if quantized:
                blocks[name] = QTensor.from_float(full, weights_ftype)
            elif name in ("conv_w", "router_bias"):
                # taps and a selection bias that do something: of the size
                # of the values they meet, not norm weights around 1
                blocks[name] = full * (25.0 if name == "conv_w" else 5.0)
            elif name in _SSM_DRAWN:
                # taps, decays and steps that do something, with the draw's
                # noise around them
                blocks[name] = (_SSM_DRAWN[name](shape[1], shape[-1])
                                + full * 5.0).astype(np.float32)
            else:
                blocks[name] = full + 1.0  # norm weights around 1
        return blocks

    # the draws keep their order (the stacks in layer order, embedding,
    # final norm, head): a seed gives the model it always gave
    out = {run.name: stack(run) for run in spec.runs()}
    out["embedding"] = randn(spec.vocab_size, spec.dim)
    out["rms_final"] = randn(spec.dim) + 1.0
    out["wcls"] = QTensor.from_float(randn(spec.vocab_size, spec.dim),
                                     weights_ftype)
    return out


def stack_names(params: Params) -> list[str]:
    """The layer stacks a params dict holds ("lead", "blocks", and a stack a
    run where the model has kinds of layer: `ModelSpec.runs`), as inserted:
    in layer order from every loader of this repo."""
    return [k for k, v in params.items() if isinstance(v, dict)]


# the two halves of latent attention's kv_b projection: batched by head and
# small, so they are held dense in the engine's dtype, not as Q40 blocks; and
# a delta-rule mixer's two second gate projections, whose input is the rank
# (128): four blocks a row, which no kernel here reads and whose scale plane
# XLA re-laid in every program (the compiled text, PR 48)
_HELD_DENSE = ("w_uk", "w_uv", "kda_fb", "kda_gb")


def hold_dense(params: Params, dtype, spec: ModelSpec | None = None) -> Params:
    """`params` with the tensors the program multiplies by head
    (`_HELD_DENSE`) dequantized once into `dtype`: what a loader's QTensor of
    them becomes before the engine places the weights. Given the `spec`, the
    embedding table too is held in `dtype` where that is not float32, the
    spec's `embedding_multiplier` made in float32 before the cast, as
    `forward` orders the two on the rows it gathers from a float32 table: a
    step program then gathers rows of the dtype it computes in, and a K-step
    scan no longer casts the whole table once a block (XLA moved the rows'
    cast in front of the gather and out of the loop: 1.17 GB read and 0.59
    written a block of A.X-K1's; PERF.md section 6, PR 49). A table that is
    not float32 is one already held."""
    out = dict(params)
    for st in stack_names(params):
        if any(isinstance(params[st].get(n), QTensor) for n in _HELD_DENSE):
            out[st] = {n: (t.dequantize(dtype=dtype)
                           if n in _HELD_DENSE and isinstance(t, QTensor)
                           else t) for n, t in params[st].items()}
    table = params["embedding"]
    if (spec is not None and table.dtype == np.float32
            and np.dtype(dtype) != np.float32):
        if spec.embedding_multiplier != 1.0:
            table = table * np.float32(spec.embedding_multiplier)
        out["embedding"] = table.astype(dtype)
    return out


_I8_CONVERTIBLE = (FloatType.Q40, FloatType.Q80)

# per-layer tensors whose scan-sliced (and, for MoE stacks, expert-sliced) form is the
# 2-D matvec the decode kernels consume. The router stays planar (use_pallas=False in
# forward — it is tiny). Tensors in _COL_SHARDED get their in-axis TP-sliced
# (ColMatmulSlice), so the i4p split-plane pack must be applied per column group
# (QTensor.to_i4p_layout).
_DENSE_MATMULS = {"wq", "wk", "wv", "wo", "wg", "w1", "w2", "w3",
                  "conv_in", "conv_out", "ssm_in", "ssm_out",
                  "kda_in", "kda_lo", "kda_b", "kda_out",
                  "moe_up", "moe_gate", "moe_down",
                  "wq_a", "wq_b", "wkv_a", "sh_gate", "sh_up", "sh_down"}
_COL_SHARDED = {"wo", "w2", "moe_down", "sh_down"}


def _kernel_convertible(t: QTensor, stacked: bool,
                        col_groups: int | None = None) -> bool:
    """Whether some kernel reads the decode layout this weight would take, at
    some number of rows. `col_groups`: the column groups of its split-plane
    pack (`_i4p_groups`) where the caller would pack a `_DENSE_MATMULS` matrix
    so: the dequant-matmul reads that pack at 2 to 512 rows whatever K is
    (`q4_mm_reads`), and at ONE row and a K over the matvec's bound `qmatmul`
    dequantizes it as it would the planar blocks. Without it the one-row
    matvec's bound (`q8_shape_supported`) decides alone: int8 planes are read
    by that kernel only, and the head and an expert stack (one expert's slice
    is what a kernel sees) keep that gate."""
    from ..ops.pallas_q4_mm import q4_mm_reads
    from ..ops.pallas_q8 import q8_shape_supported

    if not (isinstance(t, QTensor) and t.ftype in _I8_CONVERTIBLE):
        return False
    shape = t.shape[1:] if stacked else t.shape
    if len(shape) == 3:  # MoE expert stack (E, out, in)
        return q8_shape_supported(*shape[1:])
    if len(shape) != 2:
        return False
    return q8_shape_supported(*shape) or bool(
        col_groups and q4_mm_reads(shape[1] // col_groups))


_REPACKED = metrics.counter(
    "weights_repacked_bytes_total",
    "bytes of weights brought into a kernel layout, by where the shuffle "
    "ran: on the device (Q40 to split-plane nibbles, prepare_for_pallas) or "
    "on the host (int8 planes, and the NumPy/native oracle of the former)",
    labelnames=("where",))


_SCALE_PLANE_BYTES = metrics.gauge(
    "weights_scale_plane_bytes",
    "resident bytes of the Q40 weights' scale planes (int16 f16 bits, K/32 "
    "a row in whole lane tiles: quants.to_scale_plane) of the engine built "
    "last; the file's own scales are 2 bytes a block of 32 weights")


def scale_plane_bytes(params: Params) -> int:
    """Bytes the i4p weights' scale planes hold on the device(s), padding
    included; sets the `weights_scale_plane_bytes` gauge."""
    leaves = [t for st in stack_names(params) for t in params[st].values()]
    n = sum(t.scales.nbytes for t in leaves + [params["wcls"]]
            if isinstance(t, QTensor) and t.layout == "i4p")
    _SCALE_PLANE_BYTES.set(n)
    return n


_STEP_CONVERTED_BYTES = metrics.gauge(
    "weights_step_converted_bytes",
    "bytes of weights, of the engine built last, that a step program "
    "converts whole before it can use them: block-quantized matrices of the "
    "layers left planar beside the kernels (XLA dequantizes such a matrix "
    "every step; the router, planar by design, apart), and the embedding "
    "table where its dtype is not the engine's (a K-step scan casts it once "
    "a block)")


def step_converted_bytes(params: Params, dtype, use_pallas: bool) -> int:
    """Bytes of the placed weights that every step (the matrices) or every
    scan block (the table) computes from the weights alone; sets the
    `weights_step_converted_bytes` gauge. An engine without the kernels
    dequantizes every matrix by choice (the tests' oracle): its matrices are
    not counted."""
    n = 0
    if use_pallas:
        n = sum(t.nbytes() for st in stack_names(params)
                for name, t in params[st].items()
                if name in _DENSE_MATMULS and isinstance(t, QTensor)
                and t.ftype in _I8_CONVERTIBLE and t.layout == "planar")
    table = params["embedding"]
    if table.dtype != np.dtype(dtype):
        n += table.nbytes
    _STEP_CONVERTED_BYTES.set(n)
    return n


def _i4p_groups(t: QTensor, tp: int, col_sharded: bool) -> int | None:
    """The column groups a Q40 weight's split-plane pack needs, None where the
    i4p alignment does not hold (the weight then takes int8 planes)."""
    if not isinstance(t, QTensor) or t.ftype != FloatType.Q40:
        return None
    k = t.shape[-1]
    groups = tp if col_sharded else 1
    return groups if k % groups == 0 and (k // groups) % 64 == 0 else None


def _decode_layout(t: QTensor, tp: int, col_sharded: bool) -> QTensor:
    """One weight's decode-kernel layout ON THE HOST: Q40 -> i4p split-plane
    nibbles (0.5625 B/weight, the file's own density); Q80, and Q40 whose
    alignment i4p cannot take -> int8 planes (pallas_q8 kernel). The oracle
    of `_repack_on_device`, and the path of the int8 planes."""
    groups = _i4p_groups(t, tp, col_sharded)
    out = (t.to_i8_layout() if groups is None
           else t.to_i4p_layout(col_groups=groups))
    _REPACKED.labels(where="host").inc(out.nbytes())
    return out


def _split_rows(a, groups: int, row_axis: int):
    rows = a.shape[row_axis]
    assert rows % groups == 0, (a.shape, groups)
    return a.reshape(*a.shape[:row_axis], groups, rows // groups,
                     *a.shape[row_axis + 1:])


def _concat_rows_grouped(tensors: list[QTensor], tp: int, row_axis: int = 1
                         ) -> QTensor:
    """Concatenate planar QTensors along the row (out) axis, interleaved per TP
    group: the result's rows are [t0_g0, t1_g0, ..., t0_g1, t1_g1, ...] where g_i
    is shard i's row slice of each input, so a P('tp')-on-rows placement lands each
    shard exactly its own inputs' slices, contiguous. Quant blocks run along the
    *in* axis, so row concatenation never touches block structure (numerics are
    bit-identical to the separate tensors).

    row_axis: index of the out axis in the leaves — 1 for stacked dense weights
    (L, out, ...), 2 for stacked MoE expert stacks (L, E, out, ...)."""
    ft = tensors[0].ftype
    assert all(t.layout == "planar" and t.ftype == ft for t in tensors)

    def cat(leaves):
        # planar leaf shapes: data (..., out, nb, 16|32), scales (..., out, nb)
        out = np.concatenate([_split_rows(a, tp, row_axis) for a in leaves],
                             axis=row_axis + 1)
        return out.reshape(*out.shape[:row_axis], -1,
                           *out.shape[row_axis + 2:])

    return QTensor(ft, cat([np.asarray(t.data) for t in tensors]),
                   cat([np.asarray(t.scales) for t in tensors]), row_groups=tp)


# merged matvec groups: members share the same activation vector, so one kernel
# launch with the row blocks concatenated replaces 3 (QKV) / 2 (gate+up) launches
# — fewer grid setups and quantize/Xexp prologues per layer. moe_gu merges each
# expert's up+gate the same way (halving per-active-expert launches on the MoE
# decode path). The reference has no counterpart (its task lists issue one
# matmul task per tensor, llama2-tasks.cpp:246-276); this is TPU launch-overhead
# engineering.
_FUSE_GROUPS = {"wqkv": ("wq", "wk", "wv"), "w13": ("w1", "w3"),
                "moe_gu": ("moe_up", "moe_gate")}
# out-axis index within each group's stacked planar leaves
_FUSE_ROW_AXIS = {"wqkv": 1, "w13": 1, "moe_gu": 2}


def _fuse_plan(blocks: Params, spec: ModelSpec | None, tp: int,
               moe_sharding: str) -> dict[str, int]:
    """The merged groups that are safe to build: {fused name: the TP-group
    count its members' rows interleave with}."""
    from ..parallel.sharding import effective_kv_heads

    plan = {}
    for fused, members in _FUSE_GROUPS.items():
        ts = [blocks.get(m) for m in members]
        # a merged group's rows are sharded, never its columns: one group
        if not all(_kernel_convertible(t, True, _i4p_groups(t, 1, False))
                   and t.layout == "planar" for t in ts):
            continue
        if len({t.ftype for t in ts}) != 1:
            continue
        groups = tp
        if fused == "moe_gu" and moe_sharding == "expert":
            groups = 1  # whole experts shard over tp; rows stay unsharded
        if any(t.shape[_FUSE_ROW_AXIS[fused]] % groups for t in ts):
            continue
        if fused == "wqkv":
            if spec is None and tp > 1:
                continue  # can't rule out KV replication without the spec
            if spec is not None and effective_kv_heads(spec, tp) != spec.n_kv_heads:
                continue  # replication rewrites wk/wv rows later; keep separate
        plan[fused] = groups
    return plan


def fuse_matvec_groups(blocks: Params, spec: ModelSpec | None, tp: int,
                       moe_sharding: str = "slice") -> Params:
    """Replace wq/wk/wv -> wqkv, w1/w3 -> w13, moe_up/moe_gate -> moe_gu with
    row-concatenated (TP-group interleaved) planar tensors where safe, on the
    host. Skipped per group when a member is not kernel-convertible or (QKV)
    when KV-head replication is active (tp > n_kv_heads expands wk/wv rows at
    shard time, after this runs). Under expert sharding the MoE stacks shard by
    whole experts, not rows, so moe_gu concatenates with NO group interleave."""
    out = dict(blocks)
    for fused, groups in _fuse_plan(blocks, spec, tp, moe_sharding).items():
        members = _FUSE_GROUPS[fused]
        out[fused] = _concat_rows_grouped([blocks[m] for m in members], groups,
                                          row_axis=_FUSE_ROW_AXIS[fused])
        for m in members:
            del out[m]
    return out


@functools.lru_cache(maxsize=None)
def _repack_step(row_groups: int, col_groups: int, row_axis: int, sharding):
    """The jitted program that repacks ONE slice of the leading (layer) axis
    and writes it into the donated result: rows of the members concatenated
    per TP group (`_concat_rows_grouped`), nibbles re-paired within each
    column group (`quants.jnp_to_i4p`), f16 scales to their bit patterns in
    the plane the kernels read (`quants.to_scale_plane`)."""
    import jax

    from ..quants import jnp_to_i4p

    def step(data, scales, i, member_data, member_scales):
        def cat(leaves):
            if len(leaves) == 1:
                return leaves[0]
            out = jax.numpy.concatenate(
                [_split_rows(a, row_groups, row_axis) for a in leaves],
                axis=row_axis + 1)
            return out.reshape(*out.shape[:row_axis], -1,
                               *out.shape[row_axis + 2:])

        d, s = jnp_to_i4p(cat(member_data), cat(member_scales), col_groups)
        return (jax.lax.dynamic_update_index_in_dim(data, d, i, 0),
                jax.lax.dynamic_update_index_in_dim(scales, s, i, 0))

    return jax.jit(step, donate_argnums=(0, 1),
                   out_shardings=None if sharding is None
                   else (sharding, sharding))


def _repack_on_device(members: list[QTensor], row_groups: int,
                      col_groups: int, sharding=None) -> QTensor:
    """Planar Q40 tensors (NumPy from a loader, a memory map, or arrays on a
    device), stacked over a leading axis -> ONE i4p QTensor on the device:
    the members' rows concatenated per TP group, split-plane packed within
    `col_groups` column groups. Bit for bit `_concat_rows_grouped` followed by
    `QTensor.to_i4p_layout(col_groups)`, which stay as the tests' oracle.

    The leading axis is walked: a slice of each member goes up exactly as it
    lies in the host's memory (no reshape there: a loader's or the device
    draw's NumPy array need not be C-contiguous, and flattening such a stack
    is a copy of all of it, 4 s of the dense cell's 7), is shuffled on the
    device, lands in the donated result and is dropped, so the device holds
    the result and one slice's tensors, never a second stack. `sharding` (of
    the result, a NamedSharding) shards the slices the same way less the
    leading axis, so the shuffle stays shard-local and the result is where
    shard_params puts it."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    datas = [t.data for t in members]  # (n, ..., out, nb, 16)
    scales = [t.scales for t in members]  # (n, ..., out, nb)
    n = datas[0].shape[0]
    row_axis = scales[0].ndim - 3  # of a slice: (..., out, nb)
    rows = sum(s.shape[-2] for s in scales)
    lead = scales[0].shape[:-2]
    nb = scales[0].shape[-1]
    slice_sh = None
    if sharding is not None:
        slice_sh = NamedSharding(sharding.mesh,
                                 PartitionSpec(*sharding.spec[1:]))
    out_d = jnp.zeros((*lead, rows, nb * (QK // 2)), jnp.uint8,
                      device=sharding)
    out_s = jnp.zeros((*lead, rows, scale_plane_cols(nb, col_groups)),
                      jnp.int16, device=sharding)
    step = _repack_step(row_groups, col_groups, row_axis, sharding)
    for i in range(n):
        up = [[jax.device_put(a[i], slice_sh) for a in leaves]
              for leaves in (datas, scales)]
        out_d, out_s = step(out_d, out_s, np.int32(i), *up)
    _REPACKED.labels(where="device").inc(out_d.nbytes + out_s.nbytes)
    return QTensor(FloatType.Q40, out_d, out_s, layout="i4p",
                   groups=col_groups, row_groups=row_groups)


def prepare_for_pallas(params: Params, tp: int = 1,
                       moe_sharding: str = "slice",
                       spec: ModelSpec | None = None,
                       fuse: bool = True, mesh=None) -> Params:
    """Repack the dense matmul weights into the Pallas decode-kernel layouts
    (i4p packed nibbles for Q40, int8 planes for Q80). Row/col TP slices stay
    32-block-aligned; col-sharded tensors are packed per TP column group so each
    shard's slice is self-contained. Under expert sharding the MoE stacks shard by
    whole experts, so their in-axes are NOT column-sliced and pack with groups=1.

    fuse=True additionally merges the QKV and gate/up matvec groups into single
    row-concatenated tensors (`_fuse_plan`) so decode launches one kernel per
    group instead of one per tensor.

    Q40 goes to the device as the loader left it and is shuffled there
    (`_repack_on_device`): the result's leaves are device arrays, sharded as
    `shard_params` would place them when `mesh` is given, on the default
    device otherwise. The int8 planes are made on the host."""
    from jax.sharding import NamedSharding, PartitionSpec

    from ..parallel.sharding import param_pspecs

    out: Params = {}

    def convert(members, row_groups, col_sharded, pspec, row_axis=1):
        t = members[0]
        col_groups = _i4p_groups(t, tp, col_sharded)
        if not all(_kernel_convertible(m, True, col_groups)
                   and m.layout == "planar" for m in members):
            return t
        if col_groups is None:  # int8 planes: the host's
            if len(members) > 1:
                t = _concat_rows_grouped(members, row_groups, row_axis)
            return _decode_layout(t, tp, col_sharded)
        sharding = None if mesh is None else NamedSharding(mesh, pspec)
        return _repack_on_device(members, row_groups, col_groups, sharding)

    for st in stack_names(params):
        blocks = params[st]
        plan = _fuse_plan(blocks, spec, tp, moe_sharding) if fuse else {}
        merged = {m: f for f in plan for m in _FUSE_GROUPS[f]}
        # name -> members, row groups
        work: dict[str, tuple[list[QTensor], int]] = {}
        for name, t in blocks.items():
            if name in merged:
                work.setdefault(merged[name], (
                    [blocks[m] for m in _FUSE_GROUPS[merged[name]]],
                    plan[merged[name]]))
            else:
                work[name] = ([t], getattr(t, "row_groups", 1))
        pspecs = param_pspecs({"blocks": work}, moe_sharding)
        out[st] = {}
        for name, (members, row_groups) in work.items():
            if name in _DENSE_MATMULS or name in _FUSE_GROUPS:
                col = name in _COL_SHARDED and not (
                    moe_sharding == "expert" and name.startswith("moe_"))
                out[st][name] = convert(
                    members, row_groups, col, pspecs["blocks"][name],
                    _FUSE_ROW_AXIS.get(name, 1))
            else:
                out[st][name] = members[0]
    wcls = params["wcls"]
    if _kernel_convertible(wcls, stacked=False):
        # the head is a stack of one
        wcls = _map_leaves(
            convert([_map_leaves(wcls, lambda a: a[None])], 1, False,
                    PartitionSpec(None, *param_pspecs(
                        {"blocks": {}}, moe_sharding)["wcls"])),
            lambda a: a[0])
    return {**out, "embedding": params["embedding"],
            "rms_final": params["rms_final"], "wcls": wcls}


def _map_leaves(t: QTensor, fn) -> QTensor:
    """`fn` over a QTensor's leaves, its layout fields kept."""
    return QTensor(t.ftype, fn(t.data), fn(t.scales), layout=t.layout,
                   groups=t.groups, row_groups=t.row_groups)


def expected_experts_touched(n_experts: int, k: int, rows: int) -> float:
    """Distinct experts `rows` tokens read when each takes `k` of `n_experts`
    uniformly: E (1 - (1 - k/E)^rows). 2 of 8 for one row, 7.2 of 8 for eight."""
    return n_experts * (1.0 - (1.0 - k / n_experts) ** rows)


def decode_stream_bytes(params: Params, spec: ModelSpec, rows: int = 1) -> int:
    """Weight + scale bytes one decode step of `rows` sequences streams from HBM
    (embedding row reads excluded; MoE expert stacks count the expected distinct
    experts the rows read, expected_experts_touched: the grouped expert layer
    reads a chosen expert once and an unchosen one not at all). The numerator of
    the achieved-GB/s observability metric."""
    total = 0
    leaves = [nt for st in stack_names(params) for nt in params[st].items()]
    for name, t in leaves + [("wcls", params["wcls"])]:
        n = t.nbytes() if isinstance(t, QTensor) else t.nbytes
        if name.startswith("moe_") and spec.n_experts:
            # a share of a wider router is given that share of the rows
            n = int(n * expected_experts_touched(
                spec.n_router, spec.n_active_experts, rows) / spec.n_router)
        total += n
    return total


def map_params(params: Params, fn: Callable[[Any], Any]) -> Params:
    """Apply fn to every QTensor/array leaf group (QTensor treated atomically)."""
    out: Params = {}
    for k, v in params.items():
        if isinstance(v, dict):
            out[k] = map_params(v, fn)
        else:
            out[k] = fn(v)
    return out
