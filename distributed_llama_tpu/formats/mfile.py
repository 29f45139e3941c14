"""`.m` model file format — byte-compatible reader/writer.

Format (reference: src/transformer.cpp:12-148 for parsing, converter/writer.py:109-143
for writing):

    [magic 0xA00ABCD i32][header_size i32][ (key i32, value i32) * nKv ]
    then raw tensors in fixed order (transformer.cpp:494-529):
        embedding (vocab, dim) F32
        per layer: wq, wk, wv, wo; dense: w1, w2, w3 | moe: router + per-expert
                   (up, gate, down); rms_att F32, rms_ffn F32
                   [+ grok1: rms_moe, rms_ffn2 F32]
        rms_final (dim,) F32
        wcls (vocab, dim) [weights ftype]

    header_size counts magic+size+kv bytes; tensors start at byte header_size. Matmul
    tensors use the header's weights ftype (F32/F16/Q40/Q80 block streams); norms and
    embedding are always F32. Legacy magics 0xABCD00/01 use a fixed 9-int header
    (transformer.cpp:28-43).

The loader memory-maps the file and returns the params dict of models/params.py with
per-layer tensors stacked along a leading n_layers axis.
"""

from __future__ import annotations

import struct
from typing import BinaryIO

import numpy as np

from ..models.params import (MIXER, SSM, Params, block_tensor_shapes,
                             layer_tensor_shapes)
from ..models.spec import (ArchType, HeaderKey, HiddenAct, LayerKind, ModelSpec,
                           RopeType, RouterInput, RouterScore)
from ..quants import (
    FloatType,
    QTensor,
    batch_bytes,
    q40_from_bytes,
    q40_to_bytes,
    q80_from_bytes,
    q80_to_bytes,
    quantize_q40,
    quantize_q80,
)

MAGIC = 0xA00ABCD
LEGACY_MAGICS = {0xABCD00: ArchType.LLAMA, 0xABCD01: ArchType.GROK1}


_KIND_BITS = 30  # layers a header word holds (values are int32)
_KIND_WORDS = 8


# this repo's later keys (latent attention, the leading stack, the shared
# expert, the router, YaRN): field, header key, header integer -> field
def _e6(v: int) -> float:
    return v / 1e6


def _e9(v: int) -> float:
    return v / 1e9


def _or_one(v: int) -> int:
    return int(v) or 1


_UNITS = {_e6: 1e6, _e9: 1e9}  # what a float field is written in


def _as_int(value, conv) -> int:
    return round(value * _UNITS[conv]) if conv in _UNITS else int(value)


_OWN_KEYS = (
    ("q_lora_rank", HeaderKey.Q_LORA_RANK, int),
    ("kv_lora_rank", HeaderKey.KV_LORA_RANK, int),
    ("qk_nope_head_dim", HeaderKey.QK_NOPE_HEAD_DIM, int),
    ("qk_rope_head_dim", HeaderKey.QK_ROPE_HEAD_DIM, int),
    ("v_head_dim", HeaderKey.V_HEAD_DIM, int),
    ("lead_layers", HeaderKey.LEAD_LAYERS, int),
    ("lead_hidden_dim", HeaderKey.LEAD_HIDDEN_DIM, int),
    ("shared_hidden_dim", HeaderKey.SHARED_HIDDEN_DIM, int),
    ("router_score", HeaderKey.ROUTER_SCORE, RouterScore),
    ("router_renorm", HeaderKey.ROUTER_RENORM, bool),
    ("router_scale", HeaderKey.ROUTER_SCALE_E6, _e6),
    ("router_width", HeaderKey.ROUTER_WIDTH, int),
    ("expert_offset", HeaderKey.EXPERT_OFFSET, int),
    ("yarn_beta_fast", HeaderKey.YARN_BETA_FAST_E6, _e6),
    ("yarn_beta_slow", HeaderKey.YARN_BETA_SLOW_E6, _e6),
    ("yarn_mscale", HeaderKey.YARN_MSCALE_E6, _e6),
    ("yarn_mscale_all_dim", HeaderKey.YARN_MSCALE_ALL_DIM_E6, _e6),
    ("rotary_dim", HeaderKey.ROTARY_DIM, int),
    ("rope_table_scale", HeaderKey.ROPE_TABLE_SCALE_E6, _e6),
    ("attn_gate", HeaderKey.ATTN_GATE, bool),
    ("qk_norm", HeaderKey.QK_NORM, bool),
    ("router_bias", HeaderKey.ROUTER_BIAS, bool),
    ("embedding_multiplier", HeaderKey.EMBEDDING_MULTIPLIER_E6, _e6),
    ("residual_multiplier", HeaderKey.RESIDUAL_MULTIPLIER_E6, _e6),
    ("logits_scaling", HeaderKey.LOGITS_SCALING_E6, _e6),
    ("attn_multiplier", HeaderKey.ATTN_SCALE_E9, _e9),
    ("state_snapshots", HeaderKey.STATE_SNAPSHOTS, int),
)

# kinds of attention layer (ModelSpec.kinds): kind k's field at header key
# KIND_0 + _KIND_STRIDE x k + its index here; a kind's name is not stored
# (a header holds integers): a loaded kind is named "kind<k>"
_KIND_FIELDS = (
    ("n_heads", int), ("sliding_window", int), ("rope_type", RopeType),
    ("rope_theta", int), ("rotary_dim", int), ("rope_scaling_factor", _e6),
    ("rope_scaling_orig_max_seq_len", int), ("yarn_beta_fast", _e6),
    ("yarn_beta_slow", _e6), ("rope_table_scale", _e6),
    # taps of a convolution kind; a file written before the field has no
    # such key and reads 0, an attention kind
    ("conv_kernel", int),
    # a state-space kind's heads, head size, state size and groups (0, 0, 0
    # and 1 of every other kind, and of a file written before them)
    ("ssm_heads", int), ("ssm_head_dim", int), ("ssm_state", int),
    ("ssm_groups", _or_one),
)
# a second bank a kind, at KIND_MORE_0: a delta-rule kind's heads, key and
# value sizes and gate rank, and a latent kind's row and head widths (0 of
# every other kind, and of a file written before them)
_KIND_MORE_FIELDS = (
    ("kda_heads", int), ("kda_key_dim", int), ("kda_value_dim", int),
    ("kda_rank", int),
    ("q_lora_rank", int), ("kv_lora_rank", int), ("qk_nope_head_dim", int),
    ("qk_rope_head_dim", int), ("v_head_dim", int),
)
_KIND_BANKS = ((HeaderKey.KIND_0, _KIND_FIELDS),
               (HeaderKey.KIND_MORE_0, _KIND_MORE_FIELDS))
_KIND_STRIDE = 16
_MAX_KINDS = 4  # two bits a layer
_KIND_LAYERS_A_WORD = 15


def _pack_layer_kinds(spec: ModelSpec) -> list[tuple[int, int]]:
    """The kinds and each layer's as (key, value) pairs; [] without kinds."""
    if not spec.kinds:
        return []
    assert len(spec.kinds) <= _MAX_KINDS, len(spec.kinds)
    assert spec.n_layers <= _KIND_LAYERS_A_WORD * 16, spec.n_layers
    kv = [(int(HeaderKey.N_KINDS), len(spec.kinds))]
    for w in range(0, spec.n_layers, _KIND_LAYERS_A_WORD):
        word = sum(k << (2 * i) for i, k in enumerate(
            spec.layer_kinds[w:w + _KIND_LAYERS_A_WORD]))
        kv.append((HeaderKey.LAYER_KINDS_0 + w // _KIND_LAYERS_A_WORD, word))
    for k, kind in enumerate(spec.kinds):
        for base, bank in _KIND_BANKS:
            for i, (name, conv) in enumerate(bank):
                value = getattr(kind, name)
                if base == HeaderKey.KIND_0 or value:
                    kv.append((base + _KIND_STRIDE * k + i,
                               _as_int(value, conv)))
    return kv


def _unpack_layer_kinds(kv: dict[int, int], n_layers: int) -> dict:
    """ModelSpec's `kinds` and `layer_kinds` from a header; {} without."""
    n = kv.get(HeaderKey.N_KINDS, 0)
    if not n:
        return {}
    kinds = tuple(
        LayerKind(name=f"kind{k}", **{
            name: conv(kv.get(base + _KIND_STRIDE * k + i, 0))
            for base, bank in _KIND_BANKS
            for i, (name, conv) in enumerate(bank)})
        for k in range(n))
    layer_kinds = tuple(
        (kv[HeaderKey.LAYER_KINDS_0 + l // _KIND_LAYERS_A_WORD]
         >> (2 * (l % _KIND_LAYERS_A_WORD))) & 3 for l in range(n_layers))
    return {"kinds": kinds, "layer_kinds": layer_kinds}


def _pack_kinds(first_key: int, kinds: tuple[int, ...]) -> list[tuple[int, int]]:
    """A per-layer 0/1 list as (key, bit mask) pairs, 30 layers a word."""
    assert len(kinds) <= _KIND_BITS * _KIND_WORDS, len(kinds)
    out = []
    for w in range(0, len(kinds), _KIND_BITS):
        word = sum(int(bool(b)) << i for i, b in enumerate(kinds[w:w + _KIND_BITS]))
        out.append((first_key + w // _KIND_BITS, word))
    return out


def _unpack_kinds(kv: dict[int, int], first_key: int, n_layers: int) -> tuple[int, ...]:
    if first_key not in kv:
        return ()
    return tuple((kv.get(first_key + l // _KIND_BITS, 0) >> (l % _KIND_BITS)) & 1
                 for l in range(n_layers))


def read_spec(path: str, max_seq_len: int = 0,
              weights_ftype: FloatType | None = None) -> tuple[ModelSpec, FloatType, int]:
    """Parse the header. Returns (spec, weights_ftype, header_size)."""
    with open(path, "rb") as f:
        magic = struct.unpack("<i", f.read(4))[0]
        fields: dict[str, int] = {}
        if magic in LEGACY_MAGICS:
            vals = struct.unpack("<9i", f.read(36))
            (fields["dim"], fields["hidden_dim"], fields["n_layers"], fields["n_heads"],
             fields["n_kv_heads"], fields["n_experts"], fields["n_active_experts"],
             fields["vocab_size"], fields["seq_len"]) = vals
            arch = LEGACY_MAGICS[magic]
            header_size = 4 + 36
            kv: dict[int, int] = {}
        elif magic == MAGIC:
            header_size = struct.unpack("<i", f.read(4))[0]
            n_kv_bytes = header_size - 8
            raw = f.read(n_kv_bytes)
            ints = struct.unpack(f"<{n_kv_bytes // 4}i", raw)
            kv = {ints[i]: ints[i + 1] for i in range(0, len(ints), 2)}
            arch = ArchType(kv[HeaderKey.ARCH_TYPE])
            for name, key in (("dim", HeaderKey.DIM), ("hidden_dim", HeaderKey.HIDDEN_DIM),
                              ("n_layers", HeaderKey.N_LAYERS),
                              ("n_heads", HeaderKey.N_HEADS),
                              ("n_kv_heads", HeaderKey.N_KV_HEADS),
                              ("n_experts", HeaderKey.N_EXPERTS),
                              ("n_active_experts", HeaderKey.N_ACTIVE_EXPERTS),
                              ("vocab_size", HeaderKey.VOCAB_SIZE),
                              ("seq_len", HeaderKey.SEQ_LEN)):
                if key in kv:
                    fields[name] = kv[key]
        else:
            raise ValueError(f"unsupported model file magic {magic:#x}")

    if weights_ftype is None:
        if HeaderKey.WEIGHTS_FLOAT_TYPE not in kv:
            raise ValueError("weights float type not in header and not specified")
        weights_ftype = FloatType(kv[HeaderKey.WEIGHTS_FLOAT_TYPE])

    spec = ModelSpec(
        arch_type=arch,
        hidden_act=HiddenAct(kv.get(HeaderKey.HIDDEN_ACT, HiddenAct.SILU)),
        rope_theta=float(kv.get(HeaderKey.ROPE_THETA, 10000)),
        rope_type=RopeType(kv.get(HeaderKey.ROPE_TYPE, RopeType.UNKNOWN)),
        rope_scaling_factor=float(kv.get(HeaderKey.ROPE_SCALING_FACTOR, 0)),
        rope_scaling_low_freq_factor=float(
            kv.get(HeaderKey.ROPE_SCALING_LOW_FREQ_FACTOR, 0)),
        rope_scaling_high_freq_factor=float(
            kv.get(HeaderKey.ROPE_SCALING_HIGH_FREQ_FACTOR, 0)),
        rope_scaling_orig_max_seq_len=kv.get(HeaderKey.ROPE_SCALING_ORIG_MAX_SEQ_LEN, 0),
        version=kv.get(HeaderKey.VERSION, 0),
        head_dim=kv.get(HeaderKey.HEAD_DIM, 0),
        sliding_window=kv.get(HeaderKey.SLIDING_WINDOW, 0),
        router_input=RouterInput(kv.get(HeaderKey.ROUTER_INPUT,
                                        RouterInput.FFN_NORM)),
        rope_layers=_unpack_kinds(kv, HeaderKey.ROPE_LAYERS_0,
                                  fields.get("n_layers", 0)),
        window_layers=_unpack_kinds(kv, HeaderKey.WINDOW_LAYERS_0,
                                    fields.get("n_layers", 0)),
        **({"norm_eps": kv[HeaderKey.NORM_EPS_E9] / 1e9}
           if HeaderKey.NORM_EPS_E9 in kv else {}),
        **{name: conv(kv[key]) for name, key, conv in _OWN_KEYS if key in kv},
        **_unpack_layer_kinds(kv, fields.get("n_layers", 0)),
        **fields,
    ).resolved(max_seq_len)
    return spec, weights_ftype, header_size


_EXPERT_STACKS = ("moe_up", "moe_gate", "moe_down")


def _layer_order(spec: ModelSpec, lead: bool, shapes=None):
    """One layer's tensors in file order, as (name, expert or None, shape,
    quantized): the order of `block_tensor_shapes` (transformer.cpp:498-523),
    the expert stacks written expert by expert, each expert's up, gate, down
    together. `shapes`: the layer's own, where a run's layers differ in kind
    (`layer_tensor_shapes`)."""
    shapes = block_tensor_shapes(spec, lead) if shapes is None else shapes
    for name, (shape, quantized) in shapes.items():
        if name == "moe_up":
            for e in range(spec.n_experts):
                for part in _EXPERT_STACKS:
                    yield part, e, shapes[part][0][1:], True
        elif name not in _EXPERT_STACKS:
            yield name, None, shape, quantized


def model_tensor_bytes(spec: ModelSpec, wft: FloatType) -> int:
    """Total tensor bytes after the header (mirrors the reference's missedBytes check,
    transformer.cpp:531-535)."""
    total = batch_bytes(FloatType.F32, spec.dim, spec.vocab_size)  # embedding
    for l in range(spec.n_layers):  # layer by layer: kinds differ in tensors
        for name, (shape, quantized) in layer_tensor_shapes(spec, l).items():
            ft = wft if quantized else FloatType.F32
            d = int(np.prod(shape[:-1], initial=1))
            total += batch_bytes(ft, shape[-1], d)
    total += batch_bytes(FloatType.F32, spec.dim, 1)  # rms_final
    total += batch_bytes(wft, spec.dim, spec.vocab_size)  # wcls
    return total


def _tensor_from_bytes(buf: memoryview, shape: tuple[int, ...],
                       ftype: FloatType) -> QTensor:
    if ftype == FloatType.F32:
        return QTensor(ftype, np.frombuffer(buf, "<f4").reshape(shape).copy())
    if ftype == FloatType.F16:
        return QTensor(ftype, np.frombuffer(buf, "<f2").reshape(shape).copy())
    if ftype == FloatType.Q40:
        packed, scales = q40_from_bytes(buf, shape)
        return QTensor(ftype, packed, scales)
    if ftype == FloatType.Q80:
        vals, scales = q80_from_bytes(buf, shape)
        return QTensor(ftype, vals, scales)
    raise ValueError(ftype)


def _stack(tensors: list[QTensor]) -> QTensor:
    data = np.stack([t.data for t in tensors])
    scales = None if tensors[0].scales is None else np.stack([t.scales for t in tensors])
    return QTensor(tensors[0].ftype, data, scales)


def load_model(path: str, max_seq_len: int = 0,
               weights_ftype: FloatType | None = None) -> tuple[ModelSpec, Params]:
    """Load a `.m` file into (spec, params). Equivalent of Transformer::loadRootFromFile
    (transformer.cpp:467-539) — mmap + per-tensor parse, no socket distribution (sharding
    happens later via parallel.shard_params)."""
    spec, wft, header_size = read_spec(path, max_seq_len, weights_ftype)
    mm = np.memmap(path, dtype=np.uint8, mode="r")
    view = memoryview(mm)
    off = header_size

    expected = header_size + model_tensor_bytes(spec, wft)
    if expected != len(mm):
        raise ValueError(
            f"model file size mismatch: expected {expected} bytes for "
            f"{wft.name} weights, file has {len(mm)} (wrong weights float type?)")

    def take(shape: tuple[int, ...], ftype: FloatType) -> QTensor:
        nonlocal off
        nbytes = batch_bytes(ftype, shape[-1], int(np.prod(shape[:-1], initial=1)))
        t = _tensor_from_bytes(view[off:off + nbytes], shape, ftype)
        off += nbytes
        return t

    # NOTE: seq-len clamping must not affect tensor layout; file tensors are independent
    # of seq_len, so no adjustment needed.
    embedding = take((spec.vocab_size, spec.dim), FloatType.F32)

    # a run's layers in file order, each with its own kind's tensors: a run
    # of one kind stacks every tensor over all its layers, a run of a model
    # with state layers each mixer's tensors over that kind's layers
    # (models/params.py run_tensor_shapes)
    stacks: dict[str, Params] = {}
    for run in spec.runs():
        per_layer: dict[str, list[QTensor]] = {}
        quant: dict[str, bool] = {}
        for l in range(run.first, run.first + run.depth):
            shapes = layer_tensor_shapes(spec, l)
            quant.update({n: q for n, (_, q) in shapes.items()})
            experts: dict[str, list[QTensor]] = {}
            for name, e, shape, quantized in _layer_order(spec, run.lead,
                                                          shapes):
                t = take(shape, wft if quantized else FloatType.F32)
                if e is None:
                    per_layer.setdefault(name, []).append(t)
                else:
                    experts.setdefault(name, []).append(t)
            for name, ts in experts.items():
                per_layer.setdefault(name, []).append(_stack(ts))
        blocks: Params = {}
        for name, tensors in per_layer.items():
            stacked = _stack(tensors)
            blocks[name] = (stacked if quant[name] else
                            np.asarray(stacked.data, dtype=np.float32))
        stacks[run.name] = blocks

    rms_final = take((spec.dim,), FloatType.F32)
    wcls = take((spec.vocab_size, spec.dim), wft)

    if off != len(mm):
        raise ValueError(f"model file size mismatch: consumed {off}, file {len(mm)} "
                         "(missing/extra bytes — wrong weights float type?)")

    params: Params = {
        "embedding": np.asarray(embedding.data),
        **stacks,
        "rms_final": np.asarray(rms_final.data),
        "wcls": wcls,
    }
    return spec, params


# ---------------------------------------------------------------------------
# writer (converter back-end; byte-compatible with converter/writer.py)
# ---------------------------------------------------------------------------


def write_header(f: BinaryIO, spec: ModelSpec, weights_ftype: FloatType) -> None:
    kv: list[tuple[int, int]] = [
        (HeaderKey.VERSION, 0),
        (HeaderKey.ARCH_TYPE, int(spec.arch_type)),
        (HeaderKey.DIM, spec.dim),
        (HeaderKey.HIDDEN_DIM, spec.hidden_dim),
        (HeaderKey.N_LAYERS, spec.n_layers),
        (HeaderKey.N_HEADS, spec.n_heads),
        (HeaderKey.N_KV_HEADS, spec.n_kv_heads),
        (HeaderKey.N_EXPERTS, spec.n_experts),
        (HeaderKey.N_ACTIVE_EXPERTS, spec.n_active_experts),
        (HeaderKey.VOCAB_SIZE, spec.vocab_size),
        (HeaderKey.SEQ_LEN, spec.seq_len),
        (HeaderKey.HIDDEN_ACT, int(spec.hidden_act)),
        (HeaderKey.ROPE_THETA, int(spec.rope_theta)),
        (HeaderKey.WEIGHTS_FLOAT_TYPE, int(weights_ftype)),
    ]
    if spec.rope_type != RopeType.UNKNOWN:
        kv.append((HeaderKey.ROPE_TYPE, int(spec.rope_type)))
    if spec.rope_scaling_factor:
        kv += [
            (HeaderKey.ROPE_SCALING_FACTOR, int(spec.rope_scaling_factor)),
            (HeaderKey.ROPE_SCALING_LOW_FREQ_FACTOR, int(spec.rope_scaling_low_freq_factor)),
            (HeaderKey.ROPE_SCALING_HIGH_FREQ_FACTOR,
             int(spec.rope_scaling_high_freq_factor)),
            (HeaderKey.ROPE_SCALING_ORIG_MAX_SEQ_LEN, spec.rope_scaling_orig_max_seq_len),
        ]
    # this repo's own keys, written only where they say something: a model
    # without them keeps the reference's byte-exact header
    if spec.head_dim:
        kv.append((HeaderKey.HEAD_DIM, spec.head_dim))
    if spec.sliding_window:
        kv.append((HeaderKey.SLIDING_WINDOW, spec.sliding_window))
    if spec.router_input != RouterInput.FFN_NORM:
        kv.append((HeaderKey.ROUTER_INPUT, int(spec.router_input)))
    if spec.norm_eps != ModelSpec.norm_eps:
        kv.append((HeaderKey.NORM_EPS_E9, round(spec.norm_eps * 1e9)))
    kv += _pack_kinds(HeaderKey.ROPE_LAYERS_0, spec.rope_layers)
    kv += _pack_kinds(HeaderKey.WINDOW_LAYERS_0, spec.window_layers)
    kv += _pack_layer_kinds(spec)
    for name, key, conv in _OWN_KEYS:
        value = getattr(spec, name)
        if value != getattr(ModelSpec, name):  # only where it says something
            kv.append((key, _as_int(value, conv)))
    data = b"".join(struct.pack("<ii", k, v) for k, v in kv)
    f.write(struct.pack("<i", MAGIC))
    f.write(struct.pack("<i", 8 + len(data)))
    f.write(data)


def write_tensor(f: BinaryIO, x: np.ndarray | QTensor, ftype: FloatType) -> int:
    """Flattened tensor -> reference byte stream (converter/writer.py:96-107).
    A planar QTensor already in `ftype` is written as it stands: a synthetic
    checkpoint at a published size draws its blocks directly and never exists
    in f32 (examples/make_tiny_model.py --arch)."""
    if isinstance(x, QTensor):
        if x.ftype != ftype or x.layout != "planar" or ftype not in (
                FloatType.Q40, FloatType.Q80):
            raise ValueError(f"cannot write a {x.layout} {x.ftype.name} "
                             f"QTensor as {ftype.name}")
        to_bytes = q40_to_bytes if ftype == FloatType.Q40 else q80_to_bytes
        buf = to_bytes(np.asarray(x.data), np.asarray(x.scales))
        f.write(buf)
        return len(buf)
    flat = np.asarray(x, dtype=np.float32).reshape(-1)
    if ftype == FloatType.F32:
        buf = flat.astype("<f4").tobytes()
    elif ftype == FloatType.F16:
        buf = flat.astype("<f2").tobytes()
    elif ftype == FloatType.Q40:
        buf = q40_to_bytes(*quantize_q40(flat))
    elif ftype == FloatType.Q80:
        buf = q80_to_bytes(*quantize_q80(flat))
    else:
        raise ValueError(ftype)
    f.write(buf)
    return len(buf)


def write_model(path: str, spec: ModelSpec, tensors_iter, weights_ftype: FloatType) -> None:
    """Write a `.m` from an iterator of (name, np.ndarray | QTensor) in file order.

    `tensors_iter` must yield tensors in the exact order documented in load_model; norms
    and embedding are forced F32 regardless of weights_ftype (convert-llama.py:79-85).
    A tensor may arrive in several consecutive row chunks under the same name.
    """
    norm_names = {"embedding", "rms_att", "rms_ffn", "rms_moe", "rms_ffn2", "rms_final",
                  "rms_q", "rms_kv", "rms_qh", "rms_kh", "conv_w",
                  "router_bias",
                  *(n for n in SSM if n not in ("ssm_in", "ssm_out")),
                  "kda_conv_w", "kda_dt_bias", "kda_a_log", "kda_norm"}
    with open(path, "wb") as f:
        write_header(f, spec, weights_ftype)
        for name, tensor in tensors_iter:
            ftype = FloatType.F32 if name in norm_names else weights_ftype
            write_tensor(f, tensor, ftype)


def params_file_order(spec: ModelSpec, params: Params, as_stored: bool = False):
    """Yield (name, array) in `.m` order from a params dict (testing / re-export).
    `as_stored`: a planar Q40 / Q80 tensor goes out as the QTensor of its
    blocks, which `write_tensor` writes as they stand (quantizing its values
    again would not give the same blocks back)."""
    yield "embedding", params["embedding"]
    def as_np(t, idx):
        if (as_stored and isinstance(t, QTensor) and t.layout == "planar"
                and t.ftype in (FloatType.Q40, FloatType.Q80)):
            return QTensor(t.ftype, np.asarray(t.data)[idx],
                           np.asarray(t.scales)[idx])
        return t.to_numpy()[idx] if isinstance(t, QTensor) else np.asarray(t)[idx]

    for run in spec.runs():
        blocks = params[run.name]
        own: dict[str, int] = {}  # a mixer's tensors count their kind's layers
        for l in range(run.depth):
            shapes = layer_tensor_shapes(spec, run.first + l)
            for name, e, _shape, _q in _layer_order(spec, run.lead, shapes):
                i = own.get(name, 0) if spec.mixed and name in MIXER else l
                yield name, as_np(blocks[name], i if e is None else (i, e))
            for name in shapes:
                if name in MIXER:
                    own[name] = own.get(name, 0) + 1
    yield "rms_final", params["rms_final"]
    yield "wcls", as_np(params["wcls"], ())
