"""The family of LFM2-8B-A1B (Liquid AI; `model_type` `lfm2_moe`): which
`ModelSpec` the program is given, which tensors are drawn, in which stacks
they stand, and the plain reference, the block graph in jax.numpy float32.
The harness reaches it through `cells.load_family` and calls `model_spec`,
`tensor_shapes`, `stacks`, `program_params` and `logits_at`.

The layer l, input x (T x hidden), eps `norm_eps`, no bias anywhere:

    a  = x + mixer_l(RMSNorm(x; rms_att))          rms_att: `operator_norm`
    x' = a + ffn_l(RMSNorm(a; rms_ffn))            rms_ffn: `ffn_norm`

    mixer, `layer_types[l]` "conv" (a gated short convolution):
        [B, C, u] = conv_in h      three parts of hidden width, in that order
        v   = B * u
        c_p = w[:, 0] v_{p-2} + w[:, 1] v_{p-1} + w[:, 2] v_p
              depthwise, causal, `conv_L_cache` 3 taps, zeros before
              position 0, no bias
        out = conv_out (C * c)
        The layer's state after position p is (v_{p-1}, v_p): it is not a
        list of positions.
    mixer, "full_attention": q = wq h (`num_attention_heads` heads of
        hidden / heads = 64), k, v = wk h, wv h (`num_key_value_heads`);
        q and k RMS-normed over each head's 64 values (rms_qh, rms_kh: the
        family's `q_layernorm`, `k_layernorm`), then both rotated over the
        whole head in half-split pairs (j, j + 32), theta `rope_theta`;
        position i attends keys j <= i, scores / 8, softmax; query head n
        reads kv head n // 4; out = wo att.
    ffn, l < `num_dense_layers`: w2 (silu(w1 y) * w3 y), width
        `intermediate_size`.
    ffn, every other layer: s = sigmoid(router y), float32; the
        `num_experts_per_tok` experts with the largest s + router_bias
        (`use_expert_bias`); weights s at those (the bias is NOT in them),
        divided by their sum + 1e-6 (`norm_topk_prob`), times
        `routed_scaling_factor`; out = sum_e w_e down_e (silu(gate_e y) *
        up_e y), width `moe_intermediate_size`. No shared expert.
    logits = RMSNorm(x_L; rms_final) E^T, E the embedding (tied).

What the published configuration names and does not spell is listed in the
configuration file's `assumed`, each ONE value here and in the program's
`ModelSpec`: the tie, QK-norm, the + 1e-6, the order B, C, u, the rotary
convention.

Departures from the published description: (a) the tensors carry the
program's loader's names (rms_att, rms_ffn, conv_in, conv_w, conv_out, wq ..
wo, rms_qh, rms_kh, w1 w2 w3, router, router_bias, moe_up, moe_gate,
moe_down); (b) the program divides the four weights by their sum WITHOUT the
1e-6, 5e-7 of a weight, which the limits do not see; (c) the layers stand in
TWO stacks, `lead` (the `num_dense_layers` leading layers, which are
convolution layers) and `blocks`, and a layer of `blocks` is DRAWN with both
mixers' tensors, of which it uses its kind's: the harness cuts a stack by
one set of layer indices, so every tensor of a stack is as deep as the
stack, and the tensors a layer does not use are 3 % of the draw
(`program_params` leaves them out, each kind's stacked over its own layers);
(d) the harness hands a cut of the weights to `model_spec` as a DEPTH alone,
which is read so (`_cut`): the file's own depth is the whole file; 2 is
layers `num_dense_layers` - 1 and `num_dense_layers`, the last leading
(dense, convolution) layer and the first expert layer, which is an attention
layer in the file and the toy alike. `logits_at` needs no such reading: it
sees which stack holds each layer.

What the harness draws and this family maps, in ONE function that
`program_params` and `logits_at` both call (`mapped`): `weights._draw` gives
every unquantized tensor 1 + 0.02 N. Drawn so the three taps are alike (a
reversed kernel moves the output by 2 %) and the bias is a constant (it
selects nothing). The taps become `TAPS` times what was drawn, three
clearly different magnitudes with the newest position's the largest; the
bias becomes `BIAS_SPREAD` times (drawn - 1), zero mean, which changes the
chosen four at six positions of ten (PERF.md section 6, PR 42). The tie: the
head `wcls` is drawn as Q40 and its dequantized values are the embedding of
program and reference alike (the `embedding` the harness has every family
draw is dropped for it).

How it blocks the work: as the other families: one layer's tensors on the
device at a time, each row through it alone, padded with token 3 to the next
multiple of 32 (64 past 1024), queries in blocks of 1024 against one kv
head's keys, one expert dequantized at a time, the head in slices of the
vocabulary. A position's router margin is the s + bias of the last expert
taken less that of the first one left, over the spread (standard deviation)
of the position's own 32 values.

`precision`: "float32" is the reference; "bfloat16", "fp8" and "q80" round
the operands of every matrix product through `weights.rounder` (controls);
four more are float32 with one mechanism left out, what a program that lost
it would compute: "conv_state_off" (the convolution's state zeroed at every
dispatch: a position that starts a dispatch, the prompt's chunks of 64, 8
and 1 and every forced token after it, sees no earlier v, the one behind it
only v_{p-1}), "taps_reversed" (w[:, ::-1]), "bias_off" (the four largest of
s alone), "qknorm_off" (q and k rotated as projected).
"""

from __future__ import annotations

import functools

import numpy as np

from benchmark import weights as W

Q_BLOCK = 1024  # queries scored at once against one kv head's keys
HEAD_SLICES = 8
PAD_TOKEN = 3
PREFILL_CHUNKS = (64, 8, 1)  # how the program cuts a prompt into dispatches
MECHANISM_CONTROLS = ("conv_state_off", "taps_reversed", "bias_off",
                      "qknorm_off")
CONV, FULL = "conv", "full_attention"
TAPS = (0.25, -0.5, 1.0)  # times the drawn 1 + 0.02 N: oldest position first
BIAS_SPREAD = 2.0  # times the drawn 0.02 N
MAPPED = "mapped"  # a key of weights that `mapped` has been over


def _padded(n: int) -> int:
    step = 64 if n > 1024 else 32
    return -(-n // step) * step


def _checked(cfg: dict) -> None:
    """Refuse a file this family does not state."""
    n, lead = cfg["layers_here"], cfg["num_dense_layers"]
    types = cfg["layer_types"][:n]
    if set(types) - {CONV, FULL} or len(types) != n:
        raise ValueError(f"lfm2: layer_types {types} of {n} layers")
    if not 0 < lead < n or set(types[:lead]) != {CONV}:
        raise ValueError("lfm2: the dense layers lead the model and are "
                         f"convolution layers; num_dense_layers {lead}, "
                         f"layer_types {types[:lead]}")
    if types[lead] != FULL:
        raise ValueError("lfm2: the first expert layer is an attention layer "
                         "(the cut of two layers this family reads rests on "
                         f"it); layer {lead} is {types[lead]!r}")
    if cfg["conv_bias"] or not cfg["use_expert_bias"]:
        raise ValueError("lfm2: no bias in the convolution, a selection bias "
                         "in the router; this file says otherwise")
    if cfg["hidden_size"] % cfg["num_attention_heads"]:
        raise ValueError("lfm2: heads of hidden_size / num_attention_heads")


def stacks(cfg: dict) -> list[tuple[str, int]]:
    _checked(cfg)
    lead = cfg["num_dense_layers"]
    return [("lead", lead), ("blocks", cfg["layers_here"] - lead)]


def _cut(cfg: dict) -> list[int]:
    """The layers that `num_hidden_layers` stands for (departure (d))."""
    depth, n, lead = (cfg["num_hidden_layers"], cfg["layers_here"],
                      cfg["num_dense_layers"])
    if depth == n:
        return list(range(n))
    if depth == 2:
        return [lead - 1, lead]
    raise ValueError(f"lfm2: a cut of {depth} of {n} layers is not one this "
                     "family can read from its depth (2 or the whole)")


def one_layer_a_stack(cfg: dict, experts: int | None = None) -> dict:
    """The file cut to its leading layers and ONE period behind them
    (attention, convolution), with `experts` experts where given: the same
    tensors in the same stacks at a size a tool can draw that wants the
    parameter tree's structure and not its weight (`perf/aot_step.py`)."""
    lead = cfg["num_dense_layers"]
    keep = lead + 2
    out = {**cfg, "num_hidden_layers": keep, "layers_here": keep,
           "layer_types": cfg["layer_types"][:keep]}
    if experts:
        out["num_experts"] = experts
    return out


def model_spec(cfg: dict):
    """The program's ModelSpec for the file's keys: the two kinds of layer
    (`ModelSpec.kinds`: the convolution, and attention with its rotation),
    each layer's kind, the leading dense layers, QK-norm, the sigmoid router
    with its selection bias."""
    from distributed_llama_tpu.models.spec import (ArchType, HiddenAct,
                                                   LayerKind, ModelSpec,
                                                   RopeType, RouterScore)

    _checked(cfg)
    layers = _cut(cfg)
    heads = cfg["num_attention_heads"]
    types = [cfg["layer_types"][l] for l in layers]
    names = sorted(set(types))  # "conv" before "full_attention"
    kinds = tuple(
        LayerKind(name="conv", n_heads=heads,
                  conv_kernel=cfg["conv_L_cache"]) if name == CONV else
        LayerKind(name="full", n_heads=heads, rope_type=RopeType.FALCON,
                  rope_theta=float(cfg["rope_theta"])) for name in names)
    lead = sum(1 for l in layers if l < cfg["num_dense_layers"])
    return ModelSpec(
        arch_type=ArchType.MIXTRAL, dim=cfg["hidden_size"],
        hidden_dim=cfg["moe_intermediate_size"], n_layers=len(layers),
        n_heads=heads, n_kv_heads=cfg["num_key_value_heads"],
        vocab_size=cfg["vocab_size"], seq_len=cfg["context"],
        hidden_act=HiddenAct.SILU, rope_type=RopeType.FALCON,
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["norm_eps"],
        head_dim=cfg["hidden_size"] // heads, qk_norm=True,
        n_experts=cfg["num_experts"],
        n_active_experts=cfg["num_experts_per_tok"],
        router_score=RouterScore.SIGMOID, router_bias=True,
        router_renorm=bool(cfg["norm_topk_prob"]),
        router_scale=float(cfg["routed_scaling_factor"]),
        lead_layers=lead, lead_hidden_dim=cfg["intermediate_size"],
        kinds=kinds, layer_kinds=tuple(names.index(t) for t in types),
    ).resolved()


def tensor_shapes(cfg: dict) -> dict[str, tuple[tuple[int, ...], bool]]:
    """name -> (shape with the layer axis, drawn as Q40?), matrices (out,
    in), under the program's loader's names, a stack's prefix ahead of its
    tensors'. A layer of `blocks` is drawn with both mixers' tensors
    (departure (c)); the embedding drawn is replaced by the head (the tie)."""
    # a program that cannot state this model fails here, before the weights
    # are drawn: the run then ends in a second with the import's message
    from distributed_llama_tpu.models.forward import StateCache  # noqa: F401

    _checked(cfg)
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    hs = d // heads
    kv = cfg["num_key_value_heads"] * hs
    e, h, f = (cfg["num_experts"], cfg["moe_intermediate_size"],
               cfg["intermediate_size"])
    taps, v = cfg["conv_L_cache"], cfg["vocab_size"]
    # `embedding` is drawn because the harness holds every family to the three
    # tensors outside the stacks; `mapped` puts the head's values in its place
    out = {"rms_final": ((d,), False), "embedding": ((v, d), False),
           "wcls": ((v, d), True)}
    for prefix, n in stacks(cfg):
        own = {"conv_in": ((n, 3 * d, d), True), "conv_w": ((n, d, taps), False),
               "conv_out": ((n, d, d), True),
               "rms_att": ((n, d), False), "rms_ffn": ((n, d), False)}
        if prefix == "lead":
            own.update({"w1": ((n, f, d), True), "w2": ((n, d, f), True),
                        "w3": ((n, f, d), True)})
        else:
            own.update({"wq": ((n, d, d), True), "wk": ((n, kv, d), True),
                        "wv": ((n, kv, d), True), "wo": ((n, d, d), True),
                        "rms_qh": ((n, hs), False), "rms_kh": ((n, hs), False),
                        "router": ((n, e, d), True),
                        "router_bias": ((n, e), False),
                        "moe_up": ((n, e, h, d), True),
                        "moe_gate": ((n, e, h, d), True),
                        "moe_down": ((n, e, d, h), True)})
        out.update({f"{prefix}.{name}": s for name, s in own.items()})
    return out


def mapped(weights: dict) -> dict:
    """The drawn tensors as program and reference both read them: the taps
    `TAPS` times what was drawn, the selection bias `BIAS_SPREAD` times its
    deviation from 1, the embedding the head's dequantized values (in place
    of the one drawn). Weights that were mapped already pass unchanged."""
    if weights.get(MAPPED):
        return weights
    out = {**weights, MAPPED: True}
    for name, t in weights.items():
        if name.endswith(".conv_w"):
            out[name] = (t * np.asarray(TAPS, np.float32)).astype(np.float32)
        elif name.endswith(".router_bias"):
            out[name] = ((t - 1.0) * BIAS_SPREAD).astype(np.float32)
    out["embedding"] = np.asarray(W.dequantize(*weights["wcls"]), np.float32)
    return out


def _types_held(cfg: dict, weights: dict) -> list[str]:
    """The kind of each layer `weights` hold, in layer order: a cut's layers
    are the ones `_cut` reads from its depth."""
    depths = W.stack_depths(weights, cfg)
    layers = _cut({**cfg, "num_hidden_layers": sum(depths.values())})
    return [cfg["layer_types"][l] for l in layers]


def program_params(cfg: dict, weights: dict):
    """The program's two runs (`ModelSpec.runs`: `lead`, `blocks`), each
    mixer's tensors stacked over the layers of ITS kind (`models/params.py
    run_tensor_shapes`), everything else over all of the run's."""
    from distributed_llama_tpu.models.params import MIXER
    from distributed_llama_tpu.quants import FloatType, QTensor

    weights = mapped(weights)
    types = _types_held(cfg, weights)
    out, first = {}, 0
    for prefix, n in W.stack_depths(weights, cfg).items():
        if not n:
            continue
        of_run = types[first:first + n]
        first += n
        run = {}
        for name, t in weights.items():
            if not name.startswith(prefix + "."):
                continue
            bare = name.split(".", 1)[1]
            if bare in MIXER:
                kind = CONV if bare.startswith("conv_") else FULL
                keep = np.asarray([i for i, ty in enumerate(of_run)
                                   if ty == kind], np.int64)
                if not keep.size:
                    continue
                if keep.size < n:  # this kind's layers alone
                    t = (tuple(a[keep] for a in t) if isinstance(t, tuple)
                         else t[keep])
            run[bare] = (QTensor(FloatType.Q40, *t) if isinstance(t, tuple)
                         else t)
        out[prefix] = run
    out["embedding"] = weights["embedding"]
    out["rms_final"] = weights["rms_final"]
    out["wcls"] = QTensor(FloatType.Q40, *weights["wcls"])
    return out


def _rmsnorm(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rotate(x, theta: float):
    """x (T, heads, hs) at positions 0..T-1: element j against j + hs/2."""
    import jax.numpy as jnp

    hs = x.shape[-1]
    freqs = theta ** (-np.arange(0, hs, 2, dtype=np.float64) / hs)
    ang = np.outer(np.arange(x.shape[0], dtype=np.float64), freqs)
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    a, b = x[..., : hs // 2], x[..., hs // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _layer(sizes, precision, kind, x, lw, starts, flip_t):
    """One block on one row: (x, margin). x (T, d); kind = (layer type,
    control); `starts` (T,) bool: the positions that start a dispatch (read
    by "conv_state_off" alone). Whether it is a leading (dense) layer is
    read off its tensors."""
    import jax
    import jax.numpy as jnp

    heads, nkv, top, eps, renorm, rscale, theta = sizes
    layer_type, control = kind
    rnd = W.rounder(precision)
    # q80 is the program's rounding of the activations before a WEIGHT matrix
    rnd_att = W.rounder("float32") if precision == "q80" else rnd

    def mm(a, qw):  # a @ W.T with W (out, in) dequantized here
        a, w = rnd(a, W.dequantize(*qw))
        return jnp.einsum("...i,oi->...o", a, w)

    t, d = x.shape
    h = _rmsnorm(x, jnp.asarray(lw["rms_att"]), eps)
    if layer_type == CONV:
        bcu = mm(h, lw["conv_in"])
        gate_b, gate_c, u = bcu[:, :d], bcu[:, d:2 * d], bcu[:, 2 * d:]
        v = gate_b * u
        w = jnp.asarray(lw["conv_w"])  # (d, 3)
        if control == "taps_reversed":
            w = w[:, ::-1]
        v1 = jnp.pad(v[:-1], ((1, 0), (0, 0)))
        v2 = jnp.pad(v[:-2], ((2, 0), (0, 0)))
        if control == "conv_state_off":
            fresh = starts[:, None]
            behind = jnp.pad(starts[:-1], (1, 0))[:, None]
            v1 = jnp.where(fresh, 0.0, v1)
            v2 = jnp.where(fresh | behind, 0.0, v2)
        c = w[:, 0] * v2 + w[:, 1] * v1 + w[:, 2] * v
        x = x + mm(gate_c * c, lw["conv_out"])
    else:
        hs = d // heads
        q = mm(h, lw["wq"]).reshape(t, heads, hs)
        k = mm(h, lw["wk"]).reshape(t, nkv, hs)
        v = mm(h, lw["wv"]).reshape(t, nkv, hs)
        if control != "qknorm_off":
            q = _rmsnorm(q, jnp.asarray(lw["rms_qh"]), eps)
            k = _rmsnorm(k, jnp.asarray(lw["rms_kh"]), eps)
        q, k = _rotate(q, theta), _rotate(k, theta)
        g = heads // nkv
        pos = jnp.arange(t)
        out = []
        for kvh in range(nkv):  # one kv head's keys, queries in blocks
            blocks = []
            for q0 in range(0, t, Q_BLOCK):
                qi = pos[q0:q0 + Q_BLOCK]
                qa, ka = rnd_att(q[q0:q0 + Q_BLOCK, kvh * g:(kvh + 1) * g],
                                 k[:, kvh])
                s = jnp.einsum("qgd,kd->gqk", qa, ka) / np.sqrt(hs)
                s = jnp.where((pos[None, :] <= qi[:, None])[None], s,
                              -jnp.inf)
                pa, va = rnd_att(jax.nn.softmax(s, axis=-1), v[:, kvh])
                blocks.append(jnp.einsum("gqk,kd->qgd", pa, va))
            out.append(jnp.concatenate(blocks, axis=0))  # (T, g, hs)
        att = jnp.concatenate(out, axis=1)  # (T, heads, hs)
        x = x + mm(att.reshape(t, d), lw["wo"])
    y = _rmsnorm(x, jnp.asarray(lw["rms_ffn"]), eps)
    if "w1" in lw:  # a leading layer: the dense FFN, nothing routed
        out = mm(jax.nn.silu(mm(y, lw["w1"])) * mm(y, lw["w3"]), lw["w2"])
        return x + out, jnp.full((t,), jnp.inf, jnp.float32)
    s = jax.nn.sigmoid(mm(y, lw["router"]).astype(jnp.float32))
    bias = jnp.asarray(lw["router_bias"])
    sel = s if control == "bias_off" else s + bias
    order = jnp.argsort(-sel, axis=-1)
    ranked = jnp.take_along_axis(sel, order, axis=-1)
    margin = (ranked[:, top - 1] - ranked[:, top]) / jnp.std(sel, axis=-1)
    if flip_t is not None:
        order = order.at[flip_t, top - 1].set(order[flip_t, top])
    idx = order[:, :top]
    wt = jnp.take_along_axis(s, idx, axis=-1)  # the bias is NOT in them
    if renorm:
        wt = wt / (jnp.sum(wt, axis=-1, keepdims=True) + 1e-6)
    wt = wt * rscale
    share = jnp.sum(jax.nn.one_hot(idx, s.shape[-1]) * wt[..., None],
                    axis=-2)  # (T, E): a token's weight on each expert

    def expert(out, ew):  # a scan, so one expert is dequantized at a time
        up, gate, down, we = ew
        e_out = mm(jax.nn.silu(mm(y, gate)) * mm(y, up), down)
        return out + e_out * we[:, None], None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                          (lw["moe_up"], lw["moe_gate"], lw["moe_down"],
                           share.T))
    return x + out, margin


@functools.lru_cache(maxsize=None)
def _layer_fn(sizes: tuple, precision: str, kind: tuple):
    import jax

    return jax.jit(functools.partial(_layer, sizes, precision, kind))


@functools.lru_cache(maxsize=None)
def _head_fn(precision: str):
    import jax
    import jax.numpy as jnp

    def head(x, packed, scales):
        xr, wr = W.rounder(precision)(x, W.dequantize(packed, scales))
        return jnp.einsum("ni,oi->no", xr, wr)

    return jax.jit(head)


def dispatch_starts(prompt: int, total: int) -> np.ndarray:
    """(total,) bool: the positions that start a dispatch of a row whose
    prompt is `prompt` tokens: its chunks (the largest of 64, 8, 1 that
    fits, as the program cuts them) and every position behind the prompt."""
    out = np.zeros(total, bool)
    i = 0
    while i < min(prompt, total):
        out[i] = True
        i += next(c for c in PREFILL_CHUNKS if prompt - i >= c)
    out[prompt:] = True
    return out


def _used(lw: dict, layer_type: str) -> dict:
    """A layer's tensors less the other mixer's (drawn and unused)."""
    other = ("wq", "wk", "wv", "wo", "rms_qh", "rms_kh") if (
        layer_type == CONV) else ("conv_in", "conv_w", "conv_out")
    return {n: t for n, t in lw.items() if n not in other}


def logits_at(cfg: dict, weights: dict, rows, at, precision: str = "float32",
              flip: tuple[int, int, int] | None = None):
    """The logits at the positions `at[i]` of row `i` and each of those
    positions' smallest router margin over the expert layers of `weights`,
    row after row: (sum of len(at[i]), vocab) float32 and (sum of
    len(at[i]),). flip = (layer, row, t) swaps one routed expert. A row's
    prompt is taken to end at its first recorded position (`probe`)."""
    import jax
    import jax.numpy as jnp

    weights = mapped(weights)
    control = precision if precision in MECHANISM_CONTROLS else ""
    precision = "float32" if control else precision
    sizes = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
             cfg["num_experts_per_tok"], cfg["norm_eps"],
             bool(cfg["norm_topk_prob"]),
             float(cfg["routed_scaling_factor"]), float(cfg["rope_theta"]))
    types = _types_held(cfg, weights)
    where = [np.asarray(a, np.int64) for a in at]
    with jax.default_matmul_precision("highest"):
        # the embedding stays on the host: only the rows' own vectors travel
        xs = [jnp.asarray(weights["embedding"][np.asarray(
            list(r) + [PAD_TOKEN] * (_padded(len(r)) - len(r)))])
            for r in rows]
        starts = [jnp.asarray(dispatch_starts(int(a[0]) + 1, x.shape[0]))
                  for a, x in zip(where, xs)]
        margins = [np.full(len(a), np.inf, np.float32) for a in where]
        for i, layer_type in enumerate(types):
            layer_fn = _layer_fn(sizes, precision, (layer_type, control))
            lw = jax.device_put(_used(W.layer(weights, i, cfg), layer_type))
            for r in range(len(rows)):
                flip_t = flip[2] if flip and flip[:2] == (i, r) else None
                xs[r], m = layer_fn(xs[r], lw, starts[r], flip_t)
                margins[r] = np.minimum(margins[r], np.asarray(m)[where[r]])
            del lw
        x = jnp.concatenate([
            _rmsnorm(x[a], jnp.asarray(weights["rms_final"]),
                     cfg["norm_eps"]) for x, a in zip(xs, where)])
        packed, scales = weights["wcls"]
        head = _head_fn(precision)
        cuts = np.linspace(0, packed.shape[0], HEAD_SLICES + 1).astype(int)
        out = np.concatenate(
            [np.asarray(head(x, packed[a:b], scales[a:b]), np.float32)
             for a, b in zip(cuts, cuts[1:]) if b > a], axis=1)
    return out, np.concatenate(margins)
