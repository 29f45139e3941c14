"""The family of Laguna-S-2.1 (poolside; `model_type` `laguna`): which
`ModelSpec` the program is given, which tensors are drawn, in which stacks
they stand, and the plain reference, the block graph in jax.numpy float32.
The harness reaches it through `cells.load_family` and calls `model_spec`,
`tensor_shapes`, `stacks`, `program_params` and `logits_at`.

The layer l, input x (T x hidden), eps `rms_norm_eps`, no bias anywhere, no
QK-norm; H = `num_attention_heads_per_layer[l]`, hs = `head_dim`, kv heads
`num_key_value_heads`, query head n reads kv head n // (H / kv heads):

    h   = RMSNorm(x; rms_att)
    q   = wq h  (H x hs wide);  k, v = wk h, wv h  (kv heads x hs wide)
    rotation, by `layer_types[l]` and its entry of `rope_parameters`, in
        half-split pairs over the head's FIRST r = hs x partial_rotary_factor
        values, (j, j + r/2), the values behind them left as they are:
        full_attention:    r = 64; YaRN's frequencies over a rotary width of
                           r (theta, factor, original context, beta_fast,
                           beta_slow); cos and sin times `attention_factor`
        sliding_attention: r = 128; theta ^ (-2j / r), no scaling
    position i attends keys j <= i, and on a sliding layer only
        j > i - sliding_window; scores / sqrt(hs); softmax
    g   = sigmoid(wg h)               wg (H, hidden): one value a head a token
    a_n = g_n x att_n                 a head's hs outputs times its gate
    x'  = x + wo a
    u   = RMSNorm(x'; rms_ffn)
    a layer of `mlp_only_layers`:
        out = x' + w2 (silu(w1 u) * w3 u)            width intermediate_size
    every other layer:
        p   = softmax(router u) over all num_experts, float32; the
              num_experts_per_tok largest; w = p_top / sum(p_top) (where
              norm_topk_prob) x moe_routed_scaling_factor
        out = x' + sum_e w_e down_e (silu(gate_e u) * up_e u)
                 + sh_down (silu(sh_gate u) * sh_up u)      the shared expert

then a final RMSNorm and an untied head. Plain jax.numpy float32 under
`default_matmul_precision("highest")`; no kernel, no cache, no batching; it
shares no code with `models/forward.py` nor with the other families, and
takes nothing the program has made: the weights are the benchmark's own seeded
blocks, dequantized here a layer (an expert, a slice of the head) at a time.

What the published configuration names and does not spell, each listed in
the configuration file's `assumed` and each ONE value here and in the
program's `ModelSpec`: (1) the gate is a sigmoid of the normed input h (the
headwise output gate of Qiu et al., Gated Attention for Large Language
Models, 2025; the config says `per-head`); (2) the router scores by a
softmax over all experts (the config's MoE keys are the Qwen-MoE graph's,
which scores so; there is no `scoring_func`); (3) the shared expert is added
ungated; (4) SiLU; (5) no QK-norm; (6) half-split pairs, and
`attention_factor` on the rotated part's cos and sin alone (the Hugging Face
rotary convention for `partial_rotary_factor`).

Departures from the published description: (a) the experts' matrices carry
the program's loader's names up/gate/down, the shared expert's sh_*;
(b) `moe_router_logit_softcapping` 0 is read as off, and any other value is
refused; (c) the layers stand in STACKS in layer order, one a run of like
layers (`stacks`: `lead` = the leading dense layers, then `slide`, `full`,
`slide1`, ... as the kinds alternate), because a sliding layer's wq, wo and
wg have another shape than a full layer's; (d) the harness hands a cut of
the weights to `model_spec` as a DEPTH alone, which is read so (`_cut`):
the file's own depth (`layers_here`) is the whole file; 1 is the first layer
(the leading dense one, full attention); 2 is the file's LAST TWO layers (at
the timed depth of 5: layer 3, sliding, and layer 4, full, both expert
layers). `logits_at` needs no such reading: it sees which stack holds each
layer.

How it blocks the work: as the other families: one layer's tensors on the
device at a time, each row through it alone, padded with token 3 to the next
multiple of 32 (64 past 1024), queries in blocks of 1024 against one kv head's
keys, one expert dequantized at a time, the head in slices of the vocabulary.
A position's router margin is the logit of the last expert taken minus that of
the first one left, over the rms of its own (padded) row's router logits in
that layer (a softmax keeps the logits' order).

`precision`: "float32" is the reference; "bfloat16", "fp8" and "q80" round
the operands of every matrix product through `weights.rounder` (controls);
three more are float32 with one mechanism ignored, what a program that left it
out would compute: "window_off" (sliding layers read every key before them),
"gate_off" (g = 1), "one_rope" (full layers rotated as sliding ones).
"""

from __future__ import annotations

import functools
import math

import numpy as np

from benchmark import weights as W

Q_BLOCK = 1024  # queries scored at once against one kv head's keys
HEAD_SLICES = 8
PAD_TOKEN = 3
MECHANISM_CONTROLS = ("window_off", "gate_off", "one_rope")
FULL, SLIDE = "full_attention", "sliding_attention"


def _padded(n: int) -> int:
    step = 64 if n > 1024 else 32
    return -(-n // step) * step


def _checked(cfg: dict) -> None:
    """Refuse a file this family does not state."""
    n = cfg["layers_here"]
    dense = list(cfg["mlp_only_layers"])
    if dense != list(range(len(dense))) or len(dense) >= n:
        raise ValueError("laguna: the dense layers lead the model; "
                         f"mlp_only_layers {dense} of {n} layers")
    if cfg["moe_router_logit_softcapping"] or cfg["decoder_sparse_step"] != 1:
        raise ValueError("laguna: no soft cap on the router's logits and an "
                         "expert layer behind every dense one; this file "
                         "says otherwise")
    if cfg["gating"] != "per-head" or cfg["moe_apply_router_weight_on_input"]:
        raise ValueError("laguna: a per-head gate, routing weights on the "
                         "experts' output; this file says otherwise")
    if set(cfg["layer_types"][:n]) - {FULL, SLIDE}:
        raise ValueError(f"laguna: layer_types {set(cfg['layer_types'])}")


def _runs(cfg: dict) -> list[tuple[str, list[int]]]:
    """The file's `layers_here` layers as runs of like layers, in layer
    order: (stack prefix, the layers' indices). `lead` holds the leading
    dense layers, which are of one kind; behind them a run ends where
    `layer_types` changes."""
    _checked(cfg)
    n, lead = cfg["layers_here"], len(cfg["mlp_only_layers"])
    types = cfg["layer_types"]
    if len(set(types[:lead])) > 1:
        raise ValueError("laguna: the leading dense layers are of one kind")
    out = [("lead", list(range(lead)))] if lead else []
    count = {FULL: 0, SLIDE: 0}
    for l in range(lead, n):
        if l > lead and types[l] == types[l - 1]:
            out[-1][1].append(l)
            continue
        name = "full" if types[l] == FULL else "slide"
        out.append((name + (str(count[types[l]]) if count[types[l]] else ""),
                    [l]))
        count[types[l]] += 1
    return out


def stacks(cfg: dict) -> list[tuple[str, int]]:
    return [(p, len(ls)) for p, ls in _runs(cfg)]


def _cut(cfg: dict) -> list[int]:
    """The layers that `num_hidden_layers` stands for (departure (d))."""
    depth, n = cfg["num_hidden_layers"], cfg["layers_here"]
    if depth == n:
        return list(range(n))
    if depth == 1:
        return [0]
    if depth == 2:
        return [n - 2, n - 1]
    raise ValueError(f"laguna: a cut of {depth} of {n} layers is not one this "
                     "family can read from its depth (1, 2 or the whole)")


def one_layer_a_stack(cfg: dict, experts: int | None = None) -> dict:
    """The file cut to ONE layer of each of its stacks, with `experts`
    experts where given: the same tensors in the same stacks at a size a
    tool can draw that wants the parameter tree's structure and not its
    weight (`perf/aot_step.py` compiles the whole file's step programs for a
    described chip from such a tree)."""
    first = [ls[0] for _, ls in _runs(cfg)]
    lead = len(cfg["mlp_only_layers"])
    out = {**cfg, "num_hidden_layers": len(first), "layers_here": len(first),
           "mlp_only_layers": list(range(min(lead, 1))),
           **{k: [cfg[k][l] for l in first] for k in (
               "layer_types", "num_attention_heads_per_layer")}}
    if experts:
        out["num_experts"] = experts
    return out


def _heads(cfg: dict, l: int) -> int:
    return cfg["num_attention_heads_per_layer"][l]


def model_spec(cfg: dict):
    """The program's ModelSpec for the file's keys: the two kinds of layer
    (heads, window, rotation) as `ModelSpec.kinds`, each layer's kind, the
    leading dense layers, the routed block with its shared expert. A cut of
    the leading layer alone is a dense model."""
    from distributed_llama_tpu.models.spec import (ArchType, HiddenAct,
                                                   LayerKind, ModelSpec,
                                                   RopeType, RouterScore)

    _checked(cfg)
    layers = _cut(cfg)
    hs = cfg["head_dim"]
    names = sorted({cfg["layer_types"][l] for l in layers})
    kinds = []
    for name in names:
        rp = cfg["rope_parameters"][name]
        of = [l for l in layers if cfg["layer_types"][l] == name]
        heads = {_heads(cfg, l) for l in of}
        if len(heads) != 1:
            raise ValueError(f"laguna: {name} layers of {heads} heads")
        yarn = rp["rope_type"] == "yarn"
        if rp["rope_type"] not in ("yarn", "default"):
            raise ValueError(f"laguna: rope_type {rp['rope_type']!r}")
        kinds.append(LayerKind(
            name="full" if name == FULL else "slide", n_heads=heads.pop(),
            sliding_window=cfg["sliding_window"] if name == SLIDE else 0,
            rope_type=RopeType.YARN_NEOX if yarn else RopeType.FALCON,
            rope_theta=float(rp["rope_theta"]),
            rotary_dim=int(round(hs * rp["partial_rotary_factor"])),
            rope_scaling_factor=float(rp["factor"]) if yarn else 0.0,
            rope_scaling_orig_max_seq_len=(
                rp["original_max_position_embeddings"] if yarn else 0),
            yarn_beta_fast=float(rp.get("beta_fast", 32)),
            yarn_beta_slow=float(rp.get("beta_slow", 1)),
            rope_table_scale=float(rp["attention_factor"]) if yarn else 0.0))
    lead = sum(1 for l in layers if l in cfg["mlp_only_layers"])
    dense_only = lead == len(layers)
    routed = {} if dense_only else dict(
        n_experts=cfg["num_experts"],
        n_active_experts=cfg["num_experts_per_tok"],
        shared_hidden_dim=cfg["shared_expert_intermediate_size"],
        router_score=RouterScore.SOFTMAX,
        router_renorm=bool(cfg["norm_topk_prob"]),
        router_scale=float(cfg["moe_routed_scaling_factor"]),
        lead_layers=lead, lead_hidden_dim=cfg["intermediate_size"] if lead
        else 0)
    return ModelSpec(
        arch_type=ArchType.LLAMA if dense_only else ArchType.MIXTRAL,
        dim=cfg["hidden_size"],
        hidden_dim=cfg["intermediate_size" if dense_only
                       else "moe_intermediate_size"],
        n_layers=len(layers), n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], vocab_size=cfg["vocab_size"],
        seq_len=cfg["context"], hidden_act=HiddenAct.SILU,
        rope_type=RopeType.FALCON, norm_eps=cfg["rms_norm_eps"], head_dim=hs,
        attn_gate=True, kinds=tuple(kinds),
        layer_kinds=tuple(names.index(cfg["layer_types"][l])
                          for l in layers), **routed).resolved()


def tensor_shapes(cfg: dict) -> dict[str, tuple[tuple[int, ...], bool]]:
    """name -> (shape with the layer axis, drawn as Q40?), matrices (out,
    in), under the program's loader's names (`models/params.py
    block_tensor_shapes`), a stack's prefix ahead of its tensors'."""
    # a program that cannot state this model fails here, before the weights
    # are drawn: the run then ends in a second with the import's message
    from distributed_llama_tpu.models.spec import LayerKind  # noqa: F401

    d, hs = cfg["hidden_size"], cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * hs
    e, h, sh = (cfg["num_experts"], cfg["moe_intermediate_size"],
                cfg["shared_expert_intermediate_size"])
    v = cfg["vocab_size"]
    out = {"rms_final": ((d,), False), "embedding": ((v, d), False),
           "wcls": ((v, d), True)}
    for prefix, layers in _runs(cfg):
        n, nh = len(layers), _heads(cfg, layers[0])
        own = {"wq": ((n, nh * hs, d), True), "wk": ((n, kv, d), True),
               "wv": ((n, kv, d), True), "wo": ((n, d, nh * hs), True),
               "wg": ((n, nh, d), True),
               "rms_att": ((n, d), False), "rms_ffn": ((n, d), False)}
        if prefix == "lead":
            f = cfg["intermediate_size"]
            own.update({"w1": ((n, f, d), True), "w2": ((n, d, f), True),
                        "w3": ((n, f, d), True)})
        else:
            own.update({"router": ((n, e, d), True),
                        "moe_up": ((n, e, h, d), True),
                        "moe_gate": ((n, e, h, d), True),
                        "moe_down": ((n, e, d, h), True),
                        "sh_gate": ((n, sh, d), True),
                        "sh_down": ((n, d, sh), True),
                        "sh_up": ((n, sh, d), True)})
        out.update({f"{prefix}.{name}": s for name, s in own.items()})
    return out


def program_params(cfg: dict, weights: dict):
    """One entry a stack that holds a layer, under the names the program's
    own spec gives its runs of like layers (`ModelSpec.runs`), in layer
    order: the stacks of these weights and the runs of their spec are the
    same cuts of the same layers."""
    from distributed_llama_tpu.quants import FloatType, QTensor

    def q(t):
        return QTensor(FloatType.Q40, *t) if isinstance(t, tuple) else t

    depths = W.stack_depths(weights, cfg)
    held = [(p, n) for p, n in depths.items() if n]
    runs = model_spec({**cfg, "num_hidden_layers": sum(depths.values())}).runs()
    if [n for _, n in held] != [r.depth for r in runs]:
        raise ValueError(f"laguna: the weights' stacks {held} are not the "
                         f"program's runs {runs}")
    out = {}
    for (prefix, _), run in zip(held, runs):
        out[run.name] = {n.split(".", 1)[1]: q(t) for n, t in weights.items()
                         if n.startswith(prefix + ".")}
    out.update({n: q(weights[n]) for n in W.NOT_BLOCKS})
    return out


def _rmsnorm(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def inv_freq(rp: dict, hs: int) -> tuple[np.ndarray, float]:
    """One kind's rotation from its entry of `rope_parameters`: the
    frequencies of the r / 2 pairs, float64, and what cos and sin are
    multiplied by. YaRN: a pair keeps its frequency below the correction
    range, is divided by `factor` above it, and is ramped between; the range
    is where a pair turns beta_fast (floor) and beta_slow (ceiling) times
    over the original context."""
    r = int(round(hs * rp["partial_rotary_factor"]))
    theta = float(rp["rope_theta"])
    f = theta ** (-np.arange(0, r, 2, dtype=np.float64) / r)
    if rp["rope_type"] == "default":
        return f, 1.0
    orig = rp["original_max_position_embeddings"]

    def turns(beta):
        return r * math.log(orig / (beta * 2 * math.pi)) / (2 * math.log(theta))

    lo = max(math.floor(turns(rp["beta_fast"])), 0)
    hi = min(math.ceil(turns(rp["beta_slow"])), r - 1)
    ramp = np.clip((np.arange(r // 2) - lo) / max(hi - lo, 0.001), 0, 1)
    return (f / rp["factor"] * ramp + f * (1 - ramp),
            float(rp["attention_factor"]))


def _rotate(x, freqs, factor: float):
    """x (T, heads, hs) at positions 0..T-1: of the first r = 2 x len(freqs)
    values element j against j + r/2; the values behind them pass."""
    import jax.numpy as jnp

    r = 2 * len(freqs)
    ang = np.outer(np.arange(x.shape[0], dtype=np.float64), freqs)
    cos = jnp.asarray(np.cos(ang) * factor, jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang) * factor, jnp.float32)[:, None, :]
    a, b = x[..., : r // 2], x[..., r // 2:r]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos, x[..., r:]],
                           axis=-1)


def _layer(sizes, precision, kind, x, lw, flip_t):
    """One block on one row: (x, margin). x (T, d); kind = (window in keys
    or 0, the pairs' frequencies, the tables' factor, gated); whether it is
    a leading (dense) layer and how many heads it has are read off its
    tensors."""
    import jax
    import jax.numpy as jnp

    nkv, hs, top, eps, renorm, rscale = sizes
    window, freqs, factor, gated = kind
    rnd = W.rounder(precision)
    # q80 is the program's rounding of the activations before a WEIGHT matrix
    rnd_att = W.rounder("float32") if precision == "q80" else rnd

    def mm(a, qw):  # a @ W.T with W (out, in) dequantized here
        a, w = rnd(a, W.dequantize(*qw))
        return jnp.einsum("...i,oi->...o", a, w)

    t = x.shape[0]
    h = _rmsnorm(x, jnp.asarray(lw["rms_att"]), eps)
    q = mm(h, lw["wq"])
    nh = q.shape[-1] // hs
    q = q.reshape(t, nh, hs)
    k = mm(h, lw["wk"]).reshape(t, nkv, hs)
    v = mm(h, lw["wv"]).reshape(t, nkv, hs)
    fr = np.asarray(freqs, np.float64)
    q, k = _rotate(q, fr, factor), _rotate(k, fr, factor)
    g = nh // nkv
    pos = jnp.arange(t)
    heads = []
    for kvh in range(nkv):  # one kv head's keys, queries in blocks
        blocks = []
        for q0 in range(0, t, Q_BLOCK):
            qi = pos[q0:q0 + Q_BLOCK]
            qa, ka = rnd_att(q[q0:q0 + Q_BLOCK, kvh * g:(kvh + 1) * g],
                             k[:, kvh])
            s = jnp.einsum("qgd,kd->gqk", qa, ka) / np.sqrt(hs)
            ok = pos[None, :] <= qi[:, None]
            if window:
                ok &= pos[None, :] > qi[:, None] - window
            s = jnp.where(ok[None], s, -jnp.inf)
            pa, va = rnd_att(jax.nn.softmax(s, axis=-1), v[:, kvh])
            blocks.append(jnp.einsum("gqk,kd->qgd", pa, va))
        heads.append(jnp.concatenate(blocks, axis=0))  # (T, g, hs)
    att = jnp.concatenate(heads, axis=1)  # (T, nh, hs)
    if gated:
        att = att * jax.nn.sigmoid(mm(h, lw["wg"]))[..., None]
    x = x + mm(att.reshape(t, nh * hs), lw["wo"])
    u = _rmsnorm(x, jnp.asarray(lw["rms_ffn"]), eps)
    if "w1" in lw:  # a leading layer: the dense FFN, nothing routed
        y = mm(jax.nn.silu(mm(u, lw["w1"])) * mm(u, lw["w3"]), lw["w2"])
        return x + y, jnp.full((t,), jnp.inf, jnp.float32)
    logits = mm(u, lw["router"]).astype(jnp.float32)
    order = jnp.argsort(-logits, axis=-1)
    ranked = jnp.take_along_axis(logits, order, axis=-1)
    margin = (ranked[:, top - 1] - ranked[:, top]) / jnp.sqrt(
        jnp.mean(logits * logits))
    if flip_t is not None:
        order = order.at[flip_t, top - 1].set(order[flip_t, top])
    idx = order[:, :top]
    w = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), idx, axis=-1)
    if renorm:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * rscale
    share = jnp.sum(jax.nn.one_hot(idx, logits.shape[-1]) * w[..., None],
                    axis=-2)  # (T, E): a token's weight on each expert

    def expert(out, ew):  # a scan, so one expert is dequantized at a time
        up, gate, down, we = ew
        y = mm(jax.nn.silu(mm(u, gate)) * mm(u, up), down)
        return out + y * we[:, None], None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                          (lw["moe_up"], lw["moe_gate"], lw["moe_down"],
                           share.T))
    shared = mm(jax.nn.silu(mm(u, lw["sh_gate"])) * mm(u, lw["sh_up"]),
                lw["sh_down"])
    return x + out + shared, margin


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


def _kind(cfg: dict, layer_type: str, control: str) -> tuple:
    """A layer's kind as `_layer` takes it, with what a mechanism control
    leaves out left out."""
    if control == "one_rope":  # full layers rotated as sliding ones
        freqs, factor = inv_freq(cfg["rope_parameters"][SLIDE],
                                 cfg["head_dim"])
    else:
        freqs, factor = inv_freq(cfg["rope_parameters"][layer_type],
                                 cfg["head_dim"])
    window = cfg["sliding_window"] if layer_type == SLIDE else 0
    if control == "window_off":
        window = 0
    return (window, tuple(freqs.tolist()), factor, control != "gate_off")


def logits_at(cfg: dict, weights: dict, rows, at, precision: str = "float32",
              flip: tuple[int, int, int] | None = None):
    """The logits at the positions `at[i]` of row `i` and each of those
    positions' smallest router margin over the expert layers of `weights`,
    row after row: (sum of len(at[i]), vocab) float32 and (sum of
    len(at[i]),). flip = (layer, row, t) swaps one routed expert."""
    import jax
    import jax.numpy as jnp

    control = precision if precision in MECHANISM_CONTROLS else ""
    precision = "float32" if control else precision
    sizes = (cfg["num_key_value_heads"], cfg["head_dim"],
             cfg["num_experts_per_tok"], cfg["rms_norm_eps"],
             bool(cfg["norm_topk_prob"]),
             float(cfg["moe_routed_scaling_factor"]))
    # each held layer's kind, from the stack that holds it
    type_of = {p: cfg["layer_types"][ls[0]] for p, ls in _runs(cfg)}
    types = [type_of[p] for p, n in W.stack_depths(weights, cfg).items()
             for _ in range(n)]
    where = [np.asarray(a, np.int64) for a in at]
    with jax.default_matmul_precision("highest"):
        # the embedding stays on the host: only the rows' own vectors travel
        xs = [jnp.asarray(weights["embedding"][np.asarray(
            list(r) + [PAD_TOKEN] * (_padded(len(r)) - len(r)))])
            for r in rows]
        margins = [np.full(len(a), np.inf, np.float32) for a in where]
        for i, layer_type in enumerate(types):
            layer_fn = _layer_fn(sizes, precision,
                                 _kind(cfg, layer_type, control))
            lw = jax.device_put(W.layer(weights, i, cfg))  # once for all rows
            for r in range(len(rows)):
                flip_t = flip[2] if flip and flip[:2] == (i, r) else None
                xs[r], m = layer_fn(xs[r], lw, flip_t)
                margins[r] = np.minimum(margins[r], np.asarray(m)[where[r]])
            del lw
        x = jnp.concatenate([
            _rmsnorm(x[a], jnp.asarray(weights["rms_final"]),
                     cfg["rms_norm_eps"]) for x, a in zip(xs, where)])
        packed, scales = weights["wcls"]
        head = _head_fn(precision)
        cuts = np.linspace(0, packed.shape[0], HEAD_SLICES + 1).astype(int)
        out = np.concatenate(
            [np.asarray(head(x, packed[a:b], scales[a:b]), np.float32)
             for a, b in zip(cuts, cuts[1:]) if b > a], axis=1)
    return out, np.concatenate(margins)
