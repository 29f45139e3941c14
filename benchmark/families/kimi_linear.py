"""The family of Kimi-Linear-48B-A3B-Instruct (moonshotai; `model_type`
`kimi_linear`; arXiv:2510.26692): which `ModelSpec` the program is given,
which tensors are drawn, in which stack they stand, and the plain reference,
the block graph in jax.numpy float32 with the delta rule as a `lax.scan` over
POSITIONS (no chunk form, no kernel, no cache, no batching, no code shared
with `models/forward.py`). The harness reaches it through `cells.load_family`
and calls `model_spec`, `tensor_shapes`, `stacks`, `program_params` and
`logits_at`.

The layer l, input x (T x hidden), eps `rms_norm_eps`, no bias anywhere:

    a   = x + mixer_l(RMSNorm(x; rms_att))
    x'  = a + ffn_l(RMSNorm(a; rms_ffn))
    logits = wcls RMSNorm(x_L; rms_final)               (an untied head)

    mixer, a KDA layer (`linear_attn_config.kda_layers`, counted from 1;
    H = `num_heads` heads, K = V = `head_dim` channels, h the normed input):
        [q | k | v] = kda_in h            three projections hidden -> H K
        u'_p = silu(sum_{j=0..3} w[:, j] u_{p-3+j})   each channel's own
                                          `short_conv_kernel_size` 4 taps,
                                          causal, zeros before position 0,
                                          the newest position on tap 3
        q, k = l2norm(u' q-part), l2norm(u' k-part) a head:
                                          x / sqrt(sum x^2 + 1e-6);  v = u' v
        g    = -exp(a_log[head]) softplus(kda_fb (f_a h) + dt_bias)
                                          a CHANNEL of a head, float32; the
                                          pair f_a (hidden -> K), kda_fb
                                          (K -> H K); a = exp(g) in (0, 1]
        beta = sigmoid(kda_b h)           a scalar a head
        S (K x V, float32, zeros at position 0), a position t:
            S <- Diag(a_t) S
            u_t = beta_t (v_t - S^T k_t)
            S <- S + k_t u_t^T
            o_t = K^-1/2 S^T q_t
        out  = kda_out [RMSNorm(o; kda_norm, a head's V values)
                        * sigmoid(kda_gb (g_a h))]   the pair g_a, kda_gb
                                                      (hidden -> K -> H V)
    mixer, an MLA layer (`full_attn_layers`; `num_attention_heads` heads):
        q = wq h                          a head [q_nope (`qk_nope_head_dim`)
                                          ; q_pe (`qk_rope_head_dim`)]
        [c ; k_pe] = wkv_a h              `kv_lora_rank` + `qk_rope_head_dim`;
                                          c = RMSNorm(c; rms_kv)
        a head's key [w_uk c ; k_pe], its value w_uv c (`v_head_dim`); NO
        rotation of q_pe or k_pe (`mla_use_nope`); scores times
        (qk_nope + qk_rope)^-1/2, causal, softmax; out = wo att.
    ffn, layers 0 .. `first_k_dense_replace` - 1: w2 (silu(w1 y) * w3 y) at
        `intermediate_size`.
    ffn, the rest: s = sigmoid(router y) (`num_experts` wide, float32); the
        `num_experts_per_token` largest of s + router_bias (one group:
        `num_expert_group` 1); weights s of the chosen, divided by their sum
        (`moe_renormalize`), times `routed_scaling_factor`; each expert
        down_e (silu(gate_e y) * up_e y) at `moe_intermediate_size`; plus the
        shared expert sh_down (silu(sh_gate y) * sh_up y) of
        `num_shared_experts` x `moe_intermediate_size`, unweighted.

What the published configuration names and does not spell is listed in the
configuration file's `assumed`, each ONE value here and in the program's
`ModelSpec`.

Departures from the published description: (a) the tensors carry the
program's loader's names, and the published q_proj, k_proj, v_proj stand
fused in ONE `kda_in` in that order (their three convolutions one depthwise
convolution over the 3 H K channels), f_a_proj and g_a_proj in ONE `kda_lo`
([f_a | g_a]); (b) the kv_b projection is drawn as its two halves by head,
`w_uk` and `w_uv`, and the reference forms every head's keys and values (the
UNabsorbed form) where the program reads the latent row absorbed; (c)
`head_dim` 72 (hidden / heads) is read by neither mixer; (d) the layers stand
in TWO stacks (`lead`: the dense layers, KDA; `blocks`) and every layer of
`blocks` is DRAWN with both mixers' tensors, of which it uses its kind's
(`program_params` leaves the others out, each kind's stacked over its own
layers); (e) the harness hands a cut of the weights to `model_spec` as a
DEPTH alone, which is read so (`_cut`): the file's own depth is the whole
file; 2 is layer 0 (KDA, dense) and the first MLA layer (3: experts).

What the harness draws and this family maps, in ONE function that
`program_params` and `logits_at` both call (`mapped`): `weights._draw` gives
every unquantized tensor 1 + 0.02 N, under which no head decays differently
from another, the four taps are alike and the selection bias selects
nothing. Mapped: exp(a_log) = 1 .. 16 spread over the heads times what was
drawn, dt_bias so that softplus gives 0.001 .. 0.1 log-spaced over a head's
channels (a per-position decay from 0.999 down to 0.2 in one head: channels
that remember a thousand positions beside channels that forget in five), the
taps `TAPS` times what was drawn (four clearly different ones, the newest the
largest), kda_b's scales times `beta_gain` so that its logits spread by 2
and beta spans 0.1 to 0.9 (the model states no bias for it), wq's scales
times `Q_GAIN` (drawn, an MLA layer's scores spread by 0.6 and its softmax
is an average over the keys, which neither a rotation of the 64 pe values
nor any other change of the scores moves: the `pe_rotated` control read
0.024 where the sound runs read 0.011, my chip run, PR 48; times 4 they
spread by 2.5 and the softmax selects), the selection bias `BIAS_SPREAD`
times its deviation from 1.

How it blocks the work: as the other families: one layer's tensors on the
device at a time, each row through it alone, padded with token 3 to the next
multiple of 32 (64 past 1024), queries in blocks of 1024 against the keys,
one expert dequantized at a time, the head in slices of the vocabulary. A
position's router margin is the eighth largest selection score less the
ninth, over the spread (standard deviation) of the position's scores.

`precision`: "float32" is the reference; "bfloat16", "fp8" and "q80" round
the operands of every matrix product through `weights.rounder` (controls);
eight more are float32 with one mechanism changed, what a program that lost
it would compute: "kda_state_off" (S zeroed at every dispatch: a position
that starts one, the prompt's chunks of 64, 8 and 1 and every forced token
behind it, sees no earlier S), "decay_off" (g = 0), "delta_off" (the
- S^T k term dropped: u = beta v), "qk_norm_off" (q and k as the convolution
left them), "taps_reversed" (w[:, ::-1]), "out_gate_off" (the sigmoid gate
1), "pe_rotated" (q_pe and k_pe rotated at `rope_theta`, half-split pairs,
where the model states none), "router_bias_off" (the k largest of s alone).
"""

from __future__ import annotations

import functools

import numpy as np

from benchmark import weights as W

Q_BLOCK = 1024  # queries scored at once against the keys
HEAD_SLICES = 8
PAD_TOKEN = 3
PREFILL_CHUNKS = (64, 8, 1)  # how the program cuts a prompt into dispatches
MECHANISM_CONTROLS = ("kda_state_off", "decay_off", "delta_off",
                      "qk_norm_off", "taps_reversed", "out_gate_off",
                      "pe_rotated", "router_bias_off")
KDA_LAYER, MLA_LAYER = "kda", "mla"
TAPS = (0.2, -0.4, 0.6, 1.0)  # times the drawn 1 + 0.02 N: oldest first
A_RANGE = (1.0, 16.0)  # exp(a_log) over the heads
DT_RANGE = (1e-3, 1e-1)  # softplus(dt_bias) over a head's channels
BETA_SPREAD = 2.0  # the standard deviation of kda_b's logits
Q_GAIN = 4.0  # times wq's scales: an MLA layer's scores then spread by 2.5
BIAS_SPREAD = 2.0  # times the drawn 0.02 N
L2_EPS = 1e-6
MAPPED = "mapped"  # a key of weights that `mapped` has been over
KDA = ("kda_in", "kda_conv_w", "kda_lo", "kda_fb", "kda_gb", "kda_b",
       "kda_dt_bias", "kda_a_log", "kda_norm", "kda_out")
MLA = ("wq", "wkv_a", "w_uk", "w_uv", "wo", "rms_kv")


def _padded(n: int) -> int:
    step = 64 if n > 1024 else 32
    return -(-n // step) * step


def layer_types(cfg: dict) -> list[str]:
    """The kind of each of the file's `layers_here` layers, from the two
    published lists (counted from 1)."""
    lin = cfg["linear_attn_config"]
    kda, full = set(lin["kda_layers"]), set(lin["full_attn_layers"])
    out = []
    for l in range(1, cfg["layers_here"] + 1):
        if (l in kda) == (l in full):
            raise ValueError(f"kimi_linear: layer {l} is in "
                             f"{'both' if l in kda else 'neither'} of "
                             "kda_layers and full_attn_layers")
        out.append(KDA_LAYER if l in kda else MLA_LAYER)
    return out


def _checked(cfg: dict) -> list[str]:
    """Refuse a file this family does not state; the layers' kinds."""
    types = layer_types(cfg)
    lead = cfg["first_k_dense_replace"]
    if not 0 < lead < len(types) or set(types[:lead]) != {KDA_LAYER}:
        raise ValueError("kimi_linear: the dense layers lead the model and "
                         f"are KDA layers; first_k_dense_replace {lead}, "
                         f"layers {types[:lead]}")
    if MLA_LAYER not in types[lead:]:
        raise ValueError("kimi_linear: an MLA layer among the expert layers "
                         "(the cut of two layers this family reads rests on "
                         "the first)")
    if (not cfg["mla_use_nope"] or cfg["q_lora_rank"] is not None
            or cfg["rope_scaling"] is not None):
        raise ValueError("kimi_linear: latent attention without rotation "
                         "(mla_use_nope), q through one projection "
                         "(q_lora_rank null); this file says otherwise")
    if (cfg["num_expert_group"] != 1 or cfg["topk_group"] != 1
            or cfg["moe_router_activation_func"] != "sigmoid"
            or cfg["moe_layer_freq"] != 1 or cfg["tie_word_embeddings"]
            or cfg["num_nextn_predict_layers"]):
        raise ValueError("kimi_linear: one group of sigmoid-scored experts "
                         "in every layer behind the dense ones, an untied "
                         "head, no next-token layers; this file says "
                         "otherwise")
    return types


def stacks(cfg: dict) -> list[tuple[str, int]]:
    _checked(cfg)
    lead = cfg["first_k_dense_replace"]
    return [("lead", lead), ("blocks", cfg["layers_here"] - lead)]


def _cut(cfg: dict) -> list[int]:
    """The layers that `num_hidden_layers` stands for (departure (e))."""
    depth, n = cfg["num_hidden_layers"], cfg["layers_here"]
    if depth == n:
        return list(range(n))
    if depth == 2:
        return [0, layer_types(cfg).index(MLA_LAYER)]
    raise ValueError(f"kimi_linear: a cut of {depth} of {n} layers is not "
                     "one this family can read from its depth (2 or the "
                     "whole)")


def one_layer_a_stack(cfg: dict, experts: int | None = None) -> dict:
    """The file cut to its leading layer and ONE period behind it (KDA, KDA,
    MLA), with `experts` experts where given: the same tensors in the same
    stacks at a size a tool can draw that wants the parameter tree's
    structure and not its weight (`perf/aot_step.py`)."""
    keep = layer_types(cfg).index(MLA_LAYER) + 1
    out = {**cfg, "num_hidden_layers": keep, "layers_here": keep}
    if experts:
        out["num_experts"] = experts
    return out


def model_spec(cfg: dict):
    """The program's ModelSpec for the file's keys: the two kinds of layer
    (`ModelSpec.kinds`: the delta-rule mixer, and latent attention without a
    rotation and with q through one projection), each layer's kind, the
    leading dense layer, the sigmoid router with its selection bias, the
    shared expert, the snapshot pool (the file's own key
    `state_snapshots`)."""
    from distributed_llama_tpu.models.spec import (ArchType, HiddenAct,
                                                   LayerKind, ModelSpec,
                                                   RopeType, RouterScore)

    all_types = _checked(cfg)
    layers = _cut(cfg)
    types = [all_types[l] for l in layers]
    lin = cfg["linear_attn_config"]
    heads = cfg["num_attention_heads"]
    names = sorted(set(types))  # "kda" before "mla"
    kinds = tuple(
        LayerKind(name="kda", n_heads=heads, rope_type=RopeType.NONE,
                  conv_kernel=lin["short_conv_kernel_size"],
                  kda_heads=lin["num_heads"], kda_key_dim=lin["head_dim"],
                  kda_value_dim=lin["head_dim"], kda_rank=lin["head_dim"])
        if name == KDA_LAYER else
        LayerKind(name="mla", n_heads=heads, rope_type=RopeType.NONE,
                  rope_theta=float(cfg["rope_theta"]),
                  kv_lora_rank=cfg["kv_lora_rank"],
                  qk_nope_head_dim=cfg["qk_nope_head_dim"],
                  qk_rope_head_dim=cfg["qk_rope_head_dim"],
                  v_head_dim=cfg["v_head_dim"]) for name in names)
    lead = sum(1 for l in layers if l < cfg["first_k_dense_replace"])
    return ModelSpec(
        arch_type=ArchType.MIXTRAL, dim=cfg["hidden_size"],
        hidden_dim=cfg["moe_intermediate_size"], n_layers=len(layers),
        n_heads=heads, n_kv_heads=1, vocab_size=cfg["vocab_size"],
        seq_len=cfg["context"], hidden_act=HiddenAct.SILU,
        rope_type=RopeType.FALCON, rope_theta=float(cfg["rope_theta"]),
        norm_eps=cfg["rms_norm_eps"], n_experts=cfg["num_experts"],
        n_active_experts=cfg["num_experts_per_token"],
        router_score=RouterScore.SIGMOID, router_bias=True,
        router_renorm=bool(cfg["moe_renormalize"]),
        router_scale=float(cfg["routed_scaling_factor"]),
        shared_hidden_dim=(cfg["num_shared_experts"]
                           * cfg["moe_intermediate_size"]),
        lead_layers=lead, lead_hidden_dim=cfg["intermediate_size"],
        state_snapshots=int(cfg["state_snapshots"]),
        kinds=kinds, layer_kinds=tuple(names.index(t) for t in types),
    ).resolved()


def tensor_shapes(cfg: dict) -> dict[str, tuple[tuple[int, ...], bool]]:
    """name -> (shape with the layer axis, drawn as Q40?), matrices (out,
    in), under the program's loader's names, a stack's prefix ahead of its
    tensors'. A layer of `blocks` is drawn with both mixers' tensors
    (departure (d))."""
    # a program that cannot state this model fails here, before the weights
    # are drawn: the run then ends in a second with the import's message
    from distributed_llama_tpu.ops.pallas_kda import kda_step  # noqa: F401

    _checked(cfg)
    lin = cfg["linear_attn_config"]
    d, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    kh, hd = lin["num_heads"], lin["head_dim"]
    cw, taps = 3 * kh * hd, lin["short_conv_kernel_size"]
    r, dn, dr, dv = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    e, h, f = (cfg["num_experts"], cfg["moe_intermediate_size"],
               cfg["intermediate_size"])
    sh = cfg["num_shared_experts"] * h
    v = cfg["vocab_size"]
    out = {"rms_final": ((d,), False), "embedding": ((v, d), False),
           "wcls": ((v, d), True)}
    for prefix, n in stacks(cfg):
        own = {"kda_in": ((n, cw, d), True),
               "kda_conv_w": ((n, cw, taps), False),
               "kda_lo": ((n, 2 * hd, d), True),
               "kda_fb": ((n, kh * hd, hd), True),
               "kda_gb": ((n, kh * hd, hd), True),
               "kda_b": ((n, kh, d), True),
               "kda_dt_bias": ((n, kh * hd), False),
               "kda_a_log": ((n, kh), False),
               "kda_norm": ((n, hd), False),
               "kda_out": ((n, d, kh * hd), True),
               "rms_att": ((n, d), False), "rms_ffn": ((n, d), False)}
        if prefix == "lead":
            own.update({"w1": ((n, f, d), True), "w2": ((n, d, f), True),
                        "w3": ((n, f, d), True)})
        else:
            own.update({"wq": ((n, nh * (dn + dr), d), True),
                        "wkv_a": ((n, r + dr, d), True),
                        "w_uk": ((n, nh, dn, r), True),
                        "w_uv": ((n, nh, dv, r), True),
                        "wo": ((n, d, nh * dv), True),
                        "rms_kv": ((n, r), False),
                        "router": ((n, e, d), True),
                        "router_bias": ((n, e), False),
                        "moe_up": ((n, e, h, d), True),
                        "moe_gate": ((n, e, h, d), True),
                        "moe_down": ((n, e, d, h), True),
                        "sh_gate": ((n, sh, d), True),
                        "sh_up": ((n, sh, d), True),
                        "sh_down": ((n, d, sh), True)})
        out.update({f"{prefix}.{name}": s for name, s in own.items()})
    return out


def beta_gain(d: int) -> float:
    """What kda_b's scales are multiplied by: its logits over a normed
    input of width d then spread by BETA_SPREAD (drawn they spread by
    0.02 sqrt(d): 0.96 at 2304, 0.23 at a toy's 128)."""
    return BETA_SPREAD / (0.02 * float(np.sqrt(d)))


def mapped(weights: dict) -> dict:
    """The drawn tensors as program and reference both read them (the
    module's docstring says which and why). Weights that were mapped already
    pass unchanged."""
    if weights.get(MAPPED):
        return weights
    out = {**weights, MAPPED: True}
    for name, t in weights.items():
        bare = name.rsplit(".", 1)[-1]
        if bare == "kda_conv_w":
            out[name] = (t * np.asarray(TAPS, np.float32)).astype(np.float32)
        elif bare == "kda_a_log":
            heads = np.linspace(*A_RANGE, t.shape[-1]).astype(np.float32)
            out[name] = np.log(heads * t).astype(np.float32)
        elif bare == "kda_dt_bias":
            heads = weights[name.replace("kda_dt_bias",
                                         "kda_a_log")].shape[-1]
            step = np.tile(np.geomspace(*DT_RANGE, t.shape[-1] // heads),
                           heads).astype(np.float32)
            out[name] = (np.log(np.expm1(step)) + (t - 1.0)).astype(
                np.float32)
        elif bare == "kda_b":
            packed, scales = t
            gain = beta_gain(packed.shape[-2] * W.QK)
            out[name] = (packed, (scales.astype(np.float32) * gain).astype(
                scales.dtype))
        elif bare == "wq":
            packed, scales = t
            out[name] = (packed, (scales.astype(np.float32) * Q_GAIN).astype(
                scales.dtype))
        elif bare == "router_bias":
            out[name] = ((t - 1.0) * BIAS_SPREAD).astype(np.float32)
    return out


def _types_held(cfg: dict, weights: dict) -> list[str]:
    """The kind of each layer `weights` hold, in layer order: a cut's layers
    are the ones `_cut` reads from its depth."""
    depths = W.stack_depths(weights, cfg)
    layers = _cut({**cfg, "num_hidden_layers": sum(depths.values())})
    types = layer_types(cfg)
    return [types[l] for l in layers]


def program_params(cfg: dict, weights: dict):
    """The program's two runs (`ModelSpec.runs`: `lead`, `blocks`), each
    mixer's tensors stacked over the layers of ITS kind (`models/params.py
    run_tensor_shapes`), everything else over all of the run's."""
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
            kind = (KDA_LAYER if bare in KDA else MLA_LAYER if bare in MLA
                    else None)
            if kind:
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
    """x (T, heads, w) at positions 0..T-1: element j against j + w/2 (the
    "pe_rotated" control alone: the model states no rotation)."""
    import jax.numpy as jnp

    w = x.shape[-1]
    freqs = theta ** (-np.arange(0, w, 2, dtype=np.float64) / w)
    ang = np.outer(np.arange(x.shape[0], dtype=np.float64), freqs)
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    a, b = x[..., : w // 2], x[..., w // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def kda_recurrence(q, k, v, g, beta, starts=None):
    """The delta rule position by position: q, k (T, H, K), v (T, H, V), g
    (T, H, K) the log decay, beta (T, H) -> (o (T, H, V), S (H, K, V)).
    `starts` (T,) bool: positions at which S is zeroed first (the
    "kda_state_off" control); None: never."""
    import jax
    import jax.numpy as jnp

    t, heads, kk = q.shape
    if starts is None:
        starts = jnp.zeros((t,), bool)

    def pos(s, row):
        q_p, k_p, v_p, g_p, b_p, start = row
        s = jnp.where(start, 0.0, s)
        s = jnp.exp(g_p)[:, :, None] * s
        u = b_p[:, None] * (v_p - jnp.einsum("hkv,hk->hv", s, k_p))
        s = s + k_p[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_p) * kk ** -0.5

    s, o = jax.lax.scan(
        pos, jnp.zeros((heads, kk, v.shape[-1]), jnp.float32),
        (q, k, v, g, beta, starts))
    return o, s


def _kda(sizes, control, mm, h, lw, starts):
    """The KDA mixer's output (T, hidden) of the normed input h."""
    import jax
    import jax.numpy as jnp

    kh, hd = sizes[-2:]
    t = h.shape[0]
    u = mm(h, lw["kda_in"])  # (T, 3 H K): [q | k | v]
    w = jnp.asarray(lw["kda_conv_w"])  # (3 H K, 4), oldest position first
    if control == "taps_reversed":
        w = w[:, ::-1]
    taps = w.shape[-1]
    acc = w[:, taps - 1] * u
    for j in range(1, taps):
        acc = acc + w[:, taps - 1 - j] * jnp.pad(u[:-j], ((j, 0), (0, 0)))
    qkv = jax.nn.silu(acc).reshape(t, 3, kh, hd)
    q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
    if control != "qk_norm_off":
        q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + L2_EPS)
        k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + L2_EPS)
    lo = mm(h, lw["kda_lo"])  # (T, 2 K): [f_a h | g_a h]
    g = -jnp.exp(jnp.asarray(lw["kda_a_log"]))[:, None] * jax.nn.softplus(
        mm(lo[:, :hd], lw["kda_fb"])
        + jnp.asarray(lw["kda_dt_bias"])).reshape(t, kh, hd)
    if control == "decay_off":
        g = jnp.zeros_like(g)
    beta = jax.nn.sigmoid(mm(h, lw["kda_b"]))  # (T, H)
    if control == "delta_off":
        # u_t = beta_t v_t: S <- Diag(a) S + k (beta v)^T, no correction
        def pos(s, row):
            q_p, k_p, v_p, g_p, b_p = row
            s = jnp.exp(g_p)[:, :, None] * s + k_p[:, :, None] * (
                b_p[:, None] * v_p)[:, None, :]
            return s, jnp.einsum("hkv,hk->hv", s, q_p) * hd ** -0.5

        _, o = jax.lax.scan(pos, jnp.zeros((kh, hd, hd), jnp.float32),
                            (q, k, v, g, beta))
    else:
        o, _ = kda_recurrence(
            q, k, v, g, beta,
            starts if control == "kda_state_off" else None)
    y = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                     + sizes[3]) * jnp.asarray(lw["kda_norm"])
    if control != "out_gate_off":
        y = y * jax.nn.sigmoid(mm(lo[:, hd:], lw["kda_gb"])).reshape(
            t, kh, hd)
    return mm(y.reshape(t, kh * hd), lw["kda_out"])


def _mla(sizes, control, rnd, rnd_att, mm, h, lw):
    """The MLA mixer's output (T, hidden) of the normed input h, in the
    UNabsorbed form: every head's keys and values from the latent."""
    import jax
    import jax.numpy as jnp

    nh, dn, dr, eps, dv, r, theta = (sizes[0], sizes[1], sizes[2], sizes[3],
                                     sizes[4], sizes[5], sizes[6])
    t = h.shape[0]
    q = mm(h, lw["wq"]).reshape(t, nh, dn + dr)
    kv = mm(h, lw["wkv_a"])
    c = _rmsnorm(kv[:, :r], jnp.asarray(lw["rms_kv"]), eps)
    k_pe, q_pe = kv[:, None, r:], q[..., dn:]
    if control == "pe_rotated":
        k_pe, q_pe = _rotate(k_pe, theta), _rotate(q_pe, theta)
    cr, uk = rnd(c, W.dequantize(*lw["w_uk"]))
    k_nope = jnp.einsum("tc,hdc->thd", cr, uk)
    cr, uv = rnd(c, W.dequantize(*lw["w_uv"]))
    v = jnp.einsum("tc,hdc->thd", cr, uv)
    qf = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
    kf = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (t, nh, dr))],
                         axis=-1)
    pos = jnp.arange(t)
    blocks = []
    for q0 in range(0, t, Q_BLOCK):
        qi = pos[q0:q0 + Q_BLOCK]
        qa, ka = rnd_att(qf[q0:q0 + Q_BLOCK], kf)
        s = jnp.einsum("qhd,khd->hqk", qa, ka) * (dn + dr) ** -0.5
        s = jnp.where((pos[None, :] <= qi[:, None])[None], s, -jnp.inf)
        pa, va = rnd_att(jax.nn.softmax(s, axis=-1), v)
        blocks.append(jnp.einsum("hqk,khd->qhd", pa, va))
    att = jnp.concatenate(blocks, axis=0).reshape(t, nh * dv)
    return mm(att, lw["wo"])


def _layer(sizes, precision, kind, x, lw, starts, flip_t):
    """One block on one row: (x, margin). x (T, d); kind = (layer type,
    control); `starts` (T,) bool: the positions that start a dispatch (read
    by "kda_state_off" alone). Whether it is a leading (dense) layer is read
    off its tensors."""
    import jax
    import jax.numpy as jnp

    eps, top, renorm, rscale = sizes[3], sizes[7], sizes[8], sizes[9]
    layer_type, control = kind
    rnd = W.rounder(precision)
    # q80 is the program's rounding of the activations before a WEIGHT matrix
    rnd_att = W.rounder("float32") if precision == "q80" else rnd

    def mm(a, qw):  # a @ W.T with W (out, in) dequantized here
        a, w = rnd(a, W.dequantize(*qw))
        return jnp.einsum("...i,oi->...o", a, w)

    t = x.shape[0]
    h = _rmsnorm(x, jnp.asarray(lw["rms_att"]), eps)
    if layer_type == KDA_LAYER:
        x = x + _kda(sizes, control, mm, h, lw, starts)
    else:
        x = x + _mla(sizes, control, rnd, rnd_att, mm, h, lw)
    y = _rmsnorm(x, jnp.asarray(lw["rms_ffn"]), eps)
    if "w1" in lw:  # a leading layer: the dense FFN, nothing routed
        out = mm(jax.nn.silu(mm(y, lw["w1"])) * mm(y, lw["w3"]), lw["w2"])
        return x + out, jnp.full((t,), jnp.inf, jnp.float32)
    s = jax.nn.sigmoid(mm(y, lw["router"]).astype(jnp.float32))
    sel = s if control == "router_bias_off" else s + jnp.asarray(
        lw["router_bias"])
    order = jnp.argsort(-sel, axis=-1)
    ranked = jnp.take_along_axis(sel, order, axis=-1)
    margin = (ranked[:, top - 1] - ranked[:, top]) / jnp.std(sel, axis=-1)
    if flip_t is not None:
        order = order.at[flip_t, top - 1].set(order[flip_t, top])
    idx = order[:, :top]
    wt = jnp.take_along_axis(s, idx, axis=-1)  # the bias is NOT in them
    if renorm:
        wt = wt / jnp.sum(wt, axis=-1, keepdims=True)
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
    out = out + mm(jax.nn.silu(mm(y, lw["sh_gate"])) * mm(y, lw["sh_up"]),
                   lw["sh_down"])
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
    other = MLA if layer_type == KDA_LAYER else KDA
    return {n: t for n, t in lw.items() if n not in other}


def _sizes(cfg: dict) -> tuple:
    lin = cfg["linear_attn_config"]
    return (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["rms_norm_eps"], cfg["v_head_dim"],
            cfg["kv_lora_rank"], float(cfg["rope_theta"]),
            cfg["num_experts_per_token"], bool(cfg["moe_renormalize"]),
            float(cfg["routed_scaling_factor"]), lin["num_heads"],
            lin["head_dim"])


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
    sizes = _sizes(cfg)
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
                     cfg["rms_norm_eps"]) for x, a in zip(xs, where)])
        packed, scales = weights["wcls"]
        head = _head_fn(precision)
        cuts = np.linspace(0, packed.shape[0], HEAD_SLICES + 1).astype(int)
        out = np.concatenate(
            [np.asarray(head(x, packed[a:b], scales[a:b]), np.float32)
             for a, b in zip(cuts, cuts[1:]) if b > a], axis=1)
    return out, np.concatenate(margins)
