"""The family of A.X-K1 (SK Telecom; `model_type` `axk1`, whose keys are those
of the published DeepSeek-V3 graph): which `ModelSpec` the program is given,
which tensors are drawn, in which stacks they stand, and the plain
reference, the block graph in jax.numpy float32 in its published, UNabsorbed
form: every head's keys and values are formed from the latent, which the
program never does (it holds one latent row a token and multiplies the
queries through `w_uk`, `models/forward.py _latent_attention`), so the
program is held to the other formulation of the same mathematics.

The layer, input x (T x hidden), eps `rms_norm_eps`, no bias anywhere:

    h          = RMSNorm(x; rms_att)
    q          = wq_b RMSNorm(wq_a h; rms_q)       heads x (nope + rope) wide
    [c ; k_pe] = wkv_a h;  c = RMSNorm(c; rms_kv); k_pe ONE vector, all heads
    k_nope, v  = w_uk c, w_uv c                    per head (kv_b's two halves)
    q_pe, k_pe rotated at the token's position: interleaved pairs (2j, 2j+1),
               YaRN's frequencies over the rotary width
    scores     = [q_nope ; q_pe] . [k_nope ; k_pe] x (nope + rope)^-0.5 x m^2,
               m = 0.1 x mscale_all_dim x ln(factor) + 1; causal; softmax
    x'         = x + wo [heads' sum_j p_j v_j]
    g          = RMSNorm(x'; rms_ffn)
    a leading layer (index < first_k_dense_replace):
        out = x' + w2 (silu(w1 g) * w3 g)          width intermediate_size
    every other layer:
        p   = sigmoid(router g), float32, over `router_width` experts; the
              num_experts_per_tok largest; w = p_top / sum(p_top) (where
              norm_topk_prob) x routed_scaling_factor
        out = x' + sum_e w_e down_e(silu(gate_e g) * up_e g)
                 + sh_down (silu(sh_gate g) * sh_up g)      the shared expert

then a final RMSNorm and an untied head.

Departures from the published description, each where it happens below:
(1) THE SHARE. The file holds `n_routed_experts` of `router_width` experts,
from `expert_offset`, and `vocab_size` rows of the published vocabulary: the
sum over experts runs over the chosen ones that are HELD, `w` is renormalised
over all the chosen, held or not, the shared expert is whole, and what the
absent experts would have added is left out (`_layer`). (2) `topk_method`
"none": no groups, no score-correction bias, the largest of all scores;
`n_group` and `topk_group` are kept in the file and not read. (3) kv_b is
drawn as its two halves by head under the loader's names `w_uk` and `w_uv`,
Q40 blocks along the latent; the program dequantizes them once into its
bfloat16. (4) the harness hands a cut of the weights as depths of the two
stacks; layer i is a leading layer where the `lead` stack holds it.

How it blocks the work: as `families/smallthinker.py`: one layer's tensors on
the device at a time, each row alone, padded with token 3 to a multiple of
32 (64 past 1024), queries in blocks of 1024, one expert dequantized at a
time, the head in slices of the vocabulary. A position's router margin is the
8th largest score minus the 9th, over the rms of its row's scores in that
layer. `precision`: "float32" is the reference; "bfloat16", "fp8" and "q80"
round the operands of every matrix product through `weights.rounder`.
"""

from __future__ import annotations

import functools
import math

import numpy as np

from benchmark import weights as W

Q_BLOCK = 1024
HEAD_SLICES = 8
PAD_TOKEN = 3


def _padded(n: int) -> int:
    step = 64 if n > 1024 else 32
    return -(-n // step) * step


def stacks(cfg: dict) -> list[tuple[str, int]]:
    lead = cfg["first_k_dense_replace"]
    return [("lead", lead), ("blocks", cfg["num_hidden_layers"] - lead)]


def _held(cfg: dict) -> tuple[int, int, int]:
    """(experts held, the router's width, the first expert held)."""
    held = cfg["n_routed_experts"]
    return held, cfg.get("router_width", held), cfg.get("expert_offset", 0)


def _cut(cfg: dict) -> str:
    """What `num_hidden_layers` layers are: "whole" (the file's own depth,
    `layers_here`: both stacks), else a cut of the output check, which the
    harness hands the family as a DEPTH alone: up to `first_k_dense_replace`
    layers are the leading stack's ("lead"), more are expert layers
    ("blocks"). A cut across both stacks cannot be told from its depth: the
    file's cuts are of one stack each."""
    depth = cfg["num_hidden_layers"]
    if depth == cfg["layers_here"]:
        return "whole"
    return "lead" if depth <= cfg["first_k_dense_replace"] else "blocks"


def model_spec(cfg: dict):
    """The program's ModelSpec for the file's keys. A cut of the leading
    stack alone is a dense model of that width, one stack; a cut of expert
    layers alone has no leading stack."""
    from distributed_llama_tpu.models.spec import (ArchType, HiddenAct,
                                                   ModelSpec, RopeType,
                                                   RouterScore)

    if cfg["scoring_func"] != "sigmoid" or cfg["topk_method"] != "none":
        raise ValueError("axk1: the router is a sigmoid with no groups; this "
                         "file says otherwise")
    if cfg["hidden_act"] != "silu" or cfg["moe_layer_freq"] != 1:
        raise ValueError("axk1: SiLU and an expert layer behind every "
                         "leading one; this file says otherwise")
    ys = cfg["rope_scaling"]
    held, width, offset = _held(cfg)
    depth, cut = cfg["num_hidden_layers"], _cut(cfg)
    lead = cfg["first_k_dense_replace"] if cut == "whole" else 0
    routed = {} if cut == "lead" else dict(
        n_experts=held, n_active_experts=cfg["num_experts_per_tok"],
        shared_hidden_dim=cfg["n_shared_experts"]
        * cfg["moe_intermediate_size"],
        router_score=RouterScore.SIGMOID,
        router_renorm=bool(cfg["norm_topk_prob"]),
        router_scale=float(cfg["routed_scaling_factor"]),
        router_width=width, expert_offset=offset)
    return ModelSpec(
        arch_type=ArchType.LLAMA if cut == "lead" else ArchType.MIXTRAL,
        dim=cfg["hidden_size"],
        hidden_dim=cfg["intermediate_size" if cut == "lead"
                       else "moe_intermediate_size"], n_layers=depth,
        n_heads=cfg["num_attention_heads"], n_kv_heads=1,
        vocab_size=cfg["vocab_size"], seq_len=cfg["context"],
        hidden_act=HiddenAct.SILU, rope_theta=float(cfg["rope_theta"]),
        rope_type=RopeType.YARN, norm_eps=cfg["rms_norm_eps"],
        rope_scaling_factor=float(ys["factor"]),
        rope_scaling_orig_max_seq_len=ys["original_max_position_embeddings"],
        yarn_beta_fast=float(ys["beta_fast"]),
        yarn_beta_slow=float(ys["beta_slow"]), yarn_mscale=float(ys["mscale"]),
        yarn_mscale_all_dim=float(ys["mscale_all_dim"]),
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], lead_layers=lead,
        lead_hidden_dim=cfg["intermediate_size"] if lead else 0,
        **routed).resolved()


def tensor_shapes(cfg: dict) -> dict[str, tuple[tuple[int, ...], bool]]:
    """name -> (shape with the layer axis, drawn as Q40?), matrices (out,
    in), under the program's loader's names (`models/params.py
    block_tensor_shapes`), `lead.` and `blocks.` ahead of a stack's."""
    # a program that cannot state this model fails here, before the weights
    # are drawn: the run then ends in a second with the import's message
    from distributed_llama_tpu.models.spec import RouterScore  # noqa: F401

    d, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    ql, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    held, width, _ = _held(cfg)
    h, sh = cfg["moe_intermediate_size"], (cfg["n_shared_experts"]
                                           * cfg["moe_intermediate_size"])
    v = cfg["vocab_size"]
    out = {"rms_final": ((d,), False), "embedding": ((v, d), False),
           "wcls": ((v, d), True)}
    for prefix, n in stacks(cfg):
        own = {"wq_a": ((n, ql, d), True), "wq_b": ((n, nh * (dn + dr), ql), True),
               "wkv_a": ((n, r + dr, d), True), "w_uk": ((n, nh, dn, r), True),
               "w_uv": ((n, nh, dv, r), True), "wo": ((n, d, nh * dv), True),
               "rms_att": ((n, d), False), "rms_ffn": ((n, d), False),
               "rms_q": ((n, ql), False), "rms_kv": ((n, r), False)}
        if prefix == "lead":
            f = cfg["intermediate_size"]
            own.update({"w1": ((n, f, d), True), "w2": ((n, d, f), True),
                        "w3": ((n, f, d), True)})
        else:
            own.update({"router": ((n, width, d), True),
                        "moe_up": ((n, held, h, d), True),
                        "moe_gate": ((n, held, h, d), True),
                        "moe_down": ((n, held, d, h), True),
                        "sh_gate": ((n, sh, d), True),
                        "sh_down": ((n, d, sh), True),
                        "sh_up": ((n, sh, d), True)})
        out.update({f"{prefix}.{name}": s for name, s in own.items()})
    return out


def program_params(cfg: dict, weights: dict):
    """One entry a stack, as the program's loader returns them: `lead` and
    `blocks` (a stack a cut left empty is not handed over)."""
    from distributed_llama_tpu.quants import FloatType, QTensor

    def q(t):
        return QTensor(FloatType.Q40, *t) if isinstance(t, tuple) else t

    out = {n: q(weights[n]) for n in W.NOT_BLOCKS}
    held = [p for p, depth in W.stack_depths(weights, cfg).items() if depth]
    for prefix in held:
        # one stack alone is the program's one stack, whichever it is
        out[prefix if len(held) > 1 else "blocks"] = {
            n.split(".", 1)[1]: q(t) for n, t in weights.items()
            if n.startswith(prefix + ".")}
    return out


def _rmsnorm(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def yarn_inv_freq(cfg: dict) -> np.ndarray:
    """YaRN's frequencies over the rotary width, float64."""
    dim, ys = cfg["qk_rope_head_dim"], cfg["rope_scaling"]
    theta, orig = float(cfg["rope_theta"]), ys["original_max_position_embeddings"]
    f = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def turns(beta):
        return dim * math.log(orig / (beta * 2 * math.pi)) / (2 * math.log(theta))

    lo = max(math.floor(turns(ys["beta_fast"])), 0)
    hi = min(math.ceil(turns(ys["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 0.001), 0, 1)
    return f / ys["factor"] * ramp + f * (1 - ramp)


def softmax_scale(cfg: dict) -> float:
    ys = cfg["rope_scaling"]
    m = 0.1 * ys["mscale_all_dim"] * math.log(ys["factor"]) + 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def _rotate(x, inv_freq):
    """x (T, heads, rope) at positions 0..T-1: element 2j against 2j + 1."""
    import jax.numpy as jnp

    ang = np.outer(np.arange(x.shape[0], dtype=np.float64), inv_freq)
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def _layer(sizes, precision, x, lw, flip_t):
    """One block on one row: (x, margin). x (T, d); whether it is a leading
    (dense) layer is read off its tensors."""
    import jax
    import jax.numpy as jnp

    (nh, dn, dr, dv, r, top, eps, scale, inv_freq, renorm, rscale, held,
     offset) = sizes
    rnd = W.rounder(precision)
    rnd_att = W.rounder("float32") if precision == "q80" else rnd

    def mm(a, qw):  # a @ W.T with W (out, in) dequantized here
        a, w = rnd(a, W.dequantize(*qw))
        return jnp.einsum("...i,oi->...o", a, w)

    t = x.shape[0]
    h = _rmsnorm(x, jnp.asarray(lw["rms_att"]), eps)
    q = mm(_rmsnorm(mm(h, lw["wq_a"]), jnp.asarray(lw["rms_q"]), eps),
           lw["wq_b"]).reshape(t, nh, dn + dr)
    kv = mm(h, lw["wkv_a"])
    c = _rmsnorm(kv[:, :r], jnp.asarray(lw["rms_kv"]), eps)
    freqs = np.asarray(inv_freq, np.float64)
    k_pe = _rotate(kv[:, None, r:], freqs)  # (T, 1, dr): one for all heads
    q_pe = _rotate(q[..., dn:], freqs)
    # the UNabsorbed form: every head's keys and values from the latent
    cr, uk = rnd(c, W.dequantize(*lw["w_uk"]))
    k_nope = jnp.einsum("tc,hdc->thd", cr, uk)
    cr, uv = rnd(c, W.dequantize(*lw["w_uv"]))
    v = jnp.einsum("tc,hdc->thd", cr, uv)
    qf = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
    kf = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (t, nh, dr))], axis=-1)
    pos = jnp.arange(t)
    blocks = []
    for q0 in range(0, t, Q_BLOCK):
        qi = pos[q0:q0 + Q_BLOCK]
        qa, ka = rnd_att(qf[q0:q0 + Q_BLOCK], kf)
        s = jnp.einsum("qhd,khd->hqk", qa, ka) * scale
        s = jnp.where((pos[None, :] <= qi[:, None])[None], s, -jnp.inf)
        pa, va = rnd_att(jax.nn.softmax(s, axis=-1), v)
        blocks.append(jnp.einsum("hqk,khd->qhd", pa, va))
    att = jnp.concatenate(blocks, axis=0).reshape(t, nh * dv)
    x = x + mm(att, lw["wo"])
    g = _rmsnorm(x, jnp.asarray(lw["rms_ffn"]), eps)
    if "w1" in lw:  # a leading layer: the dense FFN, nothing routed
        y = mm(jax.nn.silu(mm(g, lw["w1"])) * mm(g, lw["w3"]), lw["w2"])
        return x + y, jnp.full((t,), jnp.inf, jnp.float32)
    p = jax.nn.sigmoid(mm(g, lw["router"]).astype(jnp.float32))
    order = jnp.argsort(-p, axis=-1)
    ranked = jnp.take_along_axis(p, order, axis=-1)
    margin = (ranked[:, top - 1] - ranked[:, top]) / jnp.sqrt(jnp.mean(p * p))
    if flip_t is not None:
        order = order.at[flip_t, top - 1].set(order[flip_t, top])
    idx = order[:, :top]
    w = jnp.take_along_axis(p, idx, axis=-1)
    if renorm:  # over ALL the chosen, held here or not
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * rscale
    # departure (1): a token's weight on each HELD expert; a chosen expert
    # outside [offset, offset + held) adds nothing here
    share = jnp.sum(jax.nn.one_hot(idx - offset, held) * w[..., None], axis=-2)

    def expert(out, ew):  # a scan, so one expert is dequantized at a time
        up, gate, down, we = ew
        y = mm(jax.nn.silu(mm(g, gate)) * mm(g, up), down)
        return out + y * we[:, None], None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                          (lw["moe_up"], lw["moe_gate"], lw["moe_down"],
                           share.T))
    shared = mm(jax.nn.silu(mm(g, lw["sh_gate"])) * mm(g, lw["sh_up"]),
                lw["sh_down"])
    return x + out + shared, margin


@functools.lru_cache(maxsize=None)
def _layer_fn(sizes: tuple, precision: str):
    import jax

    return jax.jit(functools.partial(_layer, sizes, precision))


@functools.lru_cache(maxsize=None)
def _head_fn(precision: str):
    import jax
    import jax.numpy as jnp

    def head(x, packed, scales):
        xr, wr = W.rounder(precision)(x, W.dequantize(packed, scales))
        return jnp.einsum("ni,oi->no", xr, wr)

    return jax.jit(head)


def _sizes(cfg: dict) -> tuple:
    held, _, offset = _held(cfg)
    return (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"],
            cfg["num_experts_per_tok"], cfg["rms_norm_eps"],
            softmax_scale(cfg), tuple(yarn_inv_freq(cfg).tolist()),
            bool(cfg["norm_topk_prob"]), float(cfg["routed_scaling_factor"]),
            held, offset)


def logits_at(cfg: dict, weights: dict, rows, at, precision: str = "float32",
              flip: tuple[int, int, int] | None = None):
    """The logits at the positions `at[i]` of row `i` and each of those
    positions' smallest router margin over the expert layers of `weights`,
    row after row: (sum of len(at[i]), vocab) float32 and (sum of
    len(at[i]),). flip = (layer, row, t) swaps one routed expert."""
    import jax
    import jax.numpy as jnp

    layer_fn = _layer_fn(_sizes(cfg), precision)
    where = [np.asarray(a, np.int64) for a in at]
    with jax.default_matmul_precision("highest"):
        xs = [jnp.asarray(weights["embedding"][np.asarray(
            list(r) + [PAD_TOKEN] * (_padded(len(r)) - len(r)))])
            for r in rows]
        margins = [np.full(len(a), np.inf, np.float32) for a in where]
        for i in range(W.depth(weights, cfg)):
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
