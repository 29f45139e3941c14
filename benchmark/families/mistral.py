"""The family of Mistral-7B and Mixtral-8x7B: which `ModelSpec` the program
is given, which tensors are drawn, and the plain reference, the block graph
in jax.numpy float32. One family for both configurations: the dense block,
and the same block with routed experts where the file has
`num_local_experts`. The harness reaches it through `cells.load_family` and
calls `model_spec`, `tensor_shapes` and `logits_at`, nothing else.

The reference is written from the published equations of both (pre-norm
decoder blocks: RMSNorm, grouped-query attention with rotary embeddings,
SwiGLU feed-forward; Mixtral replaces the feed-forward by a softmax router
over all experts, the top `k` renormalized). No kernel, no
cache, no batching of requests beyond a plain leading axis; every position
attends over the whole sequence before it under a causal mask. It shares no
code with `models/forward.py`, and takes nothing the program has made: the
weights are the benchmark's own seeded blocks (`weights.py`), dequantized
here one layer (one expert) at a time so that it fits beside the engine.

Two departures from the published code, both conventions of the `.m`
checkpoint this system loads and neither a change of the mathematics:
the dense graph rotates interleaved pairs (2k, 2k+1), which equals the
published half-split rotation under the converter's permutation of the
rows of wq and wk; and the experts' matrices are named up/gate/down for
w3/w1/w2.

`precision` is how the controls are made: "float32" is the reference;
"bfloat16", "fp8" and "q80" round both operands of every matrix product to
that type first (q80: the activations to int8 blocks of 32 with one scale,
the program's own Q80), which is what a lower-precision path would compute.
"""

from __future__ import annotations

import numpy as np

from benchmark import weights as W


def model_spec(cfg: dict):
    """The program's ModelSpec for a configuration file's published keys."""
    from distributed_llama_tpu.models.spec import (ArchType, HiddenAct,
                                                   ModelSpec)

    moe = cfg.get("num_local_experts", 0)
    assert cfg.get("hidden_act", "silu") == "silu", cfg.get("hidden_act")
    return ModelSpec(
        arch_type=ArchType.MIXTRAL if moe else ArchType.LLAMA,
        dim=cfg["hidden_size"], hidden_dim=cfg["intermediate_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], vocab_size=cfg["vocab_size"],
        seq_len=cfg["context"], n_experts=moe,
        n_active_experts=cfg.get("num_experts_per_tok", 0),
        hidden_act=HiddenAct.SILU, rope_theta=float(cfg["rope_theta"]),
        norm_eps=cfg.get("rms_norm_eps", 1e-5)).resolved()


def tensor_shapes(cfg: dict) -> dict[str, tuple[tuple[int, ...], bool]]:
    """name -> (shape with the layer axis, drawn as Q40?), from the
    published config's keys. Matrices are (out, in), blocks along `in`."""
    d = cfg["hidden_size"]
    h = cfg["intermediate_size"]
    hs = cfg.get("head_dim") or d // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * hs
    qd = cfg["num_attention_heads"] * hs
    L = cfg["num_hidden_layers"]
    e = cfg.get("num_local_experts", 0)
    shapes = {"wq": ((L, qd, d), True), "wk": ((L, kv, d), True),
              "wv": ((L, kv, d), True), "wo": ((L, d, qd), True)}
    if e:
        shapes.update({"router": ((L, e, d), True),
                       "moe_up": ((L, e, h, d), True),
                       "moe_gate": ((L, e, h, d), True),
                       "moe_down": ((L, e, d, h), True)})
    else:
        shapes.update({"w1": ((L, h, d), True), "w2": ((L, d, h), True),
                       "w3": ((L, h, d), True)})
    shapes.update({"rms_att": ((L, d), False), "rms_ffn": ((L, d), False),
                   "rms_final": ((d,), False),
                   "embedding": ((cfg["vocab_size"], d), False),
                   "wcls": ((cfg["vocab_size"], d), True)})
    return shapes


def _rmsnorm(x, w, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * w


def _rope(x, theta: float, interleaved: bool):
    """x (B, T, heads, hs) at positions 0..T-1."""
    import jax.numpy as jnp

    hs = x.shape[-1]
    freqs = 1.0 / theta ** (np.arange(hs // 2, dtype=np.float64) * 2.0 / hs)
    ang = np.outer(np.arange(x.shape[1], dtype=np.float64), freqs)
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    if interleaved:
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                         axis=-1).reshape(x.shape)
    a, b = x[..., : hs // 2], x[..., hs // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _layer(cfg, x, lw, precision, flip):
    """One block: (x, gap). lw: this layer's host tensors; flip: None or
    (b, t), the position whose last routed expert is swapped for the next
    one (test canary). gap (b, t) is the router's margin at each position:
    the logit of the last expert it takes minus that of the first it leaves,
    over the rms of the layer's router logits; infinite in the dense graph."""
    import jax
    import jax.numpy as jnp

    rnd = W.rounder(precision)

    def mm(a, qw):  # a @ W.T with W (out, in) dequantized here
        a, w = rnd(a, W.dequantize(*qw))
        return jnp.einsum("...i,oi->...o", a, w)

    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hs = cfg.get("head_dim") or cfg["hidden_size"] // nh
    eps = cfg.get("rms_norm_eps", 1e-5)
    b, t, _ = x.shape
    moe = bool(cfg.get("num_local_experts", 0))
    h = _rmsnorm(x, jnp.asarray(lw["rms_att"]), eps)
    q = _rope(mm(h, lw["wq"]).reshape(b, t, nh, hs), cfg["rope_theta"], not moe)
    k = _rope(mm(h, lw["wk"]).reshape(b, t, nkv, hs), cfg["rope_theta"], not moe)
    v = mm(h, lw["wv"]).reshape(b, t, nkv, hs)
    k = jnp.repeat(k, nh // nkv, axis=2)
    v = jnp.repeat(v, nh // nkv, axis=2)
    qa, ka = rnd(q, k)
    s = jnp.einsum("bqhd,bkhd->bhqk", qa, ka) / np.sqrt(hs)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], s, -jnp.inf)
    pa, va = rnd(jax.nn.softmax(s, axis=-1), v)
    att = jnp.einsum("bhqk,bkhd->bqhd", pa, va).reshape(b, t, nh * hs)
    x = x + mm(att, lw["wo"])
    h = _rmsnorm(x, jnp.asarray(lw["rms_ffn"]), eps)
    if not moe:
        y = x + mm(jax.nn.silu(mm(h, lw["w1"])) * mm(h, lw["w3"]), lw["w2"])
        return y, jnp.full((b, t), jnp.inf, jnp.float32)
    n_e, top = cfg["num_local_experts"], cfg["num_experts_per_tok"]
    scores = mm(h, lw["router"])
    ranked = -jnp.sort(-scores, axis=-1)
    gap = (ranked[..., top - 1] - ranked[..., top]) / jnp.sqrt(
        jnp.mean(scores * scores))
    probs = jax.nn.softmax(scores, axis=-1)
    order = jnp.argsort(-probs, axis=-1)
    if flip is not None:
        fb, ft = flip
        order = order.at[fb, ft, top - 1].set(order[fb, ft, top])
    idx = order[..., :top]
    p = jnp.take_along_axis(probs, idx, axis=-1)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    gate = jnp.sum(jax.nn.one_hot(idx, n_e) * p[..., None], axis=-2)  # (b,t,E)

    def expert(out, ew):  # a scan, so one expert is dequantized at a time
        up, gt, down, g = ew
        ye = mm(jax.nn.silu(mm(h, gt)) * mm(h, up), down)
        return out + ye * g[..., None], None

    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(x),
        (lw["moe_up"], lw["moe_gate"], lw["moe_down"],
         jnp.moveaxis(gate, -1, 0)))
    return x + out, gap


_LAYER_FNS: dict = {}


def _layer_fn(cfg: dict, precision: str):
    """One jitted block per (sizes, precision), so a loop over seeds compiles
    it once."""
    import jax

    key = (tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str))
                        and k != "num_hidden_layers")), precision)
    if key not in _LAYER_FNS:
        _LAYER_FNS[key] = jax.jit(
            lambda x, lw, fl: _layer(cfg, x, lw, precision, fl),
            static_argnums=(2,))
    return _LAYER_FNS[key]


def logits_at(cfg: dict, weights: dict, rows, at, precision: str = "float32",
              flip: tuple[int, int, int] | None = None):
    """The logits at the positions `at[i]` of row `i` and nowhere else, and
    each of those positions' smallest router margin over the layers (see
    `_layer`), row after row: (sum of len(at[i]), vocab) float32 and (sum of
    len(at[i]),). `rows` are token ids, each row of its own length; the depth
    is the layer axis of `weights`. flip = (layer, row, t) swaps one routed
    expert. The rows are padded to the longest with token 3 and run as one
    batch: a margin is divided by the rms of a layer's router logits over the
    whole padded batch, and which positions a check judges hangs on that."""
    import jax
    import jax.numpy as jnp

    t = max(len(r) for r in rows)
    tokens = np.full((len(rows), t), 3, np.int64)
    for i, r in enumerate(rows):
        tokens[i, :len(r)] = r
    b_at = np.concatenate([np.full(len(a), i) for i, a in enumerate(at)])
    t_at = np.concatenate([np.asarray(a, np.int64) for a in at])
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(weights["embedding"])[tokens]
        layer_fn = _layer_fn(cfg, precision)
        margin = np.full(len(b_at), np.inf, np.float32)
        for i in range(W.depth(weights)):
            x, gap = layer_fn(x, W.layer(weights, i),
                              (flip[1:] if flip and flip[0] == i else None))
            margin = np.minimum(margin, np.asarray(gap)[b_at, t_at])
        x = _rmsnorm(x[b_at, t_at], jnp.asarray(weights["rms_final"]),
                     cfg.get("rms_norm_eps", 1e-5))
        xr, wr = W.rounder(precision)(x, W.dequantize(*weights["wcls"]))
        out = jnp.einsum("ni,oi->no", xr, wr)
        return np.asarray(out, np.float32), margin
