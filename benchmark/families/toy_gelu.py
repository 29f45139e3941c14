"""A toy family, never a cell: routed experts gated by tanh-GELU, which the
program runs as `HiddenAct.GELU` (`models/forward.py:_act`). It exists to
show that a family is files only: this file, `configs/tiny-gelu-moe.json`
and `tests/test_family_toy_gelu.py` were added without a line of `run.py`,
`probe.py`, `check.py` or `weights.py` changing.

The block: pre-norm decoder, RMSNorm, grouped-query attention with rotary
embeddings (half-split rotation, every layer, no window), then a softmax
router over all experts behind the second norm, the top `k` renormalized,
each expert `down(gelu_tanh(gate h) * (up h))`. Plain jax.numpy float32,
no kernel, no cache; it shares no code with `models/forward.py` nor with
`families/mistral.py`. How it blocks the work: one row at a time at the
row's own length, nothing padded, one expert dequantized at a time; a
position's router margin is divided by the rms of its own row's router
logits in that layer.
"""

from __future__ import annotations

import functools

import numpy as np

from benchmark import weights as W


def model_spec(cfg: dict):
    """The program's ModelSpec for the configuration file's keys."""
    from distributed_llama_tpu.models.spec import (ArchType, HiddenAct,
                                                   ModelSpec)

    if cfg["hidden_act"] != "gelu_pytorch_tanh":
        raise ValueError(f"toy_gelu: hidden_act {cfg['hidden_act']!r}")
    return ModelSpec(
        arch_type=ArchType.MIXTRAL, dim=cfg["hidden_size"],
        hidden_dim=cfg["intermediate_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], vocab_size=cfg["vocab_size"],
        seq_len=cfg["context"], n_experts=cfg["num_local_experts"],
        n_active_experts=cfg["num_experts_per_tok"],
        hidden_act=HiddenAct.GELU, rope_theta=float(cfg["rope_theta"]),
        norm_eps=cfg["rms_norm_eps"]).resolved()


def tensor_shapes(cfg: dict) -> dict[str, tuple[tuple[int, ...], bool]]:
    """name -> (shape with the layer axis, drawn as Q40?); matrices are
    (out, in). The program's names for a block with routed experts."""
    d, h = cfg["hidden_size"], cfg["intermediate_size"]
    qd = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    L, e, v = (cfg["num_hidden_layers"], cfg["num_local_experts"],
               cfg["vocab_size"])
    return {"wq": ((L, qd, d), True), "wk": ((L, kv, d), True),
            "wv": ((L, kv, d), True), "wo": ((L, d, qd), True),
            "router": ((L, e, d), True), "moe_up": ((L, e, h, d), True),
            "moe_gate": ((L, e, h, d), True), "moe_down": ((L, e, d, h), True),
            "rms_att": ((L, d), False), "rms_ffn": ((L, d), False),
            "rms_final": ((d,), False), "embedding": ((v, d), False),
            "wcls": ((v, d), True)}


def _rmsnorm(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta: float):
    """x (T, heads, hs) at positions 0..T-1, halves rotated against each
    other."""
    import jax.numpy as jnp

    hs = x.shape[-1]
    freqs = 1.0 / theta ** (np.arange(hs // 2, dtype=np.float64) * 2.0 / hs)
    ang = np.outer(np.arange(x.shape[0], dtype=np.float64), freqs)
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    a, b = x[..., : hs // 2], x[..., hs // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _layer(sizes, precision, x, lw, flip_t):
    """One block on one row: (x, margin). x (T, d); flip_t: None or the
    position whose last routed expert is swapped for the next one. margin
    (T,): the router logit of the last expert taken minus that of the first
    one left, over the rms of the row's router logits."""
    import jax
    import jax.numpy as jnp

    nh, nkv, hs, top, theta, eps = sizes
    rnd = W.rounder(precision)

    def mm(a, qw):  # a @ W.T with W (out, in) dequantized here
        a, w = rnd(a, W.dequantize(*qw))
        return jnp.einsum("...i,oi->...o", a, w)

    t = x.shape[0]
    h = _rmsnorm(x, jnp.asarray(lw["rms_att"]), eps)
    q = _rope(mm(h, lw["wq"]).reshape(t, nh, hs), theta)
    k = _rope(mm(h, lw["wk"]).reshape(t, nkv, hs), theta)
    v = mm(h, lw["wv"]).reshape(t, nkv, hs)
    k, v = (jnp.repeat(a, nh // nkv, axis=1) for a in (k, v))
    qa, ka = rnd(q, k)
    s = jnp.einsum("qhd,khd->hqk", qa, ka) / np.sqrt(hs)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    pa, va = rnd(jax.nn.softmax(s, axis=-1), v)
    x = x + mm(jnp.einsum("hqk,khd->qhd", pa, va).reshape(t, nh * hs),
               lw["wo"])
    h = _rmsnorm(x, jnp.asarray(lw["rms_ffn"]), eps)
    scores = mm(h, lw["router"])
    order = jnp.argsort(-scores, axis=-1)
    ranked = jnp.take_along_axis(scores, order, axis=-1)
    margin = (ranked[:, top - 1] - ranked[:, top]) / jnp.sqrt(
        jnp.mean(scores * scores))
    if flip_t is not None:
        order = order.at[flip_t, top - 1].set(order[flip_t, top])
    idx = order[:, :top]
    p = jax.nn.softmax(jnp.take_along_axis(scores, idx, axis=-1), axis=-1)
    share = jnp.sum(jax.nn.one_hot(idx, scores.shape[-1]) * p[..., None],
                    axis=-2)  # (T, E): a token's weight on each expert

    def expert(out, ew):  # a scan, so one expert is dequantized at a time
        up, gate, down, g = ew
        act = jax.nn.gelu(mm(h, gate), approximate=True)
        return out + mm(act * mm(h, up), down) * g[:, None], None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                          (lw["moe_up"], lw["moe_gate"], lw["moe_down"],
                           share.T))
    return x + out, margin


@functools.lru_cache(maxsize=None)
def _layer_fn(sizes: tuple, precision: str):
    import jax

    return jax.jit(functools.partial(_layer, sizes, precision),
                   static_argnums=(2,))


def logits_at(cfg: dict, weights: dict, rows, at, precision: str = "float32",
              flip: tuple[int, int, int] | None = None):
    """The logits at the positions `at[i]` of row `i` and each of those
    positions' smallest router margin over the layers of `weights`, row
    after row: (sum of len(at[i]), vocab) float32 and (sum of len(at[i]),).
    flip = (layer, row, t) swaps one routed expert."""
    import jax
    import jax.numpy as jnp

    layer_fn = _layer_fn(
        (cfg["num_attention_heads"], cfg["num_key_value_heads"],
         cfg["head_dim"], cfg["num_experts_per_tok"],
         float(cfg["rope_theta"]), cfg["rms_norm_eps"]), precision)
    out, margins = [], []
    with jax.default_matmul_precision("highest"):
        head = W.dequantize(*weights["wcls"])
        for r, (row, where) in enumerate(zip(rows, at)):
            where = np.asarray(where, np.int64)
            x = jnp.asarray(weights["embedding"])[np.asarray(row)]
            margin = np.full(len(where), np.inf, np.float32)
            for i in range(W.depth(weights)):
                flip_t = flip[2] if flip and flip[:2] == (i, r) else None
                x, m = layer_fn(x, W.layer(weights, i), flip_t)
                margin = np.minimum(margin, np.asarray(m)[where])
            x = _rmsnorm(x[where], jnp.asarray(weights["rms_final"]),
                         cfg["rms_norm_eps"])
            xr, wr = W.rounder(precision)(x, head)
            out.append(np.asarray(jnp.einsum("ni,oi->no", xr, wr), np.float32))
            margins.append(margin)
    return np.concatenate(out), np.concatenate(margins)
