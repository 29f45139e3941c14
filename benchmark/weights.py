"""Seeded Q40 weights, made on the device in one jitted call.

The tensors are drawn directly as Q40 blocks in the planar layout of the
`.m` checkpoint (the layout `BatchEngine` is given by the program's own
loader): packed nibbles uint8 (..., n/32, 16), byte j of a block holding
element j in its low nibble and element j+16 in its high nibble, and one
float16 scale per block; a weight is (nibble - 8) * scale. Nibble 0 is
remapped to 8: uniform nibbles 0..15 have mean -0.5, every matrix is then
rank one plus noise, and bf16 rounding alone flips the sign of the logits
(PERF.md, PR 21). Scales centre on 0.02 / 4.3, so weights have a standard
deviation near 0.02. No checkpoint file is written or read.

`dequantize` is the reference's own reading of those blocks; it shares no
code with the program's `quants.py`.
"""

from __future__ import annotations

import numpy as np

QK = 32
_DELTA = 0.02 / 4.3
_SEED_MOD = 2**31 - 1


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


def _seed_key(seed: int):
    import jax

    # --seed may exceed 32 signed bits: split it instead of truncating
    return jax.random.fold_in(jax.random.key(seed % _SEED_MOD),
                              seed // _SEED_MOD)


def _draw(key, shapes):
    import jax
    import jax.numpy as jnp

    out = {}
    for i, (name, (shape, quant)) in enumerate(sorted(shapes.items())):
        k = jax.random.fold_in(key, i)
        if quant:
            kb, ks = jax.random.split(k)
            nb = shape[-1] // QK
            packed = jax.random.bits(kb, (*shape[:-1], nb, QK // 2),
                                     jnp.uint8)
            packed = packed | (((packed & 0x0F) == 0).astype(jnp.uint8) << 3)
            packed = packed | (((packed & 0xF0) == 0).astype(jnp.uint8) << 7)
            scales = ((jax.random.uniform(ks, (*shape[:-1], nb), jnp.float32)
                       + 0.5) * _DELTA).astype(jnp.float16)
            out[name] = (packed, scales)
        elif name == "embedding":
            out[name] = jax.random.normal(k, shape, jnp.float32) * 0.02
        else:
            out[name] = 1.0 + 0.02 * jax.random.normal(k, shape, jnp.float32)
    return out


def make_weights(cfg: dict, seed: int) -> dict:
    """The whole model on the host as numpy, drawn on the device in one
    jitted call: {name: (packed, scales)} for Q40 matrices, {name: array}
    for norms and the embedding. The host copy is what the program's loader
    would hand `BatchEngine`, and what the reference dequantizes."""
    import jax

    shapes = tensor_shapes(cfg)
    drawn = jax.jit(lambda k: _draw(k, shapes))(_seed_key(seed))
    host = jax.tree.map(np.asarray, drawn)
    del drawn
    return host


def layer_cut(weights: dict, layers: list[int]) -> dict:
    """The same weights with only `layers` of the block stack."""
    idx = np.asarray(layers)
    out = {}
    for name, t in weights.items():
        if name in ("embedding", "rms_final", "wcls"):
            out[name] = t
        elif isinstance(t, tuple):
            out[name] = (t[0][idx], t[1][idx])
        else:
            out[name] = t[idx]
    return out


def mis_scaled(weights: dict, name: str, factor: float, layer: int = 0) -> dict:
    """`weights` with the scales of matrix `name` in one layer off by
    `factor`: what a path that decodes its scales wrongly would compute."""
    packed, scales = weights[name]
    scales = scales.copy()
    scales[layer] = (scales[layer].astype(np.float32) * factor).astype(
        scales.dtype)
    return {**weights, name: (packed, scales)}


def dequantize(packed, scales):
    """Planar Q40 blocks -> float32 (..., n), on whatever device holds them."""
    import jax.numpy as jnp

    packed = jnp.asarray(packed)
    lo = (packed & 0x0F).astype(jnp.int32) - 8
    hi = (packed >> 4).astype(jnp.int32) - 8
    vals = jnp.concatenate([lo, hi], axis=-1).astype(jnp.float32)
    out = vals * jnp.asarray(scales).astype(jnp.float32)[..., None]
    return out.reshape(*packed.shape[:-2], packed.shape[-2] * QK)


def to_program_params(weights: dict):
    """The drawn tensors in the structure the program's loader returns
    (`formats.mfile.load_model`): QTensor leaves in the planar Q40 layout."""
    from distributed_llama_tpu.quants import FloatType, QTensor

    def q(t):
        return QTensor(FloatType.Q40, t[0], t[1])

    blocks = {n: (q(t) if isinstance(t, tuple) else t)
              for n, t in weights.items()
              if n not in ("embedding", "rms_final", "wcls")}
    return {"embedding": weights["embedding"], "blocks": blocks,
            "rms_final": weights["rms_final"], "wcls": q(weights["wcls"])}
