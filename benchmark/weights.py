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

Which tensors a model has is its family's business
(`families/<family>.py`: `tensor_shapes`). What every family means the same
by is here: three tensors stand outside the block stack (`NOT_BLOCKS`) and
every other one has the layer axis first; `dequantize` is the reference's
own reading of the blocks, sharing no code with the program's `quants.py`;
`rounder` is how every family's reference makes its controls.
"""

from __future__ import annotations

import numpy as np

from benchmark import cells

QK = 32
NOT_BLOCKS = ("embedding", "rms_final", "wcls")
_DELTA = 0.02 / 4.3
_SEED_MOD = 2**31 - 1


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

    shapes = cells.load_family(cfg["family"]).tensor_shapes(cfg)
    drawn = jax.jit(lambda k: _draw(k, shapes))(_seed_key(seed))
    host = jax.tree.map(np.asarray, drawn)
    del drawn
    return host


def depth(weights: dict) -> int:
    """The layers in `weights`: the leading axis of any block tensor."""
    t = next(t for n, t in weights.items() if n not in NOT_BLOCKS)
    return (t[0] if isinstance(t, tuple) else t).shape[0]


def layer(weights: dict, i: int) -> dict:
    """Layer `i`'s tensors alone, without the layer axis: what a reference
    that walks the block stack hands its block."""
    return {n: (tuple(a[i] for a in t) if isinstance(t, tuple) else t[i])
            for n, t in weights.items() if n not in NOT_BLOCKS}


def layer_cut(weights: dict, layers: list[int]) -> dict:
    """The same weights with only `layers` of the block stack."""
    idx = np.asarray(layers)
    out = {}
    for name, t in weights.items():
        if name in NOT_BLOCKS:
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


def rounder(precision: str):
    """How a reference makes a control: "float32" rounds nothing;
    "bfloat16" and "fp8" round both operands of a matrix product to that
    type first; "q80" rounds the activations before a weight matrix to int8
    blocks of 32 with one scale, the program's own Q80. What a path in that
    lower precision would compute."""
    import jax.numpy as jnp

    if precision == "float32":
        return lambda x, w: (x, w)
    if precision == "bfloat16":
        t = jnp.bfloat16
    elif precision == "fp8":
        t = jnp.float8_e4m3fn
    elif precision == "q80":
        def q80(x):
            g = x.reshape(*x.shape[:-1], x.shape[-1] // 32, 32)
            amax = jnp.max(jnp.abs(g), axis=-1, keepdims=True)
            d = (amax / 127.0).astype(jnp.float16).astype(jnp.float32)
            q = jnp.round(g * jnp.where(amax > 0, 127.0 / amax, 0.0))
            return (q * d).reshape(x.shape)
        return lambda x, w: (q80(x), w) if w.ndim == 2 else (x, w)
    else:
        raise ValueError(f"unknown precision {precision!r}")
    return lambda x, w: (x.astype(t).astype(jnp.float32),
                         w.astype(t).astype(jnp.float32))


def to_program_params(weights: dict):
    """The drawn tensors in the structure the program's loader returns
    (`formats.mfile.load_model`): QTensor leaves in the planar Q40 layout."""
    from distributed_llama_tpu.quants import FloatType, QTensor

    def q(t):
        return QTensor(FloatType.Q40, t[0], t[1])

    blocks = {n: (q(t) if isinstance(t, tuple) else t)
              for n, t in weights.items() if n not in NOT_BLOCKS}
    return {"embedding": weights["embedding"], "blocks": blocks,
            "rms_final": weights["rms_final"], "wcls": q(weights["wcls"])}
