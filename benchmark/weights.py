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
(`families/<family>.py`: `tensor_shapes`), and so is where its layers
stand. A family that says nothing keeps them all in ONE stack: three tensors
stand outside it (`NOT_BLOCKS`) and every other one has the layer axis
first. A family whose leading layers hold other tensors than the rest
(a dense layer ahead of expert layers) defines two optional functions,
which this module asks for by name:

    stacks(cfg)                   [(prefix, depth), ...] in layer order; a
                                  tensor named `<prefix>.<name>` lies in
                                  that stack, layer axis first, every other
                                  one outside all stacks; the depths add up
                                  to `num_hidden_layers`
    program_params(cfg, weights)  the structure the program is handed for
                                  these weights (a cut of them too)

A layer index is global over the stacks in their order: `depth`, `layer` and
`layer_cut` take the configuration as `cfg` and read each stack's depth off
the weights given, so a cut of a cut works; without `cfg` they mean the one
unnamed stack, and raise on weights whose tensors name a stack. `dequantize` is the reference's own reading of the blocks,
sharing no code with the program's `quants.py`; `rounder` is how every
family's reference makes its controls.
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

    _stacks(cfg)  # a family whose stacks do not add up fails here
    shapes = cells.load_family(cfg["family"]).tensor_shapes(cfg)
    drawn = jax.jit(lambda k: _draw(k, shapes))(_seed_key(seed))
    host = jax.tree.map(np.asarray, drawn)
    del drawn
    return host


def _stacks(cfg: dict | None):
    """The family's `stacks(cfg)`, or None where it defines none (or no
    configuration is given): one unnamed stack of every tensor but
    `NOT_BLOCKS`."""
    if cfg is None:
        return None
    fn = getattr(cells.load_family(cfg["family"]), "stacks", None)
    if fn is None:
        return None
    stacks = [(str(p), int(d)) for p, d in fn(cfg)]
    total = sum(d for _, d in stacks)
    if total != cfg["num_hidden_layers"]:
        raise ValueError(
            f"family {cfg['family']}: the stacks {stacks} hold {total} "
            f"layers, num_hidden_layers is {cfg['num_hidden_layers']}")
    return stacks


def _layers_of(t) -> int:
    return (t[0] if isinstance(t, tuple) else t).shape[0]


def stack_depths(weights: dict, cfg: dict | None = None) -> dict[str, int]:
    """prefix -> the layers `weights` hold of that stack, in layer order
    (read off the first tensor of each); "" is the one unnamed stack."""
    stacks = _stacks(cfg)
    if stacks is None:
        named = sorted({n.split(".", 1)[0] for n in weights if "." in n})
        if named:  # one set of indices would cut every stack alike
            raise ValueError(
                f"these weights lie in the stacks {named}: give the "
                "configuration (`cfg`), whose family says how deep each is")
        return {"": _layers_of(next(t for n, t in weights.items()
                                    if n not in NOT_BLOCKS))}
    return {p: _layers_of(next(t for n, t in weights.items()
                               if n.startswith(p + ".")))
            for p, _ in stacks}


def stack_of(name: str, depths: dict[str, int]) -> str | None:
    """The stack tensor `name` lies in, None for one outside all stacks."""
    if "" in depths:
        return None if name in NOT_BLOCKS else ""
    prefix = name.split(".", 1)[0]
    return prefix if "." in name and prefix in depths else None


def depth(weights: dict, cfg: dict | None = None) -> int:
    """The layers in `weights`, over all stacks."""
    return sum(stack_depths(weights, cfg).values())


def layer(weights: dict, i: int, cfg: dict | None = None) -> dict:
    """Layer `i`'s own tensors, without the layer axis and without their
    stack's prefix: what a reference that walks the layers hands its block."""
    depths = stack_depths(weights, cfg)
    start = 0
    for prefix, n in depths.items():
        if start <= i < start + n:
            k, bare = i - start, len(prefix) + 1 if prefix else 0
            return {name[bare:]: (tuple(a[k] for a in t)
                                  if isinstance(t, tuple) else t[k])
                    for name, t in weights.items()
                    if stack_of(name, depths) == prefix}
        start += n
    raise IndexError(f"layer {i} of {start}")


def layer_cut(weights: dict, layers: list[int],
              cfg: dict | None = None) -> dict:
    """The same weights with only `layers`, each stack cut by its own share
    of the (global) indices; a stack may be left with no layer."""
    depths = stack_depths(weights, cfg)
    total = sum(depths.values())
    if not all(0 <= i < total for i in layers):
        raise IndexError(f"cut {list(layers)} of {total} layers")
    idx, start = {}, 0
    for prefix, n in depths.items():
        idx[prefix] = np.asarray([i - start for i in layers
                                  if start <= i < start + n], np.int64)
        start += n
    out = {}
    for name, t in weights.items():
        stack = stack_of(name, depths)
        if stack is None:
            out[name] = t
        elif isinstance(t, tuple):
            out[name] = (t[0][idx[stack]], t[1][idx[stack]])
        else:
            out[name] = t[idx[stack]]
    return out


def mis_scaled(weights: dict, name: str, factor: float, layer: int = 0) -> dict:
    """`weights` with the scales of matrix `name` in one layer (of the
    tensor's own stack) off by `factor`: what a path that decodes its scales
    wrongly would compute."""
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


def to_program_params(weights: dict, cfg: dict | None = None):
    """The drawn tensors in the structure the program's loader returns
    (`formats.mfile.load_model`): QTensor leaves in the planar Q40 layout.
    A family that defines `program_params(cfg, weights)` says it itself
    (and may call this without `cfg` on the tensors re-laid into one
    stack)."""
    from distributed_llama_tpu.quants import FloatType, QTensor

    own = cfg and getattr(cells.load_family(cfg["family"]), "program_params",
                          None)
    if own:
        return own(cfg, weights)

    def q(t):
        return QTensor(FloatType.Q40, t[0], t[1])

    blocks = {n: (q(t) if isinstance(t, tuple) else t)
              for n, t in weights.items() if n not in NOT_BLOCKS}
    return {"embedding": weights["embedding"], "blocks": blocks,
            "rms_final": weights["rms_final"], "wcls": q(weights["wcls"])}
