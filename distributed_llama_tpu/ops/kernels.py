"""Elementwise / normalization ops.

TPU-native equivalents of the reference SIMD kernel layer (src/funcs.{hpp,cpp}): rmsnorm
(funcs.cpp rms+rmsnorm, eps=1e-5, reduction in f32), softmax, SiLU, tanh-GELU
(funcs.cpp:498-517). On TPU these are VPU ops that XLA fuses into surrounding matmuls, so
each is a plain jnp expression — no hand scheduling.
"""

import jax.numpy as jnp

RMS_EPS = 1e-5  # reference: funcs.cpp rms() `ss += 1e-5f`


def rmsnorm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = RMS_EPS) -> jnp.ndarray:
    """RMS-normalize the last axis; reduction in f32 regardless of activation dtype."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    inv = jnp.reciprocal(jnp.sqrt(ms + eps))
    return (weight.astype(jnp.float32) * (xf * inv)).astype(x.dtype)


def silu(x: jnp.ndarray) -> jnp.ndarray:
    """x * sigmoid(x) (reference: funcs.cpp:510-517)."""
    xf = x.astype(jnp.float32)
    return (xf / (1.0 + jnp.exp(-xf))).astype(x.dtype)


def gelu_tanh(x: jnp.ndarray) -> jnp.ndarray:
    """tanh-approximation GELU, coefficients as in reference funcs.cpp:498-508."""
    xf = x.astype(jnp.float32)
    c = 0.79788456080286535587989211986876  # sqrt(2/pi)
    out = 0.5 * xf * (1.0 + jnp.tanh(c * xf * (1.0 + 0.044715 * xf * xf)))
    return out.astype(x.dtype)


def relu(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.maximum(x, 0)


# gate activations by the static names the fused epilogues take
# (ops/pallas_moe_grouped.py _act_f32 matches these formulas in f32)
ACTS = {"silu": silu, "gelu_tanh": gelu_tanh, "relu": relu}


def masked_softmax(scores: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Numerically-stable softmax over the last axis with a boolean validity mask.

    Reference softmax (funcs.cpp:64-93) subtracts the max; here invalid lanes are driven to
    -inf before the max so fully-masked rows still produce zeros (not NaN).
    """
    neg = jnp.finfo(jnp.float32).min
    s = jnp.where(mask, scores.astype(jnp.float32), neg)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    e = jnp.where(mask, e, 0.0)
    denom = jnp.sum(e, axis=-1, keepdims=True)
    return e / jnp.maximum(denom, 1e-30)
