"""Rotary position embeddings — the three styles of the reference (src/commands.cpp:140-257),
and YaRN (ROPE_YARN: interleaved pairs over a head's rotary part, the frequencies scaled by
parts, `_yarn_scale_freqs`; its attention scale is `ModelSpec.attn_scale`; ROPE_YARN_NEOX: the
same frequencies over half-split pairs).

A table is as wide as the rotary part (`ModelSpec.rope_width`): where that is under the head
size (`rotary_dim`, a partial rotary factor) `apply_rope` rotates a head's first values and
passes the rest. A model with kinds of layer (`ModelSpec.kinds`) has a table a kind, stacked
on a leading axis and padded to the widest (`RopeTables.of_kind` cuts one out).

- ROPE_LLAMA: interleaved pairs (2k, 2k+1), freq_k = theta^(-2k/head_size), precomputed
  cos/sin tables over the full sequence (LlamaRopeCommand, commands.cpp:140-179).
- ROPE_LLAMA3_1: same rotation with Llama-3.1 frequency-dependent NTK scaling. NOTE: the
  reference (Llama3_1RopeCommand::forward, commands.cpp:207-227) applies `scale()` to the
  *rotated output values* — an upstream bug; the correct (and Meta-official) behavior is to
  scale the *frequencies*, which is what we do here.
- ROPE_FALCON: GPT-NeoX half-rotation layout, pairs (j, j+hs/2), freq_j = theta^(-2j/hs)
  (FalconRopeCommand, commands.cpp:229-257); used by Grok-1 and Mixtral.

Tables are computed once per model (host numpy, f32) and live on device; application is a
pure jnp function usable inside jit/scan/shard_map. Slicing across TP devices is by whole
heads, and both layouts rotate within a head, so sliced==unsliced holds by construction —
the property the reference's commands-test.cpp checks explicitly.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..models.spec import ModelSpec, RopeType


def _llama31_scale_freqs(freqs: np.ndarray, factor: float, low_freq_factor: float,
                         high_freq_factor: float, orig_max_seq_len: int) -> np.ndarray:
    """Llama-3.1 NTK-by-parts frequency scaling (correct form of commands.cpp:193-205)."""
    wavelens = 2.0 * math.pi / freqs
    low_freq_wavelen = orig_max_seq_len / low_freq_factor
    high_freq_wavelen = orig_max_seq_len / high_freq_factor
    smooth = (orig_max_seq_len / wavelens - low_freq_factor) / (high_freq_factor - low_freq_factor)
    scaled = np.where(
        wavelens < high_freq_wavelen,
        freqs,
        np.where(wavelens > low_freq_wavelen, freqs / factor,
                 (1.0 - smooth) * freqs / factor + smooth * freqs),
    )
    return scaled


def _yarn_scale_freqs(freqs: np.ndarray, factor: float, orig_max_seq_len: int,
                      beta_fast: float, beta_slow: float, theta: float
                      ) -> np.ndarray:
    """YaRN's frequencies over a rotary width of 2 x len(freqs): pair i keeps
    its own frequency below the correction range, is divided by `factor`
    above it, and is ramped between. The range is where a pair turns
    beta_fast (its floor) and beta_slow (its ceiling) times over the original
    context: dim x ln(orig / (beta 2 pi)) / (2 ln theta)."""
    dim = 2 * len(freqs)

    def turns_at(beta):
        return (dim * math.log(orig_max_seq_len / (beta * 2.0 * math.pi))
                / (2.0 * math.log(theta)))

    lo = max(math.floor(turns_at(beta_fast)), 0)
    hi = min(math.ceil(turns_at(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(len(freqs), dtype=np.float64) - lo)
                   / max(hi - lo, 0.001), 0.0, 1.0)
    return freqs / factor * ramp + freqs * (1.0 - ramp)


@jax.tree_util.register_pytree_node_class
@dataclass
class RopeTables:
    """Precomputed per-position cos/sin, shape (seq_len, rope_width // 2);
    (kinds, seq_len, widest // 2) for a model with kinds of layer."""

    cos: jax.Array
    sin: jax.Array
    rope_type: RopeType

    def tree_flatten(self):
        return (self.cos, self.sin), (self.rope_type,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux[0])

    def of_kind(self, spec: ModelSpec, kind: int | None) -> "RopeTables":
        """The table of one kind's layers, for `spec.of_kind(kind)`."""
        if kind is None:
            return self
        ks = spec.of_kind(kind)
        half = ks.rope_width // 2
        return RopeTables(self.cos[kind, :, :half], self.sin[kind, :, :half],
                          ks.rope_type)

    @classmethod
    def create(cls, spec: ModelSpec) -> "RopeTables":
        if spec.kinds:
            each = [cls.create(ks) for ks in spec.kind_specs()]
            widest = max(t.cos.shape[1] for t in each)

            def stack(tables):
                return jnp.stack([jnp.pad(a, ((0, 0), (0, widest - a.shape[1])))
                                  for a in tables])

            return cls(stack([t.cos for t in each]),
                       stack([t.sin for t in each]), spec.rope_type)
        hs = spec.rope_width
        k = np.arange(hs // 2, dtype=np.float64)
        freqs = 1.0 / (spec.rope_theta ** (2.0 * k / hs))
        scale = 1.0
        if spec.rope_type in (RopeType.YARN, RopeType.YARN_NEOX):
            from ..models.spec import yarn_mscale

            freqs = _yarn_scale_freqs(
                freqs, spec.rope_scaling_factor,
                spec.rope_scaling_orig_max_seq_len, spec.yarn_beta_fast,
                spec.yarn_beta_slow, spec.rope_theta)
            # the tables' own factor: mscale(factor, mscale) over
            # mscale(factor, mscale_all_dim), 1 where the two are equal
            scale = (yarn_mscale(spec.rope_scaling_factor, spec.yarn_mscale)
                     / yarn_mscale(spec.rope_scaling_factor,
                                   spec.yarn_mscale_all_dim))
        elif spec.rope_type == RopeType.LLAMA3_1:
            freqs = _llama31_scale_freqs(
                freqs, spec.rope_scaling_factor, spec.rope_scaling_low_freq_factor,
                spec.rope_scaling_high_freq_factor, spec.rope_scaling_orig_max_seq_len)
        if spec.rope_table_scale:  # stated as a number: nothing to derive
            scale = spec.rope_table_scale
        t = np.arange(spec.seq_len, dtype=np.float64)
        angles = np.outer(t, freqs)  # (seq_len, hs//2)
        return cls(
            cos=jnp.asarray(np.cos(angles) * scale, dtype=jnp.float32),
            sin=jnp.asarray(np.sin(angles) * scale, dtype=jnp.float32),
            rope_type=spec.rope_type,
        )


def apply_rope(x: jax.Array, tables: RopeTables, positions: jax.Array) -> jax.Array:
    """Rotate q or k. x: (..., T, n_heads, head_size); positions: (T,) int32.

    Both interleaved (llama) and half-rotation (neox/falcon) layouts rotate pair
    (a, b) -> (a*cos - b*sin, a*sin + b*cos); only the pairing differs. Tables
    narrower than the head rotate its first 2 x width values (pairs within
    them) and pass the rest as they are.
    """
    if tables.rope_type == RopeType.NONE:
        return x
    cos = tables.cos[positions][..., :, None, :]  # (..., T, 1, hs//2)
    sin = tables.sin[positions][..., :, None, :]
    hs = 2 * cos.shape[-1]
    if hs < x.shape[-1]:
        return jnp.concatenate(
            [apply_rope(x[..., :hs], tables, positions), x[..., hs:]], axis=-1)
    xf = x.astype(jnp.float32)
    if tables.rope_type in (RopeType.LLAMA, RopeType.LLAMA3_1, RopeType.YARN):
        xp = xf.reshape(*x.shape[:-1], hs // 2, 2)
        a, b = xp[..., 0], xp[..., 1]
        ra = a * cos - b * sin
        rb = a * sin + b * cos
        out = jnp.stack([ra, rb], axis=-1).reshape(x.shape)
    elif tables.rope_type in (RopeType.FALCON, RopeType.YARN_NEOX):
        a, b = xf[..., : hs // 2], xf[..., hs // 2 :]
        ra = a * cos - b * sin
        rb = a * sin + b * cos
        out = jnp.concatenate([ra, rb], axis=-1)
    else:
        raise ValueError(tables.rope_type)
    return out.astype(x.dtype)
