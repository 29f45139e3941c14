"""Grouped-query causal attention over a resident KV cache.

TPU-native replacement for the reference's per-head scalar attention loop
(src/llama2-tasks.cpp:54-94: per head, dot q·k over 0..pos, softmax, weighted sum of v).
Here the whole (heads x positions) score matrix is one batched einsum on the MXU, masked
and softmaxed on the VPU, for T query tokens at once — which also gives chunked prefill,
something the reference (token-at-a-time prefill) lacks.

Shapes (batch-first, head-major cache):
    q: (B, T, n_q_heads, hs)     k_cache/v_cache: (B, n_kv_heads, S, hs)
TP slices along the kv-head axis (reference MultiHeadAttSlice, commands.cpp:104-108);
sequence parallelism slices along S (ring attention, see ops/ring_attention.py).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .kernels import masked_softmax


def gqa_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                  positions: jax.Array,
                  key_positions: jax.Array | None = None,
                  key_lo: jax.Array | None = None) -> jax.Array:
    """Causal GQA attention of T query tokens against the full cache.

    key_lo: the lowest key position each query reads, shaped like `positions`
    (a sliding window: position - window + 1, floored at 0); None reads from 0.

    positions: absolute query positions, (T,) shared across the batch or (B, T)
    per-row (continuous batching: each batch row decodes at its own offset).
    key_positions: absolute position of each key slot, (S,) or per-row (B, S).
    Defaults to arange(S) (slot index == position, the resident-cache layout);
    models/forward.py passes [window slots ++ current-chunk positions]
    with garbage slots pushed past seq_len so the causal compare masks them.
    Returns (B, T, n_q_heads * hs)."""
    b, t, hq, hs = q.shape
    _, hk, s, _ = k_cache.shape
    g = hq // hk
    qg = q.reshape(b, t, hk, g, hs)
    scale = 1.0 / math.sqrt(hs)
    # (B, hk, g, T, S)
    scores = jnp.einsum("btkgd,bksd->bkgts", qg.astype(jnp.float32),
                        k_cache.astype(jnp.float32)) * scale
    if key_positions is None:
        key_positions = jnp.arange(s)
    if positions.ndim == 1:
        assert key_positions.ndim == 1
        valid = key_positions[None, :] <= positions[:, None]  # (T, S) causal mask
        if key_lo is not None:
            valid &= key_positions[None, :] >= key_lo[:, None]
        mask = valid[None, None, None, :, :]
    else:
        kp = key_positions if key_positions.ndim == 2 else key_positions[None, :]
        valid = kp[:, None, :] <= positions[:, :, None]  # (B, T, S)
        if key_lo is not None:
            valid &= kp[:, None, :] >= key_lo[:, :, None]
        mask = valid[:, None, None, :, :]
    probs = masked_softmax(scores, mask)
    out = jnp.einsum("bkgts,bksd->btkgd", probs, v_cache.astype(jnp.float32))
    return out.reshape(b, t, hq * hs).astype(q.dtype)


def gqa_attention_lse(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                      positions: jax.Array,
                      key_positions: jax.Array | None = None
                      ) -> tuple[jax.Array, jax.Array]:
    """gqa_attention that ALSO returns the log-sum-exp of the (masked) scores.

    The flash-attention segment form: a softmax over keys split across segments
    equals merge_attention_partials() of each segment's (normalized output, lse).
    Used by the paged KV cache (runtime/paged_cache.py) to combine the device-
    resident hot ring with the host-resident cold history — the TPU-native
    answer to the reference's mmap'd disk KV cache (transformer.cpp:312-318).

    Returns (out (B, T, hq, hs) f32, lse (B, T, hq) f32); fully-masked rows give
    out 0 and lse -inf (a zero-weight segment under the merge)."""
    b, t, hq, hs = q.shape
    _, hk, s, _ = k_cache.shape
    g = hq // hk
    qg = q.reshape(b, t, hk, g, hs)
    scale = 1.0 / math.sqrt(hs)
    scores = jnp.einsum("btkgd,bksd->bkgts", qg.astype(jnp.float32),
                        k_cache.astype(jnp.float32)) * scale  # (B, hk, g, T, S)
    if key_positions is None:
        key_positions = jnp.arange(s)
    if positions.ndim == 1:
        mask = (key_positions[None, :] <= positions[:, None])[None, None, None]
    else:
        kp = key_positions if key_positions.ndim == 2 else key_positions[None, :]
        mask = (kp[:, None, :] <= positions[:, :, None])[:, None, None]
    neg = jnp.finfo(jnp.float32).min
    sm = jnp.where(mask, scores, neg)
    m = jnp.max(sm, axis=-1)  # (B, hk, g, T)
    e = jnp.where(mask, jnp.exp(sm - m[..., None]), 0.0)
    l = jnp.sum(e, axis=-1)  # (B, hk, g, T)
    out = jnp.einsum("bkgts,bksd->btkgd", e, v_cache.astype(jnp.float32))
    l_t = jnp.transpose(l, (0, 3, 1, 2))  # (B, T, hk, g)
    m_t = jnp.transpose(m, (0, 3, 1, 2))
    out = out / jnp.maximum(l_t, 1e-30)[..., None]
    lse = jnp.where(l_t > 0.0, m_t + jnp.log(jnp.maximum(l_t, 1e-30)), -jnp.inf)
    return out.reshape(b, t, hq, hs), lse.reshape(b, t, hq)


def merge_attention_partials(out_a: jax.Array, lse_a: jax.Array,
                             out_b: jax.Array, lse_b: jax.Array) -> jax.Array:
    """Combine two attention segments' (normalized output, lse) into the exact
    full-softmax output: softmax weights re-derive from exp(lse_i - max) and an
    empty segment (lse -inf) contributes zero weight. out_*: (..., hs),
    lse_*: (...) matching out's leading axes."""
    m = jnp.maximum(lse_a, lse_b)
    m = jnp.where(jnp.isfinite(m), m, 0.0)  # both segments empty: output zeros
    wa = jnp.exp(lse_a - m)
    wb = jnp.exp(lse_b - m)
    den = jnp.maximum(wa + wb, 1e-30)[..., None]
    return (out_a * wa[..., None] + out_b * wb[..., None]) / den


def latent_attention(q: jax.Array, rows: jax.Array, positions: jax.Array,
                     key_positions: jax.Array, n_values: int,
                     scale: float) -> jax.Array:
    """Causal attention of every head against ONE row a key, in the absorbed
    form of latent attention: a head's query is as wide as the row, the score
    is their product, and the values are the row's first `n_values` entries
    (the latent), whichever head reads them.

    q: (B, T, H, W); rows: (B, S, W) (window slots, then the chunk's own
    rows); positions (T,) or (B, T); key_positions (S,) or (B, S), garbage
    slots pushed past every position as for gqa_attention.
    Returns (B, T, H, n_values) float32."""
    scores = jnp.einsum("bthw,bsw->bhts", q.astype(jnp.float32),
                        rows.astype(jnp.float32)) * scale
    if positions.ndim == 1:
        mask = (key_positions[None, :] <= positions[:, None])[None, None]
    else:
        kp = key_positions if key_positions.ndim == 2 else key_positions[None]
        mask = (kp[:, None, :] <= positions[:, :, None])[:, None]
    probs = masked_softmax(scores, mask)
    return jnp.einsum("bhts,bsv->bthv", probs,
                      rows[..., :n_values].astype(jnp.float32))
