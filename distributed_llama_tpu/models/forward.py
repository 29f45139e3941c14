"""Unified transformer forward pass for Llama / Mixtral / Grok-1.

TPU-native replacement for the reference's hand-unrolled task graphs
(src/llama2-tasks.cpp:241-298, src/grok1-tasks.cpp:275-354, src/mixtral-tasks.cpp:5-78).
The 25-tasks-per-layer lockstep lists collapse into one `lax.scan` over stacked layer
params; the sync tasks (syncUnitBuffer broadcast / syncSliceOfSlicedBuffer gather+merge,
src/tasks.cpp:44-94) collapse into `psum`/`all_gather` at exactly the points where the
reference gathers partial sums.

The SAME function is the single-device program and the per-shard program: pass
`axis_name="tp"` when tracing under shard_map and every shard-local partial result is
reduced with `psum` where the reference's root merged slices (llamaMergeAtt,
llama2-tasks.cpp:125-131). This makes sliced==unsliced a *structural* property, which the
TP equivalence tests check on an 8-device mesh.

Arch-specific structure:
- LLAMA (dense): pre-norm attention + SwiGLU FFN (w1=gate, w3=up, w2=down).
- MIXTRAL: attention as llama; FFN -> top-2-of-8 MoE (router softmax over all experts,
  top-k renormalized, hb_e = up_e(x) * act(gate_e(x)), out = sum w_ae * down_e(hb_e)).
- GROK1: embedding x78.38367176906169 (grok1-tasks.cpp:11-14); attention output is
  rmsnorm'd (rms_ffn) BEFORE the residual join (grokRmfFfn*, grok1-tasks.cpp:16-41);
  MoE input norm uses rms_moe; MoE output is rmsnorm'd with rms_ffn2 before its residual
  join; logits x0.5773502691896257 (grokFinalize2).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from ..ops.attention import gqa_attention, update_kv_cache
from ..ops.kernels import gelu_tanh, rmsnorm, silu
from ..ops.matmul import qmatmul, qmatmul_gated, qmatmul_q80
from ..ops.ring_attention import (commit_kv_rows_sharded, ring_attention,
                                  update_kv_cache_sharded)
from ..ops.rope import RopeTables, apply_rope
from .spec import ArchType, HiddenAct, ModelSpec

GROK_EMBEDDING_SCALE = 78.38367176906169  # grok1-tasks.cpp:13
GROK_LOGITS_SCALE = 0.5773502691896257  # grok1-tasks.cpp:272


def _localize_qtensors(params):
    """Reset i4p col-group metadata for shard-local execution.

    Col-sharded i4p tensors are packed per TP column group precisely so that each
    shard's slice is ONE self-contained split-plane pack; inside shard_map the local
    QTensor therefore has groups=1 physically, but the aux metadata (static through
    device_put/tree ops) still says groups=tp. Fix it up so dequantize/kernels see the
    local truth."""
    from ..quants import QTensor

    def fix(t):
        if isinstance(t, QTensor) and t.layout == "i4p" and t.groups != 1:
            return QTensor(t.ftype, t.data, t.scales, layout="i4p", groups=1,
                           row_groups=t.row_groups)
        return t

    return jax.tree_util.tree_map(fix, params,
                                  is_leaf=lambda x: isinstance(x, QTensor))


def _act(spec: ModelSpec):
    return silu if spec.hidden_act == HiddenAct.SILU else gelu_tanh


def _act_name(spec: ModelSpec) -> str:
    """Static activation name for the fused gate-pair kernel's epilogue
    (ops/pallas_q4_mm.py matches these formulas in f32)."""
    return "silu" if spec.hidden_act == HiddenAct.SILU else "gelu_tanh"


def _maybe_psum(x: jax.Array, axis_name: str | None, compress: bool = False) -> jax.Array:
    """TP merge point: the reference's gather-partials-and-sum-at-root
    (syncSliceOfSlicedBuffer + merge) becomes an all-reduce over the tp axis.
    `compress` swaps in the int8 Q80-payload all-reduce (the wire-compression
    equivalent of tasks.cpp:96-135)."""
    if axis_name is None:
        return x
    from ..parallel.collectives import psum

    return psum(x, axis_name, compress=compress)


def _attention(x, bp, layer_idx, spec: ModelSpec, rope: RopeTables, kc, vc, start_pos,
               positions, axis_name, sp_axis_name, sp_size, use_pallas, compress,
               window, deferred_write=False, prologue=False, paged_cold=None,
               block_tables=None, block_tokens=0, paged_kernel=False,
               residual=None):
    """Sharded attention sub-block against the FULL stacked caches (L, B, hk, S, hs).

    residual: optional (B, T, dim) block input; when given the returned
    attn_out is ALREADY residual-joined (residual + wo-projection). Under
    use_pallas == "fused" with a single-chip wo (axis_name None) the add runs
    inside the dequant-matmul kernel's accumulator; otherwise it is the same
    `residual + y` the caller used to compute — callers must not re-add.

    Head counts in bp may be TP-local slices; the cache sequence axis may be sp-sharded
    (ring attention). The cache WRITE discipline depends on the caller: in-scan mode
    updates (layer_idx, :, :, pos) in place and returns the caches; deferred mode
    returns only the new (k_t, v_t) rows for forward() to commit after the scan.
    Either way decode's READ is only the first `window` positions (a static bucket
    >= pos+T chosen by the caller), so cache HBM traffic scales with the live
    context, not the allocated seq_len. The reference gets the same effect for free
    because its attention loop runs 0..pos (llama2-tasks.cpp:62-93); with XLA's
    static shapes the window bucket is the equivalent lever.
    """
    b, t, _ = x.shape
    hs = spec.head_size
    _, _, hk, s, _ = kc.shape
    if prologue:
        # fused rmsnorm+quantize prologue kernel (ops/pallas_prologue.py): the
        # norm and the Q80 activation quantization every decode matvec needs
        # collapse into one VPU pass, and the quantized row feeds the inline-Xexp
        # matvec directly (qmatmul_q80)
        from ..ops.pallas_prologue import rmsnorm_quantize_q80

        xq, sx = rmsnorm_quantize_q80(x, bp["rms_att"], spec.norm_eps)

        def project(wname):
            return qmatmul_q80(xq, sx, bp[wname], use_pallas=use_pallas,
                               out_dtype=x.dtype)
    else:
        xb = rmsnorm(x, bp["rms_att"], spec.norm_eps)

        def project(wname):
            return qmatmul(xb, bp[wname], use_pallas=use_pallas)
    if "wqkv" in bp:
        # merged QKV (models/params.py fuse_matvec_groups): ONE kernel launch for
        # all three projections. Local row counts split proportionally to the
        # global dim : kv : kv ratio (exact — every term divides by tp).
        qkv = project("wqkv")
        total = qkv.shape[-1]
        lq = total * spec.dim // (spec.dim + 2 * spec.kv_dim)
        lkv = (total - lq) // 2
        q = qkv[..., :lq]
        k = qkv[..., lq:lq + lkv]
        v = qkv[..., lq + lkv:]
    else:
        q = project("wq")
        k = project("wk")
        v = project("wv")

    def project_out(att):
        """wo projection + TP merge; under the prologue the attention output is
        quantized by the fused kernel instead of inside the matvec. The TP-local
        row width (hq_local*hs) is re-checked — the forward()-level gate only
        validated spec.dim."""
        from ..ops.pallas_prologue import prologue_supported, quantize_q80_row

        if prologue and prologue_supported(att.shape[-1]):
            aq, asx = quantize_q80_row(att)
            y = qmatmul_q80(aq, asx, bp["wo"], use_pallas=use_pallas,
                            out_dtype=x.dtype)
        else:
            if (residual is not None and axis_name is None
                    and use_pallas == "fused"):
                # single-chip wo: fold the residual into the kernel's f32
                # accumulator init (TP partials must psum BEFORE the join,
                # so the fusion is gated to axis_name is None)
                return qmatmul(att, bp["wo"], use_pallas=use_pallas,
                               residual=residual)
            y = qmatmul(att, bp["wo"], use_pallas=use_pallas)
        y = _maybe_psum(y, axis_name, compress)
        return y if residual is None else residual + y
    hq_local = q.shape[-1] // hs
    hk_local = k.shape[-1] // hs
    q = apply_rope(q.reshape(b, t, hq_local, hs), rope, positions)
    k = apply_rope(k.reshape(b, t, hk_local, hs), rope, positions)
    v = v.reshape(b, t, hk_local, hs)
    if sp_axis_name is not None and sp_size > 1:
        # sequence parallelism: each sp member keeps its slice of the cache and the
        # KV blocks rotate around the ring (ops/ring_attention.py).
        if deferred_write:
            # deferred discipline on the sp path: the sharded caches stay
            # loop-invariant (read-only — no full-local-slice carry copies); the
            # ring attends COMMITTED rows only (live_end) plus the current
            # chunk's K/V as a register block, and the new rows ride out as scan
            # ys for forward() to commit with ONE masked window write per cache
            # (ops/ring_attention.py commit_kv_rows_sharded).
            #
            # The deferred sp cache is STRIPED (member m's slot j = position
            # j*sp + m): the live context occupies the same slot prefix on every
            # member, so a static window bucket bounds each rotation to
            # ceil(window/sp) columns — ICI and HBM per step track the LIVE
            # context, not the allocated seq_len (the sp analog of attn_window;
            # impossible under contiguous sharding, where the live prefix
            # concentrates on low-index members).
            k_t = jnp.swapaxes(k, 1, 2).astype(kc.dtype)  # (B, hk, T, hs)
            v_t = jnp.swapaxes(v, 1, 2).astype(vc.dtype)
            kl = jax.lax.dynamic_slice(kc, (layer_idx, 0, 0, 0, 0),
                                       (1, b, hk, s, hs))[0]
            vl = jax.lax.dynamic_slice(vc, (layer_idx, 0, 0, 0, 0),
                                       (1, b, hk, s, hs))[0]
            wl = (None if window is None
                  else min((window + sp_size - 1) // sp_size, s))
            att = ring_attention(q, kl, vl, positions, axis_name=sp_axis_name,
                                 axis_size=sp_size, live_end=start_pos,
                                 chunk=(k_t, v_t, start_pos), striped=True,
                                 window_slots=wl)
            attn_out = project_out(att)
            return attn_out, (k_t, v_t)  # new rows only; caller commits post-scan
        # in-scan form: layer slice out, sharded update, full-layer write-back
        # (the ring path reads the whole local slice anyway)
        kl = jax.lax.dynamic_slice(kc, (layer_idx, 0, 0, 0, 0), (1, b, hk, s, hs))[0]
        vl = jax.lax.dynamic_slice(vc, (layer_idx, 0, 0, 0, 0), (1, b, hk, s, hs))[0]
        kl, vl = update_kv_cache_sharded(kl, vl, k, v, start_pos,
                                         axis_name=sp_axis_name)
        att = ring_attention(q, kl, vl, positions, axis_name=sp_axis_name,
                             axis_size=sp_size)
        kc = jax.lax.dynamic_update_slice(kc, kl[None], (layer_idx, 0, 0, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, vl[None], (layer_idx, 0, 0, 0, 0))
    elif deferred_write and paged_cold is not None:
        # Paged (out-of-core) cache: the device cache's S axis is a RING of the
        # R most recent positions (slot = position mod R); everything older lives
        # in the host store, and its attention contribution arrives as a
        # (normalized output, lse) partial from the per-layer host callback —
        # merged with the hot segment by the flash-attention segment identity
        # (ops/attention.py merge_attention_partials). TPU-native equivalent of
        # the reference's mmap'd disk KV cache (transformer.cpp:312-318): same
        # capacity valve, but the resident window stays HBM-fast and only the
        # cold history pays host bandwidth.
        k_t = jnp.swapaxes(k, 1, 2).astype(kc.dtype)  # (B, hk, T, hs)
        v_t = jnp.swapaxes(v, 1, 2).astype(vc.dtype)
        kl = jax.lax.dynamic_slice(kc, (layer_idx, 0, 0, 0, 0), (1, b, hk, s, hs))[0]
        vl = jax.lax.dynamic_slice(vc, (layer_idx, 0, 0, 0, 0), (1, b, hk, s, hs))[0]
        # slot j's most recent committed position: p_j = j + R*floor((pos-1-j)/R)
        # (< start_pos by construction; negative = never written = masked). The
        # committed ring covers exactly [max(0, start_pos-R), start_pos) — the
        # host cold segment covers [0, max(0, start_pos-R)) with no overlap.
        slot = jnp.arange(s)
        p_j = slot + s * jnp.floor_divide(start_pos - 1 - slot, s)
        slot_pos = jnp.where(p_j >= 0, p_j, jnp.int32(1 << 30))
        key_pos = jnp.concatenate([slot_pos, start_pos + jnp.arange(t)])
        from ..ops.attention import gqa_attention_lse, merge_attention_partials

        out_h, lse_h = gqa_attention_lse(
            q, jnp.concatenate([kl, k_t], axis=2),
            jnp.concatenate([vl, v_t], axis=2), positions, key_positions=key_pos)
        out_c, lse_c = paged_cold(layer_idx, q.astype(jnp.float32), start_pos)
        att = merge_attention_partials(out_h, lse_h, out_c, lse_c)
        att = att.reshape(b, t, hq_local * hs).astype(x.dtype)
        attn_out = project_out(att)
        return attn_out, (k_t, v_t)  # caller commits into ring slots (mod R)
    elif deferred_write and block_tables is not None:
        # Device-resident paged KV (docs/PAGED_KV.md): the caches are a
        # BLOCK POOL (L, N, hk, bt, hs) and each row's block table maps
        # virtual positions to pool blocks. Two readers, same semantics:
        # the Pallas kernel copies the table's blocks under each row's
        # committed length pool→VMEM, 128 keys a step
        # (ops/pallas_paged_attention.py); the
        # XLA fallback gathers the table into the dense window layout and
        # runs the SAME gqa_attention as the dense deferred branch — so on
        # the CPU mesh paged logits are bit-identical to dense logits
        # (the paged-vs-dense token-identity bar, tests/test_paged_kv.py).
        # Writes commit post-scan through the same table (forward() below).
        k_t = jnp.swapaxes(k, 1, 2).astype(kc.dtype)  # (B, hk, T, hs)
        v_t = jnp.swapaxes(v, 1, 2).astype(vc.dtype)
        w_total = block_tables.shape[1]
        win = window or (w_total * block_tokens)
        nb = min(-(-win // block_tokens), w_total)
        if paged_kernel:
            from ..ops.pallas_paged_attention import paged_attention

            out = paged_attention(q, kc, vc, k_t, v_t, block_tables,
                                  start_pos, layer_idx, n_read=nb)
            att = out.reshape(b, t, hq_local * hs).astype(x.dtype)
        else:
            from ..ops.pallas_paged_attention import paged_gather_kv

            kw, vw = paged_gather_kv(kc, vc, layer_idx, block_tables, nb)
            vwin = nb * block_tokens
            slot = jnp.arange(vwin)
            # same committed-rows masking (and sentinel arithmetic) as the
            # dense per-row deferred branch below — a table entry past the
            # row's committed length is scratch/garbage and masks out
            slot_pos = jnp.where(slot[None, :] < start_pos[:, None],
                                 slot[None, :], spec.seq_len + 1)  # (B, vwin)
            key_pos = jnp.concatenate(
                [slot_pos, start_pos[:, None] + jnp.arange(t)[None, :]],
                axis=1)
            att = gqa_attention(q, jnp.concatenate([kw, k_t], axis=2),
                                jnp.concatenate([vw, v_t], axis=2),
                                positions, key_positions=key_pos)
        attn_out = project_out(att)
        return attn_out, (k_t, v_t)  # new rows only; caller commits post-scan
    elif deferred_write:
        # deferred-write path: the caches are loop-INVARIANT inside the layer scan —
        # attention reads the window of COMMITTED rows (positions < start_pos) and
        # attends to the current chunk's k/v directly from registers; the new rows
        # ride out of the scan as stacked ys and forward() commits all layers with
        # ONE top-level dynamic_update_slice per cache. Motivation: a scan carry
        # that is dynamic-update-sliced at a loop-varying layer index defeats XLA
        # TPU's in-place while-loop buffer optimization — the round-4 trace shows
        # the full (L,B,hk,S,hs) caches being copied at the step boundary
        # (~11.6 ms/token at 7B, a third of the step). A read-only operand has no
        # copy-on-write hazard.
        k_t = jnp.swapaxes(k, 1, 2).astype(kc.dtype)  # (B, hk, T, hs)
        v_t = jnp.swapaxes(v, 1, 2).astype(vc.dtype)
        win = window or s
        # windows past the single-block VMEM budget take the kernel's window-
        # tiled form (flash-attention carry in scratch, ops/pallas_attention.py)
        # — long contexts never fall back to XLA slicing mid-generation
        if use_pallas and t == 1 and b == 1 and start_pos.ndim == 0:
            # fused decode kernel: the cache window is DMA'd straight out of the
            # stacked buffers inside the kernel (ops/pallas_attention.py) — no
            # per-layer dynamic-slice materialization in XLA at all
            from ..ops.pallas_attention import fused_decode_attention

            g = hq_local // hk
            out = fused_decode_attention(
                q.reshape(hk, g, hs).astype(jnp.float32), kc, vc,
                k_t[0], v_t[0], layer_idx, start_pos, window=win)
            att = out.reshape(1, 1, hq_local * hs).astype(x.dtype)
            attn_out = project_out(att)
            return attn_out, (k_t, v_t)
        kw = jax.lax.dynamic_slice(kc, (layer_idx, 0, 0, 0, 0), (1, b, hk, win, hs))[0]
        vw = jax.lax.dynamic_slice(vc, (layer_idx, 0, 0, 0, 0), (1, b, hk, win, hs))[0]
        # window slot j holds a committed row iff j < start_pos; stale slots get a
        # past-seq_len position so the causal compare masks them. Current-chunk keys
        # carry their true absolute positions.
        slot = jnp.arange(win)
        if start_pos.ndim == 0:
            slot_pos = jnp.where(slot < start_pos, slot, s + 1)  # (win,)
            key_pos = jnp.concatenate([slot_pos, start_pos + jnp.arange(t)])
        else:  # per-row offsets (continuous batching)
            slot_pos = jnp.where(slot[None, :] < start_pos[:, None], slot[None, :],
                                 s + 1)  # (B, win)
            key_pos = jnp.concatenate(
                [slot_pos, start_pos[:, None] + jnp.arange(t)[None, :]], axis=1)
        kfull = jnp.concatenate([kw, k_t], axis=2)  # (B, hk, win+T, hs)
        vfull = jnp.concatenate([vw, v_t], axis=2)
        att = gqa_attention(q, kfull, vfull, positions, key_positions=key_pos)
        attn_out = project_out(att)
        return attn_out, (k_t, v_t)  # new rows only; caller commits post-scan
    elif start_pos.ndim == 1:
        # per-row offsets (continuous batching): vmap'd per-row write on the layer
        # slice, then full-layer write-back
        kl = jax.lax.dynamic_slice(kc, (layer_idx, 0, 0, 0, 0), (1, b, hk, s, hs))[0]
        vl = jax.lax.dynamic_slice(vc, (layer_idx, 0, 0, 0, 0), (1, b, hk, s, hs))[0]
        kl, vl = update_kv_cache(kl, vl, k, v, start_pos)
        win = window or s
        att = gqa_attention(q, kl[:, :, :win], vl[:, :, :win], positions)
        kc = jax.lax.dynamic_update_slice(kc, kl[None], (layer_idx, 0, 0, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, vl[None], (layer_idx, 0, 0, 0, 0))
    else:
        # in-scan path: tiny in-place write at (layer, :, :, pos), windowed read
        k_t = jnp.swapaxes(k, 1, 2).astype(kc.dtype)[None]  # (1, B, hk, T, hs)
        v_t = jnp.swapaxes(v, 1, 2).astype(vc.dtype)[None]
        kc = jax.lax.dynamic_update_slice(kc, k_t, (layer_idx, 0, 0, start_pos, 0))
        vc = jax.lax.dynamic_update_slice(vc, v_t, (layer_idx, 0, 0, start_pos, 0))
        win = window or s
        kw = jax.lax.dynamic_slice(kc, (layer_idx, 0, 0, 0, 0), (1, b, hk, win, hs))[0]
        vw = jax.lax.dynamic_slice(vc, (layer_idx, 0, 0, 0, 0), (1, b, hk, win, hs))[0]
        att = gqa_attention(q, kw, vw, positions)
    # col-parallel wo: local heads x local input slice -> partial (B, T, dim); psum merges
    attn_out = project_out(att)
    return attn_out, (kc, vc)


def _dense_ffn(x, bp, spec: ModelSpec, axis_name, use_pallas, compress,
               prologue=False, residual=None):
    """Dense FFN on the PRE-norm block input x (the rms_ffn norm is applied
    here so the prologue can fuse it with the activation quantize). One body
    for both modes — only the projection primitive differs: under the prologue
    each activation row is quantized by a fused kernel (ops/pallas_prologue.py)
    and qmatmul_q80 consumes the pre-quantized row; otherwise the matvecs
    quantize internally. TP-local widths are re-checked before each prologue
    kernel — the forward()-level gate only validated spec.dim.

    residual: optional (B, T, dim); when given the return value is ALREADY
    residual + ffn(x) — under use_pallas == "fused" with a single-chip w2
    the add fuses into the down-projection kernel's accumulator init, and the
    gate/up pair (when kept separate — Engine fused_matmul skips the w13
    merge) lowers to ONE silu·mul-epilogue kernel whose (B·T, hidden)
    intermediates never touch HBM. Callers must not re-add."""
    act = _act(spec)
    if prologue:
        from ..ops.pallas_prologue import (prologue_supported, quantize_q80_row,
                                           rmsnorm_quantize_q80)

        xq, sx = rmsnorm_quantize_q80(x, bp["rms_ffn"], spec.norm_eps)

        def project(wname):
            return qmatmul_q80(xq, sx, bp[wname], use_pallas=use_pallas,
                               out_dtype=jnp.float32)

        if "w13" in bp:
            h = _gated_split(project("w13"), act, gate_first=True)
        else:
            h = act(project("w1")) * project("w3")
    else:
        xb = rmsnorm(x, bp["rms_ffn"], spec.norm_eps)
        if "w13" in bp:
            # merged gate+up (fuse_matvec_groups): one launch per TP group;
            # the packed stream is already one pass, only the act·mul epilogue
            # stays un-fused on this layout
            h = _gated_split(qmatmul(xb, bp["w13"], use_pallas=use_pallas),
                             act, gate_first=True)
        else:
            h = qmatmul_gated(xb, bp["w1"], bp["w3"], act=act,
                              act_name=_act_name(spec),
                              use_pallas=use_pallas)
    if prologue and prologue_supported(h.shape[-1]):
        hq, hsx = quantize_q80_row(h)
        out = qmatmul_q80(hq, hsx, bp["w2"], use_pallas=use_pallas,
                          out_dtype=x.dtype)
    else:
        if (residual is not None and axis_name is None
                and use_pallas == "fused"):
            # single-chip w2: residual folds into the kernel accumulator
            # (TP partials must psum before the join — see _attention)
            return qmatmul(h.astype(x.dtype), bp["w2"], use_pallas=use_pallas,
                           residual=residual)
        out = qmatmul(h.astype(x.dtype), bp["w2"], use_pallas=use_pallas)
    out = _maybe_psum(out, axis_name, compress)
    return out if residual is None else residual + out


def _gated_split(y, act, gate_first: bool):
    """Gated-FFN combine from a merged projection output split in halves per TP
    group: w13 is [gate|up] (act(first)*second), moe_gu is [up|gate]
    (first*act(second)) — member order set by _FUSE_GROUPS."""
    hl = y.shape[-1] // 2
    a, b = y[..., :hl], y[..., hl:]
    return act(a) * b if gate_first else a * act(b)


def _make_expert_step(xb, act, use_pallas, merged):
    """Scan body for the expert-major MoE prefill path; the merged form consumes
    the fused [up|gate] stack. Shared by _moe_ffn and _moe_ffn_expert_sharded
    (only the combine weights differ, and they ride in the xs)."""
    if merged:
        def step(acc, ew):
            gu_e, down_e, comb = ew  # QTensors (2h0,d)/(d,h0), comb (B,T)
            hb = _gated_split(qmatmul(xb, gu_e, use_pallas=use_pallas), act,
                              gate_first=False)
            out_e = qmatmul(hb, down_e, use_pallas=use_pallas)
            return acc + out_e * comb[..., None], None
    else:
        def step(acc, ew):
            up_e, gate_e, down_e, comb = ew  # QTensors (h0,d)/(d,h0), comb (B,T)
            hb = qmatmul(xb, up_e, use_pallas=use_pallas) * act(
                qmatmul(xb, gate_e, use_pallas=use_pallas))
            out_e = qmatmul(hb, down_e, use_pallas=use_pallas)
            return acc + out_e * comb[..., None], None
    return step


def _expert_scan_xs(bp, merged, combine):
    if merged:
        return (bp["moe_gu"], bp["moe_down"], combine)
    return (bp["moe_up"], bp["moe_gate"], bp["moe_down"], combine)


def _gather_expert(w, idx):
    """Select expert slices of a stacked QTensor (E, out, in) -> (B, T, K, out, in)."""
    return jax.tree_util.tree_map(lambda a: a[idx], w)


def _moe_ffn(xb, bp, spec: ModelSpec, axis_name, use_pallas, compress):
    """Top-k MoE FFN (grokMoeRouter..grokMoeBlock2, grok1-tasks.cpp:56-228).

    Router runs replicated (the reference runs it root-only and broadcasts indexes).
    Two expert shardings (parallel/sharding.py):
    - slice (default): every expert's hidden axis is TP-sliced like the dense FFN;
      the down-matmul partial sums psum across tp.
    - expert: whole experts shard over tp (detected here by the LOCAL stack's
      expert count being smaller than spec.n_experts under shard_map) — each shard
      computes only the active experts it owns (lax.cond keeps non-owners from
      streaming weights) and the same psum merges the contributions. The capacity
      axis for Grok-1-314B-class expert weights; no reference counterpart.
    """
    b, t, d = xb.shape
    k = spec.n_active_experts
    act = _act(spec)

    router_logits = qmatmul(xb, bp["router"], use_pallas=False).astype(jnp.float32)
    probs = jax.nn.softmax(router_logits, axis=-1)  # softmax over ALL experts
    top_p, top_i = jax.lax.top_k(probs, k)  # (B, T, K)
    weights = top_p / jnp.sum(top_p, axis=-1, keepdims=True)  # renormalize (grokMoeNormWeights)

    merged = "moe_gu" in bp  # fused up+gate stack (fuse_matvec_groups)
    gu_stack = bp["moe_gu"] if merged else bp["moe_up"]
    el = gu_stack.shape[0]  # shard-local expert count
    if axis_name is not None and el != spec.n_experts:
        return _moe_ffn_expert_sharded(xb, bp, spec, axis_name, use_pallas, compress,
                                       top_i, weights, el)

    if use_pallas and b * t == 1 and gu_stack.layout in ("i4p", "i8"):
        # Decode through the fused matvec kernels: dynamic_slice each active expert's
        # packed planes out of the stacked (E, ...) QTensor (moving exactly that
        # expert's bytes through HBM — the reference's per-active-expert matmuls,
        # grok1-tasks.cpp:128-144) and run the same q4/q8 kernel as the dense path.
        def expert_q(wstack, e):
            return jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, e, 1, 0)[0], wstack)

        out = jnp.zeros_like(xb)
        for j in range(k):
            e = top_i.reshape(k)[j]
            if merged:
                hb = _gated_split(qmatmul(xb, expert_q(bp["moe_gu"], e),
                                          use_pallas=True), act, gate_first=False)
            else:
                hb = qmatmul(xb, expert_q(bp["moe_up"], e), use_pallas=True) * act(
                    qmatmul(xb, expert_q(bp["moe_gate"], e), use_pallas=True))
            out_e = qmatmul(hb, expert_q(bp["moe_down"], e), use_pallas=True)
            out = out + out_e * weights.reshape(k)[j].astype(xb.dtype)
    elif b * t * k <= spec.n_experts:
        # Decode: gather the K active experts' (sliced) weight matrices per token,
        # dequantize, matmul. Moves exactly the active experts' bytes out of HBM — the
        # same bandwidth shape as the reference's per-expert forward calls.
        down_w = _gather_expert(bp["moe_down"], top_i).dequantize(dtype=xb.dtype)
        if merged:
            gu_w = _gather_expert(bp["moe_gu"], top_i).dequantize(dtype=xb.dtype)
            hb = _gated_split(jnp.einsum("btd,btkhd->btkh", xb, gu_w), act,
                              gate_first=False)
        else:
            up_w = _gather_expert(bp["moe_up"], top_i).dequantize(dtype=xb.dtype)
            gate_w = _gather_expert(bp["moe_gate"], top_i).dequantize(dtype=xb.dtype)
            hb = jnp.einsum("btd,btkhd->btkh", xb, up_w) * act(
                jnp.einsum("btd,btkhd->btkh", xb, gate_w))
        out = jnp.einsum("btkh,btkdh->btkd", hb, down_w)
        out = jnp.einsum("btkd,btk->btd", out, weights.astype(xb.dtype))
    else:
        # Prefill: per-token weight gathers would materialize (B,T,K,h,d); instead scan
        # expert-major — each step dequantizes ONE expert's matrices and masks its
        # contribution by the routing weights (zero for tokens that didn't pick it).
        one_hot = jax.nn.one_hot(top_i, spec.n_experts, dtype=xb.dtype)  # (B,T,K,E)
        combine = jnp.einsum("btke,btk->ebt", one_hot, weights.astype(xb.dtype))

        out, _ = jax.lax.scan(_make_expert_step(xb, act, use_pallas, merged),
                              jnp.zeros_like(xb),
                              _expert_scan_xs(bp, merged, combine))
    return _maybe_psum(out, axis_name, compress)


def _moe_ffn_expert_sharded(xb, bp, spec: ModelSpec, axis_name, use_pallas, compress,
                            top_i, weights, el):
    """Expert-parallel MoE FFN body: this shard owns experts
    [shard*el, (shard+1)*el). Decode runs one lax.cond per active expert (owners
    stream and compute, everyone else contributes zeros for free); prefill scans
    the local expert stack with the global routing weights sliced to the local
    window. The trailing psum is the merge point either way."""
    b, t, _ = xb.shape
    k = spec.n_active_experts
    act = _act(spec)
    shard = jax.lax.axis_index(axis_name)
    offset = shard * el
    merged = "moe_gu" in bp

    def expert_q(wstack, e):
        return jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_slice_in_dim(a, e, 1, 0)[0], wstack)

    def expert_hb(row_x, e_loc):
        """hb for one local expert — merged [up|gate] stack or separate."""
        if merged:
            return _gated_split(qmatmul(row_x, expert_q(bp["moe_gu"], e_loc),
                                        use_pallas=use_pallas), act,
                                gate_first=False)
        return qmatmul(row_x, expert_q(bp["moe_up"], e_loc),
                       use_pallas=use_pallas) * act(
            qmatmul(row_x, expert_q(bp["moe_gate"], e_loc),
                    use_pallas=use_pallas))

    if t == 1 and b * k <= 2 * spec.n_experts:
        # decode (incl. batched slots): one cond per (row, active expert) — owner
        # shards stream and compute exactly the routed experts, everyone else's
        # branch is a free zero. Unrolls b*k conds, so bounded to small batches;
        # bigger batches amortize fine through the local-stack scan below.
        rows = []
        for r in range(b):
            row_x = xb[r:r + 1]
            row_out = jnp.zeros_like(row_x)
            for j in range(k):
                e_rel = top_i[r, 0, j] - offset
                in_range = (e_rel >= 0) & (e_rel < el)
                e_loc = jnp.clip(e_rel, 0, el - 1)
                w_j = weights[r, 0, j].astype(xb.dtype)

                def compute(row_x=row_x, e_loc=e_loc):
                    return qmatmul(expert_hb(row_x, e_loc),
                                   expert_q(bp["moe_down"], e_loc),
                                   use_pallas=use_pallas)

                out_e = jax.lax.cond(in_range, compute,
                                     lambda row_x=row_x: jnp.zeros_like(row_x))
                row_out = row_out + out_e * w_j
            rows.append(row_out)
        out = jnp.concatenate(rows, axis=0) if b > 1 else rows[0]
    else:
        one_hot = jax.nn.one_hot(top_i, spec.n_experts, dtype=xb.dtype)  # (B,T,K,E)
        combine = jnp.einsum("btke,btk->ebt", one_hot, weights.astype(xb.dtype))
        combine_local = jax.lax.dynamic_slice_in_dim(combine, offset, el, 0)

        out, _ = jax.lax.scan(_make_expert_step(xb, act, use_pallas, merged),
                              jnp.zeros_like(xb),
                              _expert_scan_xs(bp, merged, combine_local))
    return _maybe_psum(out, axis_name, compress)


def _block(carry, layer, spec: ModelSpec, rope: RopeTables, start_pos, positions,
           axis_name, sp_axis_name, sp_size, use_pallas, compress, window,
           kc_ro=None, vc_ro=None, prologue=False, paged_cold=None,
           block_tables=None, block_tokens=0, paged_kernel=False):
    """One transformer block as a scan step. Two cache disciplines:

    - in-scan (kc_ro is None): caches travel in the carry and are updated in place
      per layer — carry (x, kc, vc), ys None.
    - deferred (kc_ro/vc_ro set): caches are read-only closures (loop invariants);
      carry is just x and the layer's new K/V rows leave as ys for forward() to
      commit in one top-level write.
    """
    deferred = kc_ro is not None
    if deferred:
        x, kc, vc = carry, kc_ro, vc_ro
    else:
        x, kc, vc = carry
    bp, layer_idx = layer
    # grok residual-joins the NORMALIZED attention output, so the projection
    # kernel cannot fold the raw residual there; every other arch hands the
    # block input down as the fusable residual (contract: attn_out returns
    # already joined when residual is given)
    res_attn = None if spec.arch_type == ArchType.GROK1 else x
    attn_out, kvout = _attention(x, bp, layer_idx, spec, rope, kc, vc, start_pos,
                                 positions, axis_name, sp_axis_name, sp_size,
                                 use_pallas, compress, window,
                                 deferred_write=deferred, prologue=prologue,
                                 paged_cold=paged_cold,
                                 block_tables=block_tables,
                                 block_tokens=block_tokens,
                                 paged_kernel=paged_kernel,
                                 residual=res_attn)
    if not deferred:
        kc, vc = kvout
    if spec.arch_type == ArchType.GROK1:
        # grok: residual-join the *normalized* attention output (grokRmfFfn/Norm/Join)
        x = x + rmsnorm(attn_out, bp["rms_ffn"], spec.norm_eps)
        xb = rmsnorm(x, bp["rms_moe"], spec.norm_eps)
        moe_out = _moe_ffn(xb, bp, spec, axis_name, use_pallas, compress)
        x = x + rmsnorm(moe_out, bp["rms_ffn2"], spec.norm_eps)
    else:
        x = attn_out  # residual-joined inside _attention
        if spec.is_moe:
            xb = rmsnorm(x, bp["rms_ffn"], spec.norm_eps)
            x = x + _moe_ffn(xb, bp, spec, axis_name, use_pallas, compress)
        else:
            x = _dense_ffn(x, bp, spec, axis_name, use_pallas, compress,
                           prologue=prologue, residual=x)
    if deferred:
        return x, kvout  # ys: this layer's (k_t, v_t) new rows
    return (x, kc, vc), None


def forward(params: dict[str, Any], spec: ModelSpec, rope: RopeTables,
            tokens: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
            start_pos: jax.Array, *, dtype=jnp.float32, axis_name: str | None = None,
            sp_axis_name: str | None = None, sp_size: int = 1,
            use_pallas: bool = False, compress_collectives: bool = False,
            attn_window: int | None = None, cache_write: str = "inscan",
            fused_prologue: bool = False, paged_cold=None,
            block_tables=None, block_tokens: int = 0,
            paged_kernel: bool = False):
    """Run T tokens through the model against the KV cache.

    tokens: (B, T) int32; k_cache/v_cache: (L, B, hk[/tp], S, hs); start_pos: scalar
    (all rows at one offset — the reference's single `pos`) or (B,) per-row offsets
    (continuous batching: each sequence decodes at its own position; the reference's
    single-slot pos has no analog). Returns (logits (B, T, vocab) f32, caches).

    Per-row start_pos also carries MIXED batches (BatchEngine): rows need not
    all use their T positions — a decode row in a (B, T=chunk) prefill step
    puts its one real token at index 0 and scratch beyond. Causal masking
    confines token 0's attention to the row's committed history plus itself,
    so its logits[row, 0] equal a T=1 step's, and the scratch writes land on
    positions > start_pos that every read path masks until the row's own
    later tokens overwrite them. The batched decode scan
    (runtime/device_loop.py) parks finished rows on the same invariant.

    cache_write selects the cache discipline:
    - "inscan": caches are scan CARRIES, updated in place per layer at a dynamic
      layer index — NOT scan xs/ys, which would restack (read+write) the full
      (L, B, hk, S, hs) buffers every step (~4 GB/token at 7B/2048, measured as
      half the step time in round 3).
    - "deferred": caches are loop-INVARIANT operands of the scan (read-only);
      each layer's new K/V rows leave as ys ((L, B, hk, T, hs), tiny) and ONE
      top-level dynamic_update_slice per cache commits them after the scan.
      Motivation: the round-4 TPU trace shows the in-scan carries being copied
      whole at the step boundary (~11.6 ms/token at 7B) — XLA TPU's in-place
      while-buffer optimization does not fire for a carry that is
      dynamic-update-sliced at a loop-varying index. Under sp the same
      discipline applies to the sequence-sharded caches: the ring attends
      committed rows + the chunk's K/V as a register block, and the commit is
      a masked window write into the owning shard (commit_kv_rows_sharded).

    attn_window: static bound on cache positions attention reads (must cover
    start_pos + T). None reads the full seq_len. Callers bucket it (Engine) so decode
    cache traffic tracks the live context length.

    Equivalent of Inference::infer (tasks.cpp:173-184) for the whole token chunk; the
    embedding-row copy at tasks.cpp:176-177 is the take() below, the task loop is the scan.
    """
    t = tokens.shape[1]
    if axis_name is not None:
        params = _localize_qtensors(params)
    start_pos = jnp.asarray(start_pos)
    if start_pos.ndim == 1:
        assert sp_size == 1, "per-row start_pos is not supported with sp (ring) sharding"
        positions = start_pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]  # (B, T)
    else:
        positions = start_pos + jnp.arange(t, dtype=jnp.int32)
    x = jnp.take(params["embedding"], tokens, axis=0).astype(dtype)
    if spec.arch_type == ArchType.GROK1:
        x = x * GROK_EMBEDDING_SCALE

    assert cache_write in ("inscan", "deferred"), cache_write
    deferred = cache_write == "deferred"
    sp_active = sp_axis_name is not None and sp_size > 1
    if paged_cold is not None:
        assert deferred and not sp_active and start_pos.ndim == 0, (
            "paged KV cache requires the deferred discipline, no sp sharding, "
            "and a scalar start_pos")
        assert t <= k_cache.shape[3], (
            f"chunk {t} exceeds the {k_cache.shape[3]}-slot resident ring")
    if block_tables is not None:
        assert deferred and not sp_active and paged_cold is None, (
            "device-resident paged KV requires the deferred discipline and "
            "no sp sharding / host-spill paging")
        assert block_tokens >= 1 and start_pos.ndim == 1, (
            "paged KV needs block_tokens and per-row start_pos")
    # fused rmsnorm+quantize prologue (ops/pallas_prologue.py): single-row decode
    # only (the kernels take one activation row), opt-in via fused_prologue
    if fused_prologue:
        from ..ops.pallas_prologue import prologue_supported

        fused_prologue = (use_pallas and t == 1 and tokens.shape[0] == 1
                          and start_pos.ndim == 0
                          and prologue_supported(spec.dim))
    block_fn = functools.partial(_block, spec=spec, rope=rope, start_pos=start_pos,
                                 positions=positions, axis_name=axis_name,
                                 sp_axis_name=sp_axis_name, sp_size=sp_size,
                                 use_pallas=use_pallas, compress=compress_collectives,
                                 window=attn_window,
                                 kc_ro=k_cache if deferred else None,
                                 vc_ro=v_cache if deferred else None,
                                 prologue=fused_prologue, paged_cold=paged_cold,
                                 block_tables=block_tables,
                                 block_tokens=block_tokens,
                                 paged_kernel=paged_kernel)
    layer_ids = jnp.arange(spec.n_layers, dtype=jnp.int32)
    if deferred:
        x, (k_rows, v_rows) = jax.lax.scan(
            block_fn, x, (params["blocks"], layer_ids))
        # commit all layers' new rows in one write per cache: (L, B, hk, T, hs)
        # lands at [.., .., .., start_pos : start_pos+T, ..]
        if block_tables is not None:
            # paged commit: position p of row b lands in pool block
            # tables[b, p // bt] at offset p % bt — one scatter per cache,
            # through the same table the read path consumed. Out-of-range
            # positions cannot occur by scheduler invariant (coverage is
            # ensured pre-dispatch; parked rows clamp below seq_len).
            pos_bt = positions  # (B, T) absolute positions
            blk = jnp.take_along_axis(
                block_tables, jnp.minimum(pos_bt // block_tokens,
                                          block_tables.shape[1] - 1), axis=1)
            off = pos_bt % block_tokens  # (B, T)
            k_cache = k_cache.at[:, blk, :, off, :].set(
                jnp.transpose(k_rows, (1, 3, 0, 2, 4)))
            v_cache = v_cache.at[:, blk, :, off, :].set(
                jnp.transpose(v_rows, (1, 3, 0, 2, 4)))
        elif paged_cold is not None:
            # ring commit: position p lands in slot p mod R (scatter — the
            # chunk may wrap the ring boundary). The rows being overwritten
            # need no flush: the HOST store is authoritative for every
            # committed position (Engine writes the same rows there).
            ring = k_cache.shape[3]
            idx = (start_pos + jnp.arange(t)) % ring
            k_cache = k_cache.at[:, :, :, idx, :].set(k_rows)
            v_cache = v_cache.at[:, :, :, idx, :].set(v_rows)
        elif sp_active:
            # sequence-sharded caches: masked window write into the owning
            # shards, striped layout (see the _attention sp-deferred branch)
            k_cache, v_cache = commit_kv_rows_sharded(
                k_cache, v_cache, k_rows, v_rows, start_pos,
                axis_name=sp_axis_name, striped=True, axis_size=sp_size)
        elif start_pos.ndim == 0:
            k_cache = jax.lax.dynamic_update_slice(
                k_cache, k_rows, (0, 0, 0, start_pos, 0))
            v_cache = jax.lax.dynamic_update_slice(
                v_cache, v_rows, (0, 0, 0, start_pos, 0))
        else:  # per-row offsets: vmap the write over the batch axis
            row_write = jax.vmap(
                lambda c, n, p: jax.lax.dynamic_update_slice(c, n, (0, 0, p, 0)),
                in_axes=(1, 1, 0), out_axes=1)
            k_cache = row_write(k_cache, k_rows, start_pos)
            v_cache = row_write(v_cache, v_rows, start_pos)
    else:
        (x, k_cache, v_cache), _ = jax.lax.scan(
            block_fn, (x, k_cache, v_cache), (params["blocks"], layer_ids))

    x = rmsnorm(x, params["rms_final"], spec.norm_eps)
    logits = qmatmul(x, params["wcls"], use_pallas=use_pallas, out_dtype=jnp.float32)
    if axis_name is not None:
        # wcls is row(vocab)-sharded: concatenate the vocab shards
        logits = jax.lax.all_gather(logits, axis_name, axis=-1, tiled=True)
    if spec.arch_type == ArchType.GROK1:
        logits = logits * GROK_LOGITS_SCALE
    if paged_cold is not None:
        # the new rows ride out so the caller can append them to the host
        # store — the step's one extra device->host payload (L, B, hk, T, hs)
        return logits, k_cache, v_cache, (k_rows, v_rows)
    return logits, k_cache, v_cache


def init_kv_cache(spec: ModelSpec, batch: int = 1, dtype=jnp.float32,
                  n_kv_heads: int | None = None, seq_len: int | None = None):
    """Zeroed head-major KV caches (L, B, hk, S, hs); hk may be a TP-local count."""
    hk = n_kv_heads if n_kv_heads is not None else spec.n_kv_heads
    s = seq_len if seq_len is not None else spec.seq_len
    shape = (spec.n_layers, batch, hk, s, spec.head_size)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)
