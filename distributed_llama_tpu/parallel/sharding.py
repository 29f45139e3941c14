"""Partition specs — the TPU equivalent of the reference's slicing layer.

Maps one-to-one onto src/commands.cpp:
    RowMatmulSlice  (split output dim d; commands.cpp:11-43)  -> 'tp' on the out axis
    ColMatmulSlice  (split input dim n; commands.cpp:45-73)   -> 'tp' on the in axis
                                                                  (+ psum in forward)
    KvCacheSlice    (kvDim/nSlices per node; commands.cpp:97-102) -> 'tp' on the kv-head
                                                                      axis of the cache
    MultiHeadAttSlice (nHeads/nSlices; commands.cpp:104-108)  -> implied by row-split QKV
    RopeSlice       (commands.cpp:75-95)                      -> nothing: rope rotates
                                                                  within a head, slicing
                                                                  is by whole heads

Because quantization blocks run along the `in` axis and a QTensor's packed/scales arrays
keep `out` and `in`(-block) at the same axis indices, ONE PartitionSpec per tensor works
as a pytree prefix for both leaves, and every slice boundary lands on a 32-block boundary
by construction (the reference asserts this dynamically, commands.cpp:16-19).
"""

from __future__ import annotations

from typing import Any

from jax.sharding import PartitionSpec as P

from ..models.params import KDA, SSM
from ..models.spec import ModelSpec
from .mesh import AXIS_TP

# per-layer matmul tensors: axis index (within the stacked (L, ...) logical shape) that
# 'tp' shards. out-splits mirror RowMatmulSlice, in-splits mirror ColMatmulSlice.
_BLOCK_SPECS = {
    "wq": P(None, AXIS_TP),          # (L, q_dim->tp, dim)
    "wk": P(None, AXIS_TP),          # (L, kv_dim->tp, dim)
    "wv": P(None, AXIS_TP),
    # merged matvec groups (models/params.py fuse_matvec_groups): rows are
    # TP-group interleaved at fuse time, so plain row sharding lands each shard
    # its own [q|k|v] / [gate|up] block
    "wqkv": P(None, AXIS_TP),        # (L, (dim+2kv)->tp, dim)
    "w13": P(None, AXIS_TP),         # (L, 2*hidden->tp, dim)
    "wo": P(None, None, AXIS_TP),    # (L, dim, q_dim->tp) partial-sum
    "wg": P(None, AXIS_TP),          # (L, heads->tp, dim): a head's gate with its q
    "w1": P(None, AXIS_TP),          # (L, hidden->tp, dim)
    "w3": P(None, AXIS_TP),
    "w2": P(None, None, AXIS_TP),    # (L, dim, hidden->tp) partial-sum
    "router": P(),                    # replicated (root-only in reference)
    "moe_up": P(None, None, AXIS_TP),    # (L, E, hidden->tp, dim)
    "moe_gate": P(None, None, AXIS_TP),
    "moe_gu": P(None, None, AXIS_TP),    # (L, E, 2*hidden->tp, dim), merged up+gate
    "moe_down": P(None, None, None, AXIS_TP),  # (L, E, dim, hidden->tp)
    "rms_att": P(),
    "rms_ffn": P(),
    "rms_moe": P(),
    "rms_ffn2": P(),
    # latent attention: the low-rank projections' inner side is whole on every
    # shard (and so is the latent row); heads shard where they first appear
    "wq_a": P(),                      # (L, q_lora_rank, dim)
    "wq_b": P(None, AXIS_TP),        # (L, q_dim->tp, q_lora_rank)
    "wkv_a": P(),                     # (L, kv_lora_rank + rope, dim)
    "w_uk": P(None, AXIS_TP),        # (L, heads->tp, nope, kv_lora_rank)
    "w_uv": P(None, AXIS_TP),        # (L, heads->tp, v, kv_lora_rank)
    "rms_q": P(),
    "rms_kv": P(),
    # the shared expert: sliced like the dense FFN, under moe_sharding
    # "expert" too (its partial sum joins the routed one before the psum)
    "sh_gate": P(None, AXIS_TP),
    "sh_up": P(None, AXIS_TP),
    "sh_down": P(None, None, AXIS_TP),
    # QK-norm's weights are a head's and the same on every shard; the
    # selection bias rides with the replicated router
    "rms_qh": P(),
    "rms_kh": P(),
    "router_bias": P(),
    # a convolution layer is whole on every shard: the engine refuses a model
    # with state layers over more than one tp member (runtime/engine.py)
    "conv_in": P(),
    "conv_w": P(),
    "conv_out": P(),
    # and so is a state-space mixer
    **{n: P() for n in SSM},
    # and a delta-rule mixer
    **{n: P() for n in KDA},
}


# expert parallelism: the MoE stacks shard by WHOLE experts over the tp axis
# instead of slicing every expert's hidden dim. Each shard owns E/tp complete
# experts; a decode step streams only the active experts' weights on their owner
# shards, and the existing FFN-output psum merges contributions. This is the
# capacity axis for MoE models whose expert weights dwarf one chip's HBM
# (Grok-1-314B class) — the reference has no counterpart (it always slices).
_EP_SPECS = {
    "moe_up": P(None, AXIS_TP),    # (L, E->tp, hidden, dim), experts whole
    "moe_gate": P(None, AXIS_TP),
    "moe_gu": P(None, AXIS_TP),    # (L, E->tp, 2*hidden, dim), merged up+gate
    "moe_down": P(None, AXIS_TP),  # (L, E->tp, dim, hidden)
}


def param_pspecs(params: dict[str, Any],
                 moe_sharding: str = "slice") -> dict[str, Any]:
    """PartitionSpec pytree (prefix) matching a params dict.

    moe_sharding: "slice" (hidden-dim TP inside every expert, the default) or
    "expert" (whole experts over tp — see _EP_SPECS)."""
    assert moe_sharding in ("slice", "expert"), moe_sharding
    def stack(names):
        blocks = {k: _BLOCK_SPECS[k] for k in names}
        if moe_sharding == "expert":
            blocks.update({k: v for k, v in _EP_SPECS.items() if k in blocks})
        return blocks

    from ..models.params import stack_names

    return {
        "embedding": P(),  # replicated, root-only-F32 in reference (transformer.cpp:496)
        # "blocks", a leading dense stack, a stack a run of like layers
        **{st: stack(params[st]) for st in stack_names(params)},
        "rms_final": P(),
        "wcls": P(AXIS_TP),  # (vocab->tp, dim); logits all-gathered in forward
    }


def kv_cache_pspec(seq_axis: str | None = None) -> P:
    """Cache (L, B, hk, S, hs): batch on dp, heads on tp (KvCacheSlice),
    optionally S on sp. dp/sp of size 1 make those entries no-ops."""
    from .mesh import AXIS_DP

    return P(None, AXIS_DP, AXIS_TP, seq_axis)


def kv_cache_pspec_for_mesh(mesh) -> P:
    """Cache pspec for a mesh: sequence axis sharded iff the mesh has sp > 1."""
    from .mesh import AXIS_SP

    return kv_cache_pspec(AXIS_SP if mesh.shape.get(AXIS_SP, 1) > 1 else None)


def effective_kv_heads(spec: ModelSpec, tp: int) -> int:
    """KV-head count after TP replication.

    The reference hard-fails when nSlices > nKvHeads (transformer.cpp:108-111), which
    blocks 405B-class GQA models (8 KV heads) on pods with 16+ chips. Here the standard
    GQA trick lifts the limit: when tp > n_kv_heads, each KV head is replicated across
    tp/n_kv_heads adjacent shards (shard j holds KV head j*n_kv_heads//tp), so every
    shard's query-head slice finds its KV head locally. wk/wv rows and the KV cache head
    axis are expanded to `tp` heads at distribution time (parallel/tp.py shard_params).
    """
    if tp <= spec.n_kv_heads:
        return spec.n_kv_heads
    assert tp % spec.n_kv_heads == 0, (
        f"tp={tp} must be a multiple of n_kv_heads={spec.n_kv_heads} to replicate "
        "KV heads evenly")
    return tp


def check_divisibility(spec: ModelSpec, tp: int, sp: int = 1,
                       moe_sharding: str = "slice") -> None:
    """Even-division checks that replace the reference's 2^n assumption and its
    nSlices <= nKvHeads limit (transformer.cpp:108-111; lifted via KV-head
    replication, see effective_kv_heads)."""
    hk = effective_kv_heads(spec, tp)  # asserts tp % n_kv_heads when replicating
    assert hk % tp == 0, (
        f"tp={tp} must divide n_kv_heads={spec.n_kv_heads} (or be a multiple of it "
        "for KV-head replication)")
    assert spec.dim % tp == 0
    assert spec.vocab_size % tp == 0
    # every kind of layer's heads (the spec's own where it states no kinds)
    for ks in spec.kind_specs():
        assert ks.n_heads % tp == 0, (
            f"tp={tp} must divide n_heads={ks.n_heads}")
        if (spec.dim // tp) % 32 or (ks.o_dim // tp) % 32:
            # q_dim (n_heads x head_size) is wo's in-axis; it is dim unless
            # the model states its head size
            raise AssertionError(
                "tp slice must keep 32-wide quant blocks intact")
    for width in (spec.lead_hidden_dim, spec.shared_hidden_dim):
        if width % tp or (width // tp) % 32:
            raise AssertionError("tp slice must keep 32-wide quant blocks intact")
    if moe_sharding == "expert" and spec.is_moe:
        assert spec.n_experts % tp == 0, (
            f"expert sharding: tp={tp} must divide n_experts={spec.n_experts}")
    else:
        # hidden dim is TP-sliced (dense FFN always; MoE experts in slice mode)
        assert spec.hidden_dim % tp == 0
        if (spec.hidden_dim // tp) % 32:
            raise AssertionError("tp slice must keep 32-wide quant blocks intact")
    assert spec.seq_len % sp == 0, (
        f"sp={sp} must divide seq_len={spec.seq_len} (sequence-sharded KV cache)")
