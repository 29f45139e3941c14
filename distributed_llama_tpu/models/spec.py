"""Model hyperparameter spec — TPU-native equivalent of TransformerSpec.

Mirrors the reference header schema (src/transformer.hpp:10-90, parsing at
src/transformer.cpp:12-148): same arch types, activation enum, rope types, derived
head_size/kv_dim, seq-len clamping, and the `.m` header key numbering (used by
formats/mfile.py).
"""

from __future__ import annotations

import enum
import math
from dataclasses import dataclass, fields, replace
from typing import NamedTuple


class ArchType(enum.IntEnum):
    """Reference: src/transformer.hpp:44-48 (also the legacy file magics)."""

    LLAMA = 0xABCD00
    GROK1 = 0xABCD01
    MIXTRAL = 0xABCD02


class HiddenAct(enum.IntEnum):
    GELU = 0
    SILU = 1
    RELU = 2


class RouterInput(enum.IntEnum):
    """Where a routed block's router reads: the second norm's output (Mixtral,
    Grok-1) or the block's own input, before the first norm."""

    FFN_NORM = 0
    BLOCK_INPUT = 1


class RouterScore(enum.IntEnum):
    """What a routed block's router makes of its logits before it takes the
    k largest: a softmax over all experts (Mixtral, Grok-1) or a sigmoid of
    each (the DeepSeek-V3 graph)."""

    SOFTMAX = 0
    SIGMOID = 1


class RopeType(enum.IntEnum):
    UNKNOWN = -1
    LLAMA = 0
    FALCON = 1
    LLAMA3_1 = 2
    # interleaved pairs as LLAMA, YaRN's frequencies (ops/rope.py)
    YARN = 3
    # half-split pairs as FALCON, YaRN's frequencies
    YARN_NEOX = 4
    # no rotation at all (a kind of layer without positions, "nope"): q and k
    # pass as projected, the tables are made and not read
    NONE = 5


# .m header key ids (reference: src/transformer.hpp:10-30 / converter/writer.py:109-130)
class HeaderKey(enum.IntEnum):
    VERSION = 0
    ARCH_TYPE = 1
    DIM = 2
    HIDDEN_DIM = 3
    N_LAYERS = 4
    N_HEADS = 5
    N_KV_HEADS = 6
    N_EXPERTS = 7
    N_ACTIVE_EXPERTS = 8
    VOCAB_SIZE = 9
    SEQ_LEN = 10
    HIDDEN_ACT = 11
    ROPE_THETA = 12
    WEIGHTS_FLOAT_TYPE = 13
    ROPE_SCALING_FACTOR = 14
    ROPE_SCALING_LOW_FREQ_FACTOR = 15
    ROPE_SCALING_HIGH_FREQ_FACTOR = 16  # reference spells this "FACTORY"
    ROPE_SCALING_ORIG_MAX_SEQ_LEN = 17
    ROPE_TYPE = 18
    # keys below are this repo's own (the reference loader stops at 18); a file
    # that has none of them reads exactly as before
    HEAD_DIM = 19
    SLIDING_WINDOW = 20
    ROUTER_INPUT = 21
    NORM_EPS_E9 = 22  # rms eps in units of 1e-9 (header values are int32)
    # latent attention (kv_lora_rank > 0), a leading dense stack, the shared
    # expert, the router's score and a share of the experts, YaRN. Floats
    # ride as integers in units of 1e-6
    Q_LORA_RANK = 23
    KV_LORA_RANK = 24
    QK_NOPE_HEAD_DIM = 25
    QK_ROPE_HEAD_DIM = 26
    V_HEAD_DIM = 27
    LEAD_LAYERS = 28
    LEAD_HIDDEN_DIM = 29
    SHARED_HIDDEN_DIM = 30
    ROUTER_SCORE = 31
    ROUTER_RENORM = 50
    ROUTER_SCALE_E6 = 51
    ROUTER_WIDTH = 52
    EXPERT_OFFSET = 53
    YARN_BETA_FAST_E6 = 54
    YARN_BETA_SLOW_E6 = 55
    YARN_MSCALE_E6 = 56
    YARN_MSCALE_ALL_DIM_E6 = 57
    # per-layer kinds as bit masks, 30 layers a word: bit l of word l // 30.
    # No word present means every layer (the default of both lists)
    ROPE_LAYERS_0 = 32
    WINDOW_LAYERS_0 = 40
    # a rotary width under the head size, the tables' own factor, the
    # per-head output gate
    ROTARY_DIM = 58
    ROPE_TABLE_SCALE_E6 = 59
    ATTN_GATE = 60
    # kinds of attention layer (ModelSpec.kinds): how many, then which kind
    # each layer is, 15 layers a word at two bits a layer (up to four kinds),
    # then kind k's fields at KIND_0 + KIND_STRIDE x k + its offset
    # (formats/mfile.py _KIND_FIELDS)
    N_KINDS = 61
    # QK-norm (an RMS norm of each head's q and k ahead of the rotation,
    # tensors rms_qh and rms_kh) and the router's selection bias (tensor
    # router_bias: added to the scores for the choice, left out of the
    # weights)
    QK_NORM = 62
    ROUTER_BIAS = 63
    # the multipliers of a graph that states them (embedding, each residual
    # branch, a divisor of the logits), a stated attention scale, and the
    # snapshot pool of a model whose state is a matrix a head, in units of
    # 1e-6 where they are floats (0: the key's default)
    EMBEDDING_MULTIPLIER_E6 = 80
    RESIDUAL_MULTIPLIER_E6 = 81
    LOGITS_SCALING_E6 = 82
    ATTN_SCALE_E9 = 83
    STATE_SNAPSHOTS = 84
    LAYER_KINDS_0 = 64  # ..79
    KIND_0 = 100
    # a kind's further fields (a delta-rule mixer's, latent attention's as a
    # kind), at KIND_MORE_0 + KIND_STRIDE x k + its offset
    # (formats/mfile.py _KIND_MORE_FIELDS)
    KIND_MORE_0 = 200


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention factor: 0.1 x mscale x ln(factor) + 1."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


@dataclass(frozen=True)
class LayerKind:
    """One kind of layer of a model whose layers differ in more than a 0/1
    switch (ModelSpec.kinds): an attention layer's query heads, its window,
    its rotation, its latent row (kv_lora_rank > 0), or (conv_kernel > 0) a
    layer that holds a STATE and no keys and values at all: a gated short
    convolution, (ssm_state > 0) a state-space mixer (Mamba-2), or
    (kda_heads > 0) a delta-rule mixer (Kimi Delta Attention). Every field
    overrides the ModelSpec field of the same name for the layers of this
    kind (`ModelSpec.of_kind`)."""

    name: str
    n_heads: int
    sliding_window: int = 0  # 0: the layer attends every key
    rope_type: RopeType = RopeType.FALCON
    rope_theta: float = 10000.0
    rotary_dim: int = 0  # values of a head that are rotated; 0: all of them
    rope_scaling_factor: float = 0.0
    rope_scaling_orig_max_seq_len: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    rope_table_scale: float = 0.0  # cos and sin times this; 0: derived
    # taps of a gated short convolution: the layer's mixer is then
    #   [B, C, u] = conv_in h;  v = B * u;  c_p = sum_j conv_w[:, j] v_{p-k+1+j}
    #   (depthwise, causal, zeros before position 0);  out = conv_out (C * c)
    # and its state after position p is v's last conv_kernel - 1 rows, NOT a
    # list of positions: it stands beside the keys and values of the other
    # layers (models/forward.py StateCache). 0: an attention layer
    conv_kernel: int = 0
    # a state-space mixer (Mamba-2; models/forward.py _ssm_mixer): ssm_heads
    # heads of ssm_head_dim values, each with a running MATRIX of
    # ssm_head_dim x ssm_state that sums every earlier position, B and C of
    # ssm_groups x ssm_state shared by a group's heads, ahead of them a
    # depthwise causal convolution of conv_kernel taps (with a bias) over
    # the ssm_heads x ssm_head_dim + 2 x ssm_groups x ssm_state values of
    # [x | B | C]. Its state after position p: the matrices, and the
    # convolution's last conv_kernel - 1 input rows. 0: no such mixer
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    # a delta-rule mixer (Kimi Delta Attention; models/forward.py
    # _kda_mixer): kda_heads heads, each with a running MATRIX S of
    # kda_key_dim x kda_value_dim in float32 that is decayed BY CHANNEL
    # (a decay a key channel a position) and corrected by the delta rule
    #   S <- Diag(a_t) S;  u_t = beta_t (v_t - S^T k_t);  S <- S + k_t u_t^T
    # ahead of it a depthwise causal convolution of conv_kernel taps (no
    # bias) over the kda_heads x (2 kda_key_dim + kda_value_dim) values of
    # [q | k | v]; the decay and the output gate each through a pair of
    # projections of rank kda_rank. Its state after position p: the
    # matrices, and the convolution's last conv_kernel - 1 input rows
    kda_heads: int = 0
    kda_key_dim: int = 0
    kda_value_dim: int = 0
    kda_rank: int = 0
    # latent attention as a KIND (the attention layers beside a model's
    # state layers): the fields of ModelSpec's latent attention, stated
    # here where the model's other layers hold no keys at all.
    # q_lora_rank 0: q through ONE projection wq and no norm of its own
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0


class Run(NamedTuple):
    """Consecutive like layers: one stack of `params` and one `lax.scan`."""

    name: str  # the stack's key in `params`
    first: int  # its first layer's index in the model
    depth: int
    # index into ModelSpec.kinds; None: the model has none, or the run holds
    # layers of several (a model with state layers: `ModelSpec.mixed`)
    kind: int | None
    lead: bool  # leading dense layers (ModelSpec.lead_layers)


@dataclass(frozen=True)
class ModelSpec:
    arch_type: ArchType
    dim: int
    hidden_dim: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    vocab_size: int
    seq_len: int
    n_experts: int = 0
    n_active_experts: int = 0
    hidden_act: HiddenAct = HiddenAct.SILU
    rope_theta: float = 10000.0
    rope_type: RopeType = RopeType.UNKNOWN
    rope_scaling_factor: float = 0.0
    rope_scaling_low_freq_factor: float = 0.0
    rope_scaling_high_freq_factor: float = 0.0
    rope_scaling_orig_max_seq_len: int = 0
    orig_seq_len: int = 0
    version: int = 0
    norm_eps: float = 1e-5
    # width of one attention head; 0 = dim // n_heads (every model whose q
    # projection is dim wide, and every `.m` file written before the key existed)
    head_dim: int = 0
    # keys a windowed layer attends: position i reads j with i - window < j <= i.
    # 0 = no layer has a window
    sliding_window: int = 0
    # per-layer kinds, one 0/1 entry a layer. () = the default: every layer
    # rotates q and k, and (where sliding_window > 0) every layer is windowed
    rope_layers: tuple[int, ...] = ()
    window_layers: tuple[int, ...] = ()
    router_input: RouterInput = RouterInput.FFN_NORM
    # --- latent attention (kv_lora_rank > 0; the DeepSeek-V3 graph): q through
    # a rank-q_lora_rank pair of projections, keys and values through ONE
    # latent row a token of kv_lora_rank values (normed) and qk_rope_head_dim
    # rotated ones, shared by all heads; a head's q and k are qk_nope_head_dim
    # + qk_rope_head_dim wide, its v v_head_dim. n_kv_heads is 1. A model
    # with state layers states these on its attention KIND (LayerKind), and
    # q_lora_rank 0 there is a q through one projection `wq`
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # the first lead_layers layers are dense (FFN width lead_hidden_dim) and
    # stand in a stack and a scan of their own ahead of the n_layers -
    # lead_layers routed ones. 0 = one stack
    lead_layers: int = 0
    lead_hidden_dim: int = 0
    # a routed block's shared expert: a dense FFN of this width added to the
    # routed sum. 0 = none
    shared_hidden_dim: int = 0
    router_score: RouterScore = RouterScore.SOFTMAX
    router_renorm: bool = True  # the k taken scores divided by their sum
    router_scale: float = 1.0  # the routing weights times this
    # the router's width where it is wider than the n_experts held: this
    # checkpoint holds experts [expert_offset, expert_offset + n_experts) of
    # router_width, and a token's assignments to the others add nothing here.
    # 0 = n_experts
    router_width: int = 0
    expert_offset: int = 0
    # YaRN (rope_type YARN): rope_scaling_factor over
    # rope_scaling_orig_max_seq_len, the correction range from the two betas
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0
    # values of a head the rotation covers, from its first: the rest pass
    # unrotated (a partial rotary factor). 0 = the whole head
    rotary_dim: int = 0
    # what the rotation's cos and sin are multiplied by (YaRN's attention
    # factor where the model states it as a number). 0 = derived: the ratio
    # of the two mscales under YaRN, else 1
    rope_table_scale: float = 0.0
    # a per-head output gate: every head's attention output times
    # sigmoid(wg h) of the normed block input, one value a head a token,
    # before wo (tensor `wg`, (n_heads, dim))
    attn_gate: bool = False
    # --- kinds of attention layer. Where layers differ in head count or
    # rotation their tensors differ in shape, so each RUN of like layers
    # stands in a stack and a scan of its own (`runs`): `kinds` lists the
    # kinds, `layer_kinds` names each layer's by index. () = one kind, stated
    # by the fields above; rope_layers / window_layers are then the 0/1
    # switches within it, and are not used together with kinds
    kinds: tuple[LayerKind, ...] = ()
    layer_kinds: tuple[int, ...] = ()
    # a kind's convolution (LayerKind.conv_kernel), on the spec `of_kind`
    # makes of it; 0 on a model's own spec
    conv_kernel: int = 0
    # QK-norm: q and k RMS-normed over each head's values (one weight vector
    # of head_size each a layer, rms_qh and rms_kh) before the rotation
    qk_norm: bool = False
    # the router takes its k largest over score + router_bias (a tensor, one
    # value an expert a layer) and its weights from the scores alone
    router_bias: bool = False
    # a kind's state-space mixer (LayerKind.ssm_*), on the spec `of_kind`
    # makes of it; 0 on a model's own spec
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    # a kind's delta-rule mixer (LayerKind.kda_*), likewise
    kda_heads: int = 0
    kda_key_dim: int = 0
    kda_value_dim: int = 0
    kda_rank: int = 0
    # the graph's stated multipliers: the embedding's rows times the first,
    # each residual branch (mixer, FFN) times the second before it joins the
    # stream, the logits DIVIDED by the third. 1.0: not in the program
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # what q . k is multiplied by where the model states it as a number
    # (an `attention_multiplier`). 0: derived (`attn_scale`)
    attn_multiplier: float = 0.0
    # entries of the snapshot pool of a model whose state layers hold a
    # matrix a head: which blocks carry a snapshot is then the cache
    # manager's decision (docs/PAGED_KV.md "Typed block payload"). 0: every
    # block carries its own (a convolution's state is two rows)
    state_snapshots: int = 0

    # --- derived (reference: transformer.cpp:102-106) ---
    def _latent_kind(self):
        """Where latent attention's fields stand: the kind that states a
        latent row (a model with state layers), else this spec."""
        return next((k for k in self.kinds if k.kv_lora_rank), self)

    @property
    def latent(self) -> bool:
        return self._latent_kind().kv_lora_rank > 0

    @property
    def head_size(self) -> int:
        """Width of a head's q (and k): where attention is latent, the part
        that is not rotated and the part that is."""
        if self.latent:
            k = self._latent_kind()
            return k.qk_nope_head_dim + k.qk_rope_head_dim
        return self.head_dim or self.dim // self.n_heads

    @property
    def rope_width(self) -> int:
        """Values of a head the rotation covers (the tables' width x 2)."""
        if self.latent:
            return self._latent_kind().qk_rope_head_dim
        return self.rotary_dim or self.head_size

    @property
    def o_dim(self) -> int:
        """Width of wo's input: n_heads x the width of a head's v."""
        k = self._latent_kind()
        return k.n_heads * (k.v_head_dim if self.latent else self.head_size)

    @property
    def cache_widths(self) -> tuple[int, int]:
        """Values a token holds a layer a kv head on the cache's two sides.
        Latent: ONE row [c (kv_lora_rank) ; k_pe (qk_rope_head_dim)], padded
        with zeros to whole lanes of 128 (576 to 640: the chip's tiled memory
        pads the minor axis so whatever is asked for), and no second side."""
        if self.latent:
            k = self._latent_kind()
            return (-(-(k.kv_lora_rank + k.qk_rope_head_dim) // 128) * 128, 0)
        return self.head_size, self.head_size

    def cache_row_bytes(self, itemsize: int) -> int:
        """Bytes a token holds a layer, all kv heads, both sides."""
        return self.n_kv_heads * sum(self.cache_widths) * itemsize

    @property
    def mixed(self) -> bool:
        """Whether some kind of layer holds a state and no keys and values
        (a convolution, a state-space mixer, a delta-rule mixer): such a
        model's layers stand in
        TWO runs at most (`runs`), each one scan whose body picks the mixer
        by a per-layer flag."""
        return any(k.conv_kernel for k in self.kinds)

    @property
    def ssm(self) -> bool:
        """Whether the state layers hold a MATRIX a head beside the
        convolution's rows (`state_matrix`): state-space mixers, whose
        matrix decays by a scalar, and delta-rule mixers, whose matrix
        decays by channel and is corrected. What the cache manager does for
        one (stride snapshots, `held`, the host's word) it does for both."""
        return self.state_matrix is not None

    @property
    def state_layers(self) -> tuple[int, ...]:
        """The layers that hold a state and no keys and values."""
        return tuple(l for l, k in enumerate(self.layer_kinds)
                     if self.kinds[k].conv_kernel)

    def _state_kind(self):
        """The state kind's fields: the kind of a model's own spec, or this
        spec where `of_kind` made it of one."""
        return next((k for k in self.kinds if k.conv_kernel), self)

    @property
    def ssm_inner(self) -> int:
        """Width of a state-space mixer's x and gate: heads x head size."""
        k = self._state_kind()
        return k.ssm_heads * k.ssm_head_dim

    @property
    def state_width(self) -> int:
        """Values of one row of a state layer's tail: `dim` of a gated short
        convolution (its v), and of a state-space mixer the convolution's
        input [x | B | C]."""
        k = self._state_kind()
        if k.kda_heads:  # the convolution's input [q | k | v]
            return k.kda_heads * (2 * k.kda_key_dim + k.kda_value_dim)
        if not k.ssm_state:
            return self.dim
        return k.ssm_heads * k.ssm_head_dim + 2 * k.ssm_groups * k.ssm_state

    @property
    def state_matrix(self) -> tuple[int, int, int] | None:
        """(heads, rows, columns) of the running matrix a state layer holds
        a sequence in float32: (heads, head size, state size) of a
        state-space mixer, (heads, key size, value size) of a delta-rule
        mixer; None: a convolution holds none."""
        k = self._state_kind()
        if k.kda_heads:
            return k.kda_heads, k.kda_key_dim, k.kda_value_dim
        if not k.ssm_state:
            return None
        return k.ssm_heads, k.ssm_head_dim, k.ssm_state

    @property
    def cache_layers(self) -> tuple[int, ...]:
        """The layers that own rows of the key-value cache, in layer order:
        the cache's layer axis is as deep as this is long."""
        state = set(self.state_layers)
        return tuple(l for l in range(self.n_layers) if l not in state)

    @property
    def state_rows(self) -> int:
        """Rows of `state_width` values a state layer holds a sequence, its
        tail: the taps less one (a model has one state kind)."""
        return max([k.conv_kernel for k in self.kinds]
                   + [self.conv_kernel, 1]) - 1

    def state_block_bytes(self, itemsize: int) -> int:
        """Bytes of one snapshot: every state layer's state at a block's last
        position, the tail's rows at `itemsize` and the matrix, where the
        kind has one, in float32. A convolution's lies in every block of the
        pool; a state-space model's in the blocks the cache manager gives an
        entry of the snapshot pool (`state_snapshots`)."""
        matrix = math.prod(self.state_matrix or (0,)) * 4
        return len(self.state_layers) * (
            self.state_rows * self.state_width * itemsize + matrix)

    @property
    def attn_scale(self) -> float:
        """What q . k is multiplied by before the softmax: head_size^-0.5,
        times YaRN's mscale(factor, mscale_all_dim)^2 where the model states
        one (the DeepSeek-V3 graph's softmax_scale); the stated number where
        the model gives one (`attn_multiplier`)."""
        if self.attn_multiplier:
            return self.attn_multiplier
        scale = self.head_size ** -0.5
        if (self.rope_type in (RopeType.YARN, RopeType.YARN_NEOX)
                and self.yarn_mscale_all_dim
                and self.rope_scaling_factor > 1.0):
            m = yarn_mscale(self.rope_scaling_factor, self.yarn_mscale_all_dim)
            scale *= m * m
        return scale

    @property
    def n_router(self) -> int:
        """Experts the router scores."""
        return self.router_width or self.n_experts

    @property
    def block_layers(self) -> int:
        """Layers of the main stack, behind the leading ones."""
        return self.n_layers - self.lead_layers

    @property
    def q_dim(self) -> int:
        """Width of the q projection (and of wo's input): n_heads x head_size."""
        return self.n_heads * self.head_size

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_size

    def layer_rope(self) -> tuple[int, ...]:
        """1 where layer l rotates q and k, one entry a layer."""
        return tuple(self.rope_layers) or (1,) * self.n_layers

    def of_kind(self, kind: int | None) -> "ModelSpec":
        """The spec the layers of one kind are computed with: this one with
        the kind's fields in place of its own, and one kind of layer."""
        if kind is None:
            return self
        over = {f.name: getattr(self.kinds[kind], f.name)
                for f in fields(LayerKind) if f.name != "name"}
        return replace(self, kinds=(), layer_kinds=(), **over)

    def kind_specs(self) -> tuple["ModelSpec", ...]:
        """The spec of each kind of layer; this one where it states none."""
        return tuple(self.of_kind(k) for k in range(len(self.kinds))) or (
            self,)

    def runs(self) -> tuple[Run, ...]:
        """The model's layers as runs of like layers, in layer order: the
        leading dense layers ("lead") and the rest ("blocks"), each cut
        further wherever the kind of layer changes ("blocks", "blocks1", ..:
        a model of one kind keeps the two names it always had)."""
        kinds = self.layer_kinds or (None,) * self.n_layers
        if self.mixed:
            # one run behind the leading layers whatever the kinds: a scan
            # a run of like layers would be 13 scans for LFM2's 24 layers
            kinds = (None,) * self.n_layers
        out: list[Run] = []
        count = {True: 0, False: 0}
        for l in range(self.n_layers):
            lead = l < self.lead_layers
            if out and (out[-1].kind, out[-1].lead) == (kinds[l], lead):
                out[-1] = out[-1]._replace(depth=out[-1].depth + 1)
                continue
            name = ("lead" if lead else "blocks") + (
                str(count[lead]) if count[lead] else "")
            count[lead] += 1
            out.append(Run(name, l, 1, kinds[l], lead))
        return tuple(out)

    def layer_window(self) -> tuple[int, ...]:
        """The window of layer l in keys, 0 where it attends every key."""
        if self.kinds:
            return tuple(self.kinds[k].sliding_window
                         for k in self.layer_kinds)
        if not self.sliding_window:
            return (0,) * self.n_layers
        on = tuple(self.window_layers) or (1,) * self.n_layers
        return tuple(self.sliding_window if w else 0 for w in on)

    @property
    def q_group(self) -> int:
        """GQA group size: query heads per kv head."""
        return self.n_heads // self.n_kv_heads

    def resolved(self, max_seq_len: int = 0) -> "ModelSpec":
        """Fill in defaults the way loadSpecFromFile does (transformer.cpp:88-106)."""
        spec = self
        if spec.rope_type == RopeType.UNKNOWN:
            if spec.arch_type == ArchType.LLAMA:
                spec = replace(spec, rope_type=RopeType.LLAMA)
            elif spec.arch_type in (ArchType.GROK1, ArchType.MIXTRAL):
                spec = replace(spec, rope_type=RopeType.FALCON)
            else:
                raise ValueError(f"cannot resolve rope type for arch {spec.arch_type}")
        orig = spec.orig_seq_len or spec.seq_len
        seq = spec.seq_len
        if max_seq_len > 0 and seq > max_seq_len:
            seq = max_seq_len
        spec = replace(spec, seq_len=seq, orig_seq_len=orig)
        assert spec.head_dim or spec.dim % spec.n_heads == 0, (spec.dim, spec.n_heads)
        assert spec.n_heads % spec.n_kv_heads == 0, (spec.n_heads, spec.n_kv_heads)
        for name in ("rope_layers", "window_layers"):
            kinds = getattr(spec, name)
            assert len(kinds) in (0, spec.n_layers), (name, len(kinds), spec.n_layers)
        assert 0 <= spec.lead_layers < spec.n_layers, spec.lead_layers
        if spec.latent:
            assert spec.n_kv_heads == 1, "a latent row is one kv head"
            assert not (spec.rope_layers or spec.sliding_window), (
                "latent attention's layers have no window and no 0/1 "
                "rotation switch (beside state layers it is stated as a "
                "kind)")
        if spec.lead_layers:
            # the 0/1 switches ride in ONE scan's xs; a model whose leading
            # layers differ from the rest states its kinds (runs of their own)
            assert not (spec.rope_layers or spec.sliding_window), (
                "layers of two kinds stand in one stack")
        assert 0 <= spec.rotary_dim <= spec.head_size and not (
            spec.rotary_dim % 2), spec.rotary_dim
        if spec.kinds:
            assert len(spec.layer_kinds) == spec.n_layers and all(
                0 <= k < len(spec.kinds) for k in spec.layer_kinds), (
                f"layer_kinds {spec.layer_kinds} names {spec.n_layers} "
                f"layers' kinds among {len(spec.kinds)}")
            assert not (spec.kv_lora_rank or spec.rope_layers
                        or spec.window_layers or spec.sliding_window), (
                "kinds of layer state their own windows, rotation and "
                "latent row")
            assert not spec.latent or (spec.mixed and all(
                k.kv_lora_rank or k.conv_kernel for k in spec.kinds)), (
                "latent attention is a kind only beside state layers: "
                "kinds of attention layer share per-head keys and values")
            assert spec.arch_type != ArchType.GROK1 and (
                spec.head_dim or spec.latent), (
                "kinds of layer share a stated head size")
            assert not (spec.mixed and spec.attn_gate), (
                "the per-head gate is not stated beside convolution layers")
            if spec.mixed:
                # the two mixers' tensors stand in one stack a run under
                # their own names, and the pool has a layer to page
                assert (len([k for k in spec.kinds if k.conv_kernel]) == 1
                        and len(spec.kinds) == 2), (
                    "a model with state layers has one state kind (a gated "
                    "short convolution, a state-space mixer or a delta-rule "
                    "mixer) and one attention kind (per-head keys and "
                    "values, or a latent row)")
                assert spec.cache_layers, (
                    "a model with state layers has an attention layer too")
            for k in spec.kinds:
                assert not k.ssm_state or (
                    k.conv_kernel > 1 and k.ssm_heads and k.ssm_head_dim
                    and k.ssm_groups == 1), (
                    "a state-space kind states its heads, head size, state "
                    "size and taps, and one group of B and C", k)
                assert not k.kda_heads or (
                    k.conv_kernel > 1 and k.kda_key_dim and k.kda_value_dim
                    and k.kda_rank and not k.ssm_state), (
                    "a delta-rule kind states its heads, key and value "
                    "sizes, taps and the gates' rank, and is no "
                    "state-space kind", k)
                assert not k.kv_lora_rank or (
                    not k.conv_kernel and k.qk_nope_head_dim
                    and k.v_head_dim and not k.sliding_window), (
                    "a latent kind states its row and its heads' widths, "
                    "holds no state and has no window", k)
                assert k.n_heads % spec.n_kv_heads == 0, (k, spec.n_kv_heads)
                assert (0 <= k.rotary_dim <= spec.head_size
                        and not k.rotary_dim % 2), k
                assert k.rope_type != RopeType.UNKNOWN, k
        else:
            assert not spec.layer_kinds, "layer_kinds without kinds"
        assert spec.ssm == bool(spec.state_snapshots) or not spec.kinds, (
            "a model whose state is a matrix a head states its snapshot "
            "pool (state_snapshots), and no other model does")
        assert not (spec.attn_gate and spec.latent), (
            "the per-head gate is not stated for latent attention")
        assert (spec.expert_offset + spec.n_experts
                <= max(spec.n_router, spec.n_experts))
        return spec

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0
