"""The family of granite-4.0-h-small (IBM; `model_type` `granitemoehybrid`):
which `ModelSpec` the program is given, which tensors are drawn, in which
stack they stand, and the plain reference, the block graph in jax.numpy
float32 with the state-space recurrence as a `lax.scan` over POSITIONS (no
chunks, no kernel, no cache). The harness reaches it through
`cells.load_family` and calls `model_spec`, `tensor_shapes`, `stacks`,
`program_params` and `logits_at`.

The layer l, input x (T x hidden), eps `rms_norm_eps`, no bias but the
convolution's, m = `residual_multiplier`:

    x_0 = `embedding_multiplier` * E[token]
    a   = x + m * mixer_l(RMSNorm(x; rms_att))           `input_layernorm`
    x'  = a + m * (moe(y) + shared(y)),  y = RMSNorm(a; rms_ffn)
                                                  `post_attention_layernorm`
    logits = (RMSNorm(x_L; rms_final) E^T) / `logits_scaling`      (tied)

    mixer, `layer_types[l]` "mamba" (Mamba-2; inner = `mamba_expand` x hidden
    = `mamba_n_heads` heads of `mamba_d_head`; N = `mamba_d_state`; one
    group):
        [z | u | dt] = ssm_in h       widths inner | inner + 2 N | heads
        u'_p = silu(b + sum_{j=0..3} w[:, j] u_{p-3+j})     depthwise, causal,
                                      `mamba_d_conv` 4 taps, zeros before 0
        [x | B | C] = u'              widths inner | N | N; x as heads of P
        d_p  = softplus(dt_p + dt_bias);  A = -exp(a_log)   a scalar a head
        H_p  = exp(d_p A) H_{p-1} + d_p x_p B_p^T           H (P, N) a head,
                                                            H_{-1} = 0
        y_p  = H_p C_p + D x_p
        out  = ssm_out RMSNorm(y * silu(z); ssm_norm)  over all inner values,
                                                       the gate BEFORE it
    mixer, "attention": q = wq h (`num_attention_heads` heads of hidden /
        heads), k, v = wk h, wv h (`num_key_value_heads`); NO rotation
        (`position_embedding_type` "nope"); scores q . k times
        `attention_multiplier` (1/128, not 128^-0.5), causal, softmax; query
        head n reads kv head n // 4; out = wo att.
    moe: r = router y (`num_local_experts` wide, float32); the
        `num_experts_per_tok` largest; weights = softmax over those logits;
        out = sum_e w_e down_e (silu(gate_e y) * up_e y), width
        `intermediate_size`.
    shared: sh_down (silu(sh_gate y) * sh_up y), width
        `shared_intermediate_size`, added unweighted.

What the published configuration names and does not spell is listed in the
configuration file's `assumed`, each ONE value here and in the program's
`ModelSpec`: the order [z | u | dt] and [x | B | C], the gate before the
norm, one norm over all inner values, softmax over the chosen ten, the tie.

Departures from the published description: (a) the tensors carry the
program's loader's names (rms_att, rms_ffn, ssm_in, ssm_conv_w, ssm_conv_b,
ssm_dt_bias, ssm_a_log, ssm_d, ssm_norm, ssm_out, wq .. wo, router, moe_up,
moe_gate, moe_down, sh_gate, sh_up, sh_down); (b) `mamba_chunk_size` 256 is
how the published kernel blocks its work and no part of the function: the
reference runs no chunks at all, the program's chunks are its dispatches'
(64, 8, 1), and 256 is only where the program's snapshot stride comes from;
(c) the program takes its routing weights as a softmax over ALL 72 logits
renormalised over the chosen ten, the same numbers; (d) the program applies
the stated attention scale as q times (1/128) x 128^0.5 ahead of kernels
that multiply by 128^-0.5, one more rounding of q in bfloat16; (e) the
layers stand in ONE stack and every layer is DRAWN with both mixers'
tensors, of which it uses its kind's (`program_params` leaves the others
out, each kind's stacked over its own layers); (f) the harness hands a cut
of the weights to `model_spec` as a DEPTH alone, which is read so (`_cut`):
the file's own depth is the whole file; 2 is the first layer, a Mamba layer,
and the first attention layer (0 and 5 of the published pattern).

What the harness draws and this family maps, in ONE function that
`program_params` and `logits_at` both call (`mapped`): `weights._draw` gives
every unquantized tensor 1 + 0.02 N, under which no head decays, every step
is 1.3, the four taps are alike and D is 1. Mapped: A = 1 .. 16 spread over
the heads times what was drawn (a_log its logarithm), dt_bias so that
softplus gives 0.001 .. 0.1 log-spaced over the heads (a per-step decay from
0.999 down to 0.2: heads that remember a thousand positions and heads that
forget in five), D = 1 + 25 (drawn - 1) (1 +- 0.5), the taps `TAPS` times
what was drawn (four clearly different ones, the newest the largest), the
convolution's bias 0.1 + 5 (drawn - 1). The tie: the head `wcls` is drawn as
Q40 and its dequantized values are the embedding of program and reference
alike.

How it blocks the work: as the other families: one layer's tensors on the
device at a time, each row through it alone, padded with token 3 to the next
multiple of 32 (64 past 1024), queries in blocks of 1024 against one kv
head's keys, one expert dequantized at a time, the head in slices of the
vocabulary. A position's router margin is the tenth largest logit less the
eleventh, over the spread (standard deviation) of the position's 72.

`precision`: "float32" is the reference; "bfloat16", "fp8" and "q80" round
the operands of every matrix product through `weights.rounder` (controls);
seven more are float32 with one mechanism changed, what a program that lost
it would compute: "ssm_state_off" (H zeroed at every dispatch: a position
that starts one, the prompt's chunks of 64, 8 and 1 and every forced token
behind it, sees no earlier H), "decay_off" (A = 0), "dskip_off" (D = 0),
"taps_reversed" (w[:, ::-1]), "gate_after_norm" (RMSNorm(y) * silu(z)),
"resid_mult_off" (m = 1), "attn_scale_sqrt" (128^-0.5 for 1/128).
"""

from __future__ import annotations

import functools

import numpy as np

from benchmark import weights as W

Q_BLOCK = 1024  # queries scored at once against one kv head's keys
HEAD_SLICES = 8
PAD_TOKEN = 3
PREFILL_CHUNKS = (64, 8, 1)  # how the program cuts a prompt into dispatches
MECHANISM_CONTROLS = ("ssm_state_off", "decay_off", "dskip_off",
                      "taps_reversed", "gate_after_norm", "resid_mult_off",
                      "attn_scale_sqrt")
MAMBA, ATTN = "mamba", "attention"
TAPS = (0.2, -0.4, 0.6, 1.0)  # times the drawn 1 + 0.02 N: oldest first
A_RANGE = (1.0, 16.0)  # -A over the heads
DT_RANGE = (1e-3, 1e-1)  # softplus(dt_bias) over the heads, log-spaced
MAPPED = "mapped"  # a key of weights that `mapped` has been over
SSM = ("ssm_in", "ssm_conv_w", "ssm_conv_b", "ssm_dt_bias", "ssm_a_log",
       "ssm_d", "ssm_norm", "ssm_out")
ATTENTION = ("wq", "wk", "wv", "wo")


def _padded(n: int) -> int:
    step = 64 if n > 1024 else 32
    return -(-n // step) * step


def _checked(cfg: dict) -> None:
    """Refuse a file this family does not state."""
    n = cfg["layers_here"]
    types = cfg["layer_types"][:n]
    if set(types) - {MAMBA, ATTN} or len(types) != n or ATTN not in types:
        raise ValueError(f"granite_hybrid: layer_types {types} of {n} layers "
                         "(mamba and attention, one attention layer at least)")
    if types[0] != MAMBA:
        raise ValueError("granite_hybrid: the first layer is a Mamba layer "
                         "(the cut of two layers this family reads is it "
                         "and the first attention layer)")
    if (cfg["mamba_n_groups"] != 1 or not cfg["mamba_conv_bias"]
            or cfg["mamba_proj_bias"] or cfg["attention_bias"]):
        raise ValueError("granite_hybrid: one group of B and C, a bias in "
                         "the convolution and nowhere else; this file says "
                         "otherwise")
    if cfg["mamba_n_heads"] * cfg["mamba_d_head"] != (
            cfg["mamba_expand"] * cfg["hidden_size"]):
        raise ValueError("granite_hybrid: mamba_n_heads x mamba_d_head is "
                         "mamba_expand x hidden_size")
    if cfg["position_embedding_type"] != "nope" or not cfg[
            "tie_word_embeddings"]:
        raise ValueError("granite_hybrid: no rotation, a tied head")
    if cfg["hidden_size"] % cfg["num_attention_heads"]:
        raise ValueError("granite_hybrid: heads of hidden_size / "
                         "num_attention_heads")


def stacks(cfg: dict) -> list[tuple[str, int]]:
    _checked(cfg)
    return [("blocks", cfg["layers_here"])]


def _cut(cfg: dict) -> list[int]:
    """The layers that `num_hidden_layers` stands for (departure (f))."""
    depth, n = cfg["num_hidden_layers"], cfg["layers_here"]
    if depth == n:
        return list(range(n))
    if depth == 2:
        return [0, cfg["layer_types"].index(ATTN)]
    raise ValueError(f"granite_hybrid: a cut of {depth} of {n} layers is not "
                     "one this family can read from its depth (2 or the "
                     "whole)")


def one_layer_a_stack(cfg: dict, experts: int | None = None) -> dict:
    """The file cut to ONE Mamba layer and the attention layer, with
    `experts` experts where given: the same tensors in the same stack at a
    size a tool can draw that wants the parameter tree's structure and not
    its weight (`perf/aot_step.py`)."""
    out = {**cfg, "num_hidden_layers": 2, "layers_here": 2,
           "layer_types": [MAMBA, ATTN]}
    if experts:
        out["num_local_experts"] = experts
    return out


def model_spec(cfg: dict):
    """The program's ModelSpec for the file's keys: the two kinds of layer
    (`ModelSpec.kinds`: the state-space mixer, and attention without a
    rotation), each layer's kind, the three multipliers, the stated
    attention scale, the shared expert at its own width, the snapshot pool
    (the file's own key `state_snapshots`)."""
    from distributed_llama_tpu.models.spec import (ArchType, HiddenAct,
                                                   LayerKind, ModelSpec,
                                                   RopeType, RouterScore)

    _checked(cfg)
    layers = _cut(cfg)
    heads = cfg["num_attention_heads"]
    types = [cfg["layer_types"][l] for l in layers]
    names = sorted(set(types), reverse=True)  # "mamba" before "attention"
    kinds = tuple(
        LayerKind(name="mamba", n_heads=heads,
                  conv_kernel=cfg["mamba_d_conv"],
                  ssm_heads=cfg["mamba_n_heads"],
                  ssm_head_dim=cfg["mamba_d_head"],
                  ssm_state=cfg["mamba_d_state"],
                  ssm_groups=cfg["mamba_n_groups"]) if name == MAMBA else
        LayerKind(name="attention", n_heads=heads, rope_type=RopeType.NONE,
                  rope_theta=float(cfg["rope_theta"])) for name in names)
    return ModelSpec(
        arch_type=ArchType.MIXTRAL, dim=cfg["hidden_size"],
        hidden_dim=cfg["intermediate_size"], n_layers=len(layers),
        n_heads=heads, n_kv_heads=cfg["num_key_value_heads"],
        vocab_size=cfg["vocab_size"], seq_len=cfg["context"],
        hidden_act=HiddenAct.SILU, rope_type=RopeType.FALCON,
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        head_dim=cfg["hidden_size"] // heads,
        n_experts=cfg["num_local_experts"],
        n_active_experts=cfg["num_experts_per_tok"],
        router_score=RouterScore.SOFTMAX, router_renorm=True,
        shared_hidden_dim=cfg["shared_intermediate_size"],
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        residual_multiplier=float(cfg["residual_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]),
        attn_multiplier=float(cfg["attention_multiplier"]),
        state_snapshots=int(cfg["state_snapshots"])
        if MAMBA in types else 0,
        kinds=kinds if len(names) == 2 else (),
        layer_kinds=tuple(names.index(t) for t in types)
        if len(names) == 2 else (),
    ).resolved()


def tensor_shapes(cfg: dict) -> dict[str, tuple[tuple[int, ...], bool]]:
    """name -> (shape with the layer axis, drawn as Q40?), matrices (out,
    in), under the program's loader's names, the stack's prefix ahead. Every
    layer is drawn with both mixers' tensors (departure (e)); the embedding
    drawn is replaced by the head (the tie)."""
    # a program that cannot state this model fails here, before the weights
    # are drawn: the run then ends in a second with the import's message
    from distributed_llama_tpu.ops.pallas_ssd import ssd_step  # noqa: F401

    _checked(cfg)
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * (d // heads)
    e, h, sh = (cfg["num_local_experts"], cfg["intermediate_size"],
                cfg["shared_intermediate_size"])
    nh, state = cfg["mamba_n_heads"], cfg["mamba_d_state"]
    inner = nh * cfg["mamba_d_head"]
    cw = inner + 2 * state
    taps, v, n = cfg["mamba_d_conv"], cfg["vocab_size"], cfg["layers_here"]
    out = {"rms_final": ((d,), False), "embedding": ((v, d), False),
           "wcls": ((v, d), True)}
    own = {"ssm_in": ((n, inner + cw + nh, d), True),
           "ssm_conv_w": ((n, cw, taps), False),
           "ssm_conv_b": ((n, cw), False),
           "ssm_dt_bias": ((n, nh), False), "ssm_a_log": ((n, nh), False),
           "ssm_d": ((n, nh), False), "ssm_norm": ((n, inner), False),
           "ssm_out": ((n, d, inner), True),
           "wq": ((n, d, d), True), "wk": ((n, kv, d), True),
           "wv": ((n, kv, d), True), "wo": ((n, d, d), True),
           "rms_att": ((n, d), False), "rms_ffn": ((n, d), False),
           "router": ((n, e, d), True),
           "moe_up": ((n, e, h, d), True), "moe_gate": ((n, e, h, d), True),
           "moe_down": ((n, e, d, h), True),
           "sh_gate": ((n, sh, d), True), "sh_up": ((n, sh, d), True),
           "sh_down": ((n, d, sh), True)}
    out.update({f"blocks.{name}": s for name, s in own.items()})
    return out


def _over_heads(lo: float, hi: float, n: int, log: bool) -> np.ndarray:
    return (np.geomspace(lo, hi, n) if log else np.linspace(lo, hi, n)
            ).astype(np.float32)


def mapped(weights: dict) -> dict:
    """The drawn tensors as program and reference both read them (the
    module's docstring says which and why). Weights that were mapped already
    pass unchanged."""
    if weights.get(MAPPED):
        return weights
    out = {**weights, MAPPED: True}
    for name, t in weights.items():
        bare = name.rsplit(".", 1)[-1]
        if bare == "ssm_conv_w":
            out[name] = (t * np.asarray(TAPS, np.float32)).astype(np.float32)
        elif bare == "ssm_conv_b":
            out[name] = (0.1 + 5.0 * (t - 1.0)).astype(np.float32)
        elif bare == "ssm_d":
            out[name] = (1.0 + 25.0 * (t - 1.0)).astype(np.float32)
        elif bare == "ssm_a_log":
            out[name] = np.log(_over_heads(*A_RANGE, t.shape[-1], False)
                               * t).astype(np.float32)
        elif bare == "ssm_dt_bias":
            step = _over_heads(*DT_RANGE, t.shape[-1], True)
            out[name] = (np.log(np.expm1(step)) + (t - 1.0)).astype(
                np.float32)
    out["embedding"] = _dequantized_on_the_host(*weights["wcls"])
    return out


_HEAD: dict = {}  # the newest head dequantized: a cut of the weights holds it


def _dequantized_on_the_host(packed, scales) -> np.ndarray:
    """`weights.dequantize`, a slice of the rows at a time on the device,
    gathered on the host: the head at the published vocabulary is 1.6 GB in
    float32, which the device has no room for beside the cell's engine (a
    slice is 0.2 GB), and numpy alone takes 9 s of a run's set-up over it;
    the device works on one slice while the host copies the one before. The
    check asks for it once a cut of the same weights (`weights.layer_cut`
    hands the head on as it is), so the newest one is kept."""
    import jax

    if _HEAD.get("of") is packed:
        return _HEAD["values"]
    out = np.empty((packed.shape[0], packed.shape[1] * W.QK), np.float32)
    cuts = np.linspace(0, packed.shape[0], HEAD_SLICES + 1).astype(int)
    spans = [(a, b) for a, b in zip(cuts, cuts[1:]) if b > a]
    slice_fn = jax.jit(W.dequantize)
    ahead = None  # (a, b, the slice on the device), issued and not copied
    for a, b in spans:
        issued = (a, b, slice_fn(packed[a:b], scales[a:b]))
        if ahead is not None:
            out[ahead[0]:ahead[1]] = np.asarray(ahead[2])
        ahead = issued
    out[ahead[0]:ahead[1]] = np.asarray(ahead[2])
    _HEAD.update(of=packed, values=out)
    return out


def _types_held(cfg: dict, weights: dict) -> list[str]:
    """The kind of each layer `weights` hold, in layer order: a cut's layers
    are the ones `_cut` reads from its depth."""
    layers = _cut({**cfg, "num_hidden_layers": W.depth(weights, cfg)})
    return [cfg["layer_types"][l] for l in layers]


def program_params(cfg: dict, weights: dict):
    """The program's one run (`ModelSpec.runs`: `blocks`), each mixer's
    tensors stacked over the layers of ITS kind (`models/params.py
    run_tensor_shapes`), everything else over all of the run's."""
    from distributed_llama_tpu.quants import FloatType, QTensor

    weights = mapped(weights)
    types = _types_held(cfg, weights)
    run = {}
    for name, t in weights.items():
        if not name.startswith("blocks."):
            continue
        bare = name.split(".", 1)[1]
        kind = MAMBA if bare in SSM else ATTN if bare in ATTENTION else None
        if kind:
            keep = np.asarray([i for i, ty in enumerate(types) if ty == kind],
                              np.int64)
            if not keep.size:
                continue
            if keep.size < len(types):  # this kind's layers alone
                t = (tuple(a[keep] for a in t) if isinstance(t, tuple)
                     else t[keep])
        run[bare] = (QTensor(FloatType.Q40, *t) if isinstance(t, tuple)
                     else t)
    return {"blocks": run, "embedding": weights["embedding"],
            "rms_final": weights["rms_final"],
            "wcls": QTensor(FloatType.Q40, *weights["wcls"])}


def _rmsnorm(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _layer(sizes, precision, kind, x, lw, starts, flip_t):
    """One block on one row: (x, margin). x (T, d); kind = (layer type,
    control); `starts` (T,) bool: the positions that start a dispatch (read
    by "ssm_state_off" alone)."""
    import jax
    import jax.numpy as jnp

    (heads, nkv, top, eps, resid, att_scale, nh, p, n) = sizes
    layer_type, control = kind
    rnd = W.rounder(precision)
    # q80 is the program's rounding of the activations before a WEIGHT matrix
    rnd_att = W.rounder("float32") if precision == "q80" else rnd
    if control == "resid_mult_off":
        resid = 1.0

    def mm(a, qw):  # a @ W.T with W (out, in) dequantized here
        a, w = rnd(a, W.dequantize(*qw))
        return jnp.einsum("...i,oi->...o", a, w)

    t, d = x.shape
    h = _rmsnorm(x, jnp.asarray(lw["rms_att"]), eps)
    if layer_type == MAMBA:
        inner = nh * p
        zud = mm(h, lw["ssm_in"])
        z, u, dt = (zud[:, :inner], zud[:, inner:inner + inner + 2 * n],
                    zud[:, inner + inner + 2 * n:])
        w = jnp.asarray(lw["ssm_conv_w"])  # (cw, 4), oldest position first
        if control == "taps_reversed":
            w = w[:, ::-1]
        k = w.shape[-1]
        acc = jnp.asarray(lw["ssm_conv_b"]) + w[:, k - 1] * u
        for j in range(1, k):
            acc = acc + w[:, k - 1 - j] * jnp.pad(u[:-j], ((j, 0), (0, 0)))
        xbc = jax.nn.silu(acc)
        xs = xbc[:, :inner].reshape(t, nh, p)
        b_, c_ = xbc[:, inner:inner + n], xbc[:, inner + n:]
        step = jax.nn.softplus(dt + jnp.asarray(lw["ssm_dt_bias"]))  # (T, nh)
        a = -jnp.exp(jnp.asarray(lw["ssm_a_log"]))
        if control == "decay_off":
            a = jnp.zeros_like(a)
        skip = jnp.asarray(lw["ssm_d"])
        if control == "dskip_off":
            skip = jnp.zeros_like(skip)

        def pos(hm, row):  # the recurrence, one POSITION a step
            x_p, d_p, b_p, c_p, start = row
            if control == "ssm_state_off":
                hm = jnp.where(start, 0.0, hm)
            hm = (jnp.exp(d_p * a)[:, None, None] * hm
                  + (d_p[:, None] * x_p)[:, :, None] * b_p[None, None, :])
            return hm, jnp.einsum("hpn,n->hp", hm, c_p)

        _, y = jax.lax.scan(pos, jnp.zeros((nh, p, n), jnp.float32),
                            (xs, step, b_, c_, starts))
        y = (y + skip[:, None] * xs).reshape(t, inner)
        norm_w = jnp.asarray(lw["ssm_norm"])
        if control == "gate_after_norm":
            g = _rmsnorm(y, norm_w, eps) * jax.nn.silu(z)
        else:
            g = _rmsnorm(y * jax.nn.silu(z), norm_w, eps)
        x = x + resid * mm(g, lw["ssm_out"])
    else:
        hs = d // heads
        q = mm(h, lw["wq"]).reshape(t, heads, hs)
        k = mm(h, lw["wk"]).reshape(t, nkv, hs)
        v = mm(h, lw["wv"]).reshape(t, nkv, hs)
        scale = hs ** -0.5 if control == "attn_scale_sqrt" else att_scale
        g = heads // nkv
        at = jnp.arange(t)
        out = []
        for kvh in range(nkv):  # one kv head's keys, queries in blocks
            blocks = []
            for q0 in range(0, t, Q_BLOCK):
                qi = at[q0:q0 + Q_BLOCK]
                qa, ka = rnd_att(q[q0:q0 + Q_BLOCK, kvh * g:(kvh + 1) * g],
                                 k[:, kvh])
                s = jnp.einsum("qgd,kd->gqk", qa, ka) * scale
                s = jnp.where((at[None, :] <= qi[:, None])[None], s,
                              -jnp.inf)
                pa, va = rnd_att(jax.nn.softmax(s, axis=-1), v[:, kvh])
                blocks.append(jnp.einsum("gqk,kd->qgd", pa, va))
            out.append(jnp.concatenate(blocks, axis=0))  # (T, g, hs)
        att = jnp.concatenate(out, axis=1)  # (T, heads, hs)
        x = x + resid * mm(att.reshape(t, d), lw["wo"])
    y = _rmsnorm(x, jnp.asarray(lw["rms_ffn"]), eps)
    r = mm(y, lw["router"]).astype(jnp.float32)
    order = jnp.argsort(-r, axis=-1)
    ranked = jnp.take_along_axis(r, order, axis=-1)
    margin = (ranked[:, top - 1] - ranked[:, top]) / jnp.std(r, axis=-1)
    if flip_t is not None:
        order = order.at[flip_t, top - 1].set(order[flip_t, top])
    idx = order[:, :top]
    wt = jax.nn.softmax(jnp.take_along_axis(r, idx, axis=-1), axis=-1)
    share = jnp.sum(jax.nn.one_hot(idx, r.shape[-1]) * wt[..., None],
                    axis=-2)  # (T, E): a token's weight on each expert

    def expert(out, ew):  # a scan, so one expert is dequantized at a time
        up, gate, down, we = ew
        e_out = mm(jax.nn.silu(mm(y, gate)) * mm(y, up), down)
        return out + e_out * we[:, None], None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                          (lw["moe_up"], lw["moe_gate"], lw["moe_down"],
                           share.T))
    out = out + mm(jax.nn.silu(mm(y, lw["sh_gate"])) * mm(y, lw["sh_up"]),
                   lw["sh_down"])
    return x + resid * out, margin


@functools.lru_cache(maxsize=None)
def _layer_fn(sizes: tuple, precision: str, kind: tuple):
    import jax

    return jax.jit(functools.partial(_layer, sizes, precision, kind))


@functools.lru_cache(maxsize=None)
def _head_fn(precision: str):
    import jax
    import jax.numpy as jnp

    def head(x, packed, scales):
        xr, wr = W.rounder(precision)(x, W.dequantize(packed, scales))
        return jnp.einsum("ni,oi->no", xr, wr)

    return jax.jit(head)


def dispatch_starts(prompt: int, total: int) -> np.ndarray:
    """(total,) bool: the positions that start a dispatch of a row whose
    prompt is `prompt` tokens: its chunks (the largest of 64, 8, 1 that
    fits, as the program cuts them) and every position behind the prompt."""
    out = np.zeros(total, bool)
    i = 0
    while i < min(prompt, total):
        out[i] = True
        i += next(c for c in PREFILL_CHUNKS if prompt - i >= c)
    out[prompt:] = True
    return out


def _used(lw: dict, layer_type: str) -> dict:
    """A layer's tensors less the other mixer's (drawn and unused)."""
    other = ATTENTION if layer_type == MAMBA else SSM
    return {n: t for n, t in lw.items() if n not in other}


def logits_at(cfg: dict, weights: dict, rows, at, precision: str = "float32",
              flip: tuple[int, int, int] | None = None):
    """The logits at the positions `at[i]` of row `i` and each of those
    positions' smallest router margin over the layers of `weights`, row
    after row: (sum of len(at[i]), vocab) float32 and (sum of len(at[i]),).
    flip = (layer, row, t) swaps one routed expert. A row's prompt is taken
    to end at its first recorded position (`probe`)."""
    import jax
    import jax.numpy as jnp

    weights = mapped(weights)
    control = precision if precision in MECHANISM_CONTROLS else ""
    precision = "float32" if control else precision
    sizes = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
             cfg["num_experts_per_tok"], cfg["rms_norm_eps"],
             float(cfg["residual_multiplier"]),
             float(cfg["attention_multiplier"]), cfg["mamba_n_heads"],
             cfg["mamba_d_head"], cfg["mamba_d_state"])
    types = _types_held(cfg, weights)
    where = [np.asarray(a, np.int64) for a in at]
    with jax.default_matmul_precision("highest"):
        # the embedding stays on the host: only the rows' own vectors travel
        xs = [jnp.asarray(weights["embedding"][np.asarray(
            list(r) + [PAD_TOKEN] * (_padded(len(r)) - len(r)))]
            * np.float32(cfg["embedding_multiplier"])) for r in rows]
        starts = [jnp.asarray(dispatch_starts(int(a[0]) + 1, x.shape[0]))
                  for a, x in zip(where, xs)]
        margins = [np.full(len(a), np.inf, np.float32) for a in where]
        for i, layer_type in enumerate(types):
            layer_fn = _layer_fn(sizes, precision, (layer_type, control))
            lw = jax.device_put(_used(W.layer(weights, i, cfg), layer_type))
            for r in range(len(rows)):
                flip_t = flip[2] if flip and flip[:2] == (i, r) else None
                xs[r], m = layer_fn(xs[r], lw, starts[r], flip_t)
                margins[r] = np.minimum(margins[r], np.asarray(m)[where[r]])
            del lw
        x = jnp.concatenate([
            _rmsnorm(x[a], jnp.asarray(weights["rms_final"]),
                     cfg["rms_norm_eps"]) for x, a in zip(xs, where)])
        packed, scales = weights["wcls"]
        head = _head_fn(precision)
        cuts = np.linspace(0, packed.shape[0], HEAD_SLICES + 1).astype(int)
        out = np.concatenate(
            [np.asarray(head(x, packed[a:b], scales[a:b]), np.float32)
             for a, b in zip(cuts, cuts[1:]) if b > a], axis=1)
    return out / np.float32(cfg["logits_scaling"]), np.concatenate(margins)
