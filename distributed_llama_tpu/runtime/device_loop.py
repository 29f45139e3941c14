"""On-device multi-token decode loop: scan(forward + sample) in one compiled program.

The reference drives generation strictly token-by-token from the host (generate,
dllama.cpp:17-94): each token costs a host round trip to sample and re-dispatch. That is
a CPU-runtime artifact; the TPU-native shape of the loop is a `lax.scan` over decode
steps *inside* the jitted SPMD program — the sampled token feeds the next embedding
lookup on device, and the host gets a chunk of tokens back per dispatch instead of one.

Two loops live here: make_decode_loop (B=1, the --device-loop CLI path) and
make_batched_decode_loop (per-row positions/budgets/RNG — the BatchEngine's
K-step super-step; docs/SERVING.md). The batched loop samples with the host
Sampler's own xorshift* generator (implemented below on split uint32 halves,
bit-exact with runtime/sampler._random_u32) so a request's sample stream stays
one sequence across host- and device-sampled tokens.

Sampling runs on device with the reference Sampler's semantics (temperature softmax,
top-p nucleus with the (1-topp)/(n-1) pre-filter cutoff — src/tokenizer.cpp:307-415).
Temperature 0 (greedy argmax) matches the host sampler token-for-token; stochastic
sampling uses JAX's counter-based PRNG instead of the reference's xorshift*, so seeds
are not bit-compatible with the host Sampler (runtime/sampler.py keeps the exact
xorshift* port for host-side parity).

Under tensor parallelism the post-all-gather logits are replicated, so every device
computes the same sample — no extra collective is needed for the token broadcast (the
reference ships `pos` over TCP instead: sendPos, src/tasks.cpp:137-152).

Performance note: forward() carries the caches with layer-indexed in-place updates and
windowed attention reads (models/forward.py), so the loop no longer restacks them per
token; what remains for the device loop to win is amortizing the per-dispatch host
overhead across `n_steps` tokens per dispatch. Its device time is not measured
(ROADMAP S1): `python bench.py --device-loop N`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..models.forward import N_MOE_STATS, forward
from ..models.spec import ModelSpec
from ..ops.rope import RopeTables
from ..resilience import faults
from ..parallel.mesh import AXIS_SP, AXIS_TP
from ..parallel.sharding import kv_cache_pspec_for_mesh, param_pspecs
from ..parallel.tp import _expand_pspec_tree


def _tp_axis(mesh, compress_collectives: bool) -> str | None:
    """AXIS_TP, or None when the tp axis has one member: a 1-member axis has
    nothing to reduce, so dropping the name elides every psum/all_gather.
    Compressed collectives keep the axis: their Q80 wire quantization is
    part of the numerics even over one member."""
    return AXIS_TP if (mesh.shape[AXIS_TP] > 1 or compress_collectives) else None


def device_sample_coin(logits: jax.Array, u: jax.Array, temperature: jax.Array,
                       topp: jax.Array) -> jax.Array:
    """Sample one token id from a (vocab,) f32 logits row, reference semantics.

    `u` is the uniform coin in [0, 1) — supplied by the caller so the batched
    loop can feed the on-device xorshift* stream that mirrors the host Sampler
    (the host draws exactly one coin per stochastic sample, so carrying the
    xorshift* state through the scan keeps host and device state in sync)."""
    n = logits.shape[0]

    def greedy(_):
        return jnp.argmax(logits).astype(jnp.int32)

    def stochastic(u):
        probs = jax.nn.softmax(logits / temperature)

        def mult(u):
            csum = jnp.cumsum(probs)
            idx = jnp.searchsorted(csum, u * csum[-1], side="right")
            return jnp.minimum(idx, n - 1).astype(jnp.int32)

        def nucleus(u):
            # pre-filter cutoff (tokenizer.cpp:338-345), then nucleus over the sorted
            # survivors. Degenerate all-filtered case decays to argmax (the reference
            # reads probindex[-1], which is UB).
            cutoff = (1.0 - topp) / (n - 1)
            masked = jnp.where(probs >= cutoff, probs, 0.0)
            order = jnp.argsort(-masked)
            p = masked[order]
            csum = jnp.cumsum(p)
            over = csum > topp
            last = jnp.where(jnp.any(over), jnp.argmax(over), n - 1)
            r = u * csum[last]
            pick = jnp.searchsorted(csum, r, side="right")
            return order[jnp.minimum(pick, last)].astype(jnp.int32)

        return jax.lax.cond((topp > 0.0) & (topp < 1.0), nucleus, mult, u)

    return jax.lax.cond(temperature == 0.0, greedy, stochastic, u)


def device_sample(logits: jax.Array, key: jax.Array, temperature: jax.Array,
                  topp: jax.Array) -> jax.Array:
    """device_sample_coin with the coin drawn from JAX's counter-based PRNG
    (B=1 loop; seeds are not bit-compatible with the host xorshift* Sampler)."""
    return device_sample_coin(logits, jax.random.uniform(key), temperature, topp)


# ------------------------------------------------------------------
# on-device xorshift* (the host Sampler's RNG, utils.cpp:79-90)
# ------------------------------------------------------------------
# The uint64 state is carried as two uint32 halves: jnp.uint64 silently
# downcasts without jax_enable_x64, and flipping that flag globally would
# change every f32 promotion in the model. All ops below are bit-exact with
# runtime/sampler._random_u32, so the BatchEngine can hand a host Sampler's
# state to the device loop and write the advanced state back afterwards.

_XSM_HI = 0x2545F491  # 0x2545F4914F6CDD1D, the xorshift* multiplier
_XSM_LO = 0x4F6CDD1D


def _mul32_wide(a: jax.Array, b) -> tuple[jax.Array, jax.Array]:
    """Full 32x32 -> 64-bit product as (hi32, lo32), in uint32 arithmetic."""
    a0, a1 = a & 0xFFFF, a >> 16
    b0, b1 = b & 0xFFFF, b >> 16
    p00, p01, p10, p11 = a0 * b0, a0 * b1, a1 * b0, a1 * b1
    mid = (p00 >> 16) + (p01 & 0xFFFF) + (p10 & 0xFFFF)  # < 2^18, no overflow
    lo = (p00 & 0xFFFF) | ((mid & 0xFFFF) << 16)
    hi = p11 + (p01 >> 16) + (p10 >> 16) + (mid >> 16)
    return hi, lo


def _xor_shr(hi, lo, n: int):
    """s ^ (s >> n) on a split uint64, 0 < n < 32."""
    return hi ^ (hi >> n), lo ^ ((lo >> n) | (hi << (32 - n)))


def _xor_shl(hi, lo, n: int):
    """s ^ (s << n) on a split uint64, 0 < n < 32."""
    return hi ^ ((hi << n) | (lo >> (32 - n))), lo ^ (lo << n)


def xorshift_star_step(hi: jax.Array, lo: jax.Array):
    """One xorshift* round; returns (hi', lo', out_u32). Vectorizes over any
    leading shape. Bit-exact with sampler._random_u32 (same state evolution,
    same high-32 output of the 64-bit multiply)."""
    hi, lo = _xor_shr(hi, lo, 12)
    hi, lo = _xor_shl(hi, lo, 25)
    hi, lo = _xor_shr(hi, lo, 27)
    # out = ((s * M) mod 2^64) >> 32 = hi32(lo*M_lo) + lo*M_hi + hi*M_lo (mod 2^32)
    ph, _ = _mul32_wide(lo, jnp.uint32(_XSM_LO))
    out = ph + lo * jnp.uint32(_XSM_HI) + hi * jnp.uint32(_XSM_LO)
    return hi, lo, out


def xorshift_coin(hi: jax.Array, lo: jax.Array):
    """Advance the state and return (hi', lo', coin in [0,1) f32) — the exact
    randomF32 mapping the host Sampler uses (utils.cpp:88-90)."""
    hi, lo, out = xorshift_star_step(hi, lo)
    return hi, lo, (out >> 8).astype(jnp.float32) * jnp.float32(1.0 / 16777216.0)


def make_decode_loop(spec: ModelSpec, mesh, params, n_steps: int, *, mode: str = "greedy",
                     dtype=None, use_pallas: bool = False,
                     compress_collectives: bool = False, donate_cache: bool = True,
                     attn_window: int | None = None,
                     moe_sharding: str = "slice"):
    """Build fn(params, rope, token, kc, vc, start_pos, key, temperature, topp) ->
    (tokens (n_steps,), last_logits (vocab,), kc, vc).

    `token` is the last prompt token (B=1); the loop decodes n_steps tokens, feeding
    each sample back as the next input. KV caches advance n_steps positions.

    `mode` is static: "greedy" compiles a pure argmax step (no sort anywhere — XLA may
    execute both sides of a runtime cond, and the nucleus path's full-vocab sort is
    expensive on TPU); "sample" compiles device_sample with runtime temperature/topp.
    """
    assert mode in ("greedy", "sample"), mode
    dtype = dtype or jnp.float32
    sp = mesh.shape.get(AXIS_SP, 1)
    param_specs = _expand_pspec_tree(params, param_pspecs(params, moe_sharding))
    kv_spec = kv_cache_pspec_for_mesh(mesh)
    rope_type = spec.rope_type

    fwd = functools.partial(forward, spec=spec, dtype=dtype,
                            axis_name=_tp_axis(mesh, compress_collectives),
                            sp_axis_name=AXIS_SP if sp > 1 else None, sp_size=sp,
                            use_pallas=use_pallas,
                            compress_collectives=compress_collectives,
                            attn_window=attn_window)

    # hot-path: traced
    def loop(p, rope_cos, rope_sin, token, kc, vc, start_pos, key, temperature, topp):
        rope = RopeTables(rope_cos, rope_sin, rope_type)

        def step(carry, i):
            token, row0, kc, vc = carry
            logits, kc, vc = fwd(p, rope=rope, tokens=token[None, None],
                                 k_cache=kc, v_cache=vc, start_pos=start_pos + i)
            row = logits[0, -1].astype(jnp.float32)
            if mode == "greedy":
                nxt = jnp.argmax(row).astype(jnp.int32)
            else:
                nxt = device_sample(row, jax.random.fold_in(key, i), temperature, topp)
            return (nxt, row, kc, vc), nxt

        row0 = jnp.zeros((spec.vocab_size,), jnp.float32)
        (tok, row, kc, vc), tokens = jax.lax.scan(
            step, (token, row0, kc, vc), jnp.arange(n_steps, dtype=jnp.int32))
        return tokens, row, kc, vc

    sharded = jax.shard_map(
        loop, mesh=mesh,
        in_specs=(param_specs, P(), P(), P(), kv_spec, kv_spec, P(), P(), P(), P()),
        out_specs=(P(), P(), kv_spec, kv_spec),
        check_vma=False,
    )
    donate = (4, 5) if donate_cache else ()
    jitted = jax.jit(sharded, donate_argnums=donate)

    # hot-path
    def run(p, rope: RopeTables, token, kc, vc, start_pos, key, temperature=0.0,
            topp=0.9):
        faults.fire("device_loop.dispatch", n_steps=n_steps)
        return jitted(p, rope.cos, rope.sin, jnp.asarray(token, jnp.int32), kc, vc,
                      jnp.int32(start_pos), key, jnp.float32(temperature),
                      jnp.float32(topp))

    return run


# disallowed-logit fill for grammar masking (constrain/): finite so the
# softmax shift never meets inf-inf, large enough that exp underflows to 0
# exactly — the host mask path (batch_engine._advance_row) uses the SAME
# constant so host and device masked samples stay bit-compatible
MASK_NEG = -1e30


# hot-path: traced
def _apply_token_mask(rows, mrow):
    """Lower disallowed logits: `mrow` is the packed uint32 allowed bitmask
    gathered per row (..., W) from the constrain table; bit v&31 of word
    v>>5 covers token v. Universal rows (all-ones) make this the identity,
    so unconstrained co-batched rows are bit-identical to the unmasked
    program."""
    v = rows.shape[-1]
    vi = jnp.arange(v, dtype=jnp.int32)
    words = jnp.take(mrow, vi >> 5, axis=-1)  # (..., V)
    allowed = (words >> (vi & 31).astype(jnp.uint32)) & jnp.uint32(1)
    return jnp.where(allowed.astype(bool), rows, jnp.float32(MASK_NEG))


def make_batched_decode_loop(spec: ModelSpec, mesh, params, n_steps: int, *,
                             mode: str = "greedy", dtype=None,
                             use_pallas: bool = False,
                             compress_collectives: bool = False,
                             donate_cache: bool = True,
                             attn_window: int | None = None,
                             moe_sharding: str = "slice",
                             kv_block_tokens: int = 0,
                             paged_kernel: bool = False,
                             masked: bool = False,
                             moe_stats: bool = False):
    """Batched K-step super-step: `lax.scan` over n_steps decode steps for ALL
    cache rows at once, sampling on device — the serving-path generalization of
    make_decode_loop (B=1) that converts the BatchEngine's hot loop from one
    host sync per token to one per n_steps tokens.

    Builds fn(params, rope, tokens (B,), kc, vc, start_pos (B,), rng (B, 2)
    uint32 [hi, lo], temperature (B,), topp (B,), budget (B,)) ->
    (tokens (n_steps, B), last_tok (B,), pos (B,), rng (B, 2), kc, vc).

    The (last_tok, pos, rng) trailer is the loop's final carry, returned as
    DEVICE arrays: last_tok is each row's block-tail sample (its KV not yet
    ingested — exactly the next dispatch's input token), pos the row's
    position after its budgeted ingestions, rng the advanced xorshift*
    state. A pipelined scheduler (runtime/batch_engine.py) feeds them
    straight back as the next dispatch's (tokens, start_pos, rng) without
    waiting for the (n_steps, B) block's host transfer, so super-step N+1
    chains from N's device state while N is still being delivered host-side.

    Per-row carry: each row decodes at its own `start_pos` (continuous
    batching) and stops advancing after `budget[r]` steps — a parked row keeps
    riding the scan with its position pinned at min(pos, seq_len-1), so its
    garbage writes land on masked slots that the row's next real token
    overwrites (the same discipline the host scheduler's _park_positions
    uses). The scheduler sets budget below n_steps for rows near their
    max_tokens / context end, and 0 for empty slots.

    Sampling: `mode` is static like make_decode_loop's. "sample" carries each
    row's xorshift* state (split uint32 halves) and consumes exactly one coin
    per live stochastic sample — bit-compatible state evolution with the host
    Sampler, so the scheduler uploads sampler.state before the dispatch and
    writes the returned state back after. Greedy rows (temperature 0) draw no
    coins, matching the host.

    Under dp the row axis shards over the dp mesh axis (tokens/start_pos/rng/
    sampler params ride P(dp), like make_sharded_forward's batched step).

    kv_block_tokens > 0 selects the device-resident paged KV layout
    (docs/PAGED_KV.md): kc/vc are the (L, N, hk, bt, hs) block pool and the
    built fn takes a trailing (B, W) block-table argument mapping each
    row's virtual positions to pool blocks (loop-invariant across the scan;
    the scheduler ensures coverage for every budgeted write pre-dispatch).

    moe_stats=True (a routed model under BatchEngine): the scan's carry also
    sums what the expert layers did in each step (models/forward.py), and run()
    returns that int32 vector as one more, last value.

    masked=True builds the grammar-constrained variant (constrain/,
    docs/SERVING.md "Constrained decoding"): the per-row automaton state
    rides the scan carry, each step gathers the state's packed bitmask row
    from the device-resident constrain table, lowers disallowed logits to
    MASK_NEG BEFORE the greedy argmax / split-uint32 sampler, and advances
    the state through the emitted token. run() then takes
    constrain=(cstate (B,) int32 GLOBAL states, mask (S, W) uint32,
    delta (S, V) int32) and appends the final automaton state to its
    outputs. Rows at state 0 (the universal row) sample identically to the
    unmasked program; the unmasked build is byte-for-byte today's program
    so its pinned dispatch signature is untouched.
    """
    from ..parallel.mesh import AXIS_DP

    assert mode in ("greedy", "sample"), mode
    dtype = dtype or jnp.float32
    sp = mesh.shape.get(AXIS_SP, 1)
    dp = mesh.shape.get(AXIS_DP, 1)
    assert sp == 1, "batched decode needs per-row cache positions (no sp ring)"
    paged = kv_block_tokens > 0
    assert not (paged and dp > 1), "paged KV is tp-only (no dp sharding)"
    param_specs = _expand_pspec_tree(params, param_pspecs(params, moe_sharding))
    kv_spec = (P(None, None, AXIS_TP) if paged
               else kv_cache_pspec_for_mesh(mesh))
    rope_type = spec.rope_type
    seq_len = spec.seq_len

    fwd = functools.partial(forward, spec=spec, dtype=dtype,
                            axis_name=_tp_axis(mesh, compress_collectives),
                            sp_axis_name=None, sp_size=1, use_pallas=use_pallas,
                            compress_collectives=compress_collectives,
                            attn_window=attn_window,
                            block_tokens=kv_block_tokens,
                            paged_kernel=paged_kernel, moe_stats=True)

    # hot-path: traced
    def loop(p, rope_cos, rope_sin, tokens, kc, vc, start_pos, rng_hi, rng_lo,
             temperature, topp, budget, tables, cstate, cmask, cdelta):
        rope = RopeTables(rope_cos, rope_sin, rope_type)
        # a state-space model's running matrices as this scan found them:
        # what a flush of it puts back (models/forward.py StateCache.held).
        # They ride out beside the carry, not in it
        found = getattr(vc, "h", None)
        if found is not None:
            vc = vc._replace(held=None)

        def step(carry, i):
            tok, pos, sh, sl, cst, kc, vc, moe = carry
            live = i < budget  # (B,)
            if found is not None:
                # a parked row's matrices stay bit for bit: the step is told
                vc = vc._replace(ctl=vc.ctl.at[0, :, 0].set(
                    live.astype(jnp.int32)))
            # parked rows write scratch at their current position (clamped to
            # stay in-cache); reads mask slots >= start_pos so it is invisible,
            # and the row's next real decode overwrites it
            step_pos = jnp.where(live, pos, jnp.minimum(pos, seq_len - 1))
            logits, kc, vc, st = fwd(p, rope=rope, tokens=tok[:, None],
                                     k_cache=kc, v_cache=vc,
                                     start_pos=step_pos,
                                     block_tables=tables if paged else None)
            moe = moe + st
            rows = logits[:, -1].astype(jnp.float32)  # (B, vocab)
            if masked:
                rows = _apply_token_mask(rows, cmask[cst])
            if mode == "greedy":
                nxt = jnp.argmax(rows, axis=-1).astype(jnp.int32)
            else:
                nsh, nsl, coin = xorshift_coin(sh, sl)
                nxt = jax.vmap(device_sample_coin)(rows, coin, temperature,
                                                   topp)
                drew = live & (temperature != 0.0)
                sh = jnp.where(drew, nsh, sh)
                sl = jnp.where(drew, nsl, sl)
            if masked:
                # advance the automaton through the emitted token (a masked
                # sample is always an allowed transition)
                cst = jnp.where(live, cdelta[cst, nxt], cst)
            tok = jnp.where(live, nxt, tok)
            pos = jnp.where(live, pos + 1, pos)
            return (tok, pos, sh, sl, cst, kc, vc, moe), nxt

        (tok, pos, sh, sl, cst, kc, vc, moe), toks = jax.lax.scan(
            step, (tokens, start_pos, rng_hi, rng_lo, cstate, kc, vc,
                   jnp.zeros((N_MOE_STATS,), jnp.int32)),
            jnp.arange(n_steps, dtype=jnp.int32))
        if found is not None:
            vc = vc._replace(held=found)
        return (toks, tok, pos, sh, sl, cst, kc, vc) + (
            (moe,) if moe_stats else ())

    row = P(AXIS_DP) if dp > 1 else P()
    toks_out = P(None, AXIS_DP) if dp > 1 else P()

    if masked:
        in_specs = (param_specs, P(), P(), row, kv_spec, kv_spec, row, row,
                    row, row, row, row, P(), row, P(), P())
        out_specs = (toks_out, row, row, row, row, row, kv_spec, kv_spec) + (
            (P(),) if moe_stats else ())
        sharded = jax.shard_map(loop, mesh=mesh, in_specs=in_specs,
                                out_specs=out_specs, check_vma=False)
    else:
        # the unmasked build keeps today's exact program arity so its
        # pinned compile-manifest signature is untouched (boolean policy)
        def plain(p, rope_cos, rope_sin, tokens, kc, vc, start_pos, rng_hi,
                  rng_lo, temperature, topp, budget, tables):
            cz = jnp.zeros(tokens.shape, jnp.int32)
            toks, tok, pos, sh, sl, _, kc, vc, *moe = loop(
                p, rope_cos, rope_sin, tokens, kc, vc, start_pos, rng_hi,
                rng_lo, temperature, topp, budget, tables, cz, None, None)
            return (toks, tok, pos, sh, sl, kc, vc, *moe)

        sharded = jax.shard_map(
            plain, mesh=mesh,
            in_specs=(param_specs, P(), P(), row, kv_spec, kv_spec, row, row,
                      row, row, row, row, P()),
            out_specs=(toks_out, row, row, row, row, kv_spec, kv_spec) + (
                (P(),) if moe_stats else ()),
            check_vma=False,
        )
    donate = (4, 5) if donate_cache else ()
    jitted = jax.jit(sharded, donate_argnums=donate)

    # hot-path
    def run(p, rope: RopeTables, tokens, kc, vc, start_pos, rng, temperature,
            topp, budget, tables=None, constrain=None):
        faults.fire("device_loop.batched_dispatch", n_steps=n_steps)
        rng = jnp.asarray(rng, jnp.uint32).reshape(-1, 2)
        if tables is None:
            tables = jnp.zeros((rng.shape[0], 1), jnp.int32)  # dense: unused
        args = (p, rope.cos, rope.sin, jnp.asarray(tokens, jnp.int32), kc, vc,
                jnp.asarray(start_pos, jnp.int32), rng[:, 0], rng[:, 1],
                jnp.asarray(temperature, jnp.float32),
                jnp.asarray(topp, jnp.float32), jnp.asarray(budget, jnp.int32),
                jnp.asarray(tables, jnp.int32))
        if masked:
            cstate, cmask, cdelta = constrain
            toks, tok, pos, sh, sl, cst, kc, vc, *moe = jitted(
                *args, jnp.asarray(cstate, jnp.int32), cmask, cdelta)
            return (toks, tok, pos, jnp.stack([sh, sl], axis=1), kc, vc,
                    cst, *moe)
        toks, tok, pos, sh, sl, kc, vc, *moe = jitted(*args)
        return (toks, tok, pos, jnp.stack([sh, sl], axis=1), kc, vc, *moe)

    return run


def make_batched_verify_loop(spec: ModelSpec, mesh, params, block: int, *,
                             mode: str = "greedy", dtype=None,
                             use_pallas: bool = False,
                             compress_collectives: bool = False,
                             donate_cache: bool = True,
                             attn_window: int | None = None,
                             moe_sharding: str = "slice",
                             kv_block_tokens: int = 0,
                             paged_kernel: bool = False,
                             masked: bool = False):
    """Batched draft-verify super-step: ONE (B, T=block) forward ingests each
    row's proposal block and on-device acceptance turns it into up to T
    tokens per row — the speculative-decoding counterpart of
    make_batched_decode_loop (docs/SERVING.md "Speculative decoding").

    Decode is HBM-bandwidth-bound: a T-token dispatch streams the quantized
    weight blocks ONCE for all T positions, so verifying a k-token draft
    costs roughly one decode step while delivering accept+1 tokens. Drafts
    are host-side per-slot n-gram proposals (runtime/speculative.py); this
    program verifies every row's block in one dispatch.

    Builds fn(params, rope, proposals (B, T), kc, vc, start_pos (B,),
    rng (B, 2) uint32 [hi, lo], temperature (B,), topp (B,), ndraft (B,)) ->
    (targets (T, B), acc (B,), last_tok (B,), pos (B,), rng (B, 2), kc, vc).

    Per row r: proposals[r] = [pending_token, draft_0..draft_{nd-1}, pad...]
    with nd = ndraft[r] (-1 parks the row: its start_pos must already be
    host-clamped a la _park_positions so all T scratch writes stay
    in-cache). The forward writes the whole block's KV at start_pos..+T-1;
    a target token is sampled at every position with the host Sampler's
    semantics, and acc[r] counts the leading drafts whose target matched —
    the standard speculative identity: emitted tokens are targets[0..acc],
    where targets[acc] is the correction (first mismatch's own sample) or
    the bonus token (full accept). Rejected positions hold KV computed from
    rejected inputs, but they sit beyond the verified frontier pos+acc+1
    where every read path masks them (the free-rollback discipline).

    The (last_tok, pos, rng) trailer is rewound to the verified frontier ON
    DEVICE: last_tok = targets[acc] (sampled, not yet ingested), pos =
    start_pos + acc + 1, and rng the xorshift* state after exactly acc+1
    coins for live stochastic rows (greedy rows draw none) — coin i of the
    stream samples target i, so accepted-or-corrected tokens consume coins
    in exactly the host Sampler's order and a chained scan dispatch
    (runtime/batch_engine.py) can consume the carry for ANY accept outcome.

    masked=True is the grammar-constrained variant (constrain/): the
    automaton state chain is advanced along each row's PROPOSAL tokens, so
    position i's target is sampled under the mask of the state reached
    after drafts 0..i-1 — masked verify validates an accepted block
    position-by-position, and a draft token the grammar disallows can
    never be accepted (its position's masked target cannot equal it). The
    returned frontier state is the automaton advanced through exactly the
    acc+1 EMITTED tokens (proposal-path states equal emitted-path states
    for every accepted position). run() takes constrain=(cstate, mask,
    delta) like the masked decode loop and appends the frontier state to
    its outputs; the unmasked build keeps today's program untouched.
    """
    from ..parallel.mesh import AXIS_DP

    assert mode in ("greedy", "sample"), mode
    assert block >= 2, "a verify block needs at least one draft position"
    dtype = dtype or jnp.float32
    sp = mesh.shape.get(AXIS_SP, 1)
    dp = mesh.shape.get(AXIS_DP, 1)
    assert sp == 1, "batched verify needs per-row cache positions (no sp ring)"
    paged = kv_block_tokens > 0
    assert not (paged and dp > 1), "paged KV is tp-only (no dp sharding)"
    param_specs = _expand_pspec_tree(params, param_pspecs(params, moe_sharding))
    kv_spec = (P(None, None, AXIS_TP) if paged
               else kv_cache_pspec_for_mesh(mesh))
    rope_type = spec.rope_type

    fwd = functools.partial(forward, spec=spec, dtype=dtype,
                            axis_name=_tp_axis(mesh, compress_collectives),
                            sp_axis_name=None, sp_size=1, use_pallas=use_pallas,
                            compress_collectives=compress_collectives,
                            attn_window=attn_window,
                            block_tokens=kv_block_tokens,
                            paged_kernel=paged_kernel)

    # hot-path: traced
    def loop(p, rope_cos, rope_sin, proposals, kc, vc, start_pos, rng_hi,
             rng_lo, temperature, topp, ndraft, tables, cstate, cmask,
             cdelta):
        rope = RopeTables(rope_cos, rope_sin, rope_type)
        b = proposals.shape[0]
        live = ndraft >= 0  # (B,)
        logits, kc, vc = fwd(p, rope=rope, tokens=proposals, k_cache=kc,
                             v_cache=vc, start_pos=start_pos,
                             block_tables=tables if paged else None)
        rows = logits.astype(jnp.float32)  # (B, T, vocab)
        if masked:
            # automaton states along the PROPOSAL path: position i's target
            # is masked by the state after drafts 0..i-1 (st_chain[i]); the
            # chain equals the emitted-token path for every position up to
            # and including the first mismatch, which is all the scheduler
            # ever delivers
            sts = [cstate]
            for i in range(1, block):
                sts.append(cdelta[sts[-1], proposals[:, i]])
            st_chain = jnp.stack(sts)  # (T, B)
            rows = _apply_token_mask(rows, cmask[st_chain.T])  # (B, T, V)
        if mode == "greedy":
            targets = jnp.argmax(rows, axis=-1).astype(jnp.int32)  # (B, T)
        else:
            # T coins per row in host-stream order: coin i (and the state
            # after i+1 draws) samples the block's i-th emitted token
            def draw(carry, _):
                sh, sl = carry
                nsh, nsl, coin = xorshift_coin(sh, sl)
                return (nsh, nsl), (coin, nsh, nsl)

            _, (coins, shs, sls) = jax.lax.scan(
                draw, (rng_hi, rng_lo), None, length=block)
            sample_row = jax.vmap(device_sample_coin,
                                  in_axes=(0, 0, None, None))  # over T
            targets = jax.vmap(sample_row, in_axes=(0, 1, 0, 0))(
                rows, coins, temperature, topp)  # (B, T)
        # accepted length: leading draft positions whose target matched
        # (cumprod-of-matches sum), capped by the row's real draft count
        di = jnp.arange(block - 1, dtype=jnp.int32)
        match = ((targets[:, :-1] == proposals[:, 1:])
                 & (di[None, :] < ndraft[:, None]))
        acc = jnp.cumprod(match.astype(jnp.int32), axis=1).sum(axis=1)
        acc = jnp.where(live, acc, 0)
        ridx = jnp.arange(b)
        last = jnp.where(live, targets[ridx, acc], proposals[:, 0])
        pos = jnp.where(live, start_pos + acc + 1, start_pos)
        if mode == "sample":
            # rewind the rng carry to the verified frontier: the block
            # consumed exactly acc+1 coins (one per emitted token); greedy
            # rows drew none, matching the host Sampler
            drew = live & (temperature != 0.0)
            rng_hi = jnp.where(drew, shs[acc, ridx], rng_hi)
            rng_lo = jnp.where(drew, sls[acc, ridx], rng_lo)
        if masked:
            # frontier automaton state: the chain state at the accept
            # boundary advanced through the emitted correction/bonus token
            # (`last` was sampled under st_chain[acc]'s mask, so the
            # transition is always an allowed one)
            cst = jnp.where(live, cdelta[st_chain[acc, ridx], last], cstate)
            return targets.T, acc, last, pos, rng_hi, rng_lo, cst, kc, vc
        return targets.T, acc, last, pos, rng_hi, rng_lo, kc, vc

    row = P(AXIS_DP) if dp > 1 else P()
    mat = P(AXIS_DP, None) if dp > 1 else P()
    toks_out = P(None, AXIS_DP) if dp > 1 else P()
    if masked:
        sharded = jax.shard_map(
            loop, mesh=mesh,
            in_specs=(param_specs, P(), P(), mat, kv_spec, kv_spec, row, row,
                      row, row, row, row, P(), row, P(), P()),
            out_specs=(toks_out, row, row, row, row, row, row, kv_spec,
                       kv_spec),
            check_vma=False,
        )
    else:
        # unmasked arity unchanged: the pinned verify[...] signatures in
        # perf/compile_manifest.json stay exactly as before (boolean policy)
        def plain(p, rope_cos, rope_sin, proposals, kc, vc, start_pos,
                  rng_hi, rng_lo, temperature, topp, ndraft, tables):
            cz = jnp.zeros(proposals.shape[:1], jnp.int32)
            return loop(p, rope_cos, rope_sin, proposals, kc, vc, start_pos,
                        rng_hi, rng_lo, temperature, topp, ndraft, tables,
                        cz, None, None)

        sharded = jax.shard_map(
            plain, mesh=mesh,
            in_specs=(param_specs, P(), P(), mat, kv_spec, kv_spec, row, row,
                      row, row, row, row, P()),
            out_specs=(toks_out, row, row, row, row, row, kv_spec, kv_spec),
            check_vma=False,
        )
    donate = (4, 5) if donate_cache else ()
    jitted = jax.jit(sharded, donate_argnums=donate)

    # hot-path
    def run(p, rope: RopeTables, proposals, kc, vc, start_pos, rng,
            temperature, topp, ndraft, tables=None, constrain=None):
        faults.fire("device_loop.verify_dispatch", block=block)
        rng = jnp.asarray(rng, jnp.uint32).reshape(-1, 2)
        if tables is None:
            tables = jnp.zeros((rng.shape[0], 1), jnp.int32)  # dense: unused
        args = (p, rope.cos, rope.sin, jnp.asarray(proposals, jnp.int32), kc,
                vc, jnp.asarray(start_pos, jnp.int32), rng[:, 0], rng[:, 1],
                jnp.asarray(temperature, jnp.float32),
                jnp.asarray(topp, jnp.float32), jnp.asarray(ndraft, jnp.int32),
                jnp.asarray(tables, jnp.int32))
        if masked:
            cstate, cmask, cdelta = constrain
            toks, acc, tok, pos, sh, sl, cst, kc, vc = jitted(
                *args, jnp.asarray(cstate, jnp.int32), cmask, cdelta)
            return (toks, acc, tok, pos, jnp.stack([sh, sl], axis=1), kc, vc,
                    cst)
        toks, acc, tok, pos, sh, sl, kc, vc = jitted(*args)
        return toks, acc, tok, pos, jnp.stack([sh, sl], axis=1), kc, vc

    return run
