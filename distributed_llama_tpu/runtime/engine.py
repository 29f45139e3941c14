"""Inference engine: model loading, SPMD step compilation, generation loop, stats.

This is the TPU-native replacement for the reference's App::run wiring + Inference/Worker
drivers (src/app.cpp:123-155, src/tasks.cpp:158-230):

    SocketPool::connect + worker processes  ->  jax.sharding.Mesh over local TPU devices
    Transformer::loadRootFromFile + weight streaming -> formats.load_model + shard_params
    Inference::infer (per-token task loop)  ->  one jitted SPMD step, KV caches donated
    tryWaitForPos / sendPos                 ->  gone (start_pos is a step argument)
    Inference::getStats I/T split           ->  GenerationStats (device step wall time +
                                                analytic collective-bytes model, since
                                                ICI transfer overlaps compute under XLA)

Prefill runs in chunks of [64, 8, 1] tokens (3 compiled shapes) — the reference prefills
strictly token-by-token (dllama.cpp:163-167), so chunked prefill is a capability win.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..models.params import (Params, decode_stream_bytes, hold_dense,
                             prepare_for_pallas, scale_plane_bytes,
                             stack_names, step_converted_bytes)
from ..models.spec import ModelSpec
from ..obs import flight, metrics, trace
from ..resilience import faults
from ..ops.rope import RopeTables
from ..parallel.mesh import AXIS_TP, make_mesh
from ..parallel.tp import make_sharded_forward, shard_params
from ..quants import FloatType
from ..tokenizer.bpe import Tokenizer

PREFILL_CHUNKS = (64, 8, 1)

# Real per-dispatch wall times (device step + logits host transfer), the
# measured complement of GenerationStats' synthetic per-token averages.
# Children resolved once — the hot path pays one observe(), no label lookup.
_DISPATCH_SECONDS = metrics.histogram(
    "engine_dispatch_seconds",
    "Wall time of one device dispatch (incl. the logits host transfer)",
    labelnames=("kind",))
_DISP_PREFILL = _DISPATCH_SECONDS.labels(kind="prefill")
_DISP_DECODE = _DISPATCH_SECONDS.labels(kind="decode")
_DISP_LOOP = _DISPATCH_SECONDS.labels(kind="device_loop")
_PREFILL_TOKENS = metrics.counter(
    "engine_prefill_tokens_total", "Prompt tokens run through prefill")
_DECODE_TOKENS = metrics.counter(
    "engine_decode_tokens_total", "Tokens decoded by the sequential engine")


@dataclass
class GenerationStats:
    """Per-token timing + traffic, the analog of the reference's G/I/T + S/R printout
    (dllama.cpp:76-93, socket.cpp:280-285)."""

    prompt_tokens: int = 0
    generated_tokens: int = 0
    # prompt tokens whose prefill was skipped at admission (same-slot rewind
    # + radix prefix-cache seed) — for a resumed request this is the share of
    # prompt ⊕ delivered-tokens the new replica did NOT have to re-run
    reused_tokens: int = 0
    prefill_ms: float = 0.0
    # Per-token wall/device times. NOTE: when a dispatch covers several tokens
    # (speculative verify blocks, device-loop chunks, BatchEngine super-steps)
    # each entry is the dispatch time divided by its token count — an average,
    # not a measured per-token latency; aggregate tokens/s stays correct, but
    # per-token percentiles are synthetic whenever spec_steps > 0 or a
    # multi-token loop ran. spec_step_ms keeps the real per-dispatch times.
    token_ms: list[float] = field(default_factory=list)
    infer_ms: list[float] = field(default_factory=list)
    # speculative decoding (runtime/speculative.py + the batched verify path
    # in runtime/batch_engine.py): verify dispatches, draft tokens
    # proposed/accepted, and each verify dispatch's wall time
    spec_steps: int = 0
    spec_drafted: int = 0
    spec_accepted: int = 0
    spec_step_ms: list[float] = field(default_factory=list)
    # one (tokens_out_before, drafted, accepted) triple per verify turn —
    # keyed by output length so the batched verify path can be oracle-checked
    # against the sequential loop turn-for-turn (tests/test_batched_spec.py)
    spec_turns: list = field(default_factory=list)
    # REAL per-dispatch times (one entry per device dispatch, however many
    # tokens it covered) — the honest latency series next to the synthetic
    # token_ms averages above. The same numbers feed the
    # engine_dispatch_seconds / batch_dispatch_seconds histograms. Under
    # PIPELINED super-steps (runtime/batch_engine.py) a dispatch's wall time
    # no longer equals its cost — the host delivers the previous block while
    # it runs — so each entry is the DEVICE-SIDE span estimate (issue or
    # predecessor-completion, whichever is later, to results-ready) and
    # overlap_ms below records the hidden host slice per dispatch.
    dispatch_ms: list[float] = field(default_factory=list)
    # per-SUPER-STEP milliseconds of wall clock that ran concurrently with
    # the predecessor still executing on device (0.0 when not pipelined; one
    # entry per super-step dispatch only — docs/OBSERVABILITY.md)
    overlap_ms: list[float] = field(default_factory=list)
    sent_kbytes_per_token: float = 0.0
    recv_kbytes_per_token: float = 0.0
    # provenance of the S/R numbers: "modeled" = the analytic formula below;
    # "measured" = exact per-step accounting of the compiled program's collectives
    # (Engine.collective_stats). The reference measured socket bytes at runtime
    # (socket.cpp:280-285); printing a model as if measured was a round-1 defect.
    traffic_source: str = "modeled"

    @property
    def avg_token_ms(self) -> float:
        return float(np.mean(self.token_ms)) if self.token_ms else 0.0

    @property
    def avg_infer_ms(self) -> float:
        return float(np.mean(self.infer_ms)) if self.infer_ms else 0.0

    @property
    def tokens_per_second(self) -> float:
        return 1000.0 / self.avg_token_ms if self.token_ms else 0.0


def collective_kbytes_per_token(spec: ModelSpec, tp: int, compress: bool) -> float:
    """Bytes each device exchanges per decoded token. Mirrors the reference's
    S/R socket counters (root broadcast+gather per layer, tasks.cpp:44-94)
    with ring-collective wire costs:

    - per layer, two activation all-reduces (attention-out + ffn-out), each
      2x(tp-1)/tp of its payload. Compressed, the payload is the Q80 wire
      format (int8 vals + f16 scale per 32-block = 34/32 bytes/elem) moved by
      the two-phase quantized reduce in parallel/collectives.py — all_to_all
      then all_gather, each (tp-1)/tp of the compressed payload, so the SAME
      2x(tp-1)/tp factor holds and this estimate is true of the real program
      (the old single-phase all_gather form shipped tp/2 x more than claimed;
      estimate-vs-measured is pinned in tests/test_engine.py);
    - one logits all-gather: each device contributes its vocab/tp slice and
      receives the rest, (tp-1)/tp of the full f32 logits row."""
    if tp <= 1:
        return 0.0
    elem = 34 / 32 if compress else 4  # Q80 wire bytes/elem vs f32
    per_layer = 2 * spec.dim * elem  # attention-out psum + ffn-out psum payloads
    layers = 2 * (tp - 1) / tp * spec.n_layers * per_layer
    logits = (tp - 1) / tp * spec.vocab_size * 4
    return (layers + logits) / 1024.0


class Engine:
    def __init__(self, spec: ModelSpec, params: Params, tokenizer: Tokenizer | None = None,
                 *, tp: int | None = None, sp: int = 1, dp: int = 1, dtype=None,
                 use_pallas: bool | None = None,
                 compress_collectives: bool = False, batch: int = 1,
                 pod: bool = False, moe_sharding: str = "slice",
                 kv_cache_storage: str | None = None,
                 kv_cache_resident: int = 1024,
                 kv_cache_dir: str | None = None,
                 kv_pool: tuple[int, int] | None = None,
                 paged_kernel: bool | None = None,
                 moe_stats: bool = False):
        self.spec = spec
        self.tokenizer = tokenizer
        # what the backend decides when the caller left it open — bf16 +
        # kernels on tpu, f32 + XLA elsewhere — is resolved (and explained on
        # the start-up line) in one place; kernels off the chip need the
        # explicit interpret request
        from ..platform_env import resolve_kernel_policy

        policy = resolve_kernel_policy(use_pallas, dtype)
        self.dtype = policy.dtype
        use_pallas = policy.use_pallas
        self.compress = compress_collectives
        # one rounded resident value drives every paged-mode decision (the
        # fits-check, the tp default, and the ring allocation) — three
        # different thresholds here previously let `--kv-cache-resident 1000`
        # page against a ring rounded up to the full seq_len (empty cold,
        # pure callback overhead forever)
        self.kv_resident = max(64, (kv_cache_resident + 63) // 64 * 64)
        assert kv_cache_storage in (None, "ram", "host", "disc"), kv_cache_storage
        self.paged = (kv_cache_storage in ("host", "disc")
                      and spec.seq_len > self.kv_resident)
        # cache kinds that hold per-head keys and values, or one stack of
        # layers, say so: no silent wrong path
        if spec.latent or spec.lead_layers or spec.kinds:
            what = ("a latent cache row (kv_lora_rank > 0)" if spec.latent
                    else "layers that hold a state and no keys and values"
                    if spec.mixed
                    else "kinds of attention layer (ModelSpec.kinds)"
                    if spec.kinds
                    else "a leading dense stack (lead_layers > 0)")
            if self.paged:
                raise ValueError(
                    f"kv-cache-storage={kv_cache_storage}: the host-spill "
                    f"ring does not support {what}")
            if sp > 1:
                raise ValueError(
                    f"sp={sp}: the sequence-sharded (ring attention) cache "
                    f"does not support {what}")
        if spec.mixed:
            # a state that is not a list of positions (models/forward.py
            # StateCache): whole on one tp member, rows unsharded
            if (tp or 1) > 1 or dp > 1:
                raise ValueError(
                    f"tp={tp}, dp={dp}: a model with state layers (a gated "
                    "short convolution, a state-space mixer, a delta-rule "
                    "mixer) runs whole on one chip; its state is not "
                    "sharded")
            tp = 1
        if self.paged and tp is None:
            tp = 1  # paged mode is single-chip; don't let the mesh grab every device
        # Device-resident paged KV (docs/PAGED_KV.md): kv_pool=(n_blocks,
        # block_tokens) replaces the contiguous per-slot caches with a
        # (L, N, hk, bt, hs) block pool + per-row block tables (BatchEngine
        # owns the tables/refcounts; this engine allocates the arrays and
        # builds table-aware step programs). Excluded combinations fall
        # back to the dense layout here — ONE gate for every caller.
        if kv_pool is not None and (self.paged or sp > 1 or dp > 1):
            import sys

            print("💡 device-resident paged KV disabled: incompatible with "
                  + ("host/disc KV paging" if self.paged else "sp/dp sharding")
                  + " — using the dense contiguous cache layout",
                  file=sys.stderr)
            kv_pool = None
        self.kv_pool = kv_pool
        self._paged_kernel_req = paged_kernel  # resolved after use_pallas
        # batched serving of a routed model only (BatchEngine asks): the step
        # programs also return what the expert layers did (models/forward.py)
        self.moe_stats = moe_stats
        if pod:
            # multi-host job: mesh over EVERY chip in the job (the SPMD replacement
            # for the reference's worker fleet, dllama.cpp:205-221). Caller must have
            # run init_multihost() first so jax.devices() is global.
            from ..parallel.mesh import make_pod_mesh

            self.mesh = make_pod_mesh(tp=tp, sp=sp,
                                      dp=dp if dp > 1 else None)
            from ..parallel.mesh import AXIS_DP

            dp = self.mesh.shape[AXIS_DP]
        else:
            self.mesh = make_mesh(tp=tp, sp=sp, dp=dp)
        assert batch % dp == 0, (
            f"batch={batch} must divide over dp={dp} (each dp shard holds "
            "batch/dp cache rows)")
        self.tp = self.mesh.shape[AXIS_TP]
        self.sp = sp
        self.dp = dp
        # MoE expert placement: "slice" TP-slices every expert's hidden axis (the
        # reference's scheme); "expert" shards WHOLE experts over tp — the capacity
        # axis for Grok-1-314B-class expert weights (parallel/sharding.py)
        self.moe_sharding = moe_sharding if spec.is_moe else "slice" 
        params = hold_dense(params, self.dtype, spec)
        has_quant = any(
            getattr(t, "ftype", None) in (FloatType.Q40, FloatType.Q80)
            for st in stack_names(params) for t in params[st].values())
        self.use_pallas = use_pallas and has_quant
        if use_pallas and not has_quant:
            # the start-up line named the policy before any checkpoint was
            # read; /healthz and /v1/stats report what this engine runs
            print("💡 kernels: xla, whatever the start-up line said: this "
                  "checkpoint has no Q40/Q80 weights for the Pallas kernels")
        # paged-attention kernel gate (ops/pallas_paged_attention.py): the
        # kwarg wins (the tests' interpret-mode engines); the default follows
        # use_pallas (TPU + quantized weights), where the kernel beat the
        # gather path end to end in the dense cell (PERF.md §6, PR 27).
        self.paged_kernel = bool(
            self._paged_kernel_req if self._paged_kernel_req is not None
            else self.use_pallas) and self.kv_pool is not None
        if self.use_pallas:
            params = prepare_for_pallas(params, self.tp,
                                        moe_sharding=self.moe_sharding,
                                        spec=spec, mesh=self.mesh)
        self.params = shard_params(params, self.mesh, spec,
                                   moe_sharding=self.moe_sharding)
        # global (all-shard) weight bytes one decode step streams — per-chip traffic
        # divides by tp; used for the achieved-GB/s printout
        self.decode_weight_bytes = decode_stream_bytes(self.params, spec, batch)
        scale_plane_bytes(self.params)  # the gauges beside the memory's peak
        step_converted_bytes(self.params, self.dtype, self.use_pallas)
        self.rope = RopeTables.create(spec)
        self.batch = batch
        # Paged (out-of-core) KV cache — the reference's --kv-cache-storage
        # disc rebuilt TPU-native (runtime/paged_cache.py): device hot ring +
        # authoritative host/disk store + per-layer cold-attention callbacks.
        # A capacity valve for contexts whose cache exceeds HBM; single-chip,
        # single-sequence (use --sp to go FAST at long context instead).
        self.store = None
        if kv_cache_storage in ("host", "disc") and not self.paged:
            import sys

            print(f"💡 kv-cache-storage={kv_cache_storage} ignored: the full "
                  f"seq_len {spec.seq_len} cache fits the {self.kv_resident}-"
                  "slot resident budget (nothing to page)", file=sys.stderr)
        if self.paged:
            assert self.tp == 1 and sp == 1 and dp == 1 and batch == 1, (
                "paged KV cache is single-chip, single-sequence (tp=sp=dp="
                "batch=1); shard the cache over chips with --sp instead")
            from .paged_cache import HostKVStore

            host_dtype = (np.float32 if self.dtype == jnp.float32
                          else np.dtype(jnp.bfloat16))
            self.store = HostKVStore(spec, self.kv_resident, batch=1,
                                     storage=kv_cache_storage,
                                     directory=kv_cache_dir, dtype=host_dtype)
        self._steps: dict[int | None, object] = {}  # attn_window bucket -> jitted step
        self.k_cache, self.v_cache = self._init_cache()
        self.pos = 0
        self._decode_loops: dict[tuple, object] = {}  # (chunk, mode, window) -> loop
        self._loop_traffics: dict[tuple, object] = {}  # (chunk, mode) -> CollectiveTraffic
        self._measured_traffic = None  # lazy CollectiveTraffic of the T=1 decode step

    # attention reads only the first `window` cache positions — a static bucket so
    # decode cache traffic tracks the live context, not the allocated seq_len (the
    # reference's 0..pos attention loop gets this for free, llama2-tasks.cpp:62-93).
    # Buckets are powers of two from 256 up; each compiles once.
    _WINDOW_MIN = 256

    def _window_for(self, pos_end: int) -> int | None:
        """Smallest window bucket covering cache positions [0, pos_end)."""
        s = self.spec.seq_len
        if self.paged:
            return None  # the hot ring IS the window; cold attends on host
        if s <= self._WINDOW_MIN:
            return None  # tiny contexts: no bucketing
        w = self._WINDOW_MIN
        while w < pos_end:
            w *= 2
        return None if w >= s else w

    def _step_for(self, window: int | None):
        if window == "paged_warm":
            # warm phase of the paged engine: while pos + T <= resident the
            # ring layout coincides with a plain cache prefix (slot ==
            # position) and the cold segment is provably empty — run the
            # ordinary step over the ring-sized caches and skip the
            # n_layers host callback round-trips per step entirely
            window = None
        elif self.paged:
            if "paged" not in self._steps:
                from .paged_cache import make_paged_step

                self._steps["paged"] = make_paged_step(
                    self.spec, self.store, dtype=self.dtype,
                    use_pallas=self.use_pallas)
            return self._steps["paged"]
        if self.kv_pool is not None:
            # table-aware step (docs/PAGED_KV.md): same window buckets, one
            # extra (B, W) block-table argument — keyed apart from the dense
            # programs so the compile manifest tracks them separately
            key = ("pagedkv", window or 0)  # sortable: the whole context is 0
            if key not in self._steps:
                self._steps[key] = make_sharded_forward(
                    self.spec, self.mesh, self.params, dtype=self.dtype,
                    use_pallas=self.use_pallas,
                    compress_collectives=self.compress,
                    donate_cache=True, attn_window=window,
                    moe_sharding=self.moe_sharding,
                    kv_block_tokens=self.kv_pool[1],
                    paged_kernel=self.paged_kernel, moe_stats=self.moe_stats)
            return self._steps[key]
        if window not in self._steps:
            self._steps[window] = make_sharded_forward(
                self.spec, self.mesh, self.params, dtype=self.dtype,
                use_pallas=self.use_pallas, compress_collectives=self.compress,
                donate_cache=True, attn_window=window,
                moe_sharding=self.moe_sharding, moe_stats=self.moe_stats)
        return self._steps[window]

    @property
    def _step(self):
        """The full-window step (collective tracing / tests)."""
        return self._step_for(None)

    @classmethod
    def load(cls, model_path: str, tokenizer_path: str | None = None, *,
             max_seq_len: int = 0, weights_ftype: FloatType | None = None,
             **kw) -> "Engine":
        from ..formats.mfile import load_model

        spec, params = load_model(model_path, max_seq_len, weights_ftype)
        tokenizer = Tokenizer.load(tokenizer_path) if tokenizer_path else None
        if tokenizer is not None and tokenizer.vocab_size != spec.vocab_size:
            raise ValueError(
                f"tokenizer vocab {tokenizer.vocab_size} != model vocab {spec.vocab_size}")
        return cls(spec, params, tokenizer, **kw)

    def _init_cache(self):
        if self.paged:
            from .paged_cache import init_ring_cache

            return init_ring_cache(self.spec, self.kv_resident, batch=1,
                                   dtype=self.dtype)
        if self.kv_pool is not None:
            # device block pool (docs/PAGED_KV.md): (L, N, hk, bt, hs), kv
            # heads sharded over tp like the dense cache's head axis
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            from ..parallel.sharding import effective_kv_heads
            from ..parallel.mesh import AXIS_TP as _TP

            n_blocks, bt = self.kv_pool
            hk = effective_kv_heads(self.spec, self.tp)
            sh = NamedSharding(self.mesh, P(None, None, _TP))
            # a latent spec: one row a token, the second side empty; a spec
            # with state layers: the pool's layer axis holds the layers that
            # own rows, and the state stands beside the second side
            layers = len(self.spec.cache_layers)
            widths = self.spec.cache_widths
            from ..platform_env import interpret_requested

            if self.paged_kernel and not interpret_requested():
                # Mosaic moves a pool block in tiles of 128 lanes and refuses
                # a slice of 64 (heads of 64): the pool's rows are whole
                # lanes, zeros behind a head's values, which is what the
                # chip's tiled memory holds of a narrower row anyway
                widths = tuple(-(-w // 128) * 128 for w in widths)
            kc, vc = (
                jax.device_put(jnp.zeros(
                    (layers, n_blocks, hk, bt, w), self.dtype), sh)
                for w in widths)
            if self.spec.mixed:
                from ..models.forward import StateCache, init_state

                vc = StateCache(vc, *init_state(self.spec, self.batch,
                                                n_blocks, self.dtype))
            return kc, vc
        from ..parallel.tp import init_sharded_kv_cache

        return init_sharded_kv_cache(self.spec, self.mesh, batch=self.batch,
                                     dtype=self.dtype)

    def reset(self) -> None:
        self.pos = 0

    def seek(self, pos: int) -> None:
        """Set the decode position (prefix reuse rewind, api_server NaiveCache).

        Plain mode: the full cache keeps every position, so moving pos is
        enough. Paged mode: after a wrap, ring slots hold rows from the
        ABANDONED continuation's later positions, which the slot-position
        formula (models/forward.py paged branch) would mislabel as earlier
        committed rows — restore the ring from the authoritative host store
        (zeros for never-written slots are masked arithmetically)."""
        assert 0 <= pos <= self.pos, f"seek({pos}) past live context {self.pos}"
        if self.spec.mixed and 0 < pos < self.pos:
            from ..models.forward import STATE_RING

            if self.spec.ssm:
                raise ValueError(
                    f"seek({pos}) from {self.pos}: a state layer's "
                    "running matrix sums every earlier position and this "
                    "cache keeps no snapshot; rewind to 0 and prefill")
            if self.pos - pos > STATE_RING - self.spec.state_rows - 1:
                raise ValueError(
                    f"seek({pos}) from {self.pos}: a state layer's running "
                    f"state reaches {STATE_RING} positions back and this "
                    "cache keeps no snapshot; rewind to 0 and prefill")
        if self.paged and pos < self.pos:
            L, B, hk, R, hs = self.k_cache.shape
            n_stale = self.pos - pos
            if n_stale < R:
                # targeted patch (the speculative-decoding rollback path runs
                # this EVERY step with a rejected draft — a full ring rebuild
                # + HBM re-upload here would dwarf the step being saved):
                # each rolled-back position's slot must revert to its previous
                # occupant (position q-R, from the host store); slots whose
                # previous occupant is negative never held a valid row below
                # the new frontier and stay masked by the slot-position
                # formula regardless of content.
                stale = np.arange(pos, self.pos)
                prev = stale - R
                valid = prev >= 0
                if valid.any():
                    slots = jnp.asarray(stale[valid] % R)
                    krows = jnp.asarray(
                        np.asarray(self.store.k[:, :, :, prev[valid]],
                                   np.float32), self.dtype)
                    vrows = jnp.asarray(
                        np.asarray(self.store.v[:, :, :, prev[valid]],
                                   np.float32), self.dtype)
                    self.k_cache = self.k_cache.at[:, :, :, slots, :].set(krows)
                    self.v_cache = self.v_cache.at[:, :, :, slots, :].set(vrows)
            else:
                # rolled back a full wrap or more: rebuild the ring outright
                self._rebuild_ring(pos)
        self.pos = pos

    def _rebuild_ring(self, pos: int) -> None:
        """The device ring as it stands with `pos` positions committed, from
        the host store (authoritative for every committed position)."""
        L, B, hk, R, hs = self.k_cache.shape
        lo = max(0, pos - R)
        kr = np.zeros((L, B, hk, R, hs), np.float32)
        vr = np.zeros_like(kr)
        if pos > lo:
            idx = np.arange(lo, pos) % R
            kr[:, :, :, idx] = np.asarray(self.store.k[:, :, :, lo:pos],
                                          np.float32)
            vr[:, :, :, idx] = np.asarray(self.store.v[:, :, :, lo:pos],
                                          np.float32)
        self.k_cache = jnp.asarray(kr, self.dtype)
        self.v_cache = jnp.asarray(vr, self.dtype)

    def _trace_pos_args(self):
        """Trailing step args for collective-traffic tracing: start_pos
        (plus a zero block table in device-pool mode, where the step is
        table-aware and start_pos is per-row)."""
        if self.kv_pool is not None:
            w = -(-self.spec.seq_len // self.kv_pool[1])
            return (jnp.zeros((self.batch,), jnp.int32),
                    jnp.zeros((self.batch, w), jnp.int32))
        return (self._pos_arg(0),)

    def _pos_arg(self, pos):
        """start_pos step argument: scalar normally, per-row (B,) under dp sharding
        (the dp in_spec shards the row axis, so a scalar can't be passed)."""
        if self.dp > 1:
            return jnp.full((self.batch,), pos, jnp.int32)
        return jnp.int32(pos)

    def collective_stats(self):
        """Exact per-decode-step collective traffic of the compiled step program.

        Traces the T=1 decode step and accounts every collective it executes
        (scan-body psums x n_layers, logits all-gather, ...) with ring-algorithm
        wire costs — the measured replacement for collective_kbytes_per_token's
        analytic model (reference counted socket bytes, socket.cpp:280-285)."""
        if self._measured_traffic is None:
            from ..parallel.hlo_stats import jaxpr_collective_traffic

            tokens = jnp.zeros((self.batch, 1), jnp.int32)
            closed = jax.make_jaxpr(self._step)(
                self.params, self.rope, tokens, self.k_cache, self.v_cache,
                *self._trace_pos_args())
            self._measured_traffic = jaxpr_collective_traffic(
                closed, dict(self.mesh.shape))
            from ..parallel.hlo_stats import publish_traffic

            # surface the measured numbers as gauges — EQuARX-style accounting
            # as a permanent /metrics fact, not a one-off bench artifact
            publish_traffic(self._measured_traffic, program="decode_t1")
        return self._measured_traffic

    def compiled_collective_stats(self):
        """Collective traffic read from the XLA-OPTIMIZED module of the T=1 step —
        the cross-check for collective_stats(): the jaxpr accounting predicts what
        was traced; this sees what XLA actually lowered (all-reduce rewrites,
        combining, async pairs). Semantics differ on loops: the jaxpr walker
        multiplies scan-body collectives by the trip count, while this counts HLO
        instructions once (the layer scan compiles to a while loop), so per-layer
        collectives appear once here — compare per-instruction kinds/payloads, not
        totals. Costs a full compile on first call (memoized after)."""
        if getattr(self, "_compiled_traffic", None) is not None:
            return self._compiled_traffic
        from ..parallel.hlo_stats import collective_traffic

        tokens = jnp.zeros((self.batch, 1), jnp.int32)
        lowered = jax.jit(self._step).lower(
            self.params, self.rope, tokens, self.k_cache, self.v_cache,
            *self._trace_pos_args())
        hlo = lowered.compile().as_text()
        self._compiled_traffic = collective_traffic(hlo, self.tp * self.sp)
        return self._compiled_traffic

    def _fill_traffic(self, stats: GenerationStats, measured=None,
                      per_tokens: int = 1) -> None:
        """Per-token S/R from `measured` (a CollectiveTraffic for a program covering
        `per_tokens` tokens) or, when None, the analytic model — provenance recorded
        either way. Each program (host step vs device loop) must be measured by its
        own trace; a different program's numbers are never presented as measured."""
        if measured is not None:
            kb = measured.sent_bytes_per_device / per_tokens / 1024.0
            stats.sent_kbytes_per_token = stats.recv_kbytes_per_token = kb
            stats.traffic_source = "measured"
        else:
            stats.sent_kbytes_per_token = stats.recv_kbytes_per_token = (
                collective_kbytes_per_token(self.spec, self.tp, self.compress))
            stats.traffic_source = "modeled"

    # ------------------------------------------------------------------
    # core stepping
    # ------------------------------------------------------------------

    def infer_chunk(self, tokens: list[int] | np.ndarray) -> np.ndarray:
        """Run a chunk of tokens at the current position; returns last-token logits
        (vocab,) and advances pos. Bounds-checked against seq_len (the reference hard-stops
        at context end, dllama.cpp:190-192)."""
        return self._infer(tokens)[-1]

    def _infer(self, tokens: list[int] | np.ndarray) -> np.ndarray:
        """One step over T tokens; returns all T positions' logits (T, vocab)
        and advances pos (shared body of infer_chunk / infer_chunk_logits)."""
        tokens = np.asarray(tokens, dtype=np.int32)
        t = len(tokens)
        if self.pos + t > self.spec.seq_len:
            raise ValueError(f"context overflow: pos {self.pos} + {t} > {self.spec.seq_len}")
        with trace.span("engine.dispatch", {"t": t, "pos": self.pos}):
            return self._infer_traced(tokens, t)

    def dispatch(self, tokens: np.ndarray) -> jax.Array:
        """Enqueue the plain (not host-paged) step over T tokens at the current
        position and advance pos. Returns the (B, T, vocab) logits still on the
        device and not waited for: `_infer` adds the host copy the sampler
        needs, a caller that times the device puts its own fence after this."""
        assert not self.paged, "the host-paged step appends to its store"
        t = len(tokens)
        step = self._step_for(self._window_for(self.pos + t))
        logits, self.k_cache, self.v_cache = step(
            self.params, self.rope, self._tiled(tokens), self.k_cache,
            self.v_cache, self._pos_arg(self.pos))
        self.pos += t
        return logits

    def _tiled(self, tokens: np.ndarray) -> jax.Array:
        # the host loop drives ONE sequence; with batch>1 slots (BatchEngine backing
        # store) or dp sharding, tile the row across the batch so token/cache/pos
        # shapes stay congruent (rows 1.. do redundant work; BatchEngine drives the
        # step directly with real per-row data instead)
        if self.batch > 1 and not getattr(self, "_warned_tiled_batch", False):
            self._warned_tiled_batch = True
            import sys

            print(f"⚠️  Engine(batch={self.batch}) host loop tiles one sequence "
                  f"across all {self.batch} rows — {self.batch}x redundant compute. "
                  "Use BatchEngine (api_server --batch) to drive real per-row "
                  "requests.", file=sys.stderr)
        return jnp.tile(jnp.asarray(tokens)[None, :], (self.batch, 1))

    def _infer_traced(self, tokens: np.ndarray, t: int) -> np.ndarray:
        faults.fire("engine.dispatch", t=t, pos=self.pos)
        t0 = time.perf_counter()
        if not self.paged:
            logits = self.dispatch(tokens)
        elif self.pos + t <= self.kv_resident:
            # warm phase: slot == position, cold empty, so the callback-free
            # plain step runs (see _step_for; the paged step only
            # builds once real cold history is about to exist), with the new
            # rows sliced from the committed ring for the host-store append
            # (the authoritative history the paged step's cold callbacks will
            # read once the ring wraps)
            logits, self.k_cache, self.v_cache = self._step_for("paged_warm")(
                self.params, self.rope, self._tiled(tokens), self.k_cache,
                self.v_cache, self._pos_arg(self.pos))
            self.store.append(
                np.asarray(self.k_cache[:, :, :, self.pos:self.pos + t]),
                np.asarray(self.v_cache[:, :, :, self.pos:self.pos + t]),
                self.pos)
            self.pos += t
        else:
            try:
                logits, self.k_cache, self.v_cache, (k_rows, v_rows) = (
                    self._step_for(None)(
                        self.params, self.rope, self._tiled(tokens),
                        self.k_cache, self.v_cache, self._pos_arg(self.pos)))
                k_rows, v_rows = np.asarray(k_rows), np.asarray(v_rows)
            except Exception:
                # a cold callback raised inside the step, which had been
                # given the ring (donated): the ring is gone with it, and
                # the engine would be unusable even after reset()
                self._rebuild_ring(self.pos)
                raise
            # the host store is the authoritative history the next step's
            # cold callbacks read — append before advancing pos
            self.store.append(k_rows, v_rows, self.pos)
            self.pos += t
        out = np.asarray(logits)[0]  # the sampler needs them on the host
        dt = time.perf_counter() - t0
        # a 1-token dispatch is decode-shaped regardless of which loop issued
        # it (prefill's tail chunks of 1 land here too — same program, same
        # cost); decode TOKENS are counted at the generation loops, which know
        # whether a token was decoded or merely prompt-ingested
        (_DISP_PREFILL if t > 1 else _DISP_DECODE).observe(dt)
        return out

    def infer_chunk_logits(self, tokens: list[int] | np.ndarray) -> np.ndarray:
        """infer_chunk, but returns ALL T positions' logits (T, vocab) — the
        verify step of speculative decoding (runtime/speculative.py) needs
        every position's argmax. Advances pos by T like infer_chunk;
        speculative callers seek() back to the verified frontier."""
        return self._infer(tokens)

    def generate_speculative(self, prompt_tokens: list[int], max_tokens: int,
                             sampler, *, k: int = 8, on_token=None,
                             stop_check=None,
                             history_tokens: list[int] | None = None):
        """Greedy prompt-lookup speculative decoding (runtime/speculative.py):
        emits exactly generate()'s tokens, usually in fewer dispatches."""
        from .speculative import generate_speculative

        return generate_speculative(self, prompt_tokens, max_tokens, sampler,
                                    k=k, on_token=on_token,
                                    stop_check=stop_check,
                                    history_tokens=history_tokens)

    def prefill(self, tokens: list[int], stats: GenerationStats | None = None) -> np.ndarray:
        """Chunked prompt ingestion; returns logits after the last prompt token."""
        t0 = time.perf_counter()
        tokens = list(tokens)
        logits = None
        i = 0
        with trace.span("engine.prefill", {"tokens": len(tokens)}):
            while i < len(tokens):
                for chunk in PREFILL_CHUNKS:
                    if len(tokens) - i >= chunk:
                        logits = self.infer_chunk(tokens[i:i + chunk])
                        i += chunk
                        break
        _PREFILL_TOKENS.inc(len(tokens))
        dt_ms = (time.perf_counter() - t0) * 1000.0
        # flight-recorder timeline entry for the sequential serving path
        # (--batch 1): rid resolves from the caller's bound trace context
        # (api_server handler thread), no-op outside a recorded request
        flight.event(None, "prefill", tokens=len(tokens),
                     ms=round(dt_ms, 3))
        if stats is not None:
            stats.prefill_ms = dt_ms
            stats.prompt_tokens = len(tokens)
        return logits

    def generate(self, prompt_tokens: list[int], max_tokens: int, sampler,
                 on_token=None, stop_check=None) -> tuple[list[int], GenerationStats]:
        """Host generation loop: prefill + sample/step until max_tokens, context end, or
        stop_check truth. on_token(token_id) streams tokens out."""
        stats = GenerationStats()
        self._fill_traffic(stats, self._measured_traffic)
        logits = self.prefill(prompt_tokens, stats)
        out: list[int] = []
        for _ in range(max_tokens):
            if self.pos >= self.spec.seq_len:
                break
            t0 = time.perf_counter()
            token = sampler.sample(logits)
            out.append(token)
            stats.generated_tokens += 1
            if on_token is not None:
                on_token(token)
            if stop_check is not None and stop_check(token):
                break
            if self.pos >= self.spec.seq_len:
                break
            t1 = time.perf_counter()
            logits = self.infer_chunk([token])
            t2 = time.perf_counter()
            _DECODE_TOKENS.inc()
            stats.infer_ms.append((t2 - t1) * 1000.0)
            stats.token_ms.append((t2 - t0) * 1000.0)
            stats.dispatch_ms.append((t2 - t1) * 1000.0)
        return out, stats

    def generate_with(self, prompt_tokens: list[int], max_tokens: int, sampler,
                      *, device_loop_chunk: int = 0, speculative_k: int = 0,
                      history_tokens: list[int] | None = None,
                      **kw) -> tuple[list[int], GenerationStats]:
        """generate / generate_chunked / generate_speculative dispatch — the
        single switch point for every app surface's --device-loop and
        --speculative flags. Speculation is greedy-only (temperature 0) and
        wins over the device loop when both are requested. history_tokens
        (optional, speculative only): full already-cached context for the
        n-gram proposer when prompt_tokens is a prefix-reuse delta."""
        if speculative_k > 0:
            if getattr(sampler, "temperature", 0.0) == 0.0:
                return self.generate_speculative(prompt_tokens, max_tokens,
                                                 sampler, k=speculative_k,
                                                 history_tokens=history_tokens,
                                                 **kw)
            if not getattr(self, "_warned_spec_fallback", False):
                # once per engine, not per request — a serving default of
                # temperature 0.7 would otherwise print this on every call
                self._warned_spec_fallback = True
                import sys

                print("⚠️  --speculative is greedy-only (temperature 0); "
                      "falling back to the "
                      + ("on-device loop" if device_loop_chunk > 0
                         and not self.paged else "sequential host loop")
                      + " for sampled requests.", file=sys.stderr)
        if device_loop_chunk > 0:
            if self.paged:
                import sys

                print("⚠️  --device-loop is incompatible with the paged KV "
                      "cache (host-store appends happen between dispatches); "
                      "using the host loop.", file=sys.stderr)
            else:
                return self.generate_chunked(prompt_tokens, max_tokens, sampler,
                                             chunk=device_loop_chunk, **kw)
        return self.generate(prompt_tokens, max_tokens, sampler, **kw)

    # ------------------------------------------------------------------
    # device-loop generation (one dispatch per chunk of tokens)
    # ------------------------------------------------------------------

    def _decode_loop(self, chunk: int, mode: str, window: int | None = None):
        if (chunk, mode, window) not in self._decode_loops:
            from .device_loop import make_decode_loop

            self._decode_loops[chunk, mode, window] = make_decode_loop(
                self.spec, self.mesh, self.params, chunk, mode=mode, dtype=self.dtype,
                use_pallas=self.use_pallas,
                compress_collectives=self.compress, donate_cache=True,
                attn_window=window, moe_sharding=self.moe_sharding)
        return self._decode_loops[chunk, mode, window]

    def _loop_traffic(self, chunk: int, mode: str, loop):
        """Measured collective traffic of the device-loop program itself (it is a
        different compiled program than the host step — its own trace, not the
        T=1 step's, covers `chunk` tokens). Computed only when the user opted into
        measurement via collective_stats() — tracing a large model costs seconds."""
        key = (chunk, mode)
        if key not in self._loop_traffics:
            from ..parallel.hlo_stats import jaxpr_collective_traffic

            closed = jax.make_jaxpr(loop)(
                self.params, self.rope, jnp.int32(1), self.k_cache, self.v_cache,
                jnp.int32(0), jax.random.PRNGKey(0), jnp.float32(0.0),
                jnp.float32(0.9))
            self._loop_traffics[key] = jaxpr_collective_traffic(
                closed, dict(self.mesh.shape))
        return self._loop_traffics[key]

    def generate_chunked(self, prompt_tokens: list[int], max_tokens: int, sampler,
                         on_token=None, stop_check=None, chunk: int = 16,
                         ) -> tuple[list[int], GenerationStats]:
        """Generate with the on-device scan loop: forward + sample stay on device and
        each dispatch returns `chunk` tokens (vs the reference's strictly per-token host
        loop, dllama.cpp:17-94). Greedy (temperature 0) emits exactly the host loop's
        tokens; stochastic sampling uses the device PRNG (not xorshift-bit-compatible).

        KV-cache positions beyond an early stop are overwritten by later writes at those
        positions, so mid-chunk stops need no rollback.
        """
        stats = GenerationStats()
        self._fill_traffic(stats)
        if len(prompt_tokens) > 1:
            self.prefill(prompt_tokens[:-1], stats)
        stats.prompt_tokens = len(prompt_tokens)
        # sampler.state is a full-range uint64 (xorshift*); PRNGKey takes an int64
        key = jax.random.PRNGKey(int(getattr(sampler, "state", 0)) & (2**63 - 1))
        temperature = getattr(sampler, "temperature", 0.0)
        topp = getattr(sampler, "topp", 0.9)
        out: list[int] = []
        token = prompt_tokens[-1]
        mode = "greedy" if temperature == 0.0 else "sample"
        done = False
        while not done and len(out) < max_tokens:
            want = max_tokens - len(out)
            seq_left = self.spec.seq_len - self.pos
            if seq_left <= 0:
                break
            if seq_left < chunk:
                # near the context end a full chunk would overrun the cache; finish
                # with the per-token host loop instead of compiling a tail-sized scan
                tail, tail_stats = self.generate(
                    [token], min(want, seq_left), sampler, on_token=on_token,
                    stop_check=stop_check)
                out.extend(tail)
                stats.generated_tokens += len(tail)
                stats.token_ms.extend(tail_stats.token_ms)
                stats.infer_ms.extend(tail_stats.infer_ms)
                break
            # always run the compiled full-chunk program; a short tail (want < chunk)
            # just truncates the emitted tokens — cache entries past pos are dead and
            # overwritten by later writes at those positions
            loop = self._decode_loop(chunk, mode, self._window_for(self.pos + chunk))
            if self._measured_traffic is not None and stats.traffic_source != "measured":
                self._fill_traffic(stats, self._loop_traffic(chunk, mode, loop),
                                   per_tokens=chunk)
            t0 = time.perf_counter()
            with trace.span("engine.device_loop", {"chunk": chunk,
                                                   "pos": self.pos}):
                key, sub = jax.random.split(key)
                tokens, _, self.k_cache, self.v_cache = loop(
                    self.params, self.rope, token, self.k_cache, self.v_cache,
                    self.pos, sub, temperature, topp)
                tokens = np.asarray(tokens)[:want]
            dt_full = (time.perf_counter() - t0) * 1000.0
            _DISP_LOOP.observe(dt_full / 1000.0)
            _DECODE_TOKENS.inc(len(tokens))
            flight.event(None, "device_loop", chunk=chunk,
                         emitted=len(tokens), ms=round(dt_full, 3))
            stats.dispatch_ms.append(dt_full)
            # the dispatch always computes a full `chunk` of tokens even when the
            # emitted tail is shorter — divide by the compiled chunk size so
            # per-token stats reflect actual device cost
            dt_ms = dt_full / chunk
            for i, t in enumerate(tokens.tolist()):
                out.append(t)
                stats.generated_tokens += 1
                stats.token_ms.append(dt_ms)
                stats.infer_ms.append(dt_ms)
                if on_token is not None:
                    on_token(t)
                if stop_check is not None and stop_check(t):
                    done = True
                    self.pos += i + 1
                    break
            else:
                self.pos += len(tokens)
                token = int(tokens[-1])
        return out, stats
