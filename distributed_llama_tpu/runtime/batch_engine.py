"""Continuous-batching engine: concurrent sequences share one batched SPMD step.

The reference API server is a single-request accept loop (dllama-api.cpp:418-429) and
its whole runtime is batch=1 (no batch dim anywhere, funcs.cpp:424). On TPU a decode
step is HBM-bandwidth-bound — the weights stream past the MXU once per step regardless
of how many sequences ride along — so batching B requests costs nearly the same wall
time as one and multiplies throughput. This module is therefore a capability extension
beyond reference parity, built on the per-row `start_pos` support in models/forward.py:
each KV-cache row advances at its own position (continuous batching).

Design:
- B cache "slots", each holding one sequence's KV rows + host-side state.
- One scheduler thread owns the device. The decode hot path is a K-step
  SUPER-STEP (runtime/device_loop.py make_batched_decode_loop): forward +
  sampling scan K steps entirely on device and the host gets a (K, B) token
  block back in ONE transfer — 1 host sync per K decoded tokens instead of 1
  per token. K adapts: when a new request is waiting (or a row is within K of
  finishing) the scheduler falls back to single T=1 batched steps so admission
  latency stays bounded by one step, not K.
- Prefill never stalls decode: a prefill chunk dispatches TOGETHER with the
  active decode rows in one mixed (B, chunk) step — the prefill row carries
  chunk real tokens, each decode row carries its next token at index 0 (its
  remaining positions are scratch writes on masked future slots), and each
  decode row's logits read from index 0. One dispatch advances the prefill
  chunk AND every active sequence by one token. The program is told which row
  prefills and runs its weights over the chunk and one row a slot, not over
  the (B, chunk) rectangle (models/forward.py RowMap); its head runs at the
  one position a row that is sampled.
- Idle rows ride along with their start_pos parked at their current position: their
  cache writes land at future positions that are masked now and overwritten when those
  positions actually decode, so no masking program is needed.
- EOS/stop detection stays host-side, applied to the returned token block; a
  row that stops mid-block simply keeps its position at the verified frontier
  (the over-decoded rows beyond it sit on masked slots and are overwritten by
  the slot's next writes — the same free-rollback property speculative
  decoding relies on).
- PIPELINED super-steps (docs/SERVING.md "Pipelined decode"): the decode loop
  returns its final carry (last token, positions, xorshift* state) as device
  arrays, so super-step N+1 is issued CHAINED from N's device state before
  N's (K, B) block has even reached the host — the device runs N+1 while the
  host delivers N (EOS/stop scan, callbacks, sampler resync). When delivery
  shows the speculated schedule diverged (a row stopped/cancelled/errored
  mid-block, so N+1 decoded past the real frontier), the in-flight dispatch
  is FLUSHED: its tokens are discarded via the same free frontier-rewind
  rollback, clamp_pos keeps a context-end park from poisoning the prefix
  harvest, and the next dispatch re-uploads host state (the sampler RNG
  round-trips bit-exactly through a flush). Admission breaks the chain
  instead of riding it, bounding admission latency at one in-flight window.
- Sampling runs ON DEVICE inside the super-step with the host Sampler's
  xorshift* stream (state uploaded before, written back after), host-side
  elsewhere (prefill boundaries, single-step mode). Greedy super-steps emit
  bit-exactly the host loop's tokens.
- Per-slot NaiveCache prefix reuse (dllama-api.cpp:187-232): a new request lands on the
  free slot sharing the longest token prefix and rewinds instead of re-prefilling.
- A MODEL WITH STATE (ModelSpec.mixed: layers whose mixer is a gated short
  convolution hold their last two rows of v and no keys) gets the same free
  rollback for a short horizon and snapshots beyond it (models/forward.py
  StateCache, docs/PAGED_KV.md "Typed block payload"). The running state is
  a ring of v rows a slot by position mod 64, so each "free rollback" above
  holds as written while the writes ahead of a row's frontier stay inside
  the ring: a parked or idle row's scratch write lands AT its frontier and
  the two rows it continues from are untouched; a row that stops mid-block,
  and the rows of a flushed chained super-step (at most two scans past the
  accepted frontier: the constructor refuses a superstep that does not
  fit), wrote ring rows and block snapshots at positions that are written
  again when they are decoded for good. What the ring cannot give is a jump
  further back: a slot rewind and a prefix hit land on a BLOCK END, where
  every state layer's state was snapshot into the pool beside the block's
  keys and values, and the admission seeds the ring from it; a clamped park
  truncates the reusable history as it does for keys, and the next
  admission rewinds to a snapshot under it. Speculative verify, the dense
  per-slot caches, the Q80 tier and KV-block streaming refuse such a model.
  A STATE-SPACE layer (ModelSpec.ssm) holds besides such a tail a running
  MATRIX a head that sums every earlier position: no ring holds it, so it is
  CARRIED. A dispatch is told which slots are live (runtime/slot_cache.py
  `state_word`) and leaves
  every other slot's matrices bit for bit; a row at position 0 starts from
  zeros; a row over-decoded in a scan is gone with its request; a flushed
  chained scan's survivors go back to the matrices as that scan found them
  (`_flush_inflight`); and snapshots are taken every 256 positions into a
  pool of entries (cache/device_pool.py SnapshotPool): a prefix hit, a rewind
  and a resume land on the newest block under them that carries one.
- CROSS-REQUEST prefix reuse (runtime/slot_cache.py, docs/PREFIX_CACHE.md): a
  finished slot's committed prefix is harvested into a radix-indexed block
  pool; a new request whose prompt shares cached blocks — on ANY slot —
  starts from them and prefills only the uncached suffix. The same-slot
  rewind above remains as the token-granular (and copy-free) fast path.
"""

from __future__ import annotations

import dataclasses
import functools
import queue
import threading
import time
import uuid
from dataclasses import dataclass, field
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..cache import warn_degraded
from ..models.forward import STATE_RING, compact_rows
from ..models.spec import ModelSpec
from ..obs import flight, metrics, process, reqctx, trace
from ..ops.pallas_paged_attention import visited_keys
from ..resilience import faults
from ..resilience.errors import (DeadlineExceeded, EngineClosed,
                                 EngineDraining, EngineSaturated,
                                 EngineWedged, InvalidRequest, classify)
from ..resilience.tenancy import (CLASSES, DEFAULT_TENANT, DrainRate,
                                  TenantRegistry, WeightedFairQueue)
from .engine import PREFILL_CHUNKS, GenerationStats
from .sampler import Sampler
from .slot_cache import make_slot_cache, start_host_copy
from .speculative import (AdaptiveK, NgramProposer, ProposerMux,
                          verify_block_bucket)

__all__ = ["BatchEngine", "BatchRequest"]

# Scheduler telemetry (docs/OBSERVABILITY.md). The super-step scheduler was a
# black box: admission latency, dispatch mix, rollback volume, and slot
# occupancy were all invisible outside one-off bench runs.
_QUEUE_WAIT = metrics.histogram(
    "batch_queue_wait_seconds",
    "submit() to slot assignment (admission latency incl. queueing)")
_QUEUE_DEPTH = metrics.gauge(
    "batch_queue_depth", "Requests waiting for a free slot")
_SLOTS_TOTAL = metrics.gauge(
    "batch_slots_total", "Configured cache slots (--batch)")
_SLOTS_OCCUPIED = metrics.gauge(
    "batch_slots_occupied", "Cache slots holding a live request")
_DISPATCH_SECONDS = metrics.histogram(
    "batch_dispatch_seconds",
    "Wall time of one scheduler device dispatch, by shape",
    labelnames=("kind",))
_DISP_PREFILL = _DISPATCH_SECONDS.labels(kind="prefill")
_DISP_MIXED = _DISPATCH_SECONDS.labels(kind="mixed")
_DISP_SINGLE = _DISPATCH_SECONDS.labels(kind="single_step")
_DISP_SUPER = _DISPATCH_SECONDS.labels(kind="super_step")
_DISP_VERIFY = _DISPATCH_SECONDS.labels(kind="verify")
_SUPERSTEP_TOKENS = metrics.histogram(
    "batch_superstep_tokens",
    "Tokens decoded per super-step dispatch (sum of row budgets)",
    buckets=metrics.DEFAULT_SIZE_BUCKETS)
_ROLLBACK_TOKENS = metrics.counter(
    "batch_rollback_tokens_total",
    "Device-decoded tokens discarded by host-side stop/cancel frontier rewind")
_PARKED_ROW_STEPS = metrics.counter(
    "batch_parked_row_steps_total",
    "Row-steps spent parked (rows riding a dispatch without advancing)")
# The second kind of cached state (a model with state layers, ModelSpec.mixed:
# models/forward.py StateCache), from the shapes and the live rows like the
# counters below: nothing is read from the device.
_STATE_ROWS = metrics.counter(
    "batch_state_rows_advanced_total",
    "Rows a dispatch wrote into the state layers' rings for a token of a "
    "request: real positions x state layers")
_STATE_SNAPSHOTS = metrics.counter(
    "batch_state_snapshots_total",
    "Block snapshots of the state layers' state a dispatch wrote for a "
    "token of a request: real positions that end a pool block x state layers")
_BLOCK_ENDS = metrics.counter(
    "batch_block_ends_total",
    "Real dispatched positions that end a pool block (position + 1 a "
    "multiple of the block's tokens), whatever the model: what "
    "batch_state_snapshots_total is held against, a state layer")
_STATE_BYTES = metrics.counter(
    "batch_state_bytes_written_total",
    "Bytes a dispatch writes into the state layers' rings and snapshots, "
    "parked rows' scratch writes included: what the second kind of state "
    "costs a step in HBM writes")
# What a dispatch is given against what it needs, from the shapes and the
# live rows (host integers, nothing read from the device): positions are
# rows x T (or rows x K of a scan), attention pairs are positions x the
# window bucket; real ones hold a token of a request, with its causal length.
_POSITIONS_DISPATCHED = metrics.counter(
    "batch_positions_dispatched_total",
    "Rows the dispatched programs ran their weights over: slots x T of a "
    "step or verify block, slots x K of a scan, a prefill chunk's T + slots "
    "up to whole row tiles; parked rows and padding included")
_POSITIONS_REAL = metrics.counter(
    "batch_positions_real_total",
    "Dispatched positions that held a token of a request: a prefill chunk's "
    "tokens, one per rider or active row, a row's budget in a scan or verify")
_ATTN_PAIRS_DISPATCHED = metrics.counter(
    "batch_attn_pairs_dispatched_total",
    "Query-key pairs the attention kernel was asked for: slots x T "
    "positions (a prefill chunk that names its prefilling row: T + slots, "
    "that row's chunk and one query a slot) x the window bucket (the "
    "context length where unbucketed)")
_MOE_COUNTERS = tuple(metrics.counter(name, doc) for name, doc in (
    ("batch_moe_assignments_total",
     "Expert assignments the routed layers were given: dispatched rows x "
     "experts per token, summed over layers (parked rows and a rider's "
     "scratch positions too: the expert layer computes them)"),
    ("batch_moe_rows_computed_total",
     "Rows the grouped expert layer computed: every chosen expert's run "
     "padded to the row tile, summed over layers "
     "(ops/moe_grouped.py)"),
    ("batch_moe_experts_touched_total",
     "Distinct experts a dispatch read, summed over layers (and over the "
     "steps of a K-step scan): the experts whose weights crossed HBM"),
    ("batch_moe_experts_offered_total",
     "Experts a dispatch could have read: experts x layers (x the steps "
     "of a K-step scan)"),
    ("batch_moe_grouped_assignments_total",
     "Of batch_moe_assignments_total, those of dispatches that went through "
     "the grouped expert layer (models/forward.py sends a dispatch with "
     "many rows an expert through the all-experts scan)"),
    ("batch_moe_grouped_experts_touched_total",
     "Of batch_moe_experts_touched_total, those the grouped expert layer "
     "read")))
_MOE_ROUTED = metrics.counter(
    "batch_moe_routed_total",
    "Expert assignments the routers made: dispatched rows x experts per "
    "token, summed over the routed layers, whether this engine holds the "
    "chosen expert or not (batch_moe_assignments_total counts those on held "
    "experts: the two differ where the checkpoint holds a share of the "
    "router's width)")
_LATENT_ROWS_READ = metrics.counter(
    "batch_latent_rows_read_total",
    "Latent cache rows attention was asked to read: every dispatched row's "
    "committed length, once a layer, all heads reading each row once (the "
    "kernel's steps of 128 keys and its query blocks re-read some of them: "
    "not counted; a chunk's prefilling row twice, once in each of the two "
    "reads a chunk makes)")
_LATENT_DISPATCH_ROWS = metrics.counter(
    "batch_latent_dispatch_rows_total",
    "Query positions latent attention was run for: dispatched positions x "
    "layers, parked rows and padding included (a chunk that names its "
    "prefilling row: the chunk's positions and one a slot)")
_KV_ROW_BYTES = metrics.gauge(
    "kv_pool_row_bytes",
    "Bytes the cache really holds a token a layer (all kv heads, both sides, "
    "the lanes' padding of a latent row included)")
_ATTN_PAIRS_VISITED = metrics.counter(
    "batch_attn_pairs_visited_total",
    "Query-key pairs of the window the attention kernel computed: for every "
    "row of the dispatch, parked ones too, T x the keys of the steps that "
    "hold its committed length (ops/pallas_paged_attention.visited_keys; "
    "the chunk's own T x T fold is in neither this nor the dispatched "
    "count; a chunk that names its prefilling row: T x that row's keys and "
    "every row's once). Equal to the dispatched count where the kernel does "
    "not run")
_ATTN_WINDOW_PAIRS_VISITED = metrics.counter(
    "batch_attn_window_pairs_visited_total",
    "batch_attn_pairs_visited_total's count for the layers with a sliding "
    "window alone, summed over those layers (not averaged): T x the keys of "
    "the steps the kernel runs for each row, none wholly behind the window's "
    "lower bound. Nothing where no layer has a window")
_ATTN_WINDOW_PAIRS_UNWINDOWED = metrics.counter(
    "batch_attn_window_pairs_unwindowed_total",
    "What the same layers would have visited with no window: the steps up "
    "to the row's committed length. The ratio of the two is the share of "
    "the window layers' key traffic that the skip leaves")
_ATTN_HEADS = metrics.gauge(
    "batch_attn_heads",
    "Query heads of an attention layer by kind of layer ('window' where it "
    "has a sliding window, 'full' where not; a model of one head count "
    "reports it under the kinds it has)", labelnames=("kind",))
_ATTN_PAIRS_REAL = metrics.counter(
    "batch_attn_pairs_real_total",
    "Query-key pairs causal attention needs: for each real position, its "
    "position + 1")
_PREFILL_TOKENS = metrics.counter(
    "batch_prefill_tokens_total", "Prompt tokens prefilled by the scheduler")
_DECODE_TOKENS = metrics.counter(
    "batch_decode_tokens_total", "Tokens delivered to requests by the scheduler")
_REQUESTS = metrics.counter(
    "batch_requests_total", "Completed requests by finish reason",
    labelnames=("finish",))
# Resilience telemetry (docs/ROBUSTNESS.md): every unhappy-path decision the
# scheduler makes — error blast radius, transient retries, shed admissions,
# expired deadlines — is a counter, and scheduler liveness is a gauge pair
# (alive flag + seconds since the last successful dispatch) so a hung or dead
# scheduler is visible on /metrics before clients notice.
_ENGINE_ERRORS = metrics.counter(
    "engine_errors_total",
    "Dispatch/scheduler errors by blast radius "
    "(transient=retried, request=failed one request, engine=failed all)",
    labelnames=("kind",))
_RETRIES = metrics.counter(
    "engine_retries_total",
    "Transient dispatch failures retried with backoff")
_SHED = metrics.counter(
    "engine_shed_requests_total",
    "Admissions refused because the queue was at --max-queue")
_DEADLINE_EXPIRED = metrics.counter(
    "engine_deadline_expired_total",
    "Requests expired by queue TTL or generation deadline, by where",
    labelnames=("where",))
_SCHED_ALIVE = metrics.gauge(
    "batch_scheduler_alive",
    "1 while the BatchEngine scheduler thread is running (0 = dead/idle)")
_DISPATCH_AGE = metrics.gauge(
    "batch_dispatch_age_seconds",
    "Dispatch watchdog: seconds since the scheduler last completed a device "
    "dispatch, 0 while idle (read at scrape time)")
# Pipelined super-step telemetry (docs/SERVING.md "Pipelined decode"): the
# gap histogram is the win (host time between dispatches -> ~0 when
# chained), the flush counter the cost (speculated device work discarded
# when the host schedule diverged).
_DISPATCH_GAP = metrics.histogram(
    "batch_dispatch_gap_seconds",
    "Host time between the previous dispatch's results reaching the host "
    "and this dispatch being issued, observed before every dispatch that "
    "follows one without an idle wait (0 when chained from device state "
    "while the predecessor is in flight). Host clock: the device's own idle "
    "time is read from a profiler trace (sched.gap_ms)")
# What a dispatch sends to the device and brings back, counted where the
# copies are made (_upload, _step, _count_moe, _deliver_super_step), and a
# synchronous dispatch's three phases on the host's clock: what /metrics
# says of a chip that idles when no profiler session runs.
_H2D_TRANSFERS = metrics.counter(
    "batch_h2d_transfers_total",
    "Host-to-device copies the scheduler made for its dispatches (tokens, "
    "positions, the block table, a scan's or verify block's host inputs, "
    "seeded prefix rows)")
_H2D_BYTES = metrics.counter(
    "batch_h2d_bytes_total", "Bytes of those host-to-device copies")
_D2H_BYTES = metrics.counter(
    "batch_d2h_bytes_total",
    "Bytes of dispatch results fetched to the host: a step's logits (two "
    "positions a row), a routed model's stats vector, a scan's or verify "
    "block's tokens, sampler states and accept lengths")
_PHASE_SECONDS = metrics.histogram(
    "batch_dispatch_phase_seconds",
    "One synchronous dispatch (prefill, mixed, single_step) by phase: "
    "launch (the jitted step called until it returns its futures), wait "
    "(the host blocked until the device has finished the program), copy "
    "(from then until every row's logits are host memory)",
    labelnames=("phase",))
_PHASE_LAUNCH, _PHASE_WAIT, _PHASE_COPY = (
    _PHASE_SECONDS.labels(phase=p) for p in ("launch", "wait", "copy"))
_PIPELINE_DEPTH = metrics.gauge(
    "batch_pipeline_depth",
    "Dispatches (steps, super-steps, verify blocks) issued and not yet "
    "delivered (2 = overlapped: one executing while its predecessor's "
    "results are delivered host-side)")
_PIPELINE_FLUSHES = metrics.counter(
    "batch_pipeline_flushes_total",
    "Pipeline breaks by reason: an eagerly chained super-step was discarded "
    "before delivery (stop/cancel/error/finish — its rows diverged from the "
    "speculated schedule) or chaining was declined (admission/close, or "
    "'spec': the accept-aware policy preferred a host-drafted verify "
    "dispatch over extending the scan chain); 'row': ONE row's result of a "
    "step issued ahead was dropped, its request having left the slot by "
    "what the host alone could see (a stop_check hit, a cancel, a fault), "
    "the dispatch's other rows standing",
    labelnames=("reason",))
_STEP_CHAINED = metrics.counter(
    "batch_step_chained_total",
    "jit_step dispatches (prefill, mixed, single_step) issued before their "
    "predecessor's results were fetched, the rows' next tokens taken from "
    "its device-resident carry: of batch_dispatch_seconds' counts of those "
    "kinds, the ones the device did not wait for the host before")
# Batched speculative decoding (docs/SERVING.md "Speculative decoding"):
# per-engine spec telemetry next to the sequential path's spec_* family —
# drafted/accepted volumes and the verify-dispatch count are THE health
# signals for the batched draft-verify path (accept rate ~0 means the
# workload is paying wide dispatches for nothing).
_SPEC_VERIFY_STEPS = metrics.counter(
    "batch_spec_verify_steps_total",
    "Batched draft-verify super-step dispatches")
_SPEC_DRAFTED = metrics.counter(
    "batch_spec_drafted_tokens_total",
    "Draft tokens proposed to batched verify dispatches (per row)")
_SPEC_ACCEPTED = metrics.counter(
    "batch_spec_accepted_tokens_total",
    "Draft tokens batched verify dispatches accepted")
_SPEC_ACCEPT_RATE = metrics.gauge(
    "batch_spec_accept_rate",
    "Cumulative batched accepted/drafted ratio (process lifetime)")
# Durable-request resume (docs/FLEET.md "Resume protocol"): requests
# re-admitted mid-generation after a replica failure, and how much of their
# prompt ⊕ delivered-tokens prefix the admission re-prefill actually skipped
# (same-slot rewind + radix pool seed) — the resume-cost health signal.
_RESUMED = metrics.counter(
    "batch_resumed_requests_total",
    "Requests admitted with a resume prefix (mid-stream failover re-submits)")
_RESUME_TOKENS = metrics.counter(
    "batch_resume_prefix_tokens_total",
    "Delivered-elsewhere tokens carried by resume admissions (the suffix the "
    "new replica must re-prefill or reuse)")
# Multi-tenant serving (docs/SERVING.md "Multi-tenant serving"): per-tenant
# service accounting (labels stay bounded — unknown tenant ids collapse to
# the canonical "default" policy), fairness preemptions, SLO-driven sheds,
# quota throttles, and the measured drain rate every Retry-After hint is
# derived from (resilience/tenancy.py).
_TENANT_TOKENS = metrics.counter(
    "batch_tenant_tokens_total",
    "Decode tokens delivered, by canonical tenant", labelnames=("tenant",))
_TENANT_REQUESTS = metrics.counter(
    "batch_tenant_requests_total",
    "Completed requests by canonical tenant and class",
    labelnames=("tenant", "class"))
_PREEMPTED = metrics.counter(
    "batch_preempted_total",
    "Batch-class rows preempted at a super-step boundary so a waiting "
    "interactive request could take the slot (the preempted request is "
    "re-queued and later resumes byte-identical)")
_SLO_SHED = metrics.counter(
    "engine_slo_shed_total",
    "Admissions refused (or queued batch work evicted for an interactive "
    "arrival) because the projected queue wait exceeded the class's TTFT "
    "target or measured TPOT exceeded the interactive target, by class",
    labelnames=("class",))
_QUOTA_THROTTLED = metrics.counter(
    "engine_quota_throttled_total",
    "Admissions refused with 429: the tenant's token-bucket quota was "
    "exhausted", labelnames=("tenant",))
_DRAIN_RATE = metrics.gauge(
    "engine_drain_rate",
    "Measured request completions/sec (decayed EMA, resilience/tenancy.py "
    "DrainRate) — the denominator of drain-derived Retry-After hints")
# Hung-engine supervision (resilience/supervisor.py): the watchdog gauge
# escalated to action — recoveries attempted and the requests they failed.
_WEDGE_RECOVERIES = metrics.counter(
    "engine_wedge_recoveries_total",
    "Supervisor escalations: a wedged scheduler was abandoned and the engine "
    "re-initialized, by outcome", labelnames=("outcome",))
_WEDGE_FAILED = metrics.counter(
    "engine_wedge_failed_requests_total",
    "In-flight/queued requests failed with EngineWedged by a supervisor "
    "recovery (retriable: a durable router resumes them elsewhere)")
# Grammar-constrained decoding (constrain/, docs/SERVING.md "Constrained
# decoding"): rows with an attached TokenAutomaton, masked dispatches
# issued, and rows degraded to unconstrained output (mask fault or table
# capacity — a service condition, never a client-visible failure).
_CONSTRAIN_ROWS = metrics.gauge(
    "constrain_rows",
    "Batch rows currently decoding under an attached grammar automaton")
_CONSTRAIN_DISPATCHES = metrics.counter(
    "constrain_masked_dispatches_total",
    "Batched decode/verify dispatches issued through the masked program "
    "variants (>= 1 live constrained row in the batch)")
_CONSTRAIN_DEGRADED = metrics.counter(
    "constrain_degraded_total",
    "Constrained rows degraded to unconstrained decoding, by reason "
    "(capacity = constraint table full, mask = masking fault, "
    "divergence = delivered token left the grammar)",
    labelnames=("reason",))


def _upload(host, dtype=None, sharding=None):
    """A host value onto the device for a dispatch, counted: every copy the
    scheduler makes towards the device goes through here. `sharding`: the
    copy is committed to it. jit keeps an executable a placement of its
    arguments, so a program that is handed now a host value and now another
    program's result takes ONE executable only if the host value is placed
    as that result is."""
    if sharding is None:
        a = jnp.asarray(host, dtype)
    else:
        a = jax.device_put(np.asarray(host, dtype), sharding)
    _H2D_TRANSFERS.inc()
    _H2D_BYTES.inc(a.nbytes)
    return a


class _StaleEpoch(BaseException):
    """Raised inside an ABANDONED scheduler thread (recover_wedged bumped the
    engine epoch while this thread was stuck in a device call): the thread
    must unwind without touching engine state — the slots/queue it knew were
    replaced, so its _fail_all/_deliver paths would corrupt the NEW epoch's
    requests. BaseException so no blanket `except Exception` net keeps the
    zombie serving."""


@dataclass
class BatchRequest:
    prompt: list[int]
    max_tokens: int
    sampler: object
    on_token: Callable[[int], None] | None = None
    stop_check: Callable[[int], bool] | None = None
    # results
    out: list[int] = field(default_factory=list)
    finish: str = "length"
    error: Exception | None = None
    done: threading.Event = field(default_factory=threading.Event)
    stats: GenerationStats = field(default_factory=GenerationStats)

    cancelled: bool = False
    submit_t: float = 0.0  # perf_counter at submit(), feeds batch_queue_wait
    # multi-tenant identity (docs/SERVING.md "Multi-tenant serving"):
    # `tenant` is the serving-local tenant id (quota + fair-share key),
    # `klass` the scheduling class — "interactive" (strict queue priority,
    # may preempt batch rows at super-step boundaries) or "batch" (absorbs
    # slack, shed first under overload). `wfq_cost` is the virtual-service
    # cost the fair queue charges (≈ total token positions the request
    # consumes); `preemptions` counts slot losses to interactive arrivals.
    tenant: str = DEFAULT_TENANT
    klass: str = "interactive"
    wfq_cost: float = 1.0
    preemptions: int = 0
    # durable resume (docs/FLEET.md): the last `resume_tokens` entries of
    # `prompt` are generated-and-delivered-elsewhere tokens, not user prompt —
    # admission counts them separately and the sampler arrives fast-forwarded
    resume_tokens: int = 0
    # disaggregation export (docs/DISAGG.md): when set, _finish snapshots
    # the slot's committed prompt-prefix KV blocks to HOST arrays (on the
    # scheduler thread — the only thread allowed to read device caches)
    # into kv_export = (tokens, [(k, v) per block], block_tokens) before
    # done.set(), so the waiting /v1/kv handler can serve them
    export_kv: bool = False
    kv_export: tuple | None = None
    # request identity (docs/OBSERVABILITY.md "Request tracing"): `rid` keys
    # the flight-recorder timeline; `ctx` is the W3C trace context captured
    # at submit() — the scheduler thread re-enters it (reqctx.use) around
    # per-request work so engine-side spans/events carry this request's
    # trace id even though one super-step serves many requests
    rid: str = ""
    ctx: object = None  # obs.reqctx.TraceContext | None
    # absolute perf_counter deadline for the WHOLE request (queue + decode);
    # 0 = none. The scheduler enforces it once per loop pass (finish reason
    # "deadline"), so granularity is one dispatch (~K token-times).
    deadline_t: float = 0.0
    # absolute perf_counter bound on QUEUE time only (expired before a slot
    # was assigned -> finish "deadline" without ever prefilling); 0 = none
    queue_ttl_t: float = 0.0
    # grammar-constrained decoding (constrain/, docs/SERVING.md "Constrained
    # decoding"): a compiled TokenAutomaton the OUTPUT must satisfy, plus
    # the grammar hash the api edge logged. The engine allocates a region
    # in its device constraint table at admission and masks sampling (host
    # and device) to the automaton's allowed set; compile happens at the
    # edge so the engine never needs tokenizer bytes.
    constraint: object = None  # constrain.TokenAutomaton | None
    constraint_hash: str = ""

    def cancel(self) -> None:
        """Ask the scheduler to stop decoding this request (client went away)."""
        self.cancelled = True

    def wait(self, timeout=None) -> list[int]:
        if not self.done.wait(timeout):
            # auto-cancel: a timed-out waiter previously walked away while
            # the request kept decoding to max_tokens with its slot (and any
            # prefix-cache lease) pinned — the scheduler reaps a cancelled
            # request on its next pass through the existing _finish path
            self.cancel()
            raise TimeoutError(
                f"generation not finished within {timeout}s (auto-cancelled)")
        if self.error is not None:
            raise self.error
        return self.out


class _SlotConstraint:
    """Per-slot grammar state (scheduler-thread-only, constrain/).

    `state` is the LOCAL automaton state, the host mirror of the device
    carry — advanced in _emit per DELIVERED token, so after any full
    delivery host and device agree exactly (integer bookkeeping, no
    resync needed; a flushed/partial dispatch re-uploads from here, same
    discipline as the sampler rng). `offset` rebases local states into
    the engine's stacked ConstraintTable; `degraded` parks the row on the
    universal state 0 (unconstrained) after a mask fault or capacity
    miss — visible in metrics and the flight timeline, never to the
    client."""

    __slots__ = ("automaton", "state", "offset", "ghash", "degraded")

    def __init__(self, automaton, offset: int, ghash: str = ""):
        self.automaton = automaton
        self.state = 0
        self.offset = offset
        self.ghash = ghash
        self.degraded = False

    @property
    def gstate(self) -> int:
        """GLOBAL table state uploaded to device (0 = universal row)."""
        return 0 if self.degraded else self.offset + self.state


class _Slot:
    def __init__(self, index: int):
        self.index = index
        self.pos = 0  # next cache position for this row
        self.history: list[int] = []  # tokens whose KV is written (prefix reuse)
        self.req: BatchRequest | None = None
        self.pending: list[int] = []  # prompt tokens not yet prefilled
        self.last_token = 0  # feeds the next decode step
        self.last_logits: np.ndarray | None = None
        # token already sampled (on device, tail of a super-step block) but not
        # yet ingested — consumed by _advance_row instead of a host sample
        self.next_token: int | None = None
        # prefix-cache lease pinning the blocks this slot was seeded from
        # (released at _finish; shrunk when history is truncated)
        self.lease = None
        # device-pool block table (slot_cache.PoolSlotCache): the pool block
        # ids backing positions [0, len(blocks)*bt), one pool ref an entry
        self.blocks: list[int] = []
        self.admit_t = 0.0  # monotonic admission time (dispatch watchdog)
        # last_token is sampled/delivered but its KV not yet written: a
        # dispatch that fails AFTER _advance_row consumed next_token must not
        # re-advance (and spuriously finish) the row on retry — _advance_row
        # is a no-op while armed; the successful ingesting dispatch clears it
        self.armed = False
        # positions a step that is issued and not yet delivered advances
        # this row by (its chunk, or 1): pos + ahead is where the row stands
        # on the device, what the dispatch after it is planned from
        self.ahead = 0
        # set BEFORE a super-step's delivery loop when the scan will park
        # this row clamped at seq_len-1 (destroying that history row): a
        # mid-loop _finish must harvest the TRUNCATED history, not the
        # poisoned row (consumed by _harvest_into_cache / the post-loop clamp)
        self.clamp_pos: int | None = None
        # speculative drafting state lives in the engine's Proposer
        # (runtime/speculative.py): attached at admission, fed per delivered
        # token, detached at finish/preempt — keyed by this slot's index
        # per-tenant token counter child, resolved ONCE at admission so the
        # per-token hot path (_emit) pays a bound-method call, not a label
        # dict lookup
        self.tok_counter = None
        # grammar constraint handle (constrain/): attached at admission
        # when the request carries an automaton, advanced in _emit,
        # released (table region freed) at finish/preempt/wedge
        self.constraint: _SlotConstraint | None = None


class _InflightStep:
    """An issued-but-undelivered K-step super-step, draft-verify dispatch or
    `jit_step` dispatch.

    Holds the DEVICE arrays the dispatch will produce (`toks` the (K, B)
    token block, plus the (last_tok, pos, rng) carry the next dispatch can
    chain from) and the host-side schedule it was issued against: full
    B-length `starts`/`budget`/`temps` lists plus the (slot, request) pairs
    of its live rows. A chained dispatch's schedule is SPECULATIVE — derived
    assuming its predecessor delivers every budgeted token — and is validated
    against the predecessor's actual delivery before this dispatch is kept.

    kind "verify" (docs/SERVING.md "Speculative decoding"): `toks` is the
    (T, B) per-position target block, `ndraft` the per-row real draft counts
    (-1 = parked), `acc` the device (B,) accepted lengths, and `budget` the
    per-row MAXIMUM emit (ndraft+1) — delivery reads the actual emit, acc+1,
    from the device. The carry is rewound to each row's verified frontier on
    device, so a chained scan consumes it soundly for any accept outcome.

    kind "step" (docs/SERVING.md "Pipelined decode"): one (B, T) `jit_step`
    dispatch, a prefill chunk with its riders or a single step. `k` is T,
    `budget` what each row advances (T the prefilling row `lead`, 1 a
    rider), `piece` the chunk's tokens, `toks` the logits (fetched only
    where a row is sampled on the host) and `tok` each row's arg-max, which
    the step after it takes as its flagged rows' input. Nothing of its
    schedule is speculative but that its rows stay: a row that left its
    slot meanwhile has its one result dropped."""

    __slots__ = ("rows", "k", "starts", "budget", "temps", "toks", "tok",
                 "pos", "rng", "t_issue", "chained", "window", "kind",
                 "ndraft", "acc", "cstate", "moe", "lead", "piece", "span",
                 "mapped", "snaps")

    def __init__(self, rows, k, starts, budget, temps, toks, tok, pos, rng,
                 t_issue, chained, window, kind="scan", ndraft=None,
                 acc=None, cstate=None, moe=None, lead=None, piece=(),
                 span=None, mapped=False):
        self.rows = rows  # list[(slot, request)] for budget > 0 rows
        self.k = k
        self.starts = starts  # expected per-row device start positions
        self.budget = budget
        self.temps = temps
        self.toks = toks  # device (K, B) token block
        self.tok = tok  # device (B,) block-tail token (next dispatch's input)
        self.pos = pos  # device (B,) positions after the budgeted ingestions
        self.rng = rng  # device (B, 2) advanced xorshift* state
        self.t_issue = t_issue
        self.chained = chained
        self.window = window  # keys attention ran against (bucket or context)
        self.kind = kind  # "scan" | "verify" | "step"
        self.ndraft = ndraft  # verify: per-row draft counts (-1 = parked)
        self.acc = acc  # verify: device (B,) accepted draft lengths
        # masked dispatch only: device (B,) GLOBAL constraint states after
        # the budgeted emissions — a chained masked scan consumes it
        self.cstate = cstate
        # a routed model's scan only: device int32 vector, what the expert
        # layers did over the K steps (_count_moe reads it at delivery)
        self.moe = moe
        # a step only: the slot that prefills (None: a single step), its
        # chunk's tokens, the dispatch span's (name, args), and whether the
        # program was told of it (a compact stream: `forward.RowMap`)
        self.lead = lead
        self.piece = piece
        self.span = span
        self.mapped = mapped
        # a state-space model only: the snapshot entries this dispatch was
        # given, (slot, request, block, allotment, stride's last position)
        self.snaps: list = []

    @classmethod
    def step(cls, rows, t, starts, t0, chained, window, span, lead=None,
             piece=(), mapped=False):
        """A planned `jit_step` dispatch, nothing launched yet: `rows` its
        (slot, request) pairs, the prefilling one first."""
        budget = [0] * len(starts)
        for slot, _req in rows:
            budget[slot.index] = t if slot is lead else 1
        return cls(rows, t, starts, budget, None, None, None, None, None, t0,
                   chained, window, kind="step", lead=lead, piece=piece,
                   span=span, mapped=mapped)


class BatchEngine:
    """Engine-compatible construction (same spec/params arguments), `slots` sequences.

    Use submit() for async operation or generate() for the Engine-compatible blocking
    call. The scheduler thread starts lazily on first submit and can be stopped with
    close().
    """

    def __init__(self, spec: ModelSpec, params, tokenizer=None, *, slots: int = 2,
                 superstep: int = 8, pipeline: bool = True, prefix_cache=True,
                 prefix_cache_blocks: int = 0, prefix_block_tokens: int = 16,
                 prefix_cache_q80: bool = False, max_queue: int = 0,
                 queue_ttl: float = 0.0, max_retries: int = 3,
                 retry_backoff: float = 0.05, speculative: int = 0,
                 spec_min_draft: int = 1, spec_chain_expect: float = 2.0,
                 spec_adaptive: bool = True,
                 draft_model=None, draft_k: int = 0,
                 constrain_states: int = 512,
                 tenants: TenantRegistry | None = None,
                 slo_ttft_interactive: float = 0.0,
                 slo_ttft_batch: float = 0.0,
                 slo_tpot_interactive: float = 0.0,
                 paged_kv: bool = True, kv_block_tokens: int = 16,
                 kv_pool_blocks: int = 0,
                 **engine_kw):
        from .engine import Engine

        assert slots >= 1
        assert superstep >= 1
        assert engine_kw.get("sp", 1) in (None, 1), (
            "continuous batching needs per-row cache positions, which the "
            "sequence-sharded (ring) cache does not support")
        self.slots_n = slots
        # Device-resident paged KV (docs/PAGED_KV.md, default ON; the
        # --no-paged-kv escape hatch reverts to the dense per-slot caches):
        # KV lives in a (L, N, hk, bt, hs) device block pool, each slot
        # carries a block table, and cross-request prefix reuse is a
        # refcounted block-table REMAP — zero host→device KV bytes on a
        # radix hit. A shared dense PrefixCache instance forces the dense
        # layout (the caller asked for host-pool sharing semantics); the
        # Engine gate below additionally drops it under sp/dp sharding or
        # host/disc KV spill.
        kv_pool_cfg = None
        from ..cache import PrefixCache as _DensePC

        if paged_kv and not isinstance(prefix_cache, _DensePC):
            bt = max(int(kv_block_tokens), 1)
            while bt > 1 and spec.seq_len % bt:
                bt //= 2  # the parity gather wants bt | seq_len
            w = spec.seq_len // bt
            n_blocks = int(kv_pool_blocks) or (slots * w + slots + 1)
            # floor: one full context + the scratch block + one spare, or
            # no request could ever run to seq_len
            kv_pool_cfg = (max(n_blocks, w + 2), bt)
        # a routed model's step programs and scans also return what their
        # expert layers did (the batch_moe_* counters)
        if spec.mixed:
            # what cannot carry a state that is not a list of positions says
            # so here, in one sentence, before anything is built
            why = (
                "the dense per-slot caches (paged_kv off, or a dense "
                "PrefixCache instance) keep no snapshot a rewind could land "
                "on" if kv_pool_cfg is None else
                "speculative verify (speculative > 0, draft_model) rejects "
                "a suffix it has already written snapshots for"
                if speculative or draft_model is not None else
                "the Q80 cold tier (prefix_cache_q80) holds per-head keys "
                "and values" if prefix_cache_q80 else
                f"superstep {superstep}: a flushed chained scan rolls back "
                f"{2 * superstep} positions, and a state layer's ring keeps "
                f"{STATE_RING - spec.state_rows}"
                if 2 * superstep + spec.state_rows > STATE_RING else None)
            if why:
                raise ValueError(
                    "a model with state layers (a gated short convolution, "
                    "a state-space mixer, a delta-rule mixer) is not "
                    f"supported by {why}")
        if spec.latent and prefix_cache and (
                prefix_cache_q80 or kv_pool_cfg is None):
            raise ValueError(
                "a latent cache row (kv_lora_rank > 0) is not supported by "
                + ("the Q80 cold tier (prefix_cache_q80)" if prefix_cache_q80
                   else "the dense host prefix cache (paged_kv off)")
                + ": both hold per-head keys and values")
        self._eng = Engine(spec, params, tokenizer, batch=slots,
                           kv_pool=kv_pool_cfg, moe_stats=spec.is_moe,
                           **engine_kw)
        if spec.mixed and self._eng.kv_pool is None:
            raise ValueError(
                "a model with state layers (a gated short convolution, a "
                "state-space mixer, a delta-rule mixer) is served from the "
                "device block pool, "
                "which this engine's sharding or KV storage turned off")
        _KV_ROW_BYTES.set(spec.cache_row_bytes(
            self._eng.k_cache.dtype.itemsize))
        # attention's per-layer lower key bound, as (window, share of layers)
        wins = spec.layer_window()
        self._layer_windows = [(w, wins.count(w) / len(wins))
                               for w in sorted(set(wins))]
        # the layers with a window, as (window, how many layers have it)
        self._window_layers = [(w, wins.count(w))
                               for w in sorted(set(wins)) if w]
        heads = ([spec.kinds[k].n_heads for k in spec.layer_kinds]
                 or [spec.n_heads] * spec.n_layers)
        for h, w in zip(heads, wins):
            _ATTN_HEADS.labels(kind="window" if w else "full").set(h)
        self.spec = spec
        self.tokenizer = tokenizer
        self.superstep = superstep  # K: decode steps fused per device dispatch
        # pipelined super-steps (docs/SERVING.md "Pipelined decode"): chain
        # dispatch N+1 from N's device-resident carry while N's block is
        # delivered host-side. K=1 has no block to overlap; keep it off there.
        self.pipeline = pipeline and superstep >= 2
        self._inflight: _InflightStep | None = None
        # where a program's returned carry lies (a step's `tok`, a scan's
        # tokens, positions and sampler states): one entry a row, replicated
        # over tp. The token carry of a step that continues from none is
        # zeros placed there, so that a step takes one executable whether
        # its carry is this or the step's before it (parallel/tp.py)
        self._carry_sharding = NamedSharding(
            self._eng.mesh,
            PartitionSpec("dp") if self._eng.dp > 1 else PartitionSpec())
        self._no_carry = jax.device_put(np.zeros((slots,), np.int32),
                                        self._carry_sharding)
        self._gc_watched = False  # holds obs.process's collector watcher
        self._last_ready_t: float | None = None  # perf_counter of last results
        self._gap_t: float | None = None  # last dispatch-ready time, gap metric
        self._slots = [_Slot(i) for i in range(slots)]
        # where a slot's cache lives: the device block pool with its radix
        # directory, or the dense per-slot rows with the host prefix cache
        self.slot_cache = make_slot_cache(
            self._eng, spec, self._slots, _upload,
            prefix_cache=prefix_cache, blocks=prefix_cache_blocks,
            block_tokens=prefix_block_tokens, q80=prefix_cache_q80)
        self.kv_pool = self.slot_cache.kv_pool  # None: the dense layout
        self.prefix_cache = self.slot_cache.prefix_cache
        self._queue: "queue.Queue[BatchRequest]" = queue.Queue()
        # Multi-tenant policy (docs/SERVING.md "Multi-tenant serving"):
        # `tenants` configures per-tenant quotas + fair-share weights (None
        # = single default tenant: quotas off, weights uniform — the
        # pre-tenancy behavior); the slo_* targets drive SLO-aware shedding
        # at submit (0 = off); `_drain` measures completions/sec so every
        # Retry-After hint tracks real load instead of a constant; the
        # wait queue itself is a two-class weighted-fair queue, not a FIFO.
        self.tenants = tenants
        self.slo_ttft = {"interactive": max(slo_ttft_interactive, 0.0),
                         "batch": max(slo_ttft_batch, 0.0)}
        self.slo_tpot_interactive = max(slo_tpot_interactive, 0.0)
        self._drain = DrainRate()
        self._tpot_ema_ms = 0.0  # measured per-token ms (scheduler-written)
        # overflow requests with no free slot; guarded by _plock (close() may run while
        # the scheduler thread is still finishing a long device step)
        self._pending: WeightedFairQueue = WeightedFairQueue(tenants)
        self._plock = threading.Lock()  # guards: _pending
        # Batched speculative decoding (docs/SERVING.md "Speculative
        # decoding"): spec_k > 0 drafts up to k tokens per row from the
        # slot's NgramIndex and verifies every row's block in ONE (B, 1+k)
        # dispatch — the weights stream once for up to k+1 tokens per row.
        # spec_min_draft gates a verify dispatch on total drafted tokens
        # (below it the K-step scan serves better); spec_chain_expect is the
        # accept-aware chaining threshold: while the engine's accept EMA is
        # at/above it, back-to-back verifies beat diluting them with chained
        # scans, so chaining is declined (reason "spec").
        self.spec_k = max(int(speculative), 0)
        if self.spec_k:
            # a verify block must fit the context with room to decode
            self.spec_k = min(self.spec_k, spec.seq_len - 2)
        self.spec_min_draft = max(int(spec_min_draft), 1)
        self.spec_chain_expect = float(spec_chain_expect)
        # optimistic start: speculation engages immediately and the EMA
        # adapts down on non-repetitive workloads (updated per verify)
        self._spec_ema = float(self.spec_k)
        # Model-based drafting (docs/SERVING.md "Model-based drafting"):
        # draft_model (path, or a (spec, params) pair for tests) loads a
        # second small sharded model CO-RESIDENT on this engine's mesh that
        # drafts up to draft_k (default spec_k) tokens per row in one scan
        # dispatch; n-gram lookup remains the per-row fallback (and the
        # whole proposer when no drafter is configured, or its load fails —
        # a drafter is an accelerator, never a correctness gate). The
        # ADAPTIVE PER-ROW k controller (spec_adaptive, default on) drives
        # each row's draft length from its own accept EMA, bucketed to the
        # verify T buckets so adaptation cannot mint new compiled programs.
        self.adaptive = (AdaptiveK(self.spec_k)
                         if self.spec_k and spec_adaptive else None)
        self.drafter = None
        if draft_model is not None and self.spec_k and self._eng.dp > 1:
            # the drafter's programs are tp-only (draft/loop.py) — gate at
            # construction like the paged-KV dp/sp gate, instead of letting
            # every proposal turn raise its way to the permanent disable
            import sys

            print("💡 --draft-model disabled: the drafter is tp-only and "
                  "this engine shards rows over dp — using n-gram drafting",
                  file=sys.stderr)
            draft_model = None
        if draft_model is not None and self.spec_k:
            try:
                from ..draft.drafter import ModelDrafter

                dk = min(int(draft_k) or self.spec_k, self.spec_k)
                if isinstance(draft_model, (tuple, list)):
                    dspec, dparams = draft_model
                    self.drafter = ModelDrafter(
                        dspec, dparams, mesh=self._eng.mesh, slots=slots,
                        target_spec=spec, tokenizer=tokenizer,
                        dtype=self._eng.dtype,
                        use_pallas=self._eng.use_pallas,
                        compress_collectives=self._eng.compress,
                        moe_sharding=self._eng.moe_sharding, k_cap=dk)
                else:
                    self.drafter = ModelDrafter.load(
                        str(draft_model), mesh=self._eng.mesh, slots=slots,
                        target_spec=spec, tokenizer=tokenizer,
                        dtype=self._eng.dtype,
                        use_pallas=self._eng.use_pallas,
                        compress_collectives=self._eng.compress,
                        moe_sharding=self._eng.moe_sharding, k_cap=dk)
            except Exception as e:
                import sys

                print(f"⚠️  draft model unavailable ({e!r}) — degrading to "
                      "n-gram drafting", file=sys.stderr, flush=True)
        # Grammar-constrained decoding (constrain/, docs/SERVING.md
        # "Constrained decoding"): the stacked device constraint table is
        # created lazily at the first constrained admission (unconstrained
        # engines never pay the (cap, V) host arrays), and the
        # GrammarProposer rides the mux so constrained rows draft their
        # forced-transition chains while co-batched chat rows keep
        # model/ngram drafts.
        from ..constrain import GrammarProposer

        self.constrain_states = max(int(constrain_states), 2)
        self.constrain_table = None  # ConstraintTable, lazy
        self.constrain_degraded = 0
        self.grammar_proposer = GrammarProposer()
        self.proposer = ProposerMux(NgramProposer(), self.drafter,
                                    grammar=self.grammar_proposer)
        self.prefilled_tokens = 0  # observability: total tokens run through prefill
        self.decode_steps = 0  # observability: batched device decode dispatches
        self.super_steps = 0  # observability: K-step fused dispatches (subset)
        self.verify_steps = 0  # observability: draft-verify dispatches (subset)
        self.mixed_steps = 0  # observability: prefill dispatches carrying decode rows
        self._loops: dict[tuple, object] = {}  # (k, mode, window) -> batched loop
        # scheduler wakeup: a Condition, not a sleep-poll — submit() notifies,
        # so enqueue latency is bounded by lock handoff, not a poll interval
        self._cond = threading.Condition()
        self._shutdown = False
        self._draining = False  # drain mode: serve in-flight, refuse new
        self._thread: threading.Thread | None = None
        self._lock = threading.Lock()  # guards: _thread, _gc_watched
        # scheduler epoch (resilience/supervisor.py): recover_wedged() bumps
        # it to abandon a scheduler thread stuck in a hung device call — the
        # stale thread observes the bump at its next epoch check and unwinds
        # via _StaleEpoch instead of mutating the replacement state. Each
        # scheduler thread records ITS epoch thread-locally so the checks
        # compare against the epoch the thread was born into, not a value
        # re-read after the bump (which would blind the check to a bump
        # landing between loop entry and the dispatch)
        self._epoch = 0
        self._tls = threading.local()
        self.wedge_recoveries = 0  # observability: supervisor escalations
        # Admission control (docs/ROBUSTNESS.md): max_queue bounds the number
        # of requests WAITING for a slot (0 = unbounded, the pre-PR-4
        # behavior); queue_ttl bounds how long a request may wait queued;
        # both are plain attributes so a server can tune them live.
        self.max_queue = max_queue
        self.queue_ttl = queue_ttl
        # transient-dispatch retry policy: capped exponential backoff
        # starting at retry_backoff seconds, max_retries attempts beyond the
        # first before the error escalates to engine scope
        self.max_retries = max_retries
        self.retry_backoff = retry_backoff
        self._last_dispatch_t: float | None = None  # monotonic, watchdog
        _DISPATCH_AGE.set_function(self._dispatch_age)
        _SLOTS_TOTAL.set(slots)

    @classmethod
    def load(cls, model_path: str, tokenizer_path: str | None = None, *,
             max_seq_len: int = 0, weights_ftype=None, slots: int = 2,
             superstep: int = 8, **kw) -> "BatchEngine":
        """Engine.load-compatible constructor (same flag surface, same vocab check)."""
        from ..formats.mfile import load_model
        from ..tokenizer.bpe import Tokenizer

        spec, params = load_model(model_path, max_seq_len, weights_ftype)
        tokenizer = Tokenizer.load(tokenizer_path) if tokenizer_path else None
        if tokenizer is not None and tokenizer.vocab_size != spec.vocab_size:
            raise ValueError(
                f"tokenizer vocab {tokenizer.vocab_size} != model vocab {spec.vocab_size}")
        return cls(spec, params, tokenizer, slots=slots, superstep=superstep, **kw)

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------

    def submit(self, prompt: list[int], max_tokens: int, sampler,
               on_token=None, stop_check=None, *, deadline: float | None = None,
               ttl: float | None = None, rid: str | None = None,
               ctx=None, resume_tokens: int = 0, tenant: str = "",
               klass: str = "interactive",
               export_kv: bool = False, constraint=None,
               constraint_hash: str = "") -> BatchRequest:
        """Enqueue a request. `deadline` (seconds) bounds the WHOLE request
        (queue + generation; finish reason "deadline", partial output kept);
        `ttl` bounds queue wait only (overrides the engine's queue_ttl).
        `rid`/`ctx` set the request id and trace context; both default from
        the caller's bound reqctx (api_server's handler thread) or are
        originated here, so every request is traceable even when submitted
        outside the HTTP layer. `resume_tokens` marks the last N entries of
        `prompt` as mid-stream-failover resume tokens (generated and
        delivered by a failed replica; docs/FLEET.md "Resume protocol") —
        the caller must pass the sampler already fast-forwarded past their
        coins; admission then re-prefills prompt ⊕ resume (mostly a radix
        prefix-cache hit) and generation continues byte-identical to the
        uninterrupted run.

        `tenant`/`klass` are the multi-tenant scheduling identity
        (docs/SERVING.md "Multi-tenant serving"): `tenant` defaults from
        the bound trace context (the api layer's X-Tenant mapping) and
        keys quota + fair-share accounting; `klass` is "interactive"
        (strict priority, may preempt batch rows) or "batch" (absorbs
        slack, shed first). Raises EngineDraining/EngineClosed during
        shutdown, QuotaExceeded (429) when the tenant's token bucket is
        exhausted, and EngineSaturated (503) when the wait queue is at
        max_queue or SLO-aware shedding refuses the class — both with
        Retry-After derived from the measured queue drain rate."""
        if self._draining and not self._shutdown:
            raise EngineDraining(
                "BatchEngine is draining (serving in-flight requests only)")
        if self._shutdown:
            raise EngineClosed("BatchEngine is closed")
        faults.fire("batch.submit")
        if klass not in CLASSES:
            raise InvalidRequest(
                f"unknown scheduling class {klass!r} (want one of {CLASSES})")
        if export_kv and self.slot_cache.no_stream:
            raise InvalidRequest(self.slot_cache.no_stream)
        c = ctx if ctx is not None else reqctx.current()
        tenant = tenant or (c.tenant if c is not None else "") \
            or DEFAULT_TENANT
        cost = float(len(prompt) + max(max_tokens, 1))
        if self.tenants is not None:
            # quota first: a throttled tenant must get its honest 429 even
            # when the queue is empty (quota is policy, not load)
            try:
                self.tenants.acquire(tenant, cost)
            except Exception as e:
                _QUOTA_THROTTLED.labels(
                    tenant=self.tenants.canonical(tenant)).inc()
                raise e
        try:
            self._admission_control(tenant, klass, cost)
        except Exception:
            # a shed request received zero service: refund the quota debit
            # or overload bursts would double-punish within-quota tenants
            # (drained bucket + 503) and 429 them after capacity recovers
            if self.tenants is not None:
                self.tenants.refund(tenant, cost)
            raise
        req = BatchRequest(list(prompt), max_tokens, sampler, on_token, stop_check)
        req.tenant = tenant
        req.klass = klass
        req.wfq_cost = cost
        req.export_kv = export_kv
        if constraint is not None:
            # structural rejects belong at submit (the api edge maps them
            # to 400): an automaton that can NEVER fit the table is a
            # client error, not the runtime capacity condition alloc
            # degrades on
            if getattr(constraint, "n_states", 0) > self.constrain_states - 1:
                if self.tenants is not None:
                    self.tenants.refund(tenant, cost)
                raise InvalidRequest(
                    f"grammar too large: {constraint.n_states} automaton "
                    f"states exceed the engine's constraint table "
                    f"({self.constrain_states - 1} usable states)")
            req.constraint = constraint
            req.constraint_hash = constraint_hash
        if not req.prompt:
            req.prompt = [self.tokenizer.bos_id if self.tokenizer else 1]
        req.resume_tokens = min(max(int(resume_tokens), 0), len(req.prompt))
        if req.resume_tokens:
            _RESUMED.inc()
            _RESUME_TOKENS.inc(req.resume_tokens)
        # request identity: adopt the caller's trace context (the HTTP
        # handler thread's contextvar) or originate one, and make the
        # context carry the request id so the faults.fire → flight hook can
        # attribute injections fired inside this request's scheduler scope
        rid = rid or (c.request_id if c is not None and c.request_id else "")
        if not rid:
            rid = f"req-{uuid.uuid4().hex[:16]}"
        req.rid = rid
        if c is None:
            req.ctx = reqctx.new_context(rid, tenant)
        elif c.request_id != rid or c.tenant != tenant:
            req.ctx = dataclasses.replace(c, request_id=rid, tenant=tenant)
        else:
            req.ctx = c
        flight.start(rid, req.ctx.trace_id, prompt_tokens=len(req.prompt),
                     max_tokens=max_tokens,
                     **{"tenant": tenant, "class": klass})
        req.submit_t = time.perf_counter()
        if deadline is not None and deadline > 0:
            req.deadline_t = req.submit_t + deadline
        eff_ttl = self.queue_ttl if ttl is None else ttl
        if eff_ttl and eff_ttl > 0:
            req.queue_ttl_t = req.submit_t + eff_ttl
        # put BEFORE ensure: racing a recover_wedged(), a request already in
        # the queue is drained and failed retriable by the recovery, and a
        # put landing after it finds _thread=None so ensure spawns the fresh
        # scheduler — ensure-first could observe the doomed thread as alive
        # and then enqueue into a queue nothing serves
        self._queue.put(req)
        self._ensure_thread()
        with self._cond:
            self._cond.notify()
        return req

    def _admission_control(self, tenant: str, klass: str,
                           cost: float) -> None:
        """Load shedding at submit (docs/SERVING.md "Multi-tenant serving").
        Two displacement rules make the shed order policy-true instead of
        arrival-order-true:

        - class: an INTERACTIVE arrival that would be refused first evicts
          the least-entitled queued batch request (batch sheds before
          interactive);
        - weight: a BATCH arrival hitting the full queue displaces the
          least-entitled queued batch item when its own virtual finish tag
          is SMALLER (more entitled) — so under uniform flooding the queue
          holds weight-proportional work and delivered throughput tracks
          the configured weights rather than arrival luck.

        Every refusal carries Retry-After derived from the measured queue
        drain rate (EMA completions/sec vs depth, resilience/tenancy.py),
        never a hardcoded constant."""
        with self._plock:
            queued = len(self._pending) + self._queue.qsize()
        reason = None
        if self.max_queue and queued >= self.max_queue:
            reason = "queue"
        tgt = self.slo_ttft.get(klass, 0.0)
        if reason is None and tgt and queued > 0:
            # projected wait for the LAST place in line, applied only when
            # a backlog actually exists: an idle engine serves within ~one
            # dispatch whatever the historical drain rate says — without
            # the queued>0 gate, a long-idle engine's decayed EMA (tiny but
            # nonzero) projected an absurd wait and shed at queue depth 0.
            # Cold start (no completion observed yet) projects 0 likewise.
            if self._drain.queue_wait(queued + 1) > tgt:
                reason = "slo_ttft"
        if (reason is None and klass == "batch" and self.slo_tpot_interactive
                and self._tpot_ema_ms > self.slo_tpot_interactive * 1e3):
            # decode is already past the interactive TPOT target: one more
            # batch row widens every shared dispatch further — refuse batch
            reason = "slo_tpot"
        if reason is None:
            return
        if klass == "interactive":
            with self._plock:
                # drain first: evictable batch work may still sit in the
                # cross-thread queue while the scheduler is mid-dispatch —
                # an interactive arrival must never be refused while ANY
                # queued batch request exists
                self._drain_submit_queue()
                victim = self._pending.evict_last("batch")
            if victim is not None:
                # shed batch before interactive: the evicted batch request
                # gets the honest 503 this arrival would otherwise have
                self._shed_queued(victim, reason, queued)
                _SLO_SHED.labels(**{"class": "batch"}).inc()
                return
        elif reason == "queue":
            with self._plock:
                self._drain_submit_queue()  # same visibility rule as above
                worst = self._pending.last_tag("batch")
                victim = None
                if (worst is not None and
                        self._pending.entry_tag(tenant, "batch",
                                                cost) < worst):
                    victim = self._pending.evict_last("batch")
            if victim is not None:
                # weighted shed: this batch arrival is MORE entitled than
                # the queue's worst resident — displace it
                self._shed_queued(victim, reason, queued)
                return
        _SHED.inc()
        if reason != "queue":
            _SLO_SHED.labels(**{"class": klass}).inc()
        raise EngineSaturated(
            f"admission refused ({reason}): class={klass}, queue depth "
            f"{queued}" + (f" at max_queue={self.max_queue}"
                           if reason == "queue" else ""),
            retry_after=self._drain.retry_after(queued + 1))

    def _shed_queued(self, req: BatchRequest, reason: str,
                     queued: int) -> None:
        """Fail a queued request displaced by a higher-priority admission
        (the shed-batch-first path) with the same typed error + honest
        Retry-After an admission-time shed would have surfaced."""
        _SHED.inc()
        req.error = EngineSaturated(
            f"shed from the wait queue ({reason}): an interactive admission "
            "displaced this batch request",
            retry_after=self._drain.retry_after(queued))
        req.finish = "error"
        _REQUESTS.labels(finish="error").inc()
        flight.finish(req.rid, "error", error=repr(req.error))
        req.done.set()

    def generate(self, prompt: list[int], max_tokens: int, sampler,
                 on_token=None, stop_check=None) -> tuple[list[int], GenerationStats]:
        """Blocking Engine.generate-compatible call (rides the batched scheduler)."""
        req = self.submit(prompt, max_tokens, sampler, on_token, stop_check)
        out = req.wait()
        return out, req.stats

    @property
    def draining(self) -> bool:
        return self._draining and not self._shutdown

    # admission seeding readouts (/v1/stats): host→device KV bytes, wall ms
    seed_bytes = property(lambda self: self.slot_cache.seed_bytes)
    seed_ms = property(lambda self: self.slot_cache.seed_ms)

    def import_kv_blocks(self, tokens: list[int], blocks: list) -> int:
        """Adopt externally-shipped HOST KV blocks into the prefix cache
        (docs/DISAGG.md; `slot_cache.import_blocks`), from any thread.
        Returns the token span the cache now covers."""
        return self.slot_cache.import_blocks(tokens, blocks)

    def _read_block(self, bid: int):
        """benchmark/run.py's warm-up calls it by this name (read_block)."""
        return self.slot_cache.read_block(bid)

    def scheduler_alive(self) -> bool:
        """True while the scheduler thread can serve (running, or not yet
        lazily started). False only after the thread died — the /healthz
        liveness signal."""
        # single atomic reference read on a health-probe path: taking _lock
        # here would make /healthz contend with _ensure_thread/recover_wedged
        t = self._thread  # dlint: ignore[lock-guard] -- atomic ref snapshot; staleness only skews one health probe
        return t is None or t.is_alive()

    def load_stats(self) -> dict:
        """Slot/queue load reading for the /healthz replica block a fleet
        router's least-loaded routing consumes (fleet/membership.py):
        `free_slots` = slots with no request bound, `queue_depth` = requests
        waiting for one (admitted-pending + submit queue)."""
        with self._plock:
            occupied = sum(1 for s in self._slots if s.req is not None)
            queued = len(self._pending) + self._queue.qsize()
        return {"slots": self.slots_n,
                "free_slots": self.slots_n - occupied,
                "queue_depth": queued}

    def spec_stats(self) -> dict | None:
        """Speculative-decoding block for /v1/stats (docs/SERVING.md
        "Model-based drafting"): engine-level accept counters plus the
        proposer (which drafter is live, degradation state) and the
        adaptive controller's per-row k breakdown. None when speculation is
        off. Reads are lock-protected where the scheduler adapts
        (AdaptiveK) and plain-counter snapshots elsewhere."""
        if not self.spec_k:
            return None
        snap = metrics.snapshot()
        drafted = snap.get("batch_spec_drafted_tokens_total", 0)
        out = {
            "k": self.spec_k,
            "verify_steps": self.verify_steps,
            "drafted_tokens": drafted,
            "accepted_tokens": snap.get("batch_spec_accepted_tokens_total",
                                        0),
            "accept_rate": (snap.get("batch_spec_accepted_tokens_total", 0)
                            / drafted if drafted else None),
            "proposer": self.proposer.describe(),
        }
        if self.adaptive is not None:
            out["adaptive"] = {
                "k_cap": self.adaptive.k_cap,
                "buckets": list(self.adaptive.buckets),
                "rows": {str(r): v
                         for r, v in self.adaptive.stats().items()},
            }
        return out

    def constrain_stats(self) -> dict:
        """Constrained-decoding block for /v1/stats (docs/SERVING.md
        "Constrained decoding"): rows currently decoding under a grammar,
        table capacity, and degradations. The api layer merges the edge's
        compile-cache stats (constrain.compile_stats) alongside."""
        tbl = self.constrain_table
        return {
            "active_rows": tbl.active_rows if tbl is not None else 0,
            "table_states": self.constrain_states,
            "table_used": (sum(n for _off, n in tbl._regions.values())
                           if tbl is not None else 0),
            "degraded": self.constrain_degraded,
        }

    def _dispatch_age(self) -> float:
        """Watchdog reading: 0 while nothing is in flight (an idle scheduler
        is not a hung one); otherwise seconds since the scheduler last made
        progress — the later of the last completed dispatch and the oldest
        live admission, so a hang in the very FIRST dispatch (or the first
        after an idle period) grows from the moment work arrived instead of
        reading 0 / a stale pre-idle timestamp forever."""
        busy = [s.admit_t for s in self._slots if s.req is not None]
        if not busy:
            return 0.0
        ref = min(busy)
        if self._last_dispatch_t is not None and self._last_dispatch_t > ref:
            ref = self._last_dispatch_t
        return max(time.monotonic() - ref, 0.0)

    def dispatch_age(self) -> float:
        """Public watchdog reading (resilience/supervisor.py): seconds since
        the scheduler last made progress while work is in flight, 0 idle —
        the same number the batch_dispatch_age_seconds gauge exports."""
        return self._dispatch_age()

    def recover_wedged(self, error: Exception | None = None,
                       reinit: bool = True) -> bool:
        """Supervisor escalation (resilience/supervisor.py, docs/ROBUSTNESS.md):
        the scheduler stopped making progress — a device dispatch (or its
        result transfer) is hung — so act instead of observing:

        1. ABANDON the wedged scheduler thread: bump the engine epoch. The
           stuck thread cannot be interrupted, but every path it can wake on
           checks the epoch before touching engine state and unwinds via
           _StaleEpoch; its locals reference the OLD slot objects and OLD
           cache arrays, both replaced below.
        2. FAIL every in-flight and queued request with EngineWedged — a
           RETRIABLE error: the HTTP layer surfaces it as a resumable
           failure, so a durable fleet router re-submits each request's
           journal to a surviving replica (docs/FLEET.md "Resume protocol").
        3. RE-INITIALIZE the backend (`reinit=True`): drop every compiled
           loop/step and allocate fresh KV caches, so the next admission
           runs against clean device state instead of buffers a zombie
           dispatch may still write. Returns False when re-init itself
           fails (the replica should stay unhealthy and be ejected).

        The next submit() lazily starts a fresh scheduler thread. Safe to
        call from any thread; concurrent calls serialize on the engine lock.
        """
        err = error if error is not None else EngineWedged(
            f"engine made no dispatch progress for "
            f"{self._dispatch_age():.1f}s; in-flight requests failed "
            "(retriable) and the backend was re-initialized")
        with self._lock:
            self._epoch += 1
            stale = self._thread
            self._thread = None  # next submit spawns a fresh scheduler
        if stale is not None and stale.is_alive():
            # a LIVE (merely slow, or killed-by-a-test) scheduler observes
            # the bump at its next loop/dispatch check and exits within one
            # iteration — wait briefly so the slot/cache swap below runs
            # single-threaded. A genuinely hung thread times this out and
            # is caught by the thread-epoch checks when it eventually wakes.
            stale.join(timeout=1.0)
        self.wedge_recoveries += 1
        old_slots = self._slots
        with self._plock:
            # fresh slot objects FIRST: the abandoned thread's locals hold
            # refs to the old list, so nothing it does can reach new requests
            self._slots = self.slot_cache.slots = [
                _Slot(i) for i in range(self.slots_n)]
            # constraint table regions were keyed by the old slots; drop the
            # whole table (re-created lazily at the next constrained
            # admission) rather than freeing per-row under a wedged epoch
            self.constrain_table = None
            _CONSTRAIN_ROWS.set(0)
            for s in old_slots:
                self.slot_cache.unpin(s)
                self.slot_cache.release(s)
                req = s.req
                s.req = None
                s.pending = []
                self.proposer.detach(s.index)
                if self.adaptive is not None:
                    self.adaptive.detach(s.index)
                if req is not None and not req.done.is_set():
                    req.error = err
                    req.finish = "error"
                    _WEDGE_FAILED.inc()
                    flight.finish(req.rid, "error", error=repr(err))
                    req.done.set()
            while True:
                try:
                    self._pending.append(self._queue.get_nowait())
                except queue.Empty:
                    break
            for req in self._pending:
                req.error = err
                req.finish = "error"
                _WEDGE_FAILED.inc()
                flight.finish(req.rid, "error", error=repr(err))
                req.done.set()
            self._pending.clear()
            _QUEUE_DEPTH.set(0)
            _SLOTS_OCCUPIED.set(0)
        self._inflight = None
        _PIPELINE_DEPTH.set(0)
        self._last_dispatch_t = None  # age restarts from the next admission
        ok = True
        if reinit:
            try:
                faults.fire("engine.reinit")
                eng = self._eng
                self._loops.clear()
                eng._steps.clear()
                eng._decode_loops.clear()
                eng.k_cache, eng.v_cache = eng._init_cache()
                if self.drafter is not None:
                    # a zombie may still hold (and have donated) the
                    # drafter's buffers — fresh caches, programs, row state
                    self.drafter.reset_backend()
                self.slot_cache.reset()
            except Exception as e:
                ok = False
                print(f"🔴 backend re-initialization failed: {e!r}")
        _WEDGE_RECOVERIES.labels(outcome="ok" if ok else "reinit_failed").inc()
        return ok

    def close(self, drain: bool = False, timeout: float | None = None) -> None:
        """Stop the engine. `drain=True` (the SIGTERM path): refuse new
        admissions (submit raises EngineDraining) but let every in-flight AND
        already-queued request finish, bounded by `timeout` seconds (None =
        30); then close. `drain=False`: abort everything immediately —
        waiters get EngineClosed."""
        if drain and not self._shutdown:
            self._draining = True
            deadline = time.monotonic() + (30.0 if timeout is None else timeout)
            while time.monotonic() < deadline:
                with self._plock:
                    busy = (any(s.req is not None for s in self._slots)
                            or bool(self._pending))
                if not busy and self._queue.empty():
                    break
                time.sleep(0.01)
        self._shutdown = True
        with self._cond:
            self._cond.notify_all()
        # snapshot the scheduler ref under its lock (a concurrent
        # recover_wedged may swap it mid-close; joining the OLD reference
        # after the swap would wait on an abandoned zombie while the fresh
        # scheduler kept serving a closed engine) — but join OUTSIDE the
        # lock: holding it through a 30 s join would block _ensure_thread
        # and recover_wedged for the whole drain
        with self._lock:
            t = self._thread
        if t is not None:
            t.join(timeout=30)
        if t is None or not t.is_alive():
            # the directory outlives the device arrays: what it holds of
            # them as pending reads becomes host arrays now (not behind a
            # scheduler that is still stuck in a dispatch)
            self.slot_cache.settle(force=True)
        # detach the watchdog callback IF it is still ours (a later engine
        # may have claimed the gauge): a bound method left on the
        # module-global gauge would pin this engine's params + KV caches
        # past close() for the process lifetime
        if _DISPATCH_AGE._fn == self._dispatch_age:
            _DISPATCH_AGE.set_function(None)
        with self._lock:
            watched, self._gc_watched = self._gc_watched, False
        if watched:
            process.unwatch_gc()
        # unblock every waiter: in-flight slots and still-queued requests. The
        # scheduler may still be alive after the join timeout (long device step), so
        # snapshot each slot's request and tolerate it finishing concurrently.
        err = EngineClosed("BatchEngine closed")
        with self._plock:
            for s in self._slots:
                self.slot_cache.unpin(s)
                req = s.req
                if req is not None and not req.done.is_set():
                    req.error = err
                    s.req = None
                    s.pending = []
                    flight.finish(req.rid, "error", error=repr(err))
                    req.done.set()
            while True:
                try:
                    self._pending.append(self._queue.get_nowait())
                except queue.Empty:
                    break
            for req in self._pending:
                req.error = err
                flight.finish(req.rid, "error", error=repr(err))
                req.done.set()
            self._pending.clear()

    # ------------------------------------------------------------------
    # scheduler
    # ------------------------------------------------------------------

    def _ensure_thread(self) -> None:
        with self._lock:
            if self._thread is None or not self._thread.is_alive():
                if not self._gc_watched:  # from the first start to close()
                    self._gc_watched = True
                    process.watch_gc()
                self._thread = threading.Thread(target=self._loop, daemon=True,
                                                name="batch-engine")
                self._thread.start()

    def _assign(self, req: BatchRequest) -> _Slot | None:
        """Place a request on the free slot with the longest common token prefix
        (the multi-slot generalization of the reference NaiveCache), the
        reuse extended from the cross-request prefix cache where the radix
        index covers more of the prompt than the slot's own history
        (`slot_cache.admit`, docs/PREFIX_CACHE.md).

        A PREEMPTED request (req.out non-empty: a batch row displaced by an
        interactive admission, docs/SERVING.md "Multi-tenant serving")
        re-admits against prompt ⊕ delivered — the same forced-prefix
        construction as a durable resume (docs/FLEET.md): its sampler
        already sits after exactly the delivered coins, the preempting
        _finish-style release harvested the row into the prefix cache, so
        re-prefill is mostly a radix hit and generation continues
        byte-identical to the uninterrupted run."""
        free = [s for s in self._slots if s.req is None]
        if not free:
            return None
        # effective admission prompt: original prompt plus any tokens
        # already delivered before a preemption (empty for fresh requests)
        full = req.prompt + req.out if req.out else req.prompt

        def common(s: _Slot) -> int:
            n = 0
            for a, b in zip(s.history, full):
                if a != b:
                    break
                n += 1
            return min(n, len(full) - 1)
        best = max(free, key=common)
        rewind, reuse = self.slot_cache.admit(best, req, full, common(best))
        best.admit_t = time.monotonic()  # before .req: the watchdog keys on req
        best.req = req
        best.pos = reuse
        best.history = list(full[:reuse])
        best.pending = full[reuse:]
        best.last_logits = None
        best.next_token = None
        best.clamp_pos = None
        best.armed = False
        # drafting corpus/frontier: the FULL prompt (including any reused
        # prefix and preemption- or resume-delivered tokens) — the proposer
        # (n-gram index and/or model-drafter row state) re-attaches whole,
        # so preemption re-admission and durable resume need nothing special
        if self.spec_k:
            self.proposer.attach(best.index, full)
            if self.adaptive is not None:
                self.adaptive.attach(best.index)
        else:
            self.proposer.detach(best.index)
        self._attach_constraint(best, req)
        # per-tenant delivery counter child, resolved once per admission so
        # the per-token _emit path pays no label lookup
        best.tok_counter = _TENANT_TOKENS.labels(
            tenant=self.tenants.canonical(req.tenant)
            if self.tenants is not None else req.tenant)
        req.stats.prompt_tokens = len(full)
        # queue TTL bounds the wait before FIRST service only: a later
        # preemption must not let the original bound expire a request that
        # already has delivered output
        req.queue_ttl_t = 0.0
        # admission reuse reading (rewind + radix seed): the prefill this
        # request SKIPPED — for a resume admission this is the number the
        # "resume cost ≈ one suffix prefill" claim rests on, surfaced per
        # request so api-level resume counters can report it
        req.stats.reused_tokens = reuse
        qw_ms = ((time.perf_counter() - req.submit_t) * 1e3
                 if req.submit_t else 0.0)
        if req.submit_t:
            _QUEUE_WAIT.observe(qw_ms / 1e3)
        flight.event(req.rid, "admitted", slot=best.index,
                     queue_wait_ms=round(qw_ms, 3), rewind_tokens=rewind,
                     seeded_tokens=reuse - rewind,
                     **({"resume_tokens": req.resume_tokens}
                        if req.resume_tokens else {}))
        return best

    def _attach_constraint(self, slot: _Slot, req: BatchRequest) -> None:
        """Bind the request's grammar automaton to the slot: allocate a
        region in the (lazy) device constraint table, replay any
        already-delivered tokens through the automaton so preemption
        re-admission and durable resume continue from the right grammar
        state, and register the live handle with the GrammarProposer. A
        full table degrades this row to unconstrained (counter + flight
        event, never a client failure)."""
        slot.constraint = None
        self.grammar_proposer.detach(slot.index)
        aut = req.constraint
        if aut is None:
            if self.constrain_table is not None:
                _CONSTRAIN_ROWS.set(self.constrain_table.active_rows)
            return
        if self.constrain_table is None:
            from ..constrain import ConstraintTable

            self.constrain_table = ConstraintTable(
                self.spec.vocab_size, self.constrain_states)
        off = self.constrain_table.alloc(slot.index, aut)
        if off is None:
            self.constrain_degraded += 1
            _CONSTRAIN_DEGRADED.labels(reason="capacity").inc()
            flight.event(req.rid, "constrain_degraded", reason="capacity",
                         grammar=req.constraint_hash)
            _CONSTRAIN_ROWS.set(self.constrain_table.active_rows)
            return
        sc = _SlotConstraint(aut, off, req.constraint_hash)
        # tokens the grammar already consumed: a resume prefix (last
        # resume_tokens of the prompt — generated elsewhere) then any
        # preemption-delivered output. A replay token outside the grammar
        # means the constraint cannot be honored from here — degrade
        # honestly rather than emit a mask for the wrong state.
        replay = (req.prompt[len(req.prompt) - req.resume_tokens:]
                  if req.resume_tokens else [])
        for t in list(replay) + list(req.out):
            nxt = aut.advance(sc.state, t)
            if nxt < 0:
                sc.degraded = True
                self.constrain_degraded += 1
                _CONSTRAIN_DEGRADED.labels(reason="divergence").inc()
                flight.event(req.rid, "constrain_degraded",
                             reason="divergence", grammar=sc.ghash)
                break
            sc.state = nxt
        slot.constraint = sc
        self.grammar_proposer.attach_constraint(slot.index, sc)
        flight.event(req.rid, "constrain_attached", grammar=sc.ghash,
                     states=aut.n_states, offset=off)
        _CONSTRAIN_ROWS.set(self.constrain_table.active_rows)

    def _release_constraint(self, slot: _Slot) -> None:
        """Free the slot's constraint-table region (finish/preempt/wedge).
        The proposer-side registration is cleared by ProposerMux.detach at
        the same call sites."""
        slot.constraint = None
        if self.constrain_table is not None:
            self.constrain_table.free(slot.index)
            _CONSTRAIN_ROWS.set(self.constrain_table.active_rows)

    def _degrade_constraint(self, slot: _Slot, reason: str) -> None:
        """Park the row on the universal (unconstrained) table state after
        a masking fault or grammar divergence — decoding continues, the
        constraint is dropped, and the degradation is visible in
        constrain_degraded_total and the flight timeline (the documented
        fallback: degrade > fail, docs/ROBUSTNESS.md)."""
        sc = slot.constraint
        if sc is None or sc.degraded:
            return
        sc.degraded = True
        self.constrain_degraded += 1
        _CONSTRAIN_DEGRADED.labels(reason=reason).inc()
        if slot.req is not None:
            flight.event(slot.req.rid, "constrain_degraded", reason=reason,
                         grammar=sc.ghash)

    def _dispatched(self, kind: str, call):
        """Run one device dispatch with transient-fault retry: classify()
        'transient' errors (injected TransientDispatchError, or any exception
        carrying fault_scope='transient') are retried up to max_retries times
        with capped exponential backoff; anything else propagates unchanged.
        Retry is sound here because a transient failure by definition raised
        before the dispatch consumed its inputs (the injection points fire
        before the device call; a real mid-execution failure classifies
        'engine' and is never retried against possibly-donated buffers).

        EPOCH GUARD (recover_wedged): when the supervisor abandoned this
        thread while it was stuck inside `call()` (or the injected-latency
        sleep standing in for a hung device), the bump is observed HERE, on
        the first instruction after the stall — before the caller can rebind
        eng.k_cache/v_cache over the re-initialized backend's fresh arrays
        or deliver tokens into slots that now belong to other requests."""
        delay = self.retry_backoff
        attempt = 0
        # the THREAD's epoch, not a fresh read: a bump landing before this
        # call must still be detected at the post-call check
        epoch = getattr(self._tls, "epoch", self._epoch)
        while True:
            try:
                faults.fire("batch.dispatch", kind=kind, attempt=attempt)
                out = call()
                if self._epoch != epoch:
                    raise _StaleEpoch()
                self._last_dispatch_t = time.monotonic()
                return out
            except Exception as e:
                if self._epoch != epoch:
                    raise _StaleEpoch() from None
                if classify(e) != "transient" or attempt >= self.max_retries:
                    raise
                _ENGINE_ERRORS.labels(kind="transient").inc()
                _RETRIES.inc()
                attempt += 1
                # the retry stalls every in-flight request equally: each
                # timeline records it (the co-batched blast radius of a
                # transient, made visible per request)
                for s in self._slots:
                    if s.req is not None:
                        flight.event(s.req.rid, "dispatch_retry",
                                     kind=kind, attempt=attempt)
                time.sleep(min(delay, 1.0))
                delay *= 2

    def _stage(self, tokens_rows: list[list[int]], starts: list[int], t: int,
               lead: int | None = None):
        """Host half of one batched (B, t) step, under the caller's
        `batch.build` span: the window bucket, its program, and the inputs
        on the device. Returns the keys attention runs against (the bucket,
        or the context length where there is none) and what _step takes.
        `lead`: the one row that prefills, where every other row holds at
        most its index 0; it rides behind the rows' positions, and the
        program then runs its weights over the rows that can hold a token
        (models/forward.py `RowMap`)."""
        eng = self._eng
        window = eng._window_for(max(s + t for s in starts))
        with trace.span("batch.stage") as sp:
            toks = _upload(np.asarray(tokens_rows, dtype=np.int32))
            start_pos = _upload(np.asarray(
                starts if lead is None else starts + [lead], dtype=np.int32))
            tables, resent = self.slot_cache.table()
            sp.add(transfers=2 + resent, table=int(resent),
                   bytes=toks.nbytes + start_pos.nbytes
                   + (tables.nbytes if resent else 0))
        return (window or self.spec.seq_len,
                (eng._step_for(window), toks, start_pos, tables))

    def _count_work(self, positions: int, window: int,
                    real: list[tuple[int, int]], starts: list[int],
                    budget: list[int] | None = None,
                    lead: int | None = None) -> None:
        """One dispatch's useful-work counters: `positions` per row were
        dispatched against `window`; `real` lists (start, n) for each run of
        n request tokens from position start (causal length start + i + 1).
        `starts` is every row's committed length as the device was given it;
        `budget` (a K-step scan only: `positions` steps of one token) the
        steps each row advances, its length growing by one a step.
        `lead`: the prefilling row where the program held a row map
        (`forward.RowMap`): its weights ran over the compact stream's rows,
        and a block pool was read for the lead's `positions` queries and for
        one query a row (the lead's own among them), not for the rectangle
        (the contiguous per-row cache still is)."""
        dispatched = self.slots_n * positions  # query rows attention ran
        computed = dispatched  # rows the weight kernels ran over
        if lead is not None:
            computed = compact_rows(positions, self.slots_n)
            if self.slot_cache.block_tokens:
                dispatched = positions + self.slots_n
            else:
                lead = None  # every read below is the rectangle's
        _POSITIONS_DISPATCHED.inc(computed)
        _ATTN_PAIRS_DISPATCHED.inc(dispatched * window)
        bt = self.slot_cache.block_tokens
        if self._eng.paged_kernel:
            n_read = -(-window // bt)

            def keys(length):
                # a layer's count, averaged over the kinds of layer: a
                # windowed one skips the steps behind its first query's bound
                return sum(share * visited_keys(
                    length, n_read, bt, max(length - w + 1, 0) if w else 0)
                    for w, share in getattr(self, "_layer_windows",
                                            ((0, 1.0),)))

            # the committed lengths the kernel sees, each `times` over: a
            # row's own, once a position (under a row map the lead's once a
            # position and every row's once); in a scan, one a (row, step)
            if lead is not None:
                lengths, times = [starts[lead]] * positions + starts, 1
            elif budget is None:
                lengths, times = starts, positions
            else:
                lengths, times = [st + min(i, b)
                                  for st, b in zip(starts, budget)
                                  for i in range(positions)], 1
            visited = times * sum(keys(n) for n in lengths)
            # the layers with a window alone, summed over them: what the
            # skip leaves, and what they would visit without it
            for w, layers in getattr(self, "_window_layers", ()):
                _ATTN_WINDOW_PAIRS_VISITED.inc(layers * times * sum(
                    visited_keys(n, n_read, bt, max(n - w + 1, 0))
                    for n in lengths))
                _ATTN_WINDOW_PAIRS_UNWINDOWED.inc(layers * times * sum(
                    visited_keys(n, n_read, bt) for n in lengths))
        else:  # the gather path and the dense cache read the whole window
            visited = dispatched * window
        _ATTN_PAIRS_VISITED.inc(visited)
        spec = getattr(self, "spec", None)
        if spec is not None and spec.is_moe:
            _MOE_ROUTED.inc(computed * spec.n_active_experts
                            * spec.block_layers)
        if spec is not None and spec.latent:
            if lead is not None:  # the lead's rows in both of the two reads
                read = sum(starts) + starts[lead]
            elif budget is None:  # once a dispatched row, whatever its T
                read = sum(starts)
            else:
                read = sum(st + min(i, b) for st, b in zip(starts, budget)
                           for i in range(positions))
            _LATENT_ROWS_READ.inc(read * spec.n_layers)
            _LATENT_DISPATCH_ROWS.inc(dispatched * spec.n_layers)
        _POSITIONS_REAL.inc(sum(n for _, n in real))
        _ATTN_PAIRS_REAL.inc(sum(n * p + n * (n + 1) // 2 for p, n in real))
        if bt:
            ends = sum((p + n) // bt - p // bt for p, n in real)
            _BLOCK_ENDS.inc(ends)
            if spec is not None and spec.mixed:
                layers = len(spec.state_layers)
                if spec.ssm:  # the tails are snapshot where a stride ends
                    stride = self.slot_cache.stride
                    ends = sum((p + n) // stride - p // stride
                               for p, n in real)
                _STATE_ROWS.inc(layers * sum(n for _, n in real))
                _STATE_SNAPSHOTS.inc(layers * ends)
                # what the program writes, scratch included: a ring row a
                # computed row a layer, and a block's snapshot a block end
                # (every row's of a scan step or a lone token, the chunk's)
                row = spec.state_width * self._eng.k_cache.dtype.itemsize
                _STATE_BYTES.inc(layers * row * (
                    computed + spec.state_rows * ends))

    @staticmethod
    def _count_moe(stats) -> int:
        """The batch_moe_* counters from a dispatch's int32 vector (None for
        a dense model); returns the experts touched for the span's args."""
        if stats is None:
            return 0
        _D2H_BYTES.inc(stats.nbytes)
        vals = [int(v) for v in np.asarray(stats)]
        for c, v in zip(_MOE_COUNTERS, vals):
            c.inc(v)
        return vals[2]

    def _count_inflight(self, fl: _InflightStep,
                        real: list[tuple[int, int]]) -> None:
        """_count_work of a scan (K steps of one token, a row's length
        growing with its budget) or a verify block (one step of K)."""
        self._count_work(fl.k, fl.window, real, fl.starts,
                         fl.budget if fl.kind == "scan" else None)
        self._count_moe(fl.moe)

    def _observe_gap(self) -> None:
        """Before a dispatch is issued from host state: the host time since
        the previous dispatch's results arrived (nothing ran on the device
        in between). No observation after an idle wait."""
        if self._gap_t is not None:
            _DISPATCH_GAP.observe(max(time.perf_counter() - self._gap_t, 0.0))

    def _samples_here(self, slot: _Slot) -> bool:
        """Whether the row's next token may be the step program's own
        arg-max, so that its logits need never reach the host: its sampler
        is the engine's `Sampler` itself (not a subclass, not a look-alike
        that carries `temperature = 0.0` and wants to be shown the logits)
        at temperature 0 over the whole vocabulary, and no grammar
        constrains the row. Asked a dispatch, of the rows it samples."""
        req = slot.req
        smp, sc = req.sampler, slot.constraint
        return (type(smp) is Sampler and smp.temperature == 0.0
                and smp.vocab_size >= self.spec.vocab_size
                and req.max_tokens > 0 and (sc is None or sc.degraded))

    def _runs_ahead(self) -> bool:
        """Whether a step may be issued without waiting for it (and the one
        after it from its carry): what the `pipeline` switch governs, as it
        governs the scan's chain. Not while the engine drains or closes (the
        in-flight one is delivered, the rest run one by one), not where rows
        shard over dp, not beside speculative drafting, whose proposals are
        read from delivered tokens."""
        return (self.pipeline and not self._shutdown and not self._draining
                and self._eng.dp == 1 and not self.spec_k)

    def _launch_step(self, staged, kind: str,
                     chain: _InflightStep | None = None):
        """Hand one staged (B, t) step to the device: `batch.launch`, the
        host calls the jitted step until it returns its futures. `chain`:
        the step whose `tok` the rows with a negative token continue from
        (None: every token is the host's). Returns the logits of the one
        position a row that is sampled, (B, 1, vocab): the row's only
        position at t = 1, and of a chunk the prefilling row's last and
        every other row's index 0 (the program's head runs there alone);
        their arg-max `tok` (B,); a routed model's stats vector or None.
        All still on the device."""
        eng = self._eng
        step, toks, start_pos, tables = staged
        # snapshot the cache refs NOW and rebind only after _dispatched's
        # epoch check: a thread abandoned by recover_wedged mid-stall must
        # neither donate the re-initialized backend's fresh cache arrays nor
        # rebind its stale outputs over them
        kc_in, vc_in = eng.k_cache, eng.v_cache
        if chain is None:
            self._observe_gap()
            carry = self._no_carry
        else:
            _DISPATCH_GAP.observe(0.0)  # chained: the device never went idle
            _STEP_CHAINED.inc()
            carry = chain.tok

        def call():
            t0 = time.perf_counter()
            with trace.span("batch.launch"):
                # a routed model's program returns one value more
                logits, kc, vc, *moe, tok = step(
                    eng.params, eng.rope, toks, kc_in, vc_in, start_pos,
                    tables, carry)
            _PHASE_LAUNCH.observe(time.perf_counter() - t0)
            return logits, kc, vc, moe, tok

        logits, eng.k_cache, eng.v_cache, moe, tok = self._dispatched(
            kind, call)
        return logits, tok, moe[0] if moe else None

    def _fetch_step(self, fl: _InflightStep, sampled_here: bool):
        """Wait for a launched step and bring its results to the host, under
        the caller's dispatch span: every row's logits where a row is
        sampled on the host, else the program's own samples `tok`, 4 bytes a
        row; a routed model's stats either way. Two phases, a span and an
        observation of batch_dispatch_phase_seconds each. `batch.fetch_wait`
        (inside `batch.fetch`): the host waits for the DEVICE, the inputs'
        arrival, the program's start and the program; its end is the host's
        timestamp of "the program is done". `batch.fetch_copy` (the rest of
        `batch.fetch`): the host waits for the TRANSFER."""
        what = fl.tok if sampled_here else fl.toks
        moe = () if fl.moe is None else (fl.moe,)
        epoch = getattr(self._tls, "epoch", self._epoch)
        # demotion reads issued and not yet host arrays: what else is
        # on the way to the host when this dispatch's results are
        pending = self.slot_cache.pending_bytes()
        with trace.span("batch.fetch", {"bytes": what.nbytes}):
            t2 = time.perf_counter()
            with trace.span("batch.fetch_wait"):
                for a in (what, *moe):
                    a.block_until_ready()
            t3 = time.perf_counter()
            with trace.span("batch.fetch_copy", {
                    "bytes": what.nbytes,
                    "demote_pending_bytes": pending}) as sp:
                out = np.asarray(what)
                if moe:  # known only now: added to the open span
                    sp.add(experts_touched=self._count_moe(moe[0]))
            t4 = time.perf_counter()
        if self._epoch != epoch:
            # abandoned by recover_wedged while it waited: the slots these
            # results belong to are the replacement epoch's now
            raise _StaleEpoch()
        _D2H_BYTES.inc(what.nbytes)
        _PHASE_WAIT.observe(t3 - t2)
        _PHASE_COPY.observe(t4 - t3)
        return out

    def _finish(self, slot: _Slot, finish: str) -> None:
        req = slot.req
        req.finish = finish
        # engine-side completion: the api layer (when there is one) adds
        # TTFT/E2E to the same record after its own _observe_done; `error`
        # only when real — its presence marks the record slow-log-eligible
        flight.finish(req.rid, finish,
                      generated_tokens=req.stats.generated_tokens,
                      **({"error": repr(req.error)}
                         if req.error is not None else {}))
        slot.req = None
        slot.pending = []
        slot.next_token = None
        slot.ahead = 0  # a step in flight drops this row's result
        self.proposer.detach(slot.index)
        if self.adaptive is not None:
            self.adaptive.detach(slot.index)
        slot.tok_counter = None
        self._release_constraint(slot)
        # service-rate bookkeeping (docs/SERVING.md "Multi-tenant serving"):
        # one completion noted to the drain estimator — the denominator of
        # every Retry-After hint — plus per-tenant completion accounting
        self._drain.note()
        _DRAIN_RATE.set(self._drain.rate())
        _TENANT_REQUESTS.labels(
            tenant=(self.tenants.canonical(req.tenant)
                    if self.tenants is not None else req.tenant),
            **{"class": req.klass}).inc()
        # the lease goes before done.set(), so a caller observing completion
        # sees no residual reservation (the harvest below needs no pin)
        self.slot_cache.unpin(slot)
        if req.export_kv:
            # disaggregation export (docs/DISAGG.md): host-snapshot the
            # committed prompt blocks BEFORE done.set() — the /v1/kv
            # handler wakes on done and must find kv_export populated; and
            # this runs on the scheduler thread, the only place device
            # cache reads cannot race a donating dispatch
            try:
                req.kv_export = self.slot_cache.export_blocks(
                    slot, len(req.prompt))
            except Exception as e:
                warn_degraded("export", e)
        _REQUESTS.labels(finish=finish).inc()
        req.done.set()
        # harvest AFTER done.set(): the slot's history/rows stay valid (they
        # also back the same-slot rewind), and the copy-out must not extend
        # the finished client's wait
        self.slot_cache.harvest(slot)

    def _cover(self, rows: list[_Slot], upto,
               decline: bool = False) -> list[_Slot]:
        """Block coverage for every committed write a dispatch makes: each
        of `rows` up to position `upto(row)` (scratch beyond it lands in the
        scratch block by design). A pool that cannot serve a row even after
        reclaim fails ONLY that row's request and takes it out of `rows`
        (the rows returned); `decline` (a dispatch planned ahead) fails
        nobody and raises: the synchronous path fails the row it is."""
        short = []
        for slot in rows[:]:
            try:
                self.slot_cache.cover(slot, upto(slot))
            except Exception as e:
                if decline or classify(e) != "request":
                    raise
                self._fail_request(slot, e)
                rows.remove(slot)
                short.append(slot)
        return short

    def _park_positions(self, t: int) -> list[int]:
        """Per-row start positions for rows not participating in this step: park at the
        row's current pos so garbage lands on masked future positions, clamped so the
        write stays inside the cache. A clamped park (row sitting within t of the end)
        overwrites that row's tail history, so the reusable prefix is truncated to the
        write start."""
        s = self.spec.seq_len
        starts = []
        for sl in self._slots:
            # where the row stands once a step in flight has run (a plan made
            # ahead never comes here with a row that would be clamped)
            pos = sl.pos + sl.ahead
            p = min(pos, max(s - t, 0))
            if p < pos:
                # a pool that cannot serve the park's copy-on-write fails
                # ONLY this request: the slot parks empty like any idle row
                # (callers re-filter for reaped rows after _park_positions)
                try:
                    kept = self.slot_cache.park(sl, p, min(p + t, s))
                except Exception as e:
                    if classify(e) != "request":
                        raise
                    self._fail_request(sl, e)
                    self.slot_cache.release(sl)
                    kept = False
                if not kept:
                    p = 0
            starts.append(p)
        return starts

    def _admit(self) -> tuple[int, int]:
        """Drain the cross-thread queue into the scheduler-local
        weighted-fair wait queue, reap cancelled/expired queued requests,
        and assign in WFQ order onto free slots — interactive class first,
        tenants by weight (docs/SERVING.md "Multi-tenant serving"). When no
        slot is free and the fair queue's head is INTERACTIVE, a batch-class
        row is preempted at this super-step boundary (its request re-queued,
        to resume byte-identical later) so interactive TTFT is bounded by
        one dispatch, not a batch request's whole generation. Returns
        (requests given a slot, requests left queued)."""
        now = time.perf_counter()
        admitted = 0
        # preempted rows' prefix harvests are snapshotted under the lock but
        # copied device→host after it: the transfer must not stall
        # submit()/admission callers on _plock
        harvests: list[Callable[[], None]] = []
        with self._plock:
            self._drain_submit_queue()
            # queue-TTL / deadline expiry applies to EVERY queued request,
            # not just the head — under sustained occupancy the head may
            # never admit, and requests behind it must still time out
            expired = []
            for req in self._pending:
                expired_by = ("queue_ttl" if req.queue_ttl_t
                              and now >= req.queue_ttl_t
                              else "deadline" if req.deadline_t
                              and now >= req.deadline_t else None)
                if expired_by is not None:
                    expired.append((req, expired_by))
            for req, expired_by in expired:
                self._pending.remove(req)
                req.finish = "deadline"
                # a preempted request with delivered output keeps it (the
                # decode-deadline contract); only a never-served request
                # surfaces the typed error
                if not req.out:
                    req.error = DeadlineExceeded(
                        f"request expired in queue ({expired_by})")
                _DEADLINE_EXPIRED.labels(where="queue").inc()
                _REQUESTS.labels(finish="deadline").inc()
                flight.finish(req.rid, "deadline", expired_by=expired_by)
                req.done.set()
            while True:
                req = self._pending.peek_next()
                if req is None:
                    break
                # heads leave via pop_next(), NOT remove(): pop advances
                # the class's virtual time to the served tag, which is
                # what anchors a later-arriving tenant's first tag at
                # "now" instead of zero — without it a tenant returning
                # from idle would be charged its entire lifetime service
                # against newcomers and starve (the SFQ V(t) invariant)
                if req.cancelled:
                    self._pending.pop_next()
                    req.finish = "cancelled"
                    _REQUESTS.labels(finish="cancelled").inc()
                    flight.finish(req.rid, "cancelled")
                    req.done.set()
                    continue
                try:
                    assigned = self._assign(req)
                except Exception as e:
                    # an admission failure is attributable to the request
                    # being admitted: fail IT and dequeue — leaving it at
                    # the head would re-raise every pass (hanging its waiter
                    # forever) while _fail_all killed innocent neighbors
                    self._pending.pop_next()
                    _ENGINE_ERRORS.labels(kind="request").inc()
                    req.error = e
                    req.finish = "error"
                    _REQUESTS.labels(finish="error").inc()
                    req.done.set()
                    continue
                if assigned is None:
                    # no free slot: an interactive head may preempt a
                    # batch-class row (super-step boundary — the scheduler
                    # is between dispatches right here); a batch head waits
                    if req.klass == "interactive" and self._try_preempt(
                            harvests):
                        continue  # a slot is free now; re-try this head
                    break
                self._pending.pop_next()
                admitted += 1
            queued = len(self._pending) + self._queue.qsize()
            _QUEUE_DEPTH.set(queued)
        for harvest in harvests:
            harvest()
        return admitted, queued

    def _drain_submit_queue(self) -> None:  # holds: self._plock
        """Move cross-thread submissions into the weighted-fair queue.
        Shared by the scheduler's _admit and the submit-side shed paths —
        eviction must see EVERY queued batch request, including ones still
        in the cross-thread queue because the scheduler is mid-dispatch."""
        while True:
            try:
                self._pending.append(self._queue.get_nowait())
            except queue.Empty:
                break

    def _try_preempt(self, harvests: list) -> bool:  # holds: self._plock
        """Free one slot for a waiting interactive request by preempting the
        batch-class row with the least delivered output (the cheapest
        resume). Interactive rows are never preempted. Returns True when a
        slot was freed; the victim's deferred prefix-harvest payload (if
        any) is appended to `harvests` for the caller to run OUTSIDE the
        lock."""
        victims = [s for s in self._slots
                   if s.req is not None and s.req.klass == "batch"
                   and not s.req.done.is_set() and not s.req.cancelled]
        if not victims:
            return False
        h = self._preempt_slot(min(victims, key=lambda s: len(s.req.out)))
        if h is not None:
            harvests.append(h)
        return True

    def _preempt_slot(self, slot: _Slot):  # holds: self._plock
        """Release a batch row at a super-step boundary and re-queue its
        request (docs/SERVING.md "Multi-tenant serving"). The release
        mirrors _finish WITHOUT completing the request: the prefix-cache
        lease is released and the committed history harvested (where that
        is a copy, it is returned to be made outside _plock) — the
        later re-admission (prompt ⊕ delivered, _assign) is then mostly a
        cache hit, the same "resume cost ≈ one suffix prefill" economics
        as a durable failover. An in-flight chained dispatch covering this
        row is discarded at delivery by the existing reaped-row rollback
        (slot.req changed), exactly like a cancel, and the sampler was
        already resynced to the delivered coins — so the resumed
        generation is byte-identical to an uninterrupted run
        (tests/test_tenancy.py pins greedy AND seeded-stochastic)."""
        req = slot.req
        req.preemptions += 1
        _PREEMPTED.inc()
        flight.event(req.rid, "preempted", slot=slot.index,
                     delivered=len(req.out))
        slot.req = None
        slot.pending = []
        slot.next_token = None
        slot.ahead = 0  # a step in flight drops this row's result
        self.proposer.detach(slot.index)
        if self.adaptive is not None:
            self.adaptive.detach(slot.index)
        slot.tok_counter = None
        # the grammar state is NOT kept across preemption: re-admission
        # replays prompt ⊕ delivered through the automaton in
        # _attach_constraint, the same rebuild-from-truth the proposer does
        self._release_constraint(slot)
        self.slot_cache.unpin(slot)
        harvest = self.slot_cache.harvest(slot, deferred=True)
        # nominal re-queue cost: the original admission already charged the
        # FULL request cost into the tenant's virtual time — charging the
        # remainder again would double-bill every preemption and erode the
        # tenant's configured share
        self._pending.push(req, req.tenant, req.klass, 1.0)
        return harvest

    def _reap_slots(self) -> None:
        """Free slots whose request was cancelled or whose wall-clock
        deadline expired (finish "deadline": partial output is kept; the
        waiter gets DeadlineExceeded only when nothing was generated)."""
        now = time.perf_counter()
        for sl in self._slots:
            req = sl.req
            if req is None:
                continue
            if req.cancelled:  # frees the slot immediately, even mid-prefill
                self._finish(sl, "cancelled")
            elif req.deadline_t and now >= req.deadline_t:
                if not req.out:
                    req.error = DeadlineExceeded(
                        "generation deadline expired before the first token")
                _DEADLINE_EXPIRED.labels(where="decode").inc()
                self._finish(sl, "deadline")

    def _fail_request(self, slot: _Slot, e: Exception) -> None:
        """Blast-radius 'request': fail ONLY this slot's request; the other
        co-batched slots keep decoding."""
        _ENGINE_ERRORS.labels(kind="request").inc()
        slot.req.error = e
        self._finish(slot, "error")

    def _fail_all(self, e: Exception) -> None:
        """Blast-radius 'engine': the shared dispatch failed unattributably
        (caches possibly indeterminate) — fail every in-flight request. The
        scheduler thread itself SURVIVES and keeps serving new admissions."""
        _ENGINE_ERRORS.labels(kind="engine").inc()
        if self._inflight is not None:
            # a chained dispatch issued against the now-failed schedule is
            # garbage: drop its device refs; the next dispatch re-uploads
            # host state (which _finish below makes authoritative)
            _PIPELINE_FLUSHES.labels(reason="error").inc()
            self._inflight = None
            _PIPELINE_DEPTH.set(0)
        for s in self._slots:
            if s.req is not None:
                s.req.error = e
                self._finish(s, "error")

    def _loop(self) -> None:
        epoch = self._epoch
        self._tls.epoch = epoch  # the epoch this thread was born into
        _SCHED_ALIVE.set(1)
        try:
            while not self._shutdown and self._epoch == epoch:
                try:
                    self._loop_once()
                except _StaleEpoch:
                    return  # abandoned by recover_wedged: unwind silently
                except Exception as e:
                    # _loop_once guards the dispatch phase itself; this outer
                    # net covers the admission/reap phase too (prefix-cache
                    # lookup at _assign, lease release at a deadline _finish)
                    # so NO exception can kill the scheduler thread — the
                    # invariant perf/fault_matrix.py asserts
                    if self._epoch != epoch:
                        return  # stale thread: the state is not ours to fail
                    try:
                        self._fail_all(e)
                    except Exception:
                        pass  # even a failing abort must not stop the loop
                    with self._cond:
                        if not self._shutdown:
                            self._cond.wait(timeout=0.05)
        finally:
            # a stale thread's exit must not clobber the replacement epoch's
            # liveness gauge or pipeline state
            if self._epoch == epoch:
                if self._inflight is not None:  # close() mid-pipeline
                    _PIPELINE_FLUSHES.labels(reason="close").inc()
                    self._inflight = None
                _PIPELINE_DEPTH.set(0)
                _SCHED_ALIVE.set(0)

    def _loop_once(self) -> None:
        # every statement of a pass lies under a batch.* span (admit,
        # advance, build, the dispatch with launch and fetch, deliver, wait):
        # a profiler trace names what the host did while the device idled
        with trace.span("batch.admit") as sp:
            admitted, queued = self._admit()
            self._reap_slots()
            prefill = [s for s in self._slots if s.req and s.pending]
            active = [s for s in self._slots if s.req and not s.pending]
            _SLOTS_OCCUPIED.set(sum(1 for s in self._slots
                                    if s.req is not None))
            sp.add(admitted=admitted, queued=queued)
        try:
            if self._inflight is not None:
                # a dispatch is running on device: deliver it (and maybe
                # chain its successor) before any dispatch from host state —
                # every later device op already depends on its cache writes
                fl = self._inflight
                if fl.kind == "step":
                    # a step is running: plan and issue the one after it
                    # from where its rows will stand, then deliver it
                    self._step_ahead(fl)
                else:
                    self._inflight = None
                    self._pipeline_advance(fl)
            elif prefill:
                # class-aware prefill order (docs/SERVING.md "Multi-tenant
                # serving"): an interactive row's prefill goes first — with
                # slot-order FIFO an interactive admission could wait
                # behind several batch rows' long prompts, unbounding the
                # TTFT the preemption path just bounded
                victim = self._prefill_victim(prefill)
                try:
                    # mixed step: active decode rows ride the prefill dispatch
                    # at T=1 instead of stalling behind it
                    self._prefill_step(victim, riders=active)
                except Exception as e:
                    # a request-scope fault during a prefill dispatch is
                    # attributable to the prefilling request (it fired before
                    # shared state changed): kill ONLY it. The riders remain
                    # consistent — their armed token re-dispatches next pass.
                    if classify(e) == "request" and victim.req is not None:
                        self._fail_request(victim, e)
                    else:
                        raise
            elif active:
                self._decode_step(active)
            else:
                # idle: sleep on the condition until submit()/close()
                # notifies. The timeout is only a safety net (e.g. a
                # queued request cancelled while idle has no notifier);
                # enqueue latency is set by the notify, not this number.
                # 0.1 s also bounds queue-TTL/deadline detection while idle.
                self._gap_t = None  # an idle device is not a starved one
                self.slot_cache.settle()
                with self._cond, trace.span("batch.wait"):
                    if self._queue.empty() and not self._shutdown:
                        self._cond.wait(timeout=0.1)
        except Exception as e:  # unattributable: fail all, survive, back off
            self._fail_all(e)
            # brief condition-based backoff so a persistently failing step
            # cannot spin the scheduler hot (a notify still wakes it early)
            with self._cond:
                if not self._shutdown:
                    self._cond.wait(timeout=0.05)

    @staticmethod
    def _prefill_victim(prefill: list[_Slot]) -> _Slot:
        """Which of the rows with prompt left prefills next: an interactive
        row first, then by slot."""
        return min(prefill, key=lambda s: (s.req.klass != "interactive",
                                           s.index))

    def _emit(self, slot: _Slot, token: int) -> bool:  # hot-path
        """Deliver one sampled token to the request (output list, stats,
        on_token stream) and run the host-side finish checks. Returns False
        when the request finished (slot released). slot.pos must already count
        the ingestion of this token's input. Runs under the request's trace
        context: a fault injected at batch.emit (or a broken callback) lands
        on the right flight-recorder timeline."""
        req = slot.req
        with reqctx.use(req.ctx):
            # per-request delivery fault point: fires inside the same try
            # blocks that guard a broken sampler/on_token callback, so an
            # injected error here kills exactly one co-batched request
            # (tests/test_resilience.py)
            faults.fire("batch.emit", slot=slot.index, n_out=len(req.out))
            req.out.append(token)
            # proposer corpus/frontier sync: every DELIVERED token, in
            # order (no-op for rows with no drafting state attached)
            self.proposer.push(slot.index, token)
            sc = slot.constraint
            if sc is not None and not sc.degraded:
                # host mirror of the device constraint carry: exact integer
                # bookkeeping per delivered token, so after a full delivery
                # no device readback or resync is ever needed. A token the
                # grammar disallows can only arrive off a degraded/unmasked
                # path — park the row unconstrained rather than mask from a
                # wrong state.
                nxt = sc.automaton.advance(sc.state, token)
                if nxt < 0:
                    self._degrade_constraint(slot, "divergence")
                else:
                    sc.state = nxt
            req.stats.generated_tokens += 1
            _DECODE_TOKENS.inc()
            if slot.tok_counter is not None:  # per-tenant delivery share
                slot.tok_counter.inc()
            if req.on_token is not None:
                req.on_token(token)
            if req.stop_check is not None and req.stop_check(token):
                self._finish(slot, "stop")
                return False
            if len(req.out) >= req.max_tokens or slot.pos >= self.spec.seq_len:
                self._finish(slot, "length")
                return False
            return True

    def _advance_row(self, slot: _Slot) -> bool:  # hot-path
        """Ensure slot.last_token holds the row's next un-ingested token —
        either the device-sampled tail of the previous super-step block, or a
        fresh host-side sample from last_logits (with delivery + finish
        checks). Returns False when the request finished instead."""
        req = slot.req
        if req.cancelled:
            self._finish(slot, "cancelled")
            return False
        if slot.armed:  # last_token already holds the next un-ingested token
            return True  # (the previous dispatch failed before writing it)
        if slot.next_token is not None:  # sampled on device, already delivered
            slot.last_token = slot.next_token
            slot.next_token = None
            slot.armed = True
            return True
        if slot.last_logits is None:  # context end hit during prefill
            self._finish(slot, "length")
            return False
        if req.max_tokens <= 0:  # parity with Engine.generate: zero-token request
            self._finish(slot, "length")
            return False
        logits = slot.last_logits
        sc = slot.constraint
        if sc is not None and not sc.degraded:
            # host-side grammar enforcement (the T=1 / post-prefill sampling
            # site): the SAME finite mask value the masked device programs
            # use, so host- and device-sampled tokens agree bit-for-bit
            # under an identical rng stream. A masking fault degrades this
            # row to unconstrained — never fails the request.
            try:
                faults.fire("constrain.mask", slot=slot.index)
                from .device_loop import MASK_NEG

                allowed = sc.automaton.mask_bool(sc.state)
                arr = np.array(logits, dtype=np.float32).reshape(-1)  # dlint: ignore[hot-sync] -- logits arrive host-side for the sampler anyway; masking rides the same transfer
                n = min(arr.shape[0], allowed.shape[0])
                arr[:n][~allowed[:n]] = np.float32(MASK_NEG)
                arr[n:] = np.float32(MASK_NEG)  # vocab padding: never legal
                logits = arr
            except Exception:
                self._degrade_constraint(slot, "mask")
                logits = slot.last_logits
        return self._take_token(slot, lambda: req.sampler.sample(logits))

    def _take_token(self, slot: _Slot, sample) -> bool:  # hot-path
        """Deliver the row's next token, `sample()` (its sampler shown the
        logits, or the step program's own arg-max already on the host), and
        leave it as the row's next un-ingested one. False when the request
        finished instead."""
        req = slot.req
        if req.cancelled:
            self._finish(slot, "cancelled")
            return False
        try:
            token = sample()
            alive = self._emit(slot, token)
        except Exception as e:
            # a broken callback (e.g. client disconnect mid-stream) fails ONLY
            # this request; the other slots keep decoding
            _ENGINE_ERRORS.labels(kind="request").inc()
            req.error = e
            self._finish(slot, "error")
            return False
        if not alive:
            return False
        slot.last_token = token
        slot.last_logits = None
        slot.armed = True
        return True

    def _prefill_step(self, slot: _Slot, riders: list[_Slot] = ()) -> None:
        """One prefill chunk of `slot`, the decoding rows `riders` riding it,
        from host state (nothing in flight)."""
        # request-scope injection point: fires BEFORE the rider advance and
        # the device dispatch, so an injected error is attributable to the
        # prefilling request alone (_loop_once fails only it); bound to the
        # request's trace context for timeline attribution
        with reqctx.use(slot.req.ctx):
            faults.fire("batch.prefill", slot=slot.index,
                        pending=len(slot.pending))
        t0 = time.perf_counter()
        if self.spec.seq_len - slot.pos <= 0:
            slot.last_logits = None
            slot.pending = []
            return
        # mixed prefill+decode: each active decode row rides this dispatch with
        # its next token at index 0 (rows advance one token per prefill chunk
        # instead of stalling behind it)
        with trace.span("batch.advance", {"rows": len(riders)}):
            riders = [r for r in riders if self._advance_row(r)]
        with trace.span("batch.build"):
            plan = self._plan_chunk(slot, riders, t0)
        if plan is not None:
            self._run_step(*plan)

    def _plan_chunk(self, slot: _Slot, riders: list[_Slot], t0: float,
                    chain: _InflightStep | None = None):
        """Under `batch.build`: the (B, t) step in which `slot` prefills its
        next chunk and each of `riders` advances by one token, staged.
        Positions are where the rows stand on the device (`pos + ahead`:
        once a step that is in flight has run); with `chain`, that step, the
        riders' tokens are its to give and ride as -1. Returns what
        `_run_step` takes, or None where the prefilling request was
        reaped."""
        s = self.spec.seq_len
        pos = slot.pos + slot.ahead
        pending = slot.pending[slot.ahead:]
        chunk = next((c for c in PREFILL_CHUNKS if len(pending) >= c), 1)
        chunk = min(chunk, s - pos)
        limit = self.slot_cache.chunk_limit(pos)
        if chunk > limit:  # a chunk never runs past a state snapshot's end
            chunk = next(c for c in PREFILL_CHUNKS if c <= limit)
        # keep parked rows' scratch writes inside the cache without
        # touching history: a parked row writes [pos, pos+chunk) which
        # must fit under seq_len; shrink the chunk when any OTHER row
        # sits too close to the end (its history would be corrupted by a
        # clamped write below its pos)
        for other in self._slots:
            if other is not slot and other.req is not None:
                chunk = min(chunk, max(s - other.pos - other.ahead, 1))
        piece = pending[:chunk]
        t = len(piece)
        starts = self._park_positions(t)
        if slot.req is None:  # reaped by a clamp-park CoW exhaustion
            return None
        riders = [r for r in riders if r.req is not None]
        starts[slot.index] = pos
        rows = [[0] * t for _ in self._slots]
        rows[slot.index] = piece
        for r in riders:
            # real token at index 0, scratch beyond: the rider's
            # positions pos+1..pos+t-1 are masked future slots its own
            # later decodes overwrite (in-bounds by the chunk shrink
            # above)
            starts[r.index] = r.pos + r.ahead
            rows[r.index] = ([r.last_token if chain is None else -1]
                             + [0] * (t - 1))
        # a RIDER's exhaustion fails the rider, not the innocent prefill (the
        # victim's own propagates to _loop_once's request-scope handler)
        self.slot_cache.cover(slot, pos + t)
        for r in self._cover(riders, lambda r: starts[r.index] + 1):
            starts[r.index] = r.pos  # an idle row's park
            rows[r.index] = [0] * t
        # a chunk with scratch in it: the program is told which row
        # prefills and runs its weights over the chunk and one row a slot
        # (rows sharded over dp have no one stream to be compacted into)
        lead = slot.index if t > 1 and self._eng.dp == 1 else None
        window, staged = self._stage(rows, starts, t, lead)
        fl = _InflightStep.step(
            [(slot, slot.req)] + [(r, r.req) for r in riders], t, starts,
            t0, chain is not None, window,
            ("batch.mixed_step" if riders else "batch.prefill",
             {"chunk": t, "riders": len(riders), "window": window,
              "slots": self.slots_n}),
            lead=slot, piece=piece, mapped=lead is not None)
        return fl, staged, chain

    def _plan_single(self, active: list[_Slot], t0: float,
                     chain: _InflightStep | None = None):
        """Under `batch.build`: one batched T=1 step of `active`, staged (the
        admission-latency and tail path); positions and `chain` as
        `_plan_chunk` takes them. None where no row is left."""
        starts = self._park_positions(1)
        # a clamp-park CoW under pool exhaustion may have reaped a row
        active = [s for s in active if s.req is not None]
        if not active:
            return None
        rows = [[0]] * self.slots_n
        for slot in active:
            starts[slot.index] = slot.pos + slot.ahead
            rows[slot.index] = [slot.last_token if chain is None else -1]
        window, staged = self._stage(rows, starts, 1)
        fl = _InflightStep.step(
            [(s, s.req) for s in active], 1, starts, t0, chain is not None,
            window, ("batch.single_step", {"rows": len(active),
                                           "window": window,
                                           "slots": self.slots_n}))
        return fl, staged, chain

    def _run_step(self, fl: _InflightStep, staged,
                  chain: _InflightStep | None = None) -> None:
        """Dispatch a planned step. Where every row it samples may be
        sampled by the program itself (`_samples_here`) and the scheduler
        runs ahead, it is ISSUED: launched under `batch.step_issue`, left in
        `self._inflight`, and delivered by the pass after this one, once the
        dispatch after it has been planned and issued from its carry
        (`_step_ahead`). Else it is synchronous, as every step was: launched
        and waited for under its own span, every row's logits fetched."""
        lead = fl.lead
        ctx = fl.rows[0][1].ctx if lead is not None else None
        kind = ("single_step" if lead is None
                else "mixed" if len(fl.rows) > 1 else "prefill")
        sampled = [s for s, _req in fl.rows
                   if s is not lead or len(s.pending) - s.ahead == fl.k]
        ahead = (chain is not None or self._runs_ahead()) and all(
            self._samples_here(s) for s in sampled)
        name, args = fl.span
        fl.snaps, work = self.slot_cache.state_word(
            fl.rows, fl.starts, fl.budget,
            chunk=fl.k if lead is not None else 0)
        args.update(work)

        def launch():
            fl.toks, fl.tok, fl.moe = self._launch_step(staged, kind, chain)
            # what the host will fetch, behind the program in the device's
            # own order, where the fetch's np.asarray alone would have put it
            start_host_copy(fl.tok if ahead else fl.toks,
                             *(() if fl.moe is None else (fl.moe,)))

        if not ahead:
            assert chain is None  # _step_ahead asked the same of its rows
            # the dispatch belongs to the prefilling request: bind its
            # context so the span (and any dispatch fault) carries its id
            with reqctx.use(ctx), trace.span(name, args):
                launch()
                self.slot_cache.settle()  # the host only waits from here on
                out = self._fetch_step(fl, sampled_here=False)
            with trace.span("batch.deliver"):
                self._settle_step(fl, out, sampled_here=False)
            return
        with reqctx.use(ctx), trace.span(
                "batch.step_issue", {**args, "kind": kind,
                                     "chained": chain is not None}):
            launch()
            fl.toks = None  # the logits are never fetched: let them go
        for slot, _req in fl.rows:
            slot.ahead += fl.budget[slot.index]
        self._inflight = fl
        _PIPELINE_DEPTH.set(1 if chain is None else 2)

    def _step_ahead(self, fl: _InflightStep) -> None:
        """With the step `fl` in flight: plan the dispatch AFTER it from
        where its rows will stand, issue that one chained from `fl`'s token
        carry, then deliver `fl`. What the plan takes for granted is host
        knowledge: which slot prefills and its chunk (`pending`), that a row
        rides while it has tokens to go (a finish by length is known a
        dispatch ahead: the row is simply absent), that a row whose prompt
        ends in `fl` rides with its first token from the carry, positions and
        block coverage from the expected lengths. Only a finish the host
        could not foresee (a `stop_check` hit, a cancel, a request-scope
        fault) leaves a row in the next dispatch that is gone at delivery:
        that row's one result is dropped there (its write sits past the
        request's frontier, the scan's free rollback) and every other row's
        stands, so nothing is flushed. Whatever the plan cannot take for
        granted (a row that needs its logits on the host, a row at the
        context's end, a slot's state the synchronous path left) is not
        planned: `fl` is delivered and the next pass dispatches from host
        state."""
        with trace.span("batch.build"):
            issued = self._runs_ahead() and self._issue_after(fl)
        self._inflight = self._inflight if issued else None
        _PIPELINE_DEPTH.set(2 if issued else 1)
        self._deliver_step(fl)
        _PIPELINE_DEPTH.set(1 if self._inflight is not None else 0)

    def _issue_after(self, fl: _InflightStep) -> bool:
        """Plan and issue the dispatch after the in-flight step `fl` (under
        `batch.build`); False where it has to wait for `fl`'s delivery."""
        s = self.spec.seq_len
        now = time.perf_counter()
        t0 = now
        mine = {slot.index: req for slot, req in fl.rows}
        prefill: list[_Slot] = []
        riders: list[_Slot] = []
        for sl in self._slots:
            req = sl.req
            if req is None:
                continue
            if req.cancelled or (req.deadline_t and now >= req.deadline_t):
                return False  # _reap_slots fires next pass: don't outrun it
            if len(sl.pending) > sl.ahead:
                prefill.append(sl)
            elif mine.get(sl.index) is not req:
                return False  # its next token is host state, not `fl`'s
            elif len(req.out) + 1 < req.max_tokens and sl.pos + sl.ahead < s:
                # else it ends by length with the token `fl` gives it
                if not self._samples_here(sl):
                    return False
                riders.append(sl)
        if not prefill and not riders:
            return False
        # no row may stand so near the context's end that a park is clamped
        # or a chunk shrunk: those edit host state the delivery still needs
        reach = max(sl.pos + sl.ahead for sl in self._slots)
        if prefill:
            victim = self._prefill_victim(prefill)
            left = len(victim.pending) - victim.ahead
            t = next((c for c in PREFILL_CHUNKS if left >= c), 1)
            if reach + t > s or (t == left
                                 and not self._samples_here(victim)):
                return False
            try:
                # the same request-scope injection point, before anything
                # of the dispatch exists (_prefill_step)
                with reqctx.use(victim.req.ctx):
                    faults.fire("batch.prefill", slot=victim.index,
                                pending=left)
                plan = self._plan_chunk(victim, riders, t0, chain=fl)
            except Exception as e:
                # a request-scope fault while the next dispatch is planned
                # (batch.prefill, a pool that cannot cover the chunk) is the
                # prefilling request's alone: `fl` stands and is delivered
                if classify(e) != "request" or victim.req is None:
                    raise
                self._fail_request(victim, e)
                return False
            if plan is None:
                return False
            self._run_step(*plan)
            return True
        with self._plock:
            waiting = bool(self._pending) or not self._queue.empty()
        if self.superstep > 1 and not waiting and max(
                sl.req.max_tokens - len(sl.req.out) - 1 for sl in riders) >= 2:
            # nobody waits for a slot: the next dispatch is the K-step scan
            # (_decode_step), which leaves from host state once `fl` is
            # delivered: one synchronous gap a step-to-scan transition. Its
            # issue takes the host 5 to 6 ms (six uploads and the scan's
            # call), and made while `fl` runs it would open `fl`'s dispatch
            # span past the middle of a 15 ms execution
            return False
        if reach + 1 > s:
            return False
        try:
            self._cover(riders, lambda sl: sl.pos + sl.ahead + 1, decline=True)
        except Exception:
            return False
        plan = self._plan_single(riders, t0, chain=fl)
        if plan is None:
            return False
        self._run_step(*plan)
        return True

    def _deliver_step(self, fl: _InflightStep) -> None:
        """Deliver an issued step under ITS OWN dispatch span (the name and
        the chunk, riders, window it was planned with): the wait for its
        results and their copy lie inside it, as a synchronous dispatch's
        do, and its program's execution mostly under it."""
        name, args = fl.span
        # the dispatch belongs to the request that prefilled in it
        with reqctx.use(fl.rows[0][1].ctx if fl.lead is not None else None), \
                trace.span(name, args):
            # the host only waits from here on: pending demotions settle
            # inside the span, as between a synchronous dispatch's launch
            # and its fetch (a reclaim's 10 to 20 MB take milliseconds, and
            # a span opened after them would miss its own execution)
            self.slot_cache.settle()
            out = self._fetch_step(fl, sampled_here=True)
        with trace.span("batch.deliver"):
            self._settle_step(fl, out, sampled_here=True)

    def _settle_step(self, fl: _InflightStep, out: np.ndarray,
                     sampled_here: bool) -> None:
        """A step's results are on the host: counters, the rows' positions
        and histories, and what each sampled row continues with. `out` is
        every row's logits (B, 1 or T, vocab), left as `last_logits` for the
        row's sampler, or (`sampled_here`) the program's own samples (B,),
        delivered now: a row that lives on holds its token as one whose KV
        is not written yet (`armed`), which the dispatch after this one, if
        it is already in flight, is writing."""
        t, lead = fl.k, fl.lead
        riders = len(fl.rows) - (lead is not None)
        t_ready = time.perf_counter()
        # sampled here, the dispatch was issued while its predecessor ran:
        # its own share of the wall time starts where that one's ended
        base = fl.t_issue
        if (sampled_here and self._last_ready_t is not None
                and self._last_ready_t > base):
            base = self._last_ready_t
        dt_ms = (t_ready - base) * 1000.0
        self._last_ready_t = self._gap_t = t_ready
        self._last_dispatch_t = time.monotonic()
        if lead is None:
            self.decode_steps += 1
            _DISP_SINGLE.observe(dt_ms / 1000.0)
            _PARKED_ROW_STEPS.inc(self.slots_n - riders)
        else:
            if riders:
                self.mixed_steps += 1
            (_DISP_MIXED if riders else _DISP_PREFILL).observe(dt_ms / 1000.0)
            _PREFILL_TOKENS.inc(t)
            # rows neither prefilling nor riding spent this dispatch parked
            _PARKED_ROW_STEPS.inc(self.slots_n - 1 - riders)
            self.prefilled_tokens += t
        self._count_work(t, fl.window,
                         [(fl.starts[s.index], fl.budget[s.index])
                          for s, _req in fl.rows], fl.starts,
                         lead=lead.index if fl.mapped else None)
        for slot, req in fl.rows:
            if slot.req is not req or req.done.is_set():
                # left its slot while the dispatch ran (reaped, preempted,
                # or stopped at the delivery before by what the host alone
                # could see): this row's result is dropped, no other's
                _PIPELINE_FLUSHES.labels(reason="row").inc()
                _ROLLBACK_TOKENS.inc(1)
                flight.event(req.rid, "rollback", tokens=1, where="step")
                continue
            if sampled_here:  # it was issued ahead: no longer in flight
                slot.ahead -= fl.budget[slot.index]
            req.stats.dispatch_ms.append(dt_ms)
            if slot is lead:
                flight.event(req.rid, "prefill_chunk", chunk=t,
                             riders=riders, ms=round(dt_ms, 3))
                slot.pos += t
                slot.history.extend(fl.piece)
                slot.pending = slot.pending[t:]
                req.stats.prefill_ms += dt_ms
                if slot.pending:
                    continue
                slot.last_token = slot.history[-1]
            else:  # decoded one token in this dispatch
                slot.history.append(slot.last_token)
                slot.pos += 1
                slot.armed = False  # the dispatch ingested last_token's KV
                req.stats.token_ms.append(dt_ms)
                req.stats.infer_ms.append(dt_ms)
            if sampled_here:
                self._take_token(slot, lambda i=slot.index: int(out[i]))
            else:
                slot.last_logits = out[slot.index,
                                       -1 if slot is lead or t == 1 else 0]
        self.slot_cache.settle_state(fl.snaps, True)

    def _decode_step(self, active: list[_Slot]) -> None:
        # bring every row to its next un-ingested token (host-samples rows at a
        # prefill/single-step boundary; consumes the device-sampled tail after
        # a super-step)
        with trace.span("batch.advance", {"rows": len(active)}):
            for slot in active[:]:
                if not self._advance_row(slot):
                    active.remove(slot)
        with trace.span("batch.build"):
            # every row's next write needs a real block behind it
            self._cover(active, lambda slot: slot.pos + 1)
            if not active:
                return
            # speculative path: draft per-row n-gram proposals; when any row
            # has a draft worth verifying, spend this dispatch on a (B, T)
            # verify block instead of the scan — one weight stream for up to
            # T tokens per row. Empty drafts fall through to the scan.
            plan = self._plan_verify(active) if self.spec_k else None
            budgets = None
            if plan is None and self.superstep > 1:
                with self._plock:
                    waiting = bool(self._pending) or not self._queue.empty()
                if not waiting:
                    # per-row step budget: stop advancing at max_tokens /
                    # context end (the row parks for the rest of the scan)
                    k = self.superstep
                    budgets = {
                        slot.index: min(k,
                                        slot.req.max_tokens - len(slot.req.out),
                                        self.spec.seq_len - slot.pos)
                        for slot in active}
                    if max(budgets.values()) < 2:
                        budgets = None
        if plan is not None:
            self._verify_step(*plan)
        elif budgets is not None:
            self._super_step(active, self.superstep, budgets)
        else:
            self._single_step(active)

    def _single_step(self, active: list[_Slot]) -> None:
        """One batched T=1 step from host state: the admission-latency (and
        tail) path."""
        t0 = time.perf_counter()
        with trace.span("batch.build"):
            plan = self._plan_single(active, t0)
        if plan is not None:
            self._run_step(*plan)

    def _batched_loop(self, k: int, mode: str, window: int | None,
                      masked: bool = False):
        """Compiled K-step batched device loop for this engine's config
        (one program per (k, mode, window-bucket), memoized). `masked`
        selects the grammar-constrained variant (constraint-table mask
        applied before sampling, automaton state in the carry) — a
        SEPARATE program keyed with a masked flag, so unconstrained
        service keeps today's exact pinned programs (perf/dlint.py
        compile manifest)."""
        # keys sort (a holder of this dict may walk it as a pytree): the whole
        # context is window 0, not None
        key = (k, mode, window or 0) + (("mask",) if masked else ())
        if key not in self._loops:
            from .device_loop import make_batched_decode_loop

            eng = self._eng
            self._loops[key] = make_batched_decode_loop(
                self.spec, eng.mesh, eng.params, k, mode=mode, dtype=eng.dtype,
                use_pallas=eng.use_pallas,
                compress_collectives=eng.compress, donate_cache=True,
                attn_window=window, moe_sharding=eng.moe_sharding,
                kv_block_tokens=self.slot_cache.block_tokens,
                paged_kernel=eng.paged_kernel,
                masked=masked, moe_stats=eng.moe_stats)
        return self._loops[key]

    def _verify_loop(self, t: int, mode: str, window: int | None,
                     masked: bool = False):
        """Compiled (B, T=t) draft-verify program for this engine's config
        (one per (t, mode, window-bucket), memoized alongside the scans).
        `masked` selects the grammar-constrained variant — target rows are
        masked position-by-position along the proposal's state chain, so a
        draft token the grammar forbids can never be accepted."""
        key = (t, mode, window or 0, "verify") + (("mask",) if masked else ())
        if key not in self._loops:
            from .device_loop import make_batched_verify_loop

            eng = self._eng
            self._loops[key] = make_batched_verify_loop(
                self.spec, eng.mesh, eng.params, t, mode=mode, dtype=eng.dtype,
                use_pallas=eng.use_pallas,
                compress_collectives=eng.compress, donate_cache=True,
                attn_window=window, moe_sharding=eng.moe_sharding,
                kv_block_tokens=self.slot_cache.block_tokens,
                paged_kernel=eng.paged_kernel,
                masked=masked)
        return self._loops[key]

    def _constrained(self, rows) -> bool:
        """True when any live row in this dispatch decodes under a
        non-degraded grammar — the masked program variants engage only
        then, so purely-unconstrained batches never pay the mask gather."""
        return any(s.constraint is not None and not s.constraint.degraded
                   for s, _req in rows)

    def _cstate_vec(self) -> np.ndarray:
        """(B,) GLOBAL constraint-table states from the host mirrors —
        uploaded when a masked dispatch is NOT chained (the chained case
        consumes the predecessor's device carry). Rows without a grammar
        ride the universal state 0. The constrain.mask fault point fires
        here per constrained row: an injected error degrades that row
        (documented fallback), latency models a slow mask fetch."""
        cs = np.zeros(self.slots_n, np.int32)
        for s in self._slots:
            sc = s.constraint
            if sc is None:
                continue
            if not sc.degraded:
                try:
                    faults.fire("constrain.mask", slot=s.index)
                except Exception:
                    self._degrade_constraint(s, "mask")
            cs[s.index] = sc.gstate
        return cs

    def _verify_block_for(self, t: int) -> int:
        """Block-length bucket (2, 3, 5, 9, 17, ... capped at 1+spec_k):
        verify programs compile per length, so raw per-dispatch lengths
        would compile O(spec_k) programs; buckets bound it to O(log k).
        Padding positions are scratch writes beyond the frontier — the same
        masked-slot discipline every over-decode already relies on."""
        return verify_block_bucket(t, 1 + self.spec_k)

    def _plan_verify(self, active: list[_Slot]):
        """Draft per-row proposals for one verify dispatch. Returns
        (active, T, drafts) or None when no row drafted spec_min_draft
        tokens (a draftless verify emits 1 token per row for a full-width
        dispatch — the K-step scan serves that regime better). Caps mirror
        the sequential loop (runtime/speculative.py): a row drafts at most
        min(k, max_tokens-room, context-room) so emitting the full accepted
        block never overruns max_tokens or the cache, and T shrinks so
        every live row's T block writes stay inside seq_len.

        Per-row draft lengths additionally follow the ADAPTIVE controller
        (docs/SERVING.md "Model-based drafting"): each row's cap is its own
        accept-EMA bucket — a chat row that accepts 2-long drafts stops
        paying for 8-wide ones, a row whose EMA collapses disengages
        entirely (k=0, re-probing on the slow-reprobe horizon) — while
        proposals come from the engine's Proposer (model drafter when
        configured and able, n-gram lookup otherwise), all rows served in
        one propose_batch call so a model drafter drafts every row in ONE
        scan dispatch."""
        s = self.spec.seq_len
        want: dict[int, int] = {}
        for slot in active:
            req = slot.req
            cap = min(self.spec_k, req.max_tokens - len(req.out) - 1,
                      s - slot.pos - 2)
            if self.adaptive is not None:
                cap = min(cap, self.adaptive.k_for(slot.index))
            want[slot.index] = cap
        drafts = self.proposer.propose_batch(
            {i: c for i, c in want.items() if c > 0})
        total = 0
        max_pos = 0
        for slot in active:
            d = drafts.setdefault(slot.index, [])
            del d[max(want[slot.index], 0):]  # never outdraft the caps
            total += len(d)
            max_pos = max(max_pos, slot.pos)
        if total < self.spec_min_draft:
            return None
        t = self._verify_block_for(1 + max(len(d) for d in drafts.values()))
        room = s - max_pos
        if t > room:
            # context-end shrink rounds DOWN to a bucket: per-length tail
            # programs (t = room, room-1, ...) would mint O(k) fresh
            # compiles exactly at the latency-critical end of long requests
            b = 2
            while b < t and 2 * (b - 1) + 1 <= room:
                b = 2 * (b - 1) + 1
            t = b if b <= room else 0
        if t < 2:
            return None
        for d in drafts.values():
            del d[t - 1:]  # context-end shrink may cut long drafts
        return active, t, drafts

    def _verify_step(self, active: list[_Slot], t: int,
                     drafts: dict[int, list[int]]) -> None:
        """One draft-verify super-step (docs/SERVING.md "Speculative
        decoding"): every active row rides a (B, T) block — its pending
        token plus its n-gram draft, padded — the device verifies all rows
        in one forward (weights stream ONCE for up to T tokens per row) and
        delivery emits each row's accepted prefix plus the correction/bonus
        token. Rejected tails sit beyond the verified frontier on masked
        slots (the free-rollback discipline); the device carry is rewound to
        the frontier so a chained scan composes for any accept outcome."""
        faults.fire("batch.verify", rows=len(active), block=t)
        with trace.span("batch.build"):
            for slot in self._cover(active, lambda slot: slot.pos + t):
                drafts.pop(slot.index, None)
            if not active:
                return
            starts = self._park_positions(t)
            # a clamp-park CoW under pool exhaustion may have reaped a row
            active = [s for s in active if s.req is not None]
            if not active:
                return
            ndraft = [-1] * self.slots_n  # -1 parks the row inside the block
            props = [[0] * t for _ in range(self.slots_n)]
            budget = [0] * self.slots_n  # per-row MAX emit (accept + correction)
            rows: list[tuple[_Slot, BatchRequest]] = []
            for slot in active:
                i = slot.index
                d = drafts.get(i, [])
                starts[i] = slot.pos
                props[i] = [slot.last_token] + d + [0] * (t - 1 - len(d))
                ndraft[i] = len(d)
                budget[i] = len(d) + 1
                rows.append((slot, slot.req))
            fl = self._issue_verify_step(rows, t, ndraft, props, budget,
                                         starts)
        self._pipeline_advance(fl)

    # hot-path
    def _issue_verify_step(self, rows: list, t: int, ndraft: list[int],
                           props: list[list[int]], budget: list[int],
                           starts: list[int]) -> _InflightStep:
        """Dispatch one (B, T) verify block asynchronously. Always uploads
        host state (a verify is never chained FROM: its proposals are
        host-drafted from delivered history), but its returned carry is
        frontier-rewound on device, so successors may chain from IT."""
        eng = self._eng
        temps = [0.0] * self.slots_n
        topps = [0.9] * self.slots_n
        rng = np.zeros((self.slots_n, 2), np.uint32)
        greedy = True
        for slot, req in rows:
            i = slot.index
            smp = req.sampler
            temps[i] = float(getattr(smp, "temperature", 0.0))
            topps[i] = float(getattr(smp, "topp", 0.9))
            greedy = greedy and temps[i] == 0.0
            state = int(getattr(smp, "state", 0)) & ((1 << 64) - 1)
            rng[i] = state >> 32, state & 0xFFFFFFFF
        mode = "greedy" if greedy else "sample"
        window = eng._window_for(min(max(starts) + t, self.spec.seq_len))
        masked = self._constrained(rows)
        loop = self._verify_loop(t, mode, window, masked)
        window = window or self.spec.seq_len
        self._observe_gap()
        t_issue = time.perf_counter()
        snaps, work = self.slot_cache.state_word(rows, starts, budget)
        kc_in, vc_in = eng.k_cache, eng.v_cache  # same stale-epoch discipline
        tables, _resent = self.slot_cache.table()
        constrain = None
        if masked:
            # a verify is never chained FROM, so its constraint states come
            # from the fully-delivered host mirrors — same as the rng
            # a mask fault inside _cstate_vec degrades that row to the
            # universal state 0 — the masked program then passes its logits
            # through untouched, so the dispatch itself stays valid
            cmask, cdelta = self.constrain_table.device()
            constrain = (_upload(self._cstate_vec(), jnp.int32), cmask,
                         cdelta)
            _CONSTRAIN_DISPATCHES.inc()
        with trace.span("batch.verify_issue",
                        {"block": t, "rows": len(rows),
                         "drafted": sum(max(n, 0) for n in ndraft),
                         "window": window}):
            # the block's host inputs, uploaded HERE so that the copies are
            # counted, in the dtypes device_loop's run() states: run() keeps
            # its own jnp.asarray for the callers that hand it host values
            # (bench.py, tests/), a no-op on these
            host = (_upload(starts, jnp.int32), _upload(rng, jnp.uint32),
                    _upload(temps, jnp.float32), _upload(topps, jnp.float32),
                    _upload(ndraft, jnp.int32), tables)
            props = _upload(props, jnp.int32)
            if masked:
                def call():
                    toks, acc, tok, pos, rng_out, kc, vc, cst = loop(
                        eng.params, eng.rope, props, kc_in, vc_in, *host,
                        constrain=constrain)
                    return toks, acc, tok, pos, rng_out, kc, vc, cst

                (toks, acc, tok, pos, rng_out, eng.k_cache,
                 eng.v_cache, cst) = self._dispatched("verify", call)
            else:
                def call():
                    toks, acc, tok, pos, rng_out, kc, vc = loop(
                        eng.params, eng.rope, props, kc_in, vc_in, *host)
                    return toks, acc, tok, pos, rng_out, kc, vc

                (toks, acc, tok, pos, rng_out, eng.k_cache,
                 eng.v_cache) = self._dispatched("verify", call)
                cst = None
        _PIPELINE_DEPTH.set(1)
        start_host_copy(toks, acc, rng_out)
        return _InflightStep(rows, t, starts, budget, temps, toks, tok, pos,
                             rng_out, t_issue, False, window, kind="verify",
                             ndraft=ndraft, acc=acc, cstate=cst)

    def _drafts_ready(self, rows: list) -> bool:
        """Cheap probe: would a verify dispatch have material to work with?
        Consulted by the accept-aware chain policy BEFORE the in-flight
        block delivers, so it sees the pre-block corpus — advisory only
        (a model drafter counts as ready whenever its row can run: it
        always drafts k tokens, that is the point of it)."""
        for slot, _req in rows:
            k = (self.adaptive.k_for(slot.index)
                 if self.adaptive is not None else self.spec_k)
            if k > 0 and self.proposer.ready(slot.index, k,
                                             self.spec_min_draft):
                return True
        return False

    def _super_step(self, active: list[_Slot], k: int,
                    budgets: dict[int, int]) -> None:
        """One K-step fused dispatch from host state: every active row decodes
        up to its budget on device (sampling included), then the returned
        (K, B) block is delivered host-side with EOS/stop/max checks per
        token. A row that stops mid-block keeps its position at the verified
        frontier — the over-decoded rows beyond it sit on masked slots and
        are overwritten by the slot's next real writes (free rollback). With
        pipelining, the NEXT super-step is chained from this one's device
        carry before delivery starts (_pipeline_advance)."""
        with trace.span("batch.build"):
            self._cover(active, lambda slot: slot.pos + budgets[slot.index])
            if not active:
                return
            starts = self._park_positions(1)
            # a clamp-park CoW under pool exhaustion may have reaped a row
            active = [s for s in active if s.req is not None]
            if not active:
                return
            budget = [0] * self.slots_n
            rows: list[tuple[_Slot, BatchRequest]] = []
            for slot in active:
                starts[slot.index] = slot.pos
                budget[slot.index] = budgets[slot.index]
                rows.append((slot, slot.req))
            fl = self._issue_super_step(rows, k, budget, starts)
        self._pipeline_advance(fl)

    def _pipeline_advance(self, fl: _InflightStep) -> None:
        """Drive one pipeline turn: optionally issue the super-step AFTER
        `fl` chained from its device-resident carry (so the device never
        idles through the host delivery loop below), then deliver `fl` and
        validate the speculation — a chained dispatch survives only when
        every row it decodes delivered its full budget and stayed live."""
        nxt = None
        plan = None
        with trace.span("batch.build"):
            if self.pipeline and not self._shutdown and not self._draining:
                plan = self._plan_chain(fl)
            if plan is not None:
                with self._plock:
                    waiting = bool(self._pending) or not self._queue.empty()
                if waiting or any(s.req and s.pending for s in self._slots):
                    # a request needs the next dispatch for admission/
                    # prefill: break the chain instead of extending it — the
                    # pipelined analog of the K -> 1 admission-latency drop
                    _PIPELINE_FLUSHES.labels(reason="admission").inc()
                    plan = None
            if plan is not None:
                # the chained dispatch's speculative writes need block
                # coverage (and clamped parks need exclusive blocks) BEFORE
                # issue; a pool that cannot serve declines the chain instead
                # of failing rows
                rows, starts, budget, clamp = plan
                try:
                    self._cover(
                        [slot for slot, _req in rows], lambda slot:
                        starts[slot.index] + budget[slot.index], decline=True)
                    for slot in clamp:
                        self.slot_cache.own(slot, self.spec.seq_len - 1,
                                            self.spec.seq_len)
                except Exception:
                    _PIPELINE_FLUSHES.labels(reason="pool").inc()
                    plan = None
            if plan is not None:
                rows, starts, budget, clamp = plan
                for slot in clamp:
                    # the chained scan parks this row clamped at seq_len-1,
                    # destroying that history row — flag it before fl's
                    # delivery so a mid-delivery _finish harvests the
                    # truncated prefix
                    slot.clamp_pos = self.spec.seq_len - 1
                nxt = self._issue_super_step(rows, self.superstep, budget,
                                             starts, chain=fl)
        self.slot_cache.settle()  # the host waits for `fl` from here on
        with trace.span("batch.deliver"):
            try:
                status = self._deliver_super_step(fl)
            except BaseException:
                if nxt is not None:
                    # delivery failed with the chained dispatch still a
                    # local: account for it here — _fail_all only sees
                    # self._inflight
                    _PIPELINE_FLUSHES.labels(reason="error").inc()
                _PIPELINE_DEPTH.set(0)
                raise
            if nxt is not None:
                reason = self._chain_divergence(nxt, status)
                if reason is not None:
                    self._flush_inflight(nxt, reason)
                else:
                    self._inflight = nxt
            _PIPELINE_DEPTH.set(1 if self._inflight is not None else 0)

    def _plan_chain(self, fl: _InflightStep):  # hot-path
        """Speculative schedule for the scan super-step after `fl`, assuming
        `fl` delivers every budgeted token: same rows, re-derived budgets
        from the expected positions/output lengths. Returns (rows, starts,
        budget, clamp_slots), or None when no row would decode >= 2 steps
        (the single-step / admission path takes over), a reap is imminent,
        or the ACCEPT-AWARE policy declines (docs/SERVING.md "Speculative
        decoding"): while the engine's accept EMA is at/above
        spec_chain_expect, the next dispatch should be a host-drafted verify
        block (which cannot chain — its proposals need delivered tokens),
        not a K-step scan that would dilute it to ~1 token per step-cost.

        A verify predecessor is planned against FULL acceptance — the
        maximal positions/output lengths — so the derived budgets are sound
        for ANY actual accept: the chained scan consumes the device carry,
        which the verify loop rewound to the true frontier, and a row that
        accepted less simply decodes with a conservative budget. Only a row
        that FINISHED mid-verify (stop/length/cancel) flushes the chain,
        exactly like the scan-after-scan divergence rule."""
        k = self.superstep
        s = self.spec.seq_len
        now = time.perf_counter()
        if fl.kind == "verify":
            if self._spec_ema >= self.spec_chain_expect:
                _PIPELINE_FLUSHES.labels(reason="spec").inc()
                return None
            gain = [nd + 1 if nd >= 0 else 0 for nd in fl.ndraft]
        elif (self.spec_k and self._spec_ema >= self.spec_chain_expect
              and self._drafts_ready(fl.rows)):
            # extending the scan chain would outrun the verify those
            # drafts are ready for — break it (flush reason "spec")
            _PIPELINE_FLUSHES.labels(reason="spec").inc()
            return None
        else:
            gain = fl.budget
        starts = [st + g for st, g in zip(fl.starts, gain)]
        budget = [0] * self.slots_n
        rows: list[tuple[_Slot, BatchRequest]] = []
        clamp: list[_Slot] = []
        for slot, req in fl.rows:
            i = slot.index
            if req.cancelled or (req.deadline_t and now >= req.deadline_t):
                return None  # _reap_slots fires next pass: don't outrun it
            exp_out = len(req.out) + gain[i]
            b = min(k, req.max_tokens - exp_out, s - starts[i])
            if b > 0:
                budget[i] = b
                rows.append((slot, req))
            elif starts[i] >= s:
                clamp.append(slot)
        if not rows or max(budget) < 2:
            return None
        return rows, starts, budget, clamp

    # hot-path
    def _issue_super_step(self, rows: list, k: int, budget: list[int],
                          starts: list[int],
                          chain: _InflightStep | None = None) -> _InflightStep:
        """Dispatch one K-step batched decode WITHOUT waiting for results
        (async device dispatch: the call returns future arrays). chain=None
        uploads host state — slot last_token/pos plus each sampler's
        xorshift* state — exactly like the unpipelined super-step did;
        chain=<predecessor> feeds that dispatch's device-resident (last_tok,
        pos, rng) carry straight back in, no host round trip, with
        `starts`/`budget` the caller's speculative schedule."""
        eng = self._eng
        temps = [0.0] * self.slots_n
        topps = [0.9] * self.slots_n
        tokens = [0] * self.slots_n
        rng = np.zeros((self.slots_n, 2), np.uint32)
        greedy = True
        for slot, req in rows:
            i = slot.index
            smp = req.sampler
            temps[i] = float(getattr(smp, "temperature", 0.0))
            topps[i] = float(getattr(smp, "topp", 0.9))
            greedy = greedy and temps[i] == 0.0
            if chain is None:
                tokens[i] = slot.last_token
                state = int(getattr(smp, "state", 0)) & ((1 << 64) - 1)
                rng[i] = state >> 32, state & 0xFFFFFFFF
        mode = "greedy" if greedy else "sample"
        window = eng._window_for(min(max(st + max(b, 1)
                                         for st, b in zip(starts, budget)),
                                     self.spec.seq_len))
        masked = self._constrained(rows)
        loop = self._batched_loop(k, mode, window, masked)
        window = window or self.spec.seq_len
        if chain is None:
            tok_in, pos_in, rng_in = tokens, starts, rng
            self._observe_gap()
        else:
            tok_in, pos_in, rng_in = chain.tok, chain.pos, chain.rng
            _DISPATCH_GAP.observe(0.0)  # chained: the device never went idle
        t_issue = time.perf_counter()
        snaps, work = self.slot_cache.state_word(rows, starts, budget)
        kc_in, vc_in = eng.k_cache, eng.v_cache  # same stale-epoch discipline
        tables, _resent = self.slot_cache.table()
        constrain = None
        if masked:
            # constraint carry: a chained dispatch consumes the
            # predecessor's device-resident states (same rule as tok/rng);
            # an unchained one uploads the host mirrors. A predecessor
            # issued masked always carries cstate — _constrained() is
            # deterministic in the (identical) row set, so the chain never
            # crosses the masked/unmasked program boundary.
            cmask, cdelta = self.constrain_table.device()
            cin = (chain.cstate if chain is not None and chain.cstate
                   is not None else _upload(self._cstate_vec(), jnp.int32))
            constrain = (cin, cmask, cdelta)
            _CONSTRAIN_DISPATCHES.inc()
        with trace.span("batch.super_step_issue",
                        {"k": k, "rows": len(rows),
                         "chained": chain is not None, "window": window,
                         **work}):
            # the scan's host inputs, uploaded HERE so that the copies are
            # counted, in the dtypes device_loop's run() states (run() keeps
            # its own jnp.asarray for callers with host values, a no-op on
            # these); a chained one's tokens, positions and rng are on the
            # device
            # a carry that comes from the host is placed as a returned carry
            # is (replicated over the mesh), so that a scan from host state
            # and one chained from a scan are ONE executable (rows sharded
            # over dp keep their two)
            if chain is None:
                up = functools.partial(
                    _upload, sharding=self._carry_sharding if eng.dp == 1
                    else None)
                tok_in, pos_in, rng_in = (up(tok_in, jnp.int32),
                                          up(pos_in, jnp.int32),
                                          up(rng_in, jnp.uint32))
            host = (pos_in, rng_in, _upload(temps, jnp.float32),
                    _upload(topps, jnp.float32), _upload(budget, jnp.int32),
                    tables)
            if masked:
                def call():
                    return loop(
                        eng.params, eng.rope, tok_in, kc_in, vc_in, *host,
                        constrain=constrain)

                (toks, tok, pos, rng_out, eng.k_cache,
                 eng.v_cache, cst, *moe) = self._dispatched("super_step", call)
            else:
                def call():
                    return loop(
                        eng.params, eng.rope, tok_in, kc_in, vc_in, *host)

                (toks, tok, pos, rng_out, eng.k_cache,
                 eng.v_cache, *moe) = self._dispatched("super_step", call)
                cst = None
        _PIPELINE_DEPTH.set(2 if chain is not None else 1)
        start_host_copy(toks, rng_out)  # delivery's np.asarray picks them up
        fl = _InflightStep(rows, k, starts, budget, temps, toks, tok, pos,
                           rng_out, t_issue, chain is not None, window,
                           cstate=cst, moe=moe[0] if moe else None)
        fl.snaps = snaps
        return fl

    # hot-path
    def _deliver_super_step(self, fl: _InflightStep) -> dict[int, str]:
        """Host-side delivery of an issued super-step: block on the (K, B)
        token transfer, then per row run EOS/stop/max checks, emit tokens,
        and resync the sampler RNG (full delivery adopts the device state;
        partial delivery replays exactly the delivered coins — bit-exact
        either way). Returns per-slot-index outcomes — "alive" (full budget
        delivered, request still decoding) or the finish reason — the
        validity oracle for a dispatch chained from this one's carry."""
        k = fl.k
        s = self.spec.seq_len
        epoch = getattr(self._tls, "epoch", self._epoch)
        with trace.span("batch.super_step", {"k": k, "rows": len(fl.rows),
                                             "tokens": sum(fl.budget),
                                             "kind": fl.kind,
                                             "chained": fl.chained,
                                             "window": fl.window}):
            toks = np.asarray(fl.toks)  # dlint: ignore[hot-sync] -- THE delivery fence: one (K,B) block transfer per super-step is the design (1 sync per K tokens)
            rng_out = np.asarray(fl.rng)  # dlint: ignore[hot-sync] -- rides the same fence; copy_to_host_async at issue makes this a pickup, not a stall
            acc = np.asarray(fl.acc) if fl.kind == "verify" else None  # dlint: ignore[hot-sync] -- same fence (verify accept lengths)
            _D2H_BYTES.inc(toks.nbytes + rng_out.nbytes
                           + (0 if acc is None else acc.nbytes))
        if self._epoch != epoch:
            # a hung transfer is the other place a wedged thread blocks; an
            # abandoned thread waking here must not deliver into slots that
            # now belong to the replacement epoch's requests
            raise _StaleEpoch()
        t_ready = time.perf_counter()
        self._last_dispatch_t = time.monotonic()
        # device-span estimate: the device could not start this dispatch
        # before it was issued, nor before the previous dispatch's results
        # were ready. Under overlap the issue->ready wall includes the time
        # spent queued behind the predecessor — which the host used for the
        # predecessor's delivery loop; that hidden slice is overlap_ms.
        base = fl.t_issue
        if self._last_ready_t is not None and self._last_ready_t > base:
            base = self._last_ready_t
        dev_ms = max((t_ready - base) * 1000.0, 1e-6)
        overlap_ms = (base - fl.t_issue) * 1000.0
        self._last_ready_t = t_ready
        self._gap_t = t_ready
        self.decode_steps += 1
        if fl.kind == "verify":
            self.verify_steps += 1
            _SPEC_VERIFY_STEPS.inc()
            _DISP_VERIFY.observe(dev_ms / 1000.0)
        else:
            self.super_steps += 1
            _DISP_SUPER.observe(dev_ms / 1000.0)
        _SUPERSTEP_TOKENS.observe(sum(fl.budget))
        # rows that ride the scan without a live request park for all k steps;
        # rows with a short budget park for the steps past it
        _PARKED_ROW_STEPS.inc(self.slots_n * k - sum(fl.budget))
        self._count_inflight(fl, [(fl.starts[slot.index],
                                   fl.budget[slot.index])
                                  for slot, _req in fl.rows])
        status: dict[int, str] = {}
        accs: list[int] = []  # per-row accepted lengths (verify EMA input)
        for slot, req in fl.rows:
            i = slot.index
            b = fl.budget[i]
            if fl.kind == "verify":
                # actual emit: accepted drafts + the correction/bonus token
                # (fl.budget holds the maximum, ndraft+1)
                b = int(acc[i]) + 1
            if slot.req is not req or req.done.is_set():
                # reaped (cancel/deadline/close) between issue and delivery:
                # the block was decoded past a frontier that no longer exists
                _ROLLBACK_TOKENS.inc(b)
                flight.event(req.rid, "rollback", tokens=b, where="reaped")
                status[i] = "cancelled"
                continue
            if not self._advance_row(slot):
                # chained dispatch: consume the PREVIOUS block's tail token
                # (the device already fed it; this mirrors _decode_step's
                # pre-issue advance). A cancel observed here lands the row in
                # _finish and discards its block.
                _ROLLBACK_TOKENS.inc(b)
                status[i] = req.finish
                continue
            if fl.kind == "scan" and b < k and fl.starts[i] + b >= s:
                # the scan parked this row mid-block clamped at s-1, whose
                # scratch writes destroyed that history row — record it BEFORE
                # delivery: reaching pos == s finishes the request inside the
                # loop below, and that _finish's harvest must not commit the
                # poisoned row (_harvest_into_cache consumes clamp_pos)
                # (verify blocks never clamp a live row: _plan_verify shrinks
                # T so every live row's block fits under seq_len)
                slot.clamp_pos = s - 1
                flight.event(req.rid, "park_clamped", pos=s - 1)
            if fl.kind == "verify":
                # per-request speculation accounting, recorded BEFORE the
                # emit loop so spec_turns keys on the pre-block output length
                # (the accept-length oracle in tests/test_batched_spec.py)
                nd = fl.ndraft[i]
                a = b - 1
                accs.append(a)
                # per-row adaptation + per-proposer attribution: a drafting
                # row's EMA follows its accept; a row that rode draftless
                # ticks toward re-probe (docs/SERVING.md "Model-based
                # drafting")
                if self.adaptive is not None:
                    self.adaptive.observe(i, nd, a)
                self.proposer.observe(i, a)
                req.stats.spec_steps += 1
                req.stats.spec_drafted += nd
                req.stats.spec_accepted += a
                req.stats.spec_turns.append((len(req.out), nd, a))
                req.stats.spec_step_ms.append(dev_ms)
                _SPEC_DRAFTED.inc(nd)
                _SPEC_ACCEPTED.inc(a)
                flight.event(req.rid, "verify_step", block=k, drafted=nd,
                             accepted=a)
            block = toks[:b, i].tolist()
            smp = req.sampler
            state0 = int(getattr(smp, "state", 0))
            per_tok = dev_ms / b
            # measured decode TPOT (ms/token, decayed) — the signal the
            # slo_tpot_interactive admission gate reads: when delivered
            # pace is already past the interactive target, new batch-class
            # admissions are refused before they widen the dispatches
            self._tpot_ema_ms += 0.2 * (per_tok - self._tpot_ema_ms)
            req.stats.dispatch_ms.append(dev_ms)
            req.stats.overlap_ms.append(overlap_ms)
            x = slot.last_token  # ingested input of the block's first step
            slot.armed = False  # the scan ingested last_token's KV
            alive = True
            delivered = 0  # block tokens actually handed to the request
            try:
                for tok in block:
                    if req.cancelled:
                        self._finish(slot, "cancelled")
                        alive = False
                        break
                    slot.history.append(x)
                    slot.pos += 1  # pos counts ingestions through this token's input
                    req.stats.token_ms.append(per_tok)
                    req.stats.infer_ms.append(per_tok)
                    delivered += 1
                    if not self._emit(slot, tok):
                        alive = False
                        break
                    x = tok
            except Exception as e:
                # broken sampler/on_token/stop_check (or an injected
                # batch.emit fault): this request alone dies; the other rows'
                # blocks deliver normally (blast-radius isolation)
                _ENGINE_ERRORS.labels(kind="request").inc()
                req.error = e
                self._finish(slot, "error")
                alive = False
            if delivered < b:
                # frontier rewind: the device decoded b tokens for this row but
                # the host delivered fewer (stop/cancel/error mid-block) — the
                # tail sits on masked slots and is discarded
                _ROLLBACK_TOKENS.inc(b - delivered)
                flight.event(req.rid, "rollback", tokens=b - delivered,
                             where="mid_block")
            if fl.temps[i] != 0.0 and hasattr(smp, "state"):
                # resync the host sampler to the coins actually DELIVERED, not
                # the full budget the device drew: a stop/cancel mid-block
                # discards the tail, and the sequential stream never draws for
                # discarded tokens (a caller-owned sampler reused across
                # requests must see one unbroken sequence). For a fully
                # delivered block this equals the device's returned state —
                # which a chained successor is already carrying forward.
                if alive and delivered == b:
                    smp.state = np.uint64((int(rng_out[i, 0]) << 32)
                                          | int(rng_out[i, 1]))
                else:
                    from .sampler import _random_u32

                    s64 = np.uint64(state0)
                    for _ in range(delivered):
                        s64, _ = _random_u32(s64)
                    smp.state = s64
            if alive:
                # block fully delivered; its tail is sampled but not ingested
                slot.next_token = block[-1]
                slot.last_logits = None
            if slot.clamp_pos is not None:
                # row did not finish mid-loop (the harvest consumes clamp_pos
                # when it did): apply the clamp truncation here — mirror of
                # the _park_positions clamp, incl. the lease shrink
                self.slot_cache.truncate(slot, slot.clamp_pos)
                slot.clamp_pos = None
            # per-row timeline + trace attribution: one super_step entry per
            # request it advanced, and (tracing on) a per-row instant bound
            # to the request's context so the event carries ITS trace id —
            # the cross-thread re-entry that makes one shared dispatch
            # attributable per request in the merged fleet trace
            flight.event(req.rid, "super_step", k=k, budget=b,
                         delivered=delivered, chained=fl.chained)
            if trace.current() is not None:
                with reqctx.use(req.ctx):
                    trace.instant("batch.row_delivered",
                                  {"slot": i, "delivered": delivered,
                                   "k": k})
            status[i] = "alive" if alive else req.finish
        if fl.kind == "verify":
            if accs:
                # accept EMA drives the chain policy: high expected accept →
                # back-to-back verifies; low → chained scans keep overlap
                self._spec_ema = (0.7 * self._spec_ema
                                  + 0.3 * (sum(accs) / len(accs)))
            if _SPEC_DRAFTED.value > 0:
                _SPEC_ACCEPT_RATE.set(_SPEC_ACCEPTED.value
                                      / _SPEC_DRAFTED.value)
        elif self.spec_k:
            # slow regression toward optimism while scans run: a decayed EMA
            # must not disengage speculation FOREVER (verifies are the only
            # signal that raises it) — after ~a dozen scans the policy
            # re-probes with one verify and re-learns the true accept rate,
            # bounding the waste on hopeless workloads to one wide dispatch
            # per dozen scans while phase changes (output turning repetitive
            # mid-stream) are picked up within the same horizon
            self._spec_ema += 0.05 * (self.spec_k - self._spec_ema)
            if self.adaptive is not None:
                # the same slow-reprobe policy PER ROW: a scan turn passed
                # without these rows drafting
                for slot, _req in fl.rows:
                    self.adaptive.tick(slot.index)
        self.slot_cache.settle_state(fl.snaps, True)
        return status

    def _chain_divergence(self, nxt: _InflightStep,
                          status: dict[int, str]) -> str | None:
        """None when every row the chained dispatch decodes matched the
        speculated schedule (predecessor delivered its full budget and the
        request is still live); otherwise the flush reason."""
        for slot, _req in nxt.rows:
            st = status.get(slot.index, "cancelled")
            if st != "alive":
                return {"stop": "stop", "cancelled": "cancel",
                        "error": "error"}.get(st, "finish")
        return None

    def _flush_inflight(self, fl: _InflightStep, reason: str) -> None:
        """Discard a chained dispatch whose speculated schedule diverged from
        what its predecessor actually delivered. The rollback is free: every
        write the flushed scan makes lands at or beyond its row's committed
        frontier (masked scratch, overwritten by the slot's next real
        writes), context-end parks were flagged via clamp_pos at issue, and
        the next dispatch re-uploads tokens/positions/RNG from host state —
        which delivery kept bit-exact (the xorshift* stream never advances
        for discarded tokens). What is NOT free is put back here: a
        state-space model's running matrices, and the snapshot entries the
        flushed scan was given."""
        _PIPELINE_FLUSHES.labels(reason=reason).inc()
        _ROLLBACK_TOKENS.inc(sum(fl.budget))
        self._count_inflight(fl, [])  # ran on the device for nothing
        self.slot_cache.settle_state(fl.snaps, False, scan=fl.kind == "scan")
        for slot, req in fl.rows:
            flight.event(req.rid, "pipeline_flush", reason=reason,
                         tokens=fl.budget[slot.index])
