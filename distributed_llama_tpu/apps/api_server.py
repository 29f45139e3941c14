"""OpenAI-compatible HTTP API server.

TPU-native counterpart of src/apps/dllama-api/dllama-api.cpp: `POST /v1/chat/completions`
(streaming SSE via chunked transfer + non-streaming JSON), `GET /v1/models`, per-request
temperature/seed/max_tokens/stop overrides (dllama-api.cpp:351-380), and prefix KV reuse
through the shared-prefix cache subsystem (cache/, docs/PREFIX_CACHE.md), which subsumes
the reference's NaiveCache (dllama-api.cpp:187-232): the engine keeps the previous
conversation's KV and rewinds `pos` over the longest common token prefix, AND prefixes
harvested from past conversations are radix-indexed in a block pool, so returning to a
displaced conversation (or sharing its system prompt) seeds the cache instead of
re-prefilling.

With `--batch 1` (default) requests serialize behind a generation lock — the reference
is likewise a single-request-at-a-time accept loop (dllama-api.cpp:418-429). With
`--batch N` the server runs a continuous-batching scheduler (runtime/batch_engine.py):
up to N requests decode concurrently in one batched SPMD step, a capability the
reference lacks (its runtime has no batch dimension at all, funcs.cpp:424).
"""

from __future__ import annotations

import json
import threading
import time
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlsplit

from ..obs import flight, metrics, reqctx, trace
from ..obs.process import install_process_metrics
from ..ops import matmul as matmul_ops
from ..platform_env import describe, xla_compiles
from ..resilience import faults
from ..resilience.errors import (DeadlineExceeded, EngineClosed,
                                 EngineDraining, EngineSaturated,
                                 EngineWedged, InvalidRequest, QuotaExceeded,
                                 retriable)
from ..resilience.tenancy import (CLASSES, DEFAULT_TENANT, TenantRegistry,
                                  sanitize_tenant)
from ..resilience.quiet_http import QuietServer
from ..runtime.engine import Engine
from ..runtime.sampler import Sampler
from ..tokenizer import ChatItem, ChatTemplate, EosDetector, TemplateType
from ..tokenizer.eos import TokenStreamer

# Per-request serving latencies (docs/OBSERVABILITY.md). TTFT is request
# arrival to the first text delta (the user-visible number: prefill + queue
# wait + first decode); TPOT the mean inter-token time after it; E2E the
# whole completion.
_TTFT = metrics.histogram(
    "api_request_ttft_seconds", "Request arrival to first streamed text delta")
_TPOT = metrics.histogram(
    "api_request_tpot_seconds",
    "Mean per-token time after the first token, per request")
_E2E = metrics.histogram(
    "api_request_e2e_seconds", "Request arrival to completion")
_HTTP = metrics.counter(
    "api_http_requests_total", "HTTP requests by route and status code",
    labelnames=("route", "code"))
# Durable-request resume admissions (docs/FLEET.md "Resume protocol"): how
# many mid-stream-failover re-submits this replica served, how much resumed
# generation they carried, and how much of each resume's prompt ⊕ delivered
# prefix the admission reused instead of re-prefilling (the "resume cost ≈
# one suffix prefill" health signal a chaos bench asserts is nonzero).
_RESUMED = metrics.counter(
    "api_resumed_requests_total",
    "Completions admitted with a resume payload (router failover re-submits)")
_RESUME_TOKENS = metrics.counter(
    "api_resume_tokens_total",
    "Delivered-elsewhere tokens carried by resume payloads (RNG coins "
    "fast-forwarded; tokens re-fed through the stop detector)")
_RESUME_PREFIX = metrics.counter(
    "api_resume_prefix_tokens_total",
    "Total prompt ⊕ delivered prefix length of resume admissions")
_RESUME_REUSED = metrics.counter(
    "api_resume_reused_tokens_total",
    "Resume prefix tokens whose prefill was skipped (slot rewind + radix "
    "prefix-cache seed) at resume admission")

_KNOWN_ROUTES = ("/v1/chat/completions", "/chat/completions", "/v1/models",
                 "/v1/stats", "/metrics", "/health", "/healthz",
                 "/v1/requests", "/v1/trace", "/v1/kv")

# Prefill-replica side of the disaggregation transfer (docs/DISAGG.md):
# /v1/kv prefill-only admissions and the chunked block export they feed.
_KV_PREFILLS = metrics.counter(
    "disagg_prefill_requests_total",
    "POST /v1/kv prefill-only admissions by outcome (ok, empty = prompt "
    "shorter than one full block, error)", labelnames=("outcome",))
_KV_EXPORT_BLOCKS = metrics.counter(
    "disagg_export_blocks_total",
    "KV blocks served to decode replicas over GET /v1/kv/<id>")
_KV_EXPORT_BYTES = metrics.counter(
    "disagg_export_bytes_total",
    "Wire bytes served to decode replicas (post-codec payload)")

def _class_from(body: dict) -> str:
    """Scheduling class from the body's `"class"` field (an X-Class header
    is folded into the body by do_POST before this runs; body wins).
    Unlabeled traffic is interactive — the safe default for
    latency-sensitive clients; garbage is a 400, never a silent guess."""
    raw = str(body.get("class") or "interactive").strip().lower()
    if raw not in CLASSES:
        raise InvalidRequest(
            f"'class' must be one of {CLASSES}, got {raw!r}")
    return raw


def _count_http(path: str, code: int) -> None:
    # unknown paths collapse to one label value so scrapes stay bounded;
    # per-request flight lookups collapse to their route prefix
    path = path.split("?", 1)[0]
    if path.startswith("/v1/requests/"):
        path = "/v1/requests"
    if path.startswith("/v1/kv/"):
        path = "/v1/kv"  # per-transfer chunk fetches share one label value
    route = path if path in _KNOWN_ROUTES else "other"
    _HTTP.labels(route=route, code=str(code)).inc()


def model_config_hash(spec) -> str:
    """Stable short hash of the model configuration — the replica-identity
    field fleet routers compare to catch a replica serving a different model
    than the rest of the fleet (docs/FLEET.md). Hashes the ModelSpec fields
    (enums stringified), not the weights: it identifies the config."""
    import dataclasses
    import hashlib

    d = {f.name: str(getattr(spec, f.name))
         for f in dataclasses.fields(spec)}
    return hashlib.sha1(json.dumps(d, sort_keys=True).encode()).hexdigest()[:12]


class ApiState:
    def __init__(self, engine: Engine, template_type: TemplateType,
                 default_sampler: Sampler, device_loop_chunk: int = 0,
                 batch_engine=None, speculative_k: int = 0,
                 prefix_cache=True, prefix_cache_blocks: int = 0,
                 prefix_block_tokens: int = 16, prefix_cache_q80: bool = False,
                 request_deadline: float = 0.0,
                 tenants: TenantRegistry | None = None,
                 role: str = "both", kv_wire_q80: bool = False,
                 kv_transfer_ttl: float = 120.0, kv_transfer_cap: int = 32):
        self.engine = engine
        # disaggregation (docs/DISAGG.md): the role this replica ADVERTISES
        # in its healthz load block (routing preference only — the engine
        # serves anything), the wire mode for KV exports, and the bounded
        # TTL'd table of host-snapshot transfers GET /v1/kv/<id> serves
        from ..fleet.disagg import ROLES, KVTransferTable

        if role not in ROLES:
            raise ValueError(f"role must be one of {ROLES}, got {role!r}")
        self.role = role
        self.kv_wire_q80 = kv_wire_q80
        self.kv_transfers = (KVTransferTable(cap=kv_transfer_cap,
                                             ttl=kv_transfer_ttl)
                             if batch_engine is not None else None)
        # multi-tenant policy (docs/SERVING.md "Multi-tenant serving"): the
        # registry the X-Tenant mapping resolves against. With a batch
        # engine the SAME object is the engine's quota/fairness authority
        # (enforced at submit); the --batch 1 path enforces the quota here.
        self.tenants = tenants
        # replica identity (docs/FLEET.md): set to host:port once the server
        # socket binds (serve()); what the router's membership poller reads
        self.replica_id = ""
        self.started_mono = time.monotonic()  # /healthz uptime_s
        self.batch_engine = batch_engine  # BatchEngine when --batch > 1, else None
        self.lock = threading.Lock()
        # graceful drain (docs/ROBUSTNESS.md): set by begin_drain/SIGTERM —
        # /healthz flips to 503 "draining", new completions are refused with
        # EngineDraining (503), in-flight requests finish
        self.draining = False
        # server-side wall-clock deadline applied to every batched request
        # (seconds; 0 = none) — the scheduler enforces it, finish "deadline"
        self.request_deadline = request_deadline
        # hung-engine supervisor (resilience/supervisor.py): set by serve()
        # when --supervisor-threshold > 0; /healthz folds its health in so
        # a wedged replica is ejected from fleet rotation while it recovers
        self.supervisor = None
        # single-slot prefix reuse (cache/single_slot.py, ex-NaiveCache): the
        # resident-conversation rewind plus the cross-conversation radix pool.
        # Batched mode needs neither — slot assignment and prefix reuse live
        # in the BatchEngine scheduler (which owns its own PrefixCache).
        self.cache = None
        if engine is not None:
            from ..cache import SingleSlotCache, make_prefix_cache

            pc = None
            if not engine.paged:
                pc = make_prefix_cache(
                    engine.k_cache.shape, engine.k_cache.dtype.itemsize,
                    slots=1, prefix_cache=prefix_cache,
                    blocks=prefix_cache_blocks,
                    block_tokens=prefix_block_tokens, q80=prefix_cache_q80)
            self.cache = SingleSlotCache(engine, pc)
        tok = (batch_engine or engine).tokenizer
        self.template = ChatTemplate(template_type, tok.chat_template, tok.eos_piece())
        self.default_sampler = default_sampler
        self.device_loop_chunk = device_loop_chunk
        self.speculative_k = speculative_k
        # constrained decoding (docs/SERVING.md "Constrained decoding"):
        # per-token byte pieces the grammar compiler lowers against,
        # resolved lazily on the first response_format request
        self.constrain_vocab: list[bytes] | None = None
        self.model_name = "distributed-llama-tpu"
        # the block the start-up line printed, served by /healthz and
        # /v1/stats: a replica on the wrong device says so to whoever polls
        # it. Static for the life of the process.
        inner = batch_engine._eng if batch_engine is not None else engine
        self.device = describe(inner.dtype, inner.use_pallas)
        xla_compiles.install()  # /v1/stats "compile" (no-op after start())


def _now() -> int:
    return int(time.time())


def _completion_payload(state: ApiState, text: str, finish: str,
                        rid: str | None = None) -> dict:
    # `rid` is the serving request id (the flight-recorder key): reusing it
    # as the completion id makes GET /v1/requests/<id> reachable straight
    # from the client-visible response
    return {
        "id": rid or f"chatcmpl-{uuid.uuid4().hex[:12]}",
        "object": "chat.completion",
        "created": _now(),
        "model": state.model_name,
        "choices": [{
            "index": 0,
            "message": {"role": "assistant", "content": text},
            "finish_reason": finish,
        }],
    }


def _chunk_payload(state: ApiState, completion_id: str, delta: dict,
                   finish: str | None) -> dict:
    # one id across all chunks of a completion, per the OpenAI streaming contract
    return {
        "id": completion_id,
        "object": "chat.completion.chunk",
        "created": _now(),
        "model": state.model_name,
        "choices": [{"index": 0, "delta": delta, "finish_reason": finish}],
    }


def _load_block(state: "ApiState") -> dict:
    """Replica identity + load block served inside /healthz and /v1/stats —
    what a fleet router's membership poller consumes (fleet/membership.py):
    who this replica is (id, model config hash) and how loaded it is (slot
    count, free slots, queue depth, draining). Cheap: no device work."""
    be = state.batch_engine
    if be is not None:
        load = be.load_stats()
        draining = state.draining or be.draining
    else:
        # single-engine mode: one slot, "free" == the generation lock is
        # not held; there is no queue (requests serialize on the lock)
        locked = state.lock.locked()
        load = {"slots": 1, "free_slots": 0 if locked else 1,
                "queue_depth": 0}
        draining = state.draining
    spec = (be or state.engine).spec
    import os

    return {"id": state.replica_id, "model": state.model_name,
            "model_hash": model_config_hash(spec),
            # disaggregation role (docs/DISAGG.md): what role-aware routers
            # key on; role-less payloads read as "both" on their side
            "role": state.role,
            "batched": be is not None, "draining": bool(draining),
            # process identity/health for the fleet poller: pid matches the
            # replica's trace export, uptime catches restart loops
            "pid": os.getpid(),
            "uptime_s": round(time.monotonic() - state.started_mono, 1),
            **load}


def _stats_payload(state: "ApiState") -> dict:
    """GET /v1/stats: one JSON snapshot of every metric plus scheduler/engine
    state — the same numbers as /metrics, shaped for humans and scripts
    rather than a Prometheus scraper."""
    out: dict = {"model": state.model_name, "time": _now(),
                 "replica": _load_block(state),
                 "device": state.device,
                 "compile": xla_compiles.snapshot(),
                 "metrics": metrics.snapshot()}
    if state.supervisor is not None:
        out["supervisor"] = state.supervisor.stats()
    if state.kv_transfers is not None:
        out["disagg"] = {"role": state.role,
                         "kv_wire": "q80" if state.kv_wire_q80 else "raw",
                         "transfers": state.kv_transfers.stats()}
    if state.tenants is not None:
        out["tenants"] = state.tenants.stats()
    be = state.batch_engine
    pc = (be.prefix_cache if be is not None
          else state.cache.cache if state.cache is not None else None)
    if pc is not None:
        out["prefix_cache"] = pc.stats()
    if be is not None:
        out["batch_engine"] = {
            "slots": be.slots_n, "superstep": be.superstep,
            "pipeline": be.pipeline,
            "prefilled_tokens": be.prefilled_tokens,
            "decode_steps": be.decode_steps,
            "super_steps": be.super_steps,
            "mixed_steps": be.mixed_steps,
            "occupied": sum(1 for s in be._slots if s.req is not None),
            "scheduler_alive": be.scheduler_alive(),
            "draining": be.draining,
            "max_queue": be.max_queue,
            "queue_ttl": be.queue_ttl,
        }
        if be.kv_pool is not None:  # device-resident paged KV state
            out["batch_engine"]["paged_kv"] = dict(
                be.kv_pool.stats(), seed_bytes=be.seed_bytes,
                seed_ms=round(be.seed_ms, 3))
        spec_block = be.spec_stats()
        if spec_block is not None:
            # engine accept counters + proposer (model drafter health /
            # degradation) + per-row adaptive-k breakdown
            # (docs/SERVING.md "Model-based drafting")
            out["speculative"] = spec_block
        # constrained decoding (docs/SERVING.md "Constrained decoding"):
        # edge compile-cache health + engine table occupancy/degradations
        from ..constrain import compile_stats

        out["constrain"] = dict(be.constrain_stats(),
                                compile=compile_stats())
    elif state.engine is not None:
        eng = state.engine
        out["engine"] = {"pos": eng.pos, "tp": eng.tp, "sp": eng.sp,
                         "paged": eng.paged,
                         "seq_len": eng.spec.seq_len}
    # kernel-selection provenance (ops/matmul.py registry, docs/SERVING.md
    # "Kernel selection"): the resolved matmul policy and which lowering each
    # traced dispatch shape actually took — the human-readable view of
    # matmul_kernel_selected_total, and the place a silent xla-fallback
    # becomes visible without grepping Prometheus
    inner = be._eng if be is not None else state.engine
    if inner is not None:
        out["kernels"] = {"policy": str(inner.use_pallas),
                          # paged-attention reader of the device block pool:
                          # the Pallas kernel, or the XLA gather
                          "paged_kernel": bool(inner.paged_kernel),
                          "selections": matmul_ops.kernel_selections()}
    return out


def _opt(body: dict, key: str, default):
    """Request override with OpenAI null semantics: explicit null == unset."""
    v = body.get(key)
    return default if v is None else v


def _observe_done(t_start: float, ttft: list, n_tokens: int,
                  finish: str | None = None) -> None:
    dt = time.perf_counter() - t_start
    _E2E.observe(dt)
    tpot = None
    if ttft[0] is not None and n_tokens > 1:
        tpot = (dt - ttft[0]) / (n_tokens - 1)
        _TPOT.observe(tpot)
    # complete the flight-recorder timeline with the request-level numbers
    # only the HTTP layer knows (rid resolves from the bound trace context)
    flight.finish(
        None, finish,
        ttft_ms=round(ttft[0] * 1e3, 3) if ttft[0] is not None else None,
        tpot_ms=round(tpot * 1e3, 3) if tpot is not None else None,
        e2e_ms=round(dt * 1e3, 3), tokens=n_tokens)


def _parse_resume(body: dict, spec) -> list[int]:
    """Validate the durable-resume payload (docs/FLEET.md "Resume protocol"):
    `{"resume": {"tokens": [...]}}` — the generated tokens a failed replica
    already delivered, which this replica must treat as committed output:
    prefill them (mostly a prefix-cache hit), fast-forward the sampler past
    their coins, re-feed them through the stop detector (so a stop sequence
    spanning the failover boundary still fires), and continue generation
    byte-identical to the uninterrupted run."""
    raw = body.get("resume")
    if raw is None:
        return []
    if not isinstance(raw, dict) or not isinstance(raw.get("tokens"), list):
        raise InvalidRequest("'resume' must be {\"tokens\": [int, ...]}")
    toks = raw["tokens"]
    if not all(isinstance(t, int) and not isinstance(t, bool)
               and 0 <= t < spec.vocab_size for t in toks):
        raise InvalidRequest(
            f"'resume.tokens' must be token ids in [0, {spec.vocab_size})")
    return list(toks)


def _parse_response_format(state: "ApiState", body: dict, runner):
    """Validate + compile `response_format` at the edge (docs/SERVING.md
    "Constrained decoding") — BEFORE any queue work, so a malformed or
    unsupported grammar is an honest 400 invalid_request_error, never a
    stalled slot. Returns (TokenAutomaton, grammar_hash) or (None, "").

    Accepted forms (grammar source under its own key, OpenAI-style
    `{"json_schema": {"schema": {...}}}` nesting also honored):

      {"type": "json_schema", "json_schema": {...}}
      {"type": "regex",       "regex": "..."}
      {"type": "grammar",     "grammar": "root ::= ..."}
      {"type": "text"}   (explicit no-op)

    Compiles are LRU-cached by grammar hash (constrain/compiler.py), so a
    templated schema pays DFA construction once per process."""
    rf = body.get("response_format")
    if rf is None:
        return None, ""
    if not isinstance(rf, dict) or not isinstance(rf.get("type"), str):
        raise InvalidRequest(
            "'response_format' must be an object with a string 'type' "
            "(json_schema | regex | grammar | text)")
    kind = rf["type"]
    if kind == "text":
        return None, ""
    if kind not in ("json_schema", "regex", "grammar"):
        raise InvalidRequest(
            f"unsupported response_format type {kind!r} "
            "(want json_schema | regex | grammar | text)")
    if state.batch_engine is None:
        raise InvalidRequest(
            "response_format requires the batched engine (--batch >= 2); "
            "this server runs the sequential engine")
    tok = runner.tokenizer
    if tok is None:
        raise InvalidRequest(
            "response_format requires a tokenizer (token-level grammar "
            "masks are compiled against the served vocab)")
    source = rf.get(kind)
    if kind == "json_schema" and isinstance(source, dict) \
            and "schema" in source:
        source = source["schema"]  # OpenAI response_format nesting
    if source is None:
        raise InvalidRequest(
            f"response_format type {kind!r} needs the grammar under the "
            f"{kind!r} key")
    from ..constrain import CompileError, compile_grammar, vocab_bytes

    if state.constrain_vocab is None:
        state.constrain_vocab = vocab_bytes(tok)
    eos = getattr(tok, "chat_eos_id", None) or tok.eos_id
    try:
        aut, ghash = compile_grammar(kind, source, state.constrain_vocab,
                                     eos)
    except CompileError as e:
        raise InvalidRequest(f"invalid response_format: {e}") from None
    flight.event(None, "constrain_compiled", kind=kind, grammar=ghash,
                 states=aut.n_states)
    return aut, ghash


def run_completion(state: ApiState, body: dict, emit, *, journal=None,
                   deadline_s: float | None = None):
    """Shared completion core. `emit(text_delta)` streams; returns (text, finish).

    `journal` (durable routing, docs/FLEET.md): a mutable {"toks": [], "n": 0}
    the caller owns — every text delta's newly-flushed token ids are appended
    (and "n" advanced to the cumulative delivered count) BEFORE emit runs, so
    the streaming layer can stamp them onto the same SSE chunk as the text
    they produced. `deadline_s` is the remaining client deadline relayed via
    X-Deadline-Ms (min-combined with the server's --request-deadline).

    Raises typed resilience errors BEFORE any generation work so the HTTP
    layer can map them to honest status codes (InvalidRequest -> 400,
    EngineDraining/EngineSaturated -> 503, DeadlineExceeded -> 408)."""
    # the replica ctx lets a fault plan target ONE replica of an in-process
    # fleet (match={"replica": id}) — e.g. the gray-failure family's
    # sustained-latency injection (docs/ROBUSTNESS.md "Gray failures")
    faults.fire("api.request", replica=state.replica_id)
    if state.draining:
        raise EngineDraining("server is draining (shutting down)")
    rc = reqctx.current()
    # multi-tenant identity (docs/SERVING.md "Multi-tenant serving"): the
    # tenant rode in on the bound trace context (do_POST's X-Tenant
    # mapping); the class is a request option. Both raise 400 on garbage.
    tenant = (rc.tenant if rc is not None and rc.tenant else DEFAULT_TENANT)
    klass = _class_from(body)
    if rc is not None:
        # open the flight-recorder timeline at the HTTP boundary (the
        # BatchEngine enriches the same record from the scheduler side)
        flight.start(rc.request_id, rc.trace_id, replica=state.replica_id,
                     stream=bool(body.get("stream", False)),
                     **{"tenant": tenant, "class": klass})
    t_start = time.perf_counter()
    ttft: list = [None]
    user_emit = emit

    def emit(text):
        if ttft[0] is None:
            ttft[0] = time.perf_counter() - t_start
            _TTFT.observe(ttft[0])
        user_emit(text)

    runner = state.batch_engine or state.engine
    tok = runner.tokenizer
    spec = runner.spec
    messages = [ChatItem(m.get("role", "user"), m.get("content", ""))
                for m in body.get("messages", [])]
    rendered = state.template.generate(messages)
    prompt = tok.encode(rendered, add_bos=True)

    # request validation (docs/ROBUSTNESS.md): caller errors must be 400s,
    # never a 500 or a stall. A prompt at/over seq_len has no room to decode
    # even one token; max_tokens must be a non-negative integer (explicit 0 /
    # null keep the fill-the-context default, OpenAI null semantics).
    resume = _parse_resume(body, spec)
    if len(prompt) >= spec.seq_len:
        raise InvalidRequest(
            f"prompt is {len(prompt)} tokens but the model context is "
            f"{spec.seq_len}; reduce the conversation or raise --max-seq-len")
    if len(prompt) + len(resume) > spec.seq_len:
        # strictly MORE than the context could ever have generated: a
        # malformed payload, not a legitimate resume. == seq_len is the
        # legitimate edge — the original run ended at the context wall
        # after its last delivered token, so the resume re-emits the
        # delivered text and finishes "length" with zero new tokens.
        raise InvalidRequest(
            f"resume carries {len(resume)} tokens but the context has room "
            f"for {spec.seq_len - len(prompt)} past the prompt")
    mt_raw = _opt(body, "max_tokens", 0)
    if isinstance(mt_raw, bool) or not isinstance(mt_raw, int) or mt_raw < 0:
        raise InvalidRequest(
            f"'max_tokens' must be a non-negative integer, got {mt_raw!r}")
    # grammar compile at the edge (docs/SERVING.md "Constrained decoding"):
    # malformed/unsupported grammars 400 here, before any queue work; the
    # engine receives a ready automaton and never needs tokenizer bytes
    constraint, constraint_hash = _parse_response_format(state, body, runner)
    # disaggregated admission (docs/DISAGG.md): a router-injected kv_source
    # descriptor means a prefill replica already computed this prompt's KV —
    # pull the blocks into the prefix cache BEFORE admission so the radix
    # lookup remaps/seeds them instead of re-prefilling. Every failure mode
    # (dead prefill replica, truncated wire, mixed tokenizers) returns 0 and
    # the request admits with a plain local prefill: zero client impact.
    imported = 0
    ks = body.get("kv_source")
    if isinstance(ks, dict) and state.batch_engine is not None:
        from ..fleet.disagg import import_kv_source

        imported = import_kv_source(state.batch_engine, prompt, ks)
        if imported:
            flight.event(None, "kv_imported", tokens=imported)
    sampler = Sampler(
        spec.vocab_size,
        float(_opt(body, "temperature", state.default_sampler.temperature)),
        float(_opt(body, "top_p", state.default_sampler.topp)),
        int(_opt(body, "seed", _now())),
    )
    # the TOTAL budget is derived from the ORIGINAL prompt so a resumed
    # request stops at exactly the position the uninterrupted run would
    # have; the delivered tokens already spent part of it, and the context
    # wall caps it (a resume at the wall legitimately has zero budget)
    max_tokens = max(min((mt_raw or (spec.seq_len - len(prompt)))
                         - len(resume),
                         spec.seq_len - len(prompt) - len(resume)), 0)
    if resume:
        # the RNG half of byte-identical resume: every stochastic sample
        # drew exactly one xorshift* coin, greedy drew none — skip the
        # delivered tokens' coins so the continuation replays the
        # uninterrupted run's stream (runtime/sampler.py)
        sampler.fast_forward(len(resume))
        _RESUMED.inc()
        _RESUME_TOKENS.inc(len(resume))
        _RESUME_PREFIX.inc(len(prompt) + len(resume))
        flight.event(None, "resume_admitted", tokens=len(resume))
    # remaining-deadline propagation (docs/FLEET.md): the header-relayed
    # client deadline and the server-side --request-deadline compose by min
    # — a resumed request must never outlive the deadline the client set
    deadlines = [d for d in (state.request_deadline, deadline_s) if d]
    eff_deadline = min(deadlines) if deadlines else 0.0
    if state.batch_engine is None and state.tenants is not None:
        # --batch 1 (no scheduler to enforce policy): debit the tenant's
        # quota here — QuotaExceeded maps to 429 + Retry-After. The batched
        # path leaves enforcement to BatchEngine.submit (same registry
        # object; charging at both layers would double-bill every request).
        state.tenants.acquire(tenant, float(len(prompt) + max(max_tokens, 1)))

    stops = tok.chat_stops()
    stop_param = _opt(body, "stop", [])
    if isinstance(stop_param, str):  # OpenAI allows string-or-array
        stop_param = [stop_param]
    stops.extend(s.encode() for s in stop_param)
    detector = EosDetector(tok.chat_eos_id, stops, padding_left=2, padding_right=2)

    pieces: list[str] = []
    finish = ["length"]

    if state.batch_engine is not None:
        # continuous batching: slot assignment + per-slot prefix reuse live in the
        # BatchEngine scheduler; no server-side lock or pos bookkeeping. Socket writes
        # are decoupled from the scheduler thread through a queue — a slow client
        # backpressures only its own handler thread, never the shared decode loop.
        import queue as _queue

        deltas: "_queue.Queue[tuple | None]" = _queue.Queue()
        # token ids delivered since the last text flush: on_token appends on
        # the scheduler thread, and the streamer's synchronous emit drains
        # them into the SAME queue entry as the text they produced — the
        # token/text pairing the durable router's journal rides on
        pending_toks: list[int] = []

        def emit_queued(d: bytes):
            text = d.decode("utf-8", errors="replace")
            pieces.append(text)
            toks, pending_toks[:] = pending_toks[:], []
            deltas.put((text, toks))

        qstreamer = TokenStreamer(detector, lambda t: tok.decode_piece(0, t),
                                  emit_queued)

        def on_token(t: int):
            pending_toks.append(t)
            qstreamer.on_token(t)

        # resume re-feed (docs/FLEET.md): run the delivered tokens through
        # the SAME streamer before generation — their text re-emits (the
        # router splices by position, the client never sees a repeat) and
        # the stop detector ends up in the exact mid-stream state the failed
        # replica's was, so a stop sequence spanning the failover boundary
        # still fires
        for t in resume:
            if qstreamer.stopped:
                break
            on_token(t)
        req = None
        # a resume with zero remaining budget (the original run ended at
        # its token/context limit right after the last delivered token)
        # needs NO engine work: the re-fed text is the full completion
        if not qstreamer.stopped and not (resume and max_tokens == 0):
            req = state.batch_engine.submit(
                prompt + resume, max_tokens, sampler, on_token=on_token,
                stop_check=qstreamer.stop_check,
                deadline=eff_deadline or None,
                resume_tokens=len(resume), tenant=tenant, klass=klass,
                constraint=constraint, constraint_hash=constraint_hash)
            # sentinel closes the drain loop the moment the request completes
            # (the puts happen-before done.set(), so everything queued is
            # drained first)
            threading.Thread(target=lambda: (req.done.wait(),
                                             deltas.put(None)),
                             daemon=True).start()
        else:
            deltas.put(None)
        try:
            while (item := deltas.get()) is not None:
                text, toks = item
                if journal is not None:
                    journal["toks"].extend(toks)
                    journal["n"] += len(toks)
                emit(text)
        except Exception:
            # client went away mid-stream: free the slot instead of decoding the
            # abandoned request to max_tokens
            if req is not None:
                req.cancel()
            raise
        if req is not None and req.error is not None:
            raise req.error
        if qstreamer.stopped:
            finish[0] = "stop"
        elif req is not None and req.finish == "deadline":
            # deadline expired mid-generation WITH partial output: deliver
            # what exists, finish_reason says why it stopped early
            finish[0] = "deadline"
        gen_tokens = req.stats.generated_tokens if req is not None else 0
        if resume and req is not None:
            _RESUME_REUSED.inc(req.stats.reused_tokens)
        if imported and req is not None and req.error is None:
            # shipped-span accounting (docs/DISAGG.md): reuse must cover the
            # imported span minus the mandatory last-token inference; any
            # shortfall is a re-prefill of KV that crossed the wire for
            # nothing (the mixed-context bench asserts the sum stays 0)
            from ..fleet.disagg import note_reprefill

            note_reprefill(min(imported, len(prompt) - 1),
                           req.stats.reused_tokens)
        _observe_done(t_start, ttft, gen_tokens, finish[0])
        return "".join(pieces), finish[0]

    engine = state.engine
    jpending: list[int] = []  # tokens since the last flush (journal pairing)

    def emit_bytes(d: bytes):
        text = d.decode("utf-8", errors="replace")
        pieces.append(text)
        if journal is not None:
            journal["toks"].extend(jpending)
            journal["n"] += len(jpending)
        jpending.clear()
        emit(text)

    streamer = TokenStreamer(detector, lambda t: tok.decode_piece(0, t), emit_bytes)

    def on_token(t: int):
        jpending.append(t)
        streamer.on_token(t)

    # single-engine counterpart of the scheduler-enforced deadline: checked
    # per decoded token via stop_check, finish reason "deadline", partial
    # output delivered (granularity one token vs the scheduler's ~one
    # dispatch; generation time only — the do_POST lock wait precedes
    # t_start in this mode). eff_deadline folds in the X-Deadline-Ms
    # remaining-budget header a durable router relays across resumes.
    deadline_t = t_start + eff_deadline if eff_deadline else None

    def stop_or_deadline(t):
        if streamer.stop_check(t):
            return True
        if deadline_t is not None and time.perf_counter() >= deadline_t:
            finish[0] = "deadline"
            return True
        return False

    # resume re-feed: same contract as the batched path — delivered tokens
    # re-emit their text and arm the stop detector's cross-boundary state
    for t in resume:
        if streamer.stopped:
            break
        on_token(t)
    prompt_full = prompt + resume
    if streamer.stopped:
        _observe_done(t_start, ttft, 0, "stop")
        return "".join(pieces), "stop"
    if resume and max_tokens == 0:
        # original run ended at its limit right after the last delivered
        # token: the re-fed text IS the completion — no engine work
        _observe_done(t_start, ttft, 0, "length")
        return "".join(pieces), "length"

    # Prefix reuse (cache/single_slot.py): rewind pos over the resident
    # conversation's common prefix (for paged engines, begin() also restores
    # the hot ring from the host store via Engine.seek) and/or seed cache rows
    # from the cross-conversation block pool — prefill covers only the rest.
    # A resumed request reuses against prompt ⊕ delivered: the prompt half is
    # usually cached, so resume cost ≈ one delivered-suffix prefill.
    reuse = state.cache.begin(prompt_full)
    delta_prompt = prompt_full[reuse:]
    if resume:
        _RESUME_REUSED.inc(reuse)

    try:
        out, _stats = engine.generate_with(delta_prompt, max_tokens, sampler,
                                           on_token=on_token,
                                           stop_check=stop_or_deadline,
                                           device_loop_chunk=state.device_loop_chunk,
                                           speculative_k=state.speculative_k,
                                           # full conversation (incl. the reused
                                           # prefix) for the n-gram proposer —
                                           # delta_prompt alone would starve
                                           # prompt-lookup of exactly the
                                           # repetitive history it draws from
                                           history_tokens=prompt_full)
    except Exception:
        # KV may hold a half-written new conversation; drop the reuse index entirely
        state.cache.invalidate()
        raise
    if streamer.stopped:
        finish[0] = "stop"
    # only tokens whose KV was actually written are reusable (a final stop token is
    # sampled but never inferred, so engine.pos may be one short of prompt+out)
    state.cache.end((prompt_full + out)[: engine.pos])
    _observe_done(t_start, ttft, len(out), finish[0])
    return "".join(pieces), finish[0]


def _flight_error(rid: str, e: Exception) -> None:
    """Complete (or discard) the flight record of a failed completion.
    Admission sheds (saturated/draining/closed 503s) and caller errors
    (ValueError covers InvalidRequest and template/encode failures — the
    400 class) are DROPPED: both arrive at client-request rate, and
    finishing each one would flood --slow-log and churn every real
    timeline out of the ring exactly when the recorder matters most.
    Server-side failures (500s, deadline expiries) stay exemplars."""
    if isinstance(e, (EngineSaturated, EngineClosed, QuotaExceeded,
                      ValueError)):
        flight.drop(rid)
    else:
        flight.finish(rid, None, error=str(e))


def _map_error(e: Exception) -> tuple[int, str, float | None]:
    """Typed resilience error -> (status, OpenAI error type, Retry-After).

    InvalidRequest subclasses ValueError, so the isinstance order matters:
    the specific mappings come first and a bare ValueError (template/encode
    failures on caller input) stays a 400."""
    if isinstance(e, QuotaExceeded):
        # the tenant's own token bucket, not server load: 429, and the
        # Retry-After comes from the bucket's refill arithmetic
        return 429, "rate_limit_error", getattr(e, "retry_after", 1.0)
    if isinstance(e, EngineSaturated):
        return 503, "overloaded_error", getattr(e, "retry_after", 1.0)
    if isinstance(e, EngineWedged):
        # the supervisor failed this request while recovering a hung engine:
        # retriable by contract — a durable router resumes it elsewhere, a
        # plain client may simply retry after the recovery window
        return 503, "server_wedged", 1.0
    if isinstance(e, EngineClosed):  # covers EngineDraining
        return 503, "server_shutting_down", None
    if isinstance(e, DeadlineExceeded):
        return 408, "timeout_error", None
    if isinstance(e, ValueError):  # covers InvalidRequest
        return 400, "invalid_request_error", None
    return 500, "server_error", None


class Handler(BaseHTTPRequestHandler):
    state: ApiState  # injected

    def log_message(self, fmt, *args):  # quieter logs, reference prints per request
        print(f"🔷 {self.command} {self.path}")

    def _raw(self, code: int, content_type: str, data: bytes,
             extra_headers: dict | None = None):
        self.send_response(code)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(data)))
        for k, v in (extra_headers or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(data)
        _count_http(self.path, code)

    def _json(self, code: int, payload: dict,
              extra_headers: dict | None = None):
        self._raw(code, "application/json", json.dumps(payload).encode(),
                  extra_headers)

    def _error(self, code: int, message: str, etype: str,
               retry_after: float | None = None,
               extra_headers: dict | None = None):
        """OpenAI-style error body: {"error": {"message", "type"}} — clients
        built against the OpenAI SDK parse this shape, not bare strings.
        Load-shed 503s carry Retry-After so clients back off instead of
        hammering a saturated queue."""
        hdrs = dict(extra_headers or {})
        if retry_after is not None:
            hdrs["Retry-After"] = str(max(int(retry_after + 0.5), 1))
        self._json(code, {"error": {"message": message, "type": etype}},
                   hdrs or None)

    def _mapped_error(self, e: Exception, rid: str | None = None):
        # errored requests are the flight recorder's PRIMARY exemplars:
        # the error response must reveal the lookup key (X-Request-Id)
        # or the operator can never reach GET /v1/requests/<id> for it
        code, etype, retry_after = _map_error(e)
        hdrs = ({"X-Request-Id": rid, "X-Replica": self._replica_addr()}
                if rid else None)
        self._error(code, str(e), etype, retry_after, hdrs)

    def _replica_addr(self) -> str:
        """Routable replica address for the X-Replica header. A server bound
        to 0.0.0.0 would advertise an unroutable wildcard; the address the
        CLIENT actually connected to (this connection's local sockname) is
        reachable by that client by construction."""
        rid = self.state.replica_id
        if not rid.startswith("0.0.0.0:"):
            return rid
        try:
            host, port = self.connection.getsockname()[:2]
            return f"{host}:{port}"
        except (OSError, ValueError):
            return rid

    def do_GET(self):
        if self.path == "/v1/models":
            self._json(200, {"object": "list", "data": [
                {"id": self.state.model_name, "object": "model",
                 "created": _now(), "owned_by": "user"}]})
        elif self.path in ("/health", "/healthz"):
            # load-balancer probe: cheap, no device work. 200 while serving;
            # 503 "draining" once SIGTERM/begin_drain flips the state (the
            # LB stops routing while in-flight requests finish) and 503
            # "unhealthy" when the batch scheduler thread died.
            be = self.state.batch_engine
            alive = be is None or be.scheduler_alive()
            sup = self.state.supervisor
            # identity+load for routers, and the device this replica is on
            who = {"replica": _load_block(self.state),
                   "device": self.state.device}
            if self.state.draining or (be is not None and be.draining):
                self._json(503, {"status": "draining", **who})
            elif not alive:
                self._json(503, {"status": "unhealthy",
                                 "reason": "scheduler thread dead", **who})
            elif sup is not None and not sup.healthy:
                # the supervisor caught a wedged engine: stay out of fleet
                # rotation for the recovery window (or permanently, state
                # "failed") so the router resumes this replica's journaled
                # requests elsewhere (docs/ROBUSTNESS.md)
                self._json(503, {"status": "unhealthy",
                                 "reason": f"supervisor: engine {sup.state}",
                                 **who})
            else:
                self._json(200, {"status": "ok", **who})
        elif self.path == "/metrics":
            self._raw(200, "text/plain; version=0.0.4; charset=utf-8",
                      metrics.render().encode())
        elif self.path == "/v1/stats":
            self._json(200, _stats_payload(self.state))
        elif self.path.split("?", 1)[0] == "/v1/requests" \
                or self.path.startswith("/v1/requests/"):
            self._get_requests()
        elif self.path.startswith("/v1/kv/"):
            self._get_kv()
        elif self.path == "/v1/trace":
            # this replica's live Chrome trace (the fleet router's /v1/trace
            # pulls these from every replica and merges them)
            t = trace.current()
            if t is None:
                self._error(404, "tracing is not enabled on this replica "
                            "(start with --trace)", "invalid_request_error")
            else:
                self._json(200, t.to_chrome_trace())
        else:
            self._error(404, f"Unknown route: {self.path}", "invalid_request_error")

    def _get_requests(self):
        """GET /v1/requests[?slowest=K] | /v1/requests/<id>: the flight
        recorder's per-request timelines (docs/OBSERVABILITY.md)."""
        rec = flight.current()
        if rec is None:
            self._error(404, "flight recorder is not enabled",
                        "invalid_request_error")
            return
        parts = urlsplit(self.path)
        if parts.path.startswith("/v1/requests/"):
            key = parts.path[len("/v1/requests/"):]
            r = rec.get(key)
            if r is None:
                self._error(404, f"no flight record for {key!r} (ring keeps "
                            f"the last {rec.capacity} completed requests)",
                            "invalid_request_error")
            else:
                self._json(200, r)
            return
        qs = parse_qs(parts.query)
        try:
            slowest = int(qs.get("slowest", ["0"])[0])
        except ValueError:
            self._error(400, "'slowest' must be an integer",
                        "invalid_request_error")
            return
        tenant = qs.get("tenant", [None])[0]  # per-tenant filter
        self._json(200, rec.requests(slowest=slowest, tenant=tenant))

    def _post_kv(self):
        """POST /v1/kv (docs/DISAGG.md): prefill-only admission for the
        disaggregation transfer. Tokenizes the messages like a completion,
        runs the prefill through the batch scheduler (one throwaway greedy
        token — the decode replica generates from token zero with ITS
        sampler), and registers the host-snapshot blocks in the transfer
        table. The response is the descriptor the router injects as
        ``kv_source``; n_blocks 0 tells the planner the prompt was too
        short to ship (it routes monolithic)."""
        state = self.state
        try:
            length = int(self.headers.get("Content-Length", 0))
            body = json.loads(self.rfile.read(length) or b"{}")
            if not isinstance(body.get("messages"), list) \
                    or not body["messages"]:
                raise ValueError("'messages' must be a non-empty array")
        except (ValueError, json.JSONDecodeError):
            self._error(400, "Request body is not valid JSON with a "
                        "non-empty 'messages' array", "invalid_request_error")
            return
        be = state.batch_engine
        if be is None or state.kv_transfers is None:
            self._error(501, "KV transfer requires a batched engine "
                        "(--batch > 1)", "invalid_request_error")
            return
        try:
            faults.fire("disagg.prefill")
            if state.draining:
                raise EngineDraining("server is draining (shutting down)")
            tok = be.tokenizer
            messages = [ChatItem(m.get("role", "user"), m.get("content", ""))
                        for m in body["messages"] if isinstance(m, dict)]
            prompt = tok.encode(state.template.generate(messages),
                                add_bos=True)
            if len(prompt) >= be.spec.seq_len:
                raise InvalidRequest(
                    f"prompt is {len(prompt)} tokens but the model context "
                    f"is {be.spec.seq_len}")
            # tenant/class relayed by the planner (docs/DISAGG.md): the
            # remote prefill is charged to the REQUESTING tenant at its
            # real class — a batch tenant's split prefills must not jump
            # the prefill replica's queue as anonymous interactive work
            tenant = sanitize_tenant(self.headers.get("X-Tenant"))
            klass = str(self.headers.get("X-Class")
                        or "interactive").strip().lower()
            if klass not in CLASSES:
                klass = "interactive"
            req = be.submit(prompt, 1,
                            Sampler(be.spec.vocab_size, 0.0, 0.9, 0),
                            export_kv=True, tenant=tenant, klass=klass)
            req.wait(timeout=300)
        except Exception as e:
            _KV_PREFILLS.labels(outcome="error").inc()
            self._mapped_error(e)
            return
        exp = req.kv_export
        if not exp or not exp[1]:
            _KV_PREFILLS.labels(outcome="empty").inc()
            self._json(200, {"xfer_id": None, "n_tokens": 0, "n_blocks": 0})
            return
        tokens, blocks, bt = exp
        desc = state.kv_transfers.open(
            tokens, blocks, bt, "q80" if state.kv_wire_q80 else "raw")
        _KV_PREFILLS.labels(outcome="ok").inc()
        self._json(200, desc)

    def _get_kv(self):
        """GET /v1/kv/<xfer_id>?from=F&n=N (docs/DISAGG.md): serve wire-
        encoded blocks [F, F+N) of a registered transfer. Every range is an
        independent request against the host snapshot, so a decode replica
        resumes a broken transfer by simply re-fetching the range — and an
        expired/unknown id is an honest 404 its fallback handles."""
        state = self.state
        parts = urlsplit(self.path)
        xfer_id = parts.path[len("/v1/kv/"):]
        t = (state.kv_transfers.get(xfer_id)
             if state.kv_transfers is not None else None)
        if t is None:
            self._error(404, f"no KV transfer {xfer_id!r} (unknown or "
                        "expired)", "invalid_request_error")
            return
        qs = parse_qs(parts.query)
        try:
            frm = int(qs.get("from", ["0"])[0])
            n = int(qs.get("n", [str(len(t.blocks) - max(frm, 0))])[0])
        except ValueError:
            self._error(400, "'from' and 'n' must be integers",
                        "invalid_request_error")
            return
        if frm < 0 or n < 0 or frm + n > len(t.blocks):
            self._error(400, f"range [{frm}, {frm + n}) outside "
                        f"[0, {len(t.blocks)})", "invalid_request_error")
            return
        try:
            faults.fire("disagg.export", xfer=xfer_id)
            from ..cache.wire import encode_blocks

            payload = encode_blocks(t.blocks[frm:frm + n],
                                    q80=state.kv_wire_q80)
        except Exception as e:
            self._error(500, f"export failed: {e}", "server_error")
            return
        _KV_EXPORT_BLOCKS.inc(n)
        _KV_EXPORT_BYTES.inc(len(payload))
        # a range covering the final block marks the transfer consumed —
        # its table slot frees after a short retry grace instead of the
        # full TTL (capped table, docs/DISAGG.md)
        state.kv_transfers.note_served(t, frm, n)
        self._raw(200, "application/octet-stream", payload,
                  {"X-KV-From": str(frm), "X-KV-Count": str(n)})

    def do_POST(self):
        if self.path == "/v1/kv":
            self._post_kv()
            return
        if self.path not in ("/v1/chat/completions", "/chat/completions"):
            self._error(404, f"Unknown route: {self.path}", "invalid_request_error")
            return
        try:
            length = int(self.headers.get("Content-Length", 0))
            body = json.loads(self.rfile.read(length) or b"{}")
        except (ValueError, json.JSONDecodeError):
            self._error(400, "Request body is not valid JSON",
                        "invalid_request_error")
            return
        if not isinstance(body.get("messages"), list) or not body["messages"]:
            self._error(400, "'messages' must be a non-empty array",
                        "invalid_request_error")
            return
        stream = bool(body.get("stream", False))
        state = self.state
        # remaining client deadline (docs/FLEET.md): a durable router relays
        # the ORIGINAL X-Deadline-Ms minus elapsed time across every retry
        # and resume, so the request can never silently outlive the budget
        # the client set; an already-expired budget is an immediate 408
        deadline_s = None
        hdr = self.headers.get("X-Deadline-Ms")
        if hdr is not None:
            try:
                v = float(hdr)
                if v != v or v in (float("inf"), float("-inf")):
                    raise ValueError(hdr)  # NaN/inf pass <=0 checks below
                deadline_s = max(v, 0.0) / 1000.0
            except ValueError:
                self._error(400, "X-Deadline-Ms must be a finite number "
                            "(ms)", "invalid_request_error")
                return
            if deadline_s <= 0.0:
                self._error(408, "client deadline already expired",
                            "timeout_error")
                return
        # durable journal mode (docs/FLEET.md "Resume protocol"): the router
        # asks for token ids alongside each SSE text delta so its journal
        # can re-submit the request mid-stream; OpenAI clients ignore the
        # extra field, and it is absent without the header
        jstate = ({"toks": [], "n": 0}
                  if self.headers.get("X-Dllama-Journal") else None)
        # request identity (docs/OBSERVABILITY.md "Request tracing"): adopt
        # the inbound W3C traceparent (the fleet router stamps one on every
        # proxied hop; any W3C-speaking client works too) or originate a
        # trace here; the completion id doubles as the flight-recorder key
        rid = f"chatcmpl-{uuid.uuid4().hex[:12]}"
        # tenant identity (docs/SERVING.md "Multi-tenant serving"): the
        # X-Tenant header (relayed by the fleet router on every proxy try
        # and durable resume) rides the request context into the engine's
        # quota/fairness accounting and the flight-recorder timeline; an
        # X-Class header composes with the body's "class" field (body wins)
        ctx = reqctx.adopt(self.headers.get("traceparent"), request_id=rid,
                           tenant=sanitize_tenant(self.headers.get("X-Tenant")))
        if "class" not in body and self.headers.get("X-Class"):
            body["class"] = self.headers.get("X-Class")
        # batched mode: the scheduler serializes device access itself, so concurrent
        # requests proceed without the server-side lock (they share decode steps)
        import contextlib
        guard = contextlib.nullcontext() if state.batch_engine is not None else state.lock
        with guard, reqctx.use(ctx):
            if stream:
                # SSE headers are DEFERRED to the first delta: an error
                # raised before any output (validation, load shed, drain,
                # queue-TTL expiry) gets its real status code (400/503/408)
                # instead of a 200 stream carrying an error event
                completion_id = rid
                started = [False]

                def _start_stream():
                    self.send_response(200)
                    self.send_header("Content-Type", "text/event-stream")
                    self.send_header("Cache-Control", "no-cache")
                    self.send_header("Transfer-Encoding", "chunked")
                    self.send_header("X-Request-Id", rid)
                    self.send_header("X-Replica", self._replica_addr())
                    self.end_headers()
                    _count_http(self.path, 200)
                    started[0] = True

                def emit(text):
                    if not started[0]:
                        _start_stream()
                    payload = _chunk_payload(state, completion_id, {"content": text}, None)
                    if jstate is not None:
                        # token ids whose text THIS chunk carries + the
                        # cumulative delivered count — the durable router's
                        # journal entry (stripped before client relay)
                        payload["dllama"] = {"n": jstate["n"],
                                             "toks": jstate["toks"]}
                        jstate["toks"] = []
                    self._write_chunk(f"data: {json.dumps(payload)}\n\n".encode())

                try:
                    _text, finish = run_completion(state, body, emit,
                                                   journal=jstate,
                                                   deadline_s=deadline_s)
                except Exception as e:
                    _flight_error(rid, e)
                    if not started[0]:  # nothing sent: honest status code
                        self._mapped_error(e, rid)
                        return
                    # mid-stream: error as SSE event, then terminate. The
                    # `retriable` flag is the durable router's failover
                    # switch (docs/FLEET.md): True = the replica failed
                    # around an innocent request (wedged/closed/engine
                    # fault) and the journal may resume it elsewhere;
                    # False = deterministic, resuming would fail again.
                    code, etype, _ra = _map_error(e)
                    self._write_chunk(
                        ("data: " + json.dumps({"error": {
                            "message": str(e), "type": etype,
                            "code": code,
                            "retriable": retriable(e)}})
                         + "\n\n").encode())
                    self._write_chunk(b"data: [DONE]\n\n")
                    self._write_chunk(b"")
                    return
                if not started[0]:  # zero-delta completion still streams
                    _start_stream()
                self._write_chunk(
                    ("data: " + json.dumps(
                        _chunk_payload(state, completion_id, {}, finish))
                     + "\n\n").encode())
                # always terminate the chunked stream so clients don't hang
                self._write_chunk(b"data: [DONE]\n\n")
                self._write_chunk(b"")
            else:
                try:
                    text, finish = run_completion(state, body,
                                                  lambda _t: None,
                                                  deadline_s=deadline_s)
                    self._json(200, _completion_payload(state, text, finish,
                                                        rid),
                               {"X-Request-Id": rid,
                                "X-Replica": self._replica_addr()})
                except Exception as e:
                    _flight_error(rid, e)
                    self._mapped_error(e, rid)

    def _write_chunk(self, data: bytes):
        self.wfile.write(f"{len(data):X}\r\n".encode() + data + b"\r\n")
        self.wfile.flush()


def serve(engine: Engine, host: str = "0.0.0.0", port: int = 9990,
          template_type: TemplateType = TemplateType.UNKNOWN,
          default_sampler: Sampler | None = None,
          device_loop_chunk: int = 0, batch_engine=None,
          speculative_k: int = 0, prefix_cache=True,
          prefix_cache_blocks: int = 0, prefix_block_tokens: int = 16,
          prefix_cache_q80: bool = False,
          request_deadline: float = 0.0, flight_requests: int = 256,
          slow_log: str | None = None,
          slow_threshold: float = 1.0,
          supervisor_threshold: float = 0.0,
          supervisor_poll: float = 1.0,
          tenants: TenantRegistry | None = None,
          role: str = "both", kv_wire_q80: bool = False,
          kv_transfer_ttl: float = 120.0,
          kv_transfer_cap: int = 32) -> ThreadingHTTPServer:
    # batched speculative decoding lives in the BatchEngine scheduler
    # (construct it with speculative=K); speculative_k here drives only the
    # sequential engine's per-request verify loop. Guard EVERY caller, not
    # just the CLI: an engine built WITHOUT speculation plus speculative_k>0
    # would otherwise be silently inert.
    if (batch_engine is not None and speculative_k > 0
            and not getattr(batch_engine, "spec_k", 0)):
        raise ValueError(
            "speculative_k > 0 with a batch_engine requires the engine to "
            "be constructed with speculative=K (BatchEngine owns the "
            "batched draft-verify path)")
    runner = batch_engine or engine
    # one policy authority per replica: prefer the batch engine's own
    # registry (quota enforced at submit) so the HTTP mapping and the
    # scheduler agree on every tenant's weight and bucket
    if tenants is None and batch_engine is not None:
        tenants = getattr(batch_engine, "tenants", None)
    state = ApiState(engine, template_type,
                     default_sampler or Sampler(runner.spec.vocab_size, 0.7, 0.9, 0),
                     device_loop_chunk, batch_engine=batch_engine,
                     speculative_k=speculative_k, prefix_cache=prefix_cache,
                     prefix_cache_blocks=prefix_cache_blocks,
                     prefix_block_tokens=prefix_block_tokens,
                     prefix_cache_q80=prefix_cache_q80,
                     request_deadline=request_deadline, tenants=tenants,
                     role=role, kv_wire_q80=kv_wire_q80,
                     kv_transfer_ttl=kv_transfer_ttl,
                     kv_transfer_cap=kv_transfer_cap)
    handler = type("BoundHandler", (Handler,), {"state": state, "protocol_version": "HTTP/1.1"})
    server = QuietServer((host, port), handler)
    server.api_state = state  # drain controller / tests reach the state here
    # bound port is only known now (port=0 binds ephemeral in tests/benches)
    state.replica_id = f"{host}:{server.server_address[1]}"
    # flight recorder (docs/OBSERVABILITY.md "Request tracing"): always on —
    # a bounded ring of recent request timelines costs a few dict appends
    # per request, and GET /v1/requests must answer "why was THIS slow"
    # without a restart. A pre-installed recorder (tests, shared processes)
    # is kept ONLY when this server asked for defaults; explicit flight
    # flags must win, not silently no-op against the older instance.
    if (flight.current() is None or slow_log is not None
            or flight_requests != 256 or slow_threshold != 1.0):
        flight.install(flight_requests, slow_log=slow_log,
                       slow_threshold=slow_threshold)
    install_process_metrics()
    trace.set_process_name(f"api_server {state.replica_id}")
    if supervisor_threshold > 0 and batch_engine is not None:
        # hung-engine supervision (docs/ROBUSTNESS.md): act on the dispatch
        # watchdog instead of only exporting it — wedged past the threshold
        # ⇒ fail in-flight retriable, re-initialize the backend, and keep
        # /healthz unhealthy for the window so the fleet resumes elsewhere
        from ..resilience.supervisor import EngineSupervisor

        state.supervisor = EngineSupervisor(
            batch_engine, threshold=supervisor_threshold,
            poll=supervisor_poll).start()
        print(f"🛡️  supervisor armed: dispatch hang > "
              f"{supervisor_threshold:.0f}s fails in-flight (retriable) and "
              "re-initializes the backend")
    print(f"🟢 dllama-api listening on {host}:{port}")
    return server


def begin_drain(server: ThreadingHTTPServer, state: ApiState,
                drain_timeout: float = 30.0) -> None:
    """Graceful drain (the SIGTERM body; docs/ROBUSTNESS.md):

    1. flip state.draining — `/healthz` answers 503 "draining" (the LB stops
       routing) and new completions are refused with 503;
    2. let in-flight AND already-queued requests finish, bounded by
       drain_timeout (BatchEngine.close(drain=True); single-engine mode
       waits for the generation lock);
    3. stop accepting connections and return.

    Idempotent: a second call (double SIGTERM) skips straight to shutdown.
    """
    already = state.draining
    state.draining = True
    be = state.batch_engine
    if not already:
        print(f"🟡 draining: letting in-flight requests finish "
              f"(timeout {drain_timeout:.0f}s)")
        if be is not None:
            be.close(drain=True, timeout=drain_timeout)
        else:
            # single-engine mode: in-flight == the generation lock is held;
            # handlers queued behind it observe draining and 503 immediately
            deadline = time.monotonic() + drain_timeout
            while time.monotonic() < deadline:
                if state.lock.acquire(timeout=0.1):
                    state.lock.release()
                    break
    server.shutdown()
    print("🔴 drained, server stopped")


def install_sigterm_drain(server: ThreadingHTTPServer, state: ApiState,
                          drain_timeout: float = 30.0) -> bool:
    """Install the SIGTERM -> begin_drain handler (main thread only; returns
    False where signals can't be installed). The handler runs the drain on a
    worker thread so the signal frame returns immediately — serve_forever()
    unblocks when the drain calls server.shutdown()."""
    import signal

    def _on_term(signum, frame):
        threading.Thread(target=begin_drain,
                         args=(server, state, drain_timeout),
                         name="drain", daemon=True).start()

    try:
        signal.signal(signal.SIGTERM, _on_term)
    except ValueError:  # not the main thread
        return False
    return True


def main(argv=None) -> None:
    from .dllama import build_parser, make_engine, make_sampler, startup

    p = build_parser(include_mode=False)
    p.add_argument("--port", type=int, default=9990)
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--batch", type=int, default=1,
                   help="continuous-batching slots: up to N requests decode "
                        "concurrently in one batched step (1 = reference-style "
                        "serialized serving)")
    p.add_argument("--superstep", type=int, default=8,
                   help="K-step device decode loop for --batch > 1: forward + "
                        "sampling scan K tokens on device per dispatch (1 host "
                        "sync per K tokens); the scheduler drops to single "
                        "steps while a new request waits, so admission latency "
                        "stays ~1 step. 1 = host-side sampling every token")
    p.add_argument("--dp", type=int, default=1,
                   help="data-parallel mesh axis: shard the --batch cache rows over "
                        "N device groups (requires --batch divisible by N)")
    p.add_argument("--no-prefix-cache", action="store_true",
                   help="disable the cross-request shared-prefix KV cache "
                        "(docs/PREFIX_CACHE.md); prefix reuse falls back to "
                        "the reference-style resident/slot rewind only")
    p.add_argument("--prefix-cache-blocks", type=int, default=0, metavar="N",
                   help="prefix-cache pool capacity in blocks (0 = auto: 4 "
                        "contexts per slot set, capped at ~1 GiB host RAM)")
    p.add_argument("--prefix-cache-block-tokens", type=int, default=16,
                   metavar="T", help="tokens per prefix-cache block (reuse "
                        "granularity; smaller = finer matches, more nodes)")
    p.add_argument("--prefix-cache-q80", action="store_true",
                   help="Q80-compress cold prefix-cache blocks (~3.8x denser "
                        "than f32) — capacity over bit-exactness: a cold hit "
                        "is a near-lossless dequantized seed, not an exact "
                        "replay (docs/PREFIX_CACHE.md cost model)")
    p.add_argument("--no-paged-kv", action="store_true",
                   help="escape hatch: revert --batch engines to the dense "
                        "per-slot contiguous KV caches instead of the "
                        "device-resident block pool + block tables "
                        "(docs/PAGED_KV.md); prefix hits then SCATTER pool "
                        "rows host→device instead of remapping tables")
    p.add_argument("--kv-block-tokens", type=int, default=16, metavar="T",
                   help="paged KV: tokens per device pool block (rounded "
                        "down to divide seq_len; also the radix directory's "
                        "reuse granularity — docs/PAGED_KV.md)")
    p.add_argument("--kv-pool-blocks", type=int, default=0, metavar="N",
                   help="paged KV: device pool capacity in blocks (0 = auto: "
                        "slots x blocks-per-context + headroom). Sizing it "
                        "BELOW slots x contexts oversubscribes KV — longer "
                        "contexts fit, pool pressure evicts/demotes the "
                        "directory (docs/PAGED_KV.md)")
    p.add_argument("--max-queue", type=int, default=0, metavar="N",
                   help="admission control (--batch > 1 only): refuse new "
                        "requests with 503 + Retry-After once N are waiting "
                        "for a slot (0 = unbounded; docs/ROBUSTNESS.md)")
    p.add_argument("--queue-ttl", type=float, default=0.0, metavar="S",
                   help="(--batch > 1 only) expire requests that waited more "
                        "than S seconds for a slot: 408 timeout_error, finish "
                        "reason 'deadline' (0 = no TTL)")
    p.add_argument("--request-deadline", type=float, default=0.0, metavar="S",
                   help="wall-clock deadline per request: generation past S "
                        "seconds stops with finish reason 'deadline' (partial "
                        "output delivered); with --batch > 1 the scheduler "
                        "enforces it over queue + generation and expiry "
                        "before the first token is a 408; with --batch 1 it "
                        "bounds generation per token (0 = none)")
    p.add_argument("--drain-timeout", type=float, default=30.0, metavar="S",
                   help="SIGTERM graceful drain: /healthz flips to 503 "
                        "'draining', admissions stop, in-flight requests get "
                        "up to S seconds to finish before the server closes")
    p.add_argument("--flight-requests", type=int, default=256, metavar="N",
                   help="flight recorder ring: keep the last N completed "
                        "request timelines for GET /v1/requests "
                        "(docs/OBSERVABILITY.md)")
    p.add_argument("--slow-log", default=None, metavar="OUT.jsonl",
                   help="append every request slower than --slow-threshold "
                        "as one JSON line (its full flight-recorder "
                        "timeline) — durable exemplars after the ring "
                        "rotates")
    p.add_argument("--slow-threshold", type=float, default=1.0, metavar="S",
                   help="E2E seconds over which a request lands in "
                        "--slow-log (default 1.0)")
    p.add_argument("--supervisor-threshold", type=float, default=0.0,
                   metavar="S",
                   help="hung-engine supervisor (--batch > 1;"
                        " docs/ROBUSTNESS.md): when no device dispatch "
                        "completes for S seconds while work is in flight, "
                        "fail in-flight requests with a RETRIABLE error, "
                        "re-initialize the backend, and flip /healthz "
                        "unhealthy so a fleet router resumes the requests "
                        "elsewhere (0 = observe-only watchdog, the "
                        "pre-supervisor behavior). Size well above the "
                        "slowest legitimate dispatch incl. cold compiles")
    p.add_argument("--supervisor-poll", type=float, default=1.0, metavar="S",
                   help="supervisor watchdog sampling period (detection "
                        "latency is threshold + poll)")
    p.add_argument("--role", choices=("prefill", "decode", "both"),
                   default="both",
                   help="disaggregation role advertised in /healthz "
                        "(docs/DISAGG.md): a role-aware router sends "
                        "long-prompt admissions to 'prefill' replicas "
                        "(which ship the resulting KV blocks out over "
                        "/v1/kv) and decode chains to 'decode' replicas. "
                        "A routing preference, not a capability — the "
                        "engine serves anything regardless")
    p.add_argument("--kv-wire-q80", action="store_true",
                   help="Q80-compress KV blocks on the /v1/kv export wire "
                        "(~3.8x fewer bytes than f32; bounded error, not "
                        "bit-exact — docs/DISAGG.md \"Wire format\")")
    p.add_argument("--kv-transfer-ttl", type=float, default=120.0,
                   metavar="S",
                   help="how long an exported KV transfer stays servable "
                        "for decode-replica fetches before it expires "
                        "(fully-fetched transfers free their slot after a "
                        "short retry grace instead)")
    p.add_argument("--kv-transfer-cap", type=int, default=32, metavar="N",
                   help="max concurrently-held KV export transfers (each "
                        "holds a host snapshot of one prompt's KV blocks); "
                        "beyond N the oldest is evicted — size it above "
                        "the expected concurrent long-prompt admissions")
    p.add_argument("--tenants", default=None, metavar="SPEC",
                   help="multi-tenant policy (docs/SERVING.md \"Multi-tenant"
                        " serving\"): ';'-separated "
                        "name[:weight=W,rate=R,burst=B] entries — W drives "
                        "weighted-fair scheduling, R/B a token-bucket quota "
                        "in tokens/sec (429 + Retry-After on exhaustion; "
                        "0/absent = unlimited). Requests pick their tenant "
                        "via the X-Tenant header; unknown ids share the "
                        "'default' entry. Example: "
                        "'gold:weight=4;free:weight=1,rate=50,burst=100'")
    p.add_argument("--slo-ttft-interactive", type=float, default=0.0,
                   metavar="S",
                   help="SLO-aware shedding (--batch > 1): refuse an "
                        "interactive admission when the measured queue "
                        "drain rate projects its wait past S seconds — "
                        "after first evicting queued batch-class work "
                        "(batch sheds before interactive); 0 = off")
    p.add_argument("--slo-ttft-batch", type=float, default=0.0, metavar="S",
                   help="batch-class TTFT target: refuse batch admissions "
                        "whose projected queue wait exceeds S seconds "
                        "(503 + drain-derived Retry-After); 0 = off")
    p.add_argument("--slo-tpot", type=float, default=0.0, metavar="S",
                   help="interactive TPOT target in seconds/token: while "
                        "the measured decode pace exceeds it, new "
                        "batch-class admissions are refused (they would "
                        "widen every shared dispatch further); 0 = off")
    args = p.parse_args(argv)
    startup(args)
    from .dllama import dump_trace, install_trace

    install_trace(args)
    faults.install_from_env()  # DLLAMA_FAULTS chaos config (resilience/)
    # tenant policy is operator configuration: parse failures abort startup
    tenants = TenantRegistry.parse(args.tenants) if args.tenants else None
    batch_engine = None
    if args.dp > 1 and args.batch <= 1:
        p.error("--dp requires --batch > 1 (data parallelism shards batched cache rows)")
    if args.batch > 1:
        if args.sp > 1:
            p.error("--batch > 1 requires --sp 1: per-row cache positions are "
                    "incompatible with the sequence-sharded (ring) cache")
        if args.kv_cache_storage in ("host", "disc"):
            # refuse loudly rather than silently allocating the full-seq_len
            # HBM cache in exactly the overflow scenario the flag exists for
            p.error("--kv-cache-storage host|disc requires --batch 1: the "
                    "paged cache is single-sequence. For long-context serving "
                    "use --sp (more chips) or --batch 1.")
        from ..runtime.batch_engine import BatchEngine
        from .dllama import _FT, policy_kwargs

        batch_engine = BatchEngine.load(
            args.model, args.tokenizer, max_seq_len=args.max_seq_len,
            weights_ftype=_FT[args.weights_float_type] if args.weights_float_type
            else None,
            slots=args.batch, superstep=max(args.superstep, 1),
            pipeline=args.pipeline,
            # --draft-model without --speculative K engages the default
            # verify width (the drafter is useless without the verify path)
            speculative=(args.speculative
                         or (args.draft_k or 8 if args.draft_model else 0)),
            draft_model=args.draft_model, draft_k=args.draft_k,
            prefix_cache=not args.no_prefix_cache,
            prefix_cache_blocks=args.prefix_cache_blocks,
            prefix_block_tokens=args.prefix_cache_block_tokens,
            prefix_cache_q80=args.prefix_cache_q80,
            paged_kv=not args.no_paged_kv,
            kv_block_tokens=args.kv_block_tokens,
            kv_pool_blocks=args.kv_pool_blocks,
            max_queue=args.max_queue, queue_ttl=args.queue_ttl,
            tenants=tenants,
            slo_ttft_interactive=args.slo_ttft_interactive,
            slo_ttft_batch=args.slo_ttft_batch,
            slo_tpot_interactive=args.slo_tpot,
            tp=args.tp, dp=args.dp, pod=args.pod,
            moe_sharding=args.moe_sharding, **policy_kwargs(args),
            compress_collectives=args.buffer_float_type == "q80" and (args.tp or 1) > 1)
        engine = None
        sampler = make_sampler(args, batch_engine.spec)
        print(f"⏩ Continuous batching: {args.batch} slots, "
              f"super-step K={batch_engine.superstep}, pipelined decode "
              f"{'on' if batch_engine.pipeline else 'off'}"
              + (f", speculative k={batch_engine.spec_k}"
                 if batch_engine.spec_k else "")
              + (" (model drafter co-resident)"
                 if batch_engine.drafter is not None else ""))
    else:
        from .dllama import check_kv_storage

        if args.draft_model:
            import sys

            print("⚠️  --draft-model needs the batched verify path: add "
                  "--batch N (N > 1). Serving WITHOUT model-based drafting.",
                  file=sys.stderr)
        check_kv_storage(args)  # paged-mode cost notice (same as the CLI)
        engine = make_engine(args)
        sampler = make_sampler(args, engine.spec)
    server = serve(engine, args.host, args.port,
                   TemplateType(args.chat_template) if args.chat_template
                   else TemplateType.UNKNOWN, sampler, args.device_loop,
                   batch_engine=batch_engine, speculative_k=args.speculative,
                   prefix_cache=not args.no_prefix_cache,
                   prefix_cache_blocks=args.prefix_cache_blocks,
                   prefix_block_tokens=args.prefix_cache_block_tokens,
                   prefix_cache_q80=args.prefix_cache_q80,
                   request_deadline=args.request_deadline,
                   flight_requests=args.flight_requests,
                   slow_log=args.slow_log,
                   slow_threshold=args.slow_threshold,
                   supervisor_threshold=args.supervisor_threshold,
                   supervisor_poll=args.supervisor_poll,
                   tenants=tenants, role=args.role,
                   kv_wire_q80=args.kv_wire_q80,
                   kv_transfer_ttl=args.kv_transfer_ttl,
                   kv_transfer_cap=args.kv_transfer_cap)
    # SIGTERM -> graceful drain (docs/ROBUSTNESS.md): /healthz flips to
    # draining, admissions stop, in-flight requests finish, then shutdown
    install_sigterm_drain(server, server.api_state, args.drain_timeout)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        if server.api_state.supervisor is not None:
            server.api_state.supervisor.stop()
        if batch_engine is not None:
            # idempotent after a SIGTERM drain (close() re-entry is a no-op
            # walk over already-freed slots); a Ctrl-C exit aborts in-flight
            # requests with EngineClosed instead of leaking the scheduler
            batch_engine.close()
        dump_trace(args)  # --trace: flush the span buffer on shutdown


if __name__ == "__main__":
    main()
