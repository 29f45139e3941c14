#!/usr/bin/env python
"""Fault-injection matrix: every runtime injection point x fault kind against
live CPU-mesh engines (wired into tier-1 via tests/test_fault_matrix.py).

For each (point, kind) cell the harness installs a deterministic FaultSpec,
drives a workload through the family that owns the point, uninstalls, and
then asserts the INVARIANTS the resilience layer promises (docs/ROBUSTNESS.md):

- the BatchEngine scheduler thread NEVER dies: a fault-free probe request
  must complete normally after every cell;
- no slot leak: every slot is free, the queue is empty, and no prefix-cache
  lease stays pinned once the cell's requests are done;
- the sequential / paged Engine stays usable: reset + a short fault-free
  generation succeeds after every cell;
- the fleet router (fleet/router.py over two model-free stub replicas)
  survives `router.proxy` / `router.health` chaos: the membership poller
  thread stays alive, ejected replicas rejoin on the next clean poll, a
  fault-free probe request proxies end-to-end, and no router-side inflight
  count leaks.

Individual requests inside a cell MAY fail — that is the point of an
injected error — the matrix only fails when the process-level invariants
break. Run directly (`python perf/fault_matrix.py [--skip-paged]`): exit 0
clean, 1 with failing cells on stderr, one JSON summary line on stdout.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# a CPU tool by design: the fused family runs the Pallas kernels in interpret
# mode, and that is something a caller asks for (platform_env.py). The test
# suite's conftest makes the same request.
os.environ.setdefault("DLT_PALLAS_INTERPRET", "1")

from distributed_llama_tpu.models.params import init_random_params  # noqa: E402
from distributed_llama_tpu.models.spec import (ArchType, ModelSpec,  # noqa: E402
                                               RopeType)
from distributed_llama_tpu.quants import FloatType  # noqa: E402
from distributed_llama_tpu.resilience import faults  # noqa: E402
from distributed_llama_tpu.resilience.faults import FaultSpec  # noqa: E402
from distributed_llama_tpu.runtime.sampler import Sampler  # noqa: E402

KINDS = ("error", "transient", "latency")
BATCH_POINTS = ("batch.submit", "batch.cache_seed", "batch.prefill",
                "batch.dispatch", "batch.emit",
                "device_loop.batched_dispatch")
# speculation family (docs/SERVING.md "Speculative decoding"): the same
# blast-radius promises under batched draft-verify super-steps — faults
# mid-verify-dispatch and mid-accept-delivery, spec-enabled engines,
# pipelined AND serialized. batch.emit rides along because with spec on it
# fires inside the ACCEPT delivery loop (victim-only cells whose survivors
# must additionally stay token-identical to a fault-free run).
SPEC_POINTS = ("batch.verify", "device_loop.verify_dispatch", "batch.emit")
ENGINE_POINTS = ("engine.dispatch", "device_loop.dispatch")
PAGED_POINTS = ("paged.append", "paged.cold_attend")
ROUTER_POINTS = ("router.proxy", "router.health")
# api.request is HTTP-layer; its shed/validation/drain behavior is asserted
# against a live server in tests/test_resilience.py, not here.

# Durability family (ISSUE 9, docs/FLEET.md "Resume protocol"): mid-stream
# replica kill (a wedged engine failing all in-flight — the supervisor
# escalation shape) through the REAL durable router over two REAL in-process
# replicas, crossed over {stream, non-stream} × {pipelined, speculative}
# engines × {resume on, off}. Resume-on cells assert ZERO client-visible
# failures and byte-identical output vs a fault-free reference; resume-off
# cells assert the failure semantics the PR-6 router promised (mid-stream
# SSE error surfaced honestly for streams; pre-output failures retried).
DURABILITY_ENGINES = ("pipelined", "speculative")
DURABILITY_CELLS = len(DURABILITY_ENGINES) * 2 * 2  # × stream × resume
SUPERVISOR_CELLS = 1  # fault-injected hang -> supervisor recovery

# Disaggregation family (ISSUE 13, docs/DISAGG.md): a role-split fleet
# (prefill replica + decode replica behind the real router with the
# splitter armed) where the prefill replica "dies" mid-transfer — every
# fetch (decode side) or export chunk (prefill side) errors — crossed over
# {stream, non-stream} × {Q80 wire on, off}. Every cell asserts the
# documented degradation: the decode replica falls back to a LOCAL prefill
# with ZERO client-visible failures and byte-identical output (greedy AND
# seeded-stochastic) vs the monolithic reference, and afterwards neither
# replica leaks a device block-pool reference, slot, or lease.
DISAGG_POINTS = ("disagg.fetch", "disagg.export")
# planner-leg points: a failing plan POST (router side) or /v1/kv prefill
# admission (replica side) must route the request MONOLITHIC, untouched —
# one cell each on the raw fleet (wire mode is irrelevant before transfer)
DISAGG_PLAN_POINTS = ("disagg.plan", "disagg.prefill")
DISAGG_CELLS = 2 * len(DISAGG_POINTS) * 2 + len(DISAGG_PLAN_POINTS)

# Fairness/starvation family (ISSUE 11, docs/SERVING.md "Multi-tenant
# serving"): an adversarial flooding tenant saturates the engine's wait
# queue under ~4x-slots overload while two weighted tenants submit
# interactive and batch work AFTER the flood, crossed over {no fault,
# chaos-transient, chaos-error, failover} × {pipelined, serialized}.
# Every cell asserts EVERY tenant makes progress (>= 1 completed request
# each — the weighted-fair queue must reorder past the flood), the
# scheduler thread survives, a fault-free probe completes, and no
# slot/lease/queue entry leaks. The failover scenario recover_wedged()s
# the engine mid-overload and re-submits the retriably-failed requests
# (the durable-router stand-in) — tenants must still progress.
FAIRNESS_SCENARIOS = ("none", "chaos-transient", "chaos-error", "failover")
FAIRNESS_CELLS = len(FAIRNESS_SCENARIOS) * 2  # × {pipelined, serialized}

# Gray-failure family (ISSUE 14, docs/FLEET.md "Gray-failure resilience"):
# one replica of a REAL two-replica fleet under a SUSTAINED latency
# injection (api.request latency matched to the victim — it keeps answering
# healthz ok while serving slow, the gray shape) across resilience modes ×
# {stream, nonstream}. Modes: "route" = outlier detection + probation only,
# "timeout" = + adaptive pre-first-byte timeout (tries to the victim are
# cut and failed over), "hedge" = + budget-bounded duplicate tries. Every
# cell asserts 0 client-visible failures with byte-identical output
# (greedy AND pinned-seed), the victim observed ENTERING probation while
# slow and REJOINING after the injection clears (canary-driven), rotation
# recovered, and — hedge mode — hedge spend within the configured budget.
GRAY_MODES = ("route", "timeout", "hedge")
GRAY_CELLS = len(GRAY_MODES) * 2  # × {stream, nonstream}

# Drafter family (ISSUE 15, docs/SERVING.md "Model-based drafting"): a
# failing model drafter must DEGRADE — to n-gram drafting for rows prompt
# lookup can serve, to plain decode for the rest — and never surface to a
# client: byte-identity is the verify path's contract regardless of where
# proposals come from. draft.load cells build the engine under injection
# (error -> the drafter is dropped at construction, n-gram-only engine);
# draft.propose / draft.dispatch cells inject into a live drafter's
# proposal turns (error -> that dispatch's rows fall back to n-gram, the
# ProposerMux failure counter advances — asserted, so the cells can't go
# vacuous). Kinds: error + latency (a transient drafter is just a slow
# one — retries are not part of the proposal path, degradation is).
DRAFT_POINTS = ("draft.load", "draft.propose", "draft.dispatch")
DRAFT_KINDS = ("error", "latency")
DRAFT_CELLS = len(DRAFT_POINTS) * len(DRAFT_KINDS) * 2  # × {pipe, serial}

# Fused-kernel family (ISSUE 16, docs/SERVING.md "Kernel selection"): the
# `matmul.kernel_select` point fires at TRACE time inside the fused matmul
# dispatch (ops/matmul.py), BEFORE the shape gate — a raising kernel path
# must degrade that call site to the XLA lowering (bit-identical by the
# oracle contract) without killing co-batched rows or the engine. Cells
# build a FRESH engine with the kernels on UNDER injection, so kernel selection
# actually happens while the fault is armed: every output must equal the
# kernel-off reference byte-for-byte whether the kernel path served or
# degraded, and fs.fired is asserted > 0 (non-vacuous).
FUSED_POINT = "matmul.kernel_select"
FUSED_CELLS = len(KINDS) * 2  # × {pipelined, serialized}


def _spec(seq_len=128):
    return ModelSpec(arch_type=ArchType.LLAMA, dim=64, hidden_dim=128,
                     n_layers=2, n_heads=4, n_kv_heads=4, vocab_size=256,
                     seq_len=seq_len, rope_type=RopeType.LLAMA).resolved()


def _greedy(spec):
    return Sampler(spec.vocab_size, temperature=0.0)


def build_batch_engine(pipeline: bool = True, speculative: int = 0):
    from distributed_llama_tpu.runtime.batch_engine import BatchEngine

    spec = _spec()
    params = init_random_params(spec, FloatType.Q40, seed=11)
    return spec, BatchEngine(spec, params, slots=2, tp=1, superstep=4,
                             pipeline=pipeline, speculative=speculative)


# n-gram-dense prompts: greedy decode on the seed-11 tiny model enters a
# repetitive attractor, so verify dispatches engage within a few tokens —
# spec_reference() asserts that, keeping the family non-vacuous
SPEC_PAT = [7, 31, 5, 102, 9, 31, 5, 77]
SPEC_PROMPTS = ([1] + SPEC_PAT * 3, [1, 2] + SPEC_PAT * 3)
SPEC_GEN = 24


def spec_reference(spec, be) -> dict:
    """Fault-free reference outputs for the speculation family (also warms
    every program the cells will hit). Keyed by prompt tuple so a cell can
    check any completed request — victims excluded — against the tokens the
    fault-free scheduler emits (survivor token-identity)."""
    refs = {}
    v0 = be.verify_steps
    reqs = [(p, be.submit(list(p), SPEC_GEN, _greedy(spec)))
            for p in SPEC_PROMPTS]
    for p, r in reqs:
        refs[tuple(p)] = r.wait(timeout=120)
    assert be.verify_steps > v0, (
        "speculation family is vacuous: no verify dispatch in the fault-free "
        "reference run")
    return refs


def run_spec_cell(spec, be, point: str, kind: str, refs: dict) -> list[str]:
    """One speculation cell: inject at `point` while spec-enabled requests
    decode through verify dispatches, then assert the batch invariants PLUS
    survivor token-identity — any request that completed without error must
    have emitted exactly the fault-free reference tokens (rejected-draft
    rollback and mid-accept faults must never corrupt a survivor)."""
    problems: list[str] = []
    # mid-accept-delivery faults target ONE slot so the cell always has a
    # genuine victim/survivor split (an unmatched emit fault's first two
    # fires would kill both co-batched requests, making survivor identity
    # vacuous); dispatch-level faults stay unmatched — their engine blast
    # radius is exactly what the cell probes
    fs = _spec_for(point, kind)
    if point == "batch.emit":
        fs.match = {"slot": 0}
    with faults.active(fs):
        reqs = [(p, be.submit(list(p), SPEC_GEN, _greedy(spec)))
                for p in SPEC_PROMPTS]
        for p, r in reqs:
            try:
                out = r.wait(timeout=120)
            except TimeoutError:
                problems.append(f"{point}/{kind}: request hung (stuck slot)")
                continue
            except Exception:
                continue  # the injected victim — expected
            if out != refs[tuple(p)]:
                problems.append(
                    f"{point}/{kind}: survivor diverged from fault-free "
                    f"reference ({out[:6]}... vs {refs[tuple(p)][:6]}...)")
    faults.uninstall()
    if not be.scheduler_alive():
        problems.append(f"{point}/{kind}: scheduler thread DIED")
        return problems
    try:
        probe = be.submit(list(SPEC_PROMPTS[0]), SPEC_GEN, _greedy(spec))
        out = probe.wait(timeout=120)
        if out != refs[tuple(SPEC_PROMPTS[0])] or probe.error is not None:
            problems.append(f"{point}/{kind}: probe degraded "
                            f"({len(out)} tokens, err={probe.error!r})")
    except Exception as e:
        problems.append(f"{point}/{kind}: probe failed: {e!r}")
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        with be._plock:
            leaked = [s for s in be._slots
                      if s.req is not None or s.lease is not None]
        if not leaked and not be._pending and be._queue.empty():
            break
        time.sleep(0.01)
    else:
        problems.append(f"{point}/{kind}: slot/lease leak after probe")
    return problems


def build_draft_engine(pipeline: bool):
    """Target engine + a small RANDOM co-resident drafter (its drafts
    mostly miss — irrelevant here: the family tests degradation, not
    speedup; byte-identity holds for any proposal content)."""
    from distributed_llama_tpu.runtime.batch_engine import BatchEngine

    spec = _spec()
    params = init_random_params(spec, FloatType.Q40, seed=11)
    dspec = ModelSpec(arch_type=ArchType.LLAMA, dim=32, hidden_dim=64,
                      n_layers=1, n_heads=2, n_kv_heads=2, vocab_size=256,
                      seq_len=128, rope_type=RopeType.LLAMA).resolved()
    dparams = init_random_params(dspec, FloatType.Q40, seed=5)
    be = BatchEngine(spec, params, slots=2, tp=1, superstep=4,
                     pipeline=pipeline, speculative=4,
                     draft_model=(dspec, dparams))
    return spec, be


# one repetition-heavy prompt (n-gram can serve it when the drafter dies)
# and one structureless prompt (prompt lookup is dry there — a dead drafter
# leaves it PLAIN DECODE, the second rung of the degradation ladder)
DRAFT_PROMPTS = ([1] + SPEC_PAT * 3,
                 [1, 17, 93, 4, 55, 201, 8, 41, 113, 29])
DRAFT_GEN = 24


def run_draft_cell(spec, be, point: str, kind: str, refs: dict,
                   tag: str) -> list[str]:
    """One live-drafter cell: inject at `point` while drafter-backed
    requests decode. NO client-visible failure is acceptable — a drafter
    is an accelerator: its faults cost proposals (mux degrades that
    dispatch to n-gram), never correctness — and every output must equal
    the fault-free reference byte-for-byte."""
    problems: list[str] = []
    errs0 = be.proposer.errors
    with faults.active(_spec_for(point, kind)):
        reqs = [(p, be.submit(list(p), DRAFT_GEN, _greedy(spec)))
                for p in DRAFT_PROMPTS]
        for p, r in reqs:
            try:
                out = r.wait(timeout=120)
            except Exception as e:
                problems.append(f"draft {tag} {point}/{kind}: "
                                f"client-visible failure {e!r}")
                continue
            if r.error is not None:
                problems.append(f"draft {tag} {point}/{kind}: request "
                                f"errored {r.error!r}")
            elif out != refs[tuple(p)]:
                problems.append(f"draft {tag} {point}/{kind}: output "
                                f"diverged from fault-free reference")
    faults.uninstall()
    if kind == "error" and be.proposer.errors == errs0:
        problems.append(f"draft {tag} {point}/{kind}: fault never reached "
                        "the drafter (vacuous cell)")
    if be.proposer.disabled:
        problems.append(f"draft {tag} {point}/{kind}: bounded fault "
                        "disabled the drafter permanently")
    if not be.scheduler_alive():
        problems.append(f"draft {tag} {point}/{kind}: scheduler DIED")
        return problems
    try:
        probe = be.submit(list(DRAFT_PROMPTS[0]), DRAFT_GEN, _greedy(spec))
        out = probe.wait(timeout=120)
        if out != refs[tuple(DRAFT_PROMPTS[0])] or probe.error is not None:
            problems.append(f"draft {tag} {point}/{kind}: probe degraded")
    except Exception as e:
        problems.append(f"draft {tag} {point}/{kind}: probe failed: {e!r}")
    with be._plock:
        leaked = [s for s in be._slots
                  if s.req is not None or s.lease is not None]
    if leaked:
        problems.append(f"draft {tag} {point}/{kind}: slot/lease leak")
    return problems


def run_draft_load_cell(pipeline: bool, kind: str, refs: dict,
                        tag: str) -> list[str]:
    """draft.load cell: the engine is CONSTRUCTED under injection. An
    error must drop the drafter (n-gram-only engine, outputs unchanged);
    latency must merely delay construction."""
    problems: list[str] = []
    with faults.active(FaultSpec("draft.load", kind=kind, count=1,
                                 delay_ms=10)):
        spec, be = build_draft_engine(pipeline)
    faults.uninstall()
    try:
        if kind == "error" and be.drafter is not None:
            problems.append(f"draft {tag} load/{kind}: drafter survived an "
                            "injected load failure (vacuous cell)")
        if kind == "latency" and be.drafter is None:
            problems.append(f"draft {tag} load/{kind}: a slow load dropped "
                            "the drafter")
        for p in DRAFT_PROMPTS:
            r = be.submit(list(p), DRAFT_GEN, _greedy(spec))
            out = r.wait(timeout=120)
            if r.error is not None:
                problems.append(f"draft {tag} load/{kind}: request errored "
                                f"{r.error!r}")
            elif out != refs[tuple(p)]:
                problems.append(f"draft {tag} load/{kind}: output diverged "
                                "from fault-free reference")
    except Exception as e:
        problems.append(f"draft {tag} load/{kind}: {e!r}")
    finally:
        be.close()
    return problems


def run_draft_family() -> tuple[int, list[str]]:
    cells = 0
    problems: list[str] = []
    for pipeline in (True, False):
        tag = "pipelined" if pipeline else "serialized"
        spec, be = build_draft_engine(pipeline)
        try:
            refs = {}
            for p in DRAFT_PROMPTS:
                refs[tuple(p)] = be.submit(list(p), DRAFT_GEN,
                                           _greedy(spec)).wait(timeout=120)
            for point in ("draft.propose", "draft.dispatch"):
                for kind in DRAFT_KINDS:
                    cells += 1
                    problems += run_draft_cell(spec, be, point, kind, refs,
                                               tag)
        finally:
            be.close()
        for kind in DRAFT_KINDS:
            cells += 1
            problems += run_draft_load_cell(pipeline, kind, refs, tag)
    return cells, problems


def build_fused_engine(pipeline: bool):
    """A batched engine with the kernels on (use_pallas=True,
    ops/matmul.py): every M>1 matmul the programs trace runs the kernel
    dispatch, so `matmul.kernel_select` fires while the cell's fault is
    armed and the except-path degrades that call site to XLA."""
    from distributed_llama_tpu.runtime.batch_engine import BatchEngine

    spec = _spec()
    params = init_random_params(spec, FloatType.Q40, seed=11)
    return spec, BatchEngine(spec, params, slots=2, tp=1, superstep=4,
                             pipeline=pipeline, speculative=4,
                             use_pallas=True)


def run_fused_cell(pipeline: bool, kind: str, refs: dict) -> list[str]:
    """One fused-kernel cell: construct the engine (and trace its first
    programs — where kernel selection happens) UNDER injection. A failing
    kernel path is a TRACE-time event: it must cost only the kernel (that
    call site lowers via XLA), never a request — every output must equal
    the kernel-off reference byte-for-byte, co-batched rows included."""
    problems: list[str] = []
    tag = "fused-pipelined" if pipeline else "fused-serialized"
    name = f"[{tag}] {FUSED_POINT}/{kind}"
    fs = FaultSpec(FUSED_POINT, kind=kind, count=4, delay_ms=10)
    be = None
    try:
        with faults.active(fs):
            spec, be = build_fused_engine(pipeline)
            reqs = [(p, be.submit(list(p), SPEC_GEN, _greedy(spec)))
                    for p in SPEC_PROMPTS]
            for p, r in reqs:
                try:
                    out = r.wait(timeout=120)
                except Exception as e:
                    problems.append(f"{name}: client-visible failure {e!r}")
                    continue
                if r.error is not None:
                    problems.append(f"{name}: request errored {r.error!r}")
                elif out != refs[tuple(p)]:
                    problems.append(f"{name}: output diverged from the "
                                    "kernel-off reference "
                                    f"({out[:6]}... vs "
                                    f"{refs[tuple(p)][:6]}...)")
        faults.uninstall()
        if fs.fired == 0:
            problems.append(f"{name}: fault never reached kernel selection "
                            "(vacuous cell)")
        if not be.scheduler_alive():
            problems.append(f"{name}: scheduler thread DIED")
            return problems
        try:
            probe = be.submit(list(SPEC_PROMPTS[0]), SPEC_GEN, _greedy(spec))
            out = probe.wait(timeout=120)
            if out != refs[tuple(SPEC_PROMPTS[0])] or probe.error is not None:
                problems.append(f"{name}: probe degraded "
                                f"({len(out)} tokens, err={probe.error!r})")
        except Exception as e:
            problems.append(f"{name}: probe failed: {e!r}")
        with be._plock:
            leaked = [s for s in be._slots
                      if s.req is not None or s.lease is not None]
        if leaked:
            problems.append(f"{name}: slot/lease leak")
    finally:
        faults.uninstall()
        if be is not None:
            be.close()
    return problems


def run_fused_family() -> tuple[int, list[str]]:
    cells = 0
    problems: list[str] = []
    # kernel-off reference (the XLA oracle, use_pallas=False): fused cells
    # must emit exactly these tokens whether the kernel path served or
    # degraded mid-trace
    spec, be = build_batch_engine(pipeline=True, speculative=4)
    try:
        refs = {tuple(p): be.submit(list(p), SPEC_GEN,
                                    _greedy(spec)).wait(timeout=120)
                for p in SPEC_PROMPTS}
    finally:
        be.close()
    for pipeline in (True, False):
        for kind in KINDS:
            cells += 1
            problems += run_fused_cell(pipeline, kind, refs)
    return cells, problems


# ----------------------------------------------------------------------
# grammar-constrained decoding family (constrain/, docs/SERVING.md
# "Constrained decoding"; docs/ROBUSTNESS.md): the documented degradation
# ladder under injected faults, × {pipelined, serialized}.
#
#   constrain.compile — fires at the EDGE (constrain/compiler.py), before
#     any queue work: an injected error surfaces to the caller (the api
#     maps it to an honest 400 invalid_request_error) and the ENGINE never
#     sees the request — co-batched service is untouched, byte-for-byte.
#   constrain.mask — fires on the engine's masking paths (host sample +
#     masked dispatch state upload): an error DEGRADES that row to
#     unconstrained decoding (constrain_degraded_total, flight event) and
#     the request completes without a client-visible failure; latency
#     merely delays. Co-batched unconstrained survivors stay
#     token-identical to the fault-free reference in every cell.
# ----------------------------------------------------------------------

CONSTRAIN_PROMPT = [1, 5, 9]
CONSTRAIN_GEN = 30
CONSTRAIN_POINTS = ("constrain.compile", "constrain.mask")
CONSTRAIN_KINDS = ("error", "latency")
CONSTRAIN_CELLS = (len(CONSTRAIN_POINTS) * len(CONSTRAIN_KINDS)
                   * 2)  # × {pipelined, serialized}


def _constrain_grammar():
    from distributed_llama_tpu.constrain import byte_vocab, compile_grammar

    cv = byte_vocab(256)
    aut, gh = compile_grammar(
        "json_schema",
        {"type": "object", "properties": {
            "name": {"enum": ["alpha", "beta"]},
            "ok": {"type": "boolean"}}}, cv, eos_id=2)
    return cv, aut, gh


def run_constrain_cell(spec, be, point: str, kind: str, refs: dict,
                       aut, gh: str, cv, tag: str) -> list[str]:
    from distributed_llama_tpu.constrain import compile_grammar

    name = f"constrain {tag} {point}/{kind}"
    problems: list[str] = []
    deg0 = be.constrain_degraded
    fs = _spec_for(point, kind)
    with faults.active(fs):
        if point == "constrain.compile":
            # the edge path: compile fails/stalls BEFORE any queue work —
            # the engine never sees the request (honest 400 at the api)
            try:
                compile_grammar("regex", "[0-9]{4}", cv, eos_id=2)
                compiled = True
            except Exception:
                compiled = False
            if kind == "error" and compiled:
                problems.append(f"{name}: injected compile fault vanished")
            if kind == "latency" and not compiled:
                problems.append(f"{name}: latency injection failed the "
                                "compile")
        # engine-side service under the armed fault: one constrained row
        # co-batched with one plain row (speculation on — grammar drafts
        # on the constrained row, n-gram on the repetitive plain row)
        rc = be.submit(list(CONSTRAIN_PROMPT), CONSTRAIN_GEN, _greedy(spec),
                       constraint=aut, constraint_hash=gh)
        rp = be.submit(list(DRAFT_PROMPTS[0]), DRAFT_GEN, _greedy(spec))
        for label, r, ref in (("constrained", rc, refs["constrained"]),
                              ("plain", rp, refs["plain"])):
            try:
                out = r.wait(timeout=120)
            except Exception as e:
                problems.append(f"{name}: client-visible {label} failure "
                                f"{e!r}")
                continue
            if r.error is not None:
                problems.append(f"{name}: {label} request errored "
                                f"{r.error!r}")
                continue
            if label == "plain" and out != ref:
                # the blast-radius promise: an unconstrained co-batched
                # survivor is token-identical in EVERY cell
                problems.append(f"{name}: co-batched plain row diverged "
                                "from fault-free reference")
            if label == "constrained" and out != ref and not (
                    point == "constrain.mask" and kind == "error"):
                # mask/error legitimately degrades the victim to
                # unconstrained output; every other cell must emit the
                # fault-free constrained tokens exactly
                problems.append(f"{name}: constrained output diverged "
                                "from fault-free reference")
    faults.uninstall()
    if fs.fired == 0:
        problems.append(f"{name}: fault never fired (vacuous cell)")
    if (point == "constrain.mask" and kind == "error"
            and be.constrain_degraded == deg0):
        problems.append(f"{name}: mask fault did not degrade the "
                        "constrained row (vacuous cell)")
    if not be.scheduler_alive():
        problems.append(f"{name}: scheduler thread DIED")
        return problems
    # post-fault probe: constrained service fully restored
    try:
        probe = be.submit(list(CONSTRAIN_PROMPT), CONSTRAIN_GEN,
                          _greedy(spec), constraint=aut, constraint_hash=gh)
        out = probe.wait(timeout=120)
        if out != refs["constrained"] or probe.error is not None:
            problems.append(f"{name}: probe degraded "
                            f"({len(out)} tokens, err={probe.error!r})")
    except Exception as e:
        problems.append(f"{name}: probe failed: {e!r}")
    with be._plock:
        leaked = [s for s in be._slots
                  if s.req is not None or s.lease is not None]
    if leaked:
        problems.append(f"{name}: slot/lease leak")
    if be.constrain_table is not None and be.constrain_table.active_rows:
        problems.append(f"{name}: constraint-table region leak")
    return problems


def run_constrain_family() -> tuple[int, list[str]]:
    cv, aut, gh = _constrain_grammar()
    cells = 0
    problems: list[str] = []
    for pipeline in (True, False):
        tag = "pipelined" if pipeline else "serialized"
        spec, be = build_batch_engine(pipeline=pipeline, speculative=4)
        try:
            refs = {
                "constrained": be.submit(
                    list(CONSTRAIN_PROMPT), CONSTRAIN_GEN, _greedy(spec),
                    constraint=aut, constraint_hash=gh).wait(timeout=120),
                "plain": be.submit(
                    list(DRAFT_PROMPTS[0]), DRAFT_GEN,
                    _greedy(spec)).wait(timeout=120),
            }
            for point in CONSTRAIN_POINTS:
                for kind in CONSTRAIN_KINDS:
                    cells += 1
                    problems += run_constrain_cell(spec, be, point, kind,
                                                   refs, aut, gh, cv, tag)
        finally:
            be.close()
    return cells, problems


def build_engine(paged: bool = False):
    from distributed_llama_tpu.runtime.engine import Engine

    spec = _spec(seq_len=256 if paged else 128)
    params = init_random_params(spec, FloatType.Q40, seed=11)
    kw = (dict(kv_cache_storage="host", kv_cache_resident=64) if paged
          else {})
    return spec, Engine(spec, params, tp=1, **kw)


def _spec_for(point: str, kind: str) -> FaultSpec:
    # count=2 bounds every cell: the fault fires, the stack reacts, and the
    # cell's own workload can still make progress afterwards
    return FaultSpec(point, kind=kind, count=2, delay_ms=10)


def run_batch_cell(spec, be, point: str, kind: str) -> list[str]:
    problems: list[str] = []
    with faults.active(_spec_for(point, kind)):
        reqs = []
        for i in range(2):
            try:
                reqs.append(be.submit([1, 7 + i, 23, 5] + list(range(2, 12)),
                                      8, _greedy(spec)))
            except Exception:
                pass  # batch.submit faults reject synchronously — expected
        for r in reqs:
            try:
                r.wait(timeout=120)
            except TimeoutError:
                problems.append(f"{point}/{kind}: request hung (stuck slot)")
            except Exception:
                pass  # injected failure surfaced to the client — expected
    faults.uninstall()
    # invariants: scheduler alive, probe completes, nothing leaked
    if not be.scheduler_alive():
        problems.append(f"{point}/{kind}: scheduler thread DIED")
        return problems
    try:
        probe = be.submit([1, 2, 3], 4, _greedy(spec))
        out = probe.wait(timeout=120)
        if len(out) != 4 or probe.error is not None:
            problems.append(f"{point}/{kind}: probe degraded "
                            f"({len(out)} tokens, err={probe.error!r})")
    except Exception as e:
        problems.append(f"{point}/{kind}: probe failed: {e!r}")
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        with be._plock:
            leaked = [s for s in be._slots
                      if s.req is not None or s.lease is not None]
        if not leaked and not be._pending and be._queue.empty():
            break
        time.sleep(0.01)
    else:
        problems.append(f"{point}/{kind}: slot/lease leak after probe")
    return problems


def run_engine_cell(spec, eng, point: str, kind: str,
                    paged: bool = False) -> list[str]:
    problems: list[str] = []
    prompt = ([1] + list(range(2, 82))) if paged else [1, 7, 23, 5]
    with faults.active(_spec_for(point, kind)):
        try:
            eng.reset()
            if point == "device_loop.dispatch":
                eng.generate_with(list(prompt), 6, _greedy(spec),
                                  device_loop_chunk=4)
            else:
                eng.generate(list(prompt), 6, _greedy(spec))
        except Exception:
            pass  # the request may fail; the ENGINE must survive
    faults.uninstall()
    try:
        eng.reset()
        out, _ = eng.generate(list(prompt), 2, _greedy(spec))
        if len(out) != 2:
            problems.append(f"{point}/{kind}: probe generated {len(out)}/2")
    except Exception as e:
        problems.append(f"{point}/{kind}: engine unusable after fault: {e!r}")
    return problems


def build_router_fleet():
    """Fleet-tier family harness: the REAL router over two model-free stub
    replicas (stdlib HTTP servers answering /healthz and completions) — the
    router's fault points live entirely in its proxy/poll paths, so the cells
    need no engine. Returns (router_server, stub_servers)."""
    import json as _json
    import threading
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    from distributed_llama_tpu.fleet.router import serve_router

    class StubReplica(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, fmt, *a):
            pass

        def do_GET(self):
            body = _json.dumps({"status": "ok", "replica": {
                "id": "stub", "model_hash": "deadbeef0000", "slots": 2,
                "free_slots": 2, "queue_depth": 0, "draining": False,
            }}).encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_POST(self):
            self.rfile.read(int(self.headers.get("Content-Length", 0)))
            body = _json.dumps({"choices": [{"message": {
                "role": "assistant", "content": "ok"},
                "finish_reason": "stop", "index": 0}]}).encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    stubs = []
    for _ in range(2):
        srv = ThreadingHTTPServer(("127.0.0.1", 0), StubReplica)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        stubs.append(srv)
    router = serve_router(
        [f"127.0.0.1:{s.server_address[1]}" for s in stubs],
        host="127.0.0.1", port=0, poll_interval=0.2, poll_timeout=2.0,
        retries=2, try_timeout=10.0)
    threading.Thread(target=router.serve_forever, daemon=True).start()
    return router, stubs


def run_router_cell(router, point: str, kind: str) -> list[str]:
    """One fleet cell: inject at `point`, drive proxied requests + a poll,
    then assert the fleet-level invariants — the membership poller thread
    survives, a fault-free probe request completes end-to-end, rotation
    recovers to both stubs, and no router-side inflight count leaks."""
    import http.client
    import json as _json

    state = router.router_state
    problems: list[str] = []

    def post():
        conn = http.client.HTTPConnection(
            "127.0.0.1", router.server_address[1], timeout=30)
        try:
            conn.request("POST", "/v1/chat/completions",
                         _json.dumps({"messages": [
                             {"role": "user", "content": f"{point}/{kind}"}],
                             "max_tokens": 2}),
                         {"Content-Type": "application/json"})
            return conn.getresponse().status
        finally:
            conn.close()

    with faults.active(_spec_for(point, kind)):
        state.membership.poll_once()
        for _ in range(2):
            try:
                post()  # MAY 503 under injected proxy errors — that is the cell
            except Exception:
                pass
    faults.uninstall()
    if not state.membership._thread.is_alive():
        problems.append(f"{point}/{kind}: membership poller thread DIED")
        return problems
    state.membership.poll_once()  # clean poll: ejected stubs must rejoin
    if len(state.membership.in_rotation()) != 2:
        problems.append(f"{point}/{kind}: rotation did not recover "
                        f"({[r.snapshot() for r in state.membership.replicas]})")
    try:
        status = post()
        if status != 200:
            problems.append(f"{point}/{kind}: fault-free probe got {status}")
    except Exception as e:
        problems.append(f"{point}/{kind}: fault-free probe failed: {e!r}")
    # the probe client returns on response HEADERS; the handler thread
    # decrements inflight in its finally a beat later — poll, don't race it
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        leaked = [r.id for r in state.membership.replicas if r.inflight != 0]
        if not leaked:
            break
        time.sleep(0.01)
    else:
        problems.append(f"{point}/{kind}: router inflight leak on {leaked}")
    return problems


def run_supervisor_cell() -> list[str]:
    """Hung-engine supervision (resilience/supervisor.py): a deterministic
    fault-injected hang (latency fault parking the scheduler in a 600 s
    sleep at batch.dispatch — the stand-in for a dispatch hung in the backend)
    must be recovered within the supervisor's escalation threshold: the
    in-flight request fails with the RETRIABLE EngineWedged, the backend
    re-initializes, and a fault-free probe completes on the fresh scheduler
    while the zombie thread is still asleep."""
    from distributed_llama_tpu.resilience.errors import EngineWedged
    from distributed_llama_tpu.resilience.supervisor import EngineSupervisor

    problems: list[str] = []
    spec, be = build_batch_engine(pipeline=True)
    sup = EngineSupervisor(be, threshold=1.0, poll=0.1)
    try:
        # warm the shapes so the hang is the only slow thing in the cell
        be.generate([1, 7, 23, 5], 4, _greedy(spec))
        with faults.active(FaultSpec("batch.dispatch", kind="latency",
                                     delay_ms=600_000, count=1)):
            req = be.submit([1, 9, 9, 2], 8, _greedy(spec))
            t0 = time.monotonic()
            while be.dispatch_age() <= 1.0 and time.monotonic() - t0 < 30:
                time.sleep(0.02)
            t_esc = time.monotonic()
            sup.check_once()
            try:
                req.wait(timeout=10)
                problems.append("supervisor: wedged request COMPLETED "
                                "(hang never engaged?)")
            except EngineWedged:
                pass  # the retriable failure the escalation promises
            except Exception as e:
                problems.append(f"supervisor: wedged request failed with "
                                f"{e!r}, want EngineWedged")
            if time.monotonic() - t_esc > 5.0:
                problems.append("supervisor: escalation took "
                                f"{time.monotonic() - t_esc:.1f}s")
        faults.uninstall()
        if not sup.healthy:
            problems.append(f"supervisor: state {sup.state} after recovery")
        if sup.recoveries != 1:
            problems.append(f"supervisor: {sup.recoveries} recoveries, want 1")
        try:
            probe = be.submit([1, 2, 3], 4, _greedy(spec))
            out = probe.wait(timeout=120)
            if len(out) != 4:
                problems.append(f"supervisor: probe generated {len(out)}/4 "
                                "after recovery")
        except Exception as e:
            problems.append(f"supervisor: probe failed after recovery: {e!r}")
    finally:
        faults.uninstall()
        sup.stop()
        be.close()
    return problems


# ----------------------------------------------------------------------
# fairness family: flooding tenant, weighted survivors, chaos + failover
# ----------------------------------------------------------------------

def build_fair_engine(pipeline: bool):
    from distributed_llama_tpu.resilience.tenancy import TenantRegistry
    from distributed_llama_tpu.runtime.batch_engine import BatchEngine

    spec = _spec()
    params = init_random_params(spec, FloatType.Q40, seed=11)
    reg = TenantRegistry.parse("alpha:weight=3;beta:weight=2;flood:weight=1")
    return spec, BatchEngine(spec, params, slots=2, tp=1, superstep=4,
                             pipeline=pipeline, tenants=reg)


def run_fairness_cell(spec, be, scenario: str, tag: str) -> list[str]:
    from distributed_llama_tpu.resilience.errors import EngineWedged

    problems: list[str] = []
    name = f"[{tag}] fairness/{scenario}"
    gen = 10
    fs = None
    if scenario == "chaos-transient":
        fs = FaultSpec("batch.dispatch", kind="transient", count=3,
                       delay_ms=5)
    elif scenario == "chaos-error":
        fs = FaultSpec("batch.emit", kind="error", count=2)
    reqs = []  # (tenant, prompt, BatchRequest)

    def sub(tenant, klass, salt):
        prompt = [1, salt, 23, 5]
        return (tenant, prompt,
                be.submit(list(prompt), gen, _greedy(spec), tenant=tenant,
                          klass=klass))

    done: dict = {}
    ctx = faults.active(fs) if fs is not None else None
    if ctx is not None:
        ctx.__enter__()
    try:
        # the flood lands FIRST: a FIFO queue would serve all 8 before any
        # later tenant — the weighted-fair queue must not
        for i in range(8):
            reqs.append(sub("flood", "batch", 40 + i))
        for i in range(2):
            reqs.append(sub("alpha", "interactive", 60 + i))
            reqs.append(sub("beta", "interactive", 80 + i))
        reqs.append(sub("alpha", "batch", 90))
        reqs.append(sub("beta", "batch", 91))
        if scenario == "failover":
            # mid-overload wedge: everything in flight/queued fails
            # RETRIABLE; re-submit each failure once, as a durable router
            # would, and the tenants must still make progress
            time.sleep(0.05)
            be.recover_wedged()
        resubmit = []
        for tenant, prompt, r in reqs:
            try:
                r.wait(timeout=120)
                done[tenant] = done.get(tenant, 0) + 1
            except EngineWedged:
                resubmit.append((tenant, prompt))
            except TimeoutError:
                problems.append(f"{name}: {tenant} request hung")
            except Exception:
                pass  # injected victim — expected under chaos-error
        for tenant, prompt in resubmit:
            try:
                be.submit(list(prompt), gen, _greedy(spec), tenant=tenant,
                          klass="batch").wait(timeout=120)
                done[tenant] = done.get(tenant, 0) + 1
            except Exception as e:
                problems.append(f"{name}: {tenant} resubmit failed: {e!r}")
    finally:
        if ctx is not None:
            ctx.__exit__(None, None, None)
        faults.uninstall()
    for tenant in ("alpha", "beta", "flood"):
        if not done.get(tenant):
            problems.append(f"{name}: tenant {tenant} STARVED "
                            f"(completions: {done})")
    if not be.scheduler_alive():
        problems.append(f"{name}: scheduler thread DIED")
        return problems
    try:
        probe = be.submit([1, 2, 3], 4, _greedy(spec))
        out = probe.wait(timeout=120)
        if len(out) != 4 or probe.error is not None:
            problems.append(f"{name}: probe degraded "
                            f"({len(out)} tokens, err={probe.error!r})")
    except Exception as e:
        problems.append(f"{name}: probe failed: {e!r}")
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        with be._plock:
            leaked = [s for s in be._slots
                      if s.req is not None or s.lease is not None]
            qleft = len(be._pending)
        if not leaked and not qleft and be._queue.empty():
            break
        time.sleep(0.01)
    else:
        problems.append(f"{name}: slot/lease/queue leak after probe")
    return problems


def run_fairness_family() -> tuple[int, list[str]]:
    cells = 0
    problems: list[str] = []
    for pipeline in (True, False):
        tag = "fair-pipelined" if pipeline else "fair-serialized"
        spec, be = build_fair_engine(pipeline)
        try:
            be.generate([1, 7, 23, 5], 4, _greedy(spec))  # warm the shapes
            for scenario in FAIRNESS_SCENARIOS:
                cells += 1
                problems += run_fairness_cell(spec, be, scenario, tag)
        finally:
            be.close()
    return cells, problems


# ----------------------------------------------------------------------
# durability family: real replicas, real router, mid-stream kill
# ----------------------------------------------------------------------

_FLEET_MODEL: tuple | None = None


def _fleet_model_files():
    """Tiny real checkpoint + byte-fallback tokenizer, written once per run
    (the durability family needs full api_server replicas, which load from
    files)."""
    global _FLEET_MODEL
    if _FLEET_MODEL is not None:
        return _FLEET_MODEL
    import tempfile

    from distributed_llama_tpu.formats.mfile import (params_file_order,
                                                     write_model)
    from distributed_llama_tpu.formats.tfile import (TokenizerData,
                                                     write_tokenizer)

    tmp = tempfile.mkdtemp(prefix="dlt_durability_")
    spec = _spec(seq_len=192)
    params = init_random_params(spec, FloatType.F32, seed=21)
    mpath = os.path.join(tmp, "m.m")
    write_model(mpath, spec, params_file_order(spec, params), FloatType.F32)
    vocab = ([b"<unk>", b"<s>", b"</s>"] + [bytes([i]) for i in range(251)]
             + [b"<|im_start|>", b"<|im_end|>"])
    scores = [0.0] * 254 + [-1.0, -1.0]
    tpath = os.path.join(tmp, "t.t")
    write_tokenizer(tpath, TokenizerData(
        vocab=vocab, scores=scores, bos_id=1, eos_id=2, chat_eos_id=254,
        max_token_length=12, chat_template="{{<|im_start|>}}"))
    _FLEET_MODEL = (mpath, tpath)
    return _FLEET_MODEL


def build_durable_fleet(speculative: int = 0, router_kwargs: dict = None):
    """Two REAL in-process api_server replicas (tiny checkpoint, batched
    engines) fronted by the REAL durable router. Returns
    (replicas=[(engine, server, port)], router, rport, close).
    `router_kwargs` extends serve_router (the gray family's GrayConfig)."""
    import threading

    from distributed_llama_tpu.apps.api_server import serve
    from distributed_llama_tpu.fleet.router import close_router, serve_router
    from distributed_llama_tpu.formats.mfile import load_model
    from distributed_llama_tpu.runtime.batch_engine import BatchEngine
    from distributed_llama_tpu.tokenizer import TemplateType
    from distributed_llama_tpu.tokenizer.bpe import Tokenizer

    mpath, tpath = _fleet_model_files()
    reps = []
    for _ in range(2):
        lspec, lparams = load_model(mpath, 0)
        be = BatchEngine(lspec, lparams, Tokenizer.load(tpath), slots=2,
                         tp=1, superstep=4, speculative=speculative)
        srv = serve(None, host="127.0.0.1", port=0,
                    template_type=TemplateType.CHATML, batch_engine=be)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        reps.append((be, srv, srv.server_address[1]))
    router = serve_router([f"127.0.0.1:{p}" for _, _, p in reps],
                          host="127.0.0.1", port=0, poll_interval=0.15,
                          block_bytes=16, retries=2, try_timeout=60.0,
                          **(router_kwargs or {}))
    threading.Thread(target=router.serve_forever, daemon=True).start()

    def close():
        close_router(router)
        for be, srv, _p in reps:
            srv.shutdown()
            srv.server_close()
            be.close()

    return reps, router, router.server_address[1], close


def _durability_request(rport: int, stream: bool) -> dict:
    """One completion through the router; returns the shared driver's
    outcome dict (fleet/client.py — text/error/status are what the cells
    assert on). The repetitive content makes n-gram drafts engage on spec
    engines."""
    from distributed_llama_tpu.fleet.client import completion_request

    body = {"messages": [
        {"role": "system", "content": "shared fleet system prompt abcb abcb"},
        {"role": "user", "content": "ab ab ab ab ab ab ab ab"}],
        "max_tokens": 48, "temperature": 0.8, "seed": 4242, "stream": stream}
    return completion_request(rport, body, timeout=120)


def _start_killer(reps, min_tokens: int = 3):
    """Background thread that wedges (recover_wedged: fail in-flight
    retriable, re-init backend — the supervisor escalation body) whichever
    replica is observed serving a request with >= min_tokens generated.
    Returns (thread, fired: list)."""
    import threading

    fired: list[str] = []

    def run():
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and not fired:
            for be, _srv, port in reps:
                with be._plock:
                    busy = any(s.req is not None
                               and len(s.req.out) >= min_tokens
                               for s in be._slots)
                if busy:
                    fired.append(str(port))
                    be.recover_wedged()
                    return
            time.sleep(0.002)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    return t, fired


def run_durability_cell(reps, router, rport: int, stream: bool,
                        resume_on: bool, ref_text: str,
                        tag: str) -> list[str]:
    """One mid-stream-kill cell. Resume ON: zero client-visible failures and
    byte-identical output. Resume OFF (the PR-6 router semantics): a stream
    that lost its replica mid-flight surfaces an honest SSE error; a
    non-stream request either completes identically via the pre-output
    retry path or surfaces an honest error status — never a hang, and the
    router/poller must survive either way."""
    from distributed_llama_tpu.obs import metrics as obs_metrics

    problems: list[str] = []
    name = (f"{tag}/{'stream' if stream else 'nonstream'}/"
            f"resume={'on' if resume_on else 'off'}")
    state = router.router_state
    state.durable = resume_on
    resumed0 = (obs_metrics.snapshot()
                .get("router_resumed_requests_total") or 0)
    killer, fired = _start_killer(reps)
    try:
        res = _durability_request(rport, stream)
    finally:
        killer.join(timeout=60)
        state.durable = True
    if not fired:
        problems.append(f"{name}: the kill never engaged (request finished "
                        "before any replica had 3 tokens in flight)")
        return problems
    if resume_on:
        if res["error"] is not None or res["status"] != 200:
            problems.append(f"{name}: client-visible failure {res!r}")
        elif res["text"] != ref_text:
            problems.append(f"{name}: output diverged from fault-free "
                            f"reference ({res['text'][:40]!r} vs "
                            f"{ref_text[:40]!r})")
        resumed = (obs_metrics.snapshot()
                   .get("router_resumed_requests_total") or 0)
        if stream and resumed <= resumed0:
            problems.append(f"{name}: no resume recorded — the cell was "
                            "vacuous")
    else:
        if stream:
            # honest surfacing: the client must see the SSE error event
            # (never a silent truncation or a double-delivered splice)
            if res["error"] is None and res["text"] != ref_text:
                problems.append(f"{name}: stream neither errored nor "
                                f"matched the reference: {res!r}")
        elif res["status"] not in (200, 500, 502, 503):
            problems.append(f"{name}: unexpected status {res!r}")
        elif res["status"] == 200 and res["text"] != ref_text:
            # pre-output retry completed it: identity holds (pinned seed
            # comes from the request body here)
            problems.append(f"{name}: retried non-stream diverged: {res!r}")
    # fleet must recover for the next cell: wedged engine serves again
    # (recover_wedged re-initialized it) once the poller sees it healthy
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        state.membership.poll_once()
        if len(state.membership.in_rotation()) == len(reps):
            break
        time.sleep(0.05)
    else:
        problems.append(f"{name}: rotation did not recover after the kill")
    return problems


def run_durability_family() -> tuple[int, list[str]]:
    cells = 0
    problems: list[str] = []
    for tag in DURABILITY_ENGINES:
        spec_k = 4 if tag == "speculative" else 0
        reps, router, rport, close = build_durable_fleet(speculative=spec_k)
        try:
            refs = {}
            for stream in (True, False):
                ref = _durability_request(rport, stream)
                if ref["error"] is not None:
                    problems.append(f"{tag}: fault-free reference failed: "
                                    f"{ref!r}")
                    cells += 4
                    break
                refs[stream] = ref["text"]
            else:
                if refs[True] != refs[False]:
                    problems.append(f"{tag}: stream vs non-stream reference "
                                    "mismatch")
                for stream in (True, False):
                    for resume_on in (True, False):
                        cells += 1
                        problems += run_durability_cell(
                            reps, router, rport, stream, resume_on,
                            refs[stream], tag)
        finally:
            close()
    return cells, problems


# ----------------------------------------------------------------------
# disaggregation family: role-split fleet, prefill death mid-transfer
# ----------------------------------------------------------------------

def build_disagg_fleet(q80: bool):
    """Prefill-role + decode-role replicas (REAL in-process api_servers)
    behind the REAL router with the splitter armed. Returns
    (replicas=[(engine, server, port, role)], router, rport, close)."""
    import threading

    from distributed_llama_tpu.apps.api_server import serve
    from distributed_llama_tpu.fleet.router import close_router, serve_router
    from distributed_llama_tpu.formats.mfile import load_model
    from distributed_llama_tpu.runtime.batch_engine import BatchEngine
    from distributed_llama_tpu.tokenizer import TemplateType
    from distributed_llama_tpu.tokenizer.bpe import Tokenizer

    mpath, tpath = _fleet_model_files()
    reps = []
    for role in ("prefill", "decode"):
        lspec, lparams = load_model(mpath, 0)
        be = BatchEngine(lspec, lparams, Tokenizer.load(tpath), slots=2,
                         tp=1, superstep=4)
        srv = serve(None, host="127.0.0.1", port=0,
                    template_type=TemplateType.CHATML, batch_engine=be,
                    role=role, kv_wire_q80=q80)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        reps.append((be, srv, srv.server_address[1], role))
    router = serve_router([f"127.0.0.1:{p}" for _, _, p, _ in reps],
                          host="127.0.0.1", port=0, poll_interval=0.15,
                          block_bytes=16, retries=2, try_timeout=60.0,
                          disagg_threshold=24, disagg_timeout=30.0)
    threading.Thread(target=router.serve_forever, daemon=True).start()

    def close():
        close_router(router)
        for be, srv, _p, _r in reps:
            srv.shutdown()
            srv.server_close()
            be.close()

    return reps, router, router.server_address[1], close


def _disagg_request(rport: int, stream: bool, seed=None,
                    salt: str = "") -> dict:
    """One long-prompt completion (over the split threshold) through the
    router; {text, error, status}. `seed` switches to pinned-seed
    stochastic sampling (the seeded half of the byte-identity bar).
    `salt` makes the prompt unique per cell: a Q80-wire split leaves
    BOUNDED-ERROR KV in the decode replica's directory by design, so a
    later same-prompt request would legitimately decode from degraded
    rows — byte-identity cells must not share prompts across wire modes."""
    from distributed_llama_tpu.fleet.client import completion_request

    body = {"messages": [
        {"role": "system", "content": "s" * 64},
        {"role": "user", "content": f"tell me something {salt}"}],
        "max_tokens": 10, "temperature": 0, "stream": stream}
    if seed is not None:
        body.update(temperature=0.9, seed=seed)
    return completion_request(rport, body, timeout=120)


def _disagg_leak_check(be, tag: str) -> list[str]:
    """Post-family invariants for one replica engine: slots/leases/queue
    quiesce empty and the device block pool's refcounts BALANCE — every
    reference is attributable to the pinned scratch block, a slot table
    entry, or a directory dev node (an imported/exported transfer must not
    leave a stray pool reference on either side)."""
    problems: list[str] = []
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        with be._plock:
            leaked = [s for s in be._slots
                      if s.req is not None or s.lease is not None]
        if not leaked and not be._pending and be._queue.empty():
            break
        time.sleep(0.01)
    else:
        problems.append(f"{tag}: slot/lease leak after disagg family")
        return problems
    if be.kv_pool is not None:
        total = int(be.kv_pool.refcounts().sum())
        slots = sum(len(s.blocks) for s in be._slots)
        dev_nodes = (be.prefix_cache.stats()["dev_blocks"]
                     if be.prefix_cache is not None else 0)
        want = 1 + slots + dev_nodes  # scratch + tables + directory
        if total != want:
            problems.append(
                f"{tag}: block-pool refcount leak (total {total}, "
                f"accounted {want} = 1 scratch + {slots} slot-table + "
                f"{dev_nodes} directory)")
    return problems


def run_disagg_family() -> tuple[int, list[str]]:
    from distributed_llama_tpu.obs import metrics as obs_metrics

    cells = 0
    problems: list[str] = []
    for q80 in (False, True):
        tag = f"disagg-{'q80' if q80 else 'raw'}"
        reps, router, rport, close = build_disagg_fleet(q80)
        state = router.router_state
        try:
            # non-vacuity: a fault-free request must actually SPLIT (and on
            # the bit-exact raw wire, still match the monolithic reference)
            s0 = (obs_metrics.snapshot()
                  .get("router_disagg_requests_total") or {})
            r = _disagg_request(rport, stream=False, salt=f"warm-{tag}")
            s1 = (obs_metrics.snapshot()
                  .get("router_disagg_requests_total") or {})
            key = '{outcome="split"}'
            if (s1.get(key, 0) or 0) <= (s0.get(key, 0) or 0):
                problems.append(f"{tag}: family vacuous — the fault-free "
                                "request never split")
            if not q80:
                state.disagg.threshold = 0
                ref = _disagg_request(rport, stream=False,
                                      salt=f"warm-{tag}")
                state.disagg.threshold = 24
                if r["text"] != ref["text"]:
                    problems.append(
                        f"{tag}: raw-wire split output diverged "
                        f"({r['text']!r:.40} vs {ref['text']!r:.40})")
            for point in DISAGG_POINTS:
                for stream in (True, False):
                    cells += 1
                    name = (f"{tag}/{point}/"
                            f"{'stream' if stream else 'nonstream'}")
                    for seed in (None, 777):
                        # per-cell prompt (see _disagg_request salt note);
                        # the FAULTED request runs first — its import dies,
                        # the local-prefill fallback commits BIT-EXACT rows
                        # — then the monolithic reference, so the identity
                        # comparison is degraded-path vs clean-path, not
                        # cache-warmth luck. count=64 outlives every
                        # per-chunk retry: the prefill replica is
                        # effectively dead for the whole transfer.
                        salt = (f"{point[7]}{int(stream)}"
                                f"{0 if seed is None else 1}{int(q80)}")
                        with faults.active(FaultSpec(point, kind="error",
                                                     count=64)):
                            res = _disagg_request(rport, stream, seed,
                                                  salt=salt)
                        faults.uninstall()
                        if (res["error"] is not None
                                or res["status"] != 200):
                            problems.append(f"{name}: client-visible "
                                            f"failure {res!r}")
                            continue
                        state.disagg.threshold = 0
                        ref = _disagg_request(rport, stream=False,
                                              seed=seed, salt=salt)
                        state.disagg.threshold = 24
                        if res["text"] != ref["text"]:
                            problems.append(
                                f"{name}: fallback output diverged "
                                f"(seed={seed}, {res['text']!r:.40} vs "
                                f"{ref['text']!r:.40})")
            if not q80:
                # planner-leg cells: the split must fail CLOSED into the
                # monolithic path — same client answer, prefill_error
                # counted (non-vacuity)
                for point in DISAGG_PLAN_POINTS:
                    cells += 1
                    name = f"{tag}/{point}"
                    salt = f"p{point[7]}"
                    e0 = (obs_metrics.snapshot()
                          .get("router_disagg_requests_total") or {})
                    with faults.active(FaultSpec(point, kind="error",
                                                 count=4)):
                        res = _disagg_request(rport, False, None, salt=salt)
                    faults.uninstall()
                    e1 = (obs_metrics.snapshot()
                          .get("router_disagg_requests_total") or {})
                    ekey = '{outcome="prefill_error"}'
                    if res["error"] is not None or res["status"] != 200:
                        problems.append(f"{name}: client-visible failure "
                                        f"{res!r}")
                        continue
                    if (e1.get(ekey, 0) or 0) <= (e0.get(ekey, 0) or 0):
                        problems.append(f"{name}: vacuous — no "
                                        "prefill_error counted")
                    state.disagg.threshold = 0
                    ref = _disagg_request(rport, stream=False, salt=salt)
                    state.disagg.threshold = 24
                    if res["text"] != ref["text"]:
                        problems.append(
                            f"{name}: monolithic-fallback output diverged "
                            f"({res['text']!r:.40} vs {ref['text']!r:.40})")
            for be, _srv, port, role in reps:
                problems += _disagg_leak_check(be, f"{tag}/{role}:{port}")
        finally:
            faults.uninstall()
            close()
    return cells, problems


# ----------------------------------------------------------------------
# gray-failure family: sustained-slow replica, probation, hedging
# ----------------------------------------------------------------------

def _gray_request(rport: int, stream: bool, seed=None, salt: str = "",
                  scatter: str = "") -> dict:
    """One short completion through the router; {text, error, status}.
    `scatter` (when set) replaces the shared system prompt with a UNIQUE
    one: affinity would otherwise pin every request to one replica and the
    victim would never see the traffic detection needs — a cold prefix
    falls back to least-loaded with round-robin ties, alternating replicas.
    The unique part must LEAD the prompt (the affinity key is block-
    granular: a shared 16-byte prefix block still pins). Scattered requests
    are liveness probes only (their text depends on the prompt, so identity
    is asserted on the fixed-prompt requests)."""
    from distributed_llama_tpu.fleet.client import completion_request

    body = {"messages": [
        {"role": "system", "content": scatter or "gray fleet system prompt"},
        {"role": "user", "content": f"ab ab {salt}"}],
        "max_tokens": 6, "temperature": 0, "stream": stream}
    if seed is not None:
        body.update(temperature=0.9, seed=seed)
    return completion_request(rport, body, timeout=120)


def run_gray_mode(state, reps, rport: int, victim, mode: str,
                  refs: dict) -> list[str]:
    """One gray-failure mode over the shared fleet: configure the
    resilience layer for `mode`, sustain-slow the victim, and assert the
    family's invariants (module docstring at GRAY_MODES)."""
    from distributed_llama_tpu.fleet.latency import TokenBudget
    from distributed_llama_tpu.obs import metrics as obs_metrics

    problems: list[str] = []
    name = f"gray/{mode}"
    g = state.gray
    # mode wiring (fields mutated in place — the detector and membership
    # hold the same GrayConfig object)
    g.hedge = mode == "hedge"
    if mode == "timeout":
        # adaptive pre-first-byte timeout armed TIGHT: tries to the victim
        # are cut (censored-sample recorded) and failed over
        g.min_lat_samples = 8
        g.ttfb_floor, g.ttfb_cap, g.ttfb_mult = 0.2, None, 2.0
        delay_ms = 1200.0
    elif mode == "hedge":
        # fixed timeout (floor == cap) isolates hedging as the mechanism;
        # fixed hedge delay — with one of two replicas slow, HALF the
        # samples are slow and an adaptive p95 delay would defer itself
        g.min_lat_samples = 8
        g.ttfb_floor = g.ttfb_cap = 60.0
        g.hedge_delay = 0.2
        g.hedge_pct = 0.25
        state.hedge_budget = TokenBudget(g.hedge_pct, g.hedge_burst)
        delay_ms = 600.0
    else:  # "route": detection + probation only, timeouts/hedging at caps
        g.min_lat_samples = 10 ** 9
        g.ttfb_floor, g.ttfb_cap = 5.0, None
        delay_ms = 500.0
    # hedge-spend baseline from the LAUNCH-SITE counter, not the budget's
    # own ledger (gating stats()["spent"] against cap + rate*noted would be
    # tautological — TokenBudget enforces that internally by construction;
    # a regression that launches without spending must still fail the gate)
    h0 = (obs_metrics.snapshot().get("router_hedges_total") or {}).get(
        '{outcome="launched"}', 0)
    i = 0
    with faults.active(FaultSpec("api.request", kind="latency",
                                 delay_ms=delay_ms,
                                 match={"replica": victim.id})):
        # identity drive: fixed prompt, stream x {greedy, pinned-seed} —
        # every response client-clean and byte-identical to the reference
        for stream in (True, False):
            for seed in (None, 777):
                res = _gray_request(rport, stream, seed)
                tag = (f"{name}/{'stream' if stream else 'nonstream'}"
                       f"/seed={seed}")
                if res["error"] is not None or res["status"] != 200:
                    problems.append(f"{tag}: client-visible failure {res!r}")
                elif res["text"] != refs[(stream, seed)]:
                    problems.append(f"{tag}: diverged ({res['text']!r:.40} "
                                    f"vs {refs[(stream, seed)]!r:.40})")
        # probation entry: scattered probes keep outcome samples flowing to
        # BOTH replicas until the detector flags the victim. The budget is
        # generous: hedged rounds leave the victim's (losing) attempts
        # holding inflight counts, so least-loaded picks it only when idle
        # — its sampling rate is a fraction of the probe rate.
        deadline = time.monotonic() + 60
        while not victim.degraded and time.monotonic() < deadline:
            res = _gray_request(rport, i % 2 == 0, salt=str(i),
                                scatter=f"p{i:04d} {name} probe")
            if res["error"] is not None or res["status"] != 200:
                problems.append(f"{name}: probe failure {res!r}")
                break
            i += 1
            state.membership.poll_once()
        if not victim.degraded:
            problems.append(f"{name}: victim never entered probation "
                            f"({victim.snapshot()})")
    faults.uninstall()
    # probation exit: the injection cleared — canary traffic must rejoin
    # the victim within probation_exits in-band outcomes
    deadline = time.monotonic() + 30
    while victim.degraded and time.monotonic() < deadline:
        res = _gray_request(rport, i % 2 == 0, salt=str(i),
                            scatter=f"c{i:04d} {name} canary")
        if res["error"] is not None or res["status"] != 200:
            problems.append(f"{name}: canary failure {res!r}")
            break
        i += 1
        state.membership.poll_once()
    if victim.degraded:
        problems.append(f"{name}: victim never rejoined after the "
                        "injection cleared")
    state.membership.poll_once()
    if len(state.membership.in_rotation()) != len(reps):
        problems.append(f"{name}: rotation did not recover")
    if mode == "hedge":
        st = state.hedge_budget.stats()
        launched = (obs_metrics.snapshot().get("router_hedges_total")
                    or {}).get('{outcome="launched"}', 0) - h0
        allowance = st["cap"] + g.hedge_pct * st["noted"]
        if launched < 1:
            problems.append(f"{name}: vacuous — no hedge launched")
        if launched > allowance:
            problems.append(f"{name}: hedge spend {launched} over budget "
                            f"(allowance {allowance:.1f})")
    # no router-side inflight leak (hedge losers must release their counts)
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        leaked = [r.id for r in state.membership.replicas if r.inflight != 0]
        if not leaked:
            break
        time.sleep(0.02)
    else:
        problems.append(f"{name}: router inflight leak on {leaked}")
    return problems


def run_gray_family() -> tuple[int, list[str]]:
    from distributed_llama_tpu.fleet.latency import GrayConfig

    cells = 0
    problems: list[str] = []
    cfg = GrayConfig(eject_multiple=3.0, min_samples=4, probation_exits=2,
                     quorum_frac=0.5, canary_every=2,
                     min_lat_samples=10 ** 9, hedge=False)
    reps, router, rport, close = build_durable_fleet(
        router_kwargs={"gray": cfg})
    state = router.router_state
    victim = state.membership.by_id(f"127.0.0.1:{reps[0][2]}")
    try:
        refs = {}
        for stream in (True, False):
            for seed in (None, 777):
                r = _gray_request(rport, stream, seed)
                if r["error"] is not None:
                    problems.append(
                        f"gray: fault-free reference failed: {r!r}")
                    return GRAY_CELLS, problems
                refs[(stream, seed)] = r["text"]
        if refs[(True, None)] != refs[(False, None)]:
            problems.append("gray: stream vs non-stream reference mismatch")
        for mode in GRAY_MODES:
            cells += 2  # the mode drives stream AND nonstream cells
            problems += run_gray_mode(state, reps, rport, victim, mode, refs)
    finally:
        faults.uninstall()
        close()
    return cells, problems


def _sweep(points, cell, tag: str = "") -> tuple[int, list[str]]:
    """`cell(point, kind)` over points x KINDS: (cells run, problems)."""
    cells, problems = 0, []
    for point in points:
        for kind in KINDS:
            cells += 1
            problems += [f"[{tag}] {p}" if tag else p
                         for p in cell(point, kind)]
    return cells, problems


def run_batch_family(pipeline: bool) -> tuple[int, list[str]]:
    """Every batch point under one scheduler: pipelined (the default:
    overlapped dispatches, speculative chains that faults must flush
    cleanly) or serialized."""
    bspec, be = build_batch_engine(pipeline=pipeline)
    try:
        return _sweep(BATCH_POINTS,
                      lambda pt, kind: run_batch_cell(bspec, be, pt, kind),
                      "pipelined" if pipeline else "serialized")
    finally:
        be.close()


def run_spec_family(pipeline: bool) -> tuple[int, list[str]]:
    """The batch invariants with batched draft-verify super-steps engaged,
    plus survivor token-identity (docs/SERVING.md "Speculative decoding")."""
    bspec, be = build_batch_engine(pipeline=pipeline, speculative=4)
    try:
        refs = spec_reference(bspec, be)
        return _sweep(SPEC_POINTS,
                      lambda pt, kind: run_spec_cell(bspec, be, pt, kind, refs),
                      "spec-pipelined" if pipeline else "spec-serialized")
    finally:
        be.close()


def run_engine_family(paged: bool) -> tuple[int, list[str]]:
    """The sequential Engine, or the one over the host/disc out-of-core
    cache (its per-layer host callbacks dominate the matrix wall time)."""
    spec, eng = build_engine(paged=paged)
    return _sweep(PAGED_POINTS if paged else ENGINE_POINTS,
                  lambda pt, kind: run_engine_cell(spec, eng, pt, kind,
                                                   paged=paged))


def run_router_family() -> tuple[int, list[str]]:
    router, stubs = build_router_fleet()
    try:
        return _sweep(ROUTER_POINTS,
                      lambda pt, kind: run_router_cell(router, pt, kind))
    finally:
        from distributed_llama_tpu.fleet.router import close_router

        close_router(router)
        for s in stubs:
            s.shutdown()
            s.server_close()


# The matrix, family by family: the cells each has to run, and its runner,
# which returns (cells run, problems). main() and tests/test_fault_matrix.py
# (one case a family) both walk this table; there is no second list.
FAMILIES = {
    "batch-pipelined": (len(BATCH_POINTS) * len(KINDS),
                        lambda: run_batch_family(True)),
    "batch-serialized": (len(BATCH_POINTS) * len(KINDS),
                         lambda: run_batch_family(False)),
    "spec-pipelined": (len(SPEC_POINTS) * len(KINDS),
                       lambda: run_spec_family(True)),
    "spec-serialized": (len(SPEC_POINTS) * len(KINDS),
                        lambda: run_spec_family(False)),
    "engine": (len(ENGINE_POINTS) * len(KINDS),
               lambda: run_engine_family(paged=False)),
    "paged": (len(PAGED_POINTS) * len(KINDS),
              lambda: run_engine_family(paged=True)),
    "router": (len(ROUTER_POINTS) * len(KINDS), run_router_family),
    # hung-engine supervision + durable mid-stream failover (ISSUE 9)
    "supervisor": (SUPERVISOR_CELLS,
                   lambda: (SUPERVISOR_CELLS, run_supervisor_cell())),
    "durability": (DURABILITY_CELLS, run_durability_family),
    "fairness": (FAIRNESS_CELLS, run_fairness_family),
    "disagg": (DISAGG_CELLS, run_disagg_family),
    "gray": (GRAY_CELLS, run_gray_family),
    "draft": (DRAFT_CELLS, run_draft_family),
    "fused": (FUSED_CELLS, run_fused_family),
    "constrain": (CONSTRAIN_CELLS, run_constrain_family),
}


def run_matrix(include_paged: bool = True) -> tuple[int, list[str]]:
    cells = 0
    problems: list[str] = []
    for name, (_, run) in FAMILIES.items():
        if name == "paged" and not include_paged:
            continue
        n, found = run()
        cells += n
        problems += found
    return cells, problems


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-paged", action="store_true",
                    help="skip the paged-engine family (its per-layer host "
                         "callbacks dominate the matrix wall time)")
    args = ap.parse_args()
    t0 = time.perf_counter()
    cells, problems = run_matrix(include_paged=not args.skip_paged)
    for p in problems:
        print(p, file=sys.stderr)
    print(json.dumps({"metric": "fault_matrix_cells", "value": cells,
                      "unit": "cells", "vs_baseline": None,
                      "failures": len(problems),
                      "seconds": round(time.perf_counter() - t0, 1)}))
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
