"""Compile-manifest gate (rule `compile-manifest`): recompile-creep auditor.

A TPU serving process must settle into a FIXED set of compiled programs —
the forward step per window bucket, the K-step scan per (k, mode), the
verify block per (T, mode) — each dispatched at a fixed set of array
shapes/dtypes. Recompile creep (a new T bucket minted on the latency path, a
dtype drifting through a refactor, a shape leaking per-request) is invisible
to unit tests and expensive on hardware: XLA compiles mid-traffic and the
request eating the compile times out.

This auditor is runtime-assisted: `CompileAudit` patches the program
factories (`make_sharded_forward`, `make_decode_loop`,
`make_batched_decode_loop`, `make_batched_verify_loop`) to record

  - every PROGRAM BUILD, keyed by factory + static config
    (e.g. ``batched_scan[k=4,mode=greedy,window=None]``), and
  - every DISPATCH SIGNATURE per program — the (dtype, shape) tuple of each
    array argument (list args by length) — since jit caches per abstract
    value, each distinct signature is a distinct XLA lowering.

`run_scenario` drives the real BatchEngine through a fixed tiny-model
script: prefill (8+1 chunks), K-step scans, pipelined chaining, draft-verify
blocks, a stochastic row, a durable-resume admission, and steps issued
ahead from the token carry. The observed
manifest is diffed against the pinned ``perf/compile_manifest.json``:

  - a program key absent from the pin  -> finding (new compiled program)
  - a signature absent under its key   -> finding (new dispatch shape)
  - observed ⊂ pinned                  -> ok (scheduling may not exercise
    every pinned shape on every run; the gate is one-sided by design)

When a new dispatch shape is INTENTIONAL (a new feature legitimately adds a
program), re-pin with ``python perf/dlint.py --update-manifest`` and review
the manifest diff like any other lockfile (docs/ANALYSIS.md).
"""

from __future__ import annotations

import json
import os
from contextlib import ExitStack

from .core import REPO, Finding

MANIFEST_PATH = os.path.join(REPO, "perf", "compile_manifest.json")
_MANIFEST_REL = os.path.join("perf", "compile_manifest.json")


def _describe(a) -> str:
    """Compact, stable descriptor of one dispatch argument."""
    if hasattr(a, "shape") and hasattr(a, "dtype"):
        return f"{a.dtype}{tuple(a.shape)}"
    if isinstance(a, (list, tuple)):
        if a and isinstance(a[0], (list, tuple)):
            return f"list({len(a)}x{len(a[0])})"
        return f"list({len(a)})"
    if isinstance(a, dict):
        return "tree"
    if isinstance(a, (bool, int, float)):
        return type(a).__name__
    return type(a).__name__


class CompileAudit:
    """Records program builds + dispatch signatures while active (a context
    manager patching the factory modules; nesting is not supported)."""

    def __init__(self):
        # key -> {"builds": int, "signatures": set[str]}
        self.programs: dict[str, dict] = {}
        self._stack: ExitStack | None = None

    # -- recording ------------------------------------------------------

    def _program(self, key: str) -> dict:
        if key not in self.programs:
            self.programs[key] = {"builds": 0, "signatures": set()}
        return self.programs[key]

    def record_build(self, key: str) -> None:
        self._program(key)["builds"] += 1

    def record_call(self, key: str, args: tuple) -> None:
        sig = " ".join(_describe(a) for a in args)
        self._program(key)["signatures"].add(sig)

    def _wrap(self, key: str, fn):
        def wrapped(*args, **kw):
            self.record_call(key, args)
            return fn(*args, **kw)

        return wrapped

    def _patch_factory(self, module, name: str, keyfn):
        orig = getattr(module, name)

        def factory(*args, **kw):
            key = keyfn(*args, **kw)
            self.record_build(key)
            return self._wrap(key, orig(*args, **kw))

        setattr(module, name, factory)
        self._stack.callback(setattr, module, name, orig)

    # -- lifecycle ------------------------------------------------------

    def __enter__(self) -> "CompileAudit":
        from ..runtime import device_loop, engine

        self._stack = ExitStack()

        def _paged(kw):
            # device-resident paged KV (docs/PAGED_KV.md): the block size is
            # part of the cache key — a paged program's table/pool shapes
            # are distinct lowerings from the dense layout's
            bt = kw.get("kv_block_tokens", 0)
            return f",paged={bt}" if bt else ""

        def _kern(kw):
            # kernel-policy dimension (ops/matmul.py): an engine with the
            # Pallas kernels on lowers other programs from the same shapes
            # (and holds its weights in the kernels' layout), so its buckets
            # pin under their own keys. Kernels off adds nothing, so every
            # kernel-off key is the one it always was.
            return ",kernel=1" if kw.get("use_pallas") else ""

        def _mask(kw):
            # grammar-constrained variants (constrain/, docs/SERVING.md
            # "Constrained decoding"): masked programs are SEPARATE
            # lowerings (constraint-table operands + automaton carry) and
            # pin under their own keys. Boolean policy: the default
            # (unmasked) adds nothing, so every pre-existing pinned key is
            # unchanged.
            return ",mask=1" if kw.get("masked") else ""

        def _latent(spec):
            # a latent spec's programs (models/forward.py
            # `_latent_attention`: one cache row a token, a second side of
            # width 0; a leading stack beside the blocks) are other
            # lowerings from the same policy. Boolean: any other spec adds
            # nothing, so every pre-existing pinned key is unchanged.
            return ",latent=1" if spec.latent else ""

        def _static(kw):
            return (f"mode={kw.get('mode', 'greedy')},"
                    f"window={kw.get('attn_window')}"
                    f"{_paged(kw)}{_kern(kw)}{_mask(kw)}")

        self._patch_factory(
            engine, "make_sharded_forward",
            lambda spec, mesh, params, **kw:
                f"forward_step[window={kw.get('attn_window')}"
                f"{_paged(kw)}{_kern(kw)}{_latent(spec)}]")
        self._patch_factory(
            device_loop, "make_decode_loop",
            lambda spec, mesh, params, n, **kw:
                f"decode_loop[n={n},{_static(kw)}]")
        self._patch_factory(
            device_loop, "make_batched_decode_loop",
            lambda spec, mesh, params, n, **kw:
                f"batched_scan[k={n},{_static(kw)}{_latent(spec)}]")
        self._patch_factory(
            device_loop, "make_batched_verify_loop",
            lambda spec, mesh, params, t, **kw:
                f"verify[t={t},{_static(kw)}]")
        # model drafter programs (draft/, docs/SERVING.md "Model-based
        # drafting") — patched at the DRAFTER's namespace (its module-global
        # names bound at import, like engine.make_sharded_forward above)
        from ..draft import drafter as draft_drafter

        self._patch_factory(
            draft_drafter, "make_draft_loop",
            lambda spec, mesh, params, s, **kw:
                f"draft_scan[s={s}{_kern(kw)}]")
        self._patch_factory(
            draft_drafter, "make_draft_step",
            lambda spec, mesh, params, **kw:
                f"draft_step[window={kw.get('attn_window')}{_kern(kw)}]")
        return self

    def __exit__(self, *exc) -> None:
        self._stack.close()
        self._stack = None

    # -- export ---------------------------------------------------------

    def manifest(self) -> dict:
        return {"programs": {
            key: {"builds": rec["builds"],
                  "signatures": sorted(rec["signatures"])}
            for key, rec in sorted(self.programs.items())}}


# ----------------------------------------------------------------------
# the fixed scenario script
# ----------------------------------------------------------------------

def scenario_spec():
    """Tiny 2-layer model, seq_len 64 (< the window-bucket floor, so exactly
    one forward-step window compiles) — the same scale the spec-amortize and
    fault-matrix tier-1 gates run at."""
    from ..models.spec import ArchType, ModelSpec, RopeType

    return ModelSpec(arch_type=ArchType.LLAMA, dim=64, hidden_dim=128,
                     n_layers=2, n_heads=4, n_kv_heads=4, vocab_size=256,
                     seq_len=64, rope_type=RopeType.LLAMA).resolved()


def run_scenario(keep_engine: bool = False):
    """Drive the real BatchEngine through every serving phase the manifest
    pins: prefill (8+1 chunks), greedy K-step scans with pipelined chaining,
    a stochastic scan row, draft-verify blocks on a repetitive prompt, and a
    durable-resume admission (which must reuse the existing programs, not
    mint new ones). Deterministic by construction: fixed prompts, fixed
    seeds, phases serialized by wait()."""
    from ..models.params import init_random_params
    from ..quants import FloatType
    from ..runtime.batch_engine import BatchEngine
    from ..runtime.sampler import Sampler

    spec = scenario_spec()
    params = init_random_params(spec, FloatType.Q40, seed=11)
    eng = BatchEngine(spec, params, slots=2, superstep=4, pipeline=True,
                      speculative=4, spec_min_draft=1, tp=1,
                      prefix_cache=True)
    V = spec.vocab_size
    ok = False
    try:
        # phase 1 — prefill + greedy scans + pipelined chain: two co-batched
        # greedy requests; 9-token prompts prefill as one 8-chunk + one
        # 1-chunk; 12 decode tokens at k=4 exercise chained super-steps.
        # Non-repetitive prompts keep the n-gram drafts empty (scan path).
        p1 = [(7 * i + 3) % V for i in range(9)]
        p2 = [(11 * i + 5) % V for i in range(9)]
        r1 = eng.submit(p1, 12, Sampler(V))
        r2 = eng.submit(p2, 12, Sampler(V))
        r1.wait(60)
        r2.wait(60)
        # phase 2 — stochastic scan: one seeded sampled request alone, so
        # the sample-mode scan program (and its rng upload shape) pins.
        rs = eng.submit(p1, 8, Sampler(V, temperature=0.8, seed=7))
        out_s = rs.wait(60)
        # phase 3 — draft-verify: a repetitive prompt makes the per-slot
        # NgramIndex propose full drafts, engaging the (B, T) verify blocks.
        rep = [9, 21, 33] * 6
        rv = eng.submit(rep, 12, Sampler(V))
        rv.wait(60)
        # phase 4 — durable resume: re-admit phase 2's request as a
        # mid-stream failover would (prompt ⊕ delivered, fast-forwarded
        # sampler). Resume is an ADMISSION property: it must ride the
        # existing prefill/scan programs — a resume-only program key in the
        # manifest diff is itself the defect this phase exists to catch.
        smp = Sampler(V, temperature=0.8, seed=7)
        smp.fast_forward(len(out_s))
        rr = eng.submit(p1 + out_s, 6, smp, resume_tokens=len(out_s))
        rr.wait(60)
        # phase 5 — paged remap admission (docs/PAGED_KV.md): re-admit a
        # directory-covered prompt so the zero-copy block-table remap path
        # runs. Remap is table METADATA only — it must ride the existing
        # prefill/scan programs at their pinned signatures; a remap-shaped
        # program key or a table-shape drift here is exactly the
        # block-table recompile creep this gate exists to catch.
        rm = eng.submit(list(p2), 6, Sampler(V))
        rm.wait(60)
        # phase 6 — disaggregation import-seeded admission (docs/DISAGG.md):
        # a NEVER-SERVED prompt whose KV "arrives over the wire"
        # (import_kv_blocks → cold directory nodes, round-tripped through
        # the codec like a real transfer) and is promoted to device at
        # admission. The import is host bookkeeping and the promotion rides
        # the untracked single-block pool update; the admission itself must
        # ride the existing prefill/scan programs — an import-shaped
        # program key or signature here is disagg-induced recompile creep.
        if eng.kv_pool is not None:
            import numpy as _np

            from ..cache.wire import decode_blocks, encode_blocks

            bt = eng.slot_cache.block_tokens
            p3 = [(13 * i + 2) % V for i in range(bt + 1)]  # 1 full block
            L, _n, hk, _bt, hs = eng._eng.k_cache.shape
            rng = _np.random.default_rng(3)
            blocks = [(rng.standard_normal((L, hk, bt, hs))
                       .astype(_np.float32),
                       rng.standard_normal((L, hk, bt, hs))
                       .astype(_np.float32))]
            eng.import_kv_blocks(p3[:bt], decode_blocks(
                encode_blocks(blocks)))
            ri = eng.submit(list(p3), 4, Sampler(V))
            ri.wait(60)
        # phase 7 — model-based drafting (docs/SERVING.md "Model-based
        # drafting"): a SECOND engine, identical config plus a co-resident
        # drafter sharing the target's params (self-draft: full acceptance,
        # so the drafter's scan cadence — and thus the pinned draft_scan
        # bucket set — is deterministic). Target-side programs ride the
        # same keys/signatures the first engine pinned; the drafter adds
        # ONLY draft_scan[s=...] buckets. Adaptive-k runs live here — its
        # buckets must never mint a verify program outside the pinned
        # t=2/3/5 set (the "zero recompile creep under adaptive-k bucket
        # churn" acceptance gate).
        eng2 = BatchEngine(spec, params, slots=2, superstep=4, pipeline=True,
                           speculative=4, spec_min_draft=1, tp=1,
                           prefix_cache=True,
                           draft_model=(spec, params))
        try:
            rd = eng2.submit([(7 * i + 3) % V for i in range(9)], 12,
                             Sampler(V))
            rd.wait(60)
            rd2 = eng2.submit([(5 * i + 1) % V for i in range(6)], 8,
                              Sampler(V))
            rd2.wait(60)
            # long prompt: attach-time pending exceeds the in-scan catch-up
            # cap, so the drafter's chunked prefill program (draft_step)
            # pins alongside the scan buckets
            rd3 = eng2.submit([(3 * i + 2) % V for i in range(20)], 6,
                              Sampler(V))
            rd3.wait(60)
        finally:
            eng2.close()
        # phase 8 — the Pallas kernels on (ops/pallas_q4_mm.py): a THIRD
        # engine with use_pallas=True, so every program the batched serving
        # path builds with the kernels pins under its own `kernel=1` key.
        # The co-resident self-drafter makes verify engagement deterministic
        # for ANY prompt (n-gram proposals on a fresh engine are not) and
        # pins the drafter's own draft_scan/draft_step buckets; the
        # reachable T buckets must stay inside the kernel-off t=2/3/5 set:
        # a kernel key minting a rogue T bucket fails the gate by name.
        eng3 = BatchEngine(spec, params, slots=2, superstep=4, pipeline=True,
                           speculative=4, spec_min_draft=1, tp=1,
                           use_pallas=True,
                           draft_model=(spec, params))
        try:
            rf1 = eng3.submit(p1, 12, Sampler(V))
            rf2 = eng3.submit(p2, 12, Sampler(V))
            rf1.wait(60)
            rf2.wait(60)
            # seeded stochastic row: sample-mode scan + verify under the
            # kernel key (the greedy/sample × kernel-on cross)
            rfs = eng3.submit(p1, 8, Sampler(V, temperature=0.8, seed=7))
            rfs.wait(60)
            rfv = eng3.submit(rep, 12, Sampler(V))
            rfv.wait(60)
        finally:
            eng3.close()
        # phase 9 — grammar-constrained decoding (constrain/,
        # docs/SERVING.md "Constrained decoding"): constrained rows
        # co-batched with a plain row on a FOURTH engine, greedy AND
        # seeded-stochastic, with speculation on so the GrammarProposer's
        # forced chains engage the masked verify buckets. Masked programs
        # pin under their own mask=1 keys (separate lowerings: constraint
        # table operands + automaton carry); the unmasked keys must stay
        # untouched — a masked dispatch minting a bucket outside the
        # pinned t set, or leaking onto an unmasked key, fails the gate
        # by name.
        from ..constrain import byte_vocab, compile_grammar

        cv = byte_vocab(V)
        aut, gh = compile_grammar(
            "json_schema",
            {"type": "object", "properties": {
                "name": {"enum": ["alpha", "beta"]},
                "ok": {"type": "boolean"}}}, cv, eos_id=2)
        eng4 = BatchEngine(spec, params, slots=2, superstep=4,
                           pipeline=True, speculative=4, spec_min_draft=1,
                           tp=1, prefix_cache=True)
        try:
            rc1 = eng4.submit(p1, 12, Sampler(V), constraint=aut,
                              constraint_hash=gh)
            rc2 = eng4.submit(rep, 12, Sampler(V))  # plain co-batched row
            rc1.wait(60)
            rc2.wait(60)
            rcs = eng4.submit(p2, 10, Sampler(V, temperature=0.8, seed=7),
                              constraint=aut, constraint_hash=gh)
            rcs.wait(60)
            # a branching-only grammar (no singleton-mask states, so the
            # GrammarProposer never drafts and n-gram finds nothing on a
            # fresh prompt): constrained rows ride the masked K-step SCAN
            # buckets — greedy and sampled — instead of verify
            aut2, gh2 = compile_grammar("regex", "[a-z]{24}", cv, eos_id=2)
            rm1 = eng4.submit(p1, 10, Sampler(V), constraint=aut2,
                              constraint_hash=gh2)
            rm1.wait(60)
            rm2 = eng4.submit(p2, 8, Sampler(V, temperature=0.8, seed=7),
                              constraint=aut2, constraint_hash=gh2)
            rm2.wait(60)
        finally:
            eng4.close()
        # phase 10 — a latent spec (the DeepSeek-V3 graph at a toy size:
        # one latent cache row a token, a leading dense layer in a stack of
        # its own, 2 of 8 sigmoid-routed experts of which 4 are held, a
        # shared expert, YaRN) on a FIFTH engine, kernels off and on: its
        # prefill chunks and K-step scans pin under their own `latent=1`
        # keys, and every key that was there stays as it was.
        from ..models.spec import ArchType, ModelSpec, RopeType, RouterScore

        lspec = ModelSpec(
            arch_type=ArchType.MIXTRAL, dim=64, hidden_dim=32, n_layers=3,
            n_heads=4, n_kv_heads=1, vocab_size=V, seq_len=64, n_experts=4,
            n_active_experts=2, rope_type=RopeType.YARN,
            rope_scaling_factor=4.0, rope_scaling_orig_max_seq_len=16,
            yarn_mscale_all_dim=1.0, q_lora_rank=32, kv_lora_rank=32,
            qk_nope_head_dim=32, qk_rope_head_dim=8, v_head_dim=32,
            lead_layers=1, lead_hidden_dim=128, shared_hidden_dim=32,
            router_score=RouterScore.SIGMOID, router_scale=2.5,
            router_width=8, expert_offset=4).resolved()
        lparams = init_random_params(lspec, FloatType.Q40, seed=11)
        for kernels in (False, True):
            eng5 = BatchEngine(lspec, lparams, slots=2,
                               superstep=4, pipeline=True, tp=1,
                               prefix_cache=True, use_pallas=kernels)
            try:
                rl1 = eng5.submit(p1, 12, Sampler(V))
                rl2 = eng5.submit(p2, 12, Sampler(V))
                rl1.wait(60)
                rl2.wait(60)
            finally:
                eng5.close()
        # phase 11 — steps issued ahead (docs/SERVING.md "Pipelined
        # decode"): a SIXTH engine with speculation off, whose greedy rows
        # are sampled in the step program: a prefill chunk, a mixed step and
        # a single step issued from the token carry of the step before, a
        # scan from host state behind them. The carry is an argument of EVERY step
        # dispatch (zeros where nothing is carried), so these ride the
        # forward_step and batched_scan signatures the first engine pinned:
        # a run-ahead-only key or signature here is the defect.
        eng6 = BatchEngine(spec, params, slots=2, superstep=4, pipeline=True,
                           tp=1, prefix_cache=True)
        try:
            ra1 = eng6.submit(p1, 12, Sampler(V))
            ra2 = eng6.submit([(5 * i + 2) % V for i in range(19)], 9,
                              Sampler(V))
            ra1.wait(60)
            ra2.wait(60)
        finally:
            eng6.close()
        ok = True
    finally:
        # a failed phase must not leak a live engine (scheduler thread +
        # params + KV caches for the rest of the process) — keep_engine
        # hands the engine out only on success
        if not keep_engine or not ok:
            eng.close()
    return eng if keep_engine else None


# ----------------------------------------------------------------------
# manifest diff / pin
# ----------------------------------------------------------------------

def load_manifest(path: str | None = None) -> dict | None:
    path = path or MANIFEST_PATH
    try:
        with open(path, encoding="utf-8") as fh:
            return json.load(fh)
    except OSError:
        return None


def diff_manifest(observed: dict, pinned: dict | None) -> list[Finding]:
    """Findings for every observed program/signature the pin does not cover.
    One-sided: pinned-but-unobserved entries are fine (scheduling may skip
    shapes on a given run)."""
    if pinned is None:
        return [Finding("compile-manifest", _MANIFEST_REL, 0,
                        "pinned manifest missing — run "
                        "`python perf/dlint.py --update-manifest`")]
    pinned_programs = pinned.get("programs", {})
    findings = []
    for key, rec in sorted(observed.get("programs", {}).items()):
        pin = pinned_programs.get(key)
        if pin is None:
            findings.append(Finding(
                "compile-manifest", _MANIFEST_REL, 0,
                f"recompile creep: program {key} compiled but is not in the "
                "pinned manifest (new cache key; if intentional, re-pin "
                "with `python perf/dlint.py --update-manifest`)"))
            continue
        known = set(pin.get("signatures", []))
        for sig in sorted(rec["signatures"]):
            if sig not in known:
                findings.append(Finding(
                    "compile-manifest", _MANIFEST_REL, 0,
                    f"recompile creep: program {key} dispatched at a new "
                    f"signature [{sig}] — a fresh XLA lowering on the "
                    "serving path (shape leak or dtype drift; if "
                    "intentional, re-pin)"))
    return findings


def check_manifest(manifest_path: str | None = None) -> list[Finding]:
    """Run the scenario under audit and diff against the pin (the
    `compile_gate=True` arm of analysis/runner.py)."""
    audit = CompileAudit()
    with audit:
        run_scenario()
    return diff_manifest(audit.manifest(), load_manifest(manifest_path))


def update_manifest(path: str | None = None) -> dict:
    """Re-run the scenario and pin the observed manifest. The diff against
    the previous pin is MERGED (union), never shrunk implicitly: shapes a
    particular run didn't exercise must not silently fall out of the pin —
    delete retired programs by hand, with review."""
    path = path or MANIFEST_PATH
    audit = CompileAudit()
    with audit:
        run_scenario()
    observed = audit.manifest()
    prev = load_manifest(path)
    if prev is not None:
        for key, rec in prev.get("programs", {}).items():
            mine = observed["programs"].setdefault(
                key, {"builds": rec.get("builds", 0), "signatures": []})
            mine["signatures"] = sorted(
                set(mine["signatures"]) | set(rec.get("signatures", [])))
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(observed, fh, indent=2, sort_keys=True)
        fh.write("\n")
    return observed
