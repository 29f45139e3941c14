"""ModelDrafter: a small sharded draft model co-resident on the target mesh
(docs/SERVING.md "Model-based drafting").

PR 8 built the general batched draft-verify machinery but fed it only n-gram
lookups, which go dry off repetition-heavy traffic. This drafter closes the
deferred hook: a second, much smaller model — loaded through the SAME
formats/converter path as the target (mfile loaders, Q40/Q80 supported) —
shares the target's mesh and drafts k tokens per row in ONE `lax.scan`
dispatch (draft/loop.py). The drafter's matmuls are tiny and memory-bound,
so co-residency steals negligible HBM bandwidth from the target model while
opening speculation to chat/code/open-ended rows.

Frontier bookkeeping (all host-side, scheduler thread only): per row the
drafter tracks `toks` (the row's full delivered stream: prompt ⊕ output —
re-attached whole on preemption re-admission and durable resume, so those
paths need nothing special), `frontier` (tokens whose KV the drafter has
ingested and CONFIRMED), and `spec_tail` (its own drafted tokens whose KV it
wrote speculatively during the last scan). When the target delivers a token
(push) that matches the head of spec_tail — exactly the accepted drafts, by
the verify identity — the frontier advances for FREE: the KV written while
drafting IS that token's KV. The first mismatch (the correction token)
drops the rest of the tail; its KV sits beyond the frontier on masked slots
and the next catch-up overwrites it — the same free-rollback discipline the
target engine uses. A proposal turn then force-ingests the handful of
pending tokens (usually just the correction/bonus) and free-runs k greedy
argmax steps, all in one bucketed scan dispatch for every served row.

Failure semantics: load and propose failures degrade — the caller
(runtime/speculative.py ProposerMux) falls back to n-gram drafting and
ultimately plain decode; a drafter can slow speculation down but never
surface to a client (fault points draft.load / draft.propose,
docs/ROBUSTNESS.md).
"""

from __future__ import annotations

import time

import numpy as np

from ..models.spec import ModelSpec
from ..obs import metrics, trace
from ..resilience import faults
from .loop import make_draft_loop, make_draft_step

_DISPATCHES = metrics.counter(
    "batch_draft_dispatches_total",
    "Drafter scan dispatches (one per served proposal turn)")
_DRAFTED = metrics.counter(
    "batch_draft_drafted_tokens_total",
    "Tokens drafted by the model drafter")
_CATCHUP = metrics.counter(
    "batch_draft_catchup_tokens_total",
    "Target-delivered tokens the drafter re-ingested in-scan to sync")
_PREFILL = metrics.counter(
    "batch_draft_prefill_tokens_total",
    "Tokens chunk-prefilled into the drafter KV (attach / long catch-up)")
_SPEC_HITS = metrics.counter(
    "batch_draft_frontier_hits_total",
    "Delivered tokens whose drafter KV was already written while drafting "
    "(frontier advanced with zero re-ingest work)")
_DISPATCH_SECONDS = metrics.histogram(
    "batch_draft_dispatch_seconds",
    "Wall time of one drafter scan dispatch")

# drafter prefill chunk: the drafter context is small and its weights tiny,
# so one shape covers attach-time catch-up without the target's 64-chunk —
# the sub-chunk tail is NOT prefilled token-by-token, it simply rides the
# proposal scan's catch-up phase (which runs anyway and carries up to
# catchup_cap tokens)
PREFILL_CHUNK = 16


class _Row:
    __slots__ = ("toks", "frontier", "spec_tail")

    def __init__(self, tokens: list[int]):
        self.toks = list(tokens)  # full stream: prompt ⊕ delivered output
        self.frontier = 0  # toks[:frontier] have confirmed drafter KV
        self.spec_tail: list[int] = []  # drafted tokens with speculative KV


class ModelDrafter:
    """Proposer-protocol drafter (runtime/speculative.py) backed by a small
    sharded model on the target's mesh. Scheduler-thread-only except
    stats(), which reads plain counters (a torn read only skews a stats
    scrape)."""

    name = "model"

    def __init__(self, spec: ModelSpec, params, *, mesh, slots: int,
                 target_spec: ModelSpec, tokenizer=None, dtype=None,
                 use_pallas: bool | str = False,
                 compress_collectives: bool = False,
                 moe_sharding: str = "slice", k_cap: int = 8):
        import jax.numpy as jnp

        from ..models.params import prepare_for_pallas
        from ..parallel.mesh import AXIS_TP
        from ..parallel.sharding import check_divisibility
        from ..parallel.tp import init_sharded_kv_cache, shard_params
        from ..ops.rope import RopeTables
        from ..quants import FloatType

        faults.fire("draft.load")
        # vocab compatibility: drafts are token IDS fed straight into the
        # target's verify block — the two models (and the serving tokenizer)
        # must share one vocabulary or every draft is garbage-at-best
        if spec.vocab_size != target_spec.vocab_size:
            raise ValueError(
                f"draft model vocab {spec.vocab_size} != target vocab "
                f"{target_spec.vocab_size} (the models must share a "
                "tokenizer)")
        if tokenizer is not None and tokenizer.vocab_size != spec.vocab_size:
            raise ValueError(
                f"draft model vocab {spec.vocab_size} != tokenizer vocab "
                f"{tokenizer.vocab_size}")
        tp = mesh.shape[AXIS_TP]
        check_divisibility(spec, tp, 1, moe_sharding=moe_sharding)
        self.spec = spec
        self.mesh = mesh
        self.slots = slots
        self.k_cap = max(int(k_cap), 1)
        # in-scan catch-up bound: past this the row chunk-prefills first.
        # 2k+1 covers the steady states (full-accept turn: 2 pending; a
        # K-step scan burst between verifies: K+1 pending)
        self.catchup_cap = 2 * self.k_cap + 1
        self.dtype = dtype if dtype is not None else jnp.float32
        # the kernel policy passes through unchanged: the drafter's k-step
        # scan is a small model whose whole weight stream is the step's cost
        has_quant = any(
            getattr(t, "ftype", None) in (FloatType.Q40, FloatType.Q80)
            for t in params["blocks"].values())
        self.use_pallas = use_pallas if has_quant else False
        self.compress = compress_collectives
        self.moe_sharding = moe_sharding if spec.is_moe else "slice"
        if self.use_pallas:
            params = prepare_for_pallas(
                params, tp, moe_sharding=self.moe_sharding, spec=spec,
                mesh=mesh)
        self.params = shard_params(params, mesh, spec,
                                   moe_sharding=self.moe_sharding)
        self.rope = RopeTables.create(spec)
        self.k_cache, self.v_cache = init_sharded_kv_cache(
            spec, mesh, batch=slots, dtype=self.dtype)
        self._rows: dict[int, _Row] = {}
        self._loops: dict[int, object] = {}  # scan-length bucket -> program
        self._step = None  # chunked prefill forward
        self.dispatches = 0
        self.prefill_tokens = 0

    @classmethod
    def load(cls, path: str, **kw) -> "ModelDrafter":
        """Load a drafter from a `.m` model file — the exact loader the
        target uses (formats/mfile.py: Q40/Q80/F32, header schema, seq-len
        clamp)."""
        from ..formats.mfile import load_model

        spec, params = load_model(str(path))
        return cls(spec, params, **kw)

    # -- Proposer protocol ------------------------------------------------

    def attach(self, row: int, tokens: list[int]) -> None:
        self._rows[row] = _Row(tokens)

    def detach(self, row: int) -> None:
        self._rows.pop(row, None)

    def push(self, row: int, tok: int) -> None:
        st = self._rows.get(row)
        if st is None:
            return
        st.toks.append(tok)
        if st.spec_tail and st.spec_tail[0] == tok:
            # the target accepted this draft: the KV the drafter wrote
            # while drafting IS this token's KV — frontier advances free
            st.spec_tail.pop(0)
            st.frontier += 1
            _SPEC_HITS.inc()
        elif st.spec_tail:
            # correction/divergence: the rest of the tail's KV sits beyond
            # the frontier on masked slots (overwritten by the next scan)
            st.spec_tail.clear()

    def observe(self, row: int, accepted: int) -> None:
        pass  # frontier sync rides push(); accept EMAs live in AdaptiveK

    def can_serve(self, row: int, k: int) -> bool:
        """Room check: drafting k tokens needs the catch-up + k-1 fed-back
        drafts to fit the drafter's OWN context (which may be shorter than
        the target's — such rows fall back to n-gram drafting), and the
        stream to sit within one scan of the frontier cap."""
        st = self._rows.get(row)
        if st is None or k <= 0:
            return False
        pending = len(st.toks) - st.frontier
        return (pending >= 1 and len(st.toks) + k <= self.spec.seq_len
                and len(st.toks) <= self._frontier_cap() + self.catchup_cap)

    def stats(self) -> dict:
        return {"model": (f"dim{self.spec.dim}_L{self.spec.n_layers}"
                          f"_voc{self.spec.vocab_size}"
                          f"_s{self.spec.seq_len}"),
                "rows": len(self._rows), "k_cap": self.k_cap,
                "dispatches": self.dispatches,
                "prefill_tokens": self.prefill_tokens}

    # -- programs ---------------------------------------------------------

    def _loop(self, steps: int):
        if steps not in self._loops:
            self._loops[steps] = make_draft_loop(
                self.spec, self.mesh, self.params, steps, dtype=self.dtype,
                use_pallas=self.use_pallas,
                compress_collectives=self.compress, donate_cache=True,
                moe_sharding=self.moe_sharding)
        return self._loops[steps]

    def _prefill_step(self):
        if self._step is None:
            self._step = make_draft_step(
                self.spec, self.mesh, self.params, dtype=self.dtype,
                use_pallas=self.use_pallas,
                compress_collectives=self.compress, donate_cache=True,
                attn_window=None, moe_sharding=self.moe_sharding)
        return self._step

    def reset_backend(self) -> None:
        """Wedge-recovery hook (BatchEngine.recover_wedged): drop compiled
        programs and re-allocate the KV caches — a zombie dispatch may still
        hold (and have donated) the old buffers — and force every row back
        to a clean re-prefill."""
        from ..parallel.tp import init_sharded_kv_cache

        self._loops.clear()
        self._step = None
        self.k_cache, self.v_cache = init_sharded_kv_cache(
            self.spec, self.mesh, batch=self.slots, dtype=self.dtype)
        self._rows.clear()

    # -- drafting ---------------------------------------------------------

    def _scan_bucket(self, need: int) -> int:
        from ..runtime.speculative import verify_block_bucket

        return verify_block_bucket(max(need, 2),
                                   self.catchup_cap + self.k_cap - 1)

    def _frontier_cap(self) -> int:
        """Global frontier ceiling G: every confirmed frontier is kept at or
        below G by the retreat pass at the top of propose_batch, sized so NO
        later dispatch's park clamp (scan width <= the bucket cap, prefill
        chunk <= PREFILL_CHUNK) can ever need to move a frontier again —
        a mid-loop retreat would silently invalidate another row's already-
        captured catch-up state (review-caught). Rows whose stream outgrows
        G + catchup_cap become unservable and fall back to n-gram drafting:
        near the drafter's own context wall its useful life is over anyway."""
        steps_cap = self.catchup_cap + self.k_cap - 1
        return max(self.spec.seq_len - max(steps_cap, PREFILL_CHUNK), 0)

    def _prefill_row(self, row: int, st: _Row) -> None:
        """Chunk-ingest pending tokens until the remainder fits one
        proposal scan (<= catchup_cap) — never token-by-token: the scan's
        catch-up phase runs anyway and carries the remainder for free, and
        a short final chunk runs PADDED through the same (B, 16) program
        (the pad's garbage KV lands beyond the advanced frontier on masked
        slots — the standard free-rollback discipline — so one compiled
        shape covers every prefill). Other rows ride the dispatches parked
        at their own frontiers — all <= the cap by the propose_batch
        retreat pass, so no scratch write can touch committed rows and no
        frontier moves here."""
        step = self._prefill_step()
        import jax.numpy as jnp

        # stop once the remaining pending rides one scan; never past the cap
        target = min(max(len(st.toks) - self.catchup_cap, st.frontier),
                     self._frontier_cap())
        t0 = time.perf_counter()
        n0 = st.frontier
        with trace.span("draft.prefill",
                        {"row": row, "tokens": target - n0}):
            while st.frontier < target:
                real = min(PREFILL_CHUNK, target - st.frontier)
                toks = np.zeros((self.slots, PREFILL_CHUNK), np.int32)
                starts = np.zeros((self.slots,), np.int32)
                for i, other in self._rows.items():
                    starts[i] = other.frontier
                toks[row, :real] = st.toks[st.frontier:st.frontier + real]
                starts[row] = st.frontier
                _, self.k_cache, self.v_cache = step(
                    self.params, self.rope, jnp.asarray(toks), self.k_cache,
                    self.v_cache, jnp.asarray(starts))
                st.frontier += real
                st.spec_tail.clear()
        n = st.frontier - n0
        self.prefill_tokens += n
        _PREFILL.inc(n)
        self._dt_note(t0)

    def _dt_note(self, t0: float) -> None:
        _DISPATCH_SECONDS.observe(time.perf_counter() - t0)

    def propose_batch(self, want: dict[int, int]) -> dict[int, list[int]]:
        """Draft up to want[row] tokens for every servable row in ONE scan
        dispatch. Rows the drafter cannot serve (no pending token, context
        exhausted) are absent from the result — the mux falls back to
        n-gram for them."""
        faults.fire("draft.propose", rows=len(want))
        s = self.spec.seq_len
        # retreat pass FIRST: pin every frontier at/below the global cap
        # before ANY row's catch-up state is captured, so neither the
        # prefill parks nor the scan parks below can move a frontier
        # mid-turn (the prefix below the cap stays valid; the retreated
        # tail re-ingests as ordinary catch-up)
        cap = self._frontier_cap()
        for other in self._rows.values():
            if other.frontier > cap:
                other.frontier = cap
                other.spec_tail.clear()
        serve: dict[int, tuple[_Row, int, int]] = {}  # row -> (st, ncatch, k)
        for row, k in want.items():
            st = self._rows.get(row)
            k = min(k, self.k_cap)
            if st is None or k <= 0:
                continue
            ncatch = len(st.toks) - st.frontier
            if ncatch <= 0:
                continue  # nothing pending (e.g. a retried plan): skip
            # context room: ncatch + k - 1 ingestions from `frontier` must
            # stay inside the drafter's seq_len
            k = min(k, s - st.frontier - ncatch)
            if k <= 0:
                continue
            if ncatch > self.catchup_cap:
                self._prefill_row(row, st)
                ncatch = len(st.toks) - st.frontier
                if ncatch <= 0 or ncatch > self.catchup_cap:
                    # a stream past cap+catchup_cap cannot be carried by
                    # one scan: the row falls back to n-gram drafting
                    continue
            st.spec_tail.clear()  # the scan overwrites the old tail's slots
            serve[row] = (st, ncatch, k)
        if not serve:
            return {}
        steps = self._scan_bucket(max(nc + k - 1 for _st, nc, k
                                      in serve.values()))
        catchup = np.zeros((self.slots, steps), np.int32)
        starts = np.zeros((self.slots,), np.int32)
        ncatch = np.zeros((self.slots,), np.int32)
        budget = np.zeros((self.slots,), np.int32)
        for i, other in self._rows.items():
            # parked rows ride with scratch writes at their own frontiers —
            # all at/below the cap, so every write is masked and in-bounds
            starts[i] = other.frontier
        for row, (st, nc, k) in serve.items():
            span = st.toks[st.frontier:st.frontier + min(nc, steps)]
            catchup[row, :len(span)] = span
            starts[row] = st.frontier
            ncatch[row] = nc
            budget[row] = nc + k - 1
        t0 = time.perf_counter()
        with trace.span("draft.propose",
                        {"rows": len(serve), "steps": steps,
                         "catchup": int(ncatch.sum())}):
            loop = self._loop(steps)
            toks, _pos, self.k_cache, self.v_cache = loop(
                self.params, self.rope, catchup, self.k_cache, self.v_cache,
                starts, ncatch, budget)
            # the drafter's one delivery fence: host-side proposal slicing
            # requires the (S, B) argmax block
            toks = np.asarray(toks)
        self.dispatches += 1
        _DISPATCHES.inc()
        self._dt_note(t0)
        out: dict[int, list[int]] = {}
        for row, (st, nc, k) in serve.items():
            drafts = toks[nc - 1:nc - 1 + k, row].tolist()
            st.frontier += nc
            # all but the last draft were fed back: their KV is written
            # speculatively at the positions the tokens would occupy
            st.spec_tail = drafts[:-1]
            out[row] = drafts
            _CATCHUP.inc(nc)
            _DRAFTED.inc(len(drafts))
        return out
