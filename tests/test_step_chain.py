"""A `jit_step` dispatch that does not wait for its own results (ISSUE 43).

Where every row a step samples is a greedy row of the engine's own `Sampler`,
the step program's arg-max stays on the device as the token carry, the
scheduler plans and issues the NEXT dispatch from where the rows will stand
while this one runs, and delivers this one's tokens one dispatch behind
(runtime/batch_engine.py `_run_step`, `_step_ahead`, `_settle_step`;
docs/SERVING.md "Pipelined decode"). Held here on toys: the delivered streams
are the synchronous path's bit for bit; a finish the host can foresee is no
divergence; one it cannot drops ONE row's result of the dispatch after;
a row that needs its logits on the host makes its dispatch synchronous on the
same program.

Most cases drive the scheduler from the test's own thread, one `_loop_once`
a pass, so that "a step is in flight" is a state the test can look at.
"""

import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_tpu.obs import metrics
from distributed_llama_tpu.resilience import faults
from distributed_llama_tpu.resilience.faults import FaultSpec
from distributed_llama_tpu.runtime.batch_engine import BatchEngine
from distributed_llama_tpu.runtime.sampler import Sampler

CONTEXT = 256
TOYS = ("tiny-dense", "tiny-moe", "tiny-lfm2")  # paged dense, MoE, state layers


@functools.lru_cache(maxsize=None)
def _toy(name):
    from benchmark import cells
    from benchmark import weights as W

    cfg = {**cells.load_config(name), "context": CONTEXT}
    weights = W.make_weights(cfg, 2**31 + 43)
    spec = cells.load_family(cfg["family"]).model_spec(cfg)
    return spec, W.to_program_params(weights, cfg)


def _engine(name, pipeline, slots=3, manual=True, **kw):
    spec, params = _toy(name)
    kw.setdefault("prefix_cache", False)
    be = BatchEngine(spec, params, None, slots=slots, superstep=4, tp=1,
                     pipeline=pipeline, kv_block_tokens=16,
                     dtype=jnp.float32, **kw)
    if manual:  # the test's thread is the scheduler
        be._ensure_thread = lambda: None
    return be


def _drive(be, reqs, cap=3000):
    """Passes until every request is done and nothing is in flight."""
    n = 0
    while (not all(r.done.is_set() for r in reqs)
           or be._inflight is not None):
        be._loop_once()
        n += 1
        assert n < cap, "the scheduler makes no progress"


def _prompt(seed, n, vocab):
    return np.random.default_rng([seed, 0x57E9]).integers(
        3, vocab, size=n).tolist()


def _delta(after, before, name, **label):
    """A counter's growth between two snapshots; of a labelled one, the
    child's (the snapshot keys it as `{reason="row"}`)."""
    a, b = after.get(name, 0), before.get(name, 0)
    if label:
        (k, v), = label.items()
        key = f'{{{k}="{v}"}}'
        a = a.get(key, 0) if isinstance(a, dict) else 0
        b = b.get(key, 0) if isinstance(b, dict) else 0
    return a - b


def _steps(snap_after, snap_before):
    """`jit_step` dispatches of a run: batch_dispatch_seconds' counts."""
    n = 0
    for kind in ("prefill", "mixed", "single_step"):
        key = f'{{kind="{kind}"}}'
        a = snap_after.get("batch_dispatch_seconds", {}).get(key, {})
        b = snap_before.get("batch_dispatch_seconds", {}).get(key, {})
        n += a.get("count", 0) - b.get("count", 0)
    assert n > 0
    return n


class _Issued:
    """Every step the engine plans, in order: (kind of span, chunk, the
    requests in it, chained), recorded where `_run_step` is handed it."""

    def __init__(self, be):
        self.steps = []
        inner = be._run_step

        def run(fl, staged, chain=None):
            self.steps.append((fl.span[0], fl.k, [r for _s, r in fl.rows],
                               chain is not None))
            return inner(fl, staged, chain)

        be._run_step = run


# ------------------------------------------------------------ token streams

# prompt lengths that prefill in chunks of 64, 8 and 1 with rows riding them
# (75 = 64 + 8 + 1 + 1 + 1), more requests than slots, replies of other lengths
SCRIPT = [(75, 12), (12, 20), (140, 7), (9, 15), (66, 9)]


def _run_script(name, pipeline, **kw):
    be = _engine(name, pipeline, **kw)
    vocab = be.spec.vocab_size
    try:
        before = metrics.snapshot()
        reqs = [be.submit(_prompt(i, n, vocab), m, Sampler(vocab))
                for i, (n, m) in enumerate(SCRIPT)]
        _drive(be, reqs)
        after = metrics.snapshot()
        return be, [list(r.out) for r in reqs], before, after
    finally:
        be.close()


@pytest.mark.parametrize("name", TOYS)
def test_chained_streams_equal_the_synchronous_ones(name):
    _, want, _, _ = _run_script(name, pipeline=False)
    be, got, before, after = _run_script(name, pipeline=True)
    assert got == want
    assert [len(o) for o in got] == [m for _n, m in SCRIPT]
    assert be.mixed_steps > 0  # rows rode the chunks
    steps = _steps(after, before)
    chained = _delta(after, before, "batch_step_chained_total")
    # all but the first dispatch after an idle or a scan
    assert chained >= 0.8 * steps, (chained, steps)
    # every finish was by length, known a dispatch ahead: nothing dropped
    assert _delta(after, before, "batch_pipeline_flushes_total", reason="row") == 0
    assert _delta(after, before, "batch_rollback_tokens_total") == 0
    # 4 bytes a row fetched where every row's logits were
    assert _delta(after, before, "batch_d2h_bytes_total") < 64 * steps + 4096


def test_synchronous_engine_chains_nothing_and_fetches_logits():
    be, _, before, after = _run_script("tiny-dense", pipeline=False)
    steps = _steps(after, before)
    assert _delta(after, before, "batch_step_chained_total") == 0
    assert _delta(after, before, "batch_d2h_bytes_total") >= (
        steps * be.slots_n * be.spec.vocab_size * 4)


def test_a_row_that_ends_by_length_is_absent_from_the_next_dispatch():
    be = _engine("tiny-dense", True, slots=2)
    vocab = be.spec.vocab_size
    seen = _Issued(be)
    try:
        before = metrics.snapshot()
        a = be.submit(_prompt(1, 3, vocab), 3, Sampler(vocab))
        b = be.submit(_prompt(2, 30, vocab), 2, Sampler(vocab))
        _drive(be, [a, b])
        after = metrics.snapshot()
    finally:
        be.close()
    assert (a.finish, b.finish, len(a.out), len(b.out)) == (
        "length", "length", 3, 2)
    # A: three chunks of one token, then it rides with its first and its
    # second token; its third ends it and it is in no dispatch after that
    assert sum(a in reqs for _n, _t, reqs, _c in seen.steps) == 5
    assert all(chained for *_x, chained in seen.steps[1:])
    assert _delta(after, before, "batch_pipeline_flushes_total", reason="row") == 0
    assert _delta(after, before, "batch_rollback_tokens_total") == 0


# ------------------------------------------------ finishes nobody foresaw

def _unforeseen(kind, pipeline, name="tiny-dense"):
    """A (short prompt, ends after two tokens by `kind`) beside B (a long
    prompt), then C on the slot A left. Returns the engine's counters' deltas
    and the three outputs."""
    be = _engine(name, pipeline, slots=2)
    vocab = be.spec.vocab_size
    got = []
    try:
        before = metrics.snapshot()
        spec = []
        if kind == "stop":
            a = be.submit(_prompt(11, 5, vocab), 40, Sampler(vocab),
                          on_token=got.append,
                          stop_check=lambda _t: len(got) == 2)
        elif kind == "cancel":
            def on_token(t):
                got.append(t)
                if len(got) == 2:
                    a.cancel()
            a = be.submit(_prompt(11, 5, vocab), 40, Sampler(vocab),
                          on_token=on_token)
        else:  # a request-scope fault where A's third token is delivered
            spec = [FaultSpec("batch.emit", kind="error", scope="request",
                              match={"slot": 0, "n_out": 2}, count=1)]
            a = be.submit(_prompt(11, 5, vocab), 40, Sampler(vocab),
                          on_token=got.append)
        b = be.submit(_prompt(12, 90, vocab), 12, Sampler(vocab))
        with faults.active(*spec):
            n = 0
            while not a.done.is_set():
                be._loop_once()
                n += 1
                assert n < 500
        # the slot's next request
        c = be.submit(_prompt(13, 20, vocab), 9, Sampler(vocab))
        _drive(be, [b, c])
        after = metrics.snapshot()
    finally:
        be.close()
    return {"a": list(a.out), "b": list(b.out), "c": list(c.out),
            "finish": a.finish,
            "dropped": _delta(after, before,
                              "batch_pipeline_flushes_total", reason="row"),
            "rollback": _delta(after, before, "batch_rollback_tokens_total"),
            "flushes": {k: v - (before.get(
                "batch_pipeline_flushes_total", {}).get(k, 0))
                for k, v in after.get(
                    "batch_pipeline_flushes_total", {}).items()}}


@pytest.mark.parametrize("kind,finish", [
    ("stop", "stop"), ("cancel", "cancelled"), ("fault", "error")])
def test_an_unforeseen_finish_drops_one_rows_result_and_nothing_else(
        kind, finish):
    want = _unforeseen(kind, pipeline=False)
    got = _unforeseen(kind, pipeline=True)
    assert got["finish"] == want["finish"] == finish
    assert len(got["a"]) == 2
    for r in "abc":  # A's two tokens, B untouched, the slot's next request
        assert got[r] == want[r], r
    assert (want["dropped"], want["rollback"]) == (0, 0)
    # the dispatch that was in flight carried A once more: that one result
    # is dropped, and nothing is flushed
    assert (got["dropped"], got["rollback"]) == (1, 1)
    assert {k: v for k, v in got["flushes"].items()
            if v and "row" not in k} \
        == {k: v for k, v in want["flushes"].items() if v}


def test_a_dropped_rows_state_write_is_as_harmless_as_its_kv_write():
    """The mixed model: the dropped row's ring row and block snapshot land
    past the request's frontier; the other rows and the slot's next request
    read what the synchronous path reads."""
    want = _unforeseen("stop", pipeline=False, name="tiny-lfm2")
    got = _unforeseen("stop", pipeline=True, name="tiny-lfm2")
    assert got["dropped"] == 1
    for r in "abc":
        assert got[r] == want[r], r


def test_a_request_scope_fault_at_prefill_kills_the_prefilling_request_only():
    outs = {}
    for pipeline in (False, True):
        be = _engine("tiny-dense", pipeline, slots=2)
        vocab = be.spec.vocab_size
        try:
            a = be.submit(_prompt(21, 6, vocab), 14, Sampler(vocab))
            n = 0
            while len(a.out) < 2:  # A decodes, a step of it in flight
                be._loop_once()
                n += 1
                assert n < 200
            assert (be._inflight is not None) == pipeline
            b = be.submit(_prompt(22, 40, vocab), 5, Sampler(vocab))
            # B's second chunk (32 tokens left of 40) is refused
            with faults.active(FaultSpec(
                    "batch.prefill", kind="error", scope="request",
                    match={"pending": 32}, count=1)):
                _drive(be, [a, b])
            outs[pipeline] = (list(a.out), a.finish, b.finish,
                              type(b.error).__name__)
        finally:
            be.close()
    assert outs[True] == outs[False]
    assert outs[True][1:3] == ("length", "error")
    assert len(outs[True][0]) == 14


def test_a_transient_fault_is_retried_before_the_step_consumes_its_inputs():
    be = _engine("tiny-dense", True, slots=2, retry_backoff=0.001)
    vocab = be.spec.vocab_size
    ref = _engine("tiny-dense", False, slots=2)
    try:
        before = metrics.snapshot()
        prompts = [_prompt(31, 20, vocab), _prompt(32, 9, vocab)]
        # every third dispatch fails once before its launch
        with faults.active(FaultSpec("batch.dispatch", kind="transient",
                                     match={"attempt": 0}, after=2,
                                     count=4)):
            reqs = [be.submit(p, 10, Sampler(vocab)) for p in prompts]
            _drive(be, reqs)
        after = metrics.snapshot()
        want = [ref.submit(p, 10, Sampler(vocab)) for p in prompts]
        _drive(ref, want)
    finally:
        be.close()
        ref.close()
    assert [r.out for r in reqs] == [r.out for r in want]
    assert _delta(after, before, "engine_retries_total") == 4
    assert _delta(after, before, "batch_step_chained_total") > 0


def test_a_stale_epoch_drops_the_in_flight_record():
    from distributed_llama_tpu.runtime.batch_engine import _StaleEpoch

    be = _engine("tiny-dense", True, slots=2)
    vocab = be.spec.vocab_size
    try:
        a = be.submit(_prompt(41, 20, vocab), 10, Sampler(vocab))
        be._tls.epoch = be._epoch  # this thread is the scheduler born now
        be._loop_once()
        fl = be._inflight
        assert fl is not None and fl.kind == "step"
        assert be.recover_wedged(reinit=True)
        assert be._inflight is None and a.done.is_set()
        assert type(a.error).__name__ == "EngineWedged"
        assert all(s.ahead == 0 and s.req is None for s in be._slots)
        # the abandoned scheduler wakes where it waited for its step: it
        # unwinds and touches nothing of the replacement's
        fresh = be.submit(_prompt(42, 12, vocab), 4, Sampler(vocab))
        with pytest.raises(_StaleEpoch):
            be._deliver_step(fl)
        assert all(s.pos == 0 and not s.history for s in be._slots)
        be._tls.epoch = be._epoch  # the replacement scheduler
        _drive(be, [fresh])
        assert len(fresh.out) == 4 and fresh.error is None
    finally:
        be.close()


# --------------------------------------- blocks, the prefix cache, the pool

def _harvest_run(pipeline):
    be = _engine("tiny-dense", pipeline, slots=2, prefix_cache=True)
    vocab = be.spec.vocab_size
    got = []
    seen = {}
    try:
        a = be.submit(_prompt(51, 40, vocab), 60, Sampler(vocab),
                      on_token=got.append,
                      stop_check=lambda _t: len(got) == 5)
        b = be.submit(_prompt(52, 100, vocab), 30, Sampler(vocab))
        n = 0
        while not a.done.is_set():
            be._loop_once()
            n += 1
            assert n < 500
        slot = be._slots[0]
        history, blocks = list(slot.history), list(slot.blocks)
        bt = be.slot_cache.block_tokens
        fl = be._inflight
        if pipeline:
            # A stopped at a delivery; the dispatch issued before it still
            # writes A's slot, one token past the frontier
            assert fl is not None and any(r is a for _s, r in fl.rows)
            at = fl.starts[slot.index]
            assert at == len(history)
            seen["writes"] = blocks[at // bt]
        pc = be.prefix_cache
        lease = pc.lookup(history + [1, 2, 3], cap=be.spec.seq_len - 1)
        seen["harvested"] = lease.tokens
        seen["nodes"] = [n.handle[1] for n in lease.nodes]
        pc.mark_unused(lease)
        seen["slot_blocks"] = blocks
        seen["refs"] = be.kv_pool.refcounts().copy()
        _drive(be, [b])
        seen["history"] = history
        seen["b"] = list(b.out)
        seen["a"] = list(a.out)
        # the same prompt again: a hit on what A left
        c = be.submit(history + _prompt(53, 5, vocab), 6, Sampler(vocab))
        _drive(be, [c])
        seen["c"] = list(c.out)
        seen["reused"] = c.stats.reused_tokens
    finally:
        be.close()
    return seen


def test_a_finished_rows_blocks_stay_put_while_a_dispatch_writes_them():
    want, got = _harvest_run(False), _harvest_run(True)
    for key in ("a", "b", "c", "history", "harvested", "reused"):
        assert got[key] == want[key], key
    bt = 16
    n = len(got["history"]) // bt
    # harvested: the whole blocks under the frontier and no token past it
    assert got["harvested"] == n * bt
    assert got["nodes"] == got["slot_blocks"][:n]
    # the block the in-flight dispatch writes is the slot's own still
    # (neither freed nor another's), and no directory node holds it
    w = got["writes"]
    assert w in got["slot_blocks"][n:] and w not in got["nodes"]
    assert got["refs"][w] == 1


def test_preemption_with_a_step_in_flight():
    outs = {}
    for pipeline in (False, True):
        be = _engine("tiny-dense", pipeline, slots=2, prefix_cache=True)
        vocab = be.spec.vocab_size
        try:
            before = metrics.snapshot()
            batch = [be.submit(_prompt(61 + i, 20, vocab), 24,
                               Sampler(vocab), klass="batch")
                     for i in range(2)]
            n = 0
            while min(len(r.out) for r in batch) < 3:
                be._loop_once()
                n += 1
                assert n < 300
            assert (be._inflight is not None) == pipeline
            hot = be.submit(_prompt(63, 12, vocab), 6, Sampler(vocab),
                            klass="interactive")
            _drive(be, batch + [hot])
            after = metrics.snapshot()
            assert _delta(after, before, "batch_preempted_total") == 1
            assert sum(r.preemptions for r in batch) == 1
            outs[pipeline] = [list(r.out) for r in batch + [hot]]
        finally:
            be.close()
    assert outs[True] == outs[False]
    assert [len(o) for o in outs[True]] == [24, 24, 6]


def test_close_drain_with_a_step_in_flight():
    want = None
    for pipeline in (False, True):
        be = _engine("tiny-dense", pipeline, slots=2, manual=False)
        vocab = be.spec.vocab_size
        first = threading.Event()
        reqs = [be.submit(_prompt(71 + i, 30, vocab), 12, Sampler(vocab),
                          on_token=lambda _t: first.set())
                for i in range(3)]  # one of them still queued
        assert first.wait(120)
        be.close(drain=True, timeout=120)
        assert [r.finish for r in reqs] == ["length"] * 3
        assert all(r.error is None for r in reqs)
        assert be._inflight is None
        with pytest.raises(Exception):
            be.submit([1, 2], 1, Sampler(vocab))
        outs = [list(r.out) for r in reqs]
        want = want or outs
        assert outs == want


def test_a_step_to_scan_transition_delivers_first_once():
    """One synchronous gap a transition, not one a dispatch: the last step of
    a prompt is delivered, the scan leaves from host state, and every scan
    after it is chained from the scan before."""
    be = _engine("tiny-dense", True, slots=2)
    ref = _engine("tiny-dense", False, slots=2)
    vocab = be.spec.vocab_size
    try:
        before = metrics.snapshot()
        a = be.submit(_prompt(81, 9, vocab), 21, Sampler(vocab))
        kinds, scans = [], []
        issue = be._issue_super_step
        be._issue_super_step = lambda *a, chain=None, **kw: (
            scans.append(chain is not None), issue(*a, chain=chain, **kw))[1]
        n = 0
        while not a.done.is_set() or be._inflight is not None:
            be._loop_once()
            fl = be._inflight
            kinds.append(None if fl is None else (fl.kind, fl.chained))
            n += 1
            assert n < 200
        after = metrics.snapshot()
        want = ref.submit(_prompt(81, 9, vocab), 21, Sampler(vocab))
        _drive(ref, [want])
    finally:
        be.close()
        ref.close()
    assert a.out == want.out and len(a.out) == 21
    # chunks of 8 and 1, the second issued from the first one's carry; the
    # pass that delivers it issues nothing; then the scans
    first = kinds.index(None)
    assert kinds[:first] == [("step", False), ("step", True)]
    assert kinds[first + 1][0] == "scan"
    assert scans == [False] + [True] * (len(scans) - 1) and len(scans) >= 3
    assert _delta(after, before, "batch_step_chained_total") == 1
    assert _delta(after, before, "batch_pipeline_flushes_total",
                  reason="row") == 0
    assert metrics.snapshot()["batch_pipeline_depth"] == 0


# ----------------------------------- rows that need their logits on the host

class _Recorder:
    """A sampler that is not `Sampler`: it carries `temperature = 0.0`, as
    benchmark/probe.ForcedSampler does, and has to be shown its logits."""

    temperature = 0.0
    topp = 0.9
    state = 0

    def __init__(self):
        self.seen = []

    def sample(self, logits):
        self.seen.append(np.array(logits, np.float32).reshape(-1))
        return int(np.argmax(self.seen[-1]))


def _host_sampled(pipeline, make):
    be = _engine("tiny-dense", pipeline, slots=2)
    be.superstep = 1  # as the probe drives it: every token a T = 1 step
    vocab = be.spec.vocab_size
    seen = _Issued(be)
    try:
        before = metrics.snapshot()
        smp, kw = make(vocab)
        a = be.submit(_prompt(91, 11, vocab), 8, smp, **kw)
        # a greedy row of the engine's own sampler beside it
        b = be.submit(_prompt(92, 30, vocab), 10, Sampler(vocab))
        _drive(be, [a, b])
        after = metrics.snapshot()
    finally:
        be.close()
    # the steps that sampled A: its prompt's last chunk and every one after
    sampling = [chained for _n, _t, reqs, chained in seen.steps[3:]
                if a in reqs]
    return {"a": list(a.out), "b": list(b.out), "smp": smp,
            "sampling_chained": sampling, "steps": seen.steps,
            "d2h": _delta(after, before, "batch_d2h_bytes_total")}


def _constrained(vocab):
    from distributed_llama_tpu.constrain import byte_vocab, compile_grammar

    aut, gh = compile_grammar("regex", "[a-z]{24}", byte_vocab(vocab),
                              eos_id=2)
    return Sampler(vocab), {"constraint": aut, "constraint_hash": gh}


@pytest.mark.parametrize("make", [
    lambda v: (_Recorder(), {}),
    lambda v: (Sampler(v, temperature=0.8, seed=7), {}),
    _constrained], ids=["recorder", "temperature", "constrained"])
def test_a_row_that_needs_its_logits_makes_its_dispatch_synchronous(make):
    want = _host_sampled(False, make)
    got = _host_sampled(True, make)
    assert got["a"] == want["a"] and got["b"] == want["b"]
    # 11 tokens: chunks 8, 1, 1, 1; from the last one on A is sampled, and no
    # dispatch that samples it was issued ahead
    assert len(got["sampling_chained"]) >= 8
    assert not any(got["sampling_chained"])
    if isinstance(got["smp"], _Recorder):
        # shown the same logits as before, as often
        assert len(got["smp"].seen) == len(want["smp"].seen) == 8
        for x, y in zip(got["smp"].seen, want["smp"].seen):
            np.testing.assert_array_equal(x, y)
    # once A is done, B's steps run ahead again
    assert any(chained for _n, _t, reqs, chained in got["steps"]
               if len(reqs) == 1)


def test_no_program_is_compiled_beyond_the_pinned_set():
    """Chained steps, a synchronous step of every kind of row that needs its
    logits, a scan off a step's carry: the dispatch signatures are the
    pinned ones (perf/compile_manifest.json), and a chained engine compiles
    no executable more than a synchronous one does."""
    from distributed_llama_tpu.analysis import compile_audit
    from distributed_llama_tpu.models.params import init_random_params
    from distributed_llama_tpu.quants import FloatType

    pinned = compile_audit.load_manifest()
    spec = compile_audit.scenario_spec()
    params = init_random_params(spec, FloatType.Q40, seed=11)
    V = spec.vocab_size
    compiles = [0]

    def on(event, _secs, **_kw):
        compiles[0] += event == "/jax/core/compile/backend_compile_duration"

    jax.monitoring.register_event_duration_secs_listener(on)
    counts = {}
    audit = compile_audit.CompileAudit()
    with audit:
        for pipeline in (False, True):
            be = BatchEngine(spec, params, slots=2, superstep=4,
                             pipeline=pipeline, tp=1, prefix_cache=True)
            be._ensure_thread = lambda: None
            c0 = compiles[0]
            try:
                before = metrics.snapshot()
                reqs = [be.submit([(7 * i + 3) % V for i in range(9)], 12,
                                  Sampler(V)),
                        be.submit([(11 * i + 5) % V for i in range(20)], 9,
                                  Sampler(V))]
                _drive(be, reqs)
                for make in (lambda v: (_Recorder(), {}),
                             lambda v: (Sampler(v, temperature=0.8, seed=7),
                                        {}), _constrained):
                    smp, kw = make(V)
                    reqs = [be.submit([(5 * i + 1) % V for i in range(10)],
                                      6, smp, **kw),
                            be.submit([(3 * i + 2) % V for i in range(17)],
                                      7, Sampler(V))]
                    _drive(be, reqs)
                after = metrics.snapshot()
            finally:
                be.close()
            counts[pipeline] = compiles[0] - c0
            chained = _delta(after, before, "batch_step_chained_total")
            assert (chained > 0) == pipeline
    findings = compile_audit.diff_manifest(audit.manifest(), pinned)
    assert findings == [], "\n".join(f.message for f in findings)
    assert counts[True] <= counts[False], counts


def test_a_direct_caller_of_a_step_program_gets_what_it_always_got():
    """`eng._step_for(w)(params, rope, tok, kc, vc, start_pos, tables)`
    returns the logits and the caches; with a carry it returns `tok`, the
    first arg-max over float32, and gives a negative token its carry."""
    be = _engine("tiny-dense", True, slots=2)
    try:
        eng = be._eng
        step = eng._step_for(None)
        tables = jnp.asarray(be.slot_cache.tables_np)
        be.slot_cache.cover(be._slots[0], 2)
        be.slot_cache.cover(be._slots[1], 2)
        tables = jnp.asarray(be.slot_cache.tables_np)
        toks = jnp.asarray(np.array([[5], [7]], np.int32))
        pos = jnp.asarray(np.zeros(2, np.int32))
        logits, kc, vc = step(eng.params, eng.rope, toks, eng.k_cache,
                              eng.v_cache, pos, tables)
        assert logits.shape == (2, 1, be.spec.vocab_size)
        first = np.asarray(logits)[:, -1].argmax(-1)
        # the same step with its tokens handed over as a carry
        flagged = jnp.asarray(np.array([[-1], [7]], np.int32))
        carry = jax.device_put(np.array([5, 99], np.int32),
                               be._no_carry.sharding)
        again, kc, vc, tok = step(eng.params, eng.rope, flagged, kc, vc, pos,
                                  tables, carry)
        np.testing.assert_array_equal(np.asarray(again), np.asarray(logits))
        np.testing.assert_array_equal(np.asarray(tok), first)
        assert tok.dtype == jnp.int32
        eng.k_cache, eng.v_cache = kc, vc
    finally:
        be.close()
