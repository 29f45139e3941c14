"""Every `obs.trace.span` is a `jax.profiler.TraceAnnotation`, the batched
scheduler's loop lies under `batch.*` spans from one dispatch to the next,
and four counters say how much of a dispatch is real work.

The scheduler tests share ONE scripted run on a tiny engine with two slots
(`scenario`): request A prefills three tokens one by one and blocks the
scheduler thread inside the callback of its first token while request B is
submitted; from there on the run is deterministic: a single T=1 step of A
(B is waiting, so no scan), B's 8-token chunk with A riding it, one K=4
scan of both rows, and both requests end. While it runs one collection of
the oldest generation is forced, so that its `batch.gc_pause` span and the
collector's counters are in the same record."""

import gc
import glob
import os
import subprocess
import sys
import threading

import jax
import pytest

from distributed_llama_tpu.models.params import init_random_params
from distributed_llama_tpu.models.spec import ArchType, ModelSpec, RopeType
from distributed_llama_tpu.obs import metrics
from distributed_llama_tpu.obs import process as process_mod
from distributed_llama_tpu.obs import trace as trace_mod
from distributed_llama_tpu.quants import FloatType
from distributed_llama_tpu.runtime.batch_engine import BatchEngine
from distributed_llama_tpu.runtime.sampler import Sampler

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------- the span


def _profiled(tmp_path, body):
    """Run `body` inside a CPU profiler session with the options
    benchmark/run.py uses; returns the /host:CPU events named test.*, as
    (name, duration_ns, {stats})."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    (host,) = [p for p in data.planes if p.name == "/host:CPU"]
    return [(ev.name, ev.duration_ns, dict(ev.stats))
            for line in host.lines for ev in line.events
            if ev.name.startswith("test.")]


def test_span_without_a_tracer_reaches_a_profiler_session(tmp_path):
    trace_mod.uninstall()

    def body():
        with trace_mod.span("test.step", {"kind": "mixed", "chunk": 64}) as sp:
            sp.add(bytes=123)  # known only inside the span
        with trace_mod.span("test.bare"):
            pass

    events = _profiled(tmp_path, body)
    assert [e[0] for e in events] == ["test.step", "test.bare"]
    assert events[0][2] == {"kind": "mixed", "chunk": 64, "bytes": 123}
    assert events[0][1] > 0 and events[1][2] == {}


def test_span_reaches_the_profiler_and_an_installed_tracer(tmp_path):
    tr = trace_mod.install(capacity=16)
    try:
        def body():
            with trace_mod.span("test.both", {"k": 4}) as sp:
                sp.add(tokens=9)

        events = _profiled(tmp_path, body)
    finally:
        trace_mod.uninstall()
    assert [(e[0], e[2]) for e in events] == [
        ("test.both", {"k": 4, "tokens": 9})]
    ring = [e for e in tr.events() if e["ph"] == "X"]
    assert [(e["name"], e["args"]) for e in ring] == [
        ("test.both", {"k": 4, "tokens": 9})]


def test_span_costs_nothing_to_speak_of_with_no_listener():
    """No profiler session, no tracer: a bare annotation, no ring event."""
    trace_mod.uninstall()
    sp = trace_mod.span("test.quiet", {"a": 1})
    assert isinstance(sp, jax.profiler.TraceAnnotation)
    with sp as inner:
        inner.add(b=2)
    tr = trace_mod.install(capacity=4)
    try:
        assert tr.events() == []
    finally:
        trace_mod.uninstall()


def test_a_process_without_jax_gets_a_noop_span_and_imports_nothing():
    code = (
        "import sys\n"
        "from distributed_llama_tpu.obs import trace\n"
        "s1 = trace.span('router.proxy', {'replica': 'r0'})\n"
        "s2 = trace.span('router.proxy')\n"
        "assert s1 is s2, 'not the shared no-op'\n"
        "with s1 as sp:\n"
        "    sp.add(code=200)\n"
        "tr = trace.install()\n"
        "with trace.span('router.proxy', {'replica': 'r1'}) as sp:\n"
        "    sp.add(code=200)\n"
        "ev = [e for e in tr.events() if e['ph'] == 'X']\n"
        "assert ev[0]['args'] == {'replica': 'r1', 'code': 200}, ev\n"
        "bad = [m for m in sys.modules if m == 'jax' or m.startswith('jax.')"
        " or m.startswith('jaxlib')]\n"
        "assert not bad, bad\n"
        "print('ok')\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok"


# ----------------------------------------------------------- the scheduler

SLOTS, K, SEQ = 2, 4, 128
DISPATCH = ("batch.prefill", "batch.mixed_step", "batch.single_step")
COUNTERS = ("batch_positions_dispatched_total", "batch_positions_real_total",
            "batch_attn_pairs_dispatched_total",
            "batch_attn_pairs_real_total")


def _delta(after, before, name):
    return after[name] - before.get(name, 0.0)


@pytest.fixture(scope="module")
def scenario():
    spec = ModelSpec(arch_type=ArchType.LLAMA, dim=64, hidden_dim=128,
                     n_layers=2, n_heads=4, n_kv_heads=4, vocab_size=256,
                     seq_len=SEQ, rope_type=RopeType.LLAMA).resolved()
    params = init_random_params(spec, FloatType.Q40, seed=11)
    # pipeline off: every step is synchronous, the loop this scenario is
    # about (`chained` below runs the same script with the scheduler ahead)
    be = BatchEngine(spec, params, slots=SLOTS, tp=1, superstep=K,
                     pipeline=False)
    first_token, release = threading.Event(), threading.Event()

    def on_token(_tok):
        if not first_token.is_set():
            first_token.set()
            assert release.wait(60)  # holds the scheduler thread

    tr = trace_mod.install()
    watch = {"before": process_mod._gc_holders}
    # the one collection of the oldest generation in the record is the one
    # forced below: the collector's own schedule depends on what ran before
    gc.collect()
    gc.disable()
    try:
        idle = metrics.snapshot()
        # A emits 1 token after its prefill, 1 before the mixed step, 1 before
        # the scan and 4 in it; B 1 before the scan and 4 in it
        a = be.submit([1, 2, 3], 7, Sampler(256, temperature=0.0),
                      on_token=on_token)
        assert first_token.wait(120)
        watch["running"] = (process_mod._gc_holders,
                            process_mod._on_gc in gc.callbacks)
        gc.collect()  # the oldest generation, on this thread
        held = metrics.snapshot()  # three prefill dispatches so far
        b = be.submit(list(range(10, 18)), 5, Sampler(256, temperature=0.0))
        release.set()
        a.wait(120)
        b.wait(120)
        done = metrics.snapshot()
        events = [e for e in tr.events() if e["ph"] == "X"]
    finally:
        gc.enable()
        trace_mod.uninstall()
        be.close()
    watch["closed"] = (process_mod._gc_holders,
                       process_mod._on_gc in gc.callbacks)
    assert (a.finish, b.finish) == ("length", "length")
    tid = next(e["tid"] for e in events if e["name"] == "batch.admit")
    pauses = [e for e in events if e["name"] == "batch.gc_pause"]
    events = sorted((e for e in events if e["tid"] == tid),
                    key=lambda e: (e["ts"], -e["dur"]))
    return {"idle": idle, "held": held, "done": done, "events": events,
            "pauses": pauses, "watch": watch, "spec": spec, "params": params,
            "out": (list(a.out), list(b.out))}


def _passes(events):
    """The scheduler thread's spans cut into loop passes (each opens with
    batch.admit), as lists of top-level spans with their children."""
    passes = []
    for e in events:
        if e["name"] == "batch.admit":
            passes.append([])
        if not passes:
            continue
        top = passes[-1]
        if top and e["ts"] < top[-1][0]["ts"] + top[-1][0]["dur"]:
            top[-1][1].append(e)
        else:
            top.append((e, []))
    return passes


def _names(top):
    """Top-level span names of a pass, a run of one name once (deciding what
    to dispatch and staging it are two batch.build spans in a row)."""
    names = [e["name"] for e, _ in top]
    return [n for i, n in enumerate(names) if i == 0 or n != names[i - 1]]


def test_a_pass_is_admit_advance_build_dispatch_deliver(scenario):
    seen = []
    for top in _passes(scenario["events"]):
        names = _names(top)
        if not any(n in DISPATCH for n in names):
            continue
        assert names[:3] == ["batch.admit", "batch.advance", "batch.build"]
        assert names[3] in DISPATCH and names[4:] == ["batch.deliver"], names
        seen.append(names[3])
        span, children = top[-2]
        assert [c["name"] for c in children] == [
            "batch.launch", "batch.fetch", "batch.fetch_wait",
            "batch.fetch_copy"]
        for c in children:  # children inside their parent, in order
            assert span["ts"] <= c["ts"]
            assert c["ts"] + c["dur"] <= span["ts"] + span["dur"] + 1e-3
        assert children[0]["ts"] + children[0]["dur"] <= children[1]["ts"]
        assert children[1]["args"]["bytes"] > 0
    assert seen == ["batch.prefill"] * 3 + ["batch.single_step",
                                            "batch.mixed_step"]


def test_dispatch_spans_say_chunk_riders_window_and_slots(scenario):
    args = [{k: v for k, v in e["args"].items() if k != "trace_id"}
            for e in scenario["events"] if e["name"] in DISPATCH]
    assert args == (
        [{"chunk": 1, "riders": 0, "window": SEQ, "slots": SLOTS}] * 3
        + [{"rows": 1, "window": SEQ, "slots": SLOTS},
           {"chunk": 8, "riders": 1, "window": SEQ, "slots": SLOTS}])
    # a request's dispatch keeps its trace id in the ring
    assert all("trace_id" in e["args"] for e in scenario["events"]
               if e["name"] in ("batch.prefill", "batch.mixed_step"))
    issue = [e["args"] for e in scenario["events"]
             if e["name"] == "batch.super_step_issue"]
    assert [(a["k"], a["rows"], a["chained"], a["window"]) for a in issue] \
        == [(K, 2, False, SEQ)]
    admits = [e["args"] for e in scenario["events"]
              if e["name"] == "batch.admit"]
    assert sum(a["admitted"] for a in admits) == 2
    assert all(a["queued"] == 0 for a in admits)


def test_the_spans_of_a_pass_leave_no_time_between_them(scenario):
    checked = 0
    for top in _passes(scenario["events"]):
        if not any(e["name"] in DISPATCH for e, _ in top):
            continue
        spans = [e for e, _ in top]
        whole = spans[-1]["ts"] + spans[-1]["dur"] - spans[0]["ts"]
        between = sum(b["ts"] - (a["ts"] + a["dur"])
                      for a, b in zip(spans, spans[1:]))
        assert 0 <= between < 0.05 * whole, (between, whole,
                                             [e["name"] for e in spans])
        checked += 1
    assert checked == 5


def test_the_scan_and_the_idle_wait_lie_under_spans_too(scenario):
    names = [e["name"] for e in scenario["events"]]
    i = names.index("batch.super_step_issue")
    # the issue inside a build span, the block's fetch inside a deliver span
    assert names[i - 1] == "batch.build"
    j = names.index("batch.super_step")
    assert names[j - 1] == "batch.deliver"
    deliver = scenario["events"][j - 1]
    fetch = scenario["events"][j]
    assert deliver["ts"] <= fetch["ts"]
    assert fetch["ts"] + fetch["dur"] <= deliver["ts"] + deliver["dur"] + 1e-3
    assert fetch["args"]["window"] == SEQ


def test_useful_work_counters_equal_the_hand_computed_values(scenario):
    got = {n: _delta(scenario["done"], scenario["held"], n) for n in COUNTERS}
    # single step: A alone at position 3. Mixed: B's chunk of 8 from 0, A
    # riding at 4. Scan of K=4: A from 5, B from 8. No window bucket under
    # 256 positions of context: attention runs against all SEQ keys.
    # The chunk's weights run over its 8 tokens and one row a slot, rounded
    # to a row tile of 8 (16 rows); its attention over the chunk's 8 queries
    # and one a slot (ISSUE 45: no longer the (slots, 8) rectangle).
    from distributed_llama_tpu.models.forward import compact_rows

    single = (SLOTS * 1, 1, 3 + 1)
    mixed = (8 + SLOTS, 8 + 1, sum(range(1, 9)) + (4 + 1))
    scan = (SLOTS * K, 4 + 4,
            sum(5 + i + 1 for i in range(4)) + sum(8 + i + 1 for i in range(4)))
    steps = (single, mixed, scan)
    assert got == {
        "batch_positions_dispatched_total": (
            single[0] + compact_rows(8, SLOTS) + scan[0]),  # not mixed[0]
        "batch_positions_real_total": sum(s[1] for s in steps),
        "batch_attn_pairs_dispatched_total": SEQ * sum(s[0] for s in steps),
        "batch_attn_pairs_real_total": sum(s[2] for s in steps)}
    # and A's prefill before it: three chunks of 1 at positions 0, 1, 2
    before = {n: _delta(scenario["held"], scenario["idle"], n)
              for n in COUNTERS}
    assert before == {
        "batch_positions_dispatched_total": 3 * SLOTS,
        "batch_positions_real_total": 3,
        "batch_attn_pairs_dispatched_total": 3 * SLOTS * SEQ,
        "batch_attn_pairs_real_total": 1 + 2 + 3}


def test_without_the_kernel_every_dispatched_pair_is_visited(scenario):
    """The scenario's engine reads the pool through the XLA gather path (no
    kernel off the chip): the whole window of every row, parked or not."""
    for a, b in (("idle", "held"), ("held", "done")):
        assert _delta(scenario[b], scenario[a],
                      "batch_attn_pairs_visited_total") == _delta(
            scenario[b], scenario[a], "batch_attn_pairs_dispatched_total") > 0


@pytest.mark.parametrize("positions,starts,budget,lead,want", [
    # a 64-token chunk, 8 rows, the 1024 bucket: whole 128-key steps up to
    # each row's committed length, nothing for a row at 0
    (64, [0, 75, 190, 260, 330, 410, 520, 640], None, None,
     64 * (0 + 128 + 256 + 384 + 384 + 512 + 640 + 640)),
    # the same chunk told which row prefills (ISSUE 45): that row's keys
    # once a position, then every row's once
    (64, [0, 75, 190, 260, 330, 410, 520, 640], None, 4,
     64 * 384 + (0 + 128 + 256 + 384 + 384 + 512 + 640 + 640)),
    # a decode step: every row under one step
    (1, [5, 100, 128, 0, 0, 0, 0, 0], None, None, 3 * 128),
    # a K=4 scan: row 0 crosses a step boundary after its first token, row
    # 1 stops growing after its budget of 2, the parked rows stay at 0
    (4, [128, 254, 0, 0, 0, 0, 0, 0], [4, 2, 0, 0, 0, 0, 0, 0], None,
     (128 + 3 * 256) + (256 + 256 + 256 + 256)),
])
def test_visited_pairs_follow_each_rows_length(positions, starts, budget,
                                               lead, want):
    import types

    be = types.SimpleNamespace(
        slots_n=len(starts), _eng=types.SimpleNamespace(paged_kernel=True),
        slot_cache=types.SimpleNamespace(block_tokens=16))
    names = ("batch_attn_pairs_visited_total",
             "batch_attn_pairs_dispatched_total")
    before = metrics.snapshot()
    BatchEngine._count_work(be, positions, 1024, [], starts, budget,
                            lead=lead)
    after = metrics.snapshot()
    visited, dispatched = (_delta(after, before, n) for n in names)
    assert visited == want
    rows = (len(starts) * positions if lead is None
            else positions + len(starts))
    assert dispatched == rows * 1024


def test_dispatch_gap_is_observed_once_per_dispatch_after_the_first(scenario):
    def count(snap):
        return snap.get("batch_dispatch_gap_seconds", {"count": 0})["count"]

    # three prefills from an idle engine: the first has no predecessor
    assert count(scenario["held"]) - count(scenario["idle"]) == 2
    # then the single step, the mixed step and the scan's issue
    assert count(scenario["done"]) - count(scenario["held"]) == 3


# ------------------------------------- a synchronous dispatch in three phases

VOCAB = 256
TABLE_BYTES = SLOTS * (SEQ // 16) * 4  # the toy's whole block table
H2D = ("batch_h2d_transfers_total", "batch_h2d_bytes_total",
       "batch_table_uploads_total", "batch_d2h_bytes_total")


def _children(events, parent):
    """The spans of `events` (one thread, sorted) inside `parent`."""
    end = parent["ts"] + parent["dur"]
    return [e for e in events if e is not parent
            and parent["ts"] <= e["ts"] and e["ts"] + e["dur"] <= end + 1e-3]


def test_wait_and_copy_lie_inside_every_fetch_and_tile_it(scenario):
    fetches = [e for e in scenario["events"] if e["name"] == "batch.fetch"]
    assert len(fetches) == 5
    for fetch in fetches:
        wait, copy = _children(scenario["events"], fetch)
        assert (wait["name"], copy["name"]) == ("batch.fetch_wait",
                                                "batch.fetch_copy")
        assert wait["ts"] + wait["dur"] <= copy["ts"]
        # what the two leave of their parent is a few clock reads (a loaded
        # box may take the thread away between two of them: microseconds)
        left = fetch["dur"] - wait["dur"] - copy["dur"]
        assert 0 <= left < max(0.1 * fetch["dur"], 5e3), (left, fetch)
        assert copy["args"]["bytes"] == fetch["args"]["bytes"]
        assert copy["args"]["demote_pending_bytes"] == 0  # nothing demoted
        assert "experts_touched" not in copy["args"]  # a dense model


def test_stage_lies_inside_build_and_says_what_it_sent(scenario):
    events = scenario["events"]
    stages = [e for e in events if e["name"] == "batch.stage"]
    builds = [e for e in events if e["name"] == "batch.build"]
    assert len(stages) == 5
    for st in stages:
        assert any(st in _children(events, b) for b in builds)
    # A's three chunks of one token (the first allocates A's block and so
    # re-sends the table), the single step, B's chunk of 8 with its block
    row = SLOTS * 4
    assert [{k: v for k, v in st["args"].items() if k != "trace_id"}
            for st in stages] == [
        {"transfers": 3, "table": 1, "bytes": 2 * row + TABLE_BYTES},
        {"transfers": 2, "table": 0, "bytes": 2 * row},
        {"transfers": 2, "table": 0, "bytes": 2 * row},
        {"transfers": 2, "table": 0, "bytes": 2 * row},
        # (behind the chunk's positions, which row prefills: one int32)
        {"transfers": 3, "table": 1,
         "bytes": 8 * row + row + 4 + TABLE_BYTES}]


def test_uploads_and_fetched_bytes_equal_the_hand_computed_values(scenario):
    row = SLOTS * 4  # one int32 or float32 a slot
    logits = SLOTS * VOCAB * 4  # one position of every row
    before = {n: _delta(scenario["held"], scenario["idle"], n) for n in H2D}
    assert before == {
        "batch_h2d_transfers_total": 3 * 2 + 1,
        "batch_h2d_bytes_total": 3 * 2 * row + TABLE_BYTES,
        "batch_table_uploads_total": 1,
        "batch_d2h_bytes_total": 3 * logits}
    # the single step; the mixed step (a chunk of 8 and which row prefills
    # up, the one sampled position a row fetched);
    # the scan from host state: tokens, positions, rng (2 words a row),
    # temperatures, top-p and budgets up, K x slots tokens and the rng back
    got = {n: _delta(scenario["done"], scenario["held"], n) for n in H2D}
    assert got == {
        "batch_h2d_transfers_total": 2 + 3 + 6,
        "batch_h2d_bytes_total": (2 * row)
        + (8 * row + row + 4 + TABLE_BYTES) + (2 * row + 2 * row + 3 * row),
        "batch_table_uploads_total": 1,
        "batch_d2h_bytes_total": logits + logits + (K * SLOTS * 4 + 2 * row)}


def test_each_synchronous_dispatch_observes_its_three_phases(scenario):
    def counts(snap):
        return {k: v["count"] for k, v in
                snap.get("batch_dispatch_phase_seconds", {}).items()}

    idle, held, done = (counts(scenario[k]) for k in ("idle", "held", "done"))
    phases = {'{phase="launch"}', '{phase="wait"}', '{phase="copy"}'}
    assert set(done) == phases
    assert {k: held[k] - idle.get(k, 0) for k in phases} == dict.fromkeys(
        phases, 3)
    assert {k: done[k] - held[k] for k in phases} == dict.fromkeys(phases, 2)
    # the wait holds the program: the scan observes none
    sums = scenario["done"]["batch_dispatch_phase_seconds"]
    assert sums['{phase="wait"}']["sum"] > 0


def test_a_forced_collection_is_a_gc_pause_span_and_two_counters(scenario):
    (pause,) = scenario["pauses"]  # on the test's thread, not the scheduler's
    assert pause["tid"] != scenario["events"][0]["tid"]
    assert pause["args"]["generation"] == 2
    assert pause["args"]["collected"] >= 0 and pause["dur"] > 0
    gens = [scenario[k].get("process_gc_collections_total", {}).get(
        '{generation="2"}', 0.0) for k in ("idle", "held")]
    assert gens[1] - gens[0] == 1
    # the counter's clock and the span's are read a few instructions apart
    assert _delta(scenario["held"], scenario["idle"],
                  "process_gc_pause_seconds_total") >= 0.99 * pause["dur"] / 1e6


def test_the_collectors_watcher_is_held_from_start_to_close(scenario):
    watch = scenario["watch"]
    assert watch["running"] == (watch["before"] + 1, True)
    assert watch["closed"][0] == watch["before"]
    # the last holder takes the gc.callbacks entry away
    assert watch["closed"][1] == (watch["before"] > 0)


def test_the_scenarios_tokens_are_the_sequential_engines(scenario):
    """The split of the fetch moves no result: both requests decode what the
    one-sequence engine decodes from the same prompts."""
    from distributed_llama_tpu.runtime.engine import Engine

    eng = Engine(scenario["spec"], scenario["params"], tp=1)
    wants = []
    for prompt, n in (([1, 2, 3], 7), (list(range(10, 18)), 5)):
        eng.reset()
        out, _ = eng.generate(list(prompt), n,
                              Sampler(VOCAB, temperature=0.0))
        wants.append(out)
    assert list(scenario["out"]) == wants


def test_a_routed_models_experts_touched_is_on_the_copy_span():
    """The stats vector rides the fetch: waited for with the logits, copied
    with them, counted, and its `experts_touched` ADDED to the open copy
    span; a dispatch span holds its launch, its fetch and the fetch's two
    children and no mark beside them."""
    spec = ModelSpec(arch_type=ArchType.MIXTRAL, dim=64, hidden_dim=64,
                     n_layers=2, n_heads=4, n_kv_heads=2, vocab_size=96,
                     seq_len=SEQ, n_experts=4, n_active_experts=2,
                     rope_type=RopeType.FALCON).resolved()
    params = init_random_params(spec, FloatType.Q40, seed=4)
    be = BatchEngine(spec, params, slots=SLOTS, tp=1, superstep=K,
                     pipeline=False)  # synchronous dispatches, logits fetched
    tr = trace_mod.install()
    try:
        before = metrics.snapshot()
        be.submit([5, 6, 7], 1, Sampler(96, temperature=0.0)).wait(120)
        after = metrics.snapshot()
        events = [e for e in tr.events() if e["ph"] == "X"]
    finally:
        trace_mod.uninstall()
        be.close()
    events.sort(key=lambda e: (e["ts"], -e["dur"]))
    dispatches = [e for e in events if e["name"] == "batch.prefill"]
    assert len(dispatches) == 3  # three chunks of one token
    for d in dispatches:
        assert [c["name"] for c in _children(events, d)
                if c["tid"] == d["tid"]] == [
            "batch.launch", "batch.fetch", "batch.fetch_wait",
            "batch.fetch_copy"]
    copies = [e for e in events if e["name"] == "batch.fetch_copy"]
    assert len(copies) == 3
    touched = [c["args"]["experts_touched"] for c in copies]
    # every dispatch reads 1 to 4 experts a layer: two slots' rows, two each
    assert all(2 <= t <= 4 * spec.n_layers for t in touched)
    assert _delta(after, before, "batch_moe_experts_touched_total") == sum(
        touched)
    # the logits of one position and the six int32 of stats, three times
    assert _delta(after, before, "batch_d2h_bytes_total") == 3 * (
        SLOTS * 96 * 4 + 6 * 4)


# ------------------------------------- the scheduler one dispatch ahead (PR 43)

@pytest.fixture(scope="module")
def chained(scenario):
    """The scenario's script with the `pipeline` switch on: the same two
    requests on the same weights, every step issued ahead (docs/SERVING.md
    "Steps issued ahead"). A's three chunks of one token, the third one's
    delivery (A's first token) holding the scheduler while B is submitted,
    B's chunk of 8 with A riding from host state, once that is delivered a
    scan of both from host state, and a single step for A's last token."""
    be = BatchEngine(scenario["spec"], scenario["params"], slots=SLOTS, tp=1,
                     superstep=K)
    first_token, release = threading.Event(), threading.Event()

    def on_token(_tok):
        if not first_token.is_set():
            first_token.set()
            assert release.wait(60)  # holds the scheduler thread

    tr = trace_mod.install()
    gc.collect()
    gc.disable()  # no collector pause among a dispatch's children
    try:
        idle = metrics.snapshot()
        a = be.submit([1, 2, 3], 7, Sampler(256, temperature=0.0),
                      on_token=on_token)
        assert first_token.wait(120)
        depth_held = metrics.snapshot()["batch_pipeline_depth"]
        b = be.submit(list(range(10, 18)), 5, Sampler(256, temperature=0.0))
        release.set()
        a.wait(120)
        b.wait(120)
        done = metrics.snapshot()
        events = [e for e in tr.events() if e["ph"] == "X"]
    finally:
        gc.enable()
        trace_mod.uninstall()
        be.close()
    tid = next(e["tid"] for e in events if e["name"] == "batch.admit")
    events = sorted((e for e in events if e["tid"] == tid),
                    key=lambda e: (e["ts"], -e["dur"]))
    return {"idle": idle, "done": done, "events": events,
            "depth_held": depth_held, "out": (list(a.out), list(b.out))}


def _args(e):
    return {k: v for k, v in e["args"].items() if k != "trace_id"}


def test_issued_ahead_the_tokens_are_the_synchronous_ones(scenario, chained):
    assert chained["out"] == scenario["out"]


def test_one_dispatch_span_a_dispatch_with_its_own_args(chained):
    events = chained["events"]
    spans = [e for e in events if e["name"] in DISPATCH]
    want = ([("batch.prefill", {"chunk": 1, "riders": 0, "window": SEQ,
                                "slots": SLOTS})] * 3
            + [("batch.mixed_step", {"chunk": 8, "riders": 1, "window": SEQ,
                                     "slots": SLOTS}),
               # A's seventh token, once the scan has given it four
               ("batch.single_step", {"rows": 1, "window": SEQ,
                                      "slots": SLOTS})])
    assert [(e["name"], _args(e)) for e in spans] == want
    # the histogram counts the same dispatches
    got = {k: chained["done"]["batch_dispatch_seconds"][f'{{kind="{k}"}}'][
        "count"] - chained["idle"]["batch_dispatch_seconds"][
            f'{{kind="{k}"}}']["count"]
        for k in ("prefill", "mixed", "single_step")}
    assert got == {"prefill": 3, "mixed": 1, "single_step": 1}
    # each was issued under a span of another name that says what its
    # dispatch span will say, and whether it left from the carry
    issues = [e for e in events if e["name"] == "batch.step_issue"]
    assert [{k: v for k, v in _args(e).items()
             if k not in ("kind", "chained")} for e in issues] \
        == [a for _n, a in want]
    assert [(e["args"]["kind"], e["args"]["chained"]) for e in issues] == [
        ("prefill", False), ("prefill", True), ("prefill", True),
        ("mixed", False),  # nothing was in flight when B arrived
        ("single_step", False)]  # nor behind the scan
    # a request's dispatch keeps its trace id in the ring, both ways
    assert all("trace_id" in e["args"] for e in spans[:4] + issues[:4])


def test_a_dispatch_span_holds_the_wait_for_its_own_results(chained):
    events = chained["events"]
    issues = [e for e in events if e["name"] == "batch.step_issue"]
    spans = [e for e in events if e["name"] in DISPATCH]
    for issue, span in zip(issues, spans):
        # the launch under the issue, not under the dispatch span
        assert [c["name"] for c in _children(events, issue)] == [
            "batch.launch"]
        assert [c["name"] for c in _children(events, span)] == [
            "batch.fetch", "batch.fetch_wait", "batch.fetch_copy"]
        assert issue["ts"] + issue["dur"] <= span["ts"]
        fetch, wait, copy = _children(events, span)
        assert abs(wait["dur"] + copy["dur"] - fetch["dur"]) < 0.15 * max(
            fetch["dur"], 1.0) + 150  # us: two children tile their parent
        # the program's own samples, 4 bytes a row, where the logits were
        assert fetch["args"]["bytes"] == copy["args"]["bytes"] == 4 * SLOTS
    # the NEXT dispatch is issued before this one's results are waited for:
    # A's second and third chunk leave before the first and second deliver
    assert issues[1]["ts"] < spans[0]["ts"] and issues[2]["ts"] < spans[1]["ts"]
    # a scan is NOT issued behind a running step: it leaves from host state
    # once the last step is delivered (one synchronous gap a transition)
    scans = [e for e in events if e["name"] == "batch.super_step_issue"]
    assert [(e["args"]["rows"], e["args"]["chained"]) for e in scans] == [
        (2, False)]
    assert scans[0]["ts"] > spans[3]["ts"] + spans[3]["dur"]


def test_the_run_ahead_counters_move_as_documented(chained):
    def grew(name, **label):
        a, b = chained["done"].get(name, 0), chained["idle"].get(name, 0)
        if label:
            (k, v), = label.items()
            a = a.get(f'{{{k}="{v}"}}', 0) if isinstance(a, dict) else 0
            b = b.get(f'{{{k}="{v}"}}', 0) if isinstance(b, dict) else 0
        return a - b

    assert grew("batch_step_chained_total") == 2  # A's second, third chunk
    assert grew("batch_pipeline_flushes_total", reason="row") == 0
    assert grew("batch_pipeline_flushes_total", reason="admission") == 0
    assert grew("batch_rollback_tokens_total") == 0
    # held in A's first token: its last chunk is being delivered and nothing
    # was issued behind it; nothing is in flight once both requests end
    assert chained["depth_held"] == 1
    assert chained["done"]["batch_pipeline_depth"] == 0
    # five steps' samples and a scan's block: no logits came back
    assert grew("batch_d2h_bytes_total") < 4 * SLOTS * 256
    gap = chained["done"]["batch_dispatch_gap_seconds"]
    was = chained["idle"]["batch_dispatch_gap_seconds"]
    lowest = min(gap["buckets"], key=float)
    # a chained dispatch observes a literal 0: A's second and third chunk
    assert gap["buckets"][lowest] - was["buckets"][lowest] >= 2


def test_issued_ahead_the_spans_of_a_pass_still_leave_no_time_between(chained):
    checked = 0
    for top in _passes(chained["events"]):
        if not any(e["name"] in DISPATCH + ("batch.step_issue",)
                   for e, _ in top):
            continue
        spans = [e for e, _ in top]
        whole = spans[-1]["ts"] + spans[-1]["dur"] - spans[0]["ts"]
        between = sum(b["ts"] - (a["ts"] + a["dur"])
                      for a, b in zip(spans, spans[1:]))
        # a toy's pass is 2 ms: a tenth of it is 0.2 ms of bare statements
        assert 0 <= between < 0.1 * whole, (between, whole,
                                            [e["name"] for e in spans])
        checked += 1
    assert checked >= 5
