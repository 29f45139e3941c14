"""Chaos test suite for the resilience layer (docs/ROBUSTNESS.md).

Drives the fault-injection framework (distributed_llama_tpu/resilience/)
against the continuous-batching scheduler and the HTTP server on the CPU
mesh and asserts the acceptance criteria of ISSUE 4:

- killing one co-batched request (mid-prefill AND mid-super-step) leaves
  every survivor's output token-identical to a fault-free run — greedy and
  seeded-stochastic — and the scheduler thread never dies;
- transient dispatch failures are retried and invisible to clients;
- queue-TTL and wall-clock deadlines expire with finish reason "deadline"
  (DeadlineExceeded before the first token, partial output after);
- overload sheds with EngineSaturated / HTTP 503 + Retry-After;
- close() speaks typed errors (EngineClosed/EngineDraining) and drain mode
  lets in-flight requests finish;
- a SIGTERM round trip against a live server drains: /healthz flips to 503
  "draining", new requests 503, in-flight completes, server stops;
- BatchRequest.wait(timeout) auto-cancels instead of leaking the slot.
"""

import http.client
import json
import threading
import time

import pytest

from distributed_llama_tpu.models.params import init_random_params
from distributed_llama_tpu.models.spec import ArchType, ModelSpec, RopeType
from distributed_llama_tpu.obs import metrics
from distributed_llama_tpu.quants import FloatType
from distributed_llama_tpu.resilience import faults
from distributed_llama_tpu.resilience.errors import (DeadlineExceeded,
                                                     EngineClosed,
                                                     EngineDraining,
                                                     EngineSaturated,
                                                     FaultInjected,
                                                     TransientDispatchError,
                                                     classify)
from distributed_llama_tpu.resilience.faults import FaultSpec
from distributed_llama_tpu.runtime.batch_engine import BatchEngine
from distributed_llama_tpu.runtime.sampler import Sampler


def _spec(seq_len=128):
    return ModelSpec(arch_type=ArchType.LLAMA, dim=64, hidden_dim=128, n_layers=2,
                     n_heads=4, n_kv_heads=4, vocab_size=256, seq_len=seq_len,
                     rope_type=RopeType.LLAMA).resolved()


@pytest.fixture(autouse=True)
def _no_leftover_faults():
    """Every test leaves the process fault-free (a leaked plan would poison
    the rest of the suite)."""
    yield
    faults.uninstall()


@pytest.fixture(scope="module")
def setup():
    spec = _spec()
    params = init_random_params(spec, FloatType.Q40, seed=11)
    be = BatchEngine(spec, params, slots=2, tp=1, superstep=4)
    yield spec, params, be
    be.close()


def _greedy(spec):
    return Sampler(spec.vocab_size, temperature=0.0)


def _seeded(spec):
    return Sampler(spec.vocab_size, 0.8, 0.9, 123)


def _wait_until(cond, timeout=60.0, msg="condition"):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if cond():
            return
        time.sleep(0.005)
    raise AssertionError(f"timed out waiting for {msg}")


def _counter_value(name: str, labels: str = "") -> float:
    snap = metrics.snapshot().get(name, 0.0)
    if isinstance(snap, dict):
        return snap.get(labels, 0.0)
    return snap


# ------------------------------------------------------------------
# fault framework unit tests (no engine)
# ------------------------------------------------------------------

def test_parse_faults_grammar():
    specs = faults.parse_faults(
        "batch.dispatch:transient:0.01,batch.prefill:error,"
        "paged.*:latency:1.0:3:50")
    assert [s.point for s in specs] == ["batch.dispatch", "batch.prefill",
                                       "paged.*"]
    assert specs[0].kind == "transient" and specs[0].prob == 0.01
    assert specs[1].prob == 1.0 and specs[1].count is None
    assert specs[2].count == 3 and specs[2].delay_ms == 50.0
    # sixth field = duration_s (the sustained-degradation window)
    sustained = faults.parse_faults("p:latency:1::800:45")[0]
    assert sustained.delay_ms == 800.0 and sustained.duration_s == 45.0
    assert faults.parse_faults("p:error:1:2:3:4")[0].duration_s == 4.0
    assert faults.parse_faults("p:error:1:2:3")[0].duration_s is None
    for bad in ("point-only", "p:unknownkind", "p:error:notaprob",
                "p:error:1:2:3:notasecs", "p:error:1:2:3:4:5"):
        with pytest.raises(ValueError):
            faults.parse_faults(bad)


def test_fault_spec_count_after_and_match():
    with faults.active(FaultSpec("pt", kind="error", after=2, count=1)) as plan:
        faults.fire("pt")  # skipped (after)
        faults.fire("pt")  # skipped (after)
        with pytest.raises(FaultInjected):
            faults.fire("pt")
        faults.fire("pt")  # count exhausted
        assert plan.fired() == 1
    with faults.active(FaultSpec("pt", match={"slot": 1})):
        faults.fire("pt", slot=0)  # filtered
        with pytest.raises(FaultInjected):
            faults.fire("pt", slot=1)
    assert faults.current() is None  # active() uninstalled


def test_fault_prob_seed_deterministic():
    def run(seed):
        fired = []
        plan = faults.FaultPlan([FaultSpec("p", kind="transient", prob=0.5)],
                                seed=seed)
        for i in range(64):
            try:
                plan.fire("p")
                fired.append(0)
            except TransientDispatchError:
                fired.append(1)
        return fired

    a, b = run(7), run(7)
    assert a == b and 0 < sum(a) < 64  # deterministic, actually probabilistic
    assert run(8) != a  # seed matters


def test_latency_fault_sleeps_not_raises():
    with faults.active(FaultSpec("slow", kind="latency", delay_ms=30)):
        t0 = time.perf_counter()
        faults.fire("slow")
        assert time.perf_counter() - t0 >= 0.025


def test_install_from_env():
    plan = faults.install_from_env({"DLLAMA_FAULTS": "x:error:0.5",
                                    "DLLAMA_FAULT_SEED": "9"})
    assert plan is not None and plan.seed == 9
    # explicit install wins over a second env install
    assert faults.install_from_env({"DLLAMA_FAULTS": "y:error"}) is plan
    faults.uninstall()
    assert faults.install_from_env({}) is None


def test_classify():
    assert classify(TransientDispatchError("x")) == "transient"
    assert classify(FaultInjected("x", scope="request")) == "request"
    assert classify(FaultInjected("x", scope="engine")) == "engine"
    assert classify(RuntimeError("x")) == "engine"  # conservative default


# ------------------------------------------------------------------
# satellite: wait(timeout) auto-cancel (slot-leak regression)
# ------------------------------------------------------------------

def test_wait_timeout_autocancels_and_frees_slot(setup):
    spec, params, be = setup
    req = be.submit([1, 2, 3], 64, _greedy(spec))
    with pytest.raises(TimeoutError):
        req.wait(timeout=0.01)
    assert req.cancelled
    # the scheduler reaps the cancelled request and frees the slot (+ any
    # prefix-cache lease) via the existing _finish path
    _wait_until(lambda: req.done.is_set(), msg="cancelled request reaped")
    assert req.finish == "cancelled"
    _wait_until(lambda: all(s.req is None for s in be._slots),
                msg="slot freed")
    assert all(s.lease is None for s in be._slots)
    # the engine is fully usable afterwards (no leak): a fresh request runs
    out = be.submit([1, 2, 3], 4, _greedy(spec)).wait(timeout=120)
    assert len(out) == 4


# ------------------------------------------------------------------
# blast-radius isolation: kill one co-batched request, survivors exact
# ------------------------------------------------------------------

@pytest.mark.parametrize("make_sampler", [_greedy, _seeded],
                         ids=["greedy", "seeded-stochastic"])
def test_victim_killed_mid_prefill_survivor_identical(setup, make_sampler):
    spec, params, be = setup
    survivor_prompt = [1, 7, 23, 5]
    n = 24
    base = be.submit(list(survivor_prompt), n,
                     make_sampler(spec)).wait(timeout=120)

    surv = be.submit(list(survivor_prompt), n, make_sampler(spec))
    _wait_until(lambda: len(surv.out) >= 1, msg="survivor decoding")
    faults.install([FaultSpec("batch.prefill", kind="error", count=1)])
    victim = be.submit([1] + list(range(2, 42)), 8, make_sampler(spec))
    with pytest.raises(FaultInjected):
        victim.wait(timeout=120)
    assert victim.finish == "error"
    out = surv.wait(timeout=120)
    faults.uninstall()
    assert out == base, "survivor diverged after co-batched victim died"
    assert surv.finish == "length"
    assert be.scheduler_alive()


@pytest.mark.parametrize("make_sampler", [_greedy, _seeded],
                         ids=["greedy", "seeded-stochastic"])
def test_victim_killed_mid_superstep_survivor_identical(setup, make_sampler):
    spec, params, be = setup
    survivor_prompt = [1, 9, 2]
    n = 24
    base = be.submit(list(survivor_prompt), n,
                     make_sampler(spec)).wait(timeout=120)

    surv = be.submit(list(survivor_prompt), n, make_sampler(spec))
    victim = be.submit([1, 30, 31, 32], 64, make_sampler(spec))
    _wait_until(lambda: len(victim.out) >= 1 and len(surv.out) >= 1,
                msg="both requests decoding")
    vslot = next(s for s in be._slots if s.req is victim)
    # injected at the delivery path of the victim's slot only: fires inside
    # the super-step block-delivery loop (or a single-step advance) — the
    # "sampler/callback" blast radius
    faults.install([FaultSpec("batch.emit", kind="error", count=1,
                              match={"slot": vslot.index})])
    with pytest.raises(FaultInjected):
        victim.wait(timeout=120)
    assert victim.finish == "error"
    out = surv.wait(timeout=120)
    faults.uninstall()
    assert out == base, "survivor diverged after mid-super-step victim kill"
    assert be.scheduler_alive()


def test_radix_lookup_failure_degrades_not_kills(setup, monkeypatch):
    """A raising prefix-cache LOOKUP (a real radix/pool bug, not just an
    injected seed fault) must cost only the cache win: the admitted request
    prefills from scratch and completes identically, and co-batched
    in-flight requests are untouched — the cache is never a correctness
    gate, even when it throws at admission."""
    spec, params, be = setup
    poisoned_prompt = [1, 17, 18, 19]
    inflight_prompt = [1, 7, 23, 5]
    base_poisoned = be.submit(list(poisoned_prompt), 8,
                              _greedy(spec)).wait(timeout=120)
    base_inflight = be.submit(list(inflight_prompt), 24,
                              _greedy(spec)).wait(timeout=120)

    inflight = be.submit(list(inflight_prompt), 24, _greedy(spec))
    _wait_until(lambda: len(inflight.out) >= 1, msg="in-flight decoding")

    def boom(*a, **k):
        raise RuntimeError("radix lookup boom")

    monkeypatch.setattr(be.prefix_cache, "lookup", boom)
    poisoned = be.submit(list(poisoned_prompt), 8, _greedy(spec))
    out = poisoned.wait(timeout=120)  # degraded to plain prefill, completed
    assert out == base_poisoned and poisoned.error is None
    assert inflight.wait(timeout=120) == base_inflight
    assert inflight.finish == "length"
    assert be.scheduler_alive()


def test_cache_seed_fault_degrades_to_prefill(setup):
    """An injected prefix-cache seeding fault must cost only the cache win:
    the request prefills from scratch and completes identically."""
    spec, params, be = setup
    prompt = [1, 5, 6, 7, 8, 9, 10, 11]
    base = be.submit(list(prompt), 4, _greedy(spec)).wait(timeout=120)
    before = be.prefilled_tokens
    with faults.active(FaultSpec("batch.cache_seed", kind="error")):
        out = be.submit(list(prompt), 4, _greedy(spec)).wait(timeout=120)
    assert out == base
    # seeding was refused, so the scheduler had to prefill at least the
    # portion the same-slot rewind could not cover — and nothing crashed
    assert be.prefilled_tokens >= before


# ------------------------------------------------------------------
# transient dispatch failures: retried, invisible to clients
# ------------------------------------------------------------------

def test_transient_dispatch_retried(setup):
    spec, params, be = setup
    prompt = [1, 7, 23, 5]
    base = be.submit(list(prompt), 10, _greedy(spec)).wait(timeout=120)
    retries0 = _counter_value("engine_retries_total")
    with faults.active(FaultSpec("batch.dispatch", kind="transient",
                                 count=2)) as plan:
        req = be.submit(list(prompt), 10, _greedy(spec))
        out = req.wait(timeout=120)
        assert plan.fired() == 2
    assert out == base
    assert req.error is None and req.finish == "length"
    assert _counter_value("engine_retries_total") >= retries0 + 2


def test_transient_exhausted_fails_requests_but_scheduler_survives(setup):
    spec, params, be = setup
    with faults.active(FaultSpec("batch.dispatch", kind="transient")):
        req = be.submit([1, 2, 3], 8, _greedy(spec))
        with pytest.raises(TransientDispatchError):
            req.wait(timeout=120)
        assert req.finish == "error"
    # plan uninstalled: the SAME scheduler thread serves the next request
    assert be.scheduler_alive()
    out = be.submit([1, 2, 3], 4, _greedy(spec)).wait(timeout=120)
    assert len(out) == 4
    assert all(s.req is None for s in be._slots)


# ------------------------------------------------------------------
# admission control: TTL, deadline, shedding
# ------------------------------------------------------------------

def test_queue_ttl_expiry(setup):
    spec, params, be = setup
    blockers = [be.submit([1, 2, 3 + i], 64, _greedy(spec)) for i in range(2)]
    try:
        _wait_until(lambda: sum(1 for s in be._slots if s.req) == 2,
                    msg="slots occupied")
        # a latency fault paces the blockers (16 scans of ~40 ms): on a quiet
        # box they otherwise end inside the TTL and the victim is admitted
        with faults.active(FaultSpec("batch.dispatch", kind="latency",
                                     delay_ms=40)):
            victim = be.submit([1, 4, 5], 8, _greedy(spec), ttl=0.15)
            with pytest.raises(DeadlineExceeded):
                victim.wait(timeout=60)
        assert victim.finish == "deadline"
        assert victim.out == []  # never admitted, nothing generated
    finally:
        for b in blockers:
            b.cancel()
        for b in blockers:
            b.done.wait(60)


def test_generation_deadline_partial_output(setup):
    spec, params, be = setup
    # a latency fault paces the decode (~40 ms/dispatch) so the deadline
    # reliably lands mid-generation: after the first token, before the
    # context fills — also exercising the latency injection kind in anger
    with faults.active(FaultSpec("batch.dispatch", kind="latency",
                                 delay_ms=40)):
        req = be.submit([1, 2, 3], 1000, _greedy(spec), deadline=0.5)
        out = req.wait(timeout=120)  # no error: partial output was generated
    assert req.finish == "deadline"
    assert 0 < len(out) < 1000


def test_deadline_before_first_token_errors(setup):
    spec, params, be = setup
    blockers = [be.submit([1, 2, 3 + i], 64, _greedy(spec)) for i in range(2)]
    try:
        _wait_until(lambda: sum(1 for s in be._slots if s.req) == 2,
                    msg="slots occupied")
        # paced as its neighbour above: with warm programs the blockers'
        # 64 tokens take under the 0.1 s and the victim is admitted in time
        with faults.active(FaultSpec("batch.dispatch", kind="latency",
                                     delay_ms=40)):
            victim = be.submit([1, 6, 7], 8, _greedy(spec), deadline=0.1)
            with pytest.raises(DeadlineExceeded):
                victim.wait(timeout=60)
        assert victim.finish == "deadline" and victim.out == []
    finally:
        for b in blockers:
            b.cancel()
        for b in blockers:
            b.done.wait(60)


def test_admission_shedding(setup):
    spec, params, be = setup
    shed0 = _counter_value("engine_shed_requests_total")
    blockers = [be.submit([1, 2, 3 + i], 64, _greedy(spec)) for i in range(2)]
    try:
        _wait_until(lambda: sum(1 for s in be._slots if s.req) == 2,
                    msg="slots occupied")
        be.max_queue = 1  # AFTER the blockers left the queue for their slots
        queued = be.submit([1, 8, 9], 8, _greedy(spec))  # fills the queue
        with pytest.raises(EngineSaturated) as ei:
            be.submit([1, 10, 11], 8, _greedy(spec))
        assert ei.value.retry_after > 0
        assert _counter_value("engine_shed_requests_total") >= shed0 + 1
        queued.cancel()
        queued.done.wait(60)
    finally:
        be.max_queue = 0
        for b in blockers:
            b.cancel()
        for b in blockers:
            b.done.wait(60)


# ------------------------------------------------------------------
# typed close errors + drain
# ------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_engine_factory():
    spec = _spec(seq_len=64)
    params = init_random_params(spec, FloatType.Q40, seed=3)

    def make():
        return spec, BatchEngine(spec, params, slots=1, tp=1, superstep=2,
                                 prefix_cache=False)

    return make


def test_close_aborts_with_typed_errors(small_engine_factory):
    spec, be = small_engine_factory()
    inflight = be.submit([1, 2, 3], 500, _greedy(spec))
    _wait_until(lambda: any(s.req is not None for s in be._slots),
                msg="in-flight")
    queued = be.submit([1, 4, 5], 8, _greedy(spec))
    be.close()
    with pytest.raises(EngineClosed):
        inflight.wait(timeout=60)
    with pytest.raises(EngineClosed):
        queued.wait(timeout=60)
    with pytest.raises(EngineClosed):
        be.submit([1], 1, _greedy(spec))


def test_drain_lets_inflight_finish(small_engine_factory):
    spec, be = small_engine_factory()
    req = be.submit([1, 2, 3], 8, _greedy(spec))
    done = threading.Event()
    t = threading.Thread(target=lambda: (be.close(drain=True, timeout=120),
                                         done.set()))
    t.start()
    try:
        _wait_until(lambda: be._draining, msg="drain engaged")
        with pytest.raises(EngineDraining):
            be.submit([1], 1, _greedy(spec))
        out = req.wait(timeout=120)  # in-flight request FINISHED, not aborted
        assert req.error is None and req.finish == "length"
        assert len(out) == 8
        _wait_until(done.is_set, msg="drain close completed")
    finally:
        t.join(timeout=120)
    with pytest.raises(EngineClosed):
        be.submit([1], 1, _greedy(spec))


# ------------------------------------------------------------------
# HTTP server: validation, shedding, TTL, drain round trip
# ------------------------------------------------------------------

@pytest.fixture(scope="module")
def model_files(tmp_path_factory):
    from distributed_llama_tpu.formats.mfile import (params_file_order,
                                                     write_model)
    from distributed_llama_tpu.formats.tfile import (TokenizerData,
                                                     write_tokenizer)
    from distributed_llama_tpu.models.spec import ArchType as AT

    tmp = tmp_path_factory.mktemp("resil_api")
    spec = ModelSpec(arch_type=AT.LLAMA, dim=64, hidden_dim=128, n_layers=2,
                     n_heads=4, n_kv_heads=2, vocab_size=262,
                     seq_len=128).resolved()
    params = init_random_params(spec, FloatType.F32, seed=21)
    mpath = str(tmp / "m.m")
    write_model(mpath, spec, params_file_order(spec, params), FloatType.F32)
    vocab = [b"<unk>", b"<s>", b"</s>"] + [bytes([i]) for i in range(256)] + \
        [b"<|im_start|>", b"<|im_end|>", b" "]
    scores = [0.0] * 259 + [-1.0, -1.0, -1.5]
    tpath = str(tmp / "t.t")
    write_tokenizer(tpath, TokenizerData(
        vocab=vocab, scores=scores, bos_id=1, eos_id=2, chat_eos_id=260,
        max_token_length=12, chat_template="{{<|im_start|>}}"))
    return mpath, tpath


def _make_server(model_files, **be_kw):
    from distributed_llama_tpu.apps.api_server import serve
    from distributed_llama_tpu.formats.mfile import load_model
    from distributed_llama_tpu.tokenizer import TemplateType
    from distributed_llama_tpu.tokenizer.bpe import Tokenizer

    mpath, tpath = model_files
    lspec, lparams = load_model(mpath, 0)
    be = BatchEngine(lspec, lparams, Tokenizer.load(tpath), tp=1,
                     **be_kw)
    srv = serve(None, host="127.0.0.1", port=0,
                template_type=TemplateType.CHATML, batch_engine=be)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv, be, srv.server_address[1]


@pytest.fixture(scope="module")
def resil_server(model_files):
    srv, be, port = _make_server(model_files, slots=1, superstep=4)
    yield srv, be, port
    srv.shutdown()
    be.close()


def _get(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    conn.request("GET", path)
    return conn.getresponse()


def _post(port, body, path="/v1/chat/completions"):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=180)
    conn.request("POST", path, json.dumps(body),
                 {"Content-Type": "application/json"})
    return conn.getresponse()


def test_server_validation_400(resil_server):
    srv, be, port = resil_server
    # prompt beyond seq_len: 400, not a 500 or a stall
    r = _post(port, {"messages": [{"role": "user", "content": "ab" * 400}],
                     "max_tokens": 4})
    assert r.status == 400
    err = json.loads(r.read())["error"]
    assert err["type"] == "invalid_request_error"
    assert "context" in err["message"]
    # invalid max_tokens values: negative, non-integer, boolean
    for bad in (-1, "lots", 2.5, True):
        r = _post(port, {"messages": [{"role": "user", "content": "hi"}],
                         "max_tokens": bad})
        assert r.status == 400, bad
        assert json.loads(r.read())["error"]["type"] == "invalid_request_error"
    # a STREAMING invalid request gets a real 400 (headers are deferred to
    # the first delta), not a 200 SSE stream carrying an error event
    r = _post(port, {"messages": [{"role": "user", "content": "ab" * 400}],
                     "stream": True, "max_tokens": 4})
    assert r.status == 400
    assert json.loads(r.read())["error"]["type"] == "invalid_request_error"


def test_server_sheds_503_with_retry_after(resil_server):
    srv, be, port = resil_server
    spec = be.spec
    be.max_queue = 1
    blocker = be.submit([1, 2, 3], 200, _greedy(spec))
    try:
        _wait_until(lambda: any(s.req is not None for s in be._slots),
                    msg="slot occupied")
        queued = be.submit([1, 4, 5], 4, _greedy(spec))  # fills the queue
        r = _post(port, {"messages": [{"role": "user", "content": "hi"}],
                         "max_tokens": 4})
        assert r.status == 503
        assert r.getheader("Retry-After") is not None
        assert json.loads(r.read())["error"]["type"] == "overloaded_error"
        queued.cancel()
        queued.done.wait(60)
    finally:
        be.max_queue = 0
        blocker.cancel()
        blocker.done.wait(60)


def test_server_queue_ttl_408(resil_server):
    srv, be, port = resil_server
    spec = be.spec
    be.queue_ttl = 0.2
    blocker = be.submit([1, 2, 3], 200, _greedy(spec))
    try:
        _wait_until(lambda: any(s.req is not None for s in be._slots),
                    msg="slot occupied")
        r = _post(port, {"messages": [{"role": "user", "content": "hi"}],
                         "max_tokens": 4})
        assert r.status == 408
        assert json.loads(r.read())["error"]["type"] == "timeout_error"
    finally:
        be.queue_ttl = 0.0
        blocker.cancel()
        blocker.done.wait(60)


def test_server_resilience_metrics_exposed(resil_server):
    srv, be, port = resil_server
    r = _get(port, "/metrics")
    text = r.read().decode()
    for name in ("batch_scheduler_alive", "batch_dispatch_age_seconds",
                 "engine_retries_total", "engine_shed_requests_total",
                 "engine_errors_total", "engine_deadline_expired_total"):
        assert name in text, name
    assert "batch_scheduler_alive 1" in text
    r = _get(port, "/v1/stats")
    stats = json.loads(r.read())["batch_engine"]
    assert stats["scheduler_alive"] is True and stats["draining"] is False


def test_single_engine_request_deadline(model_files):
    """--batch 1 servers enforce --request-deadline too (per decoded token
    via stop_check): a deadline expiring mid-generation returns 200 with
    finish_reason 'deadline' and the partial output."""
    from distributed_llama_tpu.apps.api_server import serve
    from distributed_llama_tpu.runtime.engine import Engine
    from distributed_llama_tpu.tokenizer import TemplateType

    mpath, tpath = model_files
    engine = Engine.load(mpath, tpath, tp=1)
    srv = serve(engine, host="127.0.0.1", port=0,
                template_type=TemplateType.CHATML, request_deadline=0.5)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        # first-request compile alone exceeds the 0.5 s deadline, so the
        # stop fires within the first few tokens — long before max_tokens
        r = _post(srv.server_address[1],
                  {"messages": [{"role": "user", "content": "hi"}],
                   "max_tokens": 100, "temperature": 0})
        assert r.status == 200
        body = json.loads(r.read())
        assert body["choices"][0]["finish_reason"] == "deadline"
    finally:
        srv.shutdown()


def test_server_sigterm_drain_round_trip(model_files):
    """The acceptance round trip: SIGTERM against a live server -> /healthz
    reports draining (503), new requests shed 503, the in-flight request
    completes 200, the server stops — all within --drain-timeout."""
    import signal

    from distributed_llama_tpu.apps.api_server import install_sigterm_drain

    srv, be, port = _make_server(model_files, slots=1, superstep=4)
    old_handler = signal.getsignal(signal.SIGTERM)
    try:
        installed = install_sigterm_drain(srv, srv.api_state,
                                          drain_timeout=120.0)
        if not installed:
            pytest.skip("not the main thread: cannot install SIGTERM handler")

        results = {}

        def inflight():
            r = _post(port, {"messages": [{"role": "user", "content": "hi"}],
                             "max_tokens": 48, "temperature": 0})
            results["status"] = r.status
            results["body"] = json.loads(r.read())

        t = threading.Thread(target=inflight)
        t.start()
        _wait_until(lambda: any(s.req is not None for s in be._slots),
                    timeout=120, msg="in-flight request admitted")

        signal.raise_signal(signal.SIGTERM)  # the real signal path
        _wait_until(lambda: srv.api_state.draining, msg="draining flag")
        r = _get(port, "/healthz")
        assert r.status == 503
        assert json.loads(r.read())["status"] == "draining"
        # new admissions are refused while draining
        r = _post(port, {"messages": [{"role": "user", "content": "late"}],
                         "max_tokens": 4})
        assert r.status == 503

        t.join(timeout=180)
        assert not t.is_alive(), "in-flight request did not finish in drain"
        assert results["status"] == 200, results
        assert results["body"]["choices"][0]["finish_reason"] in (
            "length", "stop")
        # the drain closed the engine: everything ended cleanly
        _wait_until(lambda: be._shutdown, msg="engine closed by drain")
        assert all(s.req is None for s in be._slots)
    finally:
        signal.signal(signal.SIGTERM, old_handler)
        srv.shutdown()
        be.close()
