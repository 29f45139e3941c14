"""Gray-failure resilience (ISSUE 14): latency-aware routing, bounded
hedging, adaptive timeouts, and retry budgets.

- latency units: LatencyStat windowed quantiles/EWMA, TokenBudget
  accrual/spend accounting (incl. an 8-thread hammer — the budget is the
  hedge race's global spend ledger, so its arithmetic must survive
  contention exactly);
- detector units: an outlier enters probation and rejoins after
  consecutive in-band canaries; a UNIFORMLY slow fleet never ejects
  (peer-median baseline); the quorum floor stops ejection from dropping
  rotation below ceil(frac × healthy) — the acceptance-criteria
  regressions;
- Retry-After: a replica 503's hint becomes a pick() cooldown (unit), a
  clean idle poll ends it early, and live: the failover loop stops
  re-hammering the saturated replica while a different replica serves;
- adaptive timeouts: derived pre-first-byte timeout clamps to
  [floor, cap] and holds the cap until enough samples exist;
- sustained-degradation fault window: the 6-field DLLAMA_FAULTS grammar
  and the duration_s expiry (the gray chaos shape);
- live fleet: healthz round-trip surfaced in snapshot()/router /healthz;
  hedge/cancel races settle clean under an 8-thread hammer with
  seeded-stochastic byte-identity (journal reclaimed, inflight balanced,
  affinity stamps a real winner); a stream pacing just under the idle-gap
  timeout completes while a mid-stream stall fails over via the durable
  path byte-identically — the split the fixed 120 s try_timeout could
  not express.
"""

import http.client
import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from distributed_llama_tpu.apps.api_server import serve
from distributed_llama_tpu.fleet.latency import (GrayConfig,
                                                 GrayFailureDetector,
                                                 LatencyStat, TokenBudget)
from distributed_llama_tpu.fleet.membership import Membership, Replica
from distributed_llama_tpu.fleet.router import close_router, serve_router
from distributed_llama_tpu.formats.mfile import (load_model,
                                                 params_file_order,
                                                 write_model)
from distributed_llama_tpu.formats.tfile import TokenizerData, write_tokenizer
from distributed_llama_tpu.models.params import init_random_params
from distributed_llama_tpu.models.spec import ArchType, ModelSpec
from distributed_llama_tpu.obs import metrics as obs_metrics
from distributed_llama_tpu.quants import FloatType
from distributed_llama_tpu.resilience import faults
from distributed_llama_tpu.resilience.faults import FaultSpec, parse_faults
from distributed_llama_tpu.runtime.batch_engine import BatchEngine
from distributed_llama_tpu.tokenizer import TemplateType
from distributed_llama_tpu.tokenizer.bpe import Tokenizer

# ----------------------------------------------------------------------
# latency units
# ----------------------------------------------------------------------


def test_latency_stat_window_recency():
    s = LatencyStat(window=8)
    assert s.quantile(0.5) is None and s.count() == 0
    for _ in range(8):
        s.note(10.0)
    assert s.quantile(0.5) == 10.0
    # the window bounds judgment to RECENT behavior: after 8 fast samples
    # the slow era has fully aged out of every quantile
    for _ in range(8):
        s.note(1.0)
    assert s.quantile(0.99) == 1.0 and s.count() == 16
    assert 1.0 <= s.ewma() < 10.0
    s.reset()
    assert s.count() == 0 and s.quantile(0.5) is None


def test_latency_stat_quantile_ordering():
    s = LatencyStat(window=128)
    for i in range(100):
        s.note(float(i))
    assert s.quantile(0.0) == 0.0
    assert s.quantile(0.5) == 50.0
    assert s.quantile(0.95) == 95.0
    assert s.quantile(1.0) == 99.0


def test_token_budget_starts_full_and_bounds_spend():
    b = TokenBudget(rate=0.5, cap=2.0)
    # starts full: a cold router can still fail over the first incident
    assert b.spend() and b.spend()
    assert not b.spend()  # drained: deny instead of storming
    for _ in range(2):
        b.note()
    assert b.level() == 1.0
    assert b.spend() and not b.spend()
    for _ in range(100):
        b.note()
    assert b.level() == b.cap  # accrual is capped


def test_token_budget_hammer_exact_accounting():
    """8 threads race note()/spend(): granted spends may never exceed the
    initial cap plus everything accrued — the invariant that makes 'hedge
    spend stays within budget' assertable at all."""
    b = TokenBudget(rate=0.25, cap=4.0)
    granted = []

    def worker():
        g = 0
        for _ in range(500):
            b.note()
            if b.spend():
                g += 1
        granted.append(g)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    st = b.stats()
    assert st["noted"] == 4000
    assert sum(granted) == st["spent"]
    assert st["spent"] <= 4.0 + 0.25 * 4000
    assert 0.0 <= st["tokens"] <= b.cap


# ----------------------------------------------------------------------
# detector units (probation, uniform slowness, quorum floor)
# ----------------------------------------------------------------------


def _fake_fleet(n, p50s, min_samples=4):
    """n in-memory replicas with seeded TTFB windows (no sockets)."""
    reps = [Replica("10.0.0.1", 9000 + i) for i in range(n)]
    for rep, p50 in zip(reps, p50s):
        rep.healthy = True
        rep.status = "ok"
        for _ in range(max(min_samples, 4)):
            rep.lat.ttfb.note(p50)
    return reps


def test_detector_ejects_outlier_and_probation_exit():
    cfg = GrayConfig(eject_multiple=4.0, min_samples=4, probation_exits=2,
                     quorum_frac=0.5)
    det = GrayFailureDetector(cfg)
    reps = _fake_fleet(3, [0.05, 0.05, 1.0])
    det.evaluate(reps)
    assert [r.degraded for r in reps] == [False, False, True]
    # degraded replicas leave the peer baseline: re-evaluating must not
    # cascade (the two healthy peers are in-band vs each other)
    det.evaluate(reps)
    assert sum(r.degraded for r in reps) == 1
    # an out-of-band canary resets the streak; consecutive in-band ones
    # rejoin and reset the latency window (no re-eject on stale tail)
    det.note_outcome(reps[2], 0.06, reps)
    det.note_outcome(reps[2], 2.0, reps)   # still slow: streak back to 0
    det.note_outcome(reps[2], 0.06, reps)
    assert reps[2].degraded
    det.note_outcome(reps[2], 0.05, reps)
    assert not reps[2].degraded
    assert reps[2].lat.ttfb.count() == 0  # window reset on rejoin


def test_uniformly_slow_fleet_never_ejects():
    """Acceptance criterion: uniform slowness must degrade honestly — the
    peer-median baseline moves with the fleet, so no replica is an
    outlier vs its peers and nothing is ejected."""
    cfg = GrayConfig(eject_multiple=4.0, min_samples=4)
    det = GrayFailureDetector(cfg)
    reps = _fake_fleet(4, [2.0, 2.0, 2.0, 2.0])
    for _ in range(5):
        det.evaluate(reps)
    assert not any(r.degraded for r in reps)


def test_quorum_floor_holds_rotation():
    """Acceptance criterion: with 2 of 4 replicas genuinely slow and
    quorum_frac=0.75 (floor=3), only ONE may be ejected — the second
    ejection would drop rotation below the floor and is skipped."""
    cfg = GrayConfig(eject_multiple=4.0, min_samples=4, quorum_frac=0.75)
    det = GrayFailureDetector(cfg)
    reps = _fake_fleet(4, [0.05, 0.05, 1.0, 1.0])
    held0 = obs_metrics.snapshot().get(
        "router_probation_quorum_held_total") or 0
    for _ in range(3):
        det.evaluate(reps)
    assert sum(r.degraded for r in reps) == 1
    in_rotation = [r for r in reps if not r.degraded]
    assert len(in_rotation) == 3  # never below the floor
    held1 = obs_metrics.snapshot().get(
        "router_probation_quorum_held_total") or 0
    assert held1 > held0  # the skipped ejection is observable


def test_detector_needs_min_samples():
    cfg = GrayConfig(eject_multiple=4.0, min_samples=64)
    det = GrayFailureDetector(cfg)
    reps = _fake_fleet(2, [0.05, 5.0], min_samples=4)  # only 4 samples each
    det.evaluate(reps)
    assert not any(r.degraded for r in reps)


# ----------------------------------------------------------------------
# Retry-After cooldown + health RTT units
# ----------------------------------------------------------------------


def test_retry_after_cooldown_gates_rotation():
    m = Membership(["127.0.0.1:1", "127.0.0.1:2"])
    a, b = m.replicas
    for r in (a, b):
        r.healthy = True
        r.status = "ok"
    assert len(m.in_rotation()) == 2
    a.note_retry_after(5.0)
    assert a.in_cooldown()
    assert [r.id for r in m.in_rotation()] == [b.id]
    # the cap bounds a pathological header
    a.note_retry_after(9999.0, cap=30.0)
    assert a.retry_after_until - time.monotonic() <= 30.5
    # a clean idle poll (queue drained, slots free) ends the cooldown
    # early: the saturation the 503 reported is gone
    a.apply_poll("ok", True, {"slots": 2, "free_slots": 2,
                              "queue_depth": 0})
    assert not a.in_cooldown()
    # ... but a busy poll does NOT (the advisory window stands)
    a.note_retry_after(5.0)
    a.apply_poll("ok", True, {"slots": 2, "free_slots": 0,
                              "queue_depth": 3})
    assert a.in_cooldown()


def test_health_rtt_tie_break_in_load_score():
    a, b = Replica("10.0.0.1", 1), Replica("10.0.0.1", 2)
    for r in (a, b):
        r.slots = r.free_slots = 2
    b.lat.health_rtt.note(0.5)   # 50 buckets of 10 ms
    a.lat.health_rtt.note(0.01)  # 1 bucket
    assert a.load_score() < b.load_score()
    # equal-load, equal-RTT replicas still order deterministically by id
    a2, b2 = Replica("10.0.0.1", 3), Replica("10.0.0.1", 4)
    assert a2.load_score() < b2.load_score()
    # the snapshot surfaces the signal (None before any sample)
    assert a.snapshot()["health_rtt_ms"] == pytest.approx(10.0)
    assert a2.snapshot()["health_rtt_ms"] is None


def test_adaptive_ttfb_timeout_clamps():
    """Derived pre-first-byte timeout: the --proxy-timeout cap until
    enough samples, then mult × fleet p95 clamped to [floor, cap]."""
    from distributed_llama_tpu.fleet.router import RouterState

    m = Membership(["127.0.0.1:1"])
    st = RouterState(m, try_timeout=60.0,
                     gray=GrayConfig(min_lat_samples=8, ttfb_floor=2.0,
                                     ttfb_mult=6.0))
    assert st.ttfb_timeout() == 60.0  # no evidence: the old fixed behavior
    for _ in range(8):
        st.fleet_ttfb.note(0.05)
    assert st.ttfb_timeout() == 2.0  # 6 × 0.05 = 0.3 → floor
    for _ in range(32):
        st.fleet_ttfb.note(100.0)
    assert st.ttfb_timeout() == 60.0  # 6 × 100 → cap
    # idle-gap: fixed when configured, adaptive (mult × pace p99) else
    st.gray.idle_timeout = 7.5
    assert st.idle_timeout() == 7.5
    st.gray.idle_timeout = 0.0
    assert st.idle_timeout() == 60.0  # no pace evidence yet
    for _ in range(32):
        st.fleet_pace.note(0.02)
    assert st.idle_timeout() == pytest.approx(10.0)  # 50×0.02=1 → floor 10
    # hedge delay: None without evidence (adaptive), then ~p95
    st.gray.hedge_delay = 0.0
    st2 = RouterState(m, gray=GrayConfig(min_lat_samples=8))
    assert st2.hedge_delay() is None
    for _ in range(8):
        st2.fleet_ttfb.note(0.4)
    assert st2.hedge_delay() == pytest.approx(0.4)


# ----------------------------------------------------------------------
# sustained-degradation fault window
# ----------------------------------------------------------------------


def test_fault_spec_duration_grammar():
    (spec,) = parse_faults("api.request:latency:1::800:45")
    assert spec.kind == "latency" and spec.delay_ms == 800.0
    assert spec.duration_s == 45.0
    (spec,) = parse_faults("api.request:latency:1::800:")  # empty = none
    assert spec.duration_s is None
    with pytest.raises(ValueError):
        parse_faults("p:latency:1::800:45:extra")
    with pytest.raises(ValueError):
        parse_faults("p:latency:1::800:xyz")


def test_fault_duration_window_expires():
    """A sustained-degradation spec fires for duration_s after its FIRST
    fire, then stops — the replica 'recovers', which is what probation
    exit detection needs to observe."""
    spec = FaultSpec("gray.t", kind="latency", delay_ms=1.0,
                     duration_s=0.15)
    with faults.active(spec):
        faults.fire("gray.t")
        assert spec.fired == 1
        faults.fire("gray.t")
        assert spec.fired == 2
        time.sleep(0.2)
        faults.fire("gray.t")
        assert spec.fired == 2  # window expired: injection over
    faults.uninstall()


# ----------------------------------------------------------------------
# live: Retry-After honored across a failover
# ----------------------------------------------------------------------


class _SaturatedStub(ThreadingHTTPServer):
    """A replica that answers healthz ok (idle-looking, so least-loaded
    routing prefers it) but 503s every completion with a Retry-After —
    the saturated-replica shape the cooldown exists for."""

    def __init__(self):
        self.post_hits = 0
        stub = self

        class H(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_GET(self):
                body = json.dumps({
                    "status": "ok",
                    "replica": {"slots": 8, "free_slots": 8,
                                "queue_depth": 0},
                }).encode()
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_POST(self):
                self.rfile.read(int(self.headers.get("Content-Length", 0)))
                stub.post_hits += 1
                body = json.dumps({"error": {
                    "message": "saturated", "type": "overloaded_error"
                }}).encode()
                self.send_response(503)
                self.send_header("Content-Type", "application/json")
                self.send_header("Retry-After", "7")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        super().__init__(("127.0.0.1", 0), H)
        threading.Thread(target=self.serve_forever, daemon=True).start()


def test_retry_after_honored_live(fleet):
    """A replica 503ing with Retry-After serves exactly ONE try: the hint
    becomes a pick() cooldown, the request fails over, and later requests
    never re-hammer the stub until a clean idle poll clears it."""
    stub = _SaturatedStub()
    real_port = fleet["reps"][0][2]
    # warm the real replica through ITS router first: the test's first
    # completion must not pay a cold XLA compile, or the background poll
    # below fires mid-test and early-clears the cooldown under assertion
    warm = _stream(fleet["port"], _body(seed=4, max_tokens=4, user="warm"))
    assert warm["status"] == 200, warm
    honored0 = obs_metrics.snapshot().get(
        "router_retry_after_honored_total") or 0
    # poll_interval far past the test: no background poll can early-clear
    # the cooldown mid-assertion (the idle-shaped stub healthz would)
    router = serve_router(
        [f"127.0.0.1:{stub.server_address[1]}", f"127.0.0.1:{real_port}"],
        host="127.0.0.1", port=0, poll_interval=3600.0, retries=2,
        try_timeout=30.0,
        gray=GrayConfig(min_lat_samples=10 ** 9, hedge=False))
    threading.Thread(target=router.serve_forever, daemon=True).start()
    try:
        state = router.router_state
        victim = state.membership.by_id(
            f"127.0.0.1:{stub.server_address[1]}")
        r1 = _stream(router.server_address[1], _body(seed=5, max_tokens=6,
                                                     user="retry one"))
        assert r1["status"] == 200 and r1["error"] is None, r1
        assert stub.post_hits == 1  # idle-looking stub was tried first
        assert victim.in_cooldown()
        assert victim.snapshot()["cooldown_s"] > 0
        assert [r.id for r in state.membership.in_rotation()] \
            == [f"127.0.0.1:{real_port}"]
        honored1 = obs_metrics.snapshot().get(
            "router_retry_after_honored_total") or 0
        assert honored1 > honored0
        # a second request respects the cooldown
        r2 = _stream(router.server_address[1], _body(seed=6, max_tokens=6,
                                                     user="retry two"))
        assert r2["status"] == 200 and stub.post_hits == 1
        # a clean idle poll ends the cooldown early: back in rotation
        state.membership.poll_once()
        assert not victim.in_cooldown()
        assert len(state.membership.in_rotation()) == 2
    finally:
        close_router(router)
        stub.shutdown()
        stub.server_close()


def test_censored_timeout_canary_resets_rejoin_streak():
    """A canary try that TIMED OUT records a censored sample ("at least
    this slow") — when the effective TTFB timeout sits below the ejection
    threshold that value would read as in-band, so it must reset the
    rejoin streak, never extend it: a replica whose canaries produce no
    headers stays in probation."""
    from distributed_llama_tpu.fleet.router import RouterState

    m = Membership(["127.0.0.1:1", "127.0.0.1:2"])
    a, b = m.replicas
    for r in (a, b):
        r.healthy = True
        r.status = "ok"
    state = RouterState(m, gray=GrayConfig(min_samples=4,
                                           eject_multiple=4.0,
                                           probation_exits=3))
    for _ in range(8):
        b.lat.ttfb.note(0.1)  # peer baseline: median 100 ms
    a.set_degraded(True)
    state.note_ttfb(a, 0.15)  # in-band canaries build a streak...
    state.note_ttfb(a, 0.15)
    assert a.canary_ok == 2 and a.degraded
    # ...a censored timeout sample UNDER the 4x threshold resets it
    state.note_ttfb(a, 0.2, ok=False)
    assert a.canary_ok == 0 and a.degraded
    # and censored samples alone can never drive a rejoin
    for _ in range(6):
        state.note_ttfb(a, 0.2, ok=False)
    assert a.degraded


class _SlowOkStub(ThreadingHTTPServer):
    """A replica that answers healthz ok (idle-looking) and serves every
    completion successfully but SLOWLY — the viable-primary shape a
    saturated hedge target must not cancel."""

    def __init__(self, delay_s: float):
        self.post_hits = 0
        stub = self

        class H(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_GET(self):
                body = json.dumps({
                    "status": "ok",
                    "replica": {"slots": 8, "free_slots": 8,
                                "queue_depth": 0},
                }).encode()
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_POST(self):
                self.rfile.read(int(self.headers.get("Content-Length", 0)))
                stub.post_hits += 1
                time.sleep(delay_s)
                body = json.dumps({"id": "slow-ok", "choices": [
                    {"message": {"role": "assistant", "content": "done"},
                     "finish_reason": "stop"}]}).encode()
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        super().__init__(("127.0.0.1", 0), H)
        threading.Thread(target=self.serve_forever, daemon=True).start()


def test_hedge_503_does_not_cancel_viable_primary():
    """A hedge target answering 503 must not win the race: the refusal is
    stashed while the slow-but-viable primary finishes, the client gets
    the primary's 200, and the primary is served exactly ONCE (crowning
    the 503 used to cancel the in-flight primary and redo its work)."""
    slow = _SlowOkStub(delay_s=0.9)
    sat = _SaturatedStub()
    slow_id = f"127.0.0.1:{slow.server_address[1]}"
    # durable (default) path: its upstream leg always streams, so the
    # hedge arms even for this non-stream client; the stub's plain-JSON
    # 200 rides the pre-stream relay verbatim
    router = serve_router(
        [slow_id, f"127.0.0.1:{sat.server_address[1]}"],
        host="127.0.0.1", port=0, poll_interval=3600.0, retries=2,
        try_timeout=30.0,
        gray=GrayConfig(min_lat_samples=10 ** 9, min_samples=10 ** 9,
                        hedge=True, hedge_delay=0.25))
    threading.Thread(target=router.serve_forever, daemon=True).start()
    try:
        # the saturated stub must be the HEDGE, not the primary: give it
        # a worse polled load block than the idle-looking slow stub
        sat_rep = router.router_state.membership.by_id(
            f"127.0.0.1:{sat.server_address[1]}")
        sat_rep.apply_poll("ok", True, {"slots": 8, "free_slots": 1,
                                        "queue_depth": 5})
        conn = http.client.HTTPConnection("127.0.0.1",
                                          router.server_address[1],
                                          timeout=15.0)
        conn.request("POST", "/v1/chat/completions",
                     json.dumps({"messages": [{"role": "user",
                                               "content": "hi"}],
                                 "max_tokens": 4}).encode(),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        data = json.loads(resp.read())
        conn.close()
        assert resp.status == 200, data
        assert data["id"] == "slow-ok"
        assert slow.post_hits == 1   # never canceled + retried
        assert sat.post_hits == 1    # the hedge really launched (and lost)
        launched = (obs_metrics.snapshot().get("router_hedges_total")
                    or {}).get('{outcome="launched"}', 0)
        assert launched >= 1
    finally:
        close_router(router)
        for s in (slow, sat):
            s.shutdown()
            s.server_close()


# ----------------------------------------------------------------------
# live gray fleet
# ----------------------------------------------------------------------


@pytest.fixture(scope="module")
def model_files(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("gray")
    spec = ModelSpec(arch_type=ArchType.LLAMA, dim=64, hidden_dim=128,
                     n_layers=2, n_heads=4, n_kv_heads=2, vocab_size=262,
                     seq_len=192).resolved()
    params = init_random_params(spec, FloatType.F32, seed=23)
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


@pytest.fixture(scope="module")
def fleet(model_files):
    """Two REAL replicas + the durable router with the gray layer armed
    but inert (adaptive thresholds AND the outlier detector parked at
    never-adapt; tests flip the shared GrayConfig per scenario and restore
    it). The detector judges wall-clock TTFBs: left at its default of 20
    samples, the hammer test's slowed victim could enter probation or not
    by how a loaded box timed its requests, and every later test on this
    fleet inherited a rotation of one."""
    mpath, tpath = model_files
    reps = []
    for _ in range(2):
        lspec, lparams = load_model(mpath, 0)
        be = BatchEngine(lspec, lparams, Tokenizer.load(tpath), slots=2,
                         tp=1, superstep=4)
        srv = serve(None, host="127.0.0.1", port=0,
                    template_type=TemplateType.CHATML, batch_engine=be)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        reps.append((be, srv, srv.server_address[1]))
    router = serve_router([f"127.0.0.1:{p}" for _, _, p in reps],
                          host="127.0.0.1", port=0, poll_interval=0.15,
                          block_bytes=16, retries=2, try_timeout=60.0,
                          gray=GrayConfig(min_lat_samples=10 ** 9,
                                          min_samples=10 ** 9, hedge=False))
    threading.Thread(target=router.serve_forever, daemon=True).start()
    yield {"reps": reps, "router": router,
           "port": router.server_address[1]}
    close_router(router)
    for be, srv, _p in reps:
        srv.shutdown()
        srv.server_close()
        be.close()


@pytest.fixture()
def gray_cfg(fleet):
    """Mutate the router's live GrayConfig for one test, restore after."""
    g = fleet["router"].router_state.gray
    saved = dict(vars(g))
    yield g
    for k, v in saved.items():
        setattr(g, k, v)


def _body(seed=None, temperature=0.8, stream=True, max_tokens=40,
          user="hello gray"):
    b = {"messages": [
        {"role": "system", "content": "gray shared system prompt"},
        {"role": "user", "content": user}],
        "max_tokens": max_tokens, "temperature": temperature,
        "stream": stream}
    if seed is not None:
        b["seed"] = seed
    return b


def _stream(port, body, on_delta=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    try:
        conn.request("POST", "/v1/chat/completions", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            return {"status": resp.status,
                    "body": json.loads(resp.read() or b"{}")}
        if not body.get("stream"):
            data = json.loads(resp.read())
            return {"status": 200, "error": None,
                    "text": data["choices"][0]["message"]["content"],
                    "finish": data["choices"][0].get("finish_reason")}
        text, err, finish, n = [], None, None, 0
        while True:
            line = resp.readline()
            if not line:
                break
            line = line.decode().strip()
            if not line.startswith("data: ") or line == "data: [DONE]":
                continue
            payload = json.loads(line[6:])
            if "error" in payload:
                err = payload["error"]
                break
            d = payload["choices"][0]["delta"].get("content")
            f = payload["choices"][0].get("finish_reason")
            if f:
                finish = f
            if d:
                text.append(d)
                n += 1
                if on_delta:
                    on_delta(n)
        return {"status": 200, "text": "".join(text), "error": err,
                "finish": finish}
    finally:
        conn.close()


def test_health_rtt_surfaced_live(fleet):
    """The poller's healthz round-trip reaches snapshot() and the router's
    own /healthz — the latency signal exists before any traffic flows."""
    state = fleet["router"].router_state
    state.membership.poll_once()
    for rep in state.membership.replicas:
        assert rep.snapshot()["health_rtt_ms"] is not None
    conn = http.client.HTTPConnection("127.0.0.1", fleet["port"],
                                      timeout=10)
    try:
        conn.request("GET", "/healthz")
        body = json.loads(conn.getresponse().read())
    finally:
        conn.close()
    assert body["degraded"] == []
    for blk in body["replicas"].values():
        assert blk["health_rtt_ms"] is not None
        assert "cooldown_s" in blk


def test_hedge_hammer_settles_clean(fleet, gray_cfg):
    """8 threads hammer seeded-stochastic completions through a fleet
    whose victim replica serves 400 ms slow, with an aggressive fixed
    hedge delay. Every response must be byte-identical to the fault-free
    reference (the pre-first-byte phase is idempotent, first byte wins),
    and the winner/loser settlement must leak NOTHING: journal entries
    reclaimed, per-replica inflight back to zero, hedge spend inside the
    budget, affinity stamped with a real winner."""
    from distributed_llama_tpu.fleet.latency import TokenBudget

    state = fleet["router"].router_state
    gray_cfg.hedge = True
    gray_cfg.hedge_delay = 0.1
    gray_cfg.hedge_pct = 1.0  # the hammer tests settlement, not the cap
    gray_cfg.hedge_burst = 8.0
    saved_budget = state.hedge_budget
    state.hedge_budget = TokenBudget(gray_cfg.hedge_pct,
                                     gray_cfg.hedge_burst)
    # unique LEADING system prompts: the affinity key is block-granular,
    # so a shared prefix would pin every request to one replica — cold
    # keys spread primaries across BOTH replicas, and the victim-primary
    # half is what exercises hedge launch + cancel
    bodies = []
    for k in range(8):
        for i in range(4):
            b = _body(seed=424242, temperature=0.9, max_tokens=10,
                      stream=(k + i) % 2 == 0)
            b["messages"][0]["content"] = f"h{k}.{i} gray hammer system"
            bodies.append(b)
    refs = [_stream(fleet["port"], dict(b)) for b in bodies]
    for r in refs:
        assert r["status"] == 200 and r["error"] is None, r
    victim_id = f"127.0.0.1:{fleet['reps'][0][2]}"
    results: dict[int, dict] = {}

    def worker(k):
        for i in range(4):
            results[k * 4 + i] = _stream(fleet["port"],
                                         dict(bodies[k * 4 + i]))

    try:
        with faults.active(FaultSpec("api.request", kind="latency",
                                     delay_ms=400.0,
                                     match={"replica": victim_id})):
            threads = [threading.Thread(target=worker, args=(k,))
                       for k in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
    finally:
        faults.uninstall()
        state.hedge_budget, hammered = saved_budget, state.hedge_budget
    assert len(results) == 32
    for idx, r in results.items():
        assert r["status"] == 200 and r["error"] is None, (idx, r)
        # a double-delivery or a loser's bytes folding in would diverge
        assert r["text"] == refs[idx]["text"], idx
    st = hammered.stats()
    assert st["spent"] >= 1, "vacuous: no hedge ever launched"
    assert st["spent"] <= st["cap"] + gray_cfg.hedge_pct * st["noted"]
    # settlement leaks nothing: journal reclaimed, inflight balanced
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        leaked = [r.id for r in state.membership.replicas if r.inflight]
        if not leaked and state.journal.inflight() == 0:
            break
        time.sleep(0.02)
    assert state.journal.inflight() == 0
    assert not leaked, f"hedge losers leaked inflight on {leaked}"
    # affinity stamped only real winners: every node the hammer recorded
    # resolves to a live replica (a canceled loser stamping would poison
    # future routing toward a replica that never delivered)
    assert state.affinity.nodes() >= 1


def _warm_replicas(fleet, body):
    """Drive `body` (non-stream) DIRECTLY against each replica so its XLA
    programs are compiled before a test arms a tight idle-gap timeout —
    a cold compile stalls the stream far past any reasonable gap and
    would read as a wedge."""
    for _be, _srv, p in fleet["reps"]:
        b = dict(body)
        b["stream"] = False
        conn = http.client.HTTPConnection("127.0.0.1", p, timeout=120)
        try:
            conn.request("POST", "/v1/chat/completions", json.dumps(b),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            assert resp.status == 200, resp.read()
            resp.read()
        finally:
            conn.close()


def test_slow_paced_stream_survives_idle_timeout(fleet, gray_cfg):
    """Acceptance regression: a healthy-but-slow long stream whose
    per-token gaps sit just UNDER the idle-gap timeout must complete —
    the timeout judges the gap between events, never total duration
    (which here far exceeds the 1.5 s idle timeout)."""
    body = _body(seed=None, temperature=0.0, max_tokens=24,
                 user="slow but healthy")
    # reference + program warm BEFORE arming the tight timeout: the
    # greedy decode program may not be compiled yet, and a cold compile
    # is a legitimate >1.5 s stall, not the wedge under test
    ref = _stream(fleet["port"], dict(body))
    assert ref["status"] == 200 and ref["error"] is None, ref
    _warm_replicas(fleet, body)
    gray_cfg.idle_timeout = 1.5
    resumed0 = obs_metrics.snapshot().get(
        "router_resumed_requests_total") or 0
    t0 = time.monotonic()
    with faults.active(FaultSpec("batch.dispatch", kind="latency",
                                 delay_ms=300.0)):
        try:
            got = _stream(fleet["port"], dict(body))
        finally:
            faults.uninstall()
    assert got["status"] == 200 and got["error"] is None, got
    assert got["text"] == ref["text"] and got["finish"] == ref["finish"]
    assert time.monotonic() - t0 > 1.5  # the stream really outlived the gap
    resumed1 = obs_metrics.snapshot().get(
        "router_resumed_requests_total") or 0
    assert resumed1 == resumed0  # completed in place, no spurious failover


def test_stalled_stream_fails_over_within_idle_gap(fleet, gray_cfg):
    """The other half of the split: a mid-stream STALL (engine wedged in a
    600 s dispatch, socket open, nothing arriving) trips the idle-gap
    timeout in ~1.5 s instead of the old fixed 120 s, and the durable path
    resumes on the surviving replica byte-identically."""
    body = _body(seed=31337, temperature=0.8, max_tokens=40,
                 user="stall mid stream")
    ref = _stream(fleet["port"], dict(body))
    assert ref["status"] == 200 and ref["error"] is None, ref
    _warm_replicas(fleet, body)
    gray_cfg.idle_timeout = 1.5
    resumed0 = obs_metrics.snapshot().get(
        "router_resumed_requests_total") or 0
    stalled = []

    def stall(n):
        if n == 4 and not stalled:
            stalled.append(time.monotonic())
            faults.install([FaultSpec("batch.dispatch", kind="latency",
                                      delay_ms=600_000.0, count=1)])

    try:
        got = _stream(fleet["port"], dict(body), on_delta=stall)
    finally:
        faults.uninstall()
    assert stalled, "stall never engaged"
    assert got["status"] == 200 and got["error"] is None, got
    assert got["text"] == ref["text"] and got["finish"] == ref["finish"]
    assert time.monotonic() - stalled[0] < 45.0  # not the old 120 s shape
    resumed1 = obs_metrics.snapshot().get(
        "router_resumed_requests_total") or 0
    assert resumed1 > resumed0  # the durable path did the save
    # unstick the wedged engine (its scheduler sleeps in the injected
    # dispatch) so later tests inherit a working fleet. The stream is over,
    # so the survivor has nothing in flight (age 0); the victim's age is
    # the idle gap the router waited out, and more
    wedged = [be for be, _srv, _p in fleet["reps"]
              if be.dispatch_age() >= gray_cfg.idle_timeout]
    assert len(wedged) == 1, [be.dispatch_age() for be, _s, _p in fleet["reps"]]
    wedged[0].recover_wedged()
    state = fleet["router"].router_state
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        state.membership.poll_once()
        if len(state.membership.in_rotation()) == 2:
            break
        time.sleep(0.1)
    assert len(state.membership.in_rotation()) == 2
