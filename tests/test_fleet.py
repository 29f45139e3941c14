"""Fleet tier tests (ISSUE 6): the prefix-affinity router over 2 in-process
tiny replicas.

- AffinityMap unit/property tests against a brute-force longest-shared-prefix
  oracle (latest-wins per block, walk-up on dead replicas, LRU node cap);
- merge_prometheus label injection + family-header dedup;
- live fleet: shared-prefix requests route sticky to one replica, a draining
  or hard-killed replica is rerouted around with ZERO failed requests, a
  fully-drained fleet sheds with 503 + Retry-After, and streaming vs
  non-streaming parity holds through the proxy;
- membership poller: `router.health` fault injection ejects a replica for the
  round and it rejoins on the next clean poll (the poller thread survives).

Both replicas live in THIS process (two BatchEngines + two api_server
ThreadingHTTPServers on ephemeral ports), so the obs metrics registry is
shared between them — per-replica assertions therefore instrument the
engines directly (submit counters) instead of reading process-global
counters. Full subprocess-per-replica coverage is bench.py --replicas N
(docs/FLEET.md).
"""

import http.client
import json
import random
import threading
import time

import pytest

from distributed_llama_tpu.apps.api_server import serve
from distributed_llama_tpu.fleet.affinity import AffinityMap
from distributed_llama_tpu.fleet.membership import Membership, parse_addr
from distributed_llama_tpu.fleet.router import (close_router, merge_prometheus,
                                                serve_router)
from distributed_llama_tpu.formats.mfile import load_model, params_file_order, write_model
from distributed_llama_tpu.formats.tfile import TokenizerData, write_tokenizer
from distributed_llama_tpu.models.params import init_random_params
from distributed_llama_tpu.models.spec import ArchType, ModelSpec
from distributed_llama_tpu.quants import FloatType
from distributed_llama_tpu.resilience import faults
from distributed_llama_tpu.resilience.faults import FaultSpec
from distributed_llama_tpu.runtime.batch_engine import BatchEngine
from distributed_llama_tpu.tokenizer import TemplateType
from distributed_llama_tpu.tokenizer.bpe import Tokenizer


# ----------------------------------------------------------------------
# AffinityMap vs brute-force oracle
# ----------------------------------------------------------------------

class OracleAffinity:
    """Reference semantics: every record stamps ALL full block-prefixes of its
    key with the replica (latest-wins); lookup returns the deepest stamped
    block-prefix of the query whose replica is alive."""

    def __init__(self, block_bytes: int):
        self.bb = block_bytes
        self.owner: dict[bytes, str] = {}

    def _prefixes(self, key: bytes):
        for d in range(self.bb, len(key) + 1, self.bb):
            yield key[:d]

    def record(self, key: bytes, replica: str) -> None:
        for p in self._prefixes(key):
            self.owner[p] = replica

    def lookup(self, key: bytes, alive: set[str]):
        best = (None, 0)
        for depth, p in enumerate(self._prefixes(key), start=1):
            rep = self.owner.get(p)
            if rep is None:
                break
            if rep in alive:
                best = (rep, depth)
        return best


def test_affinity_matches_oracle_randomized():
    rng = random.Random(7)
    bb = 4
    m = AffinityMap(block_bytes=bb, max_nodes=10_000)  # cap never hit here
    oracle = OracleAffinity(bb)
    replicas = ["r0", "r1", "r2"]
    # tiny alphabet + short keys force heavy prefix sharing
    for step in range(600):
        key = bytes(rng.choice(b"ab") for _ in range(rng.randrange(0, 20)))
        if rng.random() < 0.5:
            rep = rng.choice(replicas)
            m.record(key, rep)
            oracle.record(key, rep)
        else:
            alive = {r for r in replicas if rng.random() < 0.7}
            assert m.lookup(key, alive) == oracle.lookup(key, alive), (
                step, key, alive)


def test_affinity_walkup_on_dead_replica():
    m = AffinityMap(block_bytes=2, max_nodes=64)
    m.record(b"aabb", "r1")      # depth-2 chain owned by r1
    m.record(b"aa", "r2")        # depth-1 node re-stamped by r2 (latest wins)
    assert m.lookup(b"aabb", {"r1", "r2"}) == ("r1", 2)
    # r1 dead: walk up to the depth-1 ancestor instead of giving up
    assert m.lookup(b"aabb", {"r2"}) == ("r2", 1)
    assert m.lookup(b"aabb", set()) == (None, 0)
    # partial blocks never match (block granularity, like the replica cache)
    assert m.lookup(b"a", {"r1", "r2"}) == (None, 0)


def test_affinity_node_cap_lru():
    m = AffinityMap(block_bytes=1, max_nodes=8)
    for i in range(64):
        m.record(bytes([i]) * 3, f"r{i}")
    assert m.nodes() <= 8
    # the most recent record survived the LRU sweep
    assert m.lookup(bytes([63]) * 3, {"r63"})[0] == "r63"


# ----------------------------------------------------------------------
# merge_prometheus
# ----------------------------------------------------------------------

def test_merge_prometheus_labels_and_headers():
    own = "# HELP up router up\n# TYPE up gauge\nup 1\n"
    rep = ("# HELP http_total requests\n# TYPE http_total counter\n"
           'http_total{route="/x"} 3\nhttp_total 4\n')
    merged = merge_prometheus([(None, own), ("h1:1", rep), ("h2:2", rep)])
    lines = merged.splitlines()
    # router-own sample stays unlabeled; replica samples get replica="id"
    assert "up 1" in lines
    assert 'http_total{replica="h1:1",route="/x"} 3' in lines
    assert 'http_total{replica="h2:2"} 4' in lines
    # one HELP/TYPE per family even with two sources
    assert sum(ln.startswith("# HELP http_total") for ln in lines) == 1
    assert sum(ln.startswith("# TYPE http_total") for ln in lines) == 1


def test_parse_addr():
    assert parse_addr("127.0.0.1:9990") == ("127.0.0.1", 9990)
    with pytest.raises(ValueError):
        parse_addr("nope")
    with pytest.raises(ValueError):
        Membership([])


# ----------------------------------------------------------------------
# live fleet fixtures
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def model_files(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("fleet")
    spec = ModelSpec(arch_type=ArchType.LLAMA, dim=64, hidden_dim=128,
                     n_layers=2, n_heads=4, n_kv_heads=2, vocab_size=262,
                     seq_len=192).resolved()
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


class ReplicaHarness:
    """One in-process api_server replica with a submit counter on its engine."""

    def __init__(self, model_files):
        mpath, tpath = model_files
        lspec, lparams = load_model(mpath, 0)
        self.be = BatchEngine(lspec, lparams, Tokenizer.load(tpath),
                              slots=2, tp=1)
        self.submits = 0
        orig = self.be.submit

        def counted(*a, **k):
            self.submits += 1
            return orig(*a, **k)

        self.be.submit = counted
        self.srv = serve(None, host="127.0.0.1", port=0,
                         template_type=TemplateType.CHATML, batch_engine=self.be)
        self.port = self.srv.server_address[1]
        self.id = f"127.0.0.1:{self.port}"
        threading.Thread(target=self.srv.serve_forever, daemon=True).start()
        self.closed = False

    def kill(self):
        if not self.closed:
            self.closed = True
            self.srv.shutdown()
            self.srv.server_close()

    def close(self):
        self.kill()
        self.be.close()


@pytest.fixture(scope="module")
def fleet(model_files):
    reps = [ReplicaHarness(model_files) for _ in range(2)]
    router = serve_router([r.id for r in reps], host="127.0.0.1", port=0,
                          poll_interval=0.15, poll_timeout=2.0,
                          block_bytes=16, retries=2, try_timeout=60.0)
    rport = router.server_address[1]
    threading.Thread(target=router.serve_forever, daemon=True).start()
    yield {"replicas": reps, "router": router, "port": rport,
           "state": router.router_state}
    close_router(router)
    for r in reps:
        r.close()


def _get(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    conn.request("GET", path)
    return conn.getresponse()


def _post(port, body, timeout=120):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("POST", "/v1/chat/completions", json.dumps(body),
                 {"Content-Type": "application/json"})
    return conn.getresponse()


def _body(system, user, stream=False, max_tokens=4):
    return {"messages": [{"role": "system", "content": system},
                         {"role": "user", "content": user}],
            "max_tokens": max_tokens, "temperature": 0, "stream": stream}


def _read_sse_text(resp) -> str:
    """Collect content deltas from an SSE completion response."""
    assert "text/event-stream" in resp.getheader("Content-Type", "")
    text, raw = [], resp.read().decode()
    for line in raw.splitlines():
        if not line.startswith("data: ") or line == "data: [DONE]":
            continue
        payload = json.loads(line[6:])
        assert "error" not in payload, payload
        delta = payload["choices"][0]["delta"]
        text.append(delta.get("content", ""))
    return "".join(text)


def _restore_rotation(fleet):
    """Undo any drain/kill a test left behind and re-poll membership."""
    for r in fleet["replicas"]:
        r.srv.api_state.draining = False
    fleet["state"].membership.poll_once()
    assert len(fleet["state"].membership.in_rotation()) == \
        sum(1 for r in fleet["replicas"] if not r.closed)


# ----------------------------------------------------------------------
# live fleet tests
# ----------------------------------------------------------------------

def test_replica_healthz_block_and_backcompat(fleet):
    """Satellite 1: /healthz keeps `status` (existing probes) and gains the
    identity/load block the membership poller consumes."""
    rep = fleet["replicas"][0]
    payload = json.loads(_get(rep.port, "/healthz").read())
    assert payload["status"] == "ok"  # the pre-fleet probe contract
    block = payload["replica"]
    assert block["id"] == rep.id
    assert block["slots"] == 2 and 0 <= block["free_slots"] <= 2
    assert block["queue_depth"] >= 0 and block["draining"] is False
    assert len(block["model_hash"]) == 12
    # /v1/stats carries the same block
    stats = json.loads(_get(rep.port, "/v1/stats").read())
    assert stats["replica"]["model_hash"] == block["model_hash"]


def test_router_healthz(fleet):
    payload = json.loads(_get(fleet["port"], "/healthz").read())
    assert payload["role"] == "router"
    assert payload["in_rotation"] == 2
    assert set(payload["replicas"]) == {r.id for r in fleet["replicas"]}


def test_shared_prefix_routes_sticky(fleet):
    """Requests sharing a system prompt land on ONE replica (affinity), and
    the streaming path records affinity too."""
    _restore_rotation(fleet)
    before = [r.submits for r in fleet["replicas"]]
    system = "You are a terse assistant. Answer in one word." * 2
    r0 = _post(fleet["port"], _body(system, "first"))
    assert r0.status == 200 and r0.read()
    for i in range(3):
        resp = _post(fleet["port"], _body(system, f"user {i}", stream=True))
        assert resp.status == 200
        _read_sse_text(resp)
    delta = [r.submits - b for r, b in zip(fleet["replicas"], before)]
    assert sorted(delta) == [0, 4], delta  # all four on the same replica
    # the router recorded the route and can look it up
    key = fleet["state"].affinity_key(_body(system, "another"))
    rep_id, depth = fleet["state"].affinity.lookup(
        key, {r.id for r in fleet["replicas"]})
    assert rep_id == fleet["replicas"][delta.index(4)].id and depth >= 1


def test_stream_nonstream_parity_through_router(fleet):
    _restore_rotation(fleet)
    body = _body("parity system prompt", "same question", max_tokens=6)
    r1 = _post(fleet["port"], body)
    assert r1.status == 200
    text1 = json.loads(r1.read())["choices"][0]["message"]["content"]
    r2 = _post(fleet["port"], dict(body, stream=True))
    assert r2.status == 200
    assert _read_sse_text(r2) == text1


def test_drain_reroutes_with_zero_failures(fleet):
    """Drain the replica that owns a shared prefix mid-fleet: every request
    still completes (failover to the survivor), and the affinity map follows
    the traffic to the new replica."""
    _restore_rotation(fleet)
    system = "Drain test system prompt, shared by all requests here."
    assert _post(fleet["port"], _body(system, "warm")).status == 200
    key = fleet["state"].affinity_key(_body(system, "x"))
    owner_id, _ = fleet["state"].affinity.lookup(
        key, {r.id for r in fleet["replicas"]})
    owner = next(r for r in fleet["replicas"] if r.id == owner_id)
    survivor = next(r for r in fleet["replicas"] if r.id != owner_id)
    owner.srv.api_state.draining = True  # SIGTERM's first effect
    try:
        fleet["state"].membership.poll_once()
        assert [r.id for r in fleet["state"].membership.in_rotation()] == \
            [survivor.id]
        before = survivor.submits
        for i in range(3):
            resp = _post(fleet["port"], _body(system, f"after-drain {i}",
                                              stream=(i % 2 == 0)))
            assert resp.status == 200, (i, resp.status, resp.read())
            _read_sse_text(resp) if i % 2 == 0 else resp.read()
        assert survivor.submits - before == 3
        # latest-wins: the prefix now maps to the survivor
        assert fleet["state"].affinity.lookup(
            key, {r.id for r in fleet["replicas"]})[0] == survivor.id
    finally:
        owner.srv.api_state.draining = False
        fleet["state"].membership.poll_once()
    assert len(fleet["state"].membership.in_rotation()) == 2  # rejoined


def test_saturated_fleet_sheds_503_retry_after(fleet):
    _restore_rotation(fleet)
    for r in fleet["replicas"]:
        r.srv.api_state.draining = True
    try:
        fleet["state"].membership.poll_once()
        assert fleet["state"].membership.in_rotation() == []
        resp = _post(fleet["port"], _body("any", "request"))
        assert resp.status == 503
        assert int(resp.getheader("Retry-After")) >= 1
        err = json.loads(resp.read())["error"]
        assert err["type"] in ("overloaded_error", "server_shutting_down")
        # router /healthz reflects the empty rotation
        assert _get(fleet["port"], "/healthz").status == 503
    finally:
        _restore_rotation(fleet)


def test_health_fault_point_ejects_then_rejoins(fleet):
    """router.health chaos: an injected poll error marks replicas unreachable
    for the round; the poller survives and readmits on the next clean poll."""
    _restore_rotation(fleet)
    mem = fleet["state"].membership
    with faults.active(FaultSpec("router.health", kind="error", count=2)):
        mem.poll_once()
        assert mem.in_rotation() == []
        assert all(r.status == "unreachable" for r in mem.replicas)
    mem.poll_once()
    assert len(mem.in_rotation()) == 2


def test_proxy_fault_point_fails_over(fleet):
    """router.proxy chaos on the first try: the request still completes on a
    different replica (pre-first-byte failover), counted as a retry."""
    _restore_rotation(fleet)
    with faults.active(FaultSpec("router.proxy", kind="error", count=1)):
        resp = _post(fleet["port"], _body("proxy fault system", "q"))
        assert resp.status == 200
        assert json.loads(resp.read())["choices"][0]["message"]["content"]


# ----------------------------------------------------------------------
# end-to-end request tracing (ISSUE 7 tentpole)
# ----------------------------------------------------------------------

def _post_traced(port, body, traceparent=None, timeout=120):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    headers = {"Content-Type": "application/json"}
    if traceparent:
        headers["traceparent"] = traceparent
    conn.request("POST", "/v1/chat/completions", json.dumps(body), headers)
    return conn.getresponse()


def test_trace_context_propagates_through_fleet_concurrently(fleet):
    """Satellite 3 acceptance: concurrent requests through the REAL
    2-replica fleet — every engine-side span/instant and every flight
    timeline carries exactly the trace id its request entered with, with no
    cross-request bleed even though one super-step serves many requests."""
    from distributed_llama_tpu.obs import flight as flight_mod
    from distributed_llama_tpu.obs import trace as trace_mod

    _restore_rotation(fleet)
    tr = trace_mod.install(capacity=65536)
    try:
        n = 6
        tids = [f"{i:02x}" * 16 for i in range(1, n + 1)]
        results = [None] * n

        def client(i):
            # distinct shared prefixes spread requests over both replicas
            resp = _post_traced(
                fleet["port"],
                _body(f"system prompt {i % 2}", f"traced user {i}",
                      max_tokens=5),
                traceparent=f"00-{tids[i]}-{'77' * 8}-01")
            rid = resp.getheader("X-Request-Id")
            rep = resp.getheader("X-Replica")
            status = resp.status
            resp.read()
            results[i] = {"status": status, "rid": rid, "replica": rep}

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert all(r and r["status"] == 200 for r in results), results
        # the router relays the replica's identity headers end-to-end
        assert all(r["rid"] and r["replica"] for r in results), results

        rec = flight_mod.current()
        assert rec is not None  # installed by serve()
        by_tid = {}
        for i, r in enumerate(results):
            full = rec.get(r["rid"])
            assert full is not None, r
            # the flight record carries the trace id the CLIENT sent — it
            # crossed router → replica handler → scheduler intact
            assert full["trace_id"] == tids[i], (i, full["trace_id"])
            assert full["finish"] in ("length", "stop")
            names = [e["event"] for e in full["events"]]
            assert "admitted" in names, names
            by_tid[tids[i]] = r["rid"]

        # tracer side: every event stamped with one of our trace ids must be
        # engine-side work (batch.*) or a router proxy span; each request
        # has at least one engine-side event; ids never mix
        # a span is recorded when it EXITS, and the router's handler thread
        # leaves `router.proxy` after the reply's last byte has reached the
        # client: on a loaded machine the client is back here first
        deadline = time.monotonic() + 10.0
        while True:
            per_tid = {t: [] for t in tids}
            for e in tr.events():
                t = (e.get("args") or {}).get("trace_id")
                if t in per_tid:
                    per_tid[t].append(e["name"])
            if (all("router.proxy" in names for names in per_tid.values())
                    or time.monotonic() > deadline):
                break
            time.sleep(0.02)
        for t, names in per_tid.items():
            assert any(nm.startswith("batch.") for nm in names), (t, names)
            assert "router.proxy" in names, (t, names)

        # the slow-request workflow works THROUGH the router: /v1/requests
        # lookups relay to the replica holding the record (clients may not
        # be able to reach replicas directly), listings merge per replica
        r0 = results[0]
        via_router = json.loads(
            _get(fleet["port"], f"/v1/requests/{r0['rid']}").read())
        assert via_router["id"] == r0["rid"]
        assert via_router["trace_id"] == tids[0]
        merged = json.loads(
            _get(fleet["port"], "/v1/requests?slowest=2").read())
        assert set(merged["replicas"]) == {r.id for r in fleet["replicas"]}
        miss = _get(fleet["port"], "/v1/requests/chatcmpl-nonexistent")
        assert miss.status == 404

        # fleet-merged /v1/trace: sources for the router AND both replicas,
        # distinct pids, our spans present (everything shares this process's
        # tracer here — the per-process separation is bench.py --replicas)
        doc = json.loads(_get(fleet["port"], "/v1/trace").read())
        procs = doc["otherData"]["processes"]
        assert len(procs) == 3 and len({p["pid"] for p in procs}) == 3
        assert {p["name"] for p in procs} == {
            "router", *(f"replica {r.id}" for r in fleet["replicas"])}
        stamped = {(e.get("args") or {}).get("trace_id")
                   for e in doc["traceEvents"]}
        assert set(tids) <= stamped
    finally:
        trace_mod.uninstall()


def test_fleet_stats_include_replica_process_identity(fleet):
    """Membership carries the replica's pid/uptime from /healthz into the
    router's snapshot (restart-loop visibility)."""
    _restore_rotation(fleet)
    payload = json.loads(_get(fleet["port"], "/healthz").read())
    import os

    for snap in payload["replicas"].values():
        assert snap["pid"] == os.getpid()  # in-process replicas
        assert snap["uptime_s"] > 0


def test_hard_kill_failover_zero_failures(fleet):
    """SIGKILL analog: close one replica's listener without telling anyone.
    The next requests hit a dead socket pre-first-byte and fail over; no
    client-visible failure. Runs LAST in the module: the killed replica's
    HTTP server is gone for good (its engine is closed by the fixture)."""
    _restore_rotation(fleet)
    system = "Hard kill shared system prompt for failover."
    assert _post(fleet["port"], _body(system, "warm")).status == 200
    key = fleet["state"].affinity_key(_body(system, "x"))
    owner_id, _ = fleet["state"].affinity.lookup(
        key, {r.id for r in fleet["replicas"]})
    owner = next(r for r in fleet["replicas"] if r.id == owner_id)
    survivor = next(r for r in fleet["replicas"] if r.id != owner_id)
    owner.kill()  # affinity still points at the corpse; membership is stale
    failures = []
    for i in range(4):
        resp = _post(fleet["port"], _body(system, f"post-kill {i}",
                                          stream=(i % 2 == 0)))
        if resp.status != 200:
            failures.append((i, resp.status, resp.read()))
        else:
            _read_sse_text(resp) if i % 2 == 0 else resp.read()
    assert failures == []
    # the proxy-path mark_failed ejected the corpse synchronously
    assert [r.id for r in fleet["state"].membership.in_rotation()] == \
        [survivor.id]
    # membership holds it unreachable on subsequent polls too
    fleet["state"].membership.poll_once()
    assert fleet["state"].membership.by_id(owner.id).status == "unreachable"


def test_router_metrics_merged_with_replica_labels(fleet):
    """Fleet /metrics: router-own families plus replica-labeled scrapes.
    (Both replicas share this process's registry, so per-replica VALUES are
    not meaningful here — bench.py --replicas covers that; the merge
    structure and labels are what this pins.)"""
    text = _get(fleet["port"], "/metrics").read().decode()
    assert "# TYPE router_routes_total counter" in text
    assert text.count("# TYPE router_routes_total counter") == 1
    alive = [r for r in fleet["replicas"] if not r.closed]
    for r in alive:
        assert f'replica="{r.id}"' in text
    # replica-side families arrive labeled
    assert 'api_http_requests_total{replica="' in text
    stats = json.loads(_get(fleet["port"], "/v1/stats").read())
    assert stats["router"]["policy"] == "affinity"
    for r in alive:
        assert stats["replicas"][r.id]["replica"]["model_hash"]


def test_unknown_routes_and_bad_json(fleet):
    assert _get(fleet["port"], "/nope").status == 404
    conn = http.client.HTTPConnection("127.0.0.1", fleet["port"], timeout=30)
    conn.request("POST", "/v1/chat/completions", b"{not json",
                 {"Content-Type": "application/json"})
    assert conn.getresponse().status == 400



# ----------------------------------------------------------------------
# Replica status atomicity (ISSUE 10 satellite: lock-guard pass finding)
# ----------------------------------------------------------------------

def test_replica_status_mutation_is_atomic():
    """Regression for a lock-guard finding (docs/ANALYSIS.md): Replica
    health/status used to be mutated bare from BOTH the membership poller
    thread and every proxy handler thread (`mark_failed`), so concurrent
    ejections could lose `consecutive_failures` increments (the backoff
    exponent input) and readers could observe torn states. All mutation now
    goes through `_lock`-holding Replica methods; this hammers them from 8
    threads and asserts exact counting plus never-torn snapshots."""
    from distributed_llama_tpu.fleet.membership import Replica

    rep = Replica("host", 1234)
    n_threads, n_iter = 8, 300
    torn: list[dict] = []
    stop = threading.Event()

    def reader():
        while not stop.is_set():
            snap = rep.snapshot()
            # atomic invariant: healthy=True only ever coexists with "ok"
            # (apply_poll sets both in one critical section)
            if snap["healthy"] and snap["status"] != "ok":
                torn.append(snap)

    def hammer(k: int):
        barrier.wait()
        for i in range(n_iter):
            if (i + k) % 3 == 0:
                rep.apply_poll("ok", True, {"slots": 2, "free_slots": 1,
                                            "queue_depth": i})
            else:
                rep.mark_unreachable()

    barrier = threading.Barrier(n_threads)
    rt = threading.Thread(target=reader, daemon=True)
    rt.start()
    threads = [threading.Thread(target=hammer, args=(k,))
               for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    rt.join(timeout=5)
    assert not torn, f"torn replica snapshots observed: {torn[:3]}"

    # exact increment accounting: with bare `+= 1` from N threads, CPython's
    # read-modify-write interleaving can lose updates; under the lock the
    # count is exact
    rep2 = Replica("host", 4321)
    barrier = threading.Barrier(n_threads)

    def eject():
        barrier.wait()
        for _ in range(n_iter):
            rep2.mark_unreachable()

    threads = [threading.Thread(target=eject) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert rep2.consecutive_failures == n_threads * n_iter
    assert rep2.status == "unreachable" and not rep2.healthy
