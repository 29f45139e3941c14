"""Prefill/decode disaggregation (ISSUE 13, docs/DISAGG.md).

Four layers, cheapest first:

- the shared KV wire codec (cache/wire.py): raw mode bit-exact over random
  shapes/dtypes, Q80 mode bounded-error AND bit-identical to the block
  pool's own cold-tier round trip (one arithmetic, two consumers),
  truncation raises;
- role plumbing: healthz role field with back-compat (role-less payloads
  read as "both"), role-preferring pick();
- host-side import machinery: PagedPrefixCache.insert_cold coverage +
  eviction under a full cold tier, KVTransferTable TTL/cap;
- a LIVE disaggregated fleet (in-process prefill-role + decode-role
  replicas behind the real router): long-prompt requests split, ship KV,
  import, admit with ZERO re-prefill of the shipped span, and produce
  byte-identical output to the monolithic path — greedy and
  seeded-stochastic; a broken transfer falls back to local prefill with no
  client-visible failure.
"""

import http.client
import json
import threading

import numpy as np
import pytest

from distributed_llama_tpu.cache.device_pool import DeviceKVPool, PagedPrefixCache
from distributed_llama_tpu.cache.wire import (block_wire_bytes, decode_blocks,
                                              encode_blocks, q80_compress,
                                              q80_compressible, q80_restore)
from distributed_llama_tpu.fleet.disagg import (DECODE_ROLES, PREFILL_ROLES,
                                                DisaggPlanner, KVTransferTable,
                                                estimate_prompt_tokens,
                                                tokens_hash)
from distributed_llama_tpu.fleet.membership import Membership, Replica
from distributed_llama_tpu.fleet.router import RouterState, close_router, serve_router
from distributed_llama_tpu.formats.mfile import (load_model, params_file_order,
                                                 write_model)
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
# wire codec
# ----------------------------------------------------------------------

def test_wire_codec_property_random_shapes():
    """Round trip over random shapes/dtypes: raw is bit-exact; Q80 is
    bounded-error and EQUALS the block pool's cold-tier reconstruction
    bit-for-bit (the extraction's whole point: the in-RAM tier and the
    wire can never drift)."""
    import ml_dtypes

    rng = np.random.default_rng(7)
    for trial in range(24):
        ndim = int(rng.integers(1, 5))
        shape = tuple(int(rng.integers(1, 7)) for _ in range(ndim))
        dtype = [np.float32, np.float16, ml_dtypes.bfloat16][trial % 3]
        blocks = []
        for _ in range(int(rng.integers(1, 4))):
            k = rng.standard_normal(shape).astype(dtype)
            v = rng.standard_normal(shape).astype(dtype)
            blocks.append((k, v))
        raw = encode_blocks(blocks)
        assert block_wire_bytes(blocks) == len(raw)
        out = decode_blocks(raw)
        assert len(out) == len(blocks)
        for (k, v), (k2, v2) in zip(blocks, out):
            assert k2.dtype == k.dtype and k2.shape == k.shape
            assert np.array_equal(k2, k) and np.array_equal(v2, v)
        q = encode_blocks(blocks, q80=True)
        assert block_wire_bytes(blocks, q80=True) == len(q)
        for (k, v), (k2, v2) in zip(blocks, decode_blocks(q)):
            if q80_compressible(k.shape):
                # identical to the pool's own demote->get reconstruction
                assert np.array_equal(
                    k2, q80_restore(q80_compress(k), k.shape, k.dtype))
                # bounded error: per 32-group absmax/254
                err = np.abs(k2.astype(np.float32) - k.astype(np.float32))
                bound = np.abs(k.astype(np.float32)).max() / 127.0 + 1e-6
                assert err.max() <= bound, (shape, dtype, err.max(), bound)
            else:  # incompressible shapes fall back to raw: bit-exact
                assert np.array_equal(k2, k) and np.array_equal(v2, v)


def test_wire_codec_q80_smaller_and_truncation_raises():
    rng = np.random.default_rng(1)
    k = rng.standard_normal((2, 2, 16, 8)).astype(np.float32)
    blocks = [(k, k.copy())]
    raw, q = encode_blocks(blocks), encode_blocks(blocks, q80=True)
    assert len(q) < len(raw) / 3  # ~34 bytes per 32 f32 values
    with pytest.raises(ValueError):
        decode_blocks(raw[: len(raw) // 2])
    with pytest.raises(ValueError):
        decode_blocks(b"\xff" + raw[1:])  # corrupt count -> over-read


# ----------------------------------------------------------------------
# role plumbing
# ----------------------------------------------------------------------

def test_replica_role_backcompat_old_payload():
    """A role-less healthz block (pre-disagg replica, rolling upgrade) must
    parse as role 'both'; a role-carrying one as advertised; the snapshot
    (what the router /healthz serves) surfaces it."""
    rep = Replica("127.0.0.1", 1)
    assert rep.role == "both"
    rep.apply_poll("ok", True, {"slots": 2, "free_slots": 2,
                                "queue_depth": 0})  # the OLD payload shape
    assert rep.role == "both"
    assert rep.snapshot()["role"] == "both"
    rep.apply_poll("ok", True, {"slots": 2, "role": "prefill"})
    assert rep.role == "prefill"
    assert rep.snapshot()["role"] == "prefill"
    rep.apply_poll("ok", True, {"slots": 2})  # role vanished again
    assert rep.role == "both"


def _fake_membership(roles):
    mem = Membership([f"127.0.0.1:{9000 + i}" for i in range(len(roles))])
    for rep, role in zip(mem.replicas, roles):
        rep.healthy = True
        rep.status = "ok"
        rep.role = role
    return mem


def test_pick_prefers_roles_softly():
    mem = _fake_membership(["prefill", "decode"])
    state = RouterState(mem)
    rep, _ = state.pick(b"k", set(), prefer_roles=DECODE_ROLES)
    assert rep.role == "decode"
    rep, _ = state.pick(b"k", set(), prefer_roles=PREFILL_ROLES)
    assert rep.role == "prefill"
    # soft preference: no candidate in the preferred set -> whole rotation
    rep, _ = state.pick(b"k", {mem.replicas[1].id},
                        prefer_roles=DECODE_ROLES)
    assert rep is not None and rep.role == "prefill"


def test_planner_threshold_and_topology_gates():
    planner = DisaggPlanner(threshold_tokens=32)
    long_body = {"messages": [{"role": "user", "content": "x" * 400}]}
    short_body = {"messages": [{"role": "user", "content": "hi"}]}
    assert estimate_prompt_tokens(long_body) >= 32
    # below threshold / disabled -> no plan, no network
    assert DisaggPlanner(0).plan(_fake_membership(["prefill", "decode"]),
                                 long_body) is None
    assert planner.plan(_fake_membership(["prefill", "decode"]),
                        short_body) is None
    # no distinct decode candidate -> no_topology, no network
    assert planner.plan(_fake_membership(["prefill"]), long_body) is None
    assert planner.plan(_fake_membership(["both"]), long_body) is None
    # homogeneous all-"both" fleets (incl. role-less back-compat payloads)
    # NEVER split — arming the threshold on a monolithic fleet is inert
    assert planner.plan(_fake_membership(["both", "both"]),
                        long_body) is None
    # resume/kv_source bodies never re-split
    assert planner.plan(_fake_membership(["prefill", "decode"]),
                        dict(long_body, resume={"tokens": [1]})) is None
    assert planner.plan(_fake_membership(["prefill", "decode"]),
                        dict(long_body, kv_source={"xfer_id": "x"})) is None
    # role preference: kv_source -> decode; unsplit long -> prefill;
    # short -> decode; homogeneous fleet -> None (no perturbation)
    mem = _fake_membership(["prefill", "decode"])
    assert planner.prefer_roles(dict(long_body, kv_source={}),
                                mem) == DECODE_ROLES
    assert planner.prefer_roles(long_body, mem) == PREFILL_ROLES
    assert planner.prefer_roles(short_body, mem) == DECODE_ROLES
    assert planner.prefer_roles(long_body,
                                _fake_membership(["both", "both"])) is None


def test_planner_warm_skip_follows_resident_prefix():
    """A decode-capable replica that already served the full prefix (per
    the router's affinity map) makes splitting wasteful — the planner
    skips it and prefer_roles follows the warm replica instead of
    steering the long prompt to a prefill replica."""
    from distributed_llama_tpu.fleet.affinity import AffinityMap

    planner = DisaggPlanner(threshold_tokens=32)
    mem = _fake_membership(["prefill", "decode"])
    decode_id = mem.replicas[1].id
    amap = AffinityMap(block_bytes=16)
    key = b"k" * 64
    long_body = {"messages": [{"role": "user", "content": "x" * 400}]}
    # cold key: no warm replica, long prompts prefer prefill-capable
    assert planner.warm_decode(mem, amap, key) is None
    assert planner.prefer_roles(long_body, mem, amap, key) == PREFILL_ROLES
    # the PREFILL replica serving it does not make it warm (not
    # decode-capable), so splitting remains correct
    amap.record(key, mem.replicas[0].id)
    assert planner.warm_decode(mem, amap, key) is None
    # once the DECODE replica served it, the planner skips the split and
    # routing follows the warm cache
    amap.record(key, decode_id)
    assert planner.warm_decode(mem, amap, key) == decode_id
    assert planner.prefer_roles(long_body, mem, amap, key) == DECODE_ROLES
    assert planner.plan(mem, long_body, affinity=amap, key=key) is None


def test_transfer_table_ttl_and_cap():
    table = KVTransferTable(cap=2, ttl=1000.0)
    k = np.zeros((1, 1, 4, 2), np.float32)
    descs = [table.open([1, 2, 3, 4], [(k, k)], 4, "raw") for _ in range(3)]
    assert table.stats()["live"] <= 2
    assert table.get(descs[0]["xfer_id"]) is None  # oldest evicted by cap
    assert table.get(descs[2]["xfer_id"]) is not None
    assert descs[2]["n_tokens"] == 4 and descs[2]["n_blocks"] == 1
    assert descs[2]["tokens_hash"] == tokens_hash([1, 2, 3, 4])
    # TTL expiry
    short = KVTransferTable(cap=2, ttl=0.0)
    d = short.open([1, 2, 3, 4], [(k, k)], 4, "raw")
    assert short.get(d["xfer_id"]) is None
    # consumption: a fetch covering the FINAL block drops the remaining
    # lifetime to consumed_ttl so completed transfers free their slot
    cons = KVTransferTable(cap=2, ttl=1000.0, consumed_ttl=0.0)
    d = cons.open(list(range(8)), [(k, k), (k, k)], 4, "raw")
    t = cons.get(d["xfer_id"])
    cons.note_served(t, 0, 1)  # partial range: still live
    assert cons.get(d["xfer_id"]) is not None
    cons.note_served(t, 1, 1)  # final block served: consumed
    assert cons.get(d["xfer_id"]) is None


# ----------------------------------------------------------------------
# host-side import machinery
# ----------------------------------------------------------------------

def _host_block(rng, bt=4):
    return (rng.standard_normal((1, 1, bt, 2)).astype(np.float32),
            rng.standard_normal((1, 1, bt, 2)).astype(np.float32))


def test_insert_cold_covers_and_lookup_serves():
    rng = np.random.default_rng(3)
    pool = DeviceKVPool(8, 4)
    pc = PagedPrefixCache(pool, 4, cold_blocks=8)
    tokens = list(range(10, 22))  # 3 full blocks
    blocks = [_host_block(rng) for _ in range(3)]
    assert pc.insert_cold(tokens, blocks) == 3
    lease = pc.lookup(tokens + [99])
    assert lease is not None and lease.tokens == 12
    for node, (k, _v) in zip(lease.nodes, blocks):
        tier, h = node.handle
        assert tier == "cold"
        got_k, _got_v = pc.fetch_cold(h)
        assert np.array_equal(got_k, k)
    pc.release(lease)
    # idempotent re-import: existing nodes keep their handles, coverage holds
    assert pc.insert_cold(tokens, [_host_block(rng) for _ in range(3)]) == 3
    assert pc.stats()["cold_blocks"] == 3


def test_insert_cold_full_tier_stops_chain_then_evicts_lru():
    rng = np.random.default_rng(4)
    pool = DeviceKVPool(8, 4)
    pc = PagedPrefixCache(pool, 4, cold_blocks=2)
    # 3 blocks into a 2-block cold tier: the chain being inserted is pinned
    # (its own nodes are not evictable), so coverage stops at 2
    covered = pc.insert_cold(list(range(12)), [_host_block(rng)
                                               for _ in range(3)])
    assert covered == 2
    # a DIFFERENT prefix now evicts the first chain's LRU nodes
    covered = pc.insert_cold(list(range(100, 108)),
                             [_host_block(rng) for _ in range(2)])
    assert covered == 2
    assert pc.stats()["cold_blocks"] == 2
    assert pool.free_blocks() == 7  # imports never touch device blocks


# ----------------------------------------------------------------------
# live disaggregated fleet
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def model_files(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("disagg")
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


@pytest.fixture(scope="module")
def disagg_fleet(model_files):
    from distributed_llama_tpu.apps.api_server import serve

    mpath, tpath = model_files
    reps = []
    for role in ("prefill", "decode"):
        lspec, lparams = load_model(mpath, 0)
        be = BatchEngine(lspec, lparams, Tokenizer.load(tpath), slots=2,
                         tp=1, superstep=4)
        srv = serve(None, host="127.0.0.1", port=0,
                    template_type=TemplateType.CHATML, batch_engine=be,
                    role=role)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        reps.append({"role": role, "be": be, "srv": srv,
                     "port": srv.server_address[1]})
    router = serve_router([f"127.0.0.1:{r['port']}" for r in reps],
                          host="127.0.0.1", port=0, poll_interval=0.15,
                          block_bytes=16, retries=2, try_timeout=60.0,
                          disagg_threshold=24)
    threading.Thread(target=router.serve_forever, daemon=True).start()
    yield {"replicas": reps, "router": router,
           "port": router.server_address[1],
           "state": router.router_state}
    close_router(router)
    for r in reps:
        r["srv"].shutdown()
        r["srv"].server_close()
        r["be"].close()


def _post(port, body, timeout=120):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("POST", "/v1/chat/completions", json.dumps(body),
                 {"Content-Type": "application/json"})
    return conn.getresponse()


def _long_body(seed=None, stream=False, salt=""):
    body = {"messages": [{"role": "system", "content": "s" * 80},
                         {"role": "user",
                          "content": f"tell me something {salt}"}],
            "max_tokens": 10, "temperature": 0, "stream": stream}
    if seed is not None:
        body.update(temperature=0.9, seed=seed)
    return body


def _completion_text(resp):
    assert resp.status == 200, resp.read()
    data = json.loads(resp.read())
    return data["choices"][0]["message"]["content"]


def _snapshot():
    from distributed_llama_tpu.obs import metrics

    return metrics.snapshot()


def _counter(snap, name, label=None):
    v = snap.get(name) or 0
    if isinstance(v, dict):
        return v.get(label, 0) if label else sum(v.values())
    return v


def _reference(fleet, body):
    """Monolithic reference output: same fleet, split disabled."""
    state = fleet["state"]
    thr = state.disagg.threshold
    state.disagg.threshold = 0
    try:
        return _completion_text(_post(fleet["port"], body))
    finally:
        state.disagg.threshold = thr


def test_disagg_split_byte_identical_and_zero_reprefill(disagg_fleet):
    """The tentpole end-to-end: a long-prompt completion splits (prefill on
    the prefill replica, KV shipped, decode elsewhere), output is
    byte-identical to the monolithic run (raw wire is bit-exact), and the
    decode replica re-prefills ZERO shipped tokens. The DISAGG request
    runs first (a cold affinity key — once a decode replica holds the
    prefix, the planner's warm-skip deliberately stops splitting it)."""
    s0 = _snapshot()
    out = _completion_text(_post(disagg_fleet["port"], _long_body()))
    ref = _reference(disagg_fleet, _long_body())
    assert out == ref
    s1 = _snapshot()
    assert (_counter(s1, "router_disagg_requests_total",
                     '{outcome="split"}')
            > _counter(s0, "router_disagg_requests_total",
                       '{outcome="split"}'))
    assert (_counter(s1, "disagg_import_requests_total",
                     '{outcome="imported"}')
            > _counter(s0, "disagg_import_requests_total",
                       '{outcome="imported"}'))
    assert _counter(s1, "disagg_import_tokens_total") > \
        _counter(s0, "disagg_import_tokens_total")
    assert _counter(s1, "disagg_reprefill_tokens_total") == \
        _counter(s0, "disagg_reprefill_tokens_total"), \
        "shipped KV was re-prefilled"


def test_disagg_seeded_stochastic_identity(disagg_fleet):
    """Stochastic sampling with a pinned seed: the disaggregated decode
    replica draws the SAME xorshift* stream (imported KV is bit-exact raw
    wire), so output matches the monolithic run byte-for-byte."""
    body = _long_body(seed=1234)
    ref = _reference(disagg_fleet, body)
    out = _completion_text(_post(disagg_fleet["port"], body))
    assert out == ref


def test_disagg_stream_parity(disagg_fleet):
    body = _long_body(stream=True)
    ref = _reference(disagg_fleet, _long_body())
    resp = _post(disagg_fleet["port"], body)
    assert resp.status == 200
    text = []
    for line in resp.read().decode().splitlines():
        if line.startswith("data: ") and line != "data: [DONE]":
            payload = json.loads(line[6:])
            assert "error" not in payload, payload
            text.append(payload["choices"][0]["delta"].get("content") or "")
    assert "".join(text) == ref


def test_export_endpoint_ranges_resumable_and_404(disagg_fleet):
    """GET /v1/kv/<id> contract: any range re-fetchable (the resumability
    primitive), bad ranges 400, unknown ids 404."""
    from distributed_llama_tpu.cache.wire import decode_blocks as dec
    from distributed_llama_tpu.fleet.disagg import fetch_kv_blocks

    pre = disagg_fleet["replicas"][0]
    # plant a transfer directly on the prefill replica's table
    rng = np.random.default_rng(5)
    blocks = [(rng.standard_normal((2, 2, 16, 8)).astype(np.float32),
               rng.standard_normal((2, 2, 16, 8)).astype(np.float32))
              for _ in range(3)]
    desc = pre["srv"].api_state.kv_transfers.open(
        list(range(48)), blocks, 16, "raw")
    xid = desc["xfer_id"]
    for _ in range(2):  # same range twice: resumable by construction
        got = fetch_kv_blocks("127.0.0.1", pre["port"], xid, 1, 2)
        assert len(got) == 2
        assert np.array_equal(got[0][0], blocks[1][0])
    conn = http.client.HTTPConnection("127.0.0.1", pre["port"], timeout=30)
    conn.request("GET", f"/v1/kv/{xid}?from=2&n=5")
    assert conn.getresponse().status == 400
    conn.close()
    conn = http.client.HTTPConnection("127.0.0.1", pre["port"], timeout=30)
    conn.request("GET", "/v1/kv/kv-nonexistent?from=0&n=1")
    assert conn.getresponse().status == 404
    conn.close()
    assert dec is not None  # silence unused-import style checks


def test_broken_transfer_falls_back_to_local_prefill(disagg_fleet):
    """Mid-transfer failure (the prefill replica dies between the plan and
    the fetch): the decode replica abandons the import and prefills
    locally — the client sees a normal, byte-identical completion. Unique
    prompt (cold affinity key, so the split actually engages) and the
    faulted request runs before its reference."""
    body = _long_body(salt="broken")
    s0 = _snapshot()
    # every fetch attempt fails (count covers the per-chunk retry too)
    with faults.active(FaultSpec("disagg.fetch", kind="error", count=64)):
        out = _completion_text(_post(disagg_fleet["port"], body))
    faults.uninstall()
    ref = _reference(disagg_fleet, body)
    assert out == ref
    s1 = _snapshot()
    assert (_counter(s1, "disagg_import_requests_total",
                     '{outcome="error"}')
            > _counter(s0, "disagg_import_requests_total",
                       '{outcome="error"}'))


def test_import_seeded_admission_stays_on_manifest():
    """ISSUE 13 satellite (docs/ANALYSIS.md): an import-seeded admission —
    shipped blocks entering as cold directory nodes, promoted to device at
    admission, suffix prefill + scans — must ride the programs
    perf/compile_manifest.json pins (the promotion is an untracked
    single-block pool update; the admission reuses existing programs). And
    a shape drift smuggled in THROUGH the same path must still be caught:
    an off-bucket scan after the import-seeded admission fails the gate
    with the cache key named."""
    from distributed_llama_tpu.analysis import compile_audit
    from distributed_llama_tpu.cache.wire import decode_blocks as dec
    from distributed_llama_tpu.cache.wire import encode_blocks as enc
    from distributed_llama_tpu.runtime.sampler import Sampler

    pinned = compile_audit.load_manifest()
    assert pinned is not None, "perf/compile_manifest.json missing"
    spec = compile_audit.scenario_spec()
    params = init_random_params(spec, FloatType.Q40, seed=11)
    audit = compile_audit.CompileAudit()
    with audit:
        eng = BatchEngine(spec, params, slots=2, superstep=4, pipeline=True,
                          tp=1, prefix_cache=True)
        try:
            assert eng.kv_pool is not None
            bt = eng.slot_cache.block_tokens
            rng = np.random.default_rng(9)
            L, _n, hk, _bt, hs = eng._eng.k_cache.shape
            blocks = [(rng.standard_normal((L, hk, bt, hs))
                       .astype(np.float32),
                       rng.standard_normal((L, hk, bt, hs))
                       .astype(np.float32))]
            prompt = [(5 * i + 1) % spec.vocab_size for i in range(bt + 1)]
            assert eng.import_kv_blocks(prompt[:bt],
                                        dec(enc(blocks))) == bt
            req = eng.submit(list(prompt), 6, Sampler(spec.vocab_size))
            req.wait(60)
            # the shipped span was reused, not re-prefilled
            assert req.stats.reused_tokens == bt
            clean = compile_audit.diff_manifest(audit.manifest(), pinned)
            assert clean == [], "\n".join(f.message for f in clean)
            eng._batched_loop(7, "greedy", None)  # injected drift
        finally:
            eng.close()
    findings = compile_audit.diff_manifest(audit.manifest(), pinned)
    assert any("batched_scan[k=7,mode=greedy,window=None,paged=16]"
               in f.message for f in findings), \
        [f.message for f in findings]


def test_prefill_leg_carries_tenant_and_class(disagg_fleet):
    """The remote prefill is charged to the requesting tenant at its real
    class (docs/DISAGG.md): POST /v1/kv with relayed X-Tenant/X-Class must
    attribute the prefill request to that tenant, batch class."""
    pre = disagg_fleet["replicas"][0]
    conn = http.client.HTTPConnection("127.0.0.1", pre["port"], timeout=120)
    conn.request("POST", "/v1/kv", json.dumps(
        {"messages": [{"role": "system", "content": "t" * 80},
                      {"role": "user", "content": "attribution"}]}),
        {"Content-Type": "application/json", "X-Tenant": "gold",
         "X-Class": "batch"})
    resp = conn.getresponse()
    desc = json.loads(resp.read())
    conn.close()
    assert resp.status == 200 and desc["n_blocks"] > 0
    from distributed_llama_tpu.obs import metrics

    fam = metrics.snapshot().get("batch_tenant_requests_total") or {}
    assert any("gold" in k and "batch" in k for k in fam), fam


def test_router_strips_client_supplied_kv_source(disagg_fleet):
    """Trust model (docs/DISAGG.md): kv_source is ROUTER-OWNED. A client
    smuggling a descriptor pointing at an arbitrary host must have it
    stripped at the edge — no fetch to the attacker address, no import
    attempt, the request served normally (monolithic: below threshold)."""
    s0 = _snapshot()
    body = {"messages": [{"role": "user", "content": "short q"}],
            "max_tokens": 4, "temperature": 0,
            "kv_source": {"replica": "127.0.0.1:9", "xfer_id": "kv-evil",
                          "n_tokens": 16, "n_blocks": 1,
                          "block_tokens": 16, "tokens_hash": "0" * 16,
                          "wire": "raw"}}
    resp = _post(disagg_fleet["port"], body)
    assert resp.status == 200
    json.loads(resp.read())
    s1 = _snapshot()
    # the descriptor never reached a replica: no import outcome of ANY
    # kind was recorded for it (the fleet is in-process, so the metric
    # family is shared — an attempted fetch/import would show up here)
    assert (_counter(s1, "disagg_import_requests_total")
            == _counter(s0, "disagg_import_requests_total"))


def test_disagg_stats_blocks_surface(disagg_fleet):
    for rep in disagg_fleet["replicas"]:
        conn = http.client.HTTPConnection("127.0.0.1", rep["port"],
                                          timeout=30)
        conn.request("GET", "/v1/stats")
        data = json.loads(conn.getresponse().read())
        conn.close()
        assert data["replica"]["role"] == rep["role"]
        assert data["disagg"]["role"] == rep["role"]
        assert data["disagg"]["kv_wire"] == "raw"
    # router /healthz surfaces the roles in rotation
    conn = http.client.HTTPConnection("127.0.0.1", disagg_fleet["port"],
                                      timeout=30)
    conn.request("GET", "/healthz")
    data = json.loads(conn.getresponse().read())
    conn.close()
    roles = {r["role"] for r in data["replicas"].values()}
    assert roles == {"prefill", "decode"}
