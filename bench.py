#!/usr/bin/env python
"""Benchmark: Llama-2-7B-shaped Q40 single-chip decode throughput.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ..., "device"}.
Fields:
    weight_gb      — HBM bytes decode must stream per token (weights + scales)
    achieved_gbps  — weight_gb / measured step time (lower bound on attained bandwidth)
    ms_per_token   — mean decode step wall time (over --steps dispatches)
    device         — platform, kind and count of what it ran on, on every line

It runs where JAX puts it and says where that was (platform_env.start). A run
that does not land on a TPU exits non-zero unless the caller asked for the CPU
with JAX_PLATFORMS=cpu; a kernel that fails to lower is an error, not a weaker
configuration with a number.

Baseline: the reference's best published single-node Llama-2-7B number — 101.81 ms/token
(9.82 tok/s) on a GCP c3d-highcpu-30 VM (reference README.md:129-131, BASELINE.md).
vs_baseline > 1.0 means this framework on one TPU chip beats that.

Weights are synthesized directly on device in the 4-bit split-plane kernel layout
(random packed nibbles + f16 block scales — the reference's exact Q40 HBM density,
0.5625 B/weight, src/quants.hpp:17-20). Decode cost is layout/bandwidth-bound and
independent of weight values, so this measures exactly what a converted checkpoint
costs. --layout i8 benches the older int8-plane kernel for comparison.

Usage: python bench.py [--small] [--steps N] [--tp N] [--layout i4p|i8]
                       [--device-loop N] [--window W]
                       [--batch B --superstep K]   (serving throughput mode)
                       [--workload shared-prefix]  (prefix-cache TTFT mode)
                       [--workload chaos]          (fault-injection resilience mode)

--workload shared-prefix drives the BatchEngine scheduler with a synthetic
multi-request workload (one common system prompt + distinct user turns) twice
— prefix cache ON vs OFF — and reports per-request TTFT p50/p95 for both plus
the cache's measured `prefix_hit_rate` (docs/PREFIX_CACHE.md). This is a
scheduler/cache workload bench (random Q40 weights via init_random_params),
not a kernel-layout bench.

--batch B runs the BatchEngine's hot path — the batched K-step device loop
(runtime/device_loop.py make_batched_decode_loop) over B cache rows — and
reports `aggregate_decode_tok_s` (B rows x K tokens per dispatch / wall time)
alongside per-stream tok/s. Decode is HBM-bound, so aggregate throughput
should scale ~linearly with B until the batch turns compute-bound; the
serving trajectory tracks B ∈ {1, 4, 8}.
"""

import argparse
import functools
import json
import os
import sys
import time
import zlib

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, "/root/repo")

from distributed_llama_tpu.models.params import (  # noqa: E402
    block_tensor_shapes, decode_stream_bytes)
from distributed_llama_tpu.models.presets import ARCHS  # noqa: E402
from distributed_llama_tpu.models.spec import (  # noqa: E402
    ArchType, ModelSpec, RopeType)
from distributed_llama_tpu.ops.rope import RopeTables  # noqa: E402
from distributed_llama_tpu.parallel.mesh import make_mesh  # noqa: E402
from distributed_llama_tpu.platform_env import device_block, start  # noqa: E402
from distributed_llama_tpu.parallel.tp import (  # noqa: E402
    init_sharded_kv_cache, make_sharded_forward, shard_params)
from distributed_llama_tpu.obs import trace as obs_trace  # noqa: E402
from distributed_llama_tpu.ops.matmul import kernel_selections  # noqa: E402
from distributed_llama_tpu.fleet.client import completion_request  # noqa: E402
from distributed_llama_tpu.quants import (QK, FloatType, QTensor,  # noqa: E402
                                          to_scale_plane)

BASELINE_TOK_S = 1000.0 / 101.81  # Llama-2-7B, 1x GCP c3d VM (reference README.md:131)

def emit(result: dict) -> None:
    """Print one result line. Every line names the device it was measured on
    (platform, kind, count): without it a CPU figure reads like a chip's."""
    print(json.dumps(dict(result, device=device_block())))


def _pct(sorted_vals, q):
    """Percentile from an ascending list (None when empty) — p50/p95/p99
    share one indexing convention across every workload report."""
    if not sorted_vals:
        return None
    return sorted_vals[min(int(len(sorted_vals) * q), len(sorted_vals) - 1)]


def _pct_ms(sorted_vals, q):
    v = _pct(sorted_vals, q)
    return round(v * 1e3, 2) if v is not None else None


def write_latency_log(path, samples):
    """--latency-log out.jsonl: raw per-request samples (request id, ttft,
    e2e, tokens, replica) so offline percentile analysis doesn't depend on
    the pre-chosen p50/p95/p99 cuts."""
    with open(path, "w") as f:
        for s in samples:
            f.write(json.dumps(s) + "\n")
    print(f"# wrote {len(samples)} latency samples to {path}",
          file=sys.stderr)


SMALL = dict(arch_type=ArchType.LLAMA, dim=512, hidden_dim=1408, n_layers=4,
             n_heads=8, n_kv_heads=8, vocab_size=32000, seq_len=256,
             rope_type=RopeType.LLAMA)

# overhead-bound CI geometry (the fault-matrix / pipeline-overlap tiny
# model, longer context): per-dispatch overhead dominates the matmul
# columns, which is the CPU stand-in for the TPU's HBM-bandwidth-bound
# decode — the regime where a (B, 1+k) verify block costs ~one decode step.
# The repetition workload defaults to it on CPU: SMALL's dim-512 x 32k-vocab
# matmuls are COMPUTE-bound on a 2-core box (a T-wide dispatch costs ~T
# steps), which structurally underreports the speculative win the TPU sees.
TINY_REP = dict(arch_type=ArchType.LLAMA, dim=64, hidden_dim=128, n_layers=2,
                n_heads=4, n_kv_heads=4, vocab_size=256, seq_len=512,
                rope_type=RopeType.LLAMA)


# jax.random.randint generates uint32 random bits, a 4x-the-final-bytes device
# transient for narrow dtypes. The round-5 merged matvec groups stack layers AND
# group members into one tensor (w13 at 7B i8: 32x22016x4096 = 2.9 GB final,
# 11.6 GB transient), which RESOURCE_EXHAUSTs the chip during synthesis — the
# r5 matrix's --layout i8 failure in a fresh process. Cap the transient by
# generating in slices along axis 0 into a donated (in-place) buffer.
_RAND_TRANSIENT_BUDGET = 1 << 30  # max uint32 bytes per generation call


@functools.partial(jax.jit, donate_argnums=0, static_argnames="axis")
def _fill_slice(buf, chunk, i, axis=0):
    return jax.lax.dynamic_update_slice_in_dim(buf, chunk, i, axis=axis)


def _randint_chunked(key, shape, lo, hi, dtype):
    import math

    if 4 * math.prod(shape) <= _RAND_TRANSIENT_BUDGET or len(shape) < 2:
        return jax.random.randint(key, shape, lo, hi, dtype)
    row_bytes = 4 * math.prod(shape[1:])
    if row_bytes > _RAND_TRANSIENT_BUDGET:
        # one axis-0 slice still blows the budget (MoE (L, E, N, K) stacks):
        # recurse per slice
        buf = jnp.zeros(shape, dtype)
        for i in range(shape[0]):
            key, sub = jax.random.split(key)
            chunk = _randint_chunked(sub, shape[1:], lo, hi, dtype)
            buf = _fill_slice(buf, chunk[None], i)
            del chunk
        return buf
    # maximal slabs under the budget — NOT one dispatch per row (a (131072, d)
    # wcls would otherwise make 131k dispatches)
    rows_per = max(1, _RAND_TRANSIENT_BUDGET // row_bytes)
    buf = jnp.zeros(shape, dtype)
    for i in range(0, shape[0], rows_per):
        key, sub = jax.random.split(key)
        n = min(rows_per, shape[0] - i)
        chunk = jax.random.randint(sub, (n, *shape[1:]), lo, hi, dtype)
        buf = _fill_slice(buf, chunk, i)
        del chunk
    return buf


def synth_q40(key, shape, layout: str):
    """Random Q40 tensor synthesized on device, already in the kernel's layout."""
    out, in_ = shape[-2], shape[-1]
    lead = shape[:-2]
    k1, k2 = jax.random.split(key)
    if layout == "i4p":
        data = _randint_chunked(k1, (*lead, out, in_ // 2), 0, 256, jnp.uint8)
        scales = jax.lax.bitcast_convert_type(
            (jax.random.uniform(k2, (*lead, out, in_ // QK), jnp.float32) * 0.01
             + 0.001).astype(jnp.float16), jnp.int16)  # i4p carries f16 BIT PATTERNS
        return QTensor(FloatType.Q40, data, to_scale_plane(scales), layout="i4p")
    if layout == "i8":
        vals = _randint_chunked(k1, (*lead, out, in_), -8, 8, jnp.int8)
        scales = (jax.random.uniform(k2, (*lead, out, in_ // QK), jnp.float32) * 0.01
                  + 0.001)
        return QTensor(FloatType.Q40, vals, scales, layout="i8")
    packed = _randint_chunked(k1, (*lead, out, in_ // QK, 16), 0, 256, jnp.uint8)
    scales = (jax.random.uniform(k2, (*lead, out, in_ // QK), jnp.float32) * 0.01
              + 0.001).astype(jnp.float16)
    return QTensor(FloatType.Q40, packed, scales)


def synth_params(spec: ModelSpec, layout: str, fuse: bool = True, tp: int = 1):
    from distributed_llama_tpu.models.params import _FUSE_GROUPS
    from distributed_llama_tpu.parallel.sharding import effective_kv_heads

    key = jax.random.PRNGKey(0)
    shapes = dict(block_tensor_shapes(spec))
    if fuse:
        # merged matvec groups: synthesize the fused shapes directly (random
        # weights need no interleaving), derived from the canonical
        # models/params.py _FUSE_GROUPS table so bench measures the same fusion
        # production applies — including its eligibility rules (QKV fusion is
        # skipped under KV-head replication, which rewrites wk/wv at shard time)
        for fused_name, members in _FUSE_GROUPS.items():
            if not all(n in shapes for n in members):
                continue
            if fused_name == "wqkv" and effective_kv_heads(spec, tp) != spec.n_kv_heads:
                continue
            lead = shapes[members[0]][0][:-2]  # MoE stacks carry an E axis
            rows = sum(shapes[n][0][-2] for n in members)
            in_dim = shapes[members[0]][0][-1]
            shapes[fused_name] = (((*lead, rows, in_dim)), True)
            for n in members:
                del shapes[n]
    blocks = {}
    for name, (shape, quantized) in shapes.items():
        key, sub = jax.random.split(key)
        full = (spec.n_layers, *shape)
        if quantized:
            blocks[name] = synth_q40(sub, full, layout)
            if name in _FUSE_GROUPS:
                import dataclasses

                # stamp the interleave provenance shard_params validates
                blocks[name] = dataclasses.replace(blocks[name], row_groups=tp)
        else:
            blocks[name] = jnp.ones(full, jnp.float32)
    key, k1, k2 = jax.random.split(key, 3)
    return {
        "embedding": jax.random.normal(k1, (spec.vocab_size, spec.dim), jnp.float32) * 0.02,
        "blocks": blocks,
        "rms_final": jnp.ones((spec.dim,), jnp.float32),
        "wcls": synth_q40(k2, (spec.vocab_size, spec.dim), layout),
    }




def shared_prefix_workload(args, spec):
    """--workload shared-prefix: TTFT with the prefix cache on vs off.

    One warm request establishes the shared prefix, then `--requests - 1`
    followers (same system prompt, distinct user turns) are submitted
    concurrently; TTFT is submit() -> first on_token. The identical schedule
    runs against a cache-on and a cache-off BatchEngine; compiled shapes are
    warmed by the leading request in both, so the delta isolates what the
    cache buys: the followers' shared-prefix prefill."""
    from distributed_llama_tpu.models.params import init_random_params
    from distributed_llama_tpu.obs import flight as obs_flight
    from distributed_llama_tpu.quants import FloatType as _FTy
    from distributed_llama_tpu.runtime.batch_engine import BatchEngine
    from distributed_llama_tpu.runtime.sampler import Sampler

    n_req = max(args.requests, 2)
    gen = 4  # decoded tokens per request: enough to stream, TTFT-dominated
    shared_len = args.shared_prefix
    if shared_len + 8 + gen >= spec.seq_len:
        shared_len = spec.seq_len - 8 - gen
    assert shared_len >= 16, f"seq_len {spec.seq_len} too small for the workload"
    rng = np.random.default_rng(0)
    shared = [1] + [int(t) for t in
                    rng.integers(2, spec.vocab_size, shared_len - 1)]
    prompts = [shared + [2 + i, 3 + i, 4 + i] for i in range(n_req)]
    params = init_random_params(spec, _FTy.Q40, seed=0)
    # default: every follower gets a slot immediately, so TTFT isolates the
    # prefill the cache removes instead of queue wait behind busy slots
    B = args.batch if args.batch > 0 else min(max(n_req - 1, 2), 8)
    # flight recorder: per-request engine-side timelines give the E2E
    # percentiles and the --latency-log samples without per-request threads.
    # The finally guarantees the process-global recorder is removed and the
    # samples gathered so far are flushed even when a request fails mid-run.
    rec = obs_flight.install(max(4 * n_req, 64))
    samples = []
    out = {}
    try:
        # three arms on the identical schedule: "on" = paged KV + directory
        # (the default serving config), "off" = cache disabled, "dense" =
        # the --no-paged-kv contiguous layout whose admission seed SCATTERS
        # pool rows host→device — the baseline the seed_bytes column
        # compares against (docs/PAGED_KV.md)
        for label, on, paged in (("on", True, True), ("off", False, True),
                                 ("dense", True, False)):
            # the dense arm exists for the seed-cost columns only (its TTFT
            # is not reported): a warm + 2 seeded followers suffice, keeping
            # the 3-arm bench's wall time near the old 2-arm run's
            arm_req = n_req if label != "dense" else min(n_req, 3)
            be = BatchEngine(spec, params, slots=B,
                             superstep=max(args.superstep, 1), tp=args.tp,
                             prefix_cache=on, paged_kv=paged)
            try:
                be.generate(list(prompts[0]), gen,
                            Sampler(spec.vocab_size, temperature=0.0))
                ttfts = {}
                t0s = {}

                def on_tok(i):
                    def cb(_t, i=i):
                        if i not in ttfts:
                            ttfts[i] = time.perf_counter() - t0s[i]
                    return cb

                reqs = []
                for i in range(1, arm_req):
                    t0s[i] = time.perf_counter()
                    reqs.append(be.submit(
                        list(prompts[i]), gen,
                        Sampler(spec.vocab_size, temperature=0.0),
                        on_token=on_tok(i), rid=f"bench-{label}-{i}"))
                t_all0 = time.perf_counter()
                for r in reqs:
                    r.wait(timeout=600)
                e2e = time.perf_counter() - t_all0
                # per-request E2E from the flight recorder (submit ->
                # engine finish), the per-request number the wall clock
                # above can't give
                req_e2e = []
                for i, r in enumerate(reqs, start=1):
                    fr = rec.get(f"bench-{label}-{i}") or {}
                    if fr.get("e2e_ms") is not None:
                        req_e2e.append(fr["e2e_ms"] / 1e3)
                    samples.append({"request_id": f"bench-{label}-{i}",
                                    "cache": label,
                                    "tenant": "default",
                                    "class": "interactive",
                                    "ttft_s": ttfts.get(i),
                                    "e2e_s": fr.get("e2e_ms", 0.0) / 1e3
                                    or None,
                                    "tokens": len(r.out), "replica": None})
                req_e2e.sort()
                lat = sorted(ttfts.values())
                out[label] = {
                    "ttft_p50_ms": _pct_ms(lat, 0.50),
                    "ttft_p95_ms": _pct_ms(lat, 0.95),
                    "ttft_p99_ms": _pct_ms(lat, 0.99),
                    "e2e_p99_ms": _pct_ms(req_e2e, 0.99),
                    "e2e_s": round(e2e, 3),
                }
                out[label]["prefix_seed_ms"] = round(be.seed_ms, 3)
                out[label]["seed_bytes_transferred"] = be.seed_bytes
                if on and paged:
                    st = be.prefix_cache.stats()
                    out["prefix_hit_rate"] = round(st["hit_rate"], 3)
                    out["lookup_hit_rate"] = round(st["lookup_hit_rate"], 3)
                    out["hit_tokens"] = st["hit_tokens"]
                    out["pool_blocks"] = st["pool_blocks"]
                    # ISSUE 12 acceptance, asserted IN-RUN: an admission
                    # with a radix prefix hit moves ZERO host→device KV
                    # bytes on the paged path (block-table remap only)
                    assert st["hit_tokens"] > 0, "no radix hit in the run"
                    assert be.seed_bytes == 0, (
                        f"paged admission moved {be.seed_bytes} KV bytes "
                        "host→device (remap must move none)")
                elif on and not paged:
                    st = be.prefix_cache.stats()
                    assert st["hit_tokens"] == 0 or be.seed_bytes > 0, (
                        "dense baseline seeded without any byte transfer?")
            finally:
                be.close()
    finally:
        obs_flight.uninstall()
        if args.latency_log and samples:
            write_latency_log(args.latency_log, samples)
    emit({
        "metric": "shared_prefix_ttft_p50_ms",
        "value": out["on"]["ttft_p50_ms"], "unit": "ms", "vs_baseline": None,
        "ttft_p95_ms": out["on"]["ttft_p95_ms"],
        "ttft_p99_ms": out["on"]["ttft_p99_ms"],
        "e2e_p99_ms": out["on"]["e2e_p99_ms"],
        "ttft_off_p50_ms": out["off"]["ttft_p50_ms"],
        "ttft_off_p95_ms": out["off"]["ttft_p95_ms"],
        "ttft_off_p99_ms": out["off"]["ttft_p99_ms"],
        "ttft_speedup_p50": round(
            out["off"]["ttft_p50_ms"] / max(out["on"]["ttft_p50_ms"], 1e-9), 3),
        "e2e_s_on": out["on"]["e2e_s"], "e2e_s_off": out["off"]["e2e_s"],
        "prefix_hit_rate": out["prefix_hit_rate"],
        "lookup_hit_rate": out["lookup_hit_rate"],
        "hit_tokens": out["hit_tokens"], "pool_blocks": out["pool_blocks"],
        # paged-vs-dense admission seeding cost (docs/PAGED_KV.md): the
        # paged remap moves ZERO KV bytes (asserted above); the dense
        # scatter baseline pays the full fetched span per seeded admission
        "prefix_seed_ms": out["on"]["prefix_seed_ms"],
        "seed_bytes_transferred": out["on"]["seed_bytes_transferred"],
        "prefix_seed_ms_dense": out["dense"]["prefix_seed_ms"],
        "seed_bytes_dense": out["dense"]["seed_bytes_transferred"],
        "requests": n_req, "shared_prefix": shared_len, "batch": B,
        "superstep": max(args.superstep, 1),
    })


def long_context_workload(args):
    """--workload shared-prefix --long-context: the KV-capacity↔slot-count
    decoupling demo (docs/PAGED_KV.md). A 4-slot engine gets a device pool
    holding ~1.25 contexts' worth of blocks — the DENSE layout at the same
    KV byte budget would cap every slot at ~pool/4 tokens — and ONE request
    runs a context ~3x that dense-equivalent per-slot capacity to the
    context wall, while short co-batched requests keep being served. The
    run FAILS (nonzero exit via assert) if the long request cannot finish
    at full length."""
    from distributed_llama_tpu.models.params import init_random_params
    from distributed_llama_tpu.quants import FloatType as _FTy
    from distributed_llama_tpu.runtime.batch_engine import BatchEngine
    from distributed_llama_tpu.runtime.sampler import Sampler

    slots, bt = 4, 16
    spec = ModelSpec(**dict(TINY_REP, seq_len=1024)).resolved()
    w = spec.seq_len // bt  # blocks per full context
    pool_blocks = w + w // 4 + 2  # ~1.25 contexts + scratch/spare
    params = init_random_params(spec, _FTy.Q40, seed=0)
    be = BatchEngine(spec, params, slots=slots, superstep=max(args.superstep, 1),
                     tp=args.tp, kv_block_tokens=bt, kv_pool_blocks=pool_blocks)
    assert be.kv_pool is not None
    dense_equiv_per_slot = pool_blocks * bt // slots
    rng = np.random.default_rng(0)
    long_prompt = [1] + [int(t) for t in
                         rng.integers(2, spec.vocab_size, 799)]
    gen = spec.seq_len - len(long_prompt)  # decode to the context wall
    try:
        t0 = time.perf_counter()
        req = be.submit(list(long_prompt), gen,
                        Sampler(spec.vocab_size, temperature=0.0))
        shorts = [be.submit([1, 7 + i, 9], 8,
                            Sampler(spec.vocab_size, temperature=0.0))
                  for i in range(3)]
        out = req.wait(timeout=1200)
        for r in shorts:
            r.wait(timeout=1200)
        dt = time.perf_counter() - t0
        ctx = len(long_prompt) + len(out)
        assert req.finish == "length" and ctx >= spec.seq_len, (
            req.finish, ctx)
        assert ctx > dense_equiv_per_slot, "demo geometry broken"
        elem = be._eng.k_cache.dtype.itemsize
        blk_bytes = (2 * spec.n_layers * spec.n_kv_heads * bt
                     * spec.head_size * elem)
        emit({
            "metric": "long_context_tokens", "value": ctx, "unit": "tokens",
            "vs_baseline": None,
            "dense_equiv_per_slot_tokens": dense_equiv_per_slot,
            "context_vs_dense_per_slot": round(ctx / dense_equiv_per_slot, 2),
            "slots": slots, "seq_len": spec.seq_len,
            "kv_pool_blocks": pool_blocks, "block_tokens": bt,
            "kv_pool_bytes": pool_blocks * blk_bytes,
            "dense_layout_bytes": slots * (spec.seq_len // bt) * blk_bytes,
            "short_requests_served": len(shorts),
            "e2e_s": round(dt, 3),
        })
    finally:
        be.close()


def _write_fleet_model(outdir: str) -> tuple[str, str]:
    """Tiny real-format checkpoint + chatml byte-level tokenizer for the fleet
    replicas (the examples/make_tiny_model.py pattern, sized for fast CPU
    startup: the fleet bench measures ROUTING + cache locality, not kernels)."""
    from distributed_llama_tpu.formats.mfile import params_file_order, write_model
    from distributed_llama_tpu.formats.tfile import TokenizerData, write_tokenizer
    from distributed_llama_tpu.models.params import init_random_params

    spec = ModelSpec(arch_type=ArchType.LLAMA, dim=64, hidden_dim=128,
                     n_layers=2, n_heads=4, n_kv_heads=2, vocab_size=262,
                     seq_len=512, rope_type=RopeType.LLAMA).resolved()
    params = init_random_params(spec, FloatType.F32, seed=6)
    mpath = os.path.join(outdir, "fleet.m")
    write_model(mpath, spec, params_file_order(spec, params), FloatType.F32)
    vocab = [b"<unk>", b"<s>", b"</s>"] + [bytes([i]) for i in range(256)] + \
        [b"<|im_start|>", b"<|im_end|>", b" "]
    scores = [0.0] * 259 + [-1.0, -1.0, -1.5]
    tpath = os.path.join(outdir, "fleet.t")
    write_tokenizer(tpath, TokenizerData(
        vocab=vocab, scores=scores, bos_id=1, eos_id=2, chat_eos_id=260,
        max_token_length=12, chat_template="{{<|im_start|>}}"))
    return mpath, tpath


def _fleet_free_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _fleet_get_json(port, path, timeout=10):
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read() or b"{}")
    finally:
        conn.close()


def _spawn_fleet_replicas(tmp, mpath, tpath, ports, extra_argv=(),
                          trace_dir=None, per_replica_argv=None,
                          per_replica_env=None):
    """Launch one api_server subprocess per port (tiny fleet checkpoint),
    env-scrubbed so chaos config never leaks into acceptance replicas. The
    replicas inherit JAX_PLATFORMS: they run where this process runs, and
    their result lines say where that was. This process has touched JAX, so
    on a chip it is the chip's one owner and a replica could only fail or
    hang: the workload refuses to start there rather than measure CPU
    replicas under a chip's name (replicas on their own chips: ROADMAP W6).
    Shared by the shared-prefix, chaos, and mixed-context fleet
    benches — the startup machinery must not drift between them.
    `per_replica_argv` adds per-index flags (the mixed-context bench's
    --role split); `per_replica_env` overrides env vars per index AFTER
    the scrub (the gray-failure bench's victim-only sustained-latency
    DLLAMA_FAULTS). Returns (procs, logs)."""
    import subprocess

    platform = device_block()["platform"]
    if platform != "cpu":
        sys.exit(f"bench.py: the fleet workloads start api_server replicas as "
                 f"child processes, and this process holds the {platform} "
                 "device. Run them with JAX_PLATFORMS=cpu.")
    repo_root = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, PYTHONPATH=repo_root,
               DLLAMA_FAULTS="", DLLAMA_FAULT_SEED="")
    procs, logs = [], []
    for i, port in enumerate(ports):
        log = open(os.path.join(tmp, f"replica_{port}.log"), "w")
        logs.append(log)
        own = tuple(per_replica_argv[i]) if per_replica_argv else ()
        own_env = (dict(env, **per_replica_env[i])
                   if per_replica_env and per_replica_env[i] else env)
        argv = [sys.executable, "-m", "distributed_llama_tpu.apps.api_server",
                "--model", mpath, "--tokenizer", tpath, "--chat-template",
                "chatml", "--host", "127.0.0.1", "--port", str(port),
                "--batch", "2", "--superstep", "4", *extra_argv, *own]
        if trace_dir is not None:
            # replica-side tracing: the router's GET /v1/trace pulls each
            # replica's live buffer into the merged Perfetto file
            argv += ["--trace", os.path.join(trace_dir, f"trace_{port}.json")]
        procs.append(subprocess.Popen(
            argv, env=own_env, stdout=log, stderr=subprocess.STDOUT,
            cwd=repo_root))
    return procs, logs


def _await_fleet_healthy(procs, ports, tmp, timeout_s=300):
    deadline = time.time() + timeout_s
    for port, proc in zip(ports, procs):
        while True:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"replica :{port} died during startup "
                    f"(see {tmp}/replica_{port}.log)")
            try:
                if _fleet_get_json(port, "/healthz", timeout=2)[0] == 200:
                    break
            except OSError:
                pass
            if time.time() > deadline:
                raise RuntimeError(f"replica :{port} never became healthy")
            time.sleep(0.5)


def fleet_shared_prefix_workload(args, spec):
    """--workload shared-prefix --replicas N [--routing affinity|random]
    [--kill-replica]: the fleet-tier acceptance bench (docs/FLEET.md).

    Launches N real api_server subprocesses (tiny synthetic checkpoint, CPU)
    plus the in-process fleet router, then drives G shared-prefix request
    groups through the router: one warm request per group, then concurrent
    streaming followers. Reports fleet tok/s (delivered deltas / wall), TTFT
    p50/p95, the AGGREGATE prefix-hit-rate summed over every replica's
    /v1/stats prefix_cache counters, and the router's routes-by-reason
    split. `--routing random` is the A/B control (affinity must beat it);
    `--kill-replica` SIGTERMs one replica mid-run — graceful drain + router
    failover must complete EVERY request with no client-visible failure."""
    import signal
    import subprocess
    import tempfile
    import threading

    from distributed_llama_tpu.fleet.router import close_router, serve_router
    from distributed_llama_tpu.obs import metrics as obs_metrics

    n_rep = args.replicas
    tmp = tempfile.mkdtemp(prefix="dlt_fleet_")
    mpath, tpath = _write_fleet_model(tmp)
    ports = [_fleet_free_port() for _ in range(n_rep)]
    if args.trace_fleet and obs_trace.current() is None:
        # the router runs in THIS process: its proxy spans must record for
        # the merged fleet trace (replicas get --trace below)
        obs_trace.install(process_name="router")
    procs, logs = _spawn_fleet_replicas(
        tmp, mpath, tpath, ports, extra_argv=("--drain-timeout", "60"),
        trace_dir=tmp if args.trace_fleet else None)
    _get_json = _fleet_get_json

    router = None
    try:
        _await_fleet_healthy(procs, ports, tmp)
        router = serve_router([f"127.0.0.1:{p}" for p in ports],
                              host="127.0.0.1", port=0, policy=args.routing,
                              poll_interval=0.5, block_bytes=32, retries=2,
                              try_timeout=120.0, seed=0)
        rport = router.server_address[1]
        threading.Thread(target=router.serve_forever, daemon=True).start()

        rng = np.random.default_rng(0)
        # more groups than any replica has slots (2 each): slots churn across
        # groups, so reuse flows through the RADIX pool (counted in
        # hit_tokens) rather than the same-slot resident rewind (which the
        # cache reports as unused_hits). The group count is a CONSTANT —
        # fleet-size-independent — so --replicas 1 (the single-replica
        # baseline) and --replicas N run the IDENTICAL request schedule;
        # only the routing changes, which is exactly what the acceptance
        # comparison isolates
        groups = 8
        # ~args.shared_prefix chars -> ~that many tokens via the byte-fallback
        # tokenizer; budget under the replica seq_len (512)
        sys_len = min(args.shared_prefix, 320)
        systems = ["".join(rng.choice(list("abcdefgh rstlne"))
                           for _ in range(sys_len)) for _ in range(groups)]
        gen = 8
        followers = max(args.requests - 1, 4)  # per group, measured phase

        def one_request(system, user, results, idx, headers=None):
            # shared incremental-SSE driver (fleet/client.py): TTFT is the
            # first delta's true arrival time; rid/replica are the serving
            # identity for --latency-log and the flight-recorder check
            body = {"messages": [{"role": "system", "content": system},
                                 {"role": "user", "content": user}],
                    "max_tokens": gen, "temperature": 0, "stream": True}
            r = completion_request(rport, body, timeout=180, headers=headers)
            if r["error"] is not None or r["status"] != 200:
                results[idx] = {"error": r["error"]
                                or f"status {r['status']}"}
                return
            results[idx] = {"ttft": r["ttft"], "deltas": r["deltas"],
                            "e2e": r["e2e"], "rid": r["rid"],
                            "replica": r["replica"]}

        # warm phase: one request per group, sequential — inserts each
        # group's system prompt into SOME replica's cache and (affinity
        # mode) records the route
        warm = [None] * groups
        for g, system in enumerate(systems):
            one_request(system, f"warm {g}", warm, g)
            assert "error" not in (warm[g] or {"error": "no result"}), warm[g]

        victim_stats = {}
        kill_at = None
        if args.kill_replica:
            kill_at = (groups * followers) // 2

        # measured phase: followers interleaved across groups, concurrent
        reqs = [(g, f) for f in range(followers) for g in range(groups)]
        results = [None] * len(reqs)
        threads = []
        t_all0 = time.perf_counter()
        sem = threading.Semaphore(2 * n_rep)  # fleet-wide client concurrency
        # the SAMPLED request (--trace-fleet acceptance): send an explicit
        # client traceparent on follower 0 so its known trace id can be
        # asserted in both the router's proxy span and the serving replica's
        # engine spans inside the merged trace
        sampled_tid = os.urandom(16).hex()
        sampled_hdr = {"traceparent": f"00-{sampled_tid}-{os.urandom(8).hex()}-01"}

        def run_one(i, g, f):
            with sem:
                one_request(systems[g], f"follower {f} of group {g}",
                            results, i,
                            headers=sampled_hdr if i == 0 else None)

        for i, (g, f) in enumerate(reqs):
            if kill_at is not None and i == kill_at:
                # mid-bench replica kill: snapshot its cache counters, then
                # SIGTERM (graceful drain -> router reroutes; in-flight
                # requests finish on the draining replica)
                _, victim_stats = _get_json(ports[0], "/v1/stats", timeout=10)
                procs[0].send_signal(signal.SIGTERM)
            t = threading.Thread(target=run_one, args=(i, g, f))
            t.start()
            threads.append(t)
        for t in threads:
            t.join(timeout=300)
        wall = time.perf_counter() - t_all0

        failed = [(i, r) for i, r in enumerate(results)
                  if r is None or "error" in r]
        ttfts = sorted(r["ttft"] for r in results
                       if r and r.get("ttft") is not None)
        e2es = sorted(r["e2e"] for r in results
                      if r and r.get("e2e") is not None)
        deltas = sum(r.get("deltas", 0) for r in results if r)

        if args.latency_log:
            write_latency_log(args.latency_log, [
                {"request_id": (r or {}).get("rid"), "group": g,
                 "follower": f, "tenant": "default",
                 "class": "interactive",
                 "ttft_s": (r or {}).get("ttft"),
                 "e2e_s": (r or {}).get("e2e"),
                 "tokens": (r or {}).get("deltas"),
                 "replica": (r or {}).get("replica"),
                 "error": (r or {}).get("error")}
                for (g, f), r in zip(reqs, results)])

        # --trace-fleet acceptance: pull the router's fleet-merged Perfetto
        # trace, write it, and verify end-to-end attribution — the sampled
        # request's router proxy span AND its replica-side engine events
        # carry the trace id the client sent, and the serving replica's
        # flight recorder returns that request's full timeline
        trace_info = None
        if args.trace_fleet:
            _, doc = _get_json(rport, "/v1/trace", timeout=60)
            with open(args.trace_fleet, "w") as f:
                json.dump(doc, f)
            evs = doc.get("traceEvents", [])
            router_spans = [
                e for e in evs if e.get("name") == "router.proxy"
                and (e.get("args") or {}).get("trace_id") == sampled_tid]
            engine_evs = [
                e for e in evs
                if (e.get("args") or {}).get("trace_id") == sampled_tid
                and str(e.get("name", "")).startswith(("batch.", "engine."))]
            r0 = results[0] or {}
            timeline = None
            if r0.get("rid") and r0.get("replica"):
                try:
                    st, body = _get_json(
                        int(r0["replica"].rsplit(":", 1)[1]),
                        f"/v1/requests/{r0['rid']}", timeout=10)
                    timeline = body if st == 200 else None
                except OSError:
                    timeline = None
            tl_events = [e.get("event")
                         for e in (timeline or {}).get("events", [])]
            trace_info = {
                "out": args.trace_fleet, "events": len(evs),
                "processes": len((doc.get("otherData") or {})
                                 .get("processes", [])),
                "sampled_trace_id": sampled_tid,
                "sampled_request_id": r0.get("rid"),
                "sampled_replica": r0.get("replica"),
                "router_proxy_spans": len(router_spans),
                "replica_engine_events": len(engine_evs),
                "flight_timeline_events": len(tl_events),
                "flight_has_queue_and_steps": (
                    "admitted" in tl_events
                    and any(e in ("super_step", "prefill_chunk")
                            for e in tl_events)),
                "ok": bool(router_spans and engine_evs
                           and timeline is not None
                           and timeline.get("finish") is not None
                           and "admitted" in tl_events),
            }

        # aggregate prefix-hit-rate over every replica (the victim from its
        # pre-kill snapshot; survivors live — the victim is NEVER polled
        # live, even while it is still draining, or its counters would be
        # summed twice)
        hit_tok = resident_tok = prompt_tok = 0.0
        per_replica_hits = {}
        stats_sources = ([(f"127.0.0.1:{ports[0]}", victim_stats)]
                         if victim_stats else [])
        for port, proc in zip(ports, procs):
            if victim_stats and port == ports[0]:
                continue
            if proc.poll() is None:
                try:
                    stats_sources.append(
                        (f"127.0.0.1:{port}",
                         _get_json(port, "/v1/stats", timeout=10)[1]))
                except OSError:
                    pass
        for rep_id, st in stats_sources:
            pc = st.get("prefix_cache") or {}
            hit_tok += pc.get("hit_tokens", 0)
            resident_tok += pc.get("resident_tokens", 0)
            prompt_tok += pc.get("prompt_tokens", 0)
            per_replica_hits[rep_id] = {
                "reuse_rate": round(pc.get("reuse_rate", 0.0), 3),
                "hit_tokens": pc.get("hit_tokens", 0),
                "resident_tokens": pc.get("resident_tokens", 0)}
        routes = {k.split("=")[1].strip('"}'): v for k, v in
                  (obs_metrics.snapshot().get("router_routes_total")
                   or {}).items()}
        emit({
            "metric": "fleet_shared_prefix_tok_s",
            "value": round(deltas / wall, 2) if wall else 0.0,
            "unit": "tok/s", "vs_baseline": None,
            "routing": args.routing, "replicas": n_rep,
            "killed_replica": bool(args.kill_replica),
            "failed_requests": len(failed),
            "failures": [f"{i}: {r}" for i, r in failed[:5]],
            "requests": len(reqs), "groups": groups,
            "followers_per_group": followers,
            "ttft_p50_ms": _pct_ms(ttfts, 0.50),
            "ttft_p95_ms": _pct_ms(ttfts, 0.95),
            "ttft_p99_ms": _pct_ms(ttfts, 0.99),
            "e2e_p50_ms": _pct_ms(e2es, 0.50),
            "e2e_p95_ms": _pct_ms(e2es, 0.95),
            "e2e_p99_ms": _pct_ms(e2es, 0.99),
            "trace_fleet": trace_info,
            # reuse = pool hits + resident rewinds: WHICH mechanism skipped a
            # request's prefill is a slot-scheduling accident (the same sticky
            # route lands either way), so the acceptance metric sums both;
            # prefix_hit_rate (pool only) is kept for the PR 3 comparison
            "prefix_reuse_rate": round(
                (hit_tok + resident_tok) / prompt_tok, 3)
            if prompt_tok else 0.0,
            "prefix_hit_rate": round(hit_tok / prompt_tok, 3)
            if prompt_tok else 0.0,
            "per_replica": per_replica_hits,
            "routes": routes,
            "shared_prefix_chars": sys_len, "gen_tokens": gen,
        })
        if failed:
            sys.exit(1)
        if (trace_info is not None and not trace_info["ok"]
                and not args.kill_replica):
            # acceptance gate: a merged trace without end-to-end attribution
            # (or a missing flight timeline) is a failure, not a warning
            sys.exit(1)
    finally:
        if router is not None:
            close_router(router)
        for proc in procs:
            if proc.poll() is None:
                proc.terminate()
        for proc in procs:
            try:
                proc.wait(timeout=90)
            except subprocess.TimeoutExpired:
                proc.kill()
        for log in logs:
            log.close()


def mixed_context_workload(args, spec):
    """--workload mixed-context: the disaggregation acceptance A/B
    (docs/DISAGG.md). Co-scheduled LONG prefills (unique ~290-char system
    prompts, 4 decode tokens) and SHORT streaming decode chains (24
    tokens) drive two 2-replica fleets on an IDENTICAL schedule:

    - **disaggregated** — replica 0 `--role prefill`, replica 1
      `--role decode`, router `--disagg-threshold` armed: every long
      prefills on replica 0, ships its KV blocks over /v1/kv, and decodes
      on replica 1 alongside the shorts (whose dispatches stay narrow);
    - **monolithic** — both replicas `both`, splitter off: long prefill
      chunks ride mixed (B, 64) dispatches WITH co-batched short rows,
      inflating their inter-token gaps (the exact pathology ISSUE 13
      names).

    Reports short-chain decode TPOT p50/p95 per arm and gates in-run:
    zero failed requests in both arms, every measured long actually split
    and imported, the decode replica re-prefilled ZERO shipped tokens
    (`disagg_reprefill_tokens_total == 0`), and disaggregated TPOT p95
    strictly below monolithic."""
    import subprocess
    import tempfile
    import threading

    from distributed_llama_tpu.fleet.router import close_router, serve_router

    tmp = tempfile.mkdtemp(prefix="dlt_disagg_")
    mpath, tpath = _write_fleet_model(tmp)
    rounds = max(args.requests, 6)
    shorts_per_round = 3
    gen_short, gen_long = 24, 4
    long_chars, threshold = 288, 48

    rng = np.random.default_rng(0)
    alpha = list("abcdefgh rstlne")
    # unique prompts, identical across arms: longs share NO prefix (each
    # pays a full prefill), shorts stay under the split threshold
    long_sys = ["".join(rng.choice(alpha) for _ in range(long_chars))
                for _ in range(rounds + 1)]
    short_user = ["ask " + "".join(rng.choice(alpha) for _ in range(12))
                  + f" q{i}" for i in range((rounds + 1) * shorts_per_round)]

    def run_arm(disagg: bool) -> dict:
        ports = [_fleet_free_port() for _ in range(2)]
        roles = ((("--role", "prefill"), ("--role", "decode"))
                 if disagg else None)
        procs, logs = _spawn_fleet_replicas(tmp, mpath, tpath, ports,
                                            per_replica_argv=roles)
        router = None
        failures: list[str] = []
        shorts: list[tuple] = []  # (ttft_s, tpot_s)
        long_e2es: list[float] = []
        try:
            _await_fleet_healthy(procs, ports, tmp)
            router = serve_router(
                [f"127.0.0.1:{p}" for p in ports], host="127.0.0.1",
                port=0, poll_interval=0.5, block_bytes=32, retries=2,
                try_timeout=300.0,
                disagg_threshold=threshold if disagg else 0)
            rport = router.server_address[1]
            threading.Thread(target=router.serve_forever,
                             daemon=True).start()

            def long_req(i, record):
                body = {"messages": [
                    {"role": "system", "content": long_sys[i]},
                    {"role": "user", "content": "go"}],
                    "max_tokens": gen_long, "temperature": 0,
                    "stream": False}
                r = completion_request(rport, body, timeout=600)
                if r["error"] is not None or r["status"] != 200:
                    failures.append(f"long {i}: status {r['status']} "
                                    f"{str(r['error'])[:120]}")
                elif record:
                    long_e2es.append(r["e2e"])

            def short_req(i, record):
                body = {"messages": [
                    {"role": "user", "content": short_user[i]}],
                    "max_tokens": gen_short, "temperature": 0,
                    "stream": True}
                r = completion_request(rport, body, timeout=600)
                if r["error"] is not None or r["status"] != 200:
                    failures.append(f"short {i}: "
                                    f"{r['error'] or r['status']}")
                    return
                if record and r["deltas"] > 1:
                    shorts.append((r["ttft"], r["tpot"]))

            def run_round(r, record):
                ths = [threading.Thread(target=long_req, args=(r, record))]
                ths += [threading.Thread(
                    target=short_req,
                    args=(r * shorts_per_round + s, record))
                    for s in range(shorts_per_round)]
                ths[0].start()
                time.sleep(0.05)  # the long admission lands first
                for t in ths[1:]:
                    t.start()
                for t in ths:
                    t.join(timeout=600)

            run_round(rounds, record=False)  # warm: compiles every shape
            for r in range(rounds):
                run_round(r, record=True)

            rep_stats = []
            for port in ports:
                try:
                    rep_stats.append(_fleet_get_json(port, "/v1/stats",
                                                     timeout=10)[1])
                except OSError:
                    rep_stats.append({})
            return {"failures": failures, "shorts": shorts,
                    "long_e2es": long_e2es, "rep_stats": rep_stats}
        finally:
            if router is not None:
                close_router(router)
            for proc in procs:
                if proc.poll() is None:
                    proc.terminate()
            for proc in procs:
                try:
                    proc.wait(timeout=90)
                except subprocess.TimeoutExpired:
                    proc.kill()
            for log in logs:
                log.close()

    from distributed_llama_tpu.obs import metrics as obs_metrics

    def split_count():
        fam = (obs_metrics.snapshot()
               .get("router_disagg_requests_total") or {})
        return fam.get('{outcome="split"}', 0) or 0

    s0 = split_count()
    dis = run_arm(disagg=True)
    dis_splits = split_count() - s0
    mono = run_arm(disagg=False)

    def pcts(arm):
        tpots = sorted(t for _ttft, t in arm["shorts"])
        ttfts = sorted(t for t, _tpot in arm["shorts"])
        return {
            "short_requests": len(arm["shorts"]),
            "decode_tpot_p50_ms": _pct_ms(tpots, 0.50),
            "decode_tpot_p95_ms": _pct_ms(tpots, 0.95),
            "ttft_p50_ms": _pct_ms(ttfts, 0.50),
            "ttft_p95_ms": _pct_ms(ttfts, 0.95),
            "long_e2e_p50_ms": _pct_ms(sorted(arm["long_e2es"]), 0.50),
            "failed": len(arm["failures"]),
            "failures": arm["failures"][:5],
        }

    def metric_sum(stats_list, name, label=None):
        total = 0.0
        for st in stats_list:
            fam = (st.get("metrics") or {}).get(name)
            if fam is None:
                continue
            if isinstance(fam, dict):
                total += (fam.get(label, 0) or 0) if label \
                    else sum(fam.values())
            else:
                total += fam
        return total

    imported = metric_sum(dis["rep_stats"], "disagg_import_requests_total",
                          '{outcome="imported"}')
    reprefill = metric_sum(dis["rep_stats"], "disagg_reprefill_tokens_total")
    da, ma = pcts(dis), pcts(mono)
    problems = []
    if dis["failures"] or mono["failures"]:
        problems.append(f"client-visible failures: disagg "
                        f"{dis['failures'][:3]}, mono {mono['failures'][:3]}")
    # every measured long (plus the warm one) must have split and imported
    if dis_splits < rounds:
        problems.append(f"only {dis_splits}/{rounds} longs split")
    if imported < rounds:
        problems.append(f"only {imported:.0f}/{rounds} imports landed")
    if reprefill != 0:
        problems.append(f"streamed admissions re-prefilled {reprefill:.0f} "
                        "shipped tokens (want 0)")
    if not (da["decode_tpot_p95_ms"] and ma["decode_tpot_p95_ms"]
            and da["decode_tpot_p95_ms"] < ma["decode_tpot_p95_ms"]):
        problems.append(
            f"disaggregated decode TPOT p95 {da['decode_tpot_p95_ms']} ms "
            f"not strictly better than monolithic "
            f"{ma['decode_tpot_p95_ms']} ms")
    emit({
        "metric": "mixed_context_decode_tpot_p95_ms",
        "value": da["decode_tpot_p95_ms"], "unit": "ms",
        "vs_baseline": None,
        "monolithic_tpot_p95_ms": ma["decode_tpot_p95_ms"],
        "tpot_p95_speedup": (round(ma["decode_tpot_p95_ms"]
                                   / da["decode_tpot_p95_ms"], 2)
                             if da["decode_tpot_p95_ms"]
                             and ma["decode_tpot_p95_ms"] else None),
        "disaggregated": da, "monolithic": ma,
        "rounds": rounds, "shorts_per_round": shorts_per_round,
        "long_prompt_chars": long_chars, "disagg_threshold": threshold,
        "longs_split": dis_splits, "imports": imported,
        "reprefill_tokens": reprefill,
        "problems": problems,
    })
    if problems:
        sys.exit(1)


def batched_engine_bench(args, spec):
    """--batch B --pipeline/--no-pipeline: serving decode throughput measured
    through the REAL BatchEngine scheduler — admission, device dispatch, and
    the host-side block delivery (EOS scan, callbacks, sampler resync) that
    pipelined super-steps overlap with the next dispatch — rather than the
    raw device loop. B concurrent greedy requests decode --steps tokens
    each; aggregate_decode_tok_s = delivered tokens / wall. Also reports the
    batch_dispatch_gap_seconds delta (mean + p50) for the run: the
    device-idle gap pipelining exists to remove (docs/SERVING.md)."""
    from distributed_llama_tpu.models.params import init_random_params
    from distributed_llama_tpu.obs import metrics as obs_metrics
    from distributed_llama_tpu.quants import FloatType as _FTy
    from distributed_llama_tpu.runtime.batch_engine import BatchEngine
    from distributed_llama_tpu.runtime.sampler import Sampler

    B, K = args.batch, max(args.superstep, 1)
    gen = max(args.steps, 4 * K)
    prompts = [[1, 5 + i, 9, 2 + (i % 40)] for i in range(B)]
    if len(prompts[0]) + gen + 1 >= spec.seq_len:
        gen = spec.seq_len - len(prompts[0]) - 2
    params = init_random_params(spec, _FTy.Q40, seed=0)
    be = BatchEngine(spec, params, slots=B, superstep=K, tp=args.tp,
                     pipeline=bool(args.pipeline), prefix_cache=False,
                     speculative=args.speculative,
                     paged_kv=not args.no_paged_kv)

    def _gap_state():
        h = obs_metrics.snapshot().get("batch_dispatch_gap_seconds") or {}
        return h.get("count", 0), h.get("sum", 0.0), dict(h.get("buckets", {}))

    try:
        # warm round with the MEASURED shape — B concurrent requests — so the
        # timed region recompiles nothing (concurrent prefill admission and
        # the chained-input dispatch layout both differ from a sequential
        # single-request warmup)
        warm = [be.submit(list(p), max(2 * K, 4),
                          Sampler(spec.vocab_size, temperature=0.0))
                for p in prompts]
        for r in warm:
            r.wait(timeout=600)
        c0, s0, b0 = _gap_state()
        f0 = sum((obs_metrics.snapshot().get(
            "batch_pipeline_flushes_total") or {}).values())
        t0 = time.perf_counter()
        reqs = [be.submit(list(p), gen,
                          Sampler(spec.vocab_size, temperature=0.0))
                for p in prompts]
        done = [r.wait(timeout=600) for r in reqs]
        wall = time.perf_counter() - t0
        c1, s1, b1 = _gap_state()
    finally:
        be.close()
    tokens = sum(len(d) for d in done)
    n_gap = max(c1 - c0, 1)
    gap_mean_ms = (s1 - s0) / n_gap * 1e3
    # p50 by cumulative bucket walk over the run's delta counts
    half, acc, p50 = (c1 - c0) / 2.0, 0, None
    for le in sorted(b1, key=float):
        acc += b1[le] - b0.get(le, 0)
        if acc >= half and p50 is None:
            p50 = float(le)
    flushes = sum((obs_metrics.snapshot().get(
        "batch_pipeline_flushes_total") or {}).values()) - f0
    spec_tag = f"spec{args.speculative}" if args.speculative else ""
    emit({
        # speculation is part of the metric identity: a spec-on run must
        # never land on a spec-off run's BENCH trajectory
        "metric": (f"b{B}k{K}{spec_tag}_engine_decode_"
                   + ("pipelined" if args.pipeline else "serialized")),
        "value": round(tokens / wall, 3), "unit": "tok/s",
        "vs_baseline": None,
        "aggregate_decode_tok_s": round(tokens / wall, 3),
        "tokens": tokens, "wall_s": round(wall, 3),
        "dispatch_gap_ms_mean": round(gap_mean_ms, 4),
        "dispatch_gap_ms_p50_le": (round(p50 * 1e3, 4)
                                   if p50 is not None else None),
        "pipeline": bool(args.pipeline), "pipeline_flushes": flushes,
        "batch": B, "superstep": K, "steps": gen,
        "speculative": args.speculative,
    })


def repetition_workload(args, spec):
    """--workload repetition: batched speculative decoding A/B
    (docs/SERVING.md "Speculative decoding"). Code/JSON-shaped prompts with
    heavy n-gram reuse drive the REAL BatchEngine scheduler on an identical
    schedule spec-off and spec-on (--speculative K per-row draft-verify
    blocks), interleaved over several measured rounds to decorrelate the
    shared-core noise of a CPU box, and report median aggregate decode
    tok/s both ways plus the accept rate and verify-dispatch count. The two
    modes must emit byte-identical greedy tokens — the speculative identity
    is asserted here, not just in tests."""
    import statistics

    from distributed_llama_tpu.models.params import init_random_params
    from distributed_llama_tpu.quants import FloatType as _FTy
    from distributed_llama_tpu.runtime.batch_engine import BatchEngine
    from distributed_llama_tpu.runtime.sampler import Sampler

    B = args.batch if args.batch > 0 else 4
    K = max(args.superstep, 1)
    sk = max(args.speculative, 0)
    pipeline = True if args.pipeline is None else bool(args.pipeline)
    # JSON/code-shaped prompts: a "key": value, record pattern over a small
    # token alphabet, repeated with per-row variation — the n-gram-dense
    # regime prompt lookup exists for
    record = [11, 87, 4, 302, 9, 87, 4, 177, 9, 87, 4, 302, 9, 55]
    prompts = [[1, 3 + 2 * i] + (record * 4)[:52] for i in range(B)]
    gen = max(args.steps, 120)
    gen = min(gen, spec.seq_len - len(prompts[0]) - 2)
    params = init_random_params(spec, _FTy.Q40, seed=0)
    be = BatchEngine(spec, params, slots=B, superstep=K, tp=args.tp,
                     pipeline=pipeline, prefix_cache=False,
                     speculative=sk or 8, paged_kv=not args.no_paged_kv)

    def round_(spec_on):
        be.spec_k = (sk or 8) if spec_on else 0
        v0 = be.verify_steps
        t0 = time.perf_counter()
        reqs = [be.submit(list(p), gen,
                          Sampler(spec.vocab_size, temperature=0.0))
                for p in prompts]
        outs = [r.wait(timeout=600) for r in reqs]
        wall = time.perf_counter() - t0
        tokens = sum(len(o) for o in outs)
        return {"tok_s": tokens / wall, "tokens": tokens, "outs": outs,
                "verify": be.verify_steps - v0,
                "drafted": sum(r.stats.spec_drafted for r in reqs),
                "accepted": sum(r.stats.spec_accepted for r in reqs)}

    rounds = 3
    try:
        round_(False)  # warm: scan + prefill programs
        if sk:
            round_(True)  # warm: verify programs (every block bucket)
        offs, ons = [], []
        for _ in range(rounds):  # interleaved A/B: drift hits both arms
            offs.append(round_(False))
            if sk:
                ons.append(round_(True))
    finally:
        be.close()
    off_tok_s = statistics.median(r["tok_s"] for r in offs)
    out = {
        "metric": f"b{B}k{K}spec{sk}_repetition_decode",
        "value": 0.0, "unit": "tok/s", "vs_baseline": None,
        "spec_off_tok_s": round(off_tok_s, 3),
        "tokens_per_round": offs[0]["tokens"], "rounds": rounds,
        "batch": B, "superstep": K, "speculative": sk,
        "pipeline": pipeline, "gen": gen,
        "model": (f"dim{spec.dim}_voc{spec.vocab_size}"
                  f"_L{spec.n_layers}_s{spec.seq_len}"),
    }
    if sk:
        on_tok_s = statistics.median(r["tok_s"] for r in ons)
        drafted = ons[-1]["drafted"]
        out.update({
            "value": round(on_tok_s, 3),
            "spec_on_tok_s": round(on_tok_s, 3),
            "speedup": round(on_tok_s / off_tok_s, 3),
            "accept_rate": (round(ons[-1]["accepted"] / drafted, 3)
                            if drafted else None),
            "verify_dispatches": ons[-1]["verify"],
            "drafted": drafted, "accepted": ons[-1]["accepted"],
            "identical": all(r["outs"] == offs[0]["outs"] for r in ons),
        })
    else:
        out["value"] = round(off_tok_s, 3)
    emit(out)
    if sk and not out["identical"]:
        print("❌ spec-on output diverged from spec-off", file=sys.stderr)
        sys.exit(1)


def spec_suite_workload(args, spec):
    """--workload spec-suite: the model-drafting acceptance A/B/C
    (docs/SERVING.md "Model-based drafting"). Four seeded workload
    generators — chat, code, json, open-ended — drive the REAL BatchEngine
    on an identical schedule under three proposer modes interleaved per
    round on ONE engine (off / ngram / model), with byte-identity asserted
    in-run across all three modes for every request (greedy AND
    seeded-stochastic rows) and per-workload accept rate + aggregate decode
    tok/s reported per mode.

    Drafter construction: real draft models work because distillation makes
    a small model approximate a big one. With synthetic random weights no
    independent small model predicts the target, so the suite BUILDS the
    alignment structurally: the target's layers past the first are damped
    (~no-op residual contributions) and the drafter is the target's 1-layer
    prefix — a 1/n_layers-cost drafter whose greedy argmax tracks the
    target's, the same role TINY_REP's n-gram density plays for the
    repetition bench. n-gram drafting still wins the json (repetition)
    workload; the model drafter's claim — gated in-run — is beating ngram
    tok/s on >= 2 of the NON-repetition workloads (chat/code/open-ended),
    where prompt lookup goes dry but a drafter keeps verify blocks full."""
    import statistics
    from dataclasses import replace as _replace

    from distributed_llama_tpu.models.params import init_random_params
    from distributed_llama_tpu.quants import FloatType as _FTy, QTensor
    from distributed_llama_tpu.runtime.batch_engine import BatchEngine
    from distributed_llama_tpu.runtime.sampler import Sampler

    B = args.batch if args.batch > 0 else 4
    K = max(args.superstep, 1)
    sk = max(args.speculative, 0) or 8
    pipeline = True if args.pipeline is None else bool(args.pipeline)
    V = spec.vocab_size
    gen = min(max(args.steps, 48), spec.seq_len - 80)

    base = init_random_params(spec, _FTy.Q40, seed=0)

    def rebuild(params, damp_from=None, trunc=None, damp=0.05):
        out = {"embedding": params["embedding"],
               "rms_final": params["rms_final"], "wcls": params["wcls"],
               "blocks": {}}
        for name, t in params["blocks"].items():
            if isinstance(t, QTensor):
                f = np.array(t.dequantize(dtype=np.float32))
                if damp_from is not None:
                    f[damp_from:] = f[damp_from:] * damp
                if trunc is not None:
                    f = f[:trunc]
                out["blocks"][name] = QTensor.from_float(f, t.ftype)
            else:
                out["blocks"][name] = t if trunc is None else t[:trunc]
        return out

    tparams = rebuild(base, damp_from=1)
    dspec = _replace(spec, n_layers=1)
    dparams = rebuild(base, damp_from=1, trunc=1)

    # ---- seeded workload generators: B prompts each ----
    def gen_chat(rng):
        # role-templated turns: fixed template tokens around random content
        turns = []
        for _ in range(3):
            turns += [2, 200, 201] + list(rng.integers(5, V, 6)) + [202, 203]
        return [1] + turns

    def gen_code(rng):
        # keyword/indent line pattern with per-line variation: moderate
        # n-gram reuse (between json's density and chat's dryness)
        lines = []
        kw = [40, 41, 42, 43]
        for i in range(4):
            lines += [10, kw[i % 4], 60, int(rng.integers(64, 128)), 61, 9]
        return [1] + lines * 2

    def gen_json(rng):
        # the repetition bench's record shape: n-gram-dense
        record = [11, 87, 4, 302 % V, 9, 87, 4, 177, 9, 87, 4, 302 % V, 9,
                  55]
        return [1, int(rng.integers(3, 30))] + (record * 4)[:40]

    def gen_open(rng):
        # open-ended: no structure at all — prompt lookup goes dry here
        return [1] + list(rng.integers(3, V, 24))

    gens = {"chat": gen_chat, "code": gen_code, "json": gen_json,
            "open-ended": gen_open}
    suites = {}
    for w, g in gens.items():
        # crc32, not hash(): builtin str hashing is SipHash-randomized per
        # process, which would quietly unseed the "seeded" generators
        rng = np.random.default_rng(zlib.crc32(w.encode()))
        suites[w] = [[int(t) for t in g(rng)] for _ in range(B)]

    def sampler_for(j, mixed):
        # identity rounds carry seeded-stochastic rows next to greedy ones
        # (the verify path's byte-identity contract covers both); timed
        # rounds run all-greedy — a temperature-0.8 row samples far from
        # ANY drafter's argmax, so its accept is ~0 by construction and it
        # rides verify dispatches at 1 token/turn, measuring the scheduler
        # mix instead of the proposers under comparison
        if not mixed or j % 2 == 0:
            return Sampler(V, temperature=0.0)
        return Sampler(V, temperature=0.8, topp=0.9, seed=7000 + j)

    be = BatchEngine(spec, tparams, slots=B, superstep=K, tp=args.tp,
                     pipeline=pipeline, prefix_cache=False, speculative=sk,
                     draft_model=(dspec, dparams),
                     paged_kv=not args.no_paged_kv)
    drafter = be.proposer.drafter
    assert drafter is not None, "drafter failed to load"

    def set_mode(mode):
        # one engine for every round (shared compiled programs, shared
        # slots): proposer switched between rounds while idle
        be.spec_k = 0 if mode == "off" else sk
        be.proposer.drafter = drafter if mode == "model" else None

    def round_(w, mode, mixed=False):
        set_mode(mode)
        v0 = be.verify_steps
        t0 = time.perf_counter()
        reqs = [be.submit(list(p), gen, sampler_for(j, mixed))
                for j, p in enumerate(suites[w])]
        outs = [r.wait(timeout=600) for r in reqs]
        wall = time.perf_counter() - t0
        tokens = sum(len(o) for o in outs)
        drafted = sum(r.stats.spec_drafted for r in reqs)
        accepted = sum(r.stats.spec_accepted for r in reqs)
        return {"tok_s": tokens / wall, "tokens": tokens, "outs": outs,
                "verify": be.verify_steps - v0, "drafted": drafted,
                "accepted": accepted}

    MODES = ("off", "ngram", "model")
    rounds = 3
    results = {w: {m: [] for m in MODES} for w in gens}
    mismatches = []
    try:
        for w in gens:  # warm every program each mode touches
            for m in MODES:
                round_(w, m)
        # identity sweep: greedy AND seeded-stochastic rows must emit the
        # same bytes under every proposer mode (asserted in-run)
        for w in gens:
            ref = None
            for m in MODES:
                r = round_(w, m, mixed=True)
                if ref is None:
                    ref = r["outs"]
                elif r["outs"] != ref:
                    mismatches.append((w, m, "mixed"))
        # timed sweep: interleaved rounds so box drift hits all arms
        # equally; identity asserted here too (all-greedy rows)
        for _ in range(rounds):
            for w in gens:
                ref = None
                for m in MODES:
                    r = round_(w, m)
                    results[w][m].append(r)
                    if ref is None:
                        ref = r["outs"]
                    elif r["outs"] != ref:
                        mismatches.append((w, m))
    finally:
        be.close()

    out = {"metric": f"b{B}k{K}spec{sk}_spec_suite", "unit": "tok/s",
           "vs_baseline": None, "batch": B, "superstep": K,
           "speculative": sk, "pipeline": pipeline, "gen": gen,
           "rounds": rounds, "identical": not mismatches,
           "model": (f"dim{spec.dim}_voc{spec.vocab_size}"
                     f"_L{spec.n_layers}_s{spec.seq_len}"),
           "drafter": f"dim{dspec.dim}_L{dspec.n_layers}",
           "workloads": {}}
    model_wins = []
    for w in gens:
        block = {}
        for m in MODES:
            rs = results[w][m]
            drafted = sum(r["drafted"] for r in rs)
            accepted = sum(r["accepted"] for r in rs)
            block[m] = {
                "tok_s": round(statistics.median(r["tok_s"] for r in rs), 3),
                "accept_rate": (round(accepted / drafted, 3)
                                if drafted else None),
                "verify_dispatches": rs[-1]["verify"],
            }
        block["speedup_model_vs_ngram"] = round(
            block["model"]["tok_s"] / block["ngram"]["tok_s"], 3)
        if w != "json" and block["speedup_model_vs_ngram"] > 1.0:
            model_wins.append(w)
        out["workloads"][w] = block
    out["model_beats_ngram_on"] = model_wins
    out["value"] = round(statistics.median(
        out["workloads"][w]["model"]["tok_s"] for w in gens), 3)
    emit(out)
    ok = True
    if mismatches:
        print(f"❌ output diverged across proposer modes: {mismatches}",
              file=sys.stderr)
        ok = False
    if len(model_wins) < 2:
        print("❌ model drafting beat ngram on "
              f"{model_wins} — need >= 2 non-repetition workloads",
              file=sys.stderr)
        ok = False
    if not ok:
        sys.exit(1)


def structured_workload(args, spec):
    """--workload structured: grammar-constrained decoding A/B
    (docs/SERVING.md "Constrained decoding"). Two seeded structured-output
    workloads — json records and tool calls, each pinned to a compiled
    grammar — drive the REAL BatchEngine on an identical constrained
    schedule under four proposer modes interleaved per round on ONE engine
    (off / ngram / model / grammar). Asserted IN-RUN for every request:
    the output is grammar-valid, and byte-identical across all four modes
    (the mask is applied before the sampler on every path, so the proposer
    can only change SPEED, never bytes). The headline claim — gated — is
    speedup_grammar_vs_ngram >= 1.0: forced-transition chains are
    guaranteed accepts, so grammar drafting can only fill verify blocks
    the n-gram index leaves empty."""
    import statistics
    from dataclasses import replace as _replace

    from distributed_llama_tpu.constrain import byte_vocab, compile_grammar
    from distributed_llama_tpu.models.params import init_random_params
    from distributed_llama_tpu.quants import FloatType as _FTy, QTensor
    from distributed_llama_tpu.runtime.batch_engine import BatchEngine
    from distributed_llama_tpu.runtime.sampler import Sampler

    B = args.batch if args.batch > 0 else 4
    K = max(args.superstep, 1)
    sk = max(args.speculative, 0) or 8
    pipeline = True if args.pipeline is None else bool(args.pipeline)
    V = spec.vocab_size
    gen = min(max(args.steps, 56), spec.seq_len - 80)

    # the spec-suite's structurally-aligned drafter (damped target layers,
    # 1-layer prefix drafter) so the "model" arm is a real contender
    base = init_random_params(spec, _FTy.Q40, seed=0)

    def rebuild(params, damp_from=None, trunc=None, damp=0.05):
        out = {"embedding": params["embedding"],
               "rms_final": params["rms_final"], "wcls": params["wcls"],
               "blocks": {}}
        for name, t in params["blocks"].items():
            if isinstance(t, QTensor):
                f = np.array(t.dequantize(dtype=np.float32))
                if damp_from is not None:
                    f[damp_from:] = f[damp_from:] * damp
                if trunc is not None:
                    f = f[:trunc]
                out["blocks"][name] = QTensor.from_float(f, t.ftype)
            else:
                out["blocks"][name] = t if trunc is None else t[:trunc]
        return out

    tparams = rebuild(base, damp_from=1)
    dspec = _replace(spec, n_layers=1)
    dparams = rebuild(base, damp_from=1, trunc=1)

    cv = byte_vocab(V)
    grammars = {
        # long literal key spans between short branch points: the shape
        # real json-mode traffic has (keys forced, values chosen)
        "json": compile_grammar("json_schema", {
            "type": "object", "properties": {
                "sensor": {"enum": ["alpha", "beta", "gamma"]},
                "ok": {"type": "boolean"},
                "status": {"enum": ["ok", "degraded", "failed"]},
            }}, cv, eos_id=2),
        "tool-call": compile_grammar("json_schema", {
            "type": "object", "properties": {
                "name": {"enum": ["get_weather", "get_time", "search_web"]},
                "arguments": {"enum": ["{}", "{\"q\":1}", "{\"q\":2}"]},
            }}, cv, eos_id=2),
    }
    suites = {}
    for w in grammars:
        rng = np.random.default_rng(zlib.crc32(w.encode()))
        suites[w] = [[1] + [int(t) for t in rng.integers(3, V, 8)]
                     for _ in range(B)]

    def sampler_for(j, mixed):
        # identity rounds carry seeded-stochastic rows next to greedy ones
        # (masked sampling covers both); timed rounds run all-greedy, same
        # rationale as the spec-suite bench
        if not mixed or j % 2 == 0:
            return Sampler(V, temperature=0.0)
        return Sampler(V, temperature=0.8, topp=0.9, seed=9000 + j)

    be = BatchEngine(spec, tparams, slots=B, superstep=K, tp=args.tp,
                     pipeline=pipeline, prefix_cache=False, speculative=sk,
                     draft_model=(dspec, dparams),
                     paged_kv=not args.no_paged_kv)
    drafter = be.proposer.drafter
    assert drafter is not None, "drafter failed to load"

    def set_mode(mode):
        # one engine for every round (shared compiled programs, shared
        # constraint table): proposers switched between rounds while idle
        be.spec_k = 0 if mode == "off" else sk
        be.proposer.drafter = drafter if mode == "model" else None
        be.proposer.grammar = (be.grammar_proposer if mode == "grammar"
                               else None)

    def check_valid(w, out):
        aut, _ = grammars[w]
        if 2 in out:
            i = out.index(2)
            assert set(out[i:]) == {2}, f"{w}: post-EOS tokens escaped"
            ok, complete = aut.validate(out[: i + 1])
            assert ok and complete, f"{w}: invalid output {bytes(out[:i])!r}"
        else:
            assert aut.validate(out)[0], f"{w}: invalid prefix {bytes(out)!r}"

    def round_(w, mode, mixed=False):
        set_mode(mode)
        aut, gh = grammars[w]
        t0 = time.perf_counter()
        reqs = [be.submit(list(p), gen, sampler_for(j, mixed),
                          constraint=aut, constraint_hash=gh)
                for j, p in enumerate(suites[w])]
        outs = [r.wait(timeout=600) for r in reqs]
        wall = time.perf_counter() - t0
        for o in outs:
            check_valid(w, o)
        drafted = sum(r.stats.spec_drafted for r in reqs)
        accepted = sum(r.stats.spec_accepted for r in reqs)
        return {"tok_s": sum(len(o) for o in outs) / wall, "outs": outs,
                "drafted": drafted, "accepted": accepted}

    MODES = ("off", "ngram", "model", "grammar")
    rounds = 3
    results = {w: {m: [] for m in MODES} for w in grammars}
    mismatches = []
    try:
        for w in grammars:  # warm every program each mode touches
            for m in MODES:
                round_(w, m)
        # identity sweep: greedy AND seeded-stochastic rows must emit the
        # same bytes under every proposer mode (asserted in-run)
        for w in grammars:
            ref = None
            for m in MODES:
                r = round_(w, m, mixed=True)
                if ref is None:
                    ref = r["outs"]
                elif r["outs"] != ref:
                    mismatches.append((w, m, "mixed"))
        # timed sweep: interleaved rounds so box drift hits all arms
        # equally; identity asserted here too (all-greedy rows)
        for _ in range(rounds):
            for w in grammars:
                ref = None
                for m in MODES:
                    r = round_(w, m)
                    results[w][m].append(r)
                    if ref is None:
                        ref = r["outs"]
                    elif r["outs"] != ref:
                        mismatches.append((w, m))
        degraded = be.constrain_degraded
    finally:
        be.close()

    out = {"metric": f"b{B}k{K}spec{sk}_structured", "unit": "tok/s",
           "vs_baseline": None, "batch": B, "superstep": K,
           "speculative": sk, "pipeline": pipeline, "gen": gen,
           "rounds": rounds, "identical": not mismatches,
           "constrain_degraded": degraded,
           "model": (f"dim{spec.dim}_voc{spec.vocab_size}"
                     f"_L{spec.n_layers}_s{spec.seq_len}"),
           "workloads": {}}
    speedups = []
    for w in grammars:
        block = {}
        for m in MODES:
            rs = results[w][m]
            drafted = sum(r["drafted"] for r in rs)
            accepted = sum(r["accepted"] for r in rs)
            block[m] = {
                "tok_s": round(statistics.median(r["tok_s"] for r in rs), 3),
                "accept_rate": (round(accepted / drafted, 3)
                                if drafted else None),
            }
        block["speedup_grammar_vs_ngram"] = round(
            block["grammar"]["tok_s"] / block["ngram"]["tok_s"], 3)
        speedups.append(block["speedup_grammar_vs_ngram"])
        out["workloads"][w] = block
    out["speedup_grammar_vs_ngram"] = round(
        statistics.median(speedups), 3)
    out["value"] = round(statistics.median(
        out["workloads"][w]["grammar"]["tok_s"] for w in grammars), 3)
    emit(out)
    ok = True
    if mismatches:
        print(f"❌ output diverged across proposer modes: {mismatches}",
              file=sys.stderr)
        ok = False
    if degraded:
        print(f"❌ {degraded} rows degraded to unconstrained decoding "
              "during a clean bench", file=sys.stderr)
        ok = False
    if out["speedup_grammar_vs_ngram"] < 1.0:
        print("❌ grammar drafting lost to ngram on constrained traffic: "
              f"{out['speedup_grammar_vs_ngram']}x", file=sys.stderr)
        ok = False
    if not ok:
        sys.exit(1)


def chaos_workload(args, spec):
    """--workload chaos: resilience cost of the unhappy path
    (docs/ROBUSTNESS.md). The identical concurrent-request schedule runs
    twice against one warmed BatchEngine — fault-free baseline, then with a
    --fault-rate (default 1%) injected TRANSIENT failure probability on
    every scheduler device dispatch (the retry-with-backoff path) — and
    reports survivor aggregate throughput degradation plus per-request TTFT
    p95 for both. Every request is expected to COMPLETE in both runs: a
    transient fault is retried, not surfaced; completion counts are emitted
    so a retry-path regression shows up as failed_requests > 0."""
    from distributed_llama_tpu.models.params import init_random_params
    from distributed_llama_tpu.quants import FloatType as _FTy
    from distributed_llama_tpu.resilience import faults as _faults
    from distributed_llama_tpu.resilience.faults import FaultSpec
    from distributed_llama_tpu.runtime.batch_engine import BatchEngine
    from distributed_llama_tpu.runtime.sampler import Sampler

    n_req = max(args.requests, 2)
    gen = 24  # decoded tokens per request
    rng = np.random.default_rng(0)
    prompts = [[1] + [int(t) for t in rng.integers(2, spec.vocab_size, 12)]
               for _ in range(n_req)]
    params = init_random_params(spec, _FTy.Q40, seed=0)
    B = args.batch if args.batch > 0 else min(max(n_req // 2, 2), 8)
    be = BatchEngine(spec, params, slots=B,
                     superstep=max(args.superstep, 1), tp=args.tp,
                     paged_kv=not args.no_paged_kv)
    out = {}
    samples = []
    try:
        # warm every compiled shape so both runs measure dispatch, not compile
        be.generate(list(prompts[0]), gen,
                    Sampler(spec.vocab_size, temperature=0.0))
        for label in ("baseline", "chaos"):
            plan = None
            if label == "chaos":
                plan = _faults.install(
                    [FaultSpec("batch.dispatch", kind="transient",
                               prob=args.fault_rate)], seed=7)
            try:
                ttfts, t0s, reqs = {}, {}, []

                def on_tok(i):
                    def cb(_t, i=i):
                        if i not in ttfts:
                            ttfts[i] = time.perf_counter() - t0s[i]
                    return cb

                t_all0 = time.perf_counter()
                for i in range(n_req):
                    t0s[i] = time.perf_counter()
                    reqs.append(be.submit(
                        list(prompts[i]), gen,
                        Sampler(spec.vocab_size, temperature=0.0),
                        on_token=on_tok(i)))
                failed = 0
                tokens = 0
                for i, r in enumerate(reqs):
                    err = None
                    try:
                        tokens += len(r.wait(timeout=600))
                    except Exception as ex:
                        failed += 1
                        err = repr(ex)
                    samples.append({"request_id": r.rid, "phase": label,
                                    "tenant": r.tenant, "class": r.klass,
                                    "ttft_s": ttfts.get(i), "e2e_s": None,
                                    "tokens": len(r.out), "replica": None,
                                    "error": err})
                e2e = time.perf_counter() - t_all0
            finally:
                _faults.uninstall()
            lat = sorted(ttfts.values())
            out[label] = {
                "tok_s": round(tokens / e2e, 3),
                # None, not a crash, when every request died pre-first-token
                # (e.g. --fault-rate 1.0 exhausts every dispatch's retries)
                "ttft_p95_ms": _pct_ms(lat, 0.95),
                "ttft_p99_ms": _pct_ms(lat, 0.99),
                "failed_requests": failed,
                "injected": plan.fired() if plan is not None else 0,
            }
    finally:
        be.close()
    if args.latency_log:
        write_latency_log(args.latency_log, samples)
    base, chaos = out["baseline"], out["chaos"]
    emit({
        "metric": "chaos_survivor_tok_s",
        "value": chaos["tok_s"], "unit": "tok/s", "vs_baseline": None,
        "baseline_tok_s": base["tok_s"],
        "degradation_pct": round(
            100.0 * (1.0 - chaos["tok_s"] / max(base["tok_s"], 1e-9)), 2),
        "ttft_p95_ms": chaos["ttft_p95_ms"],
        "ttft_p99_ms": chaos["ttft_p99_ms"],
        "ttft_p95_baseline_ms": base["ttft_p95_ms"],
        "ttft_p99_baseline_ms": base["ttft_p99_ms"],
        "fault_rate": args.fault_rate,
        "injected_faults": chaos["injected"],
        "failed_requests": chaos["failed_requests"],
        "failed_requests_baseline": base["failed_requests"],
        "requests": n_req, "gen_tokens": gen, "batch": B,
        "superstep": max(args.superstep, 1),
    })


def chaos_fleet_workload(args, spec):
    """--workload chaos --replicas N --kill-replica: the durable-request
    acceptance bench (docs/FLEET.md "Resume protocol"). Launches N real
    api_server subprocesses + the in-process DURABLE router, runs the
    identical request schedule twice — fault-free reference, then with one
    replica SIGKILLed (hard, no drain: the mid-stream failure graceful
    SIGTERM would hide) once the marker stream has delivered a few tokens —
    and asserts IN-RUN that every chaos-phase request completed with output
    byte-identical to its reference (greedy AND seeded-stochastic rows).
    Reports the resumed-request count from the router journal and the
    resume re-prefill prefix-cache reuse rate summed over the surviving
    replicas (nonzero = resume cost ≈ one suffix prefill, the tentpole's
    cost claim)."""
    import subprocess
    import tempfile
    import threading

    from distributed_llama_tpu.fleet.router import close_router, serve_router
    from distributed_llama_tpu.obs import metrics as obs_metrics

    n_rep = args.replicas
    if n_rep < 2:
        print("❌ --workload chaos --kill-replica needs --replicas >= 2 "
              "(a killed singleton has no survivor to resume on)",
              file=sys.stderr)
        sys.exit(2)
    tmp = tempfile.mkdtemp(prefix="dlt_chaos_fleet_")
    mpath, tpath = _write_fleet_model(tmp)
    ports = [_fleet_free_port() for _ in range(n_rep)]
    procs, logs = _spawn_fleet_replicas(
        tmp, mpath, tpath, ports,
        extra_argv=("--supervisor-threshold", "120"))
    _get_json = _fleet_get_json

    n_req = max(args.requests, 6)
    gen = 32
    system = "fleet chaos shared system prompt abcb abcb abcb"

    def req_body(i):
        # greedy AND seeded-stochastic rows, streaming AND non-streaming —
        # every combination must survive the kill token-identically
        return {"messages": [
            {"role": "system", "content": system},
            {"role": "user", "content": f"request {i} ab ab ab ab"}],
            "max_tokens": gen, "stream": i % 3 != 2,
            "temperature": 0.0 if i % 2 == 0 else 0.8,
            "seed": 1000 + i}

    def one_request(rport, i, results, on_delta=None):
        r = completion_request(rport, req_body(i), timeout=300,
                               on_delta=on_delta)
        if r["error"] is not None or r["status"] != 200:
            results[i] = {"error": r["error"] or f"status {r['status']}"}
            return
        results[i] = {"text": r["text"], "finish": r["finish"],
                      "replica": r["replica"]}

    router = None
    try:
        _await_fleet_healthy(procs, ports, tmp)
        router = serve_router([f"127.0.0.1:{p}" for p in ports],
                              host="127.0.0.1", port=0, poll_interval=0.5,
                              block_bytes=32, retries=2, try_timeout=300.0,
                              durable=True)
        rport = router.server_address[1]
        threading.Thread(target=router.serve_forever, daemon=True).start()

        def run_phase(kill: bool):
            results = [None] * n_req
            killed = []

            def on_marker_delta(n, replica):
                # SIGKILL the replica serving the marker stream once real
                # output has flowed — a hard mid-stream death, the case the
                # journal + resume machinery exists for
                if kill and n == 3 and not killed and replica:
                    victim_port = int(replica.rsplit(":", 1)[1])
                    killed.append(replica)
                    procs[ports.index(victim_port)].kill()
            threads = []
            sem = threading.Semaphore(2 * n_rep)

            def run_one(i):
                with sem:
                    one_request(rport, i, results,
                                on_delta=on_marker_delta if i == 0 else None)
            t0 = time.perf_counter()
            for i in range(n_req):
                t = threading.Thread(target=run_one, args=(i,))
                t.start()
                threads.append(t)
            for t in threads:
                t.join(timeout=600)
            return results, killed, time.perf_counter() - t0

        ref, _, _ = run_phase(kill=False)
        ref_failed = [(i, r) for i, r in enumerate(ref)
                      if r is None or "error" in r]
        if ref_failed:
            print(f"❌ fault-free reference phase failed: {ref_failed[:3]}",
                  file=sys.stderr)
            sys.exit(1)
        resumed0 = (obs_metrics.snapshot()
                    .get("router_resumed_requests_total") or 0)
        chaos, killed, wall = run_phase(kill=True)
        failed = [(i, r) for i, r in enumerate(chaos)
                  if r is None or "error" in r]
        diverged = [i for i, (a, b) in enumerate(zip(ref, chaos))
                    if a and b and "error" not in b
                    and a["text"] != b["text"]]
        snap = obs_metrics.snapshot()
        resumed = (snap.get("router_resumed_requests_total") or 0) - resumed0
        # resume re-prefill reuse over the SURVIVING replicas: the resumed
        # requests' prompt ⊕ delivered prefixes vs what their admissions
        # actually re-ran (slot rewind + radix pool seed)
        reused = prefix = 0.0
        for port, proc in zip(ports, procs):
            if proc.poll() is not None:
                continue
            try:
                st, body = _get_json(port, "/v1/stats", timeout=10)
            except OSError:
                continue
            m = (body or {}).get("metrics") or {}
            reused += m.get("api_resume_reused_tokens_total", 0) or 0
            prefix += m.get("api_resume_prefix_tokens_total", 0) or 0
        reuse_rate = round(reused / prefix, 3) if prefix else 0.0
        emit({
            "metric": "chaos_kill_replica_resumed_requests",
            "value": int(resumed), "unit": "requests", "vs_baseline": None,
            "replicas": n_rep, "requests": n_req, "gen_tokens": gen,
            "killed_replica": killed[0] if killed else None,
            "failed_requests": len(failed),
            "failures": [f"{i}: {r}" for i, r in failed[:5]],
            "diverged_requests": diverged,
            "identical": not failed and not diverged,
            "resume_prefix_reuse_rate": reuse_rate,
            "resume_reused_tokens": int(reused),
            "resume_prefix_tokens": int(prefix),
            "wall_s": round(wall, 2),
        })
        # in-run acceptance gates (ISSUE 9): a kill that never engaged, a
        # client-visible failure, a diverged resume, or a resume that
        # re-prefilled everything from scratch all fail the bench
        if not killed:
            print("❌ the kill never engaged (marker stream finished first)",
                  file=sys.stderr)
            sys.exit(1)
        if failed or diverged:
            print(f"❌ {len(failed)} failed, {len(diverged)} diverged",
                  file=sys.stderr)
            sys.exit(1)
        if resumed < 1:
            print("❌ no request was resumed — the kill was not mid-stream",
                  file=sys.stderr)
            sys.exit(1)
        if reuse_rate <= 0.0:
            print("❌ resume re-prefill hit nothing in the prefix cache",
                  file=sys.stderr)
            sys.exit(1)
    finally:
        if router is not None:
            close_router(router)
        for proc in procs:
            if proc.poll() is None:
                proc.terminate()
        for proc in procs:
            try:
                proc.wait(timeout=90)
            except subprocess.TimeoutExpired:
                proc.kill()
        for log in logs:
            log.close()


def chaos_degrade_workload(args, spec):
    """--workload chaos --replicas N --degrade-replica: the GRAY-failure
    acceptance bench (docs/FLEET.md "Gray-failure resilience"). Two real
    fleets run the IDENTICAL seeded schedule through identically-armed
    routers (probation + adaptive timeouts + bounded hedging): first a
    healthy baseline, then a fleet whose replica 0 carries a SUSTAINED
    8-10x request-latency injection (`DLLAMA_FAULTS` duration window in
    that subprocess only — it answers healthz ok while serving slow, the
    gray shape the router must detect from outcomes alone). Gates IN-RUN:

    - 0 client-visible failures in the degraded phase;
    - degraded-fleet TTFT p99 <= 2x the healthy baseline (plus one hedge
      delay + timer-noise floor — the victim's UN-governed latency is the
      9x injection, far past the gate either way);
    - hedge spend within the armed budget (the bench arms a CI-scale
      budget: in a 2-replica fleet HALF of cold picks hit the victim,
      nothing like production's 1/N share under the 5% default);
    - the victim observed ENTERING probation while slow and REJOINING
      after the injection window expires (canary-driven).

    Emits TTFT/TPOT p50/p95/p99 both ways plus hedge/probation counters in
    the standard BENCH json."""
    import http.client
    import subprocess
    import tempfile
    import threading

    from distributed_llama_tpu.fleet.latency import GrayConfig
    from distributed_llama_tpu.fleet.router import close_router, serve_router
    from distributed_llama_tpu.obs import metrics as obs_metrics

    n_rep = args.replicas
    if n_rep < 2:
        print("❌ --workload chaos --degrade-replica needs --replicas >= 2 "
              "(a degraded singleton has nowhere to hedge or fail over)",
              file=sys.stderr)
        sys.exit(2)
    n_req = max(args.requests, 24)
    gen = 16
    degrade_window_s = 60.0

    def req_body(i):
        # unique LEADING system prompts: the affinity key is
        # block-granular, so a shared prefix would pin the whole schedule
        # to one replica and the victim would see no traffic to be judged
        # on; greedy AND seeded-stochastic rows, all streaming (TTFT and
        # TPOT are client-side first-delta/delta-gap timings)
        return {"messages": [
            {"role": "system", "content": f"d{i:03d} gray degrade system"},
            {"role": "user", "content": "ab ab ab ab"}],
            "max_tokens": gen, "stream": True,
            "temperature": 0.0 if i % 2 == 0 else 0.8,
            "seed": 2000 + i}

    def one_request(rport, i, results):
        r = completion_request(rport, req_body(i), timeout=300)
        if r["error"] is not None or r["status"] != 200:
            results[i] = {"error": r["error"]
                          or f"status {r['status']}"}
            return
        results[i] = {"ttft": r["ttft"], "tpot": r["tpot"], "error": None}

    def warm_replica(port):
        # direct (router-bypassing) compile warm: a cold XLA build is tens
        # of seconds on CPU and would smear both phases' percentiles
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
        try:
            for temperature in (0.0, 0.8):
                conn.request("POST", "/v1/chat/completions", json.dumps({
                    "messages": [
                        {"role": "system", "content": "warm system"},
                        {"role": "user", "content": "ab ab"}],
                    "max_tokens": 8, "stream": False, "seed": 7,
                    "temperature": temperature},),
                    {"Content-Type": "application/json"})
                resp = conn.getresponse()
                resp.read()
                if resp.status != 200:
                    raise RuntimeError(f"warm of :{port} failed "
                                       f"({resp.status})")
        finally:
            conn.close()

    hedge_pct, hedge_burst = 0.25, 8.0

    def bench_gray_config(hedge_delay):
        # CI-scale arming: fast detection (6 samples, 3x median), a FIXED
        # hedge delay (adaptive p95 defers itself when HALF the fleet is
        # slow — the 2-replica pathology), and a budget sized for a
        # schedule where ~half of cold picks hit the victim. The delay
        # must sit ABOVE healthy TTFB (or healthy picks hedge too and
        # drain the budget the victim picks need) and far below the
        # injected delay: the degraded phase pins it from the measured
        # healthy p95.
        return GrayConfig(eject_multiple=3.0, min_samples=6,
                          probation_exits=3, canary_every=4,
                          quorum_frac=0.5, min_lat_samples=12,
                          hedge=True, hedge_delay=hedge_delay,
                          hedge_pct=hedge_pct, hedge_burst=hedge_burst)

    def labeled(snap, name):
        return {k.split('"')[1]: v
                for k, v in (snap.get(name) or {}).items()}

    def run_phase(label, victim_env, hedge_delay, window_s=0.0):
        tmp = tempfile.mkdtemp(prefix=f"dlt_gray_{label}_")
        mpath, tpath = _write_fleet_model(tmp)
        ports = [_fleet_free_port() for _ in range(n_rep)]
        procs, logs = _spawn_fleet_replicas(
            tmp, mpath, tpath, ports,
            per_replica_env=[victim_env if i == 0 else None
                             for i in range(n_rep)])
        router = None
        out = {"label": label}
        try:
            _await_fleet_healthy(procs, ports, tmp)
            # non-victim replicas warm first: the victim's fault window
            # starts at ITS first request, so it is warmed last and the
            # schedule starts immediately after
            for port in ports[1:]:
                warm_replica(port)
            tw = time.perf_counter()
            warm_replica(ports[0])
            out["warm_victim_s"] = round(time.perf_counter() - tw, 2)
            router = serve_router([f"127.0.0.1:{p}" for p in ports],
                                  host="127.0.0.1", port=0,
                                  poll_interval=0.3, block_bytes=32,
                                  retries=2, try_timeout=120.0, durable=True,
                                  gray=bench_gray_config(hedge_delay))
            rport = router.server_address[1]
            threading.Thread(target=router.serve_forever,
                             daemon=True).start()
            state = router.router_state
            victim = state.membership.by_id(f"127.0.0.1:{ports[0]}")
            probation = {"entered": False, "exited_after_entry": False,
                         "stop": False}

            def watch():
                seen = False
                while not probation["stop"]:
                    if victim.degraded:
                        seen = probation["entered"] = True
                    elif seen:
                        probation["exited_after_entry"] = True
                    time.sleep(0.05)
            watcher = threading.Thread(target=watch, daemon=True)
            watcher.start()

            results = [None] * n_req
            sem = threading.Semaphore(3)

            def run_one(i):
                with sem:
                    one_request(rport, i, results)
            snap0 = obs_metrics.snapshot()
            t0 = time.perf_counter()
            threads = [threading.Thread(target=run_one, args=(i,))
                       for i in range(n_req)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=600)
            wall = time.perf_counter() - t0
            if victim_env is not None:
                # keep outcome evidence flowing until probation entry,
                # then until the injection window has expired and the
                # canary trickle rejoins the victim
                probe_res = {}
                i = n_req
                deadline = time.monotonic() + 120
                while (not probation["entered"]
                       and time.monotonic() < deadline):
                    one_request(rport, i, probe_res)
                    i += 1
                deadline = time.monotonic() + 120 + window_s
                while ((victim.degraded or not
                        probation["exited_after_entry"])
                       and time.monotonic() < deadline):
                    one_request(rport, i, probe_res)
                    i += 1
                out["probe_requests"] = i - n_req
                out["probe_failures"] = sum(
                    1 for r in probe_res.values()
                    if r is None or r.get("error") is not None)
            probation["stop"] = True
            watcher.join(timeout=5)
            snap1 = obs_metrics.snapshot()
            hedges0 = labeled(snap0, "router_hedges_total")
            hedges1 = labeled(snap1, "router_hedges_total")
            prob0 = labeled(snap0, "router_probation_total")
            prob1 = labeled(snap1, "router_probation_total")
            ttfts = sorted(r["ttft"] for r in results
                           if r and r.get("error") is None
                           and r.get("ttft") is not None)
            tpots = sorted(r["tpot"] for r in results
                           if r and r.get("error") is None
                           and r.get("tpot") is not None)
            budget = state.hedge_budget.stats()
            out.update({
                "failed": [(i, r) for i, r in enumerate(results)
                           if r is None or r.get("error") is not None],
                "wall_s": round(wall, 2),
                "ttft_p50_ms": _pct_ms(ttfts, 0.50),
                "ttft_p95_ms": _pct_ms(ttfts, 0.95),
                "ttft_p99_ms": _pct_ms(ttfts, 0.99),
                "tpot_p50_ms": _pct_ms(tpots, 0.50),
                "tpot_p95_ms": _pct_ms(tpots, 0.95),
                "tpot_p99_ms": _pct_ms(tpots, 0.99),
                "hedges": {k: int((hedges1.get(k) or 0)
                                  - (hedges0.get(k) or 0))
                           for k in ("launched", "won", "denied", "canary")},
                "probation": {k: int((prob1.get(k) or 0)
                                     - (prob0.get(k) or 0))
                              for k in ("enter", "exit")},
                "hedge_budget": budget,
                "probation_entered": probation["entered"],
                "probation_exited": probation["exited_after_entry"],
                "degraded_roster_now": [r.id for r in
                                        state.membership.replicas
                                        if r.degraded],
            })
            return out
        finally:
            if router is not None:
                close_router(router)
            for proc in procs:
                if proc.poll() is None:
                    proc.terminate()
            for proc in procs:
                try:
                    proc.wait(timeout=90)
                except subprocess.TimeoutExpired:
                    proc.kill()
            for log in logs:
                log.close()

    # healthy baseline: hedge delay parked above any plausible healthy
    # TTFB on this box (we have no measurement yet; a delay under healthy
    # latency would hedge ordinary picks)
    healthy = run_phase("healthy", None, hedge_delay=1.0)
    if healthy["failed"]:
        print(f"❌ healthy baseline phase failed: {healthy['failed'][:3]}",
              file=sys.stderr)
        sys.exit(1)
    # sustained 8-10x: the injected stall is ~9x the measured healthy
    # median request time, floored so it dwarfs CI timer noise
    delay_ms = max(9.0 * healthy["ttft_p50_ms"], 1000.0)
    # degraded-phase hedge delay pinned from the MEASURED healthy tail:
    # above p95 (healthy picks almost never hedge, preserving budget for
    # victim picks) and far below the injection
    hedge_delay = min(max(1.5 * healthy["ttft_p95_ms"] / 1000.0, 0.3), 1.5)
    # the victim's fault window opens at its FIRST request — its own two
    # compile-warm requests. Size the window from the healthy phase's
    # MEASURED victim warm (plus the injected stall those warms now pay)
    # so a slow box cannot burn the injection before the schedule starts
    window_s = (degrade_window_s + 2.0 * healthy["warm_victim_s"]
                + 2.0 * delay_ms / 1000.0)
    degraded = run_phase("degraded", {
        "DLLAMA_FAULTS":
            f"api.request:latency:1::{delay_ms:.0f}:{window_s:.0f}",
        "DLLAMA_FAULT_SEED": "7"}, hedge_delay=hedge_delay,
        window_s=window_s)

    # the p99 gate: 2x healthy, floored by one hedge delay + p50 service
    # + timer noise (a hedged victim pick LEGITIMATELY costs delay+service;
    # on a fast box 2x p99 alone can be smaller than that)
    gate_ms = max(2.0 * healthy["ttft_p99_ms"],
                  healthy["ttft_p99_ms"] + hedge_delay * 1000.0 + 400.0)
    budget = degraded["hedge_budget"]
    allowance = budget["cap"] + hedge_pct * budget["noted"]
    emit({
        "metric": "chaos_degrade_ttft_p99_ms",
        "value": degraded["ttft_p99_ms"], "unit": "ms",
        "vs_baseline": None,
        "replicas": n_rep, "requests": n_req, "gen_tokens": gen,
        "injected_delay_ms": round(delay_ms, 1),
        "injected_window_s": round(window_s, 1),
        "hedge_delay_ms": round(hedge_delay * 1000.0, 1),
        "ttft_gate_ms": round(gate_ms, 2),
        "healthy": {k: healthy[k] for k in
                    ("ttft_p50_ms", "ttft_p95_ms", "ttft_p99_ms",
                     "tpot_p50_ms", "tpot_p95_ms", "tpot_p99_ms",
                     "wall_s", "hedges")},
        "degraded": {k: degraded[k] for k in
                     ("ttft_p50_ms", "ttft_p95_ms", "ttft_p99_ms",
                      "tpot_p50_ms", "tpot_p95_ms", "tpot_p99_ms",
                      "wall_s", "hedges", "probation", "probe_requests",
                      "probe_failures", "probation_entered",
                      "probation_exited")},
        "hedge_budget": budget,
        "hedge_allowance": round(allowance, 2),
        "failed_requests": len(degraded["failed"]),
        "failures": [f"{i}: {r}" for i, r in degraded["failed"][:5]],
    })
    # in-run acceptance gates (ISSUE 14)
    if degraded["failed"] or degraded.get("probe_failures"):
        print(f"❌ client-visible failures in the degraded phase: "
              f"{degraded['failed'][:3]} "
              f"(+{degraded.get('probe_failures', 0)} probe)",
              file=sys.stderr)
        sys.exit(1)
    if degraded["ttft_p99_ms"] > gate_ms:
        print(f"❌ degraded TTFT p99 {degraded['ttft_p99_ms']}ms over the "
              f"gate {gate_ms:.0f}ms (healthy p99 "
              f"{healthy['ttft_p99_ms']}ms)", file=sys.stderr)
        sys.exit(1)
    if degraded["hedges"]["launched"] < 1:
        print("❌ vacuous: no hedge launched in the degraded phase",
              file=sys.stderr)
        sys.exit(1)
    # gate the LAUNCH-SITE counter, not budget["spent"]: TokenBudget keeps
    # spent <= cap + rate*noted by construction, so gating its own ledger
    # would be tautological — a regression that launches duplicate tries
    # without spending a token must still fail here
    if degraded["hedges"]["launched"] > allowance:
        print(f"❌ hedges launched {degraded['hedges']['launched']} over "
              f"the configured allowance {allowance:.1f}", file=sys.stderr)
        sys.exit(1)
    if not degraded["probation_entered"]:
        print("❌ the victim never entered gray-failure probation",
              file=sys.stderr)
        sys.exit(1)
    if not degraded["probation_exited"] or degraded["degraded_roster_now"]:
        print("❌ the victim never rejoined after the injection window "
              f"expired (roster {degraded['degraded_roster_now']})",
              file=sys.stderr)
        sys.exit(1)


def trace_workload(args, spec):
    """--workload trace: the multi-tenant SLO acceptance bench
    (docs/SERVING.md "Multi-tenant serving"). A seeded trace-driven load
    generator — bursty arrivals (on/off-modulated exponential gaps),
    heavy-tailed lognormal prompt/output lengths, a configurable tenant mix
    — drives one BatchEngine at ~`--overload`x (default 2x) its MEASURED
    sustained capacity, and the BENCH json gates the SLO story in-run:

    - interactive TTFT p95 within 1.5x of its uncontended value, plus an
      absolute floor of the documented admission window (two in-flight
      K-step dispatches = 2*K*B/capacity wall seconds — milliseconds on
      accelerators, dominant on a 2-core CI box) and 30 ms timer noise;
    - ZERO failed interactive requests (batch sheds first: queue-full
      evictions displace batch, preemption frees slots at super-step
      boundaries);
    - batch-class sheds carry honest drain-derived Retry-After (503), the
      quota-capped tenant sees 429s with bucket-derived Retry-After;
    - every backlogged unthrottled tenant's delivered-token share within
      ε of its configured weight (gold:silver:bronze = 3:2:1; the
      quota-capped fourth tenant is excluded — its share is bound by its
      bucket, not its weight, and WFQ redistributes what it cannot use).

    Phases: calibrate (measure capacity tok/s + drain), uncontended
    interactive TTFT baseline, then the overload trace. One engine, shapes
    warmed by calibration, so the phases compare scheduling — not compiles.
    """
    from distributed_llama_tpu.models.params import init_random_params
    from distributed_llama_tpu.quants import FloatType as _FTy
    from distributed_llama_tpu.resilience.errors import (EngineSaturated,
                                                         QuotaExceeded)
    from distributed_llama_tpu.resilience.tenancy import TenantRegistry
    from distributed_llama_tpu.runtime.batch_engine import BatchEngine
    from distributed_llama_tpu.runtime.sampler import Sampler

    rng = np.random.default_rng(args.seed if hasattr(args, "seed") else 0)
    B = args.batch if args.batch > 0 else 4
    K = max(args.superstep, 1)
    weights = {"gold": 3.0, "silver": 2.0, "bronze": 1.0}
    reg = TenantRegistry.parse(
        "gold:weight=3;silver:weight=2;bronze:weight=1;capped:weight=1")
    params = init_random_params(spec, _FTy.Q40, seed=0)
    be = BatchEngine(spec, params, slots=B, superstep=K, tp=args.tp,
                     tenants=reg, max_queue=4 * B,
                     paged_kv=not args.no_paged_kv)
    greedy = lambda: Sampler(spec.vocab_size, temperature=0.0)  # noqa: E731

    def lens(n, mean_log, sigma, lo, hi):
        return np.clip(np.exp(rng.normal(mean_log, sigma, n)).astype(int),
                       lo, hi)

    out = {}
    try:
        # --- phase 1: calibrate sustained capacity (also warms shapes) ---
        # prompt lengths span the PREFILL_CHUNKS buckets (64/8/1) the
        # heavy-tailed trace will hit: a cold (B, 64) prefill compile
        # landing MID-TRACE would stall the scheduler ~1s and corrupt the
        # interactive TTFT gate with XLA time, not scheduling time
        # (production pre-warms; perf/compile_manifest.json pins shapes)
        def cal_round(plens):
            cal = [be.submit(
                [1] + [int(t) for t in rng.integers(2, 200, plens[
                    i % len(plens)])],
                24, greedy(), klass="batch") for i in range(2 * B)]
            t0 = time.perf_counter()
            toks = sum(len(r.wait(timeout=600)) for r in cal)
            return toks / (time.perf_counter() - t0)

        cal_round([150, 80, 24, 10])  # warm compiles across chunk buckets
        cap_tok_s = cal_round([24, 10, 17, 31])  # measure capacity, not XLA

        # --- phase 2: uncontended interactive TTFT baseline ---
        def run_interactive(tenant):
            t_sub = time.perf_counter()
            first = [None]

            def on_tok(_t):
                if first[0] is None:
                    first[0] = time.perf_counter() - t_sub
            r = be.submit([1] + [int(t) for t in rng.integers(2, 200, 7)],
                          8, greedy(), on_token=on_tok, tenant=tenant,
                          klass="interactive")
            r.wait(timeout=600)
            return first[0]

        unc = sorted(filter(None, (run_interactive("gold")
                                   for _ in range(20))))
        unc_p95 = _pct(unc, 0.95)

        # --- phase 3: the overload trace ---
        mean_gen = 20.0
        batch_rps = args.overload * cap_tok_s / mean_gen  # offered, total
        duration = args.duration
        n_batch = int(batch_rps * duration)
        if n_batch > 1500:  # bound the host-side submit work, say so
            print(f"# arrival cap: {n_batch} -> 1500 batch arrivals "
                  f"(duration shrinks to keep the {args.overload}x rate)",
                  file=sys.stderr)
            n_batch = 1500
            duration = n_batch / batch_rps
        events = []  # (t, tenant, klass, prompt_len, gen)
        share = 1.0 / (len(weights) + 1)  # equal demand incl. capped
        for tenant in (*weights, "capped"):
            t = 0.0
            rate = batch_rps * share
            n = 0
            while t < duration and n < n_batch:
                # bursty: on/off modulation — arrivals at 2.5x the mean
                # rate during the first 40% of each second, silent after
                gap = rng.exponential(1.0 / (2.5 * rate))
                t += gap
                if (t % 1.0) > 0.4:
                    t = np.floor(t) + 1.0  # skip to the next burst window
                if t >= duration:
                    break
                events.append((t, tenant, "batch", 0, 0))
                n += 1
        # heavy-tailed lengths, assigned after the count is known
        plens = lens(len(events), 2.2, 0.8, 4, max(spec.seq_len // 3, 8))
        glens = lens(len(events), 2.8, 0.9, 4, 48)
        events = [(t, tn, kl, int(p), int(g)) for (t, tn, kl, _p, _g), p, g
                  in zip(events, plens, glens)]
        # interactive trickle: gold + silver, one every ~0.6 s each (enough
        # samples that the p95 gate reads a distribution, not one outlier)
        for tenant in ("gold", "silver"):
            t = 0.3
            while t < duration:
                events.append((t, tenant, "interactive", 8, 8))
                t += 0.6
        events.sort(key=lambda e: e[0])
        # quota for the capped tenant: half its offered token rate, so the
        # bucket MUST throttle under the sustained trace
        capped_tok_s = batch_rps * share * mean_gen
        reg.set_quota("capped", rate=0.5 * capped_tok_s,
                      burst=capped_tok_s)

        recs = []
        t_start = time.perf_counter()
        for (t_at, tenant, klass, plen, gen) in events:
            now = time.perf_counter() - t_start
            if t_at > now:
                time.sleep(t_at - now)
            rec = {"tenant": tenant, "class": klass, "gen": gen,
                   "t_sub": time.perf_counter(), "first": None,
                   "last": None, "n": 0, "shed": None, "retry_after": None}

            def on_tok(_t, rec=rec):
                now = time.perf_counter()
                if rec["first"] is None:
                    rec["first"] = now
                rec["last"] = now
                rec["n"] += 1
            try:
                rec["req"] = be.submit(
                    [1] + [int(x) for x in rng.integers(2, 200, plen)],
                    gen, greedy(), on_token=on_tok, tenant=tenant,
                    klass=klass)
            except QuotaExceeded as e:
                rec["shed"] = "quota"
                rec["retry_after"] = e.retry_after
            except EngineSaturated as e:
                rec["shed"] = "saturated"
                rec["retry_after"] = e.retry_after
            recs.append(rec)
        for rec in recs:
            if rec["shed"] is None:
                try:
                    rec["req"].wait(timeout=600)
                except Exception as e:
                    rec["shed"] = f"error: {e!r}"

        # --- analysis + gates ---
        def pct_block(rs):
            ttft = sorted(r["first"] - r["t_sub"] for r in rs
                          if r["first"] is not None)
            tpot = sorted((r["last"] - r["first"]) / (r["n"] - 1)
                          for r in rs
                          if r["first"] is not None and r["n"] > 1)
            e2e = sorted(r["last"] - r["t_sub"] for r in rs
                         if r["last"] is not None)
            return {
                "requests": len(rs),
                "completed": sum(1 for r in rs if r["shed"] is None),
                "shed": sum(1 for r in rs if r["shed"] is not None),
                "ttft_p50_ms": _pct_ms(ttft, 0.50),
                "ttft_p95_ms": _pct_ms(ttft, 0.95),
                "ttft_p99_ms": _pct_ms(ttft, 0.99),
                "tpot_p50_ms": _pct_ms(tpot, 0.50),
                "tpot_p95_ms": _pct_ms(tpot, 0.95),
                "tpot_p99_ms": _pct_ms(tpot, 0.99),
                "e2e_p95_ms": _pct_ms(e2e, 0.95),
            }

        per_tenant = {}
        for tenant in (*weights, "capped"):
            per_tenant[tenant] = {
                klass: pct_block([r for r in recs if r["tenant"] == tenant
                                  and r["class"] == klass])
                for klass in ("interactive", "batch")
                if any(r["tenant"] == tenant and r["class"] == klass
                       for r in recs)}
        inter = [r for r in recs if r["class"] == "interactive"]
        batch = [r for r in recs if r["class"] == "batch"]
        inter_failed = [r for r in recs if r["class"] == "interactive"
                        and r["shed"] is not None]
        batch_shed = [r for r in batch if r["shed"] == "saturated"]
        quota_shed = [r for r in recs if r["shed"] == "quota"]
        delivered = {t: sum(r["n"] for r in batch if r["tenant"] == t
                            and r["shed"] is None) for t in weights}
        total_delivered = max(sum(delivered.values()), 1)
        total_w = sum(weights.values())
        shares = {t: delivered[t] / total_delivered for t in weights}
        share_err = {t: abs(shares[t] - weights[t] / total_w)
                     for t in weights}
        inter_ttft = sorted(r["first"] - r["t_sub"] for r in inter
                            if r["first"] is not None)
        inter_p95 = _pct(inter_ttft, 0.95)
        # admission-latency bound (docs/SERVING.md): an interactive arrival
        # waits out at most the in-flight dispatch pair (pipelined depth 2)
        # before preemption/class-priority get it a slot. The largest
        # single dispatch is either a K-step super-step (K*B tokens) or a
        # max-chunk prefill (PREFILL_CHUNKS[0] positions, with riders), so
        # the window is 2*(chunk + K*B)/capacity wall seconds. On
        # accelerators that is milliseconds and the gate tends to pure
        # 1.5x; on a 2-core CI box the dispatch window dominates a ~50 ms
        # uncontended TTFT, so the gate adds it (plus 30 ms timer noise) as
        # the absolute floor — a multi-second queueing pathology (e.g. the
        # cold-compile stall this bench caught during development) still
        # fails by an order of magnitude.
        from distributed_llama_tpu.runtime.engine import PREFILL_CHUNKS

        adm_window = (2.0 * (PREFILL_CHUNKS[0] + K * B)
                      / max(cap_tok_s, 1e-9))
        ttft_gate = (unc_p95 is not None and inter_p95 is not None
                     and inter_p95 <= max(1.5 * unc_p95,
                                          unc_p95 + adm_window + 0.030))
        gates = {
            "zero_failed_interactive": not inter_failed,
            "interactive_ttft_within_1_5x": bool(ttft_gate),
            "batch_sheds_honest": bool(batch_shed) and all(
                r["retry_after"] and 0.0 < r["retry_after"] <= 60.0
                for r in batch_shed),
            "quota_throttles_honest": bool(quota_shed) and all(
                r["retry_after"] and r["retry_after"] > 0.0
                for r in quota_shed),
            "shares_within_eps": all(e <= 0.12 for e in share_err.values()),
        }
        out = {
            "metric": "trace_interactive_ttft_p95_ms",
            "value": round(inter_p95 * 1e3, 2) if inter_p95 else None,
            "unit": "ms", "vs_baseline": None,
            "uncontended_ttft_p95_ms": round(unc_p95 * 1e3, 2)
            if unc_p95 else None,
            "ttft_ratio": round(inter_p95 / unc_p95, 3)
            if inter_p95 and unc_p95 else None,
            "admission_window_ms": round(adm_window * 1e3, 2),
            "capacity_tok_s": round(cap_tok_s, 1),
            "overload": args.overload,
            "duration_s": round(duration, 2),
            "arrivals": len(recs),
            "interactive_requests": len(inter),
            "interactive_failed": len(inter_failed),
            "batch_shed": len(batch_shed),
            "quota_throttled": len(quota_shed),
            "retry_after_p50_s": _pct(sorted(
                r["retry_after"] for r in batch_shed
                if r["retry_after"] is not None), 0.5),
            "tenant_shares": {t: round(s, 3) for t, s in shares.items()},
            "tenant_share_target": {t: round(w / total_w, 3)
                                    for t, w in weights.items()},
            "tenant_share_err": {t: round(e, 3)
                                 for t, e in share_err.items()},
            "per_tenant": per_tenant,
            "gates": gates,
            "batch": B, "superstep": K,
        }
        emit(out)
        if args.latency_log:
            write_latency_log(args.latency_log, [
                {"request_id": (r.get("req").rid if r.get("req") is not None
                                else None),
                 "tenant": r["tenant"], "class": r["class"],
                 "ttft_s": (r["first"] - r["t_sub"])
                 if r["first"] is not None else None,
                 "e2e_s": (r["last"] - r["t_sub"])
                 if r["last"] is not None else None,
                 "tokens": r["n"], "replica": None, "shed": r["shed"],
                 "retry_after_s": r["retry_after"]} for r in recs])
        if not all(gates.values()):
            print(f"❌ SLO gates failed: "
                  f"{[k for k, v in gates.items() if not v]}",
                  file=sys.stderr)
            sys.exit(1)
    finally:
        be.close()


def vs_baseline(args, tok_s: float):
    """Ratio vs the reference's published number — which exists only for the
    Llama-2-7B single-node config (README.md:131). Other archs report null rather
    than a ratio against the wrong model's baseline."""
    if args.arch == "llama2_7b" and not args.small:
        return round(tok_s / BASELINE_TOK_S, 3)
    return None


def metric_name(args) -> str:
    if getattr(args, "batch", 0) > 0:
        # B and K are part of the metric identity: the serving trajectory
        # tracks aggregate tok/s per (B, K) point across rounds. K mirrors
        # the bench loop's clamp (max(superstep, 1)) so the label always
        # names the configuration actually measured.
        kind = f"b{args.batch}k{max(args.superstep, 1)}_decode"
    else:
        kind = ("prefill" if args.prefill > 0
                else "paged_decode" if getattr(args, "kv_paged", 0) > 0
                else "decode")
    if args.small:
        return (f"small_{kind}_tok_s" if kind == "prefill"
                else f"small_q40_{kind}_tok_s")
    return f"{args.arch}_q40_{kind}_tok_s"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--small", action="store_true", help="tiny model (CI smoke)")
    ap.add_argument("--arch", choices=sorted(ARCHS), default="llama2_7b",
                    help="which BASELINE.json config shape to bench")
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--layout", choices=("i4p", "i8"), default="i4p")
    ap.add_argument("--window", type=int, default=256,
                    help="attention window bucket (cache positions decode reads)")
    ap.add_argument("--device-loop", type=int, default=0, metavar="N",
                    help="use the on-device scan loop, N tokens per dispatch")
    ap.add_argument("--batch", type=int, default=0, metavar="B",
                    help="serving-throughput mode: B cache rows decode through "
                         "the batched K-step device loop (BatchEngine's hot "
                         "path); reports aggregate_decode_tok_s = B*K/dispatch")
    ap.add_argument("--superstep", type=int, default=8, metavar="K",
                    help="decode steps fused per dispatch in --batch mode")
    ap.add_argument("--pipeline", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="with --batch: drive the REAL BatchEngine scheduler "
                         "(admission + host-side block delivery) instead of "
                         "the raw device loop, with pipelined super-steps on "
                         "(--pipeline) or off (--no-pipeline) — the A/B "
                         "surface for docs/SERVING.md \"Pipelined decode\". "
                         "Omit for the raw-loop headline measurement")
    ap.add_argument("--prefill", type=int, default=0, metavar="T",
                    help="bench chunked prefill throughput at chunk size T instead "
                         "of decode")
    ap.add_argument("--workload",
                    choices=("shared-prefix", "chaos", "repetition",
                             "spec-suite", "structured", "trace",
                             "mixed-context"),
                    default=None,
                    help="scenario mode: 'shared-prefix' drives the BatchEngine "
                         "with a common-system-prompt multi-request workload and "
                         "reports TTFT p50/p95 + prefix_hit_rate, cache on vs "
                         "off; 'chaos' runs the same schedule fault-free vs "
                         "with --fault-rate injected transient dispatch "
                         "failures and reports survivor-throughput degradation "
                         "+ TTFT p95 (docs/ROBUSTNESS.md); 'repetition' drives "
                         "n-gram-dense (code/JSON-shaped) prompts through the "
                         "batched scheduler spec-off vs --speculative K and "
                         "reports tok/s both ways + accept rate "
                         "(docs/SERVING.md \"Speculative decoding\"); "
                         "'trace' drives the multi-tenant scheduler at "
                         "--overload x measured capacity with seeded bursty "
                         "arrivals, heavy-tailed lengths, and a weighted "
                         "tenant mix, gating the SLO story in-run "
                         "(docs/SERVING.md \"Multi-tenant serving\"); "
                         "'mixed-context' A/Bs a role-split disaggregated "
                         "2-replica fleet against a monolithic one under "
                         "co-scheduled long prefills + short decode chains, "
                         "gating decode TPOT p95 and the zero-re-prefill "
                         "claim in-run (docs/DISAGG.md); 'spec-suite' runs "
                         "chat/code/json/open-ended generators through one "
                         "engine with proposer=off/ngram/model rounds "
                         "interleaved, asserting byte-identity in-run and "
                         "reporting per-workload accept rate + tok/s "
                         "(docs/SERVING.md \"Model-based drafting\")")
    ap.add_argument("--overload", type=float, default=2.0, metavar="X",
                    help="trace workload: offered batch load as a multiple "
                         "of the engine's measured sustained capacity")
    ap.add_argument("--duration", type=float, default=10.0, metavar="S",
                    help="trace workload: overload-phase length (arrivals "
                         "capped at 1500; the cap shortens the phase, "
                         "never thins the rate)")
    ap.add_argument("--speculative", type=int, default=0, metavar="S",
                    help="batched speculative decoding (--batch / --workload "
                         "repetition): draft up to S tokens per row from the "
                         "slot's n-gram index and verify each row's block in "
                         "ONE (B, 1+S) dispatch (docs/SERVING.md)")
    ap.add_argument("--fault-rate", type=float, default=0.01, metavar="P",
                    help="chaos workload: per-dispatch transient-failure "
                         "injection probability (retried by the scheduler)")
    ap.add_argument("--requests", type=int, default=5, metavar="N",
                    help="shared-prefix workload: total requests (1 warm + N-1 "
                         "concurrent followers)")
    ap.add_argument("--replicas", type=int, default=0, metavar="N",
                    help="shared-prefix workload: run the FLEET tier — N real "
                         "api_server subprocesses fronted by the in-process "
                         "prefix-affinity router (docs/FLEET.md); reports "
                         "fleet tok/s, TTFT p50/p95 and the aggregate "
                         "prefix-hit-rate over all replicas")
    ap.add_argument("--routing", choices=("affinity", "random"),
                    default="affinity",
                    help="fleet replica selection: 'affinity' (prefix-"
                         "locality, least-loaded fallback) vs the 'random' "
                         "A/B control")
    ap.add_argument("--kill-replica", action="store_true",
                    help="fleet workload: SIGTERM one replica halfway through "
                         "the measured phase — graceful drain + router "
                         "failover must complete every request (exit 1 on any "
                         "client-visible failure)")
    ap.add_argument("--degrade-replica", action="store_true",
                    help="chaos fleet workload: run the identical schedule "
                         "against a healthy fleet and one whose replica 0 "
                         "serves under a sustained 8-10x injected latency "
                         "while answering healthz ok (the GRAY failure, "
                         "docs/FLEET.md) — gates 0 failures, TTFT p99 <= 2x "
                         "healthy, hedge spend in budget, probation "
                         "entry + rejoin")
    ap.add_argument("--shared-prefix", type=int, default=192, metavar="T",
                    help="shared-prefix workload: tokens in the common system "
                         "prompt (clamped to fit seq_len)")
    ap.add_argument("--no-paged-kv", action="store_true",
                    help="escape hatch: run BatchEngine workloads on the "
                         "dense contiguous per-slot KV caches instead of the "
                         "device block pool + tables (docs/PAGED_KV.md) — "
                         "the A/B control for the paged columns")
    ap.add_argument("--long-context", action="store_true",
                    help="shared-prefix workload variant: demonstrate the "
                         "paged pool's KV-capacity↔slot-count decoupling — "
                         "one request runs a context LONGER than slot-count × "
                         "the dense-equivalent per-slot capacity at the same "
                         "KV memory budget (docs/PAGED_KV.md)")
    ap.add_argument("--profile-dir", default=None,
                    help="write a jax.profiler trace of the timed region here")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="record per-dispatch spans of the timed region and "
                         "write Chrome trace-event JSON (obs/trace.py; open "
                         "in ui.perfetto.dev)")
    ap.add_argument("--trace-fleet", default=None, metavar="OUT.json",
                    help="with --replicas N: enable tracing on the router "
                         "AND every replica subprocess, pull the router's "
                         "GET /v1/trace at the end, and write ONE merged "
                         "Perfetto file where a request's router proxy span "
                         "and its replica engine spans share a trace id "
                         "(docs/OBSERVABILITY.md); also verifies a sampled "
                         "request's flight-recorder timeline end-to-end")
    ap.add_argument("--latency-log", default=None, metavar="OUT.jsonl",
                    help="workload modes: dump raw per-request samples "
                         "(request id, ttft, e2e, tokens, replica) as JSONL "
                         "for offline percentile analysis")
    ap.add_argument("--no-fuse", action="store_true",
                    help="keep wq/wk/wv and w1/w3 as separate kernel launches "
                         "instead of the merged wqkv/w13 groups (A/B lever)")
    ap.add_argument("--kv-paged", type=int, default=0, metavar="R",
                    help="bench the paged (out-of-core) KV cache: hot ring of "
                         "R positions + host cold store, decode timed with "
                         "~128 cold positions (runtime/paged_cache.py). "
                         "Documents the capacity valve's real per-token cost")
    args = ap.parse_args()

    if args.trace:
        # NOTE: obs_trace is the MODULE-level import — a local re-import here
        # would make the name local to main() and crash every non---trace run
        # at the span sites (the make_sharded_forward shadowing bug's twin)
        tracer = obs_trace.install()
        import atexit

        atexit.register(lambda: tracer.dump(args.trace))

    if args.batch > 0 and (args.prefill > 0 or args.device_loop > 0
                           or args.kv_paged > 0):
        ap.error("--batch is its own mode (batched K-step decode); combine "
                 "only with --superstep/--steps/--arch/--layout/--tp")
    if args.workload and (args.prefill > 0 or args.device_loop > 0
                          or args.kv_paged > 0):
        ap.error(f"--workload {args.workload} is its own mode; combine only "
                 "with --small/--arch/--batch/--superstep/--requests/"
                 "--shared-prefix/--fault-rate/--speculative/--tp")
    if args.speculative and not (args.workload in ("repetition",
                                                   "spec-suite",
                                                   "structured")
                                 or args.batch > 0):
        ap.error("--speculative S applies to the batched scheduler: combine "
                 "with --batch B (engine mode) or --workload "
                 "repetition/spec-suite/structured")
    if args.replicas and args.workload not in ("shared-prefix", "chaos"):
        ap.error("--replicas N is the fleet tier of "
                 "--workload shared-prefix / chaos (docs/FLEET.md); N=1 is "
                 "the single-replica baseline the acceptance compares "
                 "against")
    if args.kill_replica and not args.replicas:
        ap.error("--kill-replica requires --replicas N")
    if args.degrade_replica and (args.workload != "chaos"
                                 or not args.replicas):
        ap.error("--degrade-replica is the gray-failure mode of "
                 "--workload chaos --replicas N (docs/FLEET.md "
                 "\"Gray-failure resilience\")")
    if args.degrade_replica and args.kill_replica:
        ap.error("--degrade-replica and --kill-replica are separate "
                 "chaos modes; run them as two bench invocations")
    if (args.workload == "chaos" and args.replicas
            and not args.kill_replica and not args.degrade_replica):
        ap.error("--workload chaos --replicas N needs a fleet chaos mode: "
                 "--kill-replica (mid-stream SIGKILL + durable resume) or "
                 "--degrade-replica (sustained gray degradation); the "
                 "in-process fault-rate chaos bench takes no --replicas)")
    if args.trace_fleet and not args.replicas:
        ap.error("--trace-fleet requires --replicas N (the fleet tier of "
                 "--workload shared-prefix)")
    if args.latency_log and not args.workload:
        ap.error("--latency-log applies to --workload modes (per-request "
                 "samples need a request workload)")
    if args.kv_paged > 0 and args.tp > 1:
        # before any mesh/device work so the error beats a mesh-size crash
        ap.error("--kv-paged is single-chip (the paged step is an unsharded "
                 "program; Engine enforces the same)")

    # One process on the chip, and it says which: the start-up line names the
    # device, and a run that did not land on a TPU stops here unless the
    # caller asked for the CPU by name. Nothing is served from a file and
    # nothing degrades to a weaker configuration: a lowering failure below is
    # an error with a non-zero exit code.
    platform = start()["platform"]
    if platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        sys.exit(f"bench.py: JAX found a {platform} device, not a tpu. A run "
                 "off the chip has to be asked for: JAX_PLATFORMS=cpu")
    on_tpu = platform == "tpu"
    spec = ModelSpec(**(SMALL if args.small else ARCHS[args.arch])).resolved()
    if args.workload == "shared-prefix":
        if args.long_context:
            # paged capacity decoupling demo (docs/PAGED_KV.md): a context
            # longer than slot-count x the dense-equivalent per-slot
            # capacity fits, because KV capacity is the POOL, not B slots
            long_context_workload(args)
        elif args.replicas >= 1:
            # --replicas 1 is the single-replica fleet baseline: the SAME
            # request schedule + router proxy, so the N>=2 comparison isolates
            # routing (docs/FLEET.md); 0 = the in-process PR 3 workload
            fleet_shared_prefix_workload(args, spec)
        else:
            shared_prefix_workload(args, spec)
        return
    if args.workload == "chaos":
        if args.replicas >= 1 and args.degrade_replica:
            # gray-failure fleet chaos (docs/FLEET.md "Gray-failure
            # resilience"): identical schedule vs a healthy fleet and one
            # with a sustained-slow replica — probation + hedging gated
            chaos_degrade_workload(args, spec)
        elif args.replicas >= 1:
            # fleet chaos (docs/FLEET.md "Resume protocol"): real replica
            # subprocesses + the durable router, SIGKILL one mid-stream —
            # every request must complete with resumed outputs byte-identical
            chaos_fleet_workload(args, spec)
        else:
            chaos_workload(args, spec)
        return
    if args.workload == "mixed-context":
        # fixed 2-replica topology per arm (a prefill/decode pair IS the
        # minimal disaggregated fleet; the monolithic control mirrors it)
        mixed_context_workload(args, spec)
        return
    if args.workload == "repetition":
        if not on_tpu and not args.small and args.arch == "llama2_7b":
            # CPU default: the overhead-bound tiny geometry (see TINY_REP) —
            # pass --small/--arch to force a specific shape instead
            spec = ModelSpec(**TINY_REP).resolved()
        repetition_workload(args, spec)
        return
    if args.workload == "spec-suite":
        if not on_tpu and not args.small and args.arch == "llama2_7b":
            # CPU default: a COMPUTE-bound geometry (dim 256, L4) — the
            # drafting win is target-step/drafter-step cost asymmetry, and
            # TINY_REP's dim-64 steps are all dispatch overhead, where an
            # L1 drafter step costs the same as an L4 target step and no
            # drafter can win (the same reasoning that sizes the
            # repetition bench the opposite way)
            spec = ModelSpec(**dict(TINY_REP, dim=256, hidden_dim=512,
                                    n_layers=4)).resolved()
        spec_suite_workload(args, spec)
        return
    if args.workload == "structured":
        if not on_tpu and not args.small and args.arch == "llama2_7b":
            # CPU default: the spec-suite's COMPUTE-bound geometry — the
            # grammar-drafting win is the same target-step/proposer-cost
            # asymmetry the model drafter needs (forced chains just make
            # the proposer free and the accept certain)
            spec = ModelSpec(**dict(TINY_REP, dim=256, hidden_dim=512,
                                    n_layers=4)).resolved()
        structured_workload(args, spec)
        return
    if args.workload == "trace":
        if not on_tpu and not args.small and args.arch == "llama2_7b":
            # same CPU default as repetition: the trace bench measures
            # SCHEDULING policy, which the tiny geometry exercises at
            # realistic queue depths in seconds instead of minutes
            spec = ModelSpec(**TINY_REP).resolved()
        trace_workload(args, spec)
        return
    if args.batch > 0 and args.pipeline is not None:
        batched_engine_bench(args, spec)
        return
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    layout = args.layout if on_tpu else "planar"
    window = min(max(args.window, 64), spec.seq_len)
    # keep the documented start_pos + T <= attn_window contract: grow the bucket to
    # cover every decoded position (warm steps + timed steps, or the loop dispatches)
    chunked = args.device_loop if args.device_loop > 0 else (
        max(args.superstep, 1) if args.batch > 0 else 0)
    steps_end = 4 + args.steps if chunked <= 0 else (
        chunked * (max(args.steps // chunked, 1) + 1))
    while window < min(steps_end, spec.seq_len):
        window *= 2
    window = None if window >= spec.seq_len else window

    mesh = make_mesh(tp=args.tp)
    rope = RopeTables.create(spec)
    state = {}

    if args.kv_paged > 0:
        # paged-cache rung: mirrors Engine's two-phase drive (plain deferred
        # step while the ring fills, paged step once cold history exists) so
        # the timed region measures exactly what a user of
        # --kv-cache-storage host pays per token. No fallback ladder — a
        # lowering failure here is an explicit error record, not a downgrade.
        # NOTE: make_sharded_forward comes from the MODULE-level import; a
        # function-local re-import here made it a local name of main() and
        # broke every non-paged bench path with an unbound-free-variable
        # NameError (the shadowing bug the smoke-lint satellite exists for).
        from distributed_llama_tpu.runtime.paged_cache import (  # noqa: E402
            HostKVStore, init_ring_cache, make_paged_step)

        resident = max(64, (args.kv_paged + 63) // 64 * 64)
        cold_target = min(128, spec.seq_len - resident - args.steps - 66)
        if cold_target < 64:
            ap.error(f"--kv-paged {resident}: ring + >=64 cold + timed steps "
                     f"must fit seq_len {spec.seq_len}")
        params = shard_params(synth_params(spec, layout, tp=args.tp), mesh, spec)
        state.update(wbytes=decode_stream_bytes(params, spec))
        store = HostKVStore(spec, resident, storage="host",
                           dtype=(np.float32 if dtype == jnp.float32
                                  else np.dtype(jnp.bfloat16)))
        kc, vc = init_ring_cache(spec, resident, dtype=dtype)
        warm_step = make_sharded_forward(spec, mesh, params, dtype=dtype,
                                         use_pallas=on_tpu, donate_cache=True,
                                         attn_window=None)
        paged_step = make_paged_step(spec, store, dtype=dtype,
                                     use_pallas=on_tpu)
        toks64 = jnp.ones((1, 64), jnp.int32)
        pos = 0
        while pos + 64 <= resident:  # fill the ring callback-free
            logits, kc, vc = warm_step(params, rope, toks64, kc, vc,
                                       jnp.int32(pos))
            store.append(np.asarray(kc[:, :, :, pos:pos + 64]),
                         np.asarray(vc[:, :, :, pos:pos + 64]), pos)
            pos += 64
        while pos < resident + cold_target:  # build real cold history
            logits, kc, vc, (kr, vr) = paged_step(params, rope, toks64, kc, vc,
                                                  jnp.int32(pos))
            store.append(np.asarray(kr), np.asarray(vr), pos)
            pos += 64
        tokp = jnp.asarray([[1]], jnp.int32)
        for _ in range(2):  # compile + warm the T=1 paged program
            logits, kc, vc, (kr, vr) = paged_step(params, rope, tokp, kc, vc,
                                                  jnp.int32(pos))
            store.append(np.asarray(kr), np.asarray(vr), pos)
            pos += 1
        logits.block_until_ready()
        t0 = time.perf_counter()
        for _ in range(args.steps):
            logits, kc, vc, (kr, vr) = paged_step(params, rope, tokp, kc, vc,
                                                  jnp.int32(pos))
            store.append(np.asarray(kr), np.asarray(vr), pos)
            pos += 1
        logits.block_until_ready()
        dt = (time.perf_counter() - t0) / args.steps
        cold = pos - resident
        emit({
            "metric": metric_name(args),
            "value": round(1.0 / dt, 3), "unit": "tok/s", "vs_baseline": None,
            "ms_per_token": round(dt * 1e3, 3), "resident": resident,
            "cold_positions": cold, "layout": layout,
            "weight_gb": round(state["wbytes"] / 1e9, 3),
            "achieved_gbps": round(state["wbytes"] / 1e9 / dt, 1),
            "cold_gb_per_token": round(
                spec.n_layers * 2 * spec.n_kv_heads * cold * spec.head_size
                * store.k.itemsize / 1e9, 3),
        })
        return

    # the configuration asked for, and no other: a kernel that fails to lower
    # is an error with a non-zero exit code, not a weaker rung with a number
    state.update(
        layout=layout,
        # every kernel whose gate admits the shape on the chip, XLA off it
        use_pallas=on_tpu)

    def build():
        params = shard_params(
            synth_params(spec, layout, fuse=not args.no_fuse, tp=args.tp),
            mesh, spec)
        state.update(params=params, wbytes=decode_stream_bytes(params, spec))
        kc, vc = init_sharded_kv_cache(spec, mesh, batch=max(args.batch, 1),
                                       dtype=dtype)
        return params, kc, vc

    tok = jnp.asarray([[1]], jnp.int32)

    import contextlib
    profile_ctx = (jax.profiler.trace(args.profile_dir) if args.profile_dir
                   else contextlib.nullcontext())

    if args.prefill > 0:
        # prefill throughput: repeated T-token chunks walking the context (the
        # reference prefills strictly token-by-token, dllama.cpp:163-167; chunked
        # prefill is a claimed capability win — this measures it)
        t_chunk = args.prefill
        if t_chunk > spec.seq_len // 2:
            ap.error(f"--prefill {t_chunk} too large: compile + timed chunks must "
                     f"fit seq_len {spec.seq_len}")
        # compile chunk + n_disp timed chunks must fit the context
        n_disp = max(min(args.steps, spec.seq_len // t_chunk - 1), 1)
        pwindow = 1 << max((t_chunk * (n_disp + 1) - 1).bit_length(), 8)
        pwindow = None if pwindow >= spec.seq_len else pwindow
        toks = jnp.ones((1, t_chunk), jnp.int32)

        def warm_prefill(params, kc, vc):
            step = make_sharded_forward(spec, mesh, params, dtype=dtype,
                                        use_pallas=state["use_pallas"],
                                        donate_cache=True,
                                        attn_window=pwindow)
            logits, kc, vc = step(params, rope, toks, kc, vc, jnp.int32(0))  # compile
            logits.block_until_ready()
            return step, params, kc, vc

        step, params, kc, vc = warm_prefill(*build())
        pos = t_chunk
        with profile_ctx:
            t0 = time.perf_counter()
            for _ in range(n_disp):
                logits, kc, vc = step(params, rope, toks, kc, vc, jnp.int32(pos))
                pos += t_chunk
            logits.block_until_ready()
            dt_all = time.perf_counter() - t0
        tok_s = n_disp * t_chunk / dt_all
        out = {
            "metric": metric_name(args), "value": round(tok_s, 1), "unit": "tok/s",
            "vs_baseline": vs_baseline(args, tok_s),
            "chunk": t_chunk, "weight_gb": round(state["wbytes"] / 1e9, 3),
            "layout": state["layout"],
            "ms_per_chunk": round(dt_all / n_disp * 1e3, 2),
        }
        # report the EFFECTIVE kernel engagement: the dequant-matmul gates
        # per-weight (q4_mm_supported), so an A/B record must say how much of
        # the weight bytes actually took the kernel, not what was requested
        if state["use_pallas"]:
            from distributed_llama_tpu.ops.pallas_q4_mm import q4_mm_supported

            eng_b = tot_b = 0
            tensors = list(state["params"]["blocks"].values()) + [
                state["params"]["wcls"]]
            for w in tensors:
                if not (isinstance(w, QTensor)
                        and w.ftype in (FloatType.Q40, FloatType.Q80)):
                    continue
                nb_bytes = w.nbytes()
                tot_b += nb_bytes
                # kernel sees the per-layer (and per-expert) 2-D slice
                d2 = QTensor(w.ftype, w.data.reshape(-1, w.data.shape[-1]),
                             None, layout=w.layout, groups=w.groups)
                if q4_mm_supported(d2, t_chunk):
                    eng_b += nb_bytes
            out["prefill_kernel"] = eng_b == tot_b and tot_b > 0
            out["prefill_kernel_coverage"] = round(eng_b / max(tot_b, 1), 3)
        else:
            out["prefill_kernel"] = False
        if args.profile_dir:
            out["profiled"] = True
        emit(out)
        return

    if args.batch > 0:
        # serving-throughput mode: the BatchEngine hot path (batched K-step
        # device loop, all B rows active) measured standalone. One dispatch =
        # B*K decoded tokens and ONE host sync.
        from distributed_llama_tpu.runtime.device_loop import (
            make_batched_decode_loop)

        B, K = args.batch, max(args.superstep, 1)
        zeros = np.zeros((B,), np.float32)
        rng = np.zeros((B, 2), np.uint32)
        ones_tok = np.ones((B,), np.int32)
        full_budget = np.full((B,), K, np.int32)

        def warm_bloop(params, kc, vc):
            loop = make_batched_decode_loop(
                spec, mesh, params, K, mode="greedy", dtype=dtype,
                use_pallas=state["use_pallas"], attn_window=window)
            toks, _tok, _pos, _, kc, vc = loop(
                params, rope, ones_tok, kc, vc, np.zeros((B,), np.int32),
                rng, zeros, zeros + 0.9, full_budget)  # compile + warm
            toks.block_until_ready()
            return loop, params, kc, vc

        loop, params, kc, vc = warm_bloop(*build())
        pos = K
        n_disp = max(args.steps // K, 1)
        with profile_ctx:
            t0 = time.perf_counter()
            for _ in range(n_disp):
                with obs_trace.span("bench.super_step", {"B": B, "K": K}):
                    toks, _tok, _pos, _, kc, vc = loop(
                        params, rope, ones_tok, kc, vc,
                        np.full((B,), pos, np.int32), rng, zeros,
                        zeros + 0.9, full_budget)
                pos += K
            toks.block_until_ready()
            dt_disp = (time.perf_counter() - t0) / n_disp
        per_stream = K / dt_disp
        aggregate = B * per_stream
        out = {
            "metric": metric_name(args),
            "value": round(aggregate, 3), "unit": "tok/s",
            "vs_baseline": None,  # aggregate metric, not the 1-stream baseline
            "aggregate_decode_tok_s": round(aggregate, 3),
            "per_stream_tok_s": round(per_stream, 3),
            "batch": B, "superstep": K,
            "ms_per_dispatch": round(dt_disp * 1e3, 3),
            "ms_per_token_per_stream": round(dt_disp / K * 1e3, 3),
            "weight_gb": round(state["wbytes"] / 1e9, 3),
            "achieved_gbps": round(state["wbytes"] / 1e9 / (dt_disp / K), 1),
            "layout": state["layout"],
            "attn_window": window or spec.seq_len, "steps": args.steps,
            # which lowering each traced dispatch shape ACTUALLY took
            # (ops/matmul.py selection registry): a record of the kernels
            # must show q4_mm here, not a silent xla-fallback
            # (docs/SERVING.md "Kernel selection")
            "kernel_policy": str(state["use_pallas"]),
            "kernels": sorted(set(kernel_selections().values())),
        }
        if args.profile_dir:
            out["profiled"] = True
        emit(out)
        return

    if args.device_loop > 0:
        from distributed_llama_tpu.runtime.device_loop import make_decode_loop

        chunk = args.device_loop
        key = jax.random.PRNGKey(0)

        def warm_loop(params, kc, vc):
            loop = make_decode_loop(spec, mesh, params, chunk, mode="greedy",
                                    dtype=dtype, use_pallas=state["use_pallas"],
                                    attn_window=window)
            toks, _, kc, vc = loop(params, rope, 1, kc, vc, 0, key)  # compile + warm
            toks.block_until_ready()
            return loop, params, kc, vc

        loop, params, kc, vc = warm_loop(*build())
        pos = chunk
        n_disp = max(args.steps // chunk, 1)
        with profile_ctx:
            t0 = time.perf_counter()
            for _ in range(n_disp):
                toks, _, kc, vc = loop(params, rope, 1, kc, vc, pos, key)
                pos += chunk
            toks.block_until_ready()
            dt = (time.perf_counter() - t0) / (n_disp * chunk)
    else:
        def warm_step(params, kc, vc):
            step = make_sharded_forward(spec, mesh, params, dtype=dtype,
                                        use_pallas=state["use_pallas"],
                                        donate_cache=True,
                                        attn_window=window)
            logits, kc, vc = step(params, rope, tok, kc, vc, jnp.int32(0))  # compile
            logits.block_until_ready()
            return step, params, kc, vc

        step, params, kc, vc = warm_step(*build())
        for i in range(3):  # warm steps
            logits, kc, vc = step(params, rope, tok, kc, vc, jnp.int32(1 + i))
        logits.block_until_ready()

        with profile_ctx:
            t0 = time.perf_counter()
            pos = 4
            for _ in range(args.steps):
                with obs_trace.span("bench.decode_step", {"pos": pos}):
                    logits, kc, vc = step(params, rope, tok, kc, vc,
                                          jnp.int32(pos))
                pos += 1
            logits.block_until_ready()
            dt = (time.perf_counter() - t0) / args.steps

    tok_s = 1.0 / dt
    out = {
        "metric": metric_name(args),
        "value": round(tok_s, 3),
        "unit": "tok/s",
        "vs_baseline": vs_baseline(args, tok_s),
        "ms_per_token": round(dt * 1e3, 3),
        "weight_gb": round(state["wbytes"] / 1e9, 3),
        "achieved_gbps": round(state["wbytes"] / 1e9 / dt, 1),
        "layout": state["layout"],
        "attn_window": window or spec.seq_len,
        "device_loop": args.device_loop,
        "steps": args.steps,
        "fused": not args.no_fuse,  # the merged wqkv / w13 matvec groups
        "kernel_policy": str(state["use_pallas"]),
        "kernels": sorted(set(kernel_selections().values())),
    }
    if args.profile_dir:
        # a profiler-instrumented run is NOT comparable to the clean headline —
        # mark it so metric-keyed JSONL consumers cannot silently pick it up
        out["profiled"] = True
    emit(out)


if __name__ == "__main__":
    main()
