#!/usr/bin/env python
"""Microbench: what the always-on observability costs one scheduler pass
while nothing listens (no tracer installed, no profiler session, no flight
recorder), against a decode dispatch. Run by hand; no test runs it.

One pass of the BatchEngine scheduler (runtime/batch_engine.py) pays exactly:

    7 trace.span() with no tracer installed, each a bare
      jax.profiler.TraceAnnotation: batch.admit (+ add of two args),
      batch.advance, batch.build, the dispatch span (four args) with its
      children batch.launch and batch.fetch (one arg), batch.deliver
    their inline args dicts
    2 time.perf_counter() calls
    2 Histogram.observe() (bisect + lock + 3 adds): the dispatch's wall
      time and batch_dispatch_gap_seconds
    5 Counter.inc(): tokens, and the four useful-work counters
    1 disabled flight.event() (global check; kwargs dict built at call site)
    1 reqctx.use() enter/exit (contextvar set + reset — the scheduler's
      per-request trace re-entry)
    1 constrain-disabled scan (ISSUE 17: every masked-capable dispatch asks
      "is any co-batched row constrained?" — B attribute loads returning
      None — before picking the unmasked program)

This script times that exact bundle standalone, times a real T=1 decode
dispatch of the tiny CI model shape on the current backend, and asserts
bundle < 1% of dispatch. Prints one JSON line (bench.py convention).

Run: JAX_PLATFORMS=cpu python perf/obs_overhead.py
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from distributed_llama_tpu.models.params import init_random_params
from distributed_llama_tpu.models.spec import ArchType, ModelSpec
from distributed_llama_tpu.obs import flight, metrics, reqctx, trace
from distributed_llama_tpu.parallel.mesh import make_mesh
from distributed_llama_tpu.parallel.tp import (init_sharded_kv_cache,
                                               make_sharded_forward,
                                               shard_params)
from distributed_llama_tpu.ops.rope import RopeTables
from distributed_llama_tpu.quants import FloatType

SMALL = dict(arch_type=ArchType.LLAMA, dim=512, hidden_dim=1408, n_layers=4,
             n_heads=8, n_kv_heads=8, vocab_size=32000, seq_len=256)


def bench_instrumentation_bundle(n: int = 100_000) -> float:
    """Seconds per scheduler pass's bundle with nothing listening (the
    module docstring lists it) — the marginal cost one dispatch pays."""
    trace.uninstall()
    flight.uninstall()
    hist = metrics.histogram("obs_overhead_bench_seconds", "bench-only")
    ctrs = [metrics.counter(f"obs_overhead_bench_{i}_total", "bench-only")
            for i in range(5)]
    ctx = reqctx.new_context("req-bench")

    class _Slot:  # the constrain-disabled scan: B rows, constraint None
        __slots__ = ("constraint",)

        def __init__(self):
            self.constraint = None

    slots = [_Slot() for _ in range(8)]
    t_start = time.perf_counter()
    for i in range(n):
        with trace.span("batch.admit") as sp:
            sp.add(admitted=0, queued=0)
        with trace.span("batch.advance", {"rows": 7}):
            pass
        with trace.span("batch.build"):
            pass
        with reqctx.use(ctx):
            with trace.span("batch.mixed_step", {"chunk": 64, "riders": 7,
                                                 "window": 512, "slots": 8}):
                with trace.span("batch.launch"):
                    pass
                with trace.span("batch.fetch", {"bytes": i}):
                    pass
        with trace.span("batch.deliver"):
            t0 = time.perf_counter()
            dt = time.perf_counter() - t0
            hist.observe(dt)
            hist.observe(dt)
            for c in ctrs:
                c.inc()
            flight.event("req-bench", "super_step", k=8, delivered=8)
            masked = False
            for s in slots:  # batch_engine._constrained(rows)
                sc = s.constraint
                if sc is not None and not sc.degraded:
                    masked = True
                    break
            assert not masked
    return (time.perf_counter() - t_start) / n


def bench_decode_dispatch(steps: int = 32) -> float:
    """Seconds per T=1 decode dispatch of the tiny CI shape (compiled once,
    host-fenced like the engine's hot loop)."""
    spec = ModelSpec(**SMALL).resolved()
    mesh = make_mesh(tp=1)
    params = shard_params(init_random_params(spec, FloatType.F32, seed=7),
                          mesh, spec)
    rope = RopeTables.create(spec)
    kc, vc = init_sharded_kv_cache(spec, mesh, batch=1, dtype=jnp.float32)
    step = make_sharded_forward(spec, mesh, params, dtype=jnp.float32,
                                use_pallas=False, donate_cache=True)
    tok = jnp.asarray([[1]], jnp.int32)
    for i in range(3):  # compile + warm
        logits, kc, vc = step(params, rope, tok, kc, vc, jnp.int32(i))
    np.asarray(logits[0, 0, 0])
    t0 = time.perf_counter()
    for i in range(steps):
        logits, kc, vc = step(params, rope, tok, kc, vc, jnp.int32(3 + i))
        np.asarray(logits[0, 0, 0])  # per-dispatch fence, like Engine._infer
    return (time.perf_counter() - t0) / steps


def main() -> int:
    bundle_s = bench_instrumentation_bundle()
    dispatch_s = bench_decode_dispatch()
    ratio = bundle_s / dispatch_s
    ok = ratio < 0.01
    print(json.dumps({
        "metric": "obs_disabled_overhead_ratio",
        "value": round(ratio, 6), "unit": "fraction",
        "pass": ok, "threshold": 0.01,
        "bundle_us": round(bundle_s * 1e6, 3),
        "dispatch_ms": round(dispatch_s * 1e3, 3),
        "backend": jax.default_backend(),
    }))
    if not ok:
        print(f"FAIL: the idle bundle {bundle_s * 1e6:.2f} µs is "
              f"{ratio:.2%} of a {dispatch_s * 1e3:.2f} ms decode dispatch "
              "(budget 1%)", file=sys.stderr)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
