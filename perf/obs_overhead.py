#!/usr/bin/env python
"""Microbench: the DISABLED observability hot path must cost <1% of a decode
dispatch (ISSUE 2 acceptance gate for always-on instrumentation; ISSUE 7
extends the bundle with the request-tracing hooks).

The per-dispatch instrumentation on runtime/engine.py / batch_engine.py is
exactly:

    1 disabled trace.span() (global check + shared no-op context manager)
    1 inline args dict build
    2 time.perf_counter() calls
    1 Histogram.observe() (bisect + lock + 3 adds)
    1 Counter.inc()
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


def bench_instrumentation_bundle(n: int = 200_000) -> float:
    """Seconds per disabled-path bundle (span + dict + 2 clocks + observe +
    inc + disabled flight event + trace-context re-entry) — the marginal
    cost one decode dispatch now pays."""
    trace.uninstall()
    flight.uninstall()
    hist = metrics.histogram("obs_overhead_bench_seconds", "bench-only")
    ctr = metrics.counter("obs_overhead_bench_total", "bench-only")
    ctx = reqctx.new_context("req-bench")

    class _Slot:  # the constrain-disabled scan: B rows, constraint None
        __slots__ = ("constraint",)

        def __init__(self):
            self.constraint = None

    slots = [_Slot() for _ in range(8)]
    t_start = time.perf_counter()
    for i in range(n):
        with reqctx.use(ctx):
            with trace.span("engine.dispatch", {"t": 1, "pos": i}):
                pass
            t0 = time.perf_counter()
            dt = time.perf_counter() - t0
            hist.observe(dt)
            ctr.inc()
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
        print(f"FAIL: disabled-path bundle {bundle_s * 1e6:.2f} µs is "
              f"{ratio:.2%} of a {dispatch_s * 1e3:.2f} ms decode dispatch "
              "(budget 1%)", file=sys.stderr)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
