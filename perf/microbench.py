#!/usr/bin/env python
"""Kernel & platform microbenchmarks — the evidence base for bench.py's numbers.

Measures, on whatever backend JAX resolves (designed for the single TPU chip):
  1. dispatch       — per-dispatch overhead of the host->device link (sync round trip
                      and async chained), which bounds the per-token host-loop cost
  2. stream         — steady-state HBM read bandwidth via a scan over stacked weights
                      (single-op timings are meaningless when dispatch overhead is
                      milliseconds; the scan amortizes it away)
  3. matvec:q4/q8   — the two decode matvec kernels (ops/pallas_q4.py packed nibbles at
                      0.5625 B/weight vs ops/pallas_q8.py int8 planes at 1.125 B/weight)
                      on the Llama-2-7B hot shapes, reported as achieved GB/s
  4. prefill_mm     — fused 4-bit dequant-matmul (ops/pallas_q4_mm.py) vs the XLA
                      dequant+dot path at prefill widths (weight GB/s)
  5. attention      — windowed vs full-seq_len cache read cost at 7B head geometry

Each result prints as one JSON line. Timed regions end in block_until_ready().

Usage: python perf/microbench.py [--section dispatch|stream|matvec|prefill_mm|
                                  attention|collectives] [--quick]
"""

import argparse
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from distributed_llama_tpu.quants import QK, FloatType, QTensor  # noqa: E402


def fence(x):
    jax.block_until_ready(x)


def timed(fn, *args, reps=10):
    fence(fn(*args))  # compile + warm
    t0 = time.perf_counter()
    out = None
    for _ in range(reps):
        out = fn(*args)
    fence(out)
    return (time.perf_counter() - t0) / reps


def emit(**kw):
    print(json.dumps(kw))


def sec_dispatch(reps):
    f = jax.jit(lambda x: x + 1)
    x = jnp.zeros((8,), jnp.float32)
    fence(f(x))
    t0 = time.perf_counter()
    for _ in range(reps):
        fence(f(x))
    emit(section="dispatch", kind="sync_roundtrip",
         ms=round((time.perf_counter() - t0) / reps * 1e3, 3))
    y = x
    t0 = time.perf_counter()
    for _ in range(reps):
        y = f(y)
    fence(y)
    emit(section="dispatch", kind="async_chained",
         ms=round((time.perf_counter() - t0) / reps * 1e3, 3))


def sec_stream(reps):
    """Steady-state HBM read bandwidth, two probes per dtype family:

    - matvec probes (bf16/int8 dot per scanned layer): what a DECODE layer attains,
      including the dot's lowering cost. Round 3 published the int8 number (87-173
      GB/s) as if it were bandwidth — it is not: XLA's int8 matvec lowering is
      compute-bound, which this section now makes explicit by...
    - raw probes (bitcast to i32 lanes, reduce): pure read bandwidth with a trivial
      VPU reduction — the actual streaming ceiling for that operand size.
    """
    L, n, k = 32, 11008, 4096
    for dt_, name, bpe in ((jnp.bfloat16, "bf16_matvec", 2), (jnp.int8, "int8_matvec", 1)):
        w = jnp.ones((L, n, k), dt_)
        x = jnp.ones((k,), jnp.bfloat16)

        def body(c, wl):
            if dt_ == jnp.int8:
                y = jax.lax.dot_general(wl, c.astype(jnp.int8)[:, None],
                                        (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.int32)
                return c, y.astype(jnp.bfloat16).sum()
            return c, (wl @ c).sum()

        g = jax.jit(lambda w, x: jax.lax.scan(body, x, w)[1].sum())
        dt = timed(g, w, x, reps=reps)
        gb = L * n * k * bpe / 1e9
        emit(section="stream", dtype=name, gb=round(gb, 2), ms=round(dt * 1e3, 2),
             gbps=round(gb / dt, 1))
    for src, name in ((jnp.bfloat16, "bf16_raw"), (jnp.int8, "int8_raw"),
                      (jnp.uint8, "uint8_raw")):
        lanes = 4 // jnp.dtype(src).itemsize
        w = jnp.ones((L, n, k), src)

        def body_raw(c, wl, lanes=lanes):
            as_i32 = jax.lax.bitcast_convert_type(
                wl.reshape(n, k // lanes, lanes), jnp.int32)
            return c + jnp.sum(as_i32, dtype=jnp.int32).astype(jnp.float32), None

        g = jax.jit(lambda w: jax.lax.scan(body_raw, jnp.float32(0), w)[0])
        dt = timed(g, w, reps=reps)
        gb = w.nbytes / 1e9
        emit(section="stream", dtype=name, gb=round(gb, 2), ms=round(dt * 1e3, 2),
             gbps=round(gb / dt, 1))


def _rand_q40(n, k, seed=0):
    rng = np.random.RandomState(seed)
    return QTensor.from_float((rng.randn(n, k) * 0.05).astype(np.float32),
                              FloatType.Q40)


def sec_matvec(reps):
    """q4 vs q8 decode kernels on the 7B hot shapes (single dispatch per call;
    the async chain in timed() amortizes dispatch overhead)."""
    on_tpu = jax.default_backend() == "tpu"
    shapes = [(4096, 4096), (11008, 4096), (4096, 11008), (32000, 4096)]
    for n, k in shapes:
        w = _rand_q40(min(n, 4096) if not on_tpu else n, k)
        w_i4p = jax.tree_util.tree_map(jnp.asarray, w.to_i4p_layout())
        for layout in ("i4p", "i4p-inline", "i8"):
            wl = (jax.tree_util.tree_map(jnp.asarray, w.to_i8_layout())
                  if layout == "i8" else w_i4p)
            x = jnp.ones((1, 1, k), jnp.bfloat16)
            if layout == "i8":
                from distributed_llama_tpu.ops.pallas_q8 import q8_matvec as mv

                g = jax.jit(functools.partial(mv, interpret=not on_tpu))
            else:
                from distributed_llama_tpu.ops.pallas_q4 import q4_matvec

                g = jax.jit(functools.partial(
                    q4_matvec, interpret=not on_tpu,
                    inline_xexp=layout == "i4p-inline"))
            dt = timed(g, x, wl, reps=reps)
            bytes_ = wl.data.nbytes + wl.scales.nbytes
            emit(section="matvec", layout=layout, n=wl.shape[0], k=k,
                 ms=round(dt * 1e3, 3), gbps=round(bytes_ / 1e9 / dt, 1))


def sec_prefill_mm(reps):
    """Fused 4-bit dequant-matmul (ops/pallas_q4_mm.py) vs the XLA dequant+dot
    path on the 7B hot shapes at prefill widths — isolates whether XLA
    materializes the bf16 operands (the prefill cost model's open question)
    and what the kernel's effective weight GB/s is."""
    from distributed_llama_tpu.ops.matmul import qmatmul
    from distributed_llama_tpu.ops.pallas_q4_mm import q4_matmul, q4_mm_supported

    on_tpu = jax.default_backend() == "tpu"
    shapes = [(4096, 4096), (11008, 4096), (4096, 11008)]
    for n, k in shapes:
        w = _rand_q40(min(n, 2048) if not on_tpu else n, k)
        wl = jax.tree_util.tree_map(jnp.asarray, w.to_i4p_layout())
        for m in (16, 64, 128):
            x = jnp.ones((m, k), jnp.bfloat16)
            bytes_ = wl.data.nbytes + wl.scales.nbytes
            if q4_mm_supported(wl, m):
                g = jax.jit(functools.partial(q4_matmul, interpret=not on_tpu))
                dt = timed(g, x, wl, reps=reps)
                emit(section="prefill_mm", path="kernel", m=m, n=wl.shape[0],
                     k=k, ms=round(dt * 1e3, 3),
                     weight_gbps=round(bytes_ / 1e9 / dt, 1))
            g = jax.jit(functools.partial(qmatmul, use_pallas=False))
            dt = timed(g, x, wl, reps=reps)
            emit(section="prefill_mm", path="xla_dequant", m=m, n=wl.shape[0],
                 k=k, ms=round(dt * 1e3, 3),
                 weight_gbps=round(bytes_ / 1e9 / dt, 1))


def sec_attention(reps):
    """Cache read cost: full 2048-window vs 256-window at 7B geometry, per layer."""
    from distributed_llama_tpu.ops.attention import gqa_attention

    b, hq, hk, hs = 1, 32, 32, 128
    q = jnp.ones((b, 1, hq, hs), jnp.bfloat16)
    for s in (2048, 256):
        kc = jnp.ones((b, hk, s, hs), jnp.bfloat16)
        vc = jnp.ones_like(kc)
        pos = jnp.asarray([100 % s], jnp.int32)
        g = jax.jit(lambda q, kc, vc, p: gqa_attention(q, kc, vc, p))
        dt = timed(g, q, kc, vc, pos, reps=reps)
        gb = 2 * kc.nbytes / 1e9
        emit(section="attention", window=s, ms=round(dt * 1e3, 3),
             cache_gb=round(gb, 3), gbps=round(gb / dt, 1))


def sec_collectives(reps):
    """quantized_psum (Q80-compressed all-reduce, the reference's wire compression
    tasks.cpp:96-135) vs plain psum: numerics always; time only as a relative number
    on whatever mesh is available. One real chip has no ICI, so run this section
    under the virtual CPU mesh (JAX_PLATFORMS=cpu
    XLA_FLAGS=--xla_force_host_platform_device_count=8) for an 8-way ring; the
    wall-clock there measures the EXTRA COMPUTE of quantize/dequantize, not wire
    time — labeled mesh="cpu" so nobody mistakes it for an ICI measurement."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_llama_tpu.parallel.collectives import psum, quantized_psum
    from distributed_llama_tpu.parallel.mesh import AXIS_TP, make_mesh

    n_dev = len(jax.devices())
    if n_dev < 2:
        emit(section="collectives", skipped=f"need >=2 devices, have {n_dev}",
             note="run under the 8-device virtual CPU mesh for numerics/compute cost")
        return
    mesh = make_mesh(tp=n_dev)
    dim = 4096
    rng = np.random.RandomState(0)
    parts = rng.randn(n_dev, dim).astype(np.float32) * 0.1
    x = jax.device_put(jnp.asarray(parts), NamedSharding(mesh, P(AXIS_TP)))
    want = parts.sum(0)

    for name, fn in (("psum", psum), ("quantized_psum",
                                      lambda v, ax: quantized_psum(v, ax))):
        g = jax.jit(jax.shard_map(lambda v: fn(v, AXIS_TP), mesh=mesh,
                                  in_specs=P(AXIS_TP), out_specs=P(AXIS_TP)))
        out = np.asarray(jax.device_get(g(x).addressable_shards[0].data))[0]
        rel = float(np.abs(out - want).max() / (np.abs(want).max() + 1e-9))
        dt = timed(g, x, reps=reps)
        emit(section="collectives", op=name, mesh=jax.default_backend(),
             n_dev=n_dev, dim=dim, rel_err=round(rel, 6), ms=round(dt * 1e3, 3))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--section", default=None,
                    choices=["dispatch", "stream", "matvec", "prefill_mm",
                             "attention", "collectives"])
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    reps = 3 if args.quick else 10
    emit(section="meta", backend=jax.default_backend(),
         device=str(jax.devices()[0]))
    secs = {"dispatch": sec_dispatch, "stream": sec_stream, "matvec": sec_matvec,
            "prefill_mm": sec_prefill_mm,
            "attention": sec_attention, "collectives": sec_collectives}
    for name, fn in secs.items():
        if args.section in (None, name):
            fn(reps)


if __name__ == "__main__":
    main()
