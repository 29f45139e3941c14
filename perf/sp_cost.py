#!/usr/bin/env python
"""Sequence-parallel (--sp ring) step-cost measurement.

Runs the sharded decode step on the 8-device virtual CPU mesh (JAX_PLATFORMS=cpu
+ xla_force_host_platform_device_count=8 — set by this script) and compares:

    sp=1 tp=2            — baseline TP-only step
    sp=2|4 tp=2          — the striped ring over the whole sharded cache
    sp=2|4 tp=2, window  — the ring bounded to a window bucket's slots

CPU-mesh times are NOT hardware numbers (no ICI; ppermute is a memcpy): they
rank the configurations, no more. Emits one JSON line per config.

    python perf/sp_cost.py [--dim 512] [--layers 8] [--seq 1024] [--steps 20]
"""

import argparse
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax.numpy as jnp
import numpy as np

from distributed_llama_tpu.models.params import init_random_params
from distributed_llama_tpu.models.spec import ArchType, ModelSpec, RopeType
from distributed_llama_tpu.ops.rope import RopeTables
from distributed_llama_tpu.parallel.mesh import make_mesh
from distributed_llama_tpu.parallel.tp import (init_sharded_kv_cache,
                                               make_sharded_forward, shard_params)
from distributed_llama_tpu.quants import FloatType


def run_config(spec, params, rope, *, sp, tp, steps, pos0, window=None):
    mesh = make_mesh(sp=sp, tp=tp)
    sparams = shard_params(params, mesh, spec)
    step = make_sharded_forward(spec, mesh, sparams, donate_cache=True,
                                attn_window=window)
    kc, vc = init_sharded_kv_cache(spec, mesh)
    tok = jnp.asarray([[1]], jnp.int32)
    # warm/compile + advance to pos0 so the ring walks a realistic live region
    logits, kc, vc = step(sparams, rope, tok, kc, vc, jnp.int32(0))
    np.asarray(logits[0, 0, 0])
    for i in range(3):
        logits, kc, vc = step(sparams, rope, tok, kc, vc, jnp.int32(1 + i))
    np.asarray(logits[0, 0, 0])

    t0 = time.perf_counter()
    pos = pos0
    for _ in range(steps):
        logits, kc, vc = step(sparams, rope, tok, kc, vc, jnp.int32(pos))
        pos += 1
    np.asarray(logits[0, 0, 0])
    dt_ms = (time.perf_counter() - t0) / steps * 1e3
    return dt_ms


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dim", type=int, default=512)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--steps", type=int, default=20)
    args = ap.parse_args()

    spec = ModelSpec(arch_type=ArchType.LLAMA, dim=args.dim,
                     hidden_dim=args.dim * 11 // 4 // 32 * 32,
                     n_layers=args.layers, n_heads=args.dim // 64,
                     n_kv_heads=args.dim // 64, vocab_size=2048,
                     seq_len=args.seq, rope_type=RopeType.LLAMA).resolved()
    params = init_random_params(spec, FloatType.F32, seed=0)
    rope = RopeTables.create(spec)
    # quarter-context: live region fits the seq//2 window bucket of the windowed
    # configs (contract: start_pos + steps <= window) while the full-cache
    # configs still walk 4x the live columns
    pos0 = args.seq // 4

    configs = [
        dict(sp=1, tp=2),
        dict(sp=2, tp=2),
        dict(sp=4, tp=2),
        # windowed striped ring: rotations move ceil(window/sp) slots
        # instead of the full shard
        dict(sp=2, tp=2, window=args.seq // 2),
        dict(sp=4, tp=2, window=args.seq // 2),
    ]
    for cfg in configs:
        ms = run_config(spec, params, rope, steps=args.steps, pos0=pos0, **cfg)
        print(json.dumps({"section": "sp_cost", "mesh": "cpu8",
                          "dim": args.dim, "layers": args.layers,
                          "seq": args.seq, "pos": pos0, **cfg,
                          "ms_per_step": round(ms, 2)}), flush=True)


if __name__ == "__main__":
    main()
