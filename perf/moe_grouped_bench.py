"""The grouped expert kernels alone, on the chip: ms a call against the call's
bytes over 819 GB/s, at the cells' dispatch shapes.

    python3 perf/moe_grouped_bench.py [--reps 20]

One layer's call of `ops/pallas_moe_grouped.moe_grouped_q4` (gate/up with
the activation fused, then down) on seeded Q40 stacks and uniformly routed
rows: 8 slots x T of 1, 8 and 64 for 6 of 64 experts of 768 (hidden 2560) and
2 of 8 experts of 14336 (hidden 4096), and the 16 and 72 rows (cases `t2`, `t9`)
that a prefill chunk of 8 and of 64 tokens computes since PR 41 (its compact
stream, `models/forward.compact_rows`). Beside it the XLA form of the same
tiles (`ops/moe_grouped._grouped_xla`, the stacks handed in as arguments: as
constants of the jitted function XLA folds their dequantization away), whose
result the kernel's is compared with. One JSON line a case.

    python3 perf/moe_grouped_bench.py --layer 1

times the whole expert layer (`models/forward._moe_ffn`: routing, the experts,
the weighted sum) both ways at one shape, the grouped layer against the
all-experts scan: what `models/forward.takes_the_scan` chooses between.

    python3 perf/moe_grouped_bench.py --scan 1

times the kernels' call INSIDE a `lax.scan` over the layers of the cell's
whole stack (24 layers of 64 experts, 8 of 8), the way a step program runs
it, from the stack (the layer a prefetched index, `LayerOf`) and from the
scan's slice of it: ms a layer for both, and the bytes each moves. The
slice is a copy of every expert of the layer, touched or not, which a call
timed alone never pays: why one layer alone flatters whatever is handed its
weights ready-made (ROADMAP S4b).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from distributed_llama_tpu.ops import moe_grouped as G  # noqa: E402
from distributed_llama_tpu.ops.matmul import LayerOf  # noqa: E402
from distributed_llama_tpu.ops.pallas_moe_grouped import (  # noqa: E402
    moe_grouped_q4)
from distributed_llama_tpu.quants import (FloatType, QTensor,  # noqa: E402
                                          to_scale_plane)

HBM = 819e9
SHAPES = {"e64": (64, 6, 768, 2560, "relu"), "e8": (8, 2, 14336, 4096, "silu")}
LAYERS = {"e64": 24, "e8": 8}  # the cells' depths


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def stack(key, lead, out, k):
    """A seeded (*lead, out, K) Q40 stack (lead: experts, or layers and
    experts) in the split-plane layout, drawn on the device (nibble 0
    remapped to 8, scales near 0.02 / 4.3)."""
    kb, ks = jax.random.split(key)
    data = jax.random.bits(kb, (*lead, out, k // 2), jnp.uint8)
    data = data | (((data & 0x0F) == 0).astype(jnp.uint8) << 3)
    data = data | (((data & 0xF0) == 0).astype(jnp.uint8) << 7)
    scales = ((jax.random.uniform(ks, (*lead, out, k // 32)) + 0.5) * 0.02 / 4.3
              ).astype(jnp.float16)
    return QTensor(FloatType.Q40, data, to_scale_plane(
        jax.lax.bitcast_convert_type(scales, jnp.int16)), layout="i4p")


def timed(fn, reps):
    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e3


# rows are 8 x T: T of 2 and 9 stand for a chunk's 16 and 72 compact rows
KERNEL_T = (1, 2, 8, 9, 64)
LAYER_T = {"e64": (1, 2, 8, 9, 64), "e8": (1, 2, 4, 8, 9, 64)}


def layer_arms(reps):
    """`_moe_ffn` through the grouped layer and through the scan, ms a call."""
    from distributed_llama_tpu.models import forward as F
    from distributed_llama_tpu.models.spec import (ArchType, HiddenAct,
                                                   ModelSpec)

    for name, (e, k, width, d, act) in SHAPES.items():
        spec = ModelSpec(arch_type=ArchType.MIXTRAL, dim=d, hidden_dim=width,
                         n_layers=1, n_heads=d // 128, n_kv_heads=d // 128,
                         vocab_size=64, seq_len=64, n_experts=e,
                         n_active_experts=k,
                         hidden_act=HiddenAct[act.upper()]).resolved()
        key = jax.random.key(7)
        bp = {"moe_gu": stack(jax.random.fold_in(key, 0), (e,), 2 * width, d),
              "moe_down": stack(jax.random.fold_in(key, 1), (e,), d, width)}
        for t in LAYER_T[name]:
            x = jax.random.normal(jax.random.fold_in(key, t), (8, t, d),
                                  jnp.bfloat16)
            logits = jax.random.normal(jax.random.fold_in(key, 100 + t),
                                       (8, t, e), jnp.float32)
            line = {"case": f"{name}-t{t}", "rows": 8 * t,
                    "mean_run": 8 * t * k / e}
            outs = {}
            for arm, scan in (("grouped", False), ("scan", True)):
                F.takes_the_scan = lambda *a, scan=scan: scan
                fn = jax.jit(lambda x, bp, lg: F._moe_ffn(
                    x, bp, spec, None, True, False, router_logits=lg)[0])
                outs[arm] = np.asarray(fn(x, bp, logits), np.float32)
                line[f"{arm}_ms"] = round(
                    timed(lambda: fn(x, bp, logits), reps), 4)
            line["max_abs_diff"] = float(
                np.max(np.abs(outs["grouped"] - outs["scan"])))
            line["rms"] = float(np.sqrt(np.mean(outs["scan"] ** 2)))
            print(json.dumps(line), flush=True)


def routed(key, t, e, k, width, d):
    """8 x t uniformly routed rows laid into their tiles: (rows, plan, tile,
    experts touched, the bytes one call has to move: the touched experts'
    Q40 and the real rows' activations)."""
    n = 8 * t
    rng = np.random.default_rng(t)
    top_i = jnp.asarray(np.stack(
        [rng.choice(e, k, replace=False) for _ in range(n)]))
    x = jax.random.normal(jax.random.fold_in(key, t), (n, d), jnp.bfloat16)
    tile = G.row_tile(n * k, e)
    p = jax.jit(lambda ti: G.plan(ti, e, 0, tile))(top_i)
    rows = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)])[p["src"]]
    touched = int(jnp.sum(p["counts"] > 0))
    call_bytes = (touched * 3 * width * d * 0.5625
                  + n * k * 2 * (d + width) * 2)
    return rows, p, tile, touched, call_bytes


def scan_arms(reps):
    """The kernels' call a layer inside a scan over the whole stack's
    layers, reading the stack in place against reading the scan's slice."""
    for name, (e, k, width, d, act) in SHAPES.items():
        layers = LAYERS[name]
        key = jax.random.key(7)
        gu = stack(jax.random.fold_in(key, 0), (layers, e), 2 * width, d)
        down = stack(jax.random.fold_in(key, 1), (layers, e), d, width)
        layer_bytes = e * 3 * width * d * 0.5625  # all of a layer's experts
        for t in (1, 8, 64):
            rows, p, tile, touched, call_bytes = routed(key, t, e, k, width, d)

            def ffn(up, dn):
                return moe_grouped_q4(rows, p["tile_expert"], p["n_used"], up,
                                      up, dn, tile=tile, act=act,
                                      interpret=False).astype(jnp.float32)

            @jax.jit
            def from_stack(gu, down):
                return jax.lax.scan(
                    lambda acc, l: (acc + ffn(LayerOf(gu, (l,)),
                                              LayerOf(down, (l,))), None),
                    jnp.zeros(rows.shape, jnp.float32),
                    jnp.arange(layers, dtype=jnp.int32))[0]

            @jax.jit
            def from_slice(gu, down):
                return jax.lax.scan(
                    lambda acc, w: (acc + ffn(*w), None),
                    jnp.zeros(rows.shape, jnp.float32), (gu, down))[0]

            used = int(p["n_used"]) * tile
            got = np.asarray(from_stack(gu, down)[:used])
            want = np.asarray(from_slice(gu, down)[:used])
            line = {"case": f"{name}-l{layers}-t{t}", "rows": 8 * t,
                    "touched": touched, "equal": bool(np.array_equal(got, want)),
                    "stack_ms_a_layer": round(timed(
                        lambda: from_stack(gu, down), reps) / layers, 4),
                    "slice_ms_a_layer": round(timed(
                        lambda: from_slice(gu, down), reps) / layers, 4),
                    "stack_mb_a_layer": round(call_bytes / 1e6, 1),
                    "slice_mb_a_layer": round(
                        (call_bytes + 2 * layer_bytes) / 1e6, 1)}
            print(json.dumps(line), flush=True)
        del gu, down


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--layer", type=int, default=0)
    ap.add_argument("--scan", type=int, default=0)
    args = ap.parse_args()
    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
    if args.layer:
        return layer_arms(args.reps)
    if args.scan:
        return scan_arms(args.reps)
    for name, (e, k, width, d, act) in SHAPES.items():
        key = jax.random.key(7)
        gu = stack(jax.random.fold_in(key, 0), (e,), 2 * width, d)
        down = stack(jax.random.fold_in(key, 1), (e,), d, width)
        for t in KERNEL_T:
            n = 8 * t
            rows, p, tile, touched, call_bytes = routed(key, t, e, k, width, d)
            floor_ms = call_bytes / HBM * 1e3

            def kernel():
                return moe_grouped_q4(rows, p["tile_expert"], p["n_used"], gu,
                                      gu, down, tile=tile, act=act,
                                      interpret=False)

            xla_fn = jax.jit(lambda r, pl, g, dn: G._grouped_xla(
                r, pl, g, g, dn, tile, G.ACTS[act], True))

            def xla(r):
                return xla_fn(r, p, gu, down)
            used = int(p["n_used"]) * tile
            got = np.asarray(kernel()[:used], np.float32)
            want = np.asarray(xla(rows)[:used], np.float32)
            line = {"case": f"{name}-t{t}", "rows": n, "tile": tile,
                    "tiles_used": int(p["n_used"]), "touched": touched,
                    "floor_ms": round(floor_ms, 4),
                    "kernel_ms": round(timed(kernel, args.reps), 4),
                    "xla_ms": round(timed(lambda: xla(rows), args.reps), 4),
                    "max_abs_diff": float(np.max(np.abs(got - want))),
                    "rms": float(np.sqrt(np.mean(want ** 2)))}
            line["share_of_floor"] = round(floor_ms / line["kernel_ms"], 4)
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
