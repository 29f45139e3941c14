"""The prefix cache's demotion read alone on the chip: a reclaim's victims off the pool.

    python3 perf/demote_bench.py [--iters 15] [--busy-ms 15] [--json FILE]

Under pool pressure the directory demotes LRU blocks to the host tier
(docs/PAGED_KV.md "Eviction"), and the scheduler does it between one
dispatch's results and the next dispatch's launch, with the device idle. This
times what that costs the scheduler's thread for a reclaim of 1, 4 and 8
blocks at each configuration's pool shape, two ways:

- `per block`: the path until ISSUE 39, kept here as the reference: two eager
  slices and two synchronous device-to-host copies a block,
  `np.asarray(k[:, bid]), np.asarray(v[:, bid])`, one block after another;
- `gather`: the engine's own `DemoteRead` (runtime/slot_cache.py): ONE
  jitted gather of the n blocks from both sides (n padded to 1, 2, 4 or 8; an empty side is not read), its
  host copy started and not waited for. `issue` is what the scheduler pays
  before it launches the next dispatch; `settle now` is the wait if the rows
  were asked for at once (nothing overlapped: the read's whole latency);
  `settle behind` is the wait when they are asked for after a dispatch of
  `--busy-ms` of device work was launched behind the gather and the host sat
  out that time, as the scheduler does between a dispatch's launch and its
  fetch: what is left of the transfer by then. The last two columns say
  whether the transfer holds the DEVICE back: the same device work from
  launch to done, alone and launched right behind a gather.

The rows of both paths are compared bit for bit. Without a TPU
(`JAX_PLATFORMS=cpu`) nothing is timed: it prints the gather's programs, one
a size, and their count.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])

RECLAIMS = (1, 4, 8)


def pools() -> dict:
    """(L, N, hk, bt, w) of every configuration's pool with the second
    side's w beside it (0: a latent row has no second side), from
    BENCHMARK.json's configurations as their engines would build them."""
    from benchmark import cells

    out = {}
    for entry in cells.benchmark_json()["configs"]:
        cfg = cells.load_config(entry["name"])
        spec = cells.load_family(cfg["family"]).model_spec(cfg)
        eng, (w1, w2) = cfg["engine"], spec.cache_widths
        out[entry["name"]] = ((spec.n_layers, eng["kv_pool_blocks"],
                               spec.n_kv_heads, eng["kv_block_tokens"], w1),
                              w2)
    return out


def make_pool(shape, w2, seed):
    import jax
    import jax.numpy as jnp

    kk, kv = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(kk, shape, jnp.bfloat16),
            jax.random.normal(kv, shape[:-1] + (w2,), jnp.bfloat16))


def per_block(pool, bids):
    """The reference: the synchronous read a block, as the engine made it."""
    k, v = pool
    return [(np.asarray(k[:, b]), np.asarray(v[:, b])) for b in bids]


def programs(pool) -> list[str]:
    """The gather's lowered signature at every size a reclaim issues."""
    import jax

    from distributed_llama_tpu.runtime.slot_cache import (DEMOTE_SIZES,
                                                          pool_gather)

    sides = tuple(c for c in pool if c.shape[-1])
    out = []
    for n in DEMOTE_SIZES:
        ids = jax.ShapeDtypeStruct((n,), np.int32)
        got = jax.eval_shape(pool_gather, sides, ids)
        out.append(f"pool_gather[{n}]: "
                   + ", ".join(f"{a.dtype}{list(a.shape)}" for a in sides)
                   + " -> " + ", ".join(f"{a.dtype}{list(a.shape)}"
                                        for a in got))
    return out


def bench(name, shape, w2, iters, busy_ms, rng):
    import jax
    import jax.numpy as jnp

    from distributed_llama_tpu.runtime.slot_cache import DemoteRead

    pool = make_pool(shape, w2, 39)
    jax.block_until_ready(pool)
    block_bytes = sum(c.nbytes // c.shape[1] for c in pool)
    # the dispatch launched behind the gather: matmuls sized to busy_ms
    a = jnp.ones((2048, 2048), jnp.bfloat16)

    @jax.jit
    def busy(x, reps):
        return jax.lax.fori_loop(0, reps, lambda _, y: (y @ x) * 1e-3, x)

    jax.block_until_ready(busy(a, 8))
    t = time.perf_counter()
    jax.block_until_ready(busy(a, 64))
    reps = max(int(64 * busy_ms / ((time.perf_counter() - t) * 1e3)), 1)
    rows = []
    for n in RECLAIMS:
        cols = {"per block": [], "issue": [], "settle now": [],
                "issue b": [], "settle behind": [], "work": [],
                "work behind": []}
        for it in range(iters + 2):  # two warm-ups: the programs compile
            bids = rng.choice(np.arange(1, shape[1]), n, replace=False).tolist()
            t0 = time.perf_counter()
            want = per_block(pool, bids)
            t1 = time.perf_counter()
            read = DemoteRead(pool)
            got = [read.block(b) for b in bids]
            read.issue(pool)
            t2 = time.perf_counter()
            got = [g.settle() for g in got]
            t3 = time.perf_counter()
            for (gk, gv), (wk, wv) in zip(got, want):
                assert np.array_equal(gk, wk) and np.array_equal(gv, wv)
                assert gk.shape == wk.shape and gv.shape == wv.shape
            bids = rng.choice(np.arange(1, shape[1]), n, replace=False).tolist()
            t4 = time.perf_counter()
            read = DemoteRead(pool)
            got = [read.block(b) for b in bids]
            read.issue(pool)
            t5 = time.perf_counter()
            work = busy(a, reps)
            time.sleep(busy_ms / 1e3)
            t6 = time.perf_counter()
            got = [g.settle() for g in got]
            t7 = time.perf_counter()
            jax.block_until_ready(work)
            # does the transfer hold the device work back? the same work
            # alone, and launched right behind a gather: launch to done
            t8 = time.perf_counter()
            jax.block_until_ready(busy(a, reps))
            t9 = time.perf_counter()
            read = DemoteRead(pool)
            got = [read.block(b) for b in bids]
            read.issue(pool)
            t10 = time.perf_counter()
            jax.block_until_ready(busy(a, reps))
            t11 = time.perf_counter()
            [g.settle() for g in got]
            if it >= 2:
                for key, dt in (("per block", t1 - t0), ("issue", t2 - t1),
                                ("settle now", t3 - t2), ("issue b", t5 - t4),
                                ("settle behind", t7 - t6), ("work", t9 - t8),
                                ("work behind", t11 - t10)):
                    cols[key].append(dt * 1e3)
        med = {k: statistics.median(v) for k, v in cols.items()}
        rows.append({"config": name, "blocks": n,
                     "block_bytes": block_bytes, **med})
        print(f"{name:22s} n={n}  {block_bytes / 1e3:7.1f} KB a block | per "
              f"block {med['per block']:7.3f} ms ({med['per block'] / n:.3f} "
              f"a block) | gather: issue {med['issue']:.3f}, settle now "
              f"{med['settle now']:.3f}; behind {busy_ms:g} ms of device "
              f"work: issue {med['issue b']:.3f}, settle "
              f"{med['settle behind']:.3f}; that work alone "
              f"{med['work']:.3f}, launched behind a gather "
              f"{med['work behind']:.3f}", flush=True)
    return rows


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=15)
    ap.add_argument("--busy-ms", type=float, default=15.0)
    ap.add_argument("--json", default="")
    args = ap.parse_args(argv)
    import jax

    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind}", flush=True)
    if dev.platform != "tpu":
        total = 0
        for name, (shape, w2) in pools().items():
            tiny = (shape[0], 16) + shape[2:]  # a pool of 16 blocks
            lines = programs(make_pool(tiny, w2, 0))
            total += len(lines)
            print(name + " (the pool cut to 16 blocks)")
            for line in lines:
                print("  " + line)
        print(f"{total} programs, {len(lines)} a configuration; no chip: "
              "nothing timed")
        return
    rng = np.random.default_rng(39)
    rows = []
    for name, (shape, w2) in pools().items():
        rows += bench(name, shape, w2, args.iters, args.busy_ms, rng)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
