"""The KV commit alone on the chip: a dispatch's new rows into the block pool.

    python3 perf/kv_commit_bench.py [--reps 16] [--iters 7] [--blocks N] [--json FILE]

`models/forward.forward` ends in one write a cache: the new rows
(L, B, hk, T, hs) of a dispatch land in the pool (L, N, hk, bt, hs) at the
(block, offset) the rows' tables give their positions. This times the forms
that write can take, each alone with the pools donated, two ways: one commit
a program, as `jit_step` holds it (`--reps` programs dispatched back to back:
under 0.1 ms a call this reads the host's launch, not the device), and
`--reps` commits chained in one scan with the pools in its carry, as the
K-step decode scan holds it; at T = 1, 8 and 64 on the four cells' pool
shapes, 8 rows whose tables are drawn as a run of `chat-closed` leaves them
(distinct blocks a row, two rows parked on the scratch block):

- `scatter`: the parent's form (until PR 37), `pool.at[:, blk, :, off, :]`,
  for which XLA re-lays the whole pool into the scatter's layout and back,
  once a program: a scan of this bench keeps the pool in the scatter's
  layout between its steps (nothing in it reads the pool as the attention
  kernels do), so the parent's cost a dispatch is the `lone` column;
- `blocks`: `forward.commit_block_rows`, a loop over the (row, block) pairs
  that reads a block, selects the new rows by position and writes it back;
- `positions`: a loop over the (row, position) pairs, one
  `dynamic_update_slice` of (L, 1, hk, 1, hs) each;
- `rows`: a scatter of hs-wide rows into the pool viewed as
  (L*N*hk*bt, hs) at computed row indices.

Each form's result is compared with `scatter`'s at every (block, offset) a
live row's table maps (the scratch block takes colliding writes and is read
by nobody). Prints median ms a commit (both sides) beside the bytes written
over the chip's 819 GB/s. On the CPU (`JAX_PLATFORMS=cpu`) it runs tiny
pools and its times say nothing.
"""

from __future__ import annotations

import argparse
import functools
import json
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])

HBM_BYTES_PER_S = 819e9  # TPU v5e (Google Cloud, "TPU v5e")
# (L, N, hk, bt, hs) of the cells' pools: benchmark/configs/*.json
POOLS = {
    "mistral-7b": ((32, 1280, 8, 16, 128), 2),
    "mixtral-8x7b-l8": ((8, 768, 8, 16, 128), 2),
    "smallthinker-21b-a3b": ((24, 1024, 4, 16, 128), 2),
    "ax-k1-ep4-l7": ((7, 2048, 1, 16, 640), 1),
}
ROWS, TABLE = 8, 96


def _where(tables, start, t, bt):
    """(block, offset), each (B, T), of every position a dispatch writes."""
    import jax.numpy as jnp

    pos = start[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    blk = jnp.take_along_axis(
        tables, jnp.minimum(pos // bt, tables.shape[1] - 1), axis=1)
    return blk, pos % bt


def scatter(pool, rows, tables, start, bt):
    """The parent's commit: one scatter through axes 1 and 3."""
    import jax.numpy as jnp

    blk, off = _where(tables, start, rows.shape[3], bt)
    return pool.at[:, blk, :, off, :].set(jnp.transpose(rows, (1, 3, 0, 2, 4)))


def positions(pool, rows, tables, start, bt):
    """One dynamic_update_slice a (row, position) pair."""
    import jax
    import jax.numpy as jnp

    l, _, hk, _, w = pool.shape
    b, t = rows.shape[1], rows.shape[3]

    def body(i, pool):
        r, j = i // t, i % t
        pos = start[r] + j
        blk = tables[r, jnp.minimum(pos // bt, tables.shape[1] - 1)]
        new = jax.lax.dynamic_slice(rows, (0, r, 0, j, 0), (l, 1, hk, 1, w))
        return jax.lax.dynamic_update_slice(pool, new, (0, blk, 0, pos % bt, 0))

    return jax.lax.fori_loop(0, b * t, body, pool)


def row_scatter(pool, rows, tables, start, bt):
    """A scatter of hs-wide rows into the (L*N*hk*bt, hs) view."""
    import jax.numpy as jnp

    l, n, hk, _, w = pool.shape
    blk, off = _where(tables, start, rows.shape[3], bt)
    at = (((jnp.arange(l)[:, None, None, None] * n + blk[None, :, None, :])
           * hk + jnp.arange(hk)[None, None, :, None]) * bt
          + off[None, :, None, :])  # (L, B, hk, T)
    flat = pool.reshape(l * n * hk * bt, w).at[at.reshape(-1)].set(
        rows.reshape(-1, w))
    return flat.reshape(pool.shape)


def forms():
    from distributed_llama_tpu.models.forward import commit_block_rows

    return {"scatter": scatter,
            "blocks": lambda pool, rows, tables, start, bt: commit_block_rows(
                pool, rows, tables, start),
            "positions": positions, "rows": row_scatter}


def traffic(rng, n: int, bt: int, t: int, reps: int):
    """Tables and start positions of ROWS rows: distinct blocks a live row
    (block 0 is the scratch block), the last two rows parked on it, and the
    start of repetition i such that no repetition writes where another did."""
    assert n > (ROWS - 2) * TABLE, f"the pool needs {(ROWS - 2) * TABLE + 1} blocks"
    tables = np.zeros((ROWS, TABLE), np.int32)
    ids = rng.permutation(np.arange(1, n))[:(ROWS - 2) * TABLE]
    tables[:ROWS - 2] = ids.reshape(ROWS - 2, TABLE)
    room = TABLE * bt - reps * t
    assert room > 0, "the table holds fewer positions than the repetitions write"
    first = rng.integers(0, room, size=ROWS).astype(np.int32)
    first[ROWS - 2:] = 0
    return tables, first


def mapped(pool, tables):
    """The blocks a live row's table maps, in one order."""
    import jax.numpy as jnp

    return pool[:, jnp.asarray(np.unique(tables[:ROWS - 2]))]


def run_case(name, shape, sides, t, *, reps, iters, seed=0):
    import jax
    import jax.numpy as jnp

    l, n, hk, bt, w = shape
    rng = np.random.default_rng(seed)
    tables, first = traffic(rng, n, bt, t, reps)
    tables_d, first_d = jnp.asarray(tables), jnp.asarray(first)
    key = jax.random.key(seed)
    new = [jax.random.normal(jax.random.fold_in(key, s), (l, ROWS, hk, t, w),
                             jnp.bfloat16) for s in range(sides)]
    written = sides * l * ROWS * hk * t * w * 2
    out = {"pool": name, "shape": list(shape), "sides": sides, "t": t,
           "bytes_written": written,
           "floor_ms": written / HBM_BYTES_PER_S * 1e3}
    want = None
    for form, fn in forms().items():
        def chained(pools, new, tables, first, n, fn=fn):
            def step(pools, i):
                start = first + jnp.where(first > 0, i * t, 0)
                return [fn(p, r, tables, start, bt)
                        for p, r in zip(pools, new)], None

            return jax.lax.scan(step, pools, jnp.arange(n))[0]

        # "lone": one commit a program, as `jit_step` holds it, `reps`
        # programs dispatched back to back; else `reps` commits in one scan
        for key, n in ((f"{form}_lone_ms", 1), (f"{form}_ms", reps)):
            run = jax.jit(functools.partial(chained, n=n), donate_argnums=(0,))
            pools = [jnp.zeros(shape, jnp.bfloat16) for _ in range(sides)]
            pools = jax.block_until_ready(run(pools, new, tables_d, first_d))
            if want is None:
                want = [mapped(p, tables) for p in pools]
            elif n == 1:  # bit for bit at every block a live row's table maps
                out[f"{form}_equal"] = all(
                    bool(jnp.array_equal(mapped(p, tables), q))
                    for p, q in zip(pools, want))
            times = []
            for _ in range(iters):
                t0 = time.perf_counter()
                for _ in range(reps // n):
                    pools = run(pools, new, tables_d, first_d)
                jax.block_until_ready(pools)
                times.append((time.perf_counter() - t0) / reps * 1e3)
            out[key] = statistics.median(times)
            del pools
    return out


def main():
    import jax

    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=16)
    ap.add_argument("--iters", type=int, default=7)
    ap.add_argument("--blocks", type=int, default=0,
                    help="pool blocks in place of the cells' (a rehearsal)")
    ap.add_argument("--json", default="")
    args = ap.parse_args()
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind}")
    rows = []
    for name, (shape, sides) in POOLS.items():
        if args.blocks:
            shape = (shape[0], args.blocks, *shape[2:])
        for t in (1, 8, 64):
            r = run_case(name, shape, sides, t, reps=args.reps,
                         iters=args.iters)
            rows.append(r)
            print(f"{name} {tuple(shape)} T={t}, ms a commit lone / chained: "
                  + ", ".join(f"{f} {r[f + '_lone_ms']:.3f} / "
                              f"{r[f + '_ms']:.3f}"
                              + ("" if f == "scatter" else
                                 f" (equal {r[f + '_equal']})")
                              for f in forms())
                  + f"; bytes' time {r['floor_ms']:.4f} ms", flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"device": f"{dev.platform} {dev.device_kind}",
                       "reps": args.reps, "rows": rows}, f, indent=1)
    if not all(r[k] for r in rows for k in r if k.endswith("_equal")):
        sys.exit("a form differs from the scatter at a mapped block")


if __name__ == "__main__":
    main()
