"""Paged-attention kernel microbench + parity oracle (ISSUE 12 satellite).

Measures achieved GB/s of ops/pallas_paged_attention.paged_attention at the
two production shapes — decode (T=1, the K-step scan's per-step read) and
speculative verify (T=1+k) — against the bytes the kernel must move per
call (the table's KV blocks + the chunk), and checks three parities:

- XLA-vs-dense BIT-EXACTNESS: paged_attention_xla (gather + gqa_attention)
  must equal the dense contiguous-window gqa_attention to the last bit when
  the gathered width equals the dense window — the structural property the
  paged engine's token-identity rests on (tests/test_paged_kv.py).
- kernel-vs-oracle numeric parity: the Pallas kernel's blockwise online
  softmax against the one-shot XLA softmax, gated at a tight f32 tolerance.
- greedy-pick agreement: argmax over a projected vocab row must match —
  the token-level consequence of the numeric gap staying far below logit
  spacing.

CPU runs use interpret mode (correctness numbers only; GB/s on interpret
mode measures the interpreter, and the JSON says so). On TPU, append the
result row to a perf/r*_hw_results.jsonl-style artifact with --json.

`--cells` times the shapes the benchmark's cells dispatch instead (B 8, hk 8,
g 4, hs 128, bt 16, bf16; T in {1, 8, 64} against the 512 and 1024 window
buckets, rows' lengths read from a run of `chat-closed`; and against 2048
and 4096 keys with illustrative long rows; and, since a prefill dispatch
reads the pool in two calls (`models/forward.py RowMap.attend`, PR 45), the
lead's call B 1, T in {8, 64} at the longest of those rows beside the riders'
call, which is the B 8, T 1 row; and last a LENGTH SWEEP of that call, every
row at 0 to 1024 keys, fitted to rows x (a + b x steps): a row's fixed cost
and a step's, beside a step's bytes at the chip's peak), the kernel beside
the XLA gather path (an engine's `paged_kernel=False`), one call a layer of a
scan inside one jit, with the bytes and FLOP a call needs over the chip's
peaks. It uses only `paged_attention` and `paged_attention_xla`, so a copy
of this file in an older checkout times that checkout's kernel.

Usage: python perf/paged_attn_bench.py [--json out.json] [--iters N] [--cells]
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def _mk(rng, shape):
    import jax.numpy as jnp

    return jnp.asarray(rng.normal(size=shape).astype(np.float32))


def bench_shape(t: int, *, L=8, N=64, hk=8, g=4, bt=64, hs=128, B=4,
                iters=20, interpret=None, seed=0):
    """One (decode or verify) shape: returns the parity + GB/s row."""
    import jax
    import jax.numpy as jnp

    from distributed_llama_tpu.ops.attention import gqa_attention
    from distributed_llama_tpu.ops.pallas_paged_attention import (
        paged_attention, paged_attention_xla)

    rng = np.random.default_rng(seed)
    hq = hk * g
    kc = _mk(rng, (L, N, hk, bt, hs))
    vc = _mk(rng, (L, N, hk, bt, hs))
    q = _mk(rng, (B, t, hq, hs))
    kn = _mk(rng, (B, hk, t, hs))
    vn = _mk(rng, (B, hk, t, hs))
    nb = (N - 1) // B  # read blocks per row (disjoint tables, block 0 scratch)
    tables = np.zeros((B, nb), np.int32)
    ids = np.arange(1, B * nb + 1)
    rng.shuffle(ids)
    tables[:] = ids.reshape(B, nb)
    tables = jnp.asarray(tables)
    lengths = jnp.asarray(
        rng.integers(bt, nb * bt + 1, size=B).astype(np.int32))
    layer = min(3, L - 1)

    out_k = paged_attention(q, kc, vc, kn, vn, tables, lengths, layer,
                            n_read=nb, interpret=interpret)
    out_x = paged_attention_xla(q, kc, vc, kn, vn, tables, lengths, layer,
                                n_read=nb)
    kernel_max_abs = float(jnp.max(jnp.abs(out_k - out_x)))

    # XLA-vs-dense bit-exactness: materialize the virtual contiguous cache
    # and run the dense deferred-window computation (same masks/sentinels)
    kl = np.asarray(kc)[layer]
    vl = np.asarray(vc)[layer]
    tbl = np.asarray(tables)
    kwin = np.stack([kl[tbl[b]].transpose(1, 0, 2, 3).reshape(
        hk, nb * bt, hs) for b in range(B)])
    vwin = np.stack([vl[tbl[b]].transpose(1, 0, 2, 3).reshape(
        hk, nb * bt, hs) for b in range(B)])
    win = nb * bt
    slot = np.arange(win)
    ln = np.asarray(lengths)
    slot_pos = np.where(slot[None, :] < ln[:, None], slot[None, :], win + 1)
    key_pos = np.concatenate([slot_pos, ln[:, None] + np.arange(t)[None, :]],
                             axis=1)
    positions = ln[:, None] + np.arange(t, dtype=np.int32)[None, :]
    dense = gqa_attention(
        q, jnp.concatenate([jnp.asarray(kwin), kn], axis=2),
        jnp.concatenate([jnp.asarray(vwin), vn], axis=2),
        jnp.asarray(positions), key_positions=jnp.asarray(key_pos))
    xla_vs_dense_bits = bool(jnp.array_equal(
        out_x.reshape(B, t, hq * hs).astype(dense.dtype), dense))

    # greedy-pick agreement through a projection head
    wproj = _mk(rng, (hs * hq, 512))
    pick_k = jnp.argmax(out_k.reshape(B, t, hq * hs) @ wproj, axis=-1)
    pick_x = jnp.argmax(out_x.reshape(B, t, hq * hs) @ wproj, axis=-1)
    greedy_agree = bool(jnp.array_equal(pick_k, pick_x))

    # timing: bytes = the KV blocks the table forces through HBM + chunk
    fn = jax.jit(lambda *a: paged_attention(*a, n_read=nb,
                                            interpret=interpret))
    args = (q, kc, vc, kn, vn, tables, lengths, layer)
    fn(*args)[0].block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    out.block_until_ready()
    dt = (time.perf_counter() - t0) / iters
    itemsize = np.dtype(np.float32).itemsize
    bytes_moved = 2 * B * nb * hk * bt * hs * itemsize \
        + 2 * B * hk * t * hs * itemsize
    import jax as _jax

    return {
        "shape": "decode_t1" if t == 1 else f"verify_t{t}",
        "B": B, "T": t, "layers_pool": L, "pool_blocks": N, "hk": hk,
        "g": g, "block_tokens": bt, "head_size": hs, "read_blocks": nb,
        "kernel_max_abs_err": kernel_max_abs,
        "xla_vs_dense_bit_exact": xla_vs_dense_bits,
        "greedy_pick_agree": greedy_agree,
        "ms_per_call": round(dt * 1e3, 4),
        "achieved_gbps": round(bytes_moved / dt / 1e9, 2),
        "bytes_per_call": bytes_moved,
        "backend": _jax.default_backend(),
        "interpret": bool(interpret if interpret is not None
                          else _jax.default_backend() != "tpu"),
    }


def run(iters: int = 20, small: bool = False, interpret=None):
    kw = dict(iters=iters)
    if small:  # tier-1 smoke geometry: seconds, not minutes, on CPU
        kw.update(L=2, N=12, hk=2, g=2, bt=8, hs=16, B=2, iters=3)
    rows = [bench_shape(1, **kw), bench_shape(5, **kw)]
    for r in rows:
        assert r["xla_vs_dense_bit_exact"], (
            "paged gather path diverged bitwise from the dense window path")
        assert r["kernel_max_abs_err"] < 2e-5, r["kernel_max_abs_err"]
        assert r["greedy_pick_agree"], "kernel numeric gap flipped an argmax"
    return rows


# TPU v5e peaks (Google Cloud documentation, "TPU v5e"), bf16
V5E_HBM_BPS = 819e9
V5E_FLOPS = 197e12
# committed lengths of the 8 rows of a 64-token dispatch under chat-closed,
# per window bucket: the median of each rank over the sorted rows of one
# run's dispatches (54 and 65 of them; mistral-7b.chat-closed, seed
# 3000000601, PR 27). T=1 and T=8 dispatches carry rows about a fifth longer.
# The 2048 and 4096 rows are ILLUSTRATIVE, no cell dispatches them yet: the
# same traffic with one or two long rows (a long-prompt cell, PERF.md §7).
CELL_LENGTHS = {512: (0, 0, 101, 145, 186, 221, 275, 332),
                1024: (0, 0, 93, 128, 184, 232, 287, 487),
                2048: (0, 75, 190, 330, 520, 640, 900, 1500),
                4096: (0, 75, 190, 330, 520, 640, 1200, 3000)}


def _time_calls(attend, args, nb, layers, reps):
    """Seconds a call of `attend(*args, layer, n_read=nb)`: one call a layer
    of a scan over `layers * reps` layers inside one jit (a lone call is all
    launch), the shortest of three runs after the one that compiles.
    Returns (the scan's summed output, seconds a call)."""
    import jax
    import jax.numpy as jnp

    q = args[0]

    @jax.jit
    def run(*a):
        def body(acc, li):
            return acc + attend(*a, li, n_read=nb), None

        return jax.lax.scan(body, jnp.zeros(q.shape, jnp.float32),
                            jnp.tile(jnp.arange(layers), reps))[0]

    out = run(*args).block_until_ready()
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        run(*args).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return out, best / (layers * reps)


_CELL_HEADS = (8, 4, 128)  # the dense cells' hk, g, hs
_CELL_BT = 16


def _cell_inputs(b, t, nb, layers, seed, pool_blocks):
    """bf16 q (b, t, hq, hs), K and V pools of `layers` x at least
    `pool_blocks` blocks, the chunk's K and V and a shuffled (b, nb) table at
    the dense cells' heads."""
    import jax.numpy as jnp

    hk, g, hs = _CELL_HEADS
    n = max(b * nb + 1, pool_blocks)
    rng = np.random.default_rng(seed)

    def mk(shape):
        return _mk(rng, shape).astype(jnp.bfloat16)

    pool = (layers, n, hk, _CELL_BT, hs)
    kc, vc = mk(pool), mk(pool)
    q = mk((b, t, hk * g, hs))
    kn, vn = mk((b, hk, t, hs)), mk((b, hk, t, hs))
    ids = np.arange(1, n)
    rng.shuffle(ids)
    tables = jnp.asarray(ids[:b * nb].reshape(b, nb).astype(np.int32))
    return q, kc, vc, kn, vn, tables


def bench_cell(t: int, window: int, *, layers=8, reps=4, seed=0,
               pool_blocks=1280, lead=False):
    """One (T, window bucket) of the cells' dispatches: ms a call of the
    kernel and of the XLA gather path, against what causal attention over
    the rows' lengths needs. `lead`: the ONE row that prefills alone (B 1,
    the longest of the bucket's rows), the first of the two calls a chunk
    makes; the second is the B 8, T 1 dispatch. The pool has the dense cell's 1280 blocks: the
    gather path's time grows with the pool it slices a layer from (0.25 ms
    at T=64 and 1024 keys from 1025 blocks, 0.30 from 1280: PR 27)."""
    import jax
    import jax.numpy as jnp

    from distributed_llama_tpu.ops.pallas_paged_attention import (
        paged_attention, paged_attention_xla)

    lens = np.asarray(CELL_LENGTHS[window], np.int32)
    if lead:
        lens = lens.max(keepdims=True)
    B, hk, g, hs = len(lens), *_CELL_HEADS
    nb = window // _CELL_BT
    q, kc, vc, kn, vn, tables = _cell_inputs(B, t, nb, layers, seed,
                                             pool_blocks)
    lengths = jnp.asarray(lens)

    args = (q, kc, vc, kn, vn, tables, lengths)
    out_k, dt_k = _time_calls(paged_attention, args, nb, layers, reps)
    out_x, dt_x = _time_calls(paged_attention_xla, args, nb, layers, reps)
    # what the call needs: each row's committed keys and its chunk, K and V
    # once; q in, the output out; two FLOP a multiply-add, QK and PV
    keys = int(lens.sum()) + B * t
    need_bytes = 2 * keys * hk * hs * 2 + B * t * hk * g * hs * (2 + 4)
    need_flop = 4 * hk * g * hs * int(sum(
        t * int(ln) + t * (t + 1) // 2 for ln in lens))
    bytes_s, flop_s = need_bytes / V5E_HBM_BPS, need_flop / V5E_FLOPS
    floor_s = max(bytes_s, flop_s)
    return {
        "B": B, "T": t, "window": window, "lengths": lens.tolist(),
        "kernel_ms": round(dt_k * 1e3, 4), "xla_ms": round(dt_x * 1e3, 4),
        "need_bytes": need_bytes, "need_flop": need_flop,
        "floor_ms": round(floor_s * 1e3, 5),
        "bound": "bytes" if bytes_s >= flop_s else "flop",
        "kernel_floor_share_pct": round(100 * floor_s / dt_k, 2),
        "kernel_vs_xla_max_abs": float(jnp.max(jnp.abs(out_k - out_x))),
        "out_max_abs": float(jnp.max(jnp.abs(out_x))),
        "backend": jax.default_backend(),
    }


SWEEP_LENGTHS = (0, 128, 256, 384, 512, 768, 1024)


def bench_sweep(*, window=1024, rows=8, layers=8, reps=16, seed=0,
                pool_blocks=1280):
    """What a ROW costs and what a STEP costs, apart: the riders' call (B 8 x
    T 1 at the 1024 bucket) with every row at the same committed length, one
    timing a length of `SWEEP_LENGTHS`, and the least-squares fit of
    call = rows x (a + b x steps), a step being 128 keys. `a` is a row before
    its first key (the grid step, the statistics' reset, a first copy that
    nothing hides, the fold of the chunk's own key), `b` one more step; beside
    them the time a step's K and V bytes take at the chip's peak."""
    import jax
    import jax.numpy as jnp

    from distributed_llama_tpu.ops.pallas_paged_attention import (
        _STEP_KEYS, paged_attention)

    hk, _, hs = _CELL_HEADS
    nb = window // _CELL_BT
    inputs = _cell_inputs(rows, 1, nb, layers, seed, pool_blocks)
    steps = [-(-ln // _STEP_KEYS) for ln in SWEEP_LENGTHS]
    calls_us = []
    for ln in SWEEP_LENGTHS:
        lengths = jnp.full((rows,), ln, jnp.int32)
        _, dt = _time_calls(paged_attention, (*inputs, lengths), nb, layers,
                            reps)
        calls_us.append(dt * 1e6)
    b_us, call0 = np.polyfit(steps, calls_us, 1)
    step_bytes = 2 * hk * _STEP_KEYS * hs * 2  # K and V, bf16
    return {
        "sweep": f"B {rows} x T 1, window {window}", "lengths": SWEEP_LENGTHS,
        "call_us": [round(c, 2) for c in calls_us],
        "row_fixed_us_a": round(float(call0) / rows, 3),
        "step_us_b": round(float(b_us) / rows, 3),
        "step_bytes_floor_us": round(step_bytes / V5E_HBM_BPS * 1e6, 3),
        "fit_worst_residual_us": round(float(np.max(np.abs(
            np.polyval((b_us, call0), steps) - calls_us))), 2),
        "backend": jax.default_backend(),
    }


def run_cells(layers=8, reps=4):
    return [bench_cell(t, w, layers=layers, reps=reps, lead=lead)
            for t, lead in ((1, False), (8, False), (64, False), (8, True),
                            (64, True)) for w in CELL_LENGTHS] + [
        bench_sweep(layers=layers, reps=4 * reps)]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--json", metavar="OUT", default=None)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--small", action="store_true",
                    help="tiny smoke geometry (the tier-1 gate's shapes)")
    ap.add_argument("--cells", action="store_true",
                    help="time the benchmark cells' dispatch shapes")
    args = ap.parse_args(argv)
    rows = (run_cells() if args.cells
            else run(iters=args.iters, small=args.small))
    out = {"bench": "paged_attention", "results": rows}
    print(json.dumps(out, indent=2))
    if args.json:
        with open(args.json, "w", encoding="utf-8") as fh:
            json.dump(out, fh, indent=2)
            fh.write("\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
