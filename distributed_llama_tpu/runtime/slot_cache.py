"""Where a slot's cache lives (docs/PAGED_KV.md, docs/PREFIX_CACHE.md): what
the scheduler (runtime/batch_engine.py) asks of it, behind one object an
engine (`make_slot_cache`) and two implementations of the same calls.

- `PoolSlotCache`: the device block pool. A slot holds a block table, a
  prefix hit remaps the radix directory's blocks, the pool is reclaimed by
  demoting them to the host, state layers snapshot beside the blocks.
- `DenseSlotCache`: the per-slot rows. A prefix hit is a copy from the host
  `PrefixCache`, a harvest a copy back. The pool's reference
  (tests/test_paged_kv.py); `dp > 1` and `paged_kv=False` run it.

A slot is the fields used here: `index`, `blocks`, `history`, `pos`, `req`,
`lease`, `clamp_pos`. Scheduler thread only, but for `import_blocks` and a
`DemoteRead`'s settle.
"""

from __future__ import annotations

import contextlib
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..cache import default_pool_blocks, make_prefix_cache, warn_degraded
from ..cache.block_pool import PendingRows
from ..cache.device_pool import (DEMOTE_READS, REMAPPED, SEED_BYTES,
                                 SETTLE_WAITS, DeviceKVPool, KVPoolExhausted,
                                 PagedPrefixCache, SnapshotPool)
from ..models.forward import STATE_STRIDE, StateCache, seed_state
from ..obs import metrics, reqctx, trace
from ..resilience import faults

__all__ = ["DEMOTE_SIZES", "DemoteRead", "DenseSlotCache", "PoolSlotCache",
           "make_slot_cache", "pool_gather", "pool_sides", "start_host_copy"]

_PREFIX_SEEDED = metrics.counter(
    "batch_prefix_seeded_tokens_total",
    "Cache rows copied from the prefix-cache pool at admission "
    "(prompt tokens whose prefill was skipped beyond the same-slot rewind)")
_TABLE_UPLOADS = metrics.counter(
    "batch_table_uploads_total",
    "Dispatches that re-sent the whole (slots, blocks a context) block "
    "table because a row of it was edited since the last one")
_STATE_RESTORES = metrics.counter(
    "paged_kv_state_restores_total",
    "Admissions (prefix hits and slot rewinds past position 0) that seeded a "
    "slot's running state from a block's snapshot")
_STATE_BLOCK_BYTES = metrics.gauge(
    "kv_pool_state_block_bytes",
    "Bytes of state snapshot a pool block holds beside its keys and values "
    "(0: the model has no state layers)")
# A state-space layer's running matrix a head (ModelSpec.ssm), counted where
# the dispatch is told of it (`state_word`), apart from the convolution's
_SSM_ROWS = metrics.counter(
    "batch_ssm_rows_stepped_total",
    "Live rows a dispatch took through ssd_step (a T = 1 step, each step of "
    "the K-step scan, a chunk's riders) x state layers")
_SSM_CHUNK_TOKENS = metrics.counter(
    "batch_ssm_chunk_tokens_total",
    "Tokens a dispatch took through ssd_chunk (the prefilling row's chunk) "
    "x state layers")
_SSM_BYTES = metrics.counter(
    "batch_ssm_state_bytes_total",
    "Bytes of running matrices H a dispatch read and wrote: two a live row "
    "a layer a step, two a chunk's slot a layer, and a snapshot's read and "
    "write where a row ended a stride")
_SSM_STRIDE_ENDS = metrics.counter(
    "batch_ssm_stride_ends_total",
    "Stride ends (a position p with (p + 1) % 256 == 0) the rows of the "
    "dispatches issued crossed: what batch_ssm_snapshots_total is held "
    "against")
_SSM_SNAPSHOTS = metrics.counter(
    "batch_ssm_snapshots_total",
    "Stride ends for which the dispatch was given an entry of the snapshot "
    "pool to write the row's state into")
_MATRIX_BYTES = metrics.gauge(
    "batch_state_matrix_bytes",
    "Bytes of running matrix one (slot, state layer) holds in float32: "
    "heads x rows x columns x 4 of the state kind (a state-space mixer's "
    "or a delta-rule mixer's); 0: the model's state layers hold none")

_NO_KV_STREAM = (
    "a model with state layers (a gated short convolution, a state-space "
    "mixer, a delta-rule mixer) is not supported "
    "by KV-block streaming between replicas (cache/wire.py): a block's "
    "state snapshot does not travel with its keys and values")

# Donated single-block pool updates (docs/PAGED_KV.md copy-on-write and
# cold promotion): an eager `pool.at[:, b].set(...)` would materialize a
# whole new pool array per block touched — O(pool) HBM traffic and 2x peak
# memory. Donating the pool lets XLA update the one block in place.
_pool_block_copy = jax.jit(lambda c, src, dst: c.at[:, dst].set(c[:, src]),
                           donate_argnums=(0,))
_pool_block_set = jax.jit(lambda c, dst, rows: c.at[:, dst].set(rows),
                          donate_argnums=(0,))


def pool_sides(eng) -> tuple:
    """The engine's arrays that are indexed by pool block on axis 1: keys,
    values and, of a model with state layers, the blocks' state snapshots
    (the typed block payload, docs/PAGED_KV.md)."""
    vc = eng.v_cache
    if isinstance(vc, StateCache):
        # a state-space model's snapshots lie in a pool of their own, by
        # entry and not by block (cache/device_pool.py SnapshotPool)
        return (eng.k_cache, vc.rows) + (() if vc.h is not None
                                         else (vc.snaps,))
    return eng.k_cache, vc


def _set_pool_sides(eng, sides) -> None:
    eng.k_cache = sides[0]
    vc = eng.v_cache
    if isinstance(vc, StateCache):
        eng.v_cache = vc._replace(rows=sides[1], **(
            {"snaps": sides[2]} if len(sides) > 2 else {}))
    else:
        eng.v_cache = sides[1]


# The prefix cache's demotion reads a reclaim's victims with ONE gather from
# every side of the pool, (n, L, hk, bt, w) a side, block-major so that a
# block's rows are one contiguous piece of the host copy. n is one of a few
# fixed sizes (a shorter list of ids is filled with the scratch block, a
# longer one is cut into eights), so every program it can need is known
# beforehand: PoolSlotCache.read_block compiles them all.
DEMOTE_SIZES = (1, 2, 4, 8)


def _pool_gather(sides, ids):
    return tuple(jnp.swapaxes(c[:, ids], 0, 1) for c in sides)


pool_gather = jax.jit(_pool_gather)


def start_host_copy(*arrays) -> None:
    """Begin the arrays' device->host copies without waiting for them, so
    that a later np.asarray picks the buffers up. A hint only: e.g. a
    sharded array may refuse the whole-array async copy."""
    for a in arrays:
        try:
            a.copy_to_host_async()
        except Exception:
            pass


class DemoteRead:
    """One reclaim's read of its victims off the device (docs/PAGED_KV.md
    "Eviction"). The directory asks for a block at a time while it chooses
    (`block`), every answer a pending row of this read; `issue` then
    enqueues the gather, before the dispatch that will write the freed
    blocks, so the device's own order keeps the rows intact, and starts the
    copy to the host without waiting for it; `settle` makes host arrays of
    it, once, wherever the rows are first needed."""

    def __init__(self, pool):
        """`pool`: the engine's arrays by block (`pool_sides`: K, V and a
        model with state layers' snapshots), (L, N, ...) a side; what a
        block of them looks like is kept, the arrays are not (the next
        dispatch donates them)."""
        k = pool[0]
        self.shape = k.shape[:1] + k.shape[2:]  # a block's K side
        self.dtype = np.dtype(k.dtype)
        self.nbytes = sum(c.nbytes // c.shape[1] for c in pool)  # a block's
        self._widths = tuple(c.shape[-1] for c in pool)  # 0: an empty side
        self.bids: list[int] = []
        self._parts = None  # a gather each: the non-empty sides, on the device
        self._host = None  # a gather each: (k, v) host arrays (n, L, hk, bt, w)
        self._error: Exception | None = None
        self._lock = threading.Lock()  # guards: _host, _error, _parts (two threads may settle: the scheduler, and an importer's whose Q80 put compresses a pending block)

    def block(self, bid: int) -> "_DemotedRows":
        self.bids.append(bid)
        return _DemotedRows(self, len(self.bids) - 1)

    def issue(self, pool) -> int:
        """Enqueue the gathers (one, unless there are more than eight
        victims) over `pool`, the engine's (K, V) arrays, and start their
        host copies; returns how many. A failure here is the read's: every
        block of it is dropped when settled."""
        top = DEMOTE_SIZES[-1]
        sides = tuple(c for c in pool if c.shape[-1])
        parts, error = [], None
        try:
            for lo in range(0, len(self.bids), top):
                ids = self.bids[lo:lo + top]
                n = next(z for z in DEMOTE_SIZES if z >= len(ids))
                out = pool_gather(
                    sides, np.asarray(ids + [0] * (n - len(ids)), np.int32))
                start_host_copy(*out)
                parts.append(out)
        except Exception as e:
            error = e
        with self._lock:
            self._parts, self._error = parts, error
        return len(parts)

    def ready(self) -> bool:
        with self._lock:
            parts = self._parts
        if parts is None:
            return False  # not issued yet
        return all(a.is_ready() for out in parts for a in out)

    def settle(self) -> list:
        ready = self.ready()
        with self._lock:
            if self._error is not None:
                raise self._error
            if self._host is None:
                if self._parts is None:
                    raise RuntimeError("demotion read settled before issue")
                if not ready:  # whoever asks (a hit, Q80, close) waits
                    SETTLE_WAITS.inc()
                try:
                    host = []
                    for out in self._parts:
                        # the demotion's one device->host copy, started
                        # at issue: picked up here, where the host waits
                        got = [np.asarray(a) for a in out]
                        # a latent row's empty second side, by K's shape
                        void = np.zeros(got[0].shape[:-1] + (0,), got[0].dtype)
                        rest = iter(got)
                        host.append(tuple(next(rest) if w else void
                                          for w in self._widths))
                except Exception as e:
                    self._error = e
                    raise
                finally:
                    self._parts = []  # the device copies can go
                self._host = host
            return self._host


class _DemotedRows(PendingRows):
    """Row `i` of a DemoteRead: one block's pending (K, V) rows, what the
    cold tier holds in place of arrays until they are settled."""

    __slots__ = ("read", "i", "shape", "dtype", "nbytes")

    def __init__(self, read: DemoteRead, i: int):
        self.read, self.i = read, i
        self.shape, self.dtype, self.nbytes = read.shape, read.dtype, read.nbytes

    def ready(self) -> bool:
        return self.read.ready()

    def settle(self):
        part, row = divmod(self.i, DEMOTE_SIZES[-1])
        # (k, v), and the block's state snapshot where the pool has one
        return tuple(a[row] for a in self.read.settle()[part])


class _SlotCache:
    """What the two share, and the answers of a cache with no blocks, no
    demotions and no state layers."""

    kv_pool = None  # DeviceKVPool metadata (None: the dense layout)
    prefix_cache = None
    block_tokens = 0  # tokens a pool block (0: no pool)
    stride = 0  # positions between two state snapshots (0: no state layers)
    no_stream: str | None = None  # why KV blocks cannot travel, if so

    def __init__(self, eng, spec, upload):
        self._eng, self._spec, self._upload = eng, spec, upload
        # admission seeding cost (/v1/stats): ~0 bytes paged, the span dense
        self.seed_bytes = 0
        self.seed_ms = 0.0

    def _nothing(self, *_args, **_kw) -> None:
        """cover(slot, upto): storage behind every position < upto;
        own(slot, lo, hi): the slot alone writes [lo, hi); release(slot);
        settle_state(snaps, accepted, scan); settle(force); reset()."""

    cover = own = release = settle_state = settle = reset = _nothing

    def park(self, slot, p: int, hi: int) -> bool:
        """A clamped park: scratch writes land at [p, hi), under the row's
        frontier. False where the slot was emptied instead (it parks at 0)."""
        self.truncate(slot, p)
        return True

    def table(self):
        """(this dispatch's device block table, whether it was re-sent)."""
        return None, False

    def chunk_limit(self, pos: int) -> int:
        """The largest prefill chunk that may start at `pos`."""
        return self._spec.seq_len

    def state_word(self, rows, starts, budget, chunk: int = 0):
        """(the snapshot entries a dispatch is allotted, its span's args)."""
        return [], {}

    def pending_bytes(self) -> int:
        """Bytes on their way to the host beside a dispatch's results."""
        return 0

    def unpin(self, slot) -> None:
        """Release the slot's lease: it pins blocks while in flight only."""
        if self.prefix_cache is not None and slot.lease is not None:
            self.prefix_cache.release(slot.lease)
            slot.lease = None

    def truncate(self, slot, p: int) -> None:
        """Truncate a slot's reusable history to p tokens — its rows >= p are
        (about to be) overwritten by clamped scratch writes — and shrink any
        prefix-cache lease past p. Without the shrink a clamped park would
        leave the radix reservation pinning blocks for a prefix the slot no
        longer holds, blocking their eviction until the request finishes
        (and lying about what the slot can re-insert)."""
        if p < len(slot.history):
            slot.history = slot.history[:p]
        if slot.lease is not None and p < slot.lease.tokens:
            self.prefix_cache.shrink(slot.lease, p)

    def _lookup(self, slot, full: list[int], held: int):
        """A lease on the longest cached prefix of `full`, None where it is
        no longer than the `held` tokens the slot has. A raising lookup (or
        injected seed fault) costs only the cache win: in the scheduler loop
        it would fail every in-flight request and leave this one queued."""
        pc = self.prefix_cache
        pc.note_resident(held)
        try:
            faults.fire("batch.cache_seed", slot=slot.index)
            lease = pc.lookup(full, cap=self._spec.seq_len - 1)
            if lease is not None and self.stride:
                # whole blocks alone: a hit lands on a block's snapshot
                pc.shrink(lease, self.state_landing(
                    lease.tokens, lambda i: lease.nodes[i].handle[1]
                    if lease.nodes[i].handle[0] == "dev" else None))
            if lease is not None and lease.tokens <= held:
                pc.mark_unused(lease)
                lease = None
            return lease
        except Exception as e:
            warn_degraded("lookup", e)
            return None

    def harvest(self, slot, deferred: bool = False):
        """The finished (or preempted: `deferred`) slot's committed prefix
        goes into the prefix cache. history's rows [0, len(history)) are
        committed by construction: every truncation site shrinks history
        before the rows are overwritten. Returns what is left to do outside
        the scheduler's lock, a call, or None."""
        if self.prefix_cache is None:
            return None
        if slot.clamp_pos is not None:
            # a super-step in flight parked this row clamped, destroying
            # that row: the delivery loop's truncation would run too late
            self.truncate(slot, slot.clamp_pos)
            slot.clamp_pos = None
        return self._harvest(slot, deferred)

    def export_blocks(self, slot, prompt_len: int):
        """Host snapshot of the slot's committed prompt-prefix KV as
        fixed-size blocks — the disaggregation export payload (docs/
        DISAGG.md): (tokens, [(k, v) per block], block_tokens), each side
        an (L, hk, bt, hs) host array. Scheduler thread ONLY: device cache
        reads must not race a donating dispatch. Only FULL blocks of the
        prompt export (a partial tail block has no directory home on the
        importing side); a clamped park truncates the exportable span the
        same way it truncates the harvest."""
        bt = self.block_tokens or (self.prefix_cache.block_tokens
                                   if self.prefix_cache is not None else 0)
        if bt <= 0 or self.no_stream:  # submit() refused export_kv
            return None
        p = min(prompt_len, len(slot.history))
        if slot.clamp_pos is not None:
            p = min(p, slot.clamp_pos)
        n = p // bt
        if n == 0:
            return None
        return list(slot.history[:n * bt]), self._read_blocks(slot, n, bt), bt

    def import_blocks(self, tokens: list[int], blocks: list) -> int:
        """Adopt externally-shipped HOST KV blocks (the decode half of a
        disaggregated admission, docs/DISAGG.md) into the prefix cache:
        `blocks[i]` is the (k, v) pair covering token block i of `tokens`.
        Pure host bookkeeping, safe from any HTTP handler thread: a paged
        directory stores them as COLD nodes (admission pays the one
        host→device promotion upload, on the scheduler thread), a dense
        cache inserts them into its host pool. Returns the token span the
        cache now covers (0: the caller admits with a plain local prefill)."""
        pc = self.prefix_cache
        if pc is None:
            return 0
        if self.no_stream:
            raise ValueError(self.no_stream)
        bt = pc.block_tokens
        n = min(len(tokens) // bt, len(blocks))
        if n <= 0:
            return 0
        return self._insert_host(list(tokens[:n * bt]), blocks[:n]) * bt


class DenseSlotCache(_SlotCache):
    """A slot's rows of the engine's `(L, slots, hk, seq_len, hs)` arrays,
    and the host `PrefixCache` (docs/PREFIX_CACHE.md; none for host/disc-spill
    engines: their ring layout has no plain [0, n) row prefix to seed)."""

    def __init__(self, eng, spec, slots, upload, *, prefix_cache, blocks,
                 block_tokens, q80):
        super().__init__(eng, spec, upload)
        if not eng.paged:
            self.prefix_cache = make_prefix_cache(
                eng.k_cache.shape, eng.k_cache.dtype.itemsize,
                slots=len(slots), prefix_cache=prefix_cache, blocks=blocks,
                block_tokens=block_tokens, q80=q80)

    def admit(self, slot, req, full: list[int], rewind: int):
        """(rewind, where prefill starts). The request's context, so that
        batch.prefix_seed carries its trace id on the scheduler's thread."""
        with reqctx.use(req.ctx):
            return rewind, self._seed(slot, rewind, full)

    def _seed(self, slot, reuse: int, full: list[int]) -> int:
        """[0, reuse) is served by the slot's own resident rows. When the
        radix index beats that, the pool blocks' rows are scattered into the
        slot's cache rows [reuse, n), and prefill starts at n. The lease
        stays on the slot until the request finishes (eviction must respect
        in-flight slots); a seeding failure falls back to plain prefill."""
        pc, eng = self.prefix_cache, self._eng
        lease = None if pc is None else self._lookup(slot, full, reuse)
        if lease is None:
            return reuse
        n = lease.tokens
        t0 = time.perf_counter()
        try:
            with trace.span("batch.prefix_seed",
                            {"slot": slot.index, "tokens": n,
                             "rewind": reuse}):
                # fetch only the span the rewind doesn't already hold, as ONE
                # contiguous (2, L, hk, n-reuse, hs) buffer: a single
                # host->device transfer and one scatter per cache tensor
                rows = self._upload(pc.fetch_packed(lease, skip=reuse),
                                    eng.dtype)
                eng.k_cache = eng.k_cache.at[:, slot.index, :, reuse:n, :].set(
                    rows[0])
                eng.v_cache = eng.v_cache.at[:, slot.index, :, reuse:n, :].set(
                    rows[1])
        except Exception as e:
            pc.mark_unused(lease)
            warn_degraded("seed", e)  # fall back to full prefill
            return reuse
        # host→device KV bytes this admission moved (the scatter baseline
        # the paged remap path eliminates — bench.py shared-prefix columns)
        self.seed_bytes += int(rows.nbytes)
        self.seed_ms += (time.perf_counter() - t0) * 1e3
        slot.lease = lease
        pc.mark_seeded(lease, n - reuse)
        _PREFIX_SEEDED.inc(n - reuse)
        return n

    def _harvest(self, slot, deferred: bool):
        """A copy of the rows, deferred over a SNAPSHOT of history and
        arrays: device→host copies must not run under the scheduler's lock,
        and jax arrays are immutable (the slot may serve its next request)."""
        history, index = list(slot.history), slot.index
        kc, vc, pc = self._eng.k_cache, self._eng.v_cache, self.prefix_cache

        def insert():
            try:
                if len(history) >= pc.block_tokens:
                    with trace.span("batch.prefix_insert",
                                    {"slot": index, "tokens": len(history)}):
                        pc.insert(history, lambda t0, t1: (
                            np.asarray(kc[:, index, :, t0:t1]),
                            np.asarray(vc[:, index, :, t0:t1])))
            except Exception as e:  # degraded cache, never a scheduler error
                warn_degraded("insert", e)

        return insert if deferred else insert()

    def _read_blocks(self, slot, n: int, bt: int) -> list:
        k = np.asarray(self._eng.k_cache[:, slot.index, :, :n * bt])
        v = np.asarray(self._eng.v_cache[:, slot.index, :, :n * bt])
        return [(k[:, :, i:i + bt], v[:, :, i:i + bt])
                for i in range(0, n * bt, bt)]

    def _insert_host(self, span: list[int], blocks: list) -> int:
        pc = self.prefix_cache
        k = np.concatenate([np.asarray(b[0]) for b in blocks], axis=2)
        v = np.concatenate([np.asarray(b[1]) for b in blocks], axis=2)
        pc.insert(span, lambda t0, t1: (k[:, :, t0:t1], v[:, :, t0:t1]))
        # report what the cache actually HOLDS, not what it was handed: a
        # lease-pinned-full pool can refuse every block, and claiming the
        # span anyway would count an "imported" success for KV the
        # admission must then re-prefill
        return pc.covered_blocks(span)


class PoolSlotCache(_SlotCache):
    """KV in a (L, N, hk, bt, hs) device block pool, a block table a slot
    (`slot.blocks`, one pool ref an entry, retained across requests as the
    same-slot rewind's stock), the prefix cache a radix DIRECTORY over
    device blocks with the dense cache's host pool as its cold tier."""

    def __init__(self, eng, spec, slots, upload, *, prefix_cache, blocks,
                 block_tokens, q80):  # the dense cache's: the pool has its own
        super().__init__(eng, spec, upload)
        self.slots = slots  # the scheduler's list: re-pointed when replaced
        n_blocks, bt = eng.kv_pool
        n = len(slots)
        itemsize = eng.k_cache.dtype.itemsize
        self.block_tokens = bt
        self.kv_pool = DeviceKVPool(n_blocks, bt)
        self.tables_np = np.zeros((n, spec.seq_len // bt), np.int32)
        self._tables_dev = None  # rebuilt lazily after table edits
        self._demote_warm = False  # read_block compiled the gather's sizes
        # what a demoted block's rows weigh on the way to the host
        self.block_bytes = sum(c.nbytes // c.shape[1]
                               for c in pool_sides(eng))
        _STATE_BLOCK_BYTES.set(
            spec.state_block_bytes(itemsize) if spec.mixed else 0)
        # a convolution's snapshot lies in every block, a state-space
        # model's where `state_word` gives the block a pool entry
        self.stride = 0 if not spec.mixed else (
            STATE_STRIDE if spec.ssm else bt)
        self.no_stream = _NO_KV_STREAM if spec.mixed else None
        _MATRIX_BYTES.set(4 * int(np.prod(spec.state_matrix or (0,))))
        if spec.ssm:
            assert STATE_STRIDE % bt == 0, bt
            self.kv_pool.snapshots = SnapshotPool(
                spec.state_snapshots, spec.state_block_bytes(itemsize))
        if prefix_cache:
            cold = blocks or default_pool_blocks(
                (spec.n_layers, n, eng.k_cache.shape[2], spec.seq_len,
                 spec.head_size), itemsize, bt, n,
                token_values=sum(spec.cache_widths))
            self.prefix_cache = PagedPrefixCache(
                self.kv_pool, bt, cold_blocks=cold, q80=q80)

    def table(self):
        """Current (B, W) device block table, once a dispatch. Re-uploaded
        WHOLE after a table edit (`_table_row`), never patched: slots x the
        blocks of a context x 4 bytes of metadata (8 or 16 KB in the
        benchmark's cells); batch_table_uploads_total counts the dispatches
        that re-sent it, most of them while every row gains a block every
        few tokens (PERF.md section 5). The cost is the transfer, not the
        bytes. Never KV rows."""
        resent = self._tables_dev is None
        if resent:
            self._tables_dev = self._upload(self.tables_np)
            _TABLE_UPLOADS.inc()
        return self._tables_dev, resent

    def _table_row(self, slot) -> None:  # hot-path
        """Rewrite one slot's table row from slot.blocks (filler entries
        point at the scratch block, whose contents are never read)."""
        row = self.tables_np[slot.index]
        row[:] = 0
        row[:len(slot.blocks)] = slot.blocks
        self._tables_dev = None

    def release(self, slot) -> None:
        """Drop a slot's whole table (and the rewind stock it backs). The
        committed full blocks live on through any directory references."""
        if slot.blocks:
            self.kv_pool.decref(slot.blocks)
        slot.blocks = []
        slot.history = []
        slot.pos = 0
        self._table_row(slot)

    def reset(self) -> None:
        """Fresh pool arrays: every allocation and directory handle
        referenced the replaced buffers."""
        self.kv_pool.reset()
        if self.prefix_cache is not None:
            self.prefix_cache.reset()
        self.tables_np[:] = 0
        self._tables_dev = None

    def _alloc(self, n: int, exclude=None) -> list[int]:
        """Allocate n pool blocks, reclaiming directory/idle-slot stock
        under pressure; raises KVPoolExhausted (request-scope) when the
        pool genuinely cannot serve. `exclude` shields one slot from the
        idle-slot reclaim tier — the ADOPTING slot looks idle (req is
        bound only after `admit` returns), and releasing it mid-adopt
        would double-free the very blocks being rewired."""
        ids = self.kv_pool.alloc(n)
        if ids is None:
            self.reclaim(n, exclude=exclude)
            ids = self.kv_pool.alloc(n)
        if ids is None:
            raise KVPoolExhausted(
                f"device KV pool exhausted: {n} block(s) needed, "
                f"{self.kv_pool.free_blocks()} free after reclaim "
                "(raise --kv-pool-blocks or admit fewer long contexts)")
        return ids

    def reclaim(self, need: int, exclude=None) -> None:
        """Free device blocks: demote/evict LRU unreferenced directory
        nodes first (cold tier keeps the prefix servable), then drop idle
        slots' retained rewind tables — their committed blocks survive via
        the directory where it references them. `exclude` (see `_alloc`)
        is never released. Only the DEFICIT is reclaimed: demoting `need`
        blocks when all but one are already free would churn the directory
        (and its D2H copies) for nothing."""
        deficit = need - self.kv_pool.free_blocks()
        if deficit <= 0:
            return
        if self.prefix_cache is not None:
            self.demote(deficit)
        if self.kv_pool.free_blocks() >= need:
            return
        for sl in self.slots:
            if sl.req is None and sl.blocks and sl is not exclude:
                self.release(sl)
                if self.kv_pool.free_blocks() >= need:
                    return

    def demote(self, deficit: int) -> None:
        """Have the directory demote (or evict) `deficit` blocks. The
        victims' rows are read by ONE gather, enqueued here, ahead of
        whatever dispatch will write the freed blocks, and NOT waited for:
        the cold tier holds the pending read and `settle` makes host
        arrays of it while a later dispatch runs."""
        pool = pool_sides(self._eng)
        read = DemoteRead(pool)
        with trace.span("batch.demote") as sp:
            self.prefix_cache.reclaim(deficit, read.block)
            reads = read.issue(pool) if read.bids else 0
            sp.add(blocks=len(read.bids), reads=reads)
            if len(pool) > 2:  # of the bytes read, the snapshots'
                sp.add(state_bytes=len(read.bids) * pool[2].nbytes
                       // pool[2].shape[1])
        if reads:
            DEMOTE_READS.inc(reads)

    def settle(self, force: bool = False) -> None:
        """Where the scheduler only waits (a dispatch launched and not yet
        fetched; idle; close): pending demotions whose read has finished
        become host arrays. Never raises: the cache is an optimization."""
        pc = self.prefix_cache
        if pc is None or not pc.unsettled:
            return
        with trace.span("batch.demote_settle") as sp:
            try:
                blocks, waited = pc.settle(force)
                sp.add(blocks=blocks, waited=waited)
            except Exception as e:
                warn_degraded("demotion", e)

    def pending_bytes(self) -> int:
        """Demotion reads issued and not yet host arrays."""
        pc = self.prefix_cache
        return 0 if pc is None else pc.unsettled * self.block_bytes

    def read_block(self, bid: int):
        """Device→host copy of one pool block's rows (L, hk, bt, hs), waited
        for: the disaggregation export's read. It goes through the
        demotion's gather, and its first call on an engine compiles that
        gather at every size a reclaim can issue, so that no eviction ever
        compiles while serving (the benchmark's warm-up calls it for that)."""
        pool = pool_sides(self._eng)
        if not self._demote_warm:
            self._demote_warm = True
            for n in DEMOTE_SIZES[1:]:
                warm = DemoteRead(pool)
                warm.bids = [0] * n
                warm.issue(pool)
                warm.settle()
            if self.stride:
                # an admission's seed of a slot's running state, compiled
                # here too: what it writes (the scratch block's snapshot
                # behind slot 0's position bt) no sequence reads
                self._seed_state(0, 0, self.stride, entry=0)
        read = DemoteRead(pool)
        rows = read.block(bid)
        read.issue(pool)
        return rows.settle()

    def cover(self, slot, upto: int) -> None:
        """Grow the slot's table so every position < upto has a real block
        (writes beyond coverage would land in the scratch block — fine for
        parked garbage, fatal for committed rows)."""
        need = -(-min(upto, self._spec.seq_len) // self.block_tokens) \
            - len(slot.blocks)
        if need <= 0:
            return
        ids = self._alloc(need, exclude=slot)
        start = len(slot.blocks)
        slot.blocks.extend(ids)
        self.tables_np[slot.index, start:start + need] = ids
        self._tables_dev = None

    def own(self, slot, lo: int, hi: int) -> None:
        """Copy-on-write: make the blocks backing positions [lo, hi)
        exclusively owned before the slot writes there. A shared block
        (directory reference or a sibling slot's remap) gets a private copy
        (D2D, zero host bytes): its committed rows are never scribbled on."""
        bt = self.block_tokens
        eng = self._eng
        for idx in range(lo // bt, min(-(-hi // bt), len(slot.blocks))):
            bid = slot.blocks[idx]
            if not self.kv_pool.shared(bid):
                continue
            nb = self._alloc(1, exclude=slot)[0]
            _set_pool_sides(eng, [_pool_block_copy(c, bid, nb)
                                  for c in pool_sides(eng)])
            self.kv_pool.decref([bid])
            self.kv_pool.note_cow()
            slot.blocks[idx] = nb
            self.tables_np[slot.index, idx] = nb
            self._tables_dev = None

    def park(self, slot, p: int, hi: int) -> bool:
        if slot.req is None:
            # idle: drop the rewind stock instead of CoW-ing possibly
            # shared tail blocks for garbage (the directory keeps its refs)
            self.release(slot)
            return False
        self.truncate(slot, p)
        self.own(slot, p, hi)  # never scribble on the directory's rows
        return True

    def admit(self, slot, req, full: list[int], rewind: int):
        """The slot rewinds to `rewind` (state layers: to the newest
        snapshot under it, a block end), the directory extends that by a
        refcounted remap, and the state layers are seeded from the block
        that ends there. Returns (the rewind, where prefill starts)."""
        if self.stride:
            rewind = self.state_landing(
                rewind, lambda i: slot.blocks[i] if i < len(slot.blocks)
                else None)
        # the request's context: batch.prefix_seed carries its trace id
        with reqctx.use(req.ctx):
            reuse = self._adopt(slot, rewind, full)
            if self.stride and reuse:
                try:
                    self._seed_state(
                        slot.index,
                        slot.blocks[reuse // self.block_tokens - 1], reuse)
                    _STATE_RESTORES.inc()
                except LookupError:  # its entry went meanwhile: cold
                    reuse = self._adopt_rewind_only(slot, 0)
        return rewind, reuse

    def _adopt(self, slot, rewind: int, full: list[int]) -> int:
        """Extend the same-slot rewind with a DIRECTORY REMAP — shared full
        blocks are increfed into the slot's table (zero bytes moved), a
        partially-used boundary block is CoW'd so the slot can append, and
        cold (demoted) blocks pay exactly one host→device promotion upload.
        Returns the reuse length (the prefill start). Mirrors the dense
        seed's degraded-mode contract: any failure falls back to what the
        rewind already covered."""
        bt = self.block_tokens
        pc = self.prefix_cache
        eng = self._eng
        t0 = time.perf_counter()
        lease = None if pc is None else self._lookup(slot, full, rewind)
        if lease is None:
            # rewind-only: trim the retained table to the rewound prefix
            # and make its boundary block writable (the first append lands
            # at `rewind`, possibly inside a directory-shared block)
            reuse = self._adopt_rewind_only(slot, rewind)
            self.seed_ms += (time.perf_counter() - t0) * 1e3
            return reuse
        n = lease.tokens
        m = n // bt
        blocks: list[int] = []
        moved = 0
        try:
            with trace.span("batch.prefix_seed",
                            {"slot": slot.index, "tokens": n,
                             "rewind": rewind, "remap": True}):
                for i, node in enumerate(lease.nodes):
                    tier, h = node.handle
                    if tier == "cold":
                        # promote: one host→device upload, then the
                        # directory itself holds the device copy again.
                        # promote() takes the DIRECTORY's own ref — drop
                        # the allocation ref right after, or every
                        # promotion leaks one never-freeable block
                        # (k, v), and the block's state snapshot with them
                        rows = pc.fetch_cold(h)
                        nb = self._alloc(1, exclude=slot)[0]
                        _set_pool_sides(eng, [
                            _pool_block_set(c, nb, self._upload(a, eng.dtype))
                            for c, a in zip(pool_sides(eng), rows,
                                            strict=True)])
                        moved += sum(a.nbytes for a in rows)
                        pc.promote(node, nb)
                        self.kv_pool.decref([nb])
                        tier, h = node.handle
                    if i < m:
                        self.kv_pool.incref([h])
                        blocks.append(h)
                    else:
                        # partial boundary block: private copy (D2D) the
                        # slot can append into without touching the
                        # directory's committed rows
                        nb = self._alloc(1, exclude=slot)[0]
                        _set_pool_sides(eng, [_pool_block_copy(c, h, nb)
                                              for c in pool_sides(eng)])
                        self.kv_pool.note_cow()
                        blocks.append(nb)
        except Exception as e:
            if blocks:
                self.kv_pool.decref(blocks)
            pc.mark_unused(lease)
            warn_degraded("seed", e)  # fall back to the rewind stock
            self.seed_ms += (time.perf_counter() - t0) * 1e3
            return self._adopt_rewind_only(slot, rewind)
        old = slot.blocks
        slot.blocks = blocks
        if old:
            self.kv_pool.decref(old)
        self._table_row(slot)
        slot.lease = lease
        pc.mark_seeded(lease, n - rewind)
        _PREFIX_SEEDED.inc(n - rewind)
        REMAPPED.inc(m)
        if moved:
            SEED_BYTES.inc(moved)
            self.seed_bytes += moved
        self.seed_ms += (time.perf_counter() - t0) * 1e3
        return n

    def _adopt_rewind_only(self, slot, rewind: int) -> int:
        """Degraded-seed fallback: keep only the rewound prefix's blocks."""
        bt = self.block_tokens
        keep = min(-(-rewind // bt), len(slot.blocks))
        if keep < len(slot.blocks):
            self.kv_pool.decref(slot.blocks[keep:])
            del slot.blocks[keep:]
            self._table_row(slot)
        if rewind % bt:
            self.own(slot, rewind, rewind + 1)
        return rewind

    def _seed_state(self, slot: int, bid: int, pos: int,
                    entry: int | None = None) -> None:
        """A model with state layers: slot `slot`'s running state at position
        `pos` (a block boundary > 0: a prefix hit's or a rewind's) becomes
        what block `bid`, which ends there, snapshot: one small jitted copy
        on the device (models/forward.py seed_state). A state-space model's
        snapshot lies at the block's ENTRY of the snapshot pool (`entry`:
        given by the warm-up alone); LookupError where the block has none."""
        eng, spec = self._eng, self._spec
        if self._spec.ssm:
            bid = (entry if entry is not None
                   else self.kv_pool.snapshots.entry(bid))
            if bid is None:
                raise LookupError("the block carries no snapshot")
        eng.v_cache = seed_state(eng.v_cache, np.int32(slot), np.int32(bid),
                                 np.int32(pos), len(spec.state_layers),
                                 spec.state_rows)

    def state_landing(self, tokens: int, block_at) -> int:
        """The longest prefix of `tokens` positions a state model can
        continue from: a multiple of the stride whose last block carries a
        snapshot; 0 with none. `block_at(i)`: the device block that holds
        positions [i bt, (i + 1) bt) of the match, None where it has none."""
        n = tokens - tokens % self.stride
        if not self._spec.ssm:
            return n  # a convolution's state lies in every block
        while n > 0:
            bid = block_at(n // self.block_tokens - 1)
            if bid is not None and self.kv_pool.snapshots.entry(
                    bid) is not None:
                return n
            n -= self.stride
        return 0

    def chunk_limit(self, pos: int) -> int:
        """A state-space model's chunk never runs past a stride's end: the
        slot's running matrix is snapshot as the chunk leaves it."""
        return (self.stride - pos % self.stride if self._spec.ssm
                else self._spec.seq_len)

    def state_word(self, rows, starts: list[int], budget: list[int],
                   chunk: int = 0) -> tuple[list, dict]:
        """A state-space model's word to the dispatch about to be issued
        (`StateCache.ctl`): which slots are live in it, and for each row that
        will end a stride the entry of the snapshot pool its state goes to,
        allotted here. `rows` its (slot, request) pairs, `starts` and
        `budget` every slot's position and the tokens it advances; `chunk`:
        the prefilling row's tokens (0: every row steps). Returns the
        allotments (`settle_state` takes them) and the dispatch span's args
        (the work of exactly this dispatch, for a reader that joins it to
        its execution); nothing of either for any other model."""
        if not self._spec.ssm:
            return [], {}
        spec, eng = self._spec, self._eng
        word = np.zeros((2, len(self.tables_np), 1), np.int32)
        snaps = []
        layers = len(spec.state_layers)
        stepped = tokens = 0
        for slot, req in rows:
            i, n = slot.index, budget[slot.index]
            word[0, i, 0] = 1
            if chunk and n == chunk and n > 1:
                tokens += n
            else:
                stepped += n
            if (starts[i] + n) // self.stride > starts[i] // self.stride:
                last = (starts[i] + n) // self.stride * self.stride - 1
                bid = slot.blocks[last // self.block_tokens]
                entry, serial = self.kv_pool.snapshots.allot(bid)
                word[1, i, 0] = entry
                _SSM_STRIDE_ENDS.inc()
                if entry:
                    _SSM_SNAPSHOTS.inc()
                    snaps.append((slot, req, bid, serial, last))
        eng.v_cache = eng.v_cache._replace(ctl=self._upload(word))
        matrix = 4 * int(np.prod(spec.state_matrix)) * layers
        nbytes = 2 * matrix * (stepped + (1 if tokens else 0) + len(snaps))
        _SSM_ROWS.inc(stepped * layers)
        _SSM_CHUNK_TOKENS.inc(tokens * layers)
        _SSM_BYTES.inc(nbytes)
        return snaps, {"ssm_rows": stepped * layers,
                       "ssm_chunk": tokens * layers, "ssm_bytes": nbytes}

    def settle_state(self, snaps: list, accepted: bool,
                     scan: bool = False) -> None:
        """A dispatch's snapshot allotments once it is delivered (or
        flushed, `accepted` False): an entry counts where its row's request
        got past the stride's last position, else it goes back. A running
        matrix sums every earlier position and cannot be written over: a
        flushed `scan`'s survivors go back to H as the scan FOUND it, their
        accepted frontier (the scan kept it: `held`)."""
        for slot, req, bid, serial, last in snaps:
            ok = accepted and (req.done.is_set() if slot.req is not req
                               else slot.pos > last)
            self.kv_pool.snapshots.settle(bid, serial, ok)
        snaps.clear()
        vc = self._eng.v_cache
        if scan and not accepted and isinstance(vc, StateCache) and (
                vc.h is not None):
            self._eng.v_cache = vc._replace(h=vc.held, held=vc.h)

    def _harvest(self, slot, deferred: bool):
        """Zero-copy: the directory takes REFS on the slot's committed full
        blocks, and nothing is deferred (that would race the slot's
        reassignment CoW-ing or freeing the very blocks being inserted)."""
        try:
            n = len(slot.history) // self.block_tokens
            if n:
                with contextlib.nullcontext() if deferred else trace.span(
                        "batch.prefix_insert",
                        {"slot": slot.index, "tokens": n * self.block_tokens,
                         "remap": True}):
                    self.prefix_cache.insert_blocks(slot.history,
                                                    slot.blocks[:n])
        except Exception as e:  # a failed insert must not kill the scheduler
            warn_degraded("insert", e)

    def _read_blocks(self, slot, n: int, bt: int) -> list:
        return [self.read_block(bid) for bid in slot.blocks[:n]]

    def _insert_host(self, span: list[int], blocks: list) -> int:
        return self.prefix_cache.insert_cold(span, blocks)


def make_slot_cache(eng, *args, **kw):
    """The block pool where `eng` was given one, else the dense rows. Then
    `spec`; `slots`, the scheduler's slot list (the idle-slot reclaim tier
    walks `.slots`); `upload`, its counted host-to-device copy;
    `prefix_cache` (False, True, or a dense instance to share), its sizes."""
    kind = PoolSlotCache if eng.kv_pool is not None else DenseSlotCache
    return kind(eng, *args, **kw)
