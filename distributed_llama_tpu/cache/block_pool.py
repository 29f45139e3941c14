"""Bounded host-side KV block store: F32/BF16 hot tier + optional Q80 cold tier.

Also home to `HostKVArena` — the RAM-or-disc (memmap) K/V arena that backs
every host-side KV spill in the repo: the long-context paged engine's
authoritative store (runtime/paged_cache.py HostKVStore delegates its
storage here) and, together with `KVBlockPool`, the device block pool's
cold tier (cache/device_pool.py demotes evicted directory blocks into a
KVBlockPool). One storage module, one cleanup discipline, one metric
family — the pre-ISSUE-12 state had paged_cache.py carrying its own
memmap + weakref-finalizer duplicate of this logic.

Each block holds the committed (K, V) rows of `block_tokens` consecutive
positions for every layer — shape (L, hk, block_tokens, hs) per side, exactly
the slice a slot's contiguous (B, hk, S, hs) device cache rows scatter from /
gather into (runtime/batch_engine.py admission seed and finish harvest).

Tiering applies the Opt4GPTQ co-optimization idea (PAPERS.md) to cache
capacity: hot blocks keep the engine dtype bit-exactly (a hot hit reproduces
the original prefill's rows and therefore the original tokens exactly); when
the hot tier overflows its budget, the LRU hot blocks are demoted to Q80
(quants.quantize_q80 over the flattened rows — 34 bytes per 32 values,
~3.8x denser than f32) and a cold hit pays one dequantize. Blocks whose
element count is not a multiple of the Q80 block size stay hot (never true
for even head sizes).

A block may carry a second, typed payload beside its rows (`state`): the
device pool's blocks of a model with state layers hold, for each such layer,
the layer's state at the block's last position (docs/PAGED_KV.md "Typed
block payload"), which is demoted and promoted with the block's keys and
values, stays uncompressed, and comes back from `get` as a third array.

A block may be committed while its rows are still ON THEIR WAY from the
device (`put_pending`: the device pool's demotion issues one batched read a
reclaim and does not wait for it, docs/PAGED_KV.md "Eviction"). Such a block
holds a `PendingRows` in place of arrays, counts against capacity at once and
becomes plain hot arrays, with no further copy, at the first of `settle`,
`get` or a Q80 compression that picks it.

The pool never evicts on its own: cache/prefix_cache.py drives eviction
through the radix index (which knows refcounts and LRU order) and calls
`free` with the handles the tree surrenders. No internal lock for the same
reason — the facade's single lock covers tree + pool together.
"""

from __future__ import annotations

import itertools

import numpy as np

from .wire import q80_compress, q80_compressible, q80_restore

__all__ = ["HostKVArena", "KVBlockPool", "PendingRows"]


class HostKVArena:
    """A (K, V) ndarray pair in host RAM ("host") or an np.memmap'd file
    pair ("disc"), with the self-cleaning temp-directory discipline the
    paged engine pioneered: a store whose directory WE created is removed
    at GC-or-exit via weakref.finalize (never atexit — that would pin every
    store for the process lifetime and leak multi-GB cache pairs across
    repeated in-process engine constructions); a caller-supplied directory
    is owner-kept. The one storage backend for every host-side KV spill
    (module docstring)."""

    def __init__(self, shape: tuple, dtype, *, storage: str = "host",
                 directory: str | None = None,
                 names: tuple[str, str] = ("key.cache", "value.cache")):
        import os

        assert storage in ("host", "disc"), storage
        self.storage = storage
        self.paths: tuple[str, str] | None = None
        self._owned_dir: str | None = None
        if storage == "disc":
            import shutil
            import tempfile
            import weakref

            if directory is None:
                directory = tempfile.mkdtemp(prefix="dlt_kv_cache_")
                self._owned_dir = directory
                self._finalizer = weakref.finalize(
                    self, shutil.rmtree, directory, ignore_errors=True)
            os.makedirs(directory, exist_ok=True)
            self.paths = (os.path.join(directory, names[0]),
                          os.path.join(directory, names[1]))
            self.k = np.memmap(self.paths[0], dtype=dtype, mode="w+",
                               shape=shape)
            self.v = np.memmap(self.paths[1], dtype=dtype, mode="w+",
                               shape=shape)
        else:
            self.k = np.zeros(shape, dtype)
            self.v = np.zeros(shape, dtype)

    def cleanup(self) -> None:
        """Delete the file pair + directory IF this arena created the
        directory itself. Idempotent; detaches the GC/exit finalizer."""
        if not self._owned_dir:
            return
        self._owned_dir = None
        self.k = self.v = None  # drop the memmaps before unlinking
        self._finalizer()

    def nbytes(self) -> int:
        return self.k.nbytes + self.v.nbytes


class PendingRows:
    """One block's (K, V) rows read off the device and not yet waited for:
    what `KVBlockPool.put_pending` takes in place of arrays. The reader that
    issued the read implements it (runtime/batch_engine.py: one gather a
    reclaim, each victim a row of its result)."""

    shape: tuple   # of the K side, (L, hk, block_tokens, hs)
    dtype: np.dtype
    nbytes: int    # both sides, once settled

    def ready(self) -> bool:
        """False while settle() would wait for the device."""
        raise NotImplementedError

    def settle(self) -> tuple[np.ndarray, ...]:
        """The rows as host arrays the caller may keep (no copy is taken of
        them), (k, v) or (k, v, state); waits for the read if it has to.
        Raises what the read raised, every time it is asked."""
        raise NotImplementedError


class _Block:
    __slots__ = ("k", "v", "state", "kq", "vq", "pending", "shape", "dtype",
                 "seq")

    def __init__(self, k: np.ndarray | None, v: np.ndarray | None, seq: int,
                 pending: PendingRows | None = None,
                 state: np.ndarray | None = None):
        self.k = k            # hot: ndarray (L, hk, N, hs); None when cold
        self.v = v            # or while `pending`
        self.state = state    # the typed payload, never compressed; or None
        self.kq = None        # cold: (values int8, scales f16) of the flat rows
        self.vq = None
        self.pending = pending  # the rows' read, until it is settled
        src = k if pending is None else pending
        self.shape = src.shape
        self.dtype = src.dtype
        self.seq = seq        # hot-LRU clock value of the last touch

    @property
    def cold(self) -> bool:
        return self.k is None and self.pending is None

    def nbytes(self) -> int:
        if self.pending is not None:
            return self.pending.nbytes
        more = 0 if self.state is None else self.state.nbytes
        if self.cold:
            return more + sum(q[0].nbytes + q[1].nbytes
                              for q in (self.kq, self.vq))
        return more + self.k.nbytes + self.v.nbytes

    def settle(self) -> None:
        """Pending rows -> hot arrays; idempotent, and safe against a racing
        second caller (both are handed the same arrays)."""
        rows = self.pending
        if rows is not None:
            k, v, *state = rows.settle()
            if self.pending is rows:  # not compressed by a racing put()
                self.k, self.v = k, v
                self.state = state[0] if state else None
                self.pending = None


class KVBlockPool:
    def __init__(self, max_blocks: int, hot_blocks: int | None = None,
                 q80: bool = False):
        assert max_blocks >= 1
        self.max_blocks = max_blocks
        # q80 off => everything stays hot (the bit-exact default; the
        # acceptance bar is token-identical output with the cache enabled)
        self.hot_blocks = (max_blocks if not q80
                           else max(1, hot_blocks if hot_blocks is not None
                                    else max_blocks // 4))
        self.q80 = q80
        self._blocks: dict[int, _Block] = {}
        self._next_handle = 0
        # LRU clock. itertools.count: get() runs OUTSIDE the facade lock
        # (prefix_cache.fetch) concurrently with locked put/demote — a plain
        # `+= 1` there would lose increments and hand two blocks the same
        # stamp, steering the q80 demotion at the wrong "LRU" block
        self._seq = itertools.count(1)
        self.demoted_blocks = 0  # lifetime hot->Q80 demotions (stats)

    def __len__(self) -> int:
        return len(self._blocks)

    @property
    def full(self) -> bool:
        return len(self._blocks) >= self.max_blocks

    def hot_count(self) -> int:
        return sum(1 for b in self._blocks.values() if not b.cold)

    def nbytes(self) -> int:
        return sum(b.nbytes() for b in self._blocks.values())

    # ------------------------------------------------------------------

    def put(self, k: np.ndarray, v: np.ndarray,
            state: np.ndarray | None = None) -> int | None:
        """Commit one block (copies taken), with its typed payload where it
        has one; returns a handle, or None when the pool is at capacity
        (caller evicts via the radix index and retries)."""
        if self.full:
            return None
        # the sides differ in their last axis alone: a latent row's second
        # side is empty (the equal-shape assertion that stood here refused
        # every latent pair, so a latent model's demotions ended as
        # evictions until ISSUE 39)
        assert k.shape[:-1] == v.shape[:-1]
        h = self._next_handle
        self._next_handle += 1
        self._blocks[h] = _Block(
            np.array(k, copy=True), np.array(v, copy=True), next(self._seq),
            state=None if state is None else np.array(state, copy=True))
        self._maybe_demote()
        return h

    def put_pending(self, rows: PendingRows) -> int | None:
        """put() for a block whose rows are still being read off the device:
        the handle is valid at once, no copy is ever taken (the read's own
        host arrays become the block's). None at capacity, as put()."""
        if self.full:
            return None
        h = self._next_handle
        self._next_handle += 1
        self._blocks[h] = _Block(None, None, next(self._seq), pending=rows)
        self._maybe_demote()
        return h

    def pending(self, handle: int) -> PendingRows | None:
        """The block's unsettled read; None once its rows are host arrays
        (or the handle was freed)."""
        b = self._blocks.get(handle)
        return b.pending if b is not None else None

    def settle(self, handle: int) -> None:
        """Make a pending block's rows host arrays now (waits for the read
        if it must). Raises what the read raised; the block then stays
        pending and its owner drops it. KeyError for a freed handle."""
        self._blocks[handle].settle()

    def get(self, handle: int) -> tuple[np.ndarray, ...]:
        """Block data in its original dtype/shape, (k, v) and behind them the
        block's typed payload where it has one; a cold block dequantizes
        (Q80 round-trip precision, not bit-exact — see module docstring); a
        pending one settles first (and raises what its read raised).

        Callers may read outside the facade lock (prefix_cache.lookup), so a
        concurrent demotion can clear b.k between a tier check and the read —
        snapshot the hot arrays once and fall through to the cold path when
        they vanished (demotion assigns kq/vq BEFORE clearing k/v)."""
        b = self._blocks[handle]
        b.seq = next(self._seq)
        b.settle()
        k, v = b.k, b.v
        more = () if b.state is None else (b.state,)
        if k is not None and v is not None:  # demotion may land between reads
            return (k, v, *more)
        k = q80_restore(b.kq, b.shape, b.dtype)
        v = q80_restore(b.vq, b.shape, b.dtype)
        return (k, v, *more)

    def is_cold(self, handle: int) -> bool:
        return self._blocks[handle].cold

    def free(self, handle: int) -> None:
        del self._blocks[handle]

    # ------------------------------------------------------------------

    def _maybe_demote(self) -> None:
        if not self.q80:
            return
        import heapq

        hot = [b for b in self._blocks.values() if not b.cold]
        excess = len(hot) - self.hot_blocks
        if excess <= 0:
            return
        # nsmallest over the (normally 1-deep) excess: O(H), not a full sort
        # per put — a harvest inserts block-by-block and each put can push the
        # tier over budget by at most one
        compressible = (b for b in hot if q80_compressible(b.shape))
        for b in heapq.nsmallest(excess, compressible, key=lambda b: b.seq):
            try:
                b.settle()  # a pending block's rows have to be here first
            except Exception:
                continue  # a failed read: its owner drops the block
            # cache/wire.py owns the round trip (shared with the disagg
            # wire codec so the tiers can never drift apart)
            b.kq = q80_compress(b.k)
            b.vq = q80_compress(b.v)
            b.k = b.v = None
            self.demoted_blocks += 1
