"""Device-resident paged KV: block-pool metadata + the radix *directory*.

The vLLM-style refactor (docs/PAGED_KV.md): instead of one contiguous
(L, B, hk, S, hs) cache row per slot, KV lives in a device-resident POOL of
fixed-size blocks — (L, N, hk, block_tokens, hs) per side — and each slot
carries a BLOCK TABLE mapping virtual positions [0, seq_len) to pool blocks
(position p lives in block table[p // bt] at offset p % bt). The arrays
themselves stay on the Engine (they are donated through every dispatch like
the dense caches were); this module owns only the HOST metadata:

- `DeviceKVPool` — refcounts + free list over the N block ids. Block 0 is a
  permanent SCRATCH block: idle rows park their masked garbage writes there
  and unpopulated table entries point at it, so a dispatch never needs a
  "no block" sentinel. A block with refcount 1 is exclusively owned by its
  holder and may be written; refcount > 1 means shared (a slot appending
  into a shared block must copy-on-write first — the engine does the device
  copy, this module just answers `shared()`).

- `PagedPrefixCache` — the host-side radix index re-cast as a *directory*
  over device blocks: a node's handle is a ("dev", block_id) reference (one
  pool refcount held per node), so a prefix hit is a refcounted block-table
  REMAP — zero bytes moved — and a finished slot's harvest is an incref,
  not a copy. Under pool pressure, LRU unreferenced directory nodes DEMOTE
  their blocks device→host into the existing `cache/block_pool.KVBlockPool`
  (the same hot/Q80 tier + LRU the host prefix cache already had — one
  unified spill path, docs/PAGED_KV.md "Eviction"); a later hit on a
  ("cold", handle) node pays one host→device upload and promotes back.
  A block of a model with state layers carries a second, TYPED payload,
  each such layer's state at the block's last position: it lies in an array
  of the engine's indexed by the same block ids, so the allocator, the
  refcounts, this directory and a remap serve it untouched, and only what
  moves a block's bytes (copy-on-write, demotion, promotion) moves it with
  them, as a third array beside (k, v) (docs/PAGED_KV.md "Typed block
  payload").
  A demotion does not wait for its device read: the node turns cold at
  once over a PENDING payload (block_pool.PendingRows) that `settle()`
  makes host arrays where the scheduler only waits. Victims come off two
  LRU heaps kept as nodes are touched, so a reclaim costs what it frees.

Locking: `DeviceKVPool` has its own lock (alloc/free/refs are touched from
the scheduler thread and close()); the directory keeps the PrefixCache
convention of one lock over tree + tier state.
"""

from __future__ import annotations

import heapq
import itertools
import threading

import numpy as np

from ..obs import metrics
from .radix import RadixIndex, RadixNode

__all__ = ["DeviceKVPool", "PagedPrefixCache", "PagedLease",
           "KVPoolExhausted", "SCRATCH_BLOCK"]

SCRATCH_BLOCK = 0  # permanent garbage target; never allocated, never read

# REMAPPED, SEED_BYTES, DEMOTE_READS and SETTLE_WAITS are bumped by the cache
# manager that drives the pool (runtime/slot_cache.py), the rest here

_POOL_BLOCKS = metrics.gauge(
    "paged_kv_pool_blocks", "Device KV pool capacity in blocks (--kv-pool-blocks)")
_POOL_FREE = metrics.gauge(
    "paged_kv_free_blocks", "Device KV pool blocks currently unallocated")
REMAPPED = metrics.counter(
    "paged_kv_remapped_blocks_total",
    "Directory blocks remapped into a slot's table at admission "
    "(zero-copy prefix reuse — no KV bytes moved)")
_COW = metrics.counter(
    "paged_kv_cow_blocks_total",
    "Copy-on-write block duplications (a slot about to append into a "
    "shared block gets a private device-side copy)")
_DEMOTED = metrics.counter(
    "paged_kv_demoted_blocks_total",
    "Directory blocks demoted device->host under pool pressure (into the "
    "unified cache/block_pool.py tier)")
DEMOTE_READS = metrics.counter(
    "paged_kv_demote_reads_total",
    "Device reads issued for demotions (one batched gather a reclaim: reads "
    "over paged_kv_demoted_blocks_total is 1/n for a deficit of n)")
SETTLE_WAITS = metrics.counter(
    "paged_kv_demote_settle_waits_total",
    "Demotion reads the device had not finished when their rows were needed "
    "as host arrays (a hit, a Q80 compression, close): the settle waited for "
    "the device. The scheduler's own settles take finished reads only")
_PROMOTED = metrics.counter(
    "paged_kv_promoted_blocks_total",
    "Cold directory blocks promoted host->device on a prefix hit")
SEED_BYTES = metrics.counter(
    "paged_kv_seed_bytes_total",
    "KV bytes moved host->device at admission seeding (0 for device-tier "
    "hits — the zero-copy remap claim, asserted by the shared-prefix bench; "
    "nonzero only when a cold block is promoted)")


_SNAPSHOT_EVICTIONS = metrics.counter(
    "paged_kv_ssm_snapshot_evictions_total",
    "Entries of the state snapshot pool given up for another block's: the "
    "least recently used snapshot that no dispatch in flight is writing")
_SNAPSHOT_BYTES = metrics.gauge(
    "kv_pool_ssm_snapshot_bytes",
    "Bytes of the state snapshot pool that blocks hold an entry of (entries "
    "held x a snapshot's bytes; 0: the model has no state-space layers)")


class SnapshotPool:
    """Which blocks of the device pool carry a STATE SNAPSHOT, for a model
    whose state layers hold a matrix a head (docs/PAGED_KV.md "Typed block
    payload"): one snapshot is tens of MB, so the pool of them has a few
    dozen entries and not one a block. Entry 0 is scratch (what a dispatch
    writes where it was given none); entries 1..n belong to at most one
    block each. An entry is ALLOTTED to a block before the dispatch that
    will cross the block's last position, CONFIRMED when that dispatch's
    tokens were accepted (only then can a prefix hit, a rewind or a resume
    land on it), DROPPED if they were not, and RELEASED with the block
    (`DeviceKVPool.decref`, so a demoted block gives its snapshot up). With
    no entry free the least recently used confirmed one is given up.
    `serial` tells an allotment from a later one of the same block."""

    def __init__(self, entries: int, entry_bytes: int = 0):
        self.entries = entries
        self.entry_bytes = entry_bytes
        self._lock = threading.Lock()  # guards: everything below
        self._free = list(range(entries, 0, -1))
        self._of: dict[int, int] = {}  # block -> entry
        self._serial: dict[int, int] = {}  # block -> its allotment's number
        self._valid: set[int] = set()  # blocks whose snapshot was confirmed
        self._used: dict[int, int] = {}  # block -> when it was last wanted
        self._tick = 0
        self.evictions = 0
        _SNAPSHOT_BYTES.set(0)

    def _give_up(self, bid: int) -> None:  # holds: self._lock
        self._free.append(self._of.pop(bid))
        self._serial.pop(bid, None)
        self._used.pop(bid, None)
        self._valid.discard(bid)
        _SNAPSHOT_BYTES.set(len(self._of) * self.entry_bytes)

    def allot(self, bid: int) -> tuple[int, int]:
        """(entry, serial) for the dispatch that will write block `bid`'s
        snapshot; entry 0 where none can be had (every entry is being
        written)."""
        with self._lock:
            self._tick += 1
            self._valid.discard(bid)
            if bid not in self._of:
                if not self._free:
                    victim = min(self._valid, key=self._used.get,
                                 default=None)
                    if victim is None:
                        return 0, 0
                    self._give_up(victim)
                    self.evictions += 1
                    _SNAPSHOT_EVICTIONS.inc()
                self._of[bid] = self._free.pop()
                _SNAPSHOT_BYTES.set(len(self._of) * self.entry_bytes)
            self._serial[bid] = self._used[bid] = self._tick
            return self._of[bid], self._tick

    def settle(self, bid: int, serial: int, accepted: bool) -> None:
        """The dispatch that wrote allotment `serial` was delivered: the
        snapshot counts from now on, or (its tokens were not accepted) the
        entry goes back. A later allotment of the block is left alone."""
        with self._lock:
            if self._serial.get(bid) != serial:
                return
            if accepted:
                self._valid.add(bid)
            else:
                self._give_up(bid)

    def entry(self, bid: int) -> int | None:
        """The entry of block `bid`'s confirmed snapshot, None without."""
        with self._lock:
            if bid not in self._valid:
                return None
            self._tick += 1
            self._used[bid] = self._tick
            return self._of[bid]

    def release(self, bid: int) -> None:
        with self._lock:
            if bid in self._of:
                self._give_up(bid)

    def reset(self) -> None:
        with self._lock:
            for bid in list(self._of):
                self._give_up(bid)

    def held(self) -> int:
        with self._lock:
            return len(self._of)


class KVPoolExhausted(RuntimeError):
    """The device block pool could not serve an allocation even after
    reclaiming the directory and idle slots. Attributable to the request
    whose growth needed the blocks: the scheduler fails only it."""

    fault_scope = "request"


class DeviceKVPool:
    """Refcount + free-list metadata for the device block pool. The arrays
    live on the Engine; `n_blocks` must match their N axis."""

    def __init__(self, n_blocks: int, block_tokens: int):
        assert n_blocks >= 2, "pool needs the scratch block plus one real block"
        assert block_tokens >= 1
        self.n_blocks = n_blocks
        self.block_tokens = block_tokens
        self._lock = threading.Lock()  # guards: _refs, _free
        self._refs = np.zeros(n_blocks, np.int32)
        self._refs[SCRATCH_BLOCK] = 1  # permanently pinned, never allocatable
        self._free = list(range(n_blocks - 1, 0, -1))  # stack, low ids first out
        # the snapshot pool of a model whose state is a matrix a head (set
        # by the engine that owns the arrays); None: a block's typed
        # payload, if any, lies in the block itself
        self.snapshots: SnapshotPool | None = None
        _POOL_BLOCKS.set(n_blocks)
        _POOL_FREE.set(len(self._free))

    # ------------------------------------------------------------------

    def alloc(self, n: int) -> list[int] | None:
        """Allocate n blocks (refcount 1 each), all-or-nothing. None when
        fewer than n are free — the caller reclaims and retries."""
        with self._lock:
            if len(self._free) < n:
                return None
            ids = [self._free.pop() for _ in range(n)]
            for b in ids:
                assert self._refs[b] == 0, (b, int(self._refs[b]))
                self._refs[b] = 1
            _POOL_FREE.set(len(self._free))
            return ids

    def incref(self, ids) -> None:
        with self._lock:
            for b in ids:
                assert self._refs[b] > 0, f"incref on free block {b}"
                self._refs[b] += 1

    def decref(self, ids) -> int:
        """Drop one reference per id; blocks reaching zero return to the
        free list. Returns how many were freed."""
        freed = 0
        with self._lock:
            for b in ids:
                assert b != SCRATCH_BLOCK and self._refs[b] > 0, (
                    b, int(self._refs[b]))
                self._refs[b] -= 1
                if self._refs[b] == 0:
                    self._free.append(b)
                    freed += 1
                    if self.snapshots is not None:
                        self.snapshots.release(b)  # freed with the block
            _POOL_FREE.set(len(self._free))
        return freed

    def shared(self, bid: int) -> bool:
        """True when more than one holder references the block — a slot must
        copy-on-write before appending into it."""
        with self._lock:
            return int(self._refs[bid]) > 1

    def free_blocks(self) -> int:
        with self._lock:
            return len(self._free)

    def used_blocks(self) -> int:
        with self._lock:
            return self.n_blocks - 1 - len(self._free)

    def reset(self) -> None:
        """Drop every allocation (engine re-initialization: the device
        arrays were rebuilt, nothing references the old blocks)."""
        with self._lock:
            self._refs[:] = 0
            self._refs[SCRATCH_BLOCK] = 1
            self._free = list(range(self.n_blocks - 1, 0, -1))
            _POOL_FREE.set(len(self._free))
        if self.snapshots is not None:
            self.snapshots.reset()

    def refcounts(self) -> np.ndarray:
        """Snapshot for tests/stats."""
        with self._lock:
            return self._refs.copy()

    def note_cow(self) -> None:
        _COW.inc()

    def stats(self) -> dict:
        with self._lock:
            free = len(self._free)
        return {"pool_blocks": self.n_blocks, "free_blocks": free,
                "block_tokens": self.block_tokens}


class PagedLease:
    """Refcount pin on the directory chain a request was admitted against
    (the paged analog of prefix_cache.PrefixLease — same lifecycle:
    mark_seeded/mark_unused + release, shrink on history truncation)."""

    __slots__ = ("nodes", "tokens")

    def __init__(self, nodes: list[RadixNode], tokens: int):
        self.nodes = nodes
        self.tokens = tokens


class PagedPrefixCache:
    """Radix directory over device blocks + unified host cold tier.

    Node handles are ("dev", block_id) — one DeviceKVPool reference held per
    node — or ("cold", host_handle) into `cold` (a cache/block_pool.py
    KVBlockPool: the existing host hot/Q80 tier, now the ONE demotion target
    for paged eviction). The public surface mirrors PrefixCache so the
    scheduler, /v1/stats and the benches keep one vocabulary."""

    def __init__(self, pool: DeviceKVPool, block_tokens: int,
                 cold_blocks: int = 0, q80: bool = False):
        from .block_pool import KVBlockPool

        self.pool = pool
        self.block_tokens = block_tokens
        self.radix = RadixIndex(block_tokens)
        self.cold = (KVBlockPool(cold_blocks, q80=q80)
                     if cold_blocks > 0 else None)
        self._lock = threading.Lock()  # guards: radix, _lru, _unsettled, hits, misses, unused_hits, hit_tokens, resident_tokens, evicted_blocks, demoted, promoted, prompt_tokens
        # LRU order without a tree walk: one heap a tier of (stamp, depth,
        # tick, node), entered whenever an unreferenced node is stamped,
        # released or changes tier, and checked against the node when popped
        # (a re-stamped, pinned, moved or dropped node's entry is stale). One
        # touch stamps one root path, so (stamp, depth) is the order the old
        # whole-tree walk and sort gave: oldest stamp first, root first.
        self._lru: dict[str, list] = {"dev": [], "cold": []}
        self._tick = itertools.count()
        # cold handle -> node, for demotions whose read is not settled yet
        self._unsettled: dict[int, RadixNode] = {}
        self.hits = 0
        self.misses = 0
        self.unused_hits = 0
        self.hit_tokens = 0
        self.resident_tokens = 0
        self.evicted_blocks = 0
        self.demoted = 0
        self.promoted = 0
        self.prompt_tokens = 0

    # ------------------------------------------------------------------
    # lookup / lease lifecycle (PrefixCache-compatible)
    # ------------------------------------------------------------------

    def lookup(self, prompt: list[int], cap: int | None = None
               ) -> PagedLease | None:
        """Longest directory block-prefix of `prompt` as an acquired lease —
        same reuse caps as PrefixCache.lookup (len-1, caller cap). No data
        is touched: the engine resolves each node's tier when it adopts the
        chain into a slot table."""
        with self._lock:
            self.prompt_tokens += len(prompt)
            nodes = self.radix.match(prompt)
            n = len(nodes) * self.block_tokens
            n = min(n, len(prompt) - 1)
            if cap is not None:
                n = min(n, cap)
            if n < 1:
                self._queue(nodes)
                self.misses += 1
                from .prefix_cache import _MISSES

                _MISSES.inc()
                return None
            keep = (n + self.block_tokens - 1) // self.block_tokens
            self._queue(nodes[keep:])  # matched (stamped) beyond the lease
            nodes = nodes[:keep]
            self.radix.acquire(nodes)
        return PagedLease(nodes, n)

    def mark_seeded(self, lease: PagedLease, used_tokens: int) -> None:
        from .prefix_cache import _HIT_TOKENS, _HITS

        with self._lock:
            self.hits += 1
            self.hit_tokens += used_tokens
        _HITS.inc()
        _HIT_TOKENS.inc(used_tokens)

    def note_resident(self, tokens: int) -> None:
        if tokens <= 0:
            return
        from .prefix_cache import _RESIDENT_TOKENS

        with self._lock:
            self.resident_tokens += tokens
        _RESIDENT_TOKENS.inc(tokens)

    def mark_unused(self, lease: PagedLease | None) -> None:
        if lease is None:
            return
        from .prefix_cache import _UNUSED

        with self._lock:
            self.unused_hits += 1
        _UNUSED.inc()
        self.release(lease)

    def release(self, lease: PagedLease | None) -> None:
        if lease is None:
            return
        with self._lock:
            nodes, lease.nodes = lease.nodes, []
            lease.tokens = 0
            if nodes:
                self.radix.release(nodes)
                self._queue(nodes)

    def shrink(self, lease: PagedLease, n_tokens: int) -> None:
        if n_tokens >= lease.tokens:
            return
        keep = (max(n_tokens, 0) + self.block_tokens - 1) // self.block_tokens
        with self._lock:
            drop, lease.nodes = lease.nodes[keep:], lease.nodes[:keep]
            lease.tokens = max(n_tokens, 0)
            if drop:
                self.radix.release(drop)
                self._queue(drop)

    # ------------------------------------------------------------------
    # directory mutation
    # ------------------------------------------------------------------

    def insert_blocks(self, tokens: list[int], block_ids: list[int]) -> int:
        """Attach the slot's committed full blocks to the directory BY
        REFERENCE: node i takes a pool ref on block_ids[i]. No data moves —
        this is the zero-copy harvest. Block positions the tree already
        covers keep their existing blocks (the slot's duplicate is simply
        not referenced and dies with the slot's own table). Returns how many
        new nodes were created."""
        from .prefix_cache import _INSERTED

        bt = self.block_tokens
        n_blocks = min(len(tokens) // bt, len(block_ids))
        if n_blocks == 0:
            return 0
        blocked = tokens[:n_blocks * bt]
        created = 0

        def make_handle(i: int):
            nonlocal created
            self.pool.incref([block_ids[i]])
            created += 1
            return ("dev", block_ids[i])

        with self._lock:
            self._queue(self.radix.insert(blocked, make_handle))
        _INSERTED.inc(created)
        return created

    def insert_cold(self, tokens: list[int], blocks: list) -> int:
        """Import externally-supplied HOST rows (disaggregation transfer,
        docs/DISAGG.md) as COLD directory nodes: `blocks[i]` is the (k, v)
        host pair for token block i of `tokens`. No device work — the
        existing admission path promotes cold nodes on the first hit, on
        the scheduler thread, so this is safe from any thread. Positions
        the tree already covers keep their existing (possibly device-tier)
        blocks; the supplied copy is simply unused there. A full cold tier
        first evicts its LRU unreferenced subtrees; if it still refuses,
        the chain stops at the last block that fit (prefix-closed by
        construction). Returns how many blocks of `tokens` the directory
        COVERS after the insert (pre-existing nodes count — the importer
        cares about servable span, not authorship)."""
        from .prefix_cache import _INSERTED

        if self.cold is None:
            return 0
        bt = self.block_tokens
        n_blocks = min(len(tokens) // bt, len(blocks))
        if n_blocks == 0:
            return 0
        blocked = tokens[:n_blocks * bt]
        created = 0
        dev_freed: list[int] = []

        def make_handle(i: int):
            nonlocal created
            k, v = blocks[i]
            h = self.cold.put(k, v)
            if h is None:
                dev_freed.extend(self._evict_cold_locked(1))
                h = self.cold.put(k, v)
            if h is None:
                return None  # cold tier pinned full: stop extending
            created += 1
            return ("cold", h)

        with self._lock:
            chain = self.radix.insert(blocked, make_handle)
            self._queue(chain)
        if dev_freed:
            # dev-tier descendants dropped with an evicted cold subtree
            # surrender their pool refs (same contract as reclaim())
            self.pool.decref(dev_freed)
        _INSERTED.inc(created)
        return len(chain)

    def promote(self, node: RadixNode, new_bid: int) -> None:
        """A cold node's rows were uploaded into freshly-allocated device
        block `new_bid` (the engine did the transfer): the directory adopts
        the device copy — one tier, one LRU — and frees the host block."""
        with self._lock:
            tier, h = node.handle
            assert tier == "cold", node.handle
            self.pool.incref([new_bid])
            node.handle = ("dev", new_bid)
            if self.cold is not None:
                self.cold.free(h)
            self._unsettled.pop(h, None)
            self._queue([node])
            self.promoted += 1
        _PROMOTED.inc()

    def _queue(self, nodes) -> None:  # holds: self._lock
        """Enter the unreferenced ones of `nodes` into their tier's LRU heap
        under their current stamp (after a touch, a release, a tier change).
        Stale entries go when popped, or here once they outnumber the tree
        four to one: a filter of the heap itself, never a walk of the tree."""
        for n in nodes:
            heap = self._lru.get(n.handle[0])
            if n.refs == 0 and heap is not None:
                heapq.heappush(heap, (n.stamp, n.depth, next(self._tick), n))
        for tier, heap in self._lru.items():
            if len(heap) > 64 + 4 * self.radix.nodes:
                heap[:] = [e for e in heap if self._current(tier, e)]
                heapq.heapify(heap)

    @staticmethod
    def _current(tier: str, entry) -> bool:
        stamp, _depth, _tick, node = entry
        return (node.handle[0] == tier and node.stamp == stamp
                and node.refs == 0)

    def _pop_lru(self, tier: str, seen: set) -> RadixNode | None:  # holds: self._lock
        """The least recently used unreferenced node of `tier` that this
        call has not been handed yet; the caller re-queues what it leaves
        in the tree."""
        heap = self._lru[tier]
        while heap:
            entry = heapq.heappop(heap)
            node = entry[3]
            if self._current(tier, entry) and id(node) not in seen:
                seen.add(id(node))
                return node
        return None

    def reclaim(self, n_blocks: int, read_block) -> int:
        """Free up to n_blocks device blocks by demoting (or, with no cold
        tier, evicting) LRU UNREFERENCED device-tier nodes. `read_block(bid)`
        gives the block's rows for demotion: a block_pool.PendingRows (the
        engine's: the read is issued after this returns, one gather for all
        the victims, and nobody waits for it here) or a (k, v) pair of host
        arrays (L, hk, bt, hs). The cold tier's room is made BEFORE a block
        is read, so each victim is read once. Returns how many device blocks
        were released to the pool's free list (shared blocks drop the
        directory's ref but stay alive for the slots still holding them)."""
        with self._lock:
            released: list[int] = []
            popped: list[RadixNode] = []
            seen: set = set()
            # keep going past victims that release nothing (a subtree drop
            # aborted by a lease pin): stopping at the first n_blocks LRU
            # nodes would let reclaimable younger nodes starve an allocation
            # into a spurious KVPoolExhausted
            while len(released) < n_blocks:
                node = self._pop_lru("dev", seen)
                if node is None:
                    break
                popped.append(node)
                bid = node.handle[1]
                if self.cold is not None:
                    if self.cold.full:
                        # cold tier full: evict ITS LRU content first by
                        # dropping the oldest cold-tier node outright (any
                        # dev-tier descendants dropped with it surrender
                        # their pool refs through `released` like every
                        # other eviction)
                        released.extend(self._evict_cold_locked(1))
                        if node.handle[0] != "dev":
                            continue  # the victim itself rode out with the
                            # dropped cold subtree (its ref is in released)
                    h = None
                    if not self.cold.full:
                        try:
                            h = self._put_cold(read_block(bid), node)
                        except Exception:
                            h = None  # demotion is best-effort; evict instead
                    if h is not None:
                        node.handle = ("cold", h)
                        self.demoted += 1
                        _DEMOTED.inc()
                        released.append(bid)
                        continue
                # no cold tier (or it refused): evict the node entirely. The
                # node may be mid-chain; prefix closure only constrains the
                # TREE, so drop this node and its whole subtree (descendants
                # without this block are unreachable prefixes anyway).
                released.extend(self._drop_subtree_locked(node))
            self._queue(popped)  # demoted: the cold heap; left as it was
            # (an aborted drop): the device heap again
            freed = 0
        if released:
            freed = self.pool.decref(released)
        return freed

    def _put_cold(self, rows, node: RadixNode) -> int | None:  # holds: self._lock
        from .block_pool import PendingRows

        if not isinstance(rows, PendingRows):
            return self.cold.put(*rows)
        h = self.cold.put_pending(rows)
        if h is not None:
            self._unsettled[h] = node
        return h

    @property
    def unsettled(self) -> int:
        """Demoted blocks whose rows are not host arrays yet."""
        with self._lock:
            return len(self._unsettled)

    def settle(self, force: bool = False) -> tuple[int, int]:
        """Make pending demotions' rows host arrays: the ones whose read has
        finished, or with `force` (close) all of them, waiting where it
        must. Called where the scheduler only waits (between a dispatch's
        launch and its fetch, and when idle); fetch_cold and the Q80 tier
        settle a block themselves when they need it first. A read that
        FAILED drops its node and the subtree under it: demotion is
        best-effort, and the eviction it stood in for is what is left.
        Returns (blocks settled, of which had to wait for the device)."""
        with self._lock:
            todo = [(h, self.cold.pending(h))
                    for h in self._unsettled] if self._unsettled else []
        done, failed, waited = [], [], 0
        for h, rows in todo:
            if rows is not None:
                ready = rows.ready()
                if not (ready or force):
                    continue
                try:
                    self.cold.settle(h)
                    waited += not ready
                except KeyError:
                    pass  # freed by another thread since the snapshot
                except Exception:
                    failed.append(h)
                    continue
            done.append(h)
        released: list[int] = []
        with self._lock:
            for h in done:
                self._unsettled.pop(h, None)
            for h in failed:
                node = self._unsettled.get(h)
                if node is not None and node.handle == ("cold", h):
                    # a pinned subtree aborts the drop: the entry stays and
                    # the next settle tries again
                    released.extend(self._drop_subtree_locked(node))
        if released:
            self.pool.decref(released)
        return len(done), waited

    def _drop_subtree_locked(self, node: RadixNode) -> list[int]:  # holds: self._lock
        """Remove `node` and every descendant from the tree; returns the
        device block ids whose directory refs must be dropped. Descendant
        nodes with refs > 0 (a live lease) abort the drop of that branch —
        the caller simply reclaims less this round."""
        from .prefix_cache import _EVICTED

        stack, doomed = [node], []
        for n in stack:
            stack.extend(n.children.values())
            doomed.append(n)
        if any(n.refs > 0 for n in doomed):
            return []
        del node.parent.children[node.key]
        self.radix.nodes -= len(doomed)
        self.evicted_blocks += len(doomed)
        _EVICTED.inc(len(doomed))
        dev_ids = []
        for n in doomed:
            tier, h = n.handle
            if tier == "dev":
                dev_ids.append(h)
            elif tier == "cold" and self.cold is not None:
                self.cold.free(h)
                self._unsettled.pop(h, None)
            n.handle = ("dropped", None)  # its LRU entries are stale now: a
            # block must not be released twice
        return dev_ids

    def _evict_cold_locked(self, n: int) -> list[int]:  # holds: self._lock
        """Drop the n LRU unreferenced cold-tier subtrees (frees host pool
        room for an incoming demotion). Returns the DEVICE block ids of any
        dev-tier descendants dropped with them — the caller must decref
        those into the pool, or the blocks leak (their directory refs die
        with the nodes)."""
        popped: list[RadixNode] = []
        seen: set = set()
        dev_ids: list[int] = []
        while len(popped) < n:
            node = self._pop_lru("cold", seen)
            if node is None:
                break
            popped.append(node)
            dev_ids.extend(self._drop_subtree_locked(node))
        self._queue(popped)  # a drop a lease aborted stays the LRU node
        return dev_ids

    def fetch_cold(self, handle: int):
        """Host rows of a cold block (dequantized when Q80), (k, v) and the
        block's state snapshot where it has one — the upload payload for
        promotion. Outside the lock (Q80 dequantize must not
        stall lookups; the caller's lease pins the node). A block whose
        demotion is still pending settles here, waiting for its read if it
        must; a read that failed raises (the caller falls back to prefill,
        the next settle() drops the node)."""
        assert self.cold is not None
        return self.cold.get(handle)

    def reset(self) -> None:
        """Drop the whole directory (engine re-initialization: the device
        pool was rebuilt, every dev handle is stale)."""
        with self._lock:
            self.radix = RadixIndex(self.block_tokens)
            self._lru = {"dev": [], "cold": []}
            self._unsettled.clear()  # pending reads are of the old arrays
            if self.cold is not None:
                for h in list(self.cold._blocks):
                    self.cold.free(h)

    def total_refs(self) -> int:
        with self._lock:
            return self.radix.total_refs()

    # ------------------------------------------------------------------
    # stats (PrefixCache-compatible keys + paged extras)
    # ------------------------------------------------------------------

    def stats(self) -> dict:
        with self._lock:
            looked = self.hits + self.unused_hits + self.misses
            dev_nodes = 0
            cold_nodes = 0
            stack = [self.radix.root]
            while stack:
                node = stack.pop()
                stack.extend(node.children.values())
                if node is not self.radix.root:
                    if node.handle[0] == "dev":
                        dev_nodes += 1
                    else:
                        cold_nodes += 1
            return {
                "paged": True,
                "hits": self.hits, "misses": self.misses,
                "unused_hits": self.unused_hits,
                "hit_tokens": self.hit_tokens,
                "resident_tokens": self.resident_tokens,
                "prompt_tokens": self.prompt_tokens,
                "hit_rate": (self.hit_tokens / self.prompt_tokens
                             if self.prompt_tokens else 0.0),
                "reuse_rate": ((self.hit_tokens + self.resident_tokens)
                               / self.prompt_tokens
                               if self.prompt_tokens else 0.0),
                "lookup_hit_rate": ((self.hits + self.unused_hits) / looked
                                    if looked else 0.0),
                "evicted_blocks": self.evicted_blocks,
                "demoted_blocks": self.demoted,
                "promoted_blocks": self.promoted,
                "tree_nodes": self.radix.nodes,
                "dev_blocks": dev_nodes, "cold_blocks": cold_nodes,
                "unsettled_blocks": len(self._unsettled),
                "pool_blocks": self.pool.n_blocks,
                "pool_free_blocks": self.pool.free_blocks(),
                "block_tokens": self.block_tokens,
                "q80_tier": self.cold.q80 if self.cold is not None else False,
            }
