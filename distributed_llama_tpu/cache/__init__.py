"""Shared-prefix KV cache subsystem (docs/PREFIX_CACHE.md).

The cross-request layer between request admission and the device KV cache:

- `radix.py`    — token-block radix index (refcounts, LRU, hit accounting)
- `block_pool.py` — bounded host block store (hot tier + optional Q80 tier)
  + HostKVArena, the one RAM/memmap backend for every host-side KV spill
- `prefix_cache.py` — the facade: lookup/insert/leases/eviction + metrics
- `device_pool.py` — device-resident paged KV (docs/PAGED_KV.md): block
  pool refcounts + the radix DIRECTORY over device blocks (zero-copy
  remap hits, device→host demotion into the KVBlockPool tier)
- `single_slot.py`  — Engine (api_server --batch 1) client, retiring NaiveCache

BatchEngine integrates directly (runtime/batch_engine.py: admission seeding in
`_assign`, harvest in `_finish`).

Submodules are imported lazily (PEP 562): the fleet router (fleet/affinity.py)
reuses the dependency-free radix trie from a process that deliberately loads
no jax and registers no replica-tier metrics — an eager `from .block_pool
import ...` here would drag quants/jax and the prefix_cache_* metric families
into every `cache.radix` importer.
"""

from __future__ import annotations

__all__ = ["DeviceKVPool", "HostKVArena", "KVBlockPool", "PagedPrefixCache",
           "PrefixCache", "PrefixLease", "RadixIndex",
           "SingleSlotCache", "default_pool_blocks", "make_prefix_cache",
           "warn_degraded"]

_LAZY = {"DeviceKVPool": "device_pool", "HostKVArena": "block_pool",
         "KVBlockPool": "block_pool", "PagedPrefixCache": "device_pool",
         "PrefixCache": "prefix_cache",
         "PrefixLease": "prefix_cache", "RadixIndex": "radix",
         "SingleSlotCache": "single_slot"}


def __getattr__(name: str):
    try:
        mod = _LAZY[name]
    except KeyError:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    from importlib import import_module

    return getattr(import_module(f".{mod}", __name__), name)


def make_prefix_cache(cache_shape, itemsize: int, *, slots: int,
                      prefix_cache=True, blocks: int = 0,
                      block_tokens: int = 16,
                      q80: bool = False) -> PrefixCache | None:
    """The one PrefixCache construction path for every engine entry point
    (BatchEngine and the single-slot ApiState): resolves the enable flag /
    passthrough-instance convention and the auto pool sizing, so the two
    surfaces cannot drift."""
    from .prefix_cache import PrefixCache

    if not prefix_cache:
        return None
    if isinstance(prefix_cache, PrefixCache):
        return prefix_cache
    n = blocks or default_pool_blocks(cache_shape, itemsize, block_tokens,
                                      slots)
    return PrefixCache(max_blocks=n, block_tokens=block_tokens, q80=q80)


def warn_degraded(what: str, exc: Exception) -> None:
    """Uniform stderr warning for cache degradations (seed/insert failures):
    the cache is an optimization, never a correctness gate — callers fall
    back to plain prefill/no-harvest after calling this."""
    import sys

    print(f"⚠️  prefix-cache {what} failed ({type(exc).__name__}: {exc}); "
          "continuing without it", file=sys.stderr)


def default_pool_blocks(cache_shape, itemsize: int, block_tokens: int,
                        slots: int, byte_budget: int = 1 << 30,
                        token_values: int | None = None) -> int:
    """Default pool capacity: 4 full contexts per slot set, hard-capped by a
    host byte budget (~1 GiB). The budget wins even when it holds less than
    one full context — a partial-prefix cache (system prompts are usually
    far shorter than seq_len) is still useful, a silent multi-GiB host
    allocation is not. Size explicitly via prefix_cache_blocks for more."""
    n_layers, _b, hk, seq_len, hs = cache_shape
    blocks_per_seq = -(-seq_len // block_tokens)
    # values a token holds a layer a kv head, both sides: keys and values of
    # hs each unless the caller says otherwise (a latent row)
    block_bytes = (n_layers * hk * block_tokens * (token_values or 2 * hs)
                   * itemsize)
    cap = max(byte_budget // block_bytes, 1)
    return int(min(4 * max(slots, 1) * blocks_per_seq, cap))
