"""Paged (out-of-core) KV cache: host/disk-resident history + device hot ring.

TPU-native equivalent of the reference's `--kv-cache-storage disc`
(src/transformer.cpp:312-318, src/utils.cpp:50-67 — the KV cache mmap'd to disk
files so contexts larger than RAM still run, at page-fault speed). On TPU the
chip can only attend HBM-resident keys, so the same capacity valve is built the
flash-attention way instead of the mmap way:

- The device cache keeps a RING of the R most recent positions (slot = position
  mod R) — decode's hot window stays HBM-fast.
- Every committed row is also appended to an authoritative HOST store (RAM for
  "host", an np.memmap file pair for "disc" — the direct descendant of the
  reference's createMmap'd kvCache files).
- Attention over the cold history [0, pos-R) is computed ON HOST per layer
  (one jax.pure_callback per layer inside the layer scan) and merged with the
  device's hot segment by the flash-attention segment identity
  (ops/attention.py merge_attention_partials) — mathematically exact, not an
  approximation (no history truncation).

Cost model (honest): each decoded token reads the entire cold cache from host
memory — bytes = L * 2 * hk * (pos - R) * hs * itemsize — plus L small
host<->device callback round-trips. At 7B/16k ctx that is ~2-8 GB/token from
host DRAM/disk page cache: a capacity valve, not a fast path (the reference's
disc mode pays the same shape of cost through page faults). For speed at long
context, shard the cache over chips with --sp (ring attention) instead; use
paged mode when the context simply does not fit the chips you have.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..models.forward import forward, init_kv_cache
from ..models.spec import ModelSpec
from ..obs import metrics, trace
from ..ops.rope import RopeTables
from ..resilience import faults

_RESIDENT = metrics.gauge(
    "paged_resident_positions", "HBM hot-ring slots (--kv-cache-resident)")
_STORE_BYTES = metrics.gauge(
    "paged_store_bytes", "Authoritative host/disc KV store allocation")
_APPENDED = metrics.counter(
    "paged_appended_rows_total", "Positions committed to the host store")
_SPILL_BYTES = metrics.counter(
    "paged_spill_bytes_total", "Bytes written to the disc-backed store (mmap)")
_COLD_CALLS = metrics.counter(
    "paged_cold_attend_calls_total", "Host cold-attention callbacks served")
_COLD_BYTES = metrics.counter(
    "paged_cold_bytes_total", "Cold K/V bytes read from the host store")


class HostKVStore:
    """Authoritative full-context KV store on host RAM ("host") or an
    np.memmap'd file pair ("disc"). Layout (L, B, hk, S, hs), same axis order
    as the device caches.

    Storage (allocation, memmap files, owned-temp-dir weakref cleanup) is
    delegated to cache/block_pool.HostKVArena — the ONE host-spill backend
    (ISSUE 12 satellite: this module previously carried its own duplicate
    of that logic); this class keeps only the paged-attention semantics
    (append discipline + the per-layer cold-attention callback)."""

    def __init__(self, spec: ModelSpec, resident: int, *, batch: int = 1,
                 storage: str = "host", directory: str | None = None,
                 dtype=np.float32):
        from ..cache.block_pool import HostKVArena

        self.spec = spec
        self.resident = resident
        self.storage = storage
        shape = (spec.n_layers, batch, spec.n_kv_heads, spec.seq_len,
                 spec.head_size)
        self._arena = HostKVArena(shape, dtype, storage=storage,
                                  directory=directory)
        _RESIDENT.set(resident)
        _STORE_BYTES.set(self.nbytes())

    # storage facade: existing callers (engine.py seek/append paths, tests)
    # read .k/.v/.paths directly — keep them as live views of the arena
    @property
    def k(self):
        return self._arena.k

    @property
    def v(self):
        return self._arena.v

    @property
    def paths(self):
        return self._arena.paths

    @property
    def _owned_dir(self):
        return self._arena._owned_dir

    def cleanup(self) -> None:
        """Delete the cache file pair and its directory IF the arena created
        the directory itself (mkdtemp default). Idempotent."""
        self._arena.cleanup()

    def nbytes(self) -> int:
        return self._arena.nbytes()

    def append(self, k_rows: np.ndarray, v_rows: np.ndarray, pos: int) -> None:
        """Write the step's new rows (L, B, hk, T, hs) at positions
        [pos, pos+T)."""
        faults.fire("paged.append", pos=pos)
        t = k_rows.shape[3]
        self.k[:, :, :, pos:pos + t] = k_rows
        self.v[:, :, :, pos:pos + t] = v_rows
        _APPENDED.inc(t)
        if self.storage == "disc":
            _SPILL_BYTES.inc(k_rows.nbytes + v_rows.nbytes)

    def cold_attend(self, layer: int, q: np.ndarray, start_pos: int
                    ) -> tuple[np.ndarray, np.ndarray]:
        """Host-side attention partial over the cold history [0, start_pos-R).

        q: (B, T, hq, hs) f32. Returns (normalized out (B, T, hq, hs) f32,
        lse (B, T, hq) f32); an empty cold segment returns lse -inf (zero
        weight under the merge). All cold positions precede every query
        position, so no causal mask is needed."""
        faults.fire("paged.cold_attend", layer=layer)
        b, t, hq, hs = q.shape
        cold = max(0, int(start_pos) - self.resident)
        if cold <= 0:
            return (np.zeros((b, t, hq, hs), np.float32),
                    np.full((b, t, hq), -np.inf, np.float32))
        with trace.span("paged.cold_attend", {"layer": layer, "cold": cold}):
            return self._cold_attend_traced(layer, q, cold)

    def _cold_attend_traced(self, layer: int, q: np.ndarray, cold: int
                            ) -> tuple[np.ndarray, np.ndarray]:
        b, t, hq, hs = q.shape
        hk = self.k.shape[2]
        g = hq // hk
        kc = np.asarray(self.k[layer, :, :, :cold], np.float32)  # (B,hk,C,hs)
        vc = np.asarray(self.v[layer, :, :, :cold], np.float32)
        _COLD_CALLS.inc()
        _COLD_BYTES.inc(kc.nbytes + vc.nbytes)
        qg = q.reshape(b, t, hk, g, hs) * np.float32(1.0 / math.sqrt(hs))
        scores = np.einsum("btkgd,bkcd->btkgc", qg, kc)  # (B,T,hk,g,C)
        m = scores.max(axis=-1)
        e = np.exp(scores - m[..., None])
        l = e.sum(axis=-1)
        out = np.einsum("btkgc,bkcd->btkgd", e, vc) / l[..., None]
        lse = m + np.log(l)
        return (out.reshape(b, t, hq, hs).astype(np.float32),
                lse.reshape(b, t, hq).astype(np.float32))


def init_ring_cache(spec: ModelSpec, resident: int, *, batch: int = 1,
                    dtype=jnp.float32):
    """Device hot-ring caches: (L, B, hk, R, hs) — seq axis sized to the
    resident window instead of seq_len."""
    return init_kv_cache(spec, batch=batch, dtype=dtype, seq_len=resident)


def make_paged_step(spec: ModelSpec, store: HostKVStore, *, dtype=jnp.float32,
                    use_pallas: bool = False):
    """Jitted single-device paged forward step.

    Returns fn(params, rope, tokens, kc, vc, start_pos) ->
    (logits, kc, vc, (k_rows, v_rows)). The caller must append the returned
    rows to `store` (Engine.infer_chunk does) — the host store is the
    authoritative history the per-layer cold callback reads."""

    def cold_host(layer_idx, q, start_pos):
        return store.cold_attend(int(layer_idx), np.asarray(q, np.float32),
                                 int(start_pos))

    def paged_cold(layer_idx, q, start_pos):
        shapes = (jax.ShapeDtypeStruct(q.shape, jnp.float32),
                  jax.ShapeDtypeStruct(q.shape[:-1], jnp.float32))
        return jax.pure_callback(cold_host, shapes, layer_idx, q, start_pos)

    fwd = functools.partial(forward, spec=spec, dtype=dtype, axis_name=None,
                            use_pallas=use_pallas, attn_window=None,
                            paged_cold=paged_cold)
    rope_type = spec.rope_type

    def step(p, rope_cos, rope_sin, tokens, kc, vc, start_pos):
        rope = RopeTables(rope_cos, rope_sin, rope_type)
        return fwd(p, rope=rope, tokens=tokens, k_cache=kc, v_cache=vc,
                   start_pos=start_pos)

    jitted = jax.jit(step, donate_argnums=(4, 5))

    def run(p, rope: RopeTables, tokens, kc, vc, start_pos):
        return jitted(p, rope.cos, rope.sin, tokens, kc, vc, start_pos)

    return run
