"""Paged (out-of-core) KV cache: host/disc store + device hot ring + merged
cold attention (runtime/paged_cache.py) — the TPU-native rebuild of the
reference's `--kv-cache-storage disc` (transformer.cpp:312-318, utils.cpp:50-67).

The load-bearing property is EXACTNESS: paged attention is the flash-attention
segment decomposition, not an approximation, so a paged engine must produce the
same logits as a plain full-HBM engine at every step — including after the ring
has wrapped several times and most of the history is cold."""

import numpy as np
import pytest
import jax.numpy as jnp

from distributed_llama_tpu.models.params import init_random_params
from distributed_llama_tpu.models.spec import ArchType, ModelSpec
from distributed_llama_tpu.quants import FloatType
from distributed_llama_tpu.runtime.engine import Engine
from distributed_llama_tpu.runtime.sampler import Sampler

SPEC = dict(arch_type=ArchType.LLAMA, dim=64, hidden_dim=128, n_layers=3,
            n_heads=4, n_kv_heads=2, vocab_size=96, seq_len=256)
RESIDENT = 64  # already a multiple of 64; seq_len >> resident so cold is real


@pytest.fixture(scope="module")
def spec_params():
    spec = ModelSpec(**SPEC).resolved()
    return spec, init_random_params(spec, FloatType.Q40, seed=11)


def _engines(spec, params, storage, tmp=None):
    ref = Engine(spec, dict(params), tp=1, dtype=jnp.float32)
    paged = Engine(spec, dict(params), tp=1, dtype=jnp.float32,
                   kv_cache_storage=storage, kv_cache_resident=RESIDENT,
                   kv_cache_dir=str(tmp) if tmp else None)
    assert paged.paged and paged.kv_resident == RESIDENT
    assert paged.k_cache.shape[3] == RESIDENT  # hot ring, not seq_len
    return ref, paged


def _drive(ref, paged, rng, n_steps=150, chunk_mix=(64, 8, 1, 1, 7, 1)):
    """Feed identical random chunks through both engines; compare every
    logits vector. The mix crosses the cold boundary (pos 64) and wraps the
    ring twice (pos 128, 192)."""
    pos = 0
    i = 0
    while pos < n_steps:
        t = chunk_mix[i % len(chunk_mix)]
        t = min(t, n_steps - pos)
        toks = rng.integers(0, ref.spec.vocab_size, size=t).tolist()
        lr = ref.infer_chunk(toks)
        lp = paged.infer_chunk(toks)
        np.testing.assert_allclose(
            lp, lr, rtol=2e-4, atol=2e-4,
            err_msg=f"paged logits diverged at pos {pos}..{pos + t}")
        pos += t
        i += 1
    assert ref.pos == paged.pos == n_steps


def test_host_paged_matches_full_cache(spec_params):
    spec, params = spec_params
    ref, paged = _engines(spec, params, "host")
    _drive(ref, paged, np.random.default_rng(0))


def test_disc_paged_matches_full_cache_and_creates_mmap(spec_params, tmp_path):
    spec, params = spec_params
    ref, paged = _engines(spec, params, "disc", tmp=tmp_path)
    assert paged.store.paths is not None
    _drive(ref, paged, np.random.default_rng(1), n_steps=100)
    # the mmap file pair exists and is sized for the FULL context
    import os

    expected = (spec.n_layers * spec.n_kv_heads * spec.seq_len
                * spec.head_size * 4)
    for p in paged.store.paths:
        assert os.path.exists(p)
        assert os.path.getsize(p) == expected


def test_paged_generate_greedy_matches(spec_params):
    """End-to-end generate(): greedy decode far past the resident window must
    emit the same tokens as the full-cache engine."""
    spec, params = spec_params
    ref, paged = _engines(spec, params, "host")
    prompt = list(range(10, 80))  # prefill 70 > resident 64
    out_r, _ = ref.generate(prompt, 60, Sampler(spec.vocab_size, temperature=0.0))
    out_p, _ = paged.generate(prompt, 60,
                              Sampler(spec.vocab_size, temperature=0.0))
    assert out_r == out_p


def test_paged_reset_discards_stale_history(spec_params):
    """reset() + re-run must equal a fresh engine: stale ring slots and stale
    host-store rows beyond the new pos are never read."""
    spec, params = spec_params
    _, paged = _engines(spec, params, "host")
    rng = np.random.default_rng(2)
    toks = rng.integers(0, spec.vocab_size, size=90).tolist()
    for t in (64, 8, 8, 8, 1, 1):  # fill past the cold boundary
        paged.infer_chunk(toks[:t])
        toks = toks[t:]
    paged.reset()
    fresh = Engine(spec, dict(params), tp=1, dtype=jnp.float32,
                   kv_cache_storage="host", kv_cache_resident=RESIDENT)
    probe = list(range(5, 75))
    np.testing.assert_allclose(paged.infer_chunk(probe[:64]),
                               fresh.infer_chunk(probe[:64]),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(paged.infer_chunk(probe[64:]),
                               fresh.infer_chunk(probe[64:]),
                               rtol=2e-4, atol=2e-4)


def test_a_failed_cold_step_leaves_the_engine_where_it_was(spec_params):
    """A cold callback that raises takes the step's donated ring with it:
    the engine rebuilds the ring from the host store, so the SAME chunk sent
    again gives the logits of a run that never failed (no reset needed)."""
    from distributed_llama_tpu.resilience import faults
    from distributed_llama_tpu.resilience.faults import FaultSpec

    spec, params = spec_params
    ref, paged = _engines(spec, params, "host")
    rng = np.random.default_rng(4)
    toks = rng.integers(0, spec.vocab_size, size=150).tolist()
    for eng in (ref, paged):  # past the cold boundary and one wrap
        for lo, hi in ((0, 64), (64, 128), (128, 140)):
            eng.infer_chunk(toks[lo:hi])
    with faults.active(FaultSpec("paged.cold_attend", kind="error", count=1)):
        with pytest.raises(Exception):
            paged.infer_chunk(toks[140:148])
    faults.uninstall()
    assert paged.pos == 140
    np.testing.assert_allclose(paged.infer_chunk(toks[140:148]),
                               ref.infer_chunk(toks[140:148]),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(paged.infer_chunk(toks[148:149]),
                               ref.infer_chunk(toks[148:149]),
                               rtol=2e-4, atol=2e-4)


def test_warm_phase_skips_cold_callbacks(spec_params):
    """While pos + T <= resident the cold segment is provably empty: the
    engine must drive the callback-free plain step (no host round-trips), and
    the host store must still receive every committed row so the first paged
    step after the wrap sees the full history."""
    spec, params = spec_params
    ref, paged = _engines(spec, params, "host")
    calls = []
    orig = paged.store.cold_attend
    paged.store.cold_attend = lambda *a: (calls.append(a[0]), orig(*a))[1]
    rng = np.random.default_rng(4)
    toks = rng.integers(0, spec.vocab_size, size=80).tolist()
    for t in (40, 20):  # stays within the 64-slot ring (40+20 <= 64)
        lr = ref.infer_chunk(toks[:t])
        lp = paged.infer_chunk(toks[:t])
        np.testing.assert_allclose(lp, lr, rtol=2e-4, atol=2e-4)
        toks = toks[t:]
    assert not calls, "cold callbacks fired during the warm phase"
    # host store already holds the warm rows (appended from the device ring)
    assert np.abs(paged.store.k[:, :, :, :60]).sum() > 0
    assert np.abs(paged.store.k[:, :, :, 60:]).sum() == 0
    # crossing the boundary engages the paged step; logits still match
    lr = ref.infer_chunk(toks[:20])
    lp = paged.infer_chunk(toks[:20])
    np.testing.assert_allclose(lp, lr, rtol=2e-4, atol=2e-4)
    assert calls, "paged step crossed the ring boundary without cold callbacks"


def test_paged_disabled_when_context_fits(spec_params):
    spec, params = spec_params
    eng = Engine(spec, dict(params), tp=1, dtype=jnp.float32,
                 kv_cache_storage="host", kv_cache_resident=4096)
    assert not eng.paged  # nothing to page: full seq_len fits the budget
    assert eng.k_cache.shape[3] == spec.seq_len


def test_paged_seek_restores_ring_after_wrap(spec_params):
    """Prefix-reuse rewind (api_server NaiveCache): after the ring has
    wrapped, seek(pos) must restore the hot ring from the host store —
    wrapped slots hold the abandoned continuation's rows, which the
    slot-position formula would otherwise mislabel as earlier positions."""
    spec, params = spec_params
    rng = np.random.default_rng(5)
    shared = rng.integers(0, spec.vocab_size, size=90).tolist()  # wraps (>64)
    branch_a = rng.integers(0, spec.vocab_size, size=30).tolist()
    branch_b = rng.integers(0, spec.vocab_size, size=30).tolist()
    ref, paged = _engines(spec, params, "host")
    for eng in (ref, paged):
        pos = 0
        for t in (64, 8, 8, 8, 1, 1):
            eng.infer_chunk(shared[pos:pos + t])
            pos += t
        for i in range(0, 30, 10):
            eng.infer_chunk(branch_a[i:i + 10])
        eng.seek(90)  # rewind: drop branch A, keep the shared prefix
    for i in range(0, 30, 10):
        lr = ref.infer_chunk(branch_b[i:i + 10])
        lp = paged.infer_chunk(branch_b[i:i + 10])
        np.testing.assert_allclose(lp, lr, rtol=2e-4, atol=2e-4,
                                   err_msg=f"post-seek divergence at +{i}")


def test_moe_paged_matches_full_cache():
    """The paged branch is arch-independent (_attention only); pin that with a
    Mixtral-shaped MoE spec across the cold boundary."""
    spec = ModelSpec(arch_type=ArchType.MIXTRAL, dim=64, hidden_dim=96,
                     n_layers=2, n_heads=4, n_kv_heads=2, vocab_size=96,
                     seq_len=256, n_experts=4, n_active_experts=2).resolved()
    params = init_random_params(spec, FloatType.Q40, seed=13)
    ref, paged = _engines(spec, params, "host")
    _drive(ref, paged, np.random.default_rng(6), n_steps=100)


def test_disc_store_cleanup_owned_tempdir(spec_params):
    """A store that mkdtemp'd its own directory deletes it on cleanup();
    a caller-supplied directory is owner-kept."""
    import os

    from distributed_llama_tpu.runtime.paged_cache import HostKVStore

    spec, _ = spec_params
    st = HostKVStore(spec, 64, storage="disc")
    d = os.path.dirname(st.paths[0])
    assert os.path.exists(d) and st._owned_dir == d
    st.cleanup()
    assert not os.path.exists(d)
    st.cleanup()  # idempotent


def test_lse_merge_equals_monolithic_attention():
    """Property: splitting the key axis into segments and merging
    (out, lse) partials reproduces gqa_attention over the whole axis."""
    from distributed_llama_tpu.ops.attention import (
        gqa_attention, gqa_attention_lse, merge_attention_partials)

    rng = np.random.default_rng(3)
    b, t, hq, hk, hs, s = 2, 3, 4, 2, 8, 24
    q = jnp.asarray(rng.normal(size=(b, t, hq, hs)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, hk, s, hs)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, hk, s, hs)), jnp.float32)
    positions = jnp.asarray([20, 21, 22])  # all keys visible
    full = gqa_attention(q, k, v, positions)
    cut = 10
    out_a, lse_a = gqa_attention_lse(q, k[:, :, :cut], v[:, :, :cut], positions,
                                     key_positions=jnp.arange(cut))
    out_b, lse_b = gqa_attention_lse(q, k[:, :, cut:], v[:, :, cut:], positions,
                                     key_positions=jnp.arange(cut, s))
    merged = merge_attention_partials(out_a, lse_a, out_b, lse_b)
    np.testing.assert_allclose(np.asarray(merged).reshape(b, t, hq * hs),
                               np.asarray(full), rtol=1e-5, atol=1e-5)
    # empty segment: zero weight, merge degenerates to the other segment
    empty_out = jnp.zeros_like(out_a)
    empty_lse = jnp.full(lse_a.shape, -jnp.inf)
    out_f, lse_f = gqa_attention_lse(q, k, v, positions)
    alone = merge_attention_partials(out_f, lse_f, empty_out, empty_lse)
    np.testing.assert_allclose(np.asarray(alone).reshape(b, t, hq * hs),
                               np.asarray(full), rtol=1e-5, atol=1e-5)
