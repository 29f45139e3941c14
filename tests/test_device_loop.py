"""On-device decode loop tests (8-device CPU mesh via conftest).

The scan-based device loop must reproduce the host generation loop exactly under greedy
sampling (the host loop is itself tied to the reference's generate driver), and
device_sample must honor the reference Sampler's temperature/top-p semantics."""

import numpy as np
import jax
import jax.numpy as jnp

from distributed_llama_tpu.models.params import init_random_params
from distributed_llama_tpu.models.spec import ArchType, ModelSpec, RopeType
from distributed_llama_tpu.quants import FloatType
from distributed_llama_tpu.runtime.device_loop import device_sample
from distributed_llama_tpu.runtime.engine import Engine
from distributed_llama_tpu.runtime.sampler import Sampler


def _spec():
    return ModelSpec(arch_type=ArchType.LLAMA, dim=64, hidden_dim=128, n_layers=2,
                     n_heads=4, n_kv_heads=4, vocab_size=256, seq_len=64,
                     rope_type=RopeType.LLAMA).resolved()


def test_device_loop_matches_host_greedy():
    spec = _spec()
    params = init_random_params(spec, FloatType.Q40, seed=11)
    eng = Engine(spec, params, tp=2)
    prompt = [1, 7, 23, 5]
    sampler = Sampler(spec.vocab_size, temperature=0.0)
    want, _ = eng.generate(list(prompt), 12, sampler)

    eng.reset()
    got, stats = eng.generate_chunked(list(prompt), 12, sampler, chunk=5)
    assert got == want
    assert stats.generated_tokens == 12
    assert stats.prompt_tokens == len(prompt)

    # continuation state: pos advanced exactly by prompt-1 prefill + generated count
    assert eng.pos == len(prompt) - 1 + 12


def test_device_loop_stop_check_midchunk():
    spec = _spec()
    params = init_random_params(spec, FloatType.Q40, seed=11)
    eng = Engine(spec, params, tp=1)
    sampler = Sampler(spec.vocab_size, temperature=0.0)
    prompt = [1, 7, 23, 5]
    full, _ = eng.generate(list(prompt), 12, sampler)
    stop_at = full[3]

    eng.reset()
    got, _ = eng.generate_chunked(list(prompt), 12, sampler, chunk=8,
                                  stop_check=lambda t: t == stop_at)
    assert got == full[:4]
    assert eng.pos == len(prompt) - 1 + 4


def test_device_loop_context_end_tail():
    """Near seq_len the chunked loop must clamp to the context like the host loop
    (finishing via the per-token fallback, with no tail-sized recompile)."""
    spec = _spec()
    params = init_random_params(spec, FloatType.Q40, seed=11)
    eng = Engine(spec, params, tp=1)
    sampler = Sampler(spec.vocab_size, temperature=0.0)
    prompt = [1, 7, 23, 5]
    room = spec.seq_len - (len(prompt) - 1)
    want, _ = eng.generate(list(prompt), room + 10, sampler)

    eng.reset()
    got, _ = eng.generate_chunked(list(prompt), room + 10, sampler, chunk=16)
    assert got == want
    assert eng.pos <= spec.seq_len
    # only the full-size chunk (plus mode) was ever compiled for the scan loop
    assert all(c == 16 for c, _, _ in eng._decode_loops)


def test_device_sample_greedy_and_topp():
    key = jax.random.PRNGKey(0)
    logits = jnp.asarray(np.random.RandomState(0).randn(128).astype(np.float32)) * 3

    greedy = device_sample(logits, key, jnp.float32(0.0), jnp.float32(0.9))
    assert int(greedy) == int(np.argmax(np.asarray(logits)))

    # top-p: every sampled token must lie in the nucleus the host sampler would build
    probs = np.exp(np.asarray(logits) / 0.7)
    probs /= probs.sum()
    order = np.argsort(-probs)
    csum = np.cumsum(probs[order])
    nucleus = set(order[: int(np.argmax(csum > 0.8)) + 1].tolist())
    for i in range(20):
        t = int(device_sample(logits, jax.random.fold_in(key, i), jnp.float32(0.7),
                              jnp.float32(0.8)))
        assert t in nucleus

    # topp >= 1 takes the plain multinomial branch and still returns a valid id
    t = int(device_sample(logits, key, jnp.float32(1.3), jnp.float32(1.0)))
    assert 0 <= t < 128


def test_device_loop_with_sp_striped_matches_host():
    """Chunked device-loop generation on an sp=2 mesh (striped cache) must
    reproduce the tp-only host loop exactly — the loop carries the sharded
    caches through its scan over decode steps."""
    spec = _spec()
    params = init_random_params(spec, FloatType.Q40, seed=11)
    sampler = Sampler(spec.vocab_size, temperature=0.0)
    prompt = [1, 7, 23, 5]

    ref = Engine(spec, params, tp=1)
    want, _ = ref.generate(list(prompt), 12, sampler)

    eng = Engine(spec, params, tp=2, sp=2)
    got, _ = eng.generate_chunked(list(prompt), 12,
                                  Sampler(spec.vocab_size, temperature=0.0),
                                  chunk=5)
    assert got == want, (got, want)
