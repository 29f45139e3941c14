"""Test configuration: force an 8-device virtual CPU platform BEFORE jax import.

The reference project tests multi-node slicing without a cluster (SURVEY.md §4); we improve
on that with a real 8-device mesh of virtual CPU devices, so TP/SP sharding tests exercise
actual collectives.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
# the suite's explicit request for Pallas interpret mode: kernels are never run
# that way because the backend happens to be the CPU (platform_env.py). Server
# and CLI subprocesses the tests start inherit it.
os.environ["DLT_PALLAS_INTERPRET"] = "1"
# no persistent compile cache for the suite: entry points started in-process
# or as subprocesses must not write <checkout>/.jax_cache or read stale
# programs back
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"

import jax  # noqa: E402

# golden tests compare against f32 numpy oracles; don't let matmuls drop to bf16
jax.config.update("jax_default_matmul_precision", "highest")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs[:8]


@pytest.fixture
def matvec_bound_under_1024(monkeypatch):
    """The one-row matvec's bound on K lowered so that a K of 1024 stands
    over it as A.X-K1's dense `w2` (18432) stands over the real one
    (17378): K x K/32 <= 8192 admits 512 and no more."""
    from distributed_llama_tpu.ops import pallas_q4, pallas_q8

    monkeypatch.setattr(pallas_q4, "_XEXP_VMEM_LIMIT", 8192)
    monkeypatch.setattr(pallas_q8, "_XEXP_VMEM_LIMIT", 8192)
    assert pallas_q8.q8_shape_supported(256, 512)
    assert not pallas_q8.q8_shape_supported(256, 1024)
