"""Process start-up: which device JAX runs on, what that decides, where compiles go.

Every entry point (apps/dllama.py, apps/api_server.py, bench.py and the children
of chip_smoke.py) calls `start()` once, before it builds an engine. It prints ONE
line naming the platform, device kind and count, the dtype and kernel policy they
resolve to, and the compile-cache directory, so a process that came up on the
wrong device says so on its first line instead of serving from it quietly. The
same block rides in `/healthz` and `/v1/stats`.

Nothing here guesses. JAX honours `JAX_PLATFORMS` itself; Pallas interpret mode
is something a caller asks for (`DLT_PALLAS_INTERPRET=1`: the test suite and the
CPU rehearsal of chip_smoke.py), never something inferred from the backend; and
the compile cache lives where `JAX_COMPILATION_CACHE_DIR` says or at one fixed
path in the checkout, because the path is part of the cache key.
"""

from __future__ import annotations

import json
import os
import threading
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

REPO_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
INTERPRET_ENV = "DLT_PALLAS_INTERPRET"
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def interpret_requested() -> bool:
    """True when the caller asked for Pallas interpret mode. The default of
    every kernel wrapper's `interpret=None`; without the request a kernel is
    compiled for the chip, and off the chip that is an error, not a detour."""
    return os.environ.get(INTERPRET_ENV, "").lower() in ("1", "true", "yes")


def device_block() -> dict[str, Any]:
    """The device as JAX reports it. Initializes the backend."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


@dataclass(frozen=True)
class KernelPolicy:
    dtype: Any
    use_pallas: bool
    reason: str  # why, when the caller left the choice open


def resolve_kernel_policy(use_pallas: bool | None = None,
                          dtype=None) -> KernelPolicy:
    """The dtype and kernel choice an Engine makes when its caller left them
    open: bf16 + Pallas kernels on a TPU backend (decode is HBM-bound, and the
    kernels only compile there), f32 + XLA elsewhere (the golden tests' exact
    path). Asking for kernels off the chip needs the interpret request."""
    platform = jax.default_backend()
    on_tpu = platform == "tpu"
    if dtype is None:
        dtype = jnp.bfloat16 if on_tpu else jnp.float32
    if use_pallas is None:
        reason = ("tpu backend" if on_tpu
                  else f"backend is {platform}, not tpu: XLA path")
        return KernelPolicy(dtype, on_tpu, reason)
    if use_pallas and not on_tpu and not interpret_requested():
        raise ValueError(
            f"use_pallas=True on the {platform} backend: the Pallas kernels "
            f"compile only for tpu. Set {INTERPRET_ENV}=1 to run them in "
            "interpret mode (tests, rehearsals), or drop the request.")
    return KernelPolicy(dtype, bool(use_pallas), "requested")


class CompileStats:
    """Backend compiles seen by this process: how many programs, how long, and
    how many came back from the persistent cache (jax.monitoring events)."""

    def __init__(self):
        self._lock = threading.Lock()  # guards: programs, seconds, cache_hits
        self._installed = False
        self.programs = 0
        self.seconds = 0.0
        self.cache_hits = 0

    def install(self) -> None:
        if self._installed:
            return
        self._installed = True
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, seconds: float, **_kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            with self._lock:
                self.programs += 1
                self.seconds += seconds

    def _on_event(self, event: str, **_kw) -> None:
        if event == CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits += 1

    def snapshot(self) -> dict[str, Any]:
        with self._lock:
            return {"programs": self.programs,
                    "seconds": round(self.seconds, 3),
                    "cache_hits": self.cache_hits}


# process-wide, like the obs/metrics registry: listeners attach in start()
xla_compiles = CompileStats()


def place_compile_cache() -> str:
    """Where compiled programs persist: `JAX_COMPILATION_CACHE_DIR` when the
    caller set it (JAX reads it itself; nothing is set in code), otherwise
    `<checkout>/.jax_cache`. Never a temp name, pid or time."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    path = os.path.join(REPO_DIR, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def describe(dtype, use_pallas) -> dict[str, Any]:
    """The block the start-up line, `/healthz` and `/v1/stats` carry: the
    device, and the dtype, kernel policy and compile cache in force."""
    cache_on = (jax.config.jax_enable_compilation_cache
                and jax.config.jax_compilation_cache_dir)
    return dict(
        device_block(), dtype=jnp.dtype(dtype).name,
        kernels=("xla" if not use_pallas else
                 "pallas-interpret" if interpret_requested() else "pallas"),
        compile_cache=jax.config.jax_compilation_cache_dir if cache_on
        else None)


def start(use_pallas: bool | None = None, dtype=None) -> dict[str, Any]:
    """Entry-point start-up. Places the compile cache, initializes the
    backend, resolves the kernel policy the entry point's flags ask for, and
    prints the one start-up line. Returns the block that line carries."""
    place_compile_cache()
    xla_compiles.install()
    policy = resolve_kernel_policy(use_pallas, dtype)
    block = dict(describe(policy.dtype, policy.use_pallas),
                 kernel_reason=policy.reason)
    print("🧭 startup " + json.dumps(block), flush=True)
    return block
