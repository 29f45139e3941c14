"""Multi-host bootstrap: init_multihost + make_pod_mesh across REAL processes.

The reference bootstraps a cluster with `dllama worker --port ...` on each node plus
`--workers host:port ...` at the root (src/apps/dllama/dllama.cpp:205-221). The SPMD
replacement is jax.distributed: every host runs the SAME program and
init_multihost() wires them into one runtime whose jax.devices() is global.

This test launches TWO actual OS processes with JAX_PLATFORMS=cpu (2 local CPU
devices each), joins them through init_multihost on a localhost coordinator, builds
the pod mesh over the 4 global devices, and runs a shard_map psum over the
process-spanning tp axis — the same collective path a 405B tp=16 pod job exercises,
minus the ICI. Skipped quietly if the cross-process CPU collective backend is
unavailable in this jax build.
"""

import os
import socket
import subprocess
import sys

import numpy as np
import pytest

_WORKER = r"""
import os, sys
import jax
import numpy as np

coord, nproc, pid = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
from distributed_llama_tpu.parallel.mesh import AXIS_TP, init_multihost, make_pod_mesh

idx = init_multihost(coordinator=coord, num_processes=nproc, process_id=pid)
assert idx == pid, (idx, pid)
assert jax.process_count() == nproc
mesh = make_pod_mesh()  # all 4 global devices -> tp axis (single ICI-equivalent domain)
assert mesh.shape[AXIS_TP] == jax.device_count() == 2 * nproc, mesh.shape

import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

x = jax.device_put(
    np.arange(jax.device_count(), dtype=np.float32),
    NamedSharding(mesh, P(AXIS_TP)))
f = jax.jit(jax.shard_map(lambda v: jax.lax.psum(v, AXIS_TP), mesh=mesh,
                          in_specs=P(AXIS_TP), out_specs=P(AXIS_TP)))
out = f(x)
total = float(np.asarray(jax.device_get(out.addressable_shards[0].data))[0])
want = sum(range(jax.device_count()))
assert total == want, (total, want)
print(f"POD_OK process={pid} devices={jax.device_count()} psum={total}")
"""


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_pod_bootstrap(tmp_path):
    # watchdog lives in communicate(timeout=210) below; pytest-timeout is not
    # installed in this image, so a mark would be inert
    worker = tmp_path / "pod_worker.py"
    worker.write_text(_WORKER)
    coord = f"127.0.0.1:{_free_port()}"
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    env["PYTHONPATH"] = repo_root
    env.pop("PYTHONWARNINGS", None)
    procs = [
        subprocess.Popen([sys.executable, str(worker), coord, "2", str(i)],
                         stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                         text=True, env=env, cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=210)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    joined = "\n---\n".join(outs)
    lowered = joined.lower()
    if any(p.returncode != 0 for p in procs) and (
            ("multihost" in lowered or "multiprocess" in lowered)
            and ("not implemented" in lowered or "implemented" in lowered
                 and "n't" in lowered)):
        # e.g. "Multiprocess computations aren't implemented on the CPU
        # backend" (jaxlib wording varies across versions)
        pytest.skip(f"cross-process CPU collectives unavailable: {joined[-300:]}")
    assert all(p.returncode == 0 for p in procs), joined
    assert "POD_OK process=0 devices=4" in joined, joined
    assert "POD_OK process=1 devices=4" in joined, joined
