"""Device-mesh construction.

The reference's cluster topology is a TCP star of 2^n hosts (--workers host:port...,
socket.cpp:160-185). Here the topology is a jax.sharding.Mesh over TPU chips with named
axes:

    dp — data parallel (independent sequences; no reference equivalent, batch was 1)
    sp — sequence parallel (ring attention over the KV sequence axis; reference: absent)
    tp — tensor parallel (the reference's nSlices axis)

Collectives ride ICI when the mesh axes are laid out within a slice, DCN across slices —
XLA handles placement; we only pick axis sizes. The reference's 2^n-nodes restriction
(README.md:33-34) disappears: any divisor layout works.

A fourth capacity strategy, expert parallelism, rides the tp axis rather than adding a
mesh axis: moe_sharding="expert" (parallel/sharding.py) shards WHOLE experts over tp
while attention stays head-sharded — same mesh, different PartitionSpecs.

Pipeline parallelism is deliberately absent: for autoregressive DECODE a layer
pipeline serializes on the single in-flight token (the bubble is the whole pipeline),
and on TPU the per-layer all-reduce that tp costs rides ICI at full bandwidth, so tp
(+ ep for MoE capacity, + sp for context capacity) dominates pp at every scale the
BASELINE targets — including 405B on a v5p-16, which fits tp=16 across the slice.
pp earns its bubbles only in throughput-batch prefill/training regimes the reference
(and this framework's serving focus) does not target.
"""

from __future__ import annotations

import numpy as np
import jax
from jax.sharding import Mesh

AXIS_DP, AXIS_SP, AXIS_TP = "dp", "sp", "tp"


def _device_grid(shape: tuple[int, int, int], devs: list) -> np.ndarray:
    """Devices laid out so that mesh neighbours are ICI neighbours. On TPU the
    enumeration order is not a ring: a 2x2 host lists its chips (0,0) (1,0)
    (0,1) (1,1), whose second and fourth hops are diagonals, so the per-layer
    all-reduce ring would cross the torus. create_device_mesh reorders them
    (2x2: 0, 1, 3, 2). Other platforms, and shapes it cannot map, keep the
    enumeration order."""
    if devs[0].platform == "tpu":
        from jax.experimental import mesh_utils

        try:
            return mesh_utils.create_device_mesh(shape, devices=devs)
        except (ValueError, NotImplementedError, AssertionError) as e:
            print(f"💡 mesh {shape}: device order left as enumerated "
                  f"({e})", flush=True)
    return np.array(devs).reshape(shape)


def make_mesh(tp: int | None = None, sp: int = 1, dp: int = 1,
              devices: list | None = None) -> Mesh:
    """Build a (dp, sp, tp) mesh. Defaults: all devices on tp."""
    devs = devices if devices is not None else jax.devices()
    n = len(devs)
    if tp is None:
        assert n % (sp * dp) == 0, (n, sp, dp)
        tp = n // (sp * dp)
    need = dp * sp * tp
    assert need <= n, f"mesh {dp}x{sp}x{tp} needs {need} devices, have {n}"
    return Mesh(_device_grid((dp, sp, tp), list(devs[:need])),
                (AXIS_DP, AXIS_SP, AXIS_TP))


def init_multihost(coordinator: str | None = None, num_processes: int | None = None,
                   process_id: int | None = None) -> int:
    """Join a multi-host TPU pod job (the SPMD replacement for the reference's
    `dllama worker --port ...` + `--workers host:port ...` bootstrap,
    src/apps/dllama/dllama.cpp:205-221).

    Every host runs the SAME program; jax.distributed wires them into one runtime.
    On Cloud TPU pods all three arguments come from the metadata server, so plain
    `init_multihost()` suffices; elsewhere pass coordinator="host0:1234",
    num_processes and process_id explicitly. Returns this host's process index.
    """
    kw = {k: v for k, v in (("coordinator_address", coordinator),
                            ("num_processes", num_processes),
                            ("process_id", process_id)) if v is not None}
    jax.distributed.initialize(**kw)
    return jax.process_index()


def make_pod_mesh(tp: int | None = None, sp: int = 1, dp: int | None = None) -> Mesh:
    """DCN-aware (dp, sp, tp) mesh over every chip in a multi-host job.

    Axis placement follows the bandwidth hierarchy: tp (all-reduce per layer —
    the heaviest traffic, tasks.cpp:44-94's broadcast/gather pattern) and sp
    (ring permutes) stay INSIDE an ICI domain; dp (independent sequences, no
    per-step traffic) spans ICI domains over DCN. This is the standard
    ici/dcn hybrid-mesh recipe; the reference's 1 GbE star forced ALL traffic
    over the slow link, which is why its 8-node numbers collapse
    (reference README.md:122).

    The ICI domain is a pod SLICE, not a host: on a v5p-16 (4 hosts, one slice)
    every chip is ICI-connected, so tp=16 across all 4 hosts is the right layout
    — the BASELINE.json 405B north-star config. Only MULTISLICE jobs (devices
    reporting distinct slice_index) have a DCN boundary, and there dp must span
    the slices.
    """
    from jax.experimental import mesh_utils

    devs = jax.devices()  # global: every chip in the job, all processes
    n_total = len(devs)
    n_slices = len({getattr(d, "slice_index", 0) for d in devs})
    if tp is None:
        dp = dp if dp is not None else n_slices
        assert n_total % (dp * sp) == 0, (n_total, dp, sp)
        tp = n_total // (dp * sp)
    elif dp is None:
        assert n_total % (sp * tp) == 0, (n_total, sp, tp)
        dp = n_total // (sp * tp)
    assert dp * sp * tp == n_total, (dp, sp, tp, n_total)
    if n_slices == 1:
        # one ICI domain (single- or multi-host): make_mesh lays the devices
        # out so mesh neighbors are torus neighbors (_device_grid)
        return make_mesh(tp=tp, sp=sp, dp=dp, devices=devs)
    assert dp % n_slices == 0, (
        f"dp={dp} must span the {n_slices} slices (tp/sp must fit inside one "
        f"slice: {sp * tp} chips vs {n_total // n_slices} per slice)")
    grid = mesh_utils.create_hybrid_device_mesh(
        mesh_shape=(dp // n_slices, sp, tp), dcn_mesh_shape=(n_slices, 1, 1))
    return Mesh(grid, (AXIS_DP, AXIS_SP, AXIS_TP))
