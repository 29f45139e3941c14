"""The kernels' weight layouts are made on the device (models/params.py
`_repack_on_device`, quants.py `jnp_to_i4p`): the bytes the NumPy/native
routines give (`fuse_matvec_groups`, `QTensor.to_i4p_layout`), which stay as
the oracle here, without the weights' trip through the host's shuffles."""

import jax
import numpy as np
import pytest

from distributed_llama_tpu.models.params import (_COL_SHARDED, _DENSE_MATMULS,
                                                 _FUSE_GROUPS, _REPACKED,
                                                 _decode_layout,
                                                 fuse_matvec_groups,
                                                 init_random_params,
                                                 prepare_for_pallas)
from distributed_llama_tpu.models.spec import ArchType, ModelSpec, RopeType
from distributed_llama_tpu.quants import (FloatType, QTensor, jnp_to_i4p,
                                          q40_to_bytes, scale_plane_cols)


def _dense_spec():
    return ModelSpec(arch_type=ArchType.LLAMA, dim=256, hidden_dim=256,
                     n_layers=3, n_heads=8, n_kv_heads=4, vocab_size=128,
                     seq_len=16, rope_type=RopeType.LLAMA).resolved()


def _moe_spec():
    return ModelSpec(arch_type=ArchType.MIXTRAL, dim=256, hidden_dim=256,
                     n_layers=2, n_heads=8, n_kv_heads=4, vocab_size=128,
                     seq_len=16, n_experts=4, n_active_experts=2,
                     rope_type=RopeType.FALCON).resolved()


def _host_prepare(params, tp, moe_sharding, spec):
    """What prepare_for_pallas did before the shuffles moved to the device."""
    blocks = fuse_matvec_groups(params["blocks"], spec, tp,
                                moe_sharding=moe_sharding)
    out = {}
    for name, t in blocks.items():
        if name in _DENSE_MATMULS or name in _FUSE_GROUPS:
            col = name in _COL_SHARDED and not (
                moe_sharding == "expert" and name.startswith("moe_"))
            out[name] = _decode_layout(t, tp, col)
    out["wcls"] = _decode_layout(params["wcls"], tp, False)
    return out


def _same(got: QTensor, want: QTensor, name: str):
    assert (got.layout, got.groups, got.row_groups) == (
        want.layout, want.groups, want.row_groups), name
    assert isinstance(got.data, jax.Array) and isinstance(got.scales,
                                                          jax.Array), name
    assert got.data.dtype == want.data.dtype, name
    assert got.scales.dtype == want.scales.dtype, name
    np.testing.assert_array_equal(np.asarray(got.data), want.data, name)
    np.testing.assert_array_equal(np.asarray(got.scales), want.scales, name)


@pytest.mark.parametrize("col_groups", [1, 2, 4])
@pytest.mark.parametrize("flat", [False, True])
def test_jnp_to_i4p_is_to_i4p_layout(col_groups, flat):
    rng = np.random.RandomState(col_groups)
    w = QTensor.from_float(rng.randn(3, 40, 512).astype(np.float32),
                           FloatType.Q40)
    want = w.to_i4p_layout(col_groups=col_groups)
    data = w.data.reshape(3, 40, -1) if flat else w.data
    d, s = jax.jit(jnp_to_i4p, static_argnums=2)(data, w.scales, col_groups)
    np.testing.assert_array_equal(np.asarray(d), want.data)
    np.testing.assert_array_equal(np.asarray(s), want.scales)


# K and the column groups it is packed in: K/32 a group of 24, 12 and 6
# (an expert's down at tp 1, 2, 4), 80 and 40, 112, and a lane tile as it is
@pytest.mark.parametrize("k,col_groups", [(768, 1), (768, 2), (768, 4),
                                          (2560, 1), (2560, 2), (3584, 1),
                                          (4096, 1), (8192, 2)])
@pytest.mark.parametrize("device", [False, True], ids=["host", "device"])
def test_the_scale_plane_gives_the_files_blocks_back(k, col_groups, device):
    """`to_i4p_layout` and `jnp_to_i4p` store the scales as the plane the
    kernels read, each column group's K/32 in whole lane tiles; `to_numpy`,
    `dequantize` and `block_scales` read the file's blocks out of it in the
    file's order, and the `.m` writer then writes the file's bytes."""
    import io

    import jax.numpy as jnp

    from distributed_llama_tpu.formats.mfile import write_tensor

    rng = np.random.RandomState(k // 32 + col_groups)
    w = QTensor.from_float(rng.randn(2, 24, k).astype(np.float32),
                           FloatType.Q40)
    wi = w.to_i4p_layout(col_groups=col_groups)
    if device:
        d, s = jax.jit(jnp_to_i4p, static_argnums=2)(w.data, w.scales,
                                                     col_groups)
        wi = QTensor(FloatType.Q40, d, s, layout="i4p", groups=col_groups)
    nb = k // 32
    per = -(-(nb // col_groups) // 128) * 128
    assert wi.scales.shape == (2, 24, col_groups * per) == (
        2, 24, scale_plane_cols(nb, col_groups))
    np.testing.assert_array_equal(np.asarray(wi.block_scales()),
                                  w.scales.view(np.int16))
    np.testing.assert_array_equal(wi.to_numpy(), w.to_numpy())
    np.testing.assert_array_equal(
        np.asarray(wi.dequantize(jnp.float32)),
        np.asarray(w.dequantize(jnp.float32)))
    # what a writer makes of the layout's values is the file's own stream
    buf = io.BytesIO()
    write_tensor(buf, wi.to_numpy(), FloatType.Q40)
    assert buf.getvalue() == q40_to_bytes(w.data, w.scales)


@pytest.mark.parametrize("name", ["wo", "w2"])
def test_tp2_shards_hold_their_own_plane(name):
    """A column-sharded weight on a tp = 2 mesh: each shard's part of the
    scales' plane is the plane of its own K/32 columns (4 of wo's 8 here, in
    one lane tile), so what `_localize_qtensors` hands the kernels inside
    `shard_map`, the shard's leaves with one column group, decodes to the
    shard's columns of the file's weights."""
    from distributed_llama_tpu.models.forward import _localize_qtensors
    from distributed_llama_tpu.parallel.mesh import make_mesh
    from distributed_llama_tpu.parallel.tp import shard_params

    spec = _dense_spec()
    params = init_random_params(spec, FloatType.Q40, seed=11)
    mesh = make_mesh(tp=2)
    placed = shard_params(
        prepare_for_pallas(params, 2, spec=spec, mesh=mesh), mesh, spec)
    t = placed["blocks"][name]
    full = params["blocks"][name].to_numpy()
    k = full.shape[-1]
    assert t.groups == 2 and t.scales.shape[-1] == 2 * 128
    np.testing.assert_array_equal(t.to_numpy(), full)
    # one distinct shard a tp index (the mesh may replicate over other axes)
    planes = {s.device: s.data for s in t.scales.addressable_shards}
    shards = {d.index[-1].start or 0: (d.data, planes[d.device])
              for d in t.data.addressable_shards}
    assert len(shards) == 2
    for i, (_, (data, scales)) in enumerate(sorted(shards.items())):
        assert scales.shape[-1] == 128 == scale_plane_cols(k // 32 // 2)
        local = _localize_qtensors({"w": QTensor(
            t.ftype, data, scales, layout="i4p", groups=2)})["w"]
        assert local.groups == 1
        np.testing.assert_array_equal(
            local.to_numpy(), full[..., i * k // 2:(i + 1) * k // 2])


def test_an_engine_states_its_scale_planes_bytes(monkeypatch):
    """`weights_scale_plane_bytes` is set when an engine is built: the
    planes' resident bytes, padding included, against the file's 2 bytes a
    block (here every K/32 is 8, stored as one lane tile: 16 times)."""
    from distributed_llama_tpu.obs import metrics
    from distributed_llama_tpu.runtime.engine import Engine

    monkeypatch.setenv("DLT_PALLAS_INTERPRET", "1")
    spec = _dense_spec()
    params = init_random_params(spec, FloatType.Q40, seed=5)
    file_bytes = sum(
        t.scales.nbytes for t in [*params["blocks"].values(), params["wcls"]]
        if isinstance(t, QTensor) and t.ftype == FloatType.Q40)
    eng = Engine(spec, params, tp=1, use_pallas=True)
    held = [t for t in [*eng.params["blocks"].values(), eng.params["wcls"]]
            if isinstance(t, QTensor) and t.ftype == FloatType.Q40]
    assert len(held) == 5 and all(t.layout == "i4p" and t.scales.shape[-1] == 128
                        for t in held)
    got = metrics.snapshot()["weights_scale_plane_bytes"]
    assert got == sum(t.scales.nbytes for t in held) == 16 * file_bytes


@pytest.mark.parametrize("multiplier", [1.0, 12.0])
def test_a_bf16_engine_holds_the_table_in_its_dtype(multiplier):
    """The engine places the embedding table in the dtype it computes in
    (`hold_dense`), the spec's multiplier made in float32 before the cast as
    `forward` orders the two on a float32 table's rows: the same logits bit
    for bit, a cast commuting with a gather. The caller's params, and what
    `mfile` would write of them, stay float32. `weights_step_converted_bytes`
    reads 0, and the float32 table's bytes with that table put back."""
    import dataclasses

    import jax.numpy as jnp

    from distributed_llama_tpu.formats.mfile import params_file_order
    from distributed_llama_tpu.models.params import step_converted_bytes
    from distributed_llama_tpu.obs import metrics
    from distributed_llama_tpu.runtime.engine import Engine

    spec = dataclasses.replace(_dense_spec(), embedding_multiplier=multiplier)
    params = init_random_params(spec, FloatType.Q40, seed=9)
    table = params["embedding"]
    eng = Engine(spec, params, tp=1, dtype=jnp.bfloat16, use_pallas=False)
    held = eng.params["embedding"]
    assert held.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(held.astype(jnp.float32)),
        (table * np.float32(multiplier)).astype(jnp.bfloat16).astype(
            np.float32))
    assert metrics.snapshot()["weights_step_converted_bytes"] == 0
    assert params["embedding"] is table and table.dtype == np.float32
    name, written = next(iter(params_file_order(spec, params)))
    assert name == "embedding" and written.dtype == np.float32
    tokens = [3, 17, 99, 4, 120]
    with_held = eng.infer_chunk_logits(tokens)
    eng.params = {**eng.params, "embedding": jnp.asarray(table)}
    eng.reset()
    np.testing.assert_array_equal(eng.infer_chunk_logits(tokens), with_held)
    assert step_converted_bytes(eng.params, eng.dtype, eng.use_pallas) \
        == table.nbytes == metrics.snapshot()["weights_step_converted_bytes"]


def test_a_float32_engine_leaves_the_table_as_loaded():
    """Nothing to hold where the engine computes in float32: the table is
    the loader's, the multiplier not in it, and the gauge reads 0."""
    import dataclasses

    from distributed_llama_tpu.obs import metrics
    from distributed_llama_tpu.runtime.engine import Engine

    spec = dataclasses.replace(_dense_spec(), embedding_multiplier=12.0)
    params = init_random_params(spec, FloatType.Q40, seed=9)
    eng = Engine(spec, params, tp=1, use_pallas=False)
    assert eng.dtype == np.float32
    assert eng.params["embedding"].dtype == np.float32
    np.testing.assert_array_equal(np.asarray(eng.params["embedding"]),
                                  params["embedding"])
    assert metrics.snapshot()["weights_step_converted_bytes"] == 0


def test_the_gauge_counts_a_matrix_left_planar_beside_the_kernels(
        monkeypatch):
    """`weights_step_converted_bytes`, kernels on: 0 where every matrix of
    the layers is packed; the blocks' bytes of one that no kernel's pack
    takes (a Q80 `w2` over the matvec's bound, lowered here), which XLA
    dequantizes whole every step. The router is planar by design and an
    engine without the kernels dequantizes by choice: neither is counted."""
    from distributed_llama_tpu.obs import metrics
    from distributed_llama_tpu.ops import pallas_q8
    from distributed_llama_tpu.runtime.engine import Engine

    monkeypatch.setenv("DLT_PALLAS_INTERPRET", "1")
    spec = _moe_spec()
    eng = Engine(spec, init_random_params(spec, FloatType.Q40, seed=5),
                 tp=1, use_pallas=True)
    assert eng.params["blocks"]["router"].layout == "planar"
    assert metrics.snapshot()["weights_step_converted_bytes"] == 0
    monkeypatch.setattr(pallas_q8, "_XEXP_VMEM_LIMIT", 256 * 8 - 1)
    spec = _dense_spec()  # every K is 256: over the lowered bound
    params = init_random_params(spec, FloatType.Q80, seed=5)
    eng = Engine(spec, params, tp=1, use_pallas=True)
    left = [t for t in eng.params["blocks"].values()
            if isinstance(t, QTensor)]
    assert left and all(t.layout == "planar" for t in left)
    assert metrics.snapshot()["weights_step_converted_bytes"] == sum(
        t.nbytes() for t in left)
    Engine(spec, params, tp=1, use_pallas=False)
    assert metrics.snapshot()["weights_step_converted_bytes"] == 0


CASES = [  # arch, tp, moe_sharding, on a mesh
    ("dense", 1, "slice", False),
    ("dense", 2, "slice", False),
    ("dense", 4, "slice", False),
    ("dense", 2, "slice", True),
    ("dense", 4, "slice", True),
    ("moe", 1, "slice", False),
    ("moe", 2, "slice", False),
    ("moe", 4, "slice", False),
    ("moe", 2, "expert", False),
    ("moe", 4, "expert", False),
    ("moe", 2, "slice", True),
    ("moe", 4, "expert", True),
]


@pytest.mark.parametrize("arch,tp,moe_sharding,on_mesh", CASES)
def test_prepare_for_pallas_bytes_are_the_hosts(arch, tp, moe_sharding,
                                                on_mesh):
    """Stacked dense weights, the merged wqkv / w13 / moe_gu with their
    TP-group interleave, column groups 1, 2 and 4, both MoE shardings: data
    and scales byte for byte the host path's, placed as shard_params would
    place them when a mesh is given."""
    from distributed_llama_tpu.parallel.mesh import make_mesh
    from distributed_llama_tpu.parallel.tp import shard_params

    spec = _dense_spec() if arch == "dense" else _moe_spec()
    params = init_random_params(spec, FloatType.Q40, seed=3 + tp)
    want = _host_prepare(params, tp, moe_sharding, spec)
    mesh = make_mesh(tp=tp) if on_mesh else None
    got = prepare_for_pallas(params, tp, moe_sharding=moe_sharding, spec=spec,
                             mesh=mesh)
    merged = {"wqkv", "w13"} if arch == "dense" else {"wqkv", "moe_gu"}
    assert merged <= set(got["blocks"])
    for name, t in want.items():
        _same(got["wcls"] if name == "wcls" else got["blocks"][name], t, name)
    if on_mesh:
        placed = shard_params(got, mesh, spec, moe_sharding=moe_sharding)
        for name in merged | {"wo"}:
            a, b = got["blocks"][name].data, placed["blocks"][name].data
            assert a.sharding.is_equivalent_to(b.sharding, a.ndim), name
            assert len(a.sharding.device_set) == tp, name


def test_repack_never_returns_to_the_host_and_counts_its_side():
    """Once uploaded nothing comes back: the walk over the layer axis runs
    under a guard that refuses every device-to-host transfer. The bytes are
    counted on the device's side and the host's stays where it was."""
    spec = _moe_spec()
    params = init_random_params(spec, FloatType.Q40, seed=11)
    dev = _REPACKED.labels(where="device")
    host = _REPACKED.labels(where="host")
    dev0, host0 = dev.value, host.value
    with jax.transfer_guard_device_to_host("disallow"):
        got = prepare_for_pallas(params, spec=spec)
        jax.block_until_ready([t.data for t in got["blocks"].values()
                               if isinstance(t, QTensor)])
    i4p = [t for t in list(got["blocks"].values()) + [got["wcls"]]
           if isinstance(t, QTensor) and t.layout == "i4p"]
    assert {"wqkv", "wo", "moe_gu", "moe_down"} <= set(got["blocks"])
    assert dev.value - dev0 == sum(t.nbytes() for t in i4p)
    assert host.value == host0
    # Q80 has no device path: its int8 planes are the host's
    p80 = init_random_params(_dense_spec(), FloatType.Q80, seed=1)
    got80 = prepare_for_pallas(p80, spec=_dense_spec())
    assert got80["blocks"]["wqkv"].layout == "i8"
    assert host.value > host0 and dev.value - dev0 == sum(
        t.nbytes() for t in i4p)


def test_repack_takes_arrays_already_on_a_device():
    spec = _dense_spec()
    params = init_random_params(spec, FloatType.Q40, seed=2)
    want = _host_prepare(params, 1, "slice", spec)
    on_dev = dict(params, blocks=jax.tree.map(jax.numpy.asarray,
                                              params["blocks"]))
    got = prepare_for_pallas(on_dev, spec=spec)
    for name in ("wqkv", "wo", "w13", "w2"):
        _same(got["blocks"][name], want[name], name)
