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
from distributed_llama_tpu.quants import FloatType, QTensor, jnp_to_i4p


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
