"""File-format tests: .m/.t round trips + byte compatibility with the reference writer."""

import os

import numpy as np
import pytest

from distributed_llama_tpu.formats.mfile import (
    load_model,
    params_file_order,
    read_spec,
    write_model,
)
from distributed_llama_tpu.formats.tfile import TokenizerData, load_tokenizer, write_tokenizer
from distributed_llama_tpu.models.params import init_random_params
from distributed_llama_tpu.models.spec import ArchType, HiddenAct, ModelSpec
from distributed_llama_tpu.quants import FloatType


def tiny_spec(arch=ArchType.LLAMA, **kw):
    d = dict(arch_type=arch, dim=64, hidden_dim=96, n_layers=2, n_heads=4, n_kv_heads=2,
             vocab_size=128, seq_len=32, rope_theta=10000.0)
    if arch != ArchType.LLAMA:
        d.update(n_experts=4, n_active_experts=2)
    if arch == ArchType.GROK1:
        d.update(hidden_act=HiddenAct.GELU)
    d.update(kw)
    return ModelSpec(**d).resolved()


@pytest.mark.parametrize("arch", [ArchType.LLAMA, ArchType.MIXTRAL, ArchType.GROK1])
@pytest.mark.parametrize("ftype", [FloatType.F32, FloatType.Q40])
def test_mfile_roundtrip(tmp_path, arch, ftype):
    spec = tiny_spec(arch)
    params = init_random_params(spec, ftype, seed=1)
    path = str(tmp_path / "model.m")
    write_model(path, spec, params_file_order(spec, params), ftype)

    spec2, params2 = load_model(path)
    assert spec2.arch_type == spec.arch_type
    assert (spec2.dim, spec2.hidden_dim, spec2.n_layers) == (spec.dim, spec.hidden_dim,
                                                             spec.n_layers)
    assert (spec2.n_experts, spec2.n_active_experts) == (spec.n_experts,
                                                         spec.n_active_experts)
    assert spec2.hidden_act == spec.hidden_act
    # tensors survive (through one quantization round for quantized types)
    np.testing.assert_allclose(params2["embedding"], params["embedding"], atol=1e-6)
    for name in params["blocks"]:
        a, b = params["blocks"][name], params2["blocks"][name]
        a = a.to_numpy() if hasattr(a, "to_numpy") else np.asarray(a)
        b = b.to_numpy() if hasattr(b, "to_numpy") else np.asarray(b)
        np.testing.assert_allclose(a, b, atol=1e-6, err_msg=name)


@pytest.mark.parametrize("ftype", [FloatType.F32, FloatType.Q40])
def test_mfile_roundtrip_of_a_model_with_state_layers(tmp_path, ftype):
    """The header keys and tensors LFM2 brought: a convolution kind
    (`LayerKind.conv_kernel`), QK-norm, the selection bias; in the file a
    layer's own kind's tensors in layer order, in `params` each mixer's
    stacked over its kind's layers of the run."""
    from distributed_llama_tpu.models.spec import LayerKind, RouterScore

    spec = tiny_spec(ArchType.MIXTRAL, n_layers=5, head_dim=16, qk_norm=True,
                     router_bias=True, router_score=RouterScore.SIGMOID,
                     lead_layers=1, lead_hidden_dim=64,
                     kinds=(LayerKind("conv", 4, conv_kernel=3),
                            LayerKind("full", 4, rope_theta=1e6)),
                     layer_kinds=(0, 1, 0, 0, 1))
    params = init_random_params(spec, ftype, seed=4)
    assert params["blocks"]["conv_in"].shape[0] == 2
    assert params["blocks"]["wq"].shape[0] == 2
    assert params["blocks"]["router_bias"].shape[0] == 4
    path = str(tmp_path / "mixed.m")
    write_model(path, spec, params_file_order(spec, params), ftype)
    spec2, params2 = load_model(path)
    assert (spec2.qk_norm, spec2.router_bias, spec2.layer_kinds) == (
        True, True, (0, 1, 0, 0, 1))
    assert [k.conv_kernel for k in spec2.kinds] == [3, 0]
    assert spec2.kinds[1].rope_theta == 1e6 and spec2.mixed
    assert sorted(params2) == sorted(params)
    for st in ("lead", "blocks"):
        assert set(params2[st]) == set(params[st])
        for name in params[st]:
            a, b = params[st][name], params2[st][name]
            a = a.to_numpy() if hasattr(a, "to_numpy") else np.asarray(a)
            b = b.to_numpy() if hasattr(b, "to_numpy") else np.asarray(b)
            assert a.shape == b.shape, name
            np.testing.assert_allclose(a, b, atol=1e-6, err_msg=name)


@pytest.mark.parametrize("ftype", [FloatType.F32, FloatType.Q40])
def test_mfile_roundtrip_of_a_state_space_model(tmp_path, ftype):
    """The header keys and tensors granite-4.0-h-small brought: a kind's
    state-space fields (heads, head size, state size, groups), a kind
    without a rotation, the three multipliers, the stated attention scale
    (in units of 1e-9: 1/128 is no whole number of 1e-6), the shared expert
    at its own width and the snapshot pool's entries."""
    from distributed_llama_tpu.models.spec import LayerKind, RopeType

    spec = tiny_spec(ArchType.MIXTRAL, n_layers=4, head_dim=16,
                     shared_hidden_dim=96, embedding_multiplier=12.0,
                     residual_multiplier=0.22, logits_scaling=16.0,
                     attn_multiplier=1 / 128, state_snapshots=6,
                     kinds=(LayerKind("mamba", 4, conv_kernel=4, ssm_heads=4,
                                      ssm_head_dim=32, ssm_state=16),
                            LayerKind("attention", 4,
                                      rope_type=RopeType.NONE)),
                     layer_kinds=(0, 0, 1, 0))
    params = init_random_params(spec, ftype, seed=5)
    assert params["blocks"]["ssm_in"].shape == (3, 128 + 160 + 4, 64)
    assert params["blocks"]["wq"].shape[0] == 1
    path = str(tmp_path / "ssm.m")
    write_model(path, spec, params_file_order(spec, params), ftype)
    spec2, params2 = load_model(path)
    assert (spec2.embedding_multiplier, spec2.residual_multiplier,
            spec2.logits_scaling, spec2.attn_multiplier,
            spec2.state_snapshots, spec2.shared_hidden_dim) == (
        12.0, 0.22, 16.0, 1 / 128, 6, 96)
    mamba, attn = spec2.kinds
    assert (mamba.conv_kernel, mamba.ssm_heads, mamba.ssm_head_dim,
            mamba.ssm_state, mamba.ssm_groups) == (4, 4, 32, 16, 1)
    assert attn.rope_type == RopeType.NONE and attn.ssm_groups == 1
    assert spec2.ssm and spec2.state_matrix == (4, 32, 16)
    assert spec2.state_width == 160 and spec2.layer_kinds == (0, 0, 1, 0)
    assert set(params2["blocks"]) == set(params["blocks"])
    for name in params["blocks"]:
        a, b = params["blocks"][name], params2["blocks"][name]
        a = a.to_numpy() if hasattr(a, "to_numpy") else np.asarray(a)
        b = b.to_numpy() if hasattr(b, "to_numpy") else np.asarray(b)
        assert a.shape == b.shape, name
        np.testing.assert_allclose(a, b, atol=1e-6, err_msg=name)


def test_mfile_seq_len_clamp(tmp_path):
    spec = tiny_spec()
    params = init_random_params(spec, FloatType.F32, seed=2)
    path = str(tmp_path / "m.m")
    write_model(path, spec, params_file_order(spec, params), FloatType.F32)
    spec2, _, _ = read_spec(path, max_seq_len=8)
    assert spec2.seq_len == 8 and spec2.orig_seq_len == 32


def test_mfile_wrong_ftype_detected(tmp_path):
    spec = tiny_spec()
    params = init_random_params(spec, FloatType.Q40, seed=3)
    path = str(tmp_path / "m.m")
    write_model(path, spec, params_file_order(spec, params), FloatType.Q40)
    with pytest.raises(ValueError, match="mismatch"):
        load_model(path, weights_ftype=FloatType.F32)


def test_mfile_reference_writer_compatibility(tmp_path):
    """A file produced by the REFERENCE converter's writer must load identically.

    Runs /root/reference/converter/writer.py (public untrusted code, used here only as a
    byte-format oracle) to build a tiny llama .m file.
    """
    torch = pytest.importorskip("torch")
    import sys

    if not os.path.isfile("/root/reference/converter/writer.py"):
        pytest.skip("reference repo not present (byte-format oracle unavailable)")
    sys.path.insert(0, "/root/reference/converter")
    import writer as refwriter  # noqa

    spec = tiny_spec()
    params = init_random_params(spec, FloatType.Q40, seed=4)
    path = str(tmp_path / "ref.m")
    with open(path, "wb") as f:
        refwriter.writeHeader(f, {
            "version": 0, "arch_type": int(spec.arch_type), "dim": spec.dim,
            "hidden_dim": spec.hidden_dim, "n_layers": spec.n_layers,
            "n_heads": spec.n_heads, "n_kv_heads": spec.n_kv_heads,
            "n_experts": 0, "n_active_experts": 0, "vocab_size": spec.vocab_size,
            "max_seq_len": spec.seq_len, "hidden_act": int(spec.hidden_act),
            "rope_theta": int(spec.rope_theta),
            "weights_float_type": int(FloatType.Q40),
        })
        norm_names = {"embedding", "rms_att", "rms_ffn", "rms_final"}
        for name, tensor in params_file_order(spec, params):
            ft = refwriter.FloatType.F32 if name in norm_names else refwriter.FloatType.Q40
            refwriter.writeTensor(f, torch.from_numpy(np.ascontiguousarray(tensor)), ft)

    spec2, params2 = load_model(path)
    assert spec2.dim == spec.dim and spec2.arch_type == ArchType.LLAMA
    np.testing.assert_allclose(params2["embedding"], params["embedding"], atol=1e-6)
    np.testing.assert_allclose(params2["blocks"]["wq"].to_numpy(),
                               params["blocks"]["wq"].to_numpy(), atol=1e-6)
    np.testing.assert_allclose(params2["wcls"].to_numpy(), params["wcls"].to_numpy(),
                               atol=1e-6)


def test_tfile_roundtrip(tmp_path):
    vocab = [b"<unk>", b"<s>", b"</s>"] + [bytes([i]) for i in range(32, 60)]
    td = TokenizerData(vocab=vocab, scores=[float(-i) for i in range(len(vocab))],
                       bos_id=1, eos_id=2, chat_eos_id=2, max_token_length=6,
                       chat_template="{% if %}<|im_start|>{% endif %}", chat_stop="<|done|>")
    path = str(tmp_path / "tok.t")
    write_tokenizer(path, td)
    td2 = load_tokenizer(path)
    assert td2.vocab == vocab
    assert td2.scores == td.scores
    assert (td2.bos_id, td2.eos_id, td2.chat_eos_id) == (1, 2, 2)
    assert td2.chat_template == td.chat_template
    assert td2.chat_stop == td.chat_stop


def test_tfile_reference_writer_compatibility(tmp_path):
    import sys

    if not os.path.isfile("/root/reference/converter/writer.py"):
        pytest.skip("reference repo not present (byte-format oracle unavailable)")
    sys.path.insert(0, "/root/reference/converter")
    import importlib

    reftw = importlib.import_module("tokenizer-writer")

    vocab = [b"<unk>", b"<s>", b"</s>", b"ab", b"cd"]
    scores = [0.0, 0.0, 0.0, -1.0, -2.0]
    path = str(tmp_path / "ref.t")
    with open(path, "wb") as f:
        reftw.writeTokenizer(f, {"bos_id": 1, "eos_id": 2, "chat_eos_id": 2},
                             vocab, scores, b"<|im_start|>x", None)
    td = load_tokenizer(path)
    assert td.vocab == vocab
    assert td.bos_id == 1 and td.eos_id == 2
    assert td.chat_template == "<|im_start|>x"
