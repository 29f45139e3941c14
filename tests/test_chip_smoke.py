"""chip_smoke.py off the chip, and the start-up routine it leans on.

The smoke's contract is checked on a TPU by whoever runs it there; what can be
held here is everything around the device: the whole control flow at tiny size
under an explicit CPU + interpret request (which must end not-ok), that the
parent process stays off JAX, that no failing phase can end in exit code 0,
and that the start-up routine places the compile cache where it says.
"""

import ast
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402  (stdlib only: importing it touches no device)

from distributed_llama_tpu import platform_env  # noqa: E402
from distributed_llama_tpu.apps import parity  # noqa: E402

PHASES = ("device", "model", "cli", "parity", "serve")
# what a CPU rehearsal is expected to fail on, and nothing else
CPU_FAILURES = ("device is cpu, not tpu", "the q4_matvec kernel did not engage",
                "the paged-attention kernel did not engage")


def test_rehearsal_runs_every_phase_and_cannot_end_ok(tmp_path):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               JAX_ENABLE_COMPILATION_CACHE="1")
    p = subprocess.run([sys.executable, "chip_smoke.py", "--rehearse"],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=600)
    lines = [json.loads(x) for x in p.stdout.strip().splitlines()]
    assert p.returncode != 0, p.stdout
    assert lines[-1] == {"ok": False, "device": {
        "platform": "cpu", "kind": lines[-1]["device"]["kind"], "count": 1}}
    by_phase = {x["phase"]: x for x in lines[:-1]}
    assert tuple(by_phase) == PHASES
    for name, line in by_phase.items():
        unexpected = [f for f in line["failures"]
                      if not f.endswith(CPU_FAILURES)]
        assert not unexpected, (name, unexpected)
    assert by_phase["model"]["ok"] and by_phase["model"]["arch"] == "tiny"
    assert [r["generated"] for r in by_phase["cli"]["runs"]] == [32, 32]
    for name, bounds in parity.BOUNDS.items():
        done = by_phase["parity"]["passes"][name]
        assert done["arms"]["kernels"]["kernels"] == "True"  # interpret mode
        assert done["within"] and done["bounds"] == list(bounds)
    assert by_phase["parity"]["passes"]["shallow"]["canary"]["caught"]
    serve = by_phase["serve"]
    assert serve["exit_code"] == 0 and serve["stream_events"] > 0
    # the compared pair rewound to the last prompt token; the priming send
    # prefilled in chunks and ended on a different program
    repeat = serve["repeat"]
    assert repeat["path"]["chunks"] == [1]
    assert repeat["prime_path"]["chunks"][-1] == 8
    assert serve["batch_engine"]["super_steps"] > 0 and serve["paged_kv"]
    assert serve["device"]["compile_cache"] == str(tmp_path / "cache")
    assert not os.path.exists(chip_smoke.WORK)  # the checkpoint is removed


def test_parent_imports_nothing_but_the_standard_library():
    """A parent that has touched JAX holds the chip; every import anywhere in
    chip_smoke.py, at any depth, must be stdlib."""
    with open(os.path.join(REPO, "chip_smoke.py")) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported.update(a.name.split(".")[0] for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            assert node.level == 0, "relative import"
            imported.add(node.module.split(".")[0])
    assert imported and imported <= sys.stdlib_module_names, (
        imported - sys.stdlib_module_names)


def _stub_phases(monkeypatch, tmp_path, failing):
    monkeypatch.setattr(chip_smoke, "WORK", str(tmp_path / "work"))
    monkeypatch.setattr(chip_smoke, "LOGS", str(tmp_path / "logs"))
    for name in PHASES:
        def phase(ctx, name=name):
            if name == "device":
                ctx.device = {"platform": "tpu", "kind": "TPU v5 lite",
                              "count": 1}
            ok = name != failing
            return {"phase": name, "ok": ok,
                    "failures": [] if ok else ["injected"]}
        monkeypatch.setattr(chip_smoke, f"phase_{name}", phase)


@pytest.mark.parametrize("failing", PHASES)
def test_a_failing_phase_cannot_yield_exit_code_0(monkeypatch, tmp_path,
                                                  capsys, failing):
    _stub_phases(monkeypatch, tmp_path, failing)
    assert chip_smoke.main([]) == 1
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert lines[-1]["ok"] is False and set(lines[-1]) == {"ok", "device"}
    ran = [x["phase"] for x in lines[:-1]]
    # nothing to drive without a device or a checkpoint; otherwise go on
    stops_at = failing if failing in ("device", "model") else "serve"
    assert ran == list(PHASES[:PHASES.index(stops_at) + 1])


def test_all_phases_ok_gives_the_contract_line_and_exit_code_0(
        monkeypatch, tmp_path, capsys):
    _stub_phases(monkeypatch, tmp_path, failing=None)
    assert chip_smoke.main([]) == 0
    assert capsys.readouterr().out.splitlines()[-1] == json.dumps(
        {"ok": True, "device": {"platform": "tpu", "kind": "TPU v5 lite",
                                "count": 1}})


@pytest.fixture
def cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_goes_where_the_environment_says(monkeypatch, tmp_path,
                                                       cache_config):
    monkeypatch.setenv(platform_env.CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert platform_env.place_compile_cache() == str(tmp_path)
    # and nothing is set in code: JAX read the variable itself at import
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_one_fixed_path_in_the_checkout(
        monkeypatch, cache_config):
    monkeypatch.delenv(platform_env.CACHE_ENV, raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert platform_env.place_compile_cache() == want
    assert platform_env.place_compile_cache() == want  # no pid, time or temp
    assert jax.config.jax_compilation_cache_dir == want


def test_kernels_off_the_chip_need_the_interpret_request(monkeypatch):
    """use_pallas=None resolves to XLA on the CPU and says why; use_pallas=True
    is an error there unless interpret mode was asked for."""
    policy = platform_env.resolve_kernel_policy(None)
    assert policy.use_pallas is False and "cpu" in policy.reason
    assert platform_env.resolve_kernel_policy(True).use_pallas is True
    monkeypatch.delenv(platform_env.INTERPRET_ENV)
    with pytest.raises(ValueError, match="DLT_PALLAS_INTERPRET"):
        platform_env.resolve_kernel_policy(True)
    assert platform_env.resolve_kernel_policy(None).use_pallas is False


def test_engine_says_when_a_checkpoint_leaves_the_kernels_nothing_to_read(capsys):
    """The start-up line names the policy before a checkpoint is read; an
    engine that drops it for unquantized weights says so, and reports xla."""
    from distributed_llama_tpu.models.params import init_random_params
    from distributed_llama_tpu.models.spec import ArchType, ModelSpec, RopeType
    from distributed_llama_tpu.quants import FloatType
    from distributed_llama_tpu.runtime.engine import Engine

    spec = ModelSpec(arch_type=ArchType.LLAMA, dim=64, hidden_dim=128,
                     n_layers=1, n_heads=4, n_kv_heads=4, vocab_size=64,
                     seq_len=32, rope_type=RopeType.LLAMA).resolved()
    engine = Engine(spec, init_random_params(spec, FloatType.F32), tp=1,
                    use_pallas=True)
    assert "kernels: xla" in capsys.readouterr().out
    assert platform_env.describe(engine.dtype, engine.use_pallas)[
        "kernels"] == "xla"


@pytest.fixture(scope="module")
def tiny_checkpoint(tmp_path_factory):
    out = tmp_path_factory.mktemp("tiny")
    subprocess.run([sys.executable, "examples/make_tiny_model.py", str(out)],
                   cwd=REPO, check=True, capture_output=True)
    return str(out / "tiny.m")


def test_parity_default_pair_runs_the_dequant_matmul(tiny_checkpoint):
    """The default pair, kernels against use_pallas=False: the T=1 steps go
    through the matvec and the 64-token chunk through the fused
    dequant-matmul, with no call site degraded to XLA. One process only,
    because it builds five interpret-mode engines."""
    p = subprocess.run(
        [sys.executable, "-m", "distributed_llama_tpu.apps.parity", "--model",
         tiny_checkpoint, "--steps", "4"],
        cwd=REPO, capture_output=True, text=True, timeout=600,
        env={**os.environ, "DLT_PALLAS_INTERPRET": "1"})
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["ok"] and set(out["passes"]["full"]["arms"]) == {
        "kernels", "xla"}
    engaged = set(out["kernel_selections"].values())
    assert {"q4_mm", "q4_matvec"} <= engaged
    assert "xla-fallback" not in engaged


# the switches that went: with the hardware A/B (PR 30) the dequant-matmul
# became the default, and PR 32 took out the prologue kernels and the in-scan
# cache discipline with their flags. None is an Engine keyword or a flag of
# the entry points any more, and parity has no opt-in family to compare.
@pytest.mark.parametrize("flag", ["prologue", "cache-write",
                                  "prefill-kernel", "fused-matmul"])
def test_retired_switches_are_gone(flag):
    import inspect

    from distributed_llama_tpu.apps.dllama import build_parser
    from distributed_llama_tpu.runtime.engine import Engine

    word = flag.replace("-", "_")
    assert not [n for n in inspect.signature(Engine.__init__).parameters
                if word in n]
    assert not [o for a in build_parser()._actions for o in a.option_strings
                if flag in o]
    assert not hasattr(parity, "POLICIES")


def test_parity_shallow_cut_is_the_first_and_the_last_layer(tiny_checkpoint):
    from distributed_llama_tpu.formats.mfile import load_model

    spec, params = load_model(tiny_checkpoint)
    cut_spec, cut = parity.shallow_cut(spec, params)
    assert cut_spec.n_layers == 2 and spec.n_layers == 4
    assert cut["wcls"] is params["wcls"]
    for name, whole in params["blocks"].items():
        for got, want in zip(jax.tree.leaves(cut["blocks"][name]),
                             jax.tree.leaves(whole)):
            np.testing.assert_array_equal(got, want[[0, -1]])
    wo = cut["blocks"]["wo"]
    wrong = parity.mis_scaled(cut, "wo", 1.125)["blocks"]["wo"]
    ratio = wrong.scales.astype(np.float32) / wo.scales
    np.testing.assert_allclose(ratio[0], 1.125, rtol=2e-3)
    np.testing.assert_array_equal(ratio[1], 1.0)
    np.testing.assert_array_equal(wrong.data, wo.data)


@pytest.mark.parametrize("error, within", [
    (0.0, True),     # the same logits
    (0.01, True),    # under every bound
    (0.1, False),    # a tenth of the scale: inside the full pass's bf16
                     # floor, and exactly what the shallow pass must refuse
    (1.0, False)])   # uncorrelated
def test_parity_shallow_bounds(error, within):
    rng = np.random.default_rng(0)
    b = rng.standard_normal((8, 512)).astype(np.float32)
    a = b + error * rng.standard_normal(b.shape).astype(np.float32)
    assert parity.compare(a, b, parity.BOUNDS["shallow"])["within"] is within
