"""Tier-1 wiring for the unified static-analysis runner (ISSUE 10,
docs/ANALYSIS.md): the whole repo must carry ZERO unsuppressed findings
across every pass, every suppression must carry a written reason, and the
compile-manifest gate must hold on the pinned manifest AND catch an
injected recompile with the offending cache key named."""

import json
import os
import sys

from distributed_llama_tpu.analysis import core, drift, runner

REPO = core.REPO


def test_repo_zero_unsuppressed_findings():
    """The acceptance gate: every pass over every first-party file, zero
    unsuppressed findings. A new violation fails HERE with its rule, file,
    and line; the fix is to repair the code or triage it with a reasoned
    `# dlint: ignore[rule] -- why` (never to widen the lint)."""
    report = runner.run()
    assert report.files_scanned > 100, "scan did not find the repo"
    assert not report.unsuppressed, "\n".join(
        f.format() for f in report.unsuppressed)
    # the annotation conventions are live, not vestigial: the lock and
    # hot-path passes actually guard real declarations in the package
    assert report.suppressed, "expected triaged suppressions in the repo"
    for f in report.suppressed:
        assert f.reason, f"suppression without a reason: {f.format()}"
    # no stale excuses: a suppression matching nothing outlived its defect
    assert not report.unused_suppressions, report.unused_suppressions


def test_analysis_scan_covers_itself_and_the_runner():
    files = {os.path.relpath(f, REPO) for f in core.repo_py_files()}
    for mod in ("core", "locks", "hotpath", "drift", "smoke", "runner",
                "compile_audit", "__init__"):
        assert os.path.join("distributed_llama_tpu", "analysis",
                            f"{mod}.py") in files, mod
    assert os.path.join("perf", "dlint.py") in files


def test_fault_point_inventory_complete():
    """ISSUE 10 satellite: every `faults.fire("...")` in the package must be
    in docs/ROBUSTNESS.md's injection-point inventory (same drift pattern
    as the metric-docs lint)."""
    sources = core.load_sources(core.package_py_files())
    points = {p for p, _f, _l in drift.collect_fault_points(sources)}
    # the collector sees the real inventory, not a partial scan
    for expected in ("batch.submit", "batch.dispatch", "engine.reinit",
                     "router.proxy", "router.health",
                     "device_loop.verify_dispatch", "api.request"):
        assert expected in points, (expected, sorted(points))
    missing = drift.check_fault_docs(sources)
    assert not missing, "\n".join(f.format() for f in missing)


def test_dlint_cli_emits_json_artifact(tmp_path):
    """`perf/dlint.py --json` writes the findings/suppressions summary
    artifact (satellite: machine-readable output next to the BENCH files)."""
    sys.path.insert(0, os.path.join(REPO, "perf"))
    try:
        import dlint
    finally:
        sys.path.pop(0)
    out = tmp_path / "DLINT.json"
    rc = dlint.main(["--json", str(out)])
    assert rc == 0
    data = json.loads(out.read_text())
    assert data["ok"] is True
    assert data["counts"]["unsuppressed"] == 0
    assert data["counts"]["suppressed"] >= 1
    assert all(s["reason"] for s in data["suppressions"])
    assert data["files_scanned"] > 100


def test_compile_manifest_gate_holds_and_catches_injection():
    """The runtime compile audit: (1) the fixed tiny-model scenario —
    prefill, scans, pipelined chains, draft-verify blocks, a stochastic
    row, a durable resume — compiles ONLY programs/signatures the pinned
    perf/compile_manifest.json covers; (2) a deliberately injected shape
    bucket (a k=6 scan the scheduler never uses) fails the gate with the
    offending cache key named. One scenario run serves both halves."""
    from distributed_llama_tpu.analysis import compile_audit

    pinned = compile_audit.load_manifest()
    assert pinned is not None, "perf/compile_manifest.json missing"
    audit = compile_audit.CompileAudit()
    with audit:
        eng = compile_audit.run_scenario(keep_engine=True)
        try:
            clean = compile_audit.diff_manifest(audit.manifest(), pinned)
            assert clean == [], "\n".join(f.message for f in clean)
            # inject recompile creep: a new scan bucket = a new cache key
            eng._batched_loop(6, "greedy", None)
        finally:
            eng.close()
    findings = compile_audit.diff_manifest(audit.manifest(), pinned)
    assert findings, "gate failed to detect the injected shape bucket"
    assert any("batched_scan[k=6,mode=greedy,window=None,paged=16]"
               in f.message for f in findings), [f.message for f in findings]
    assert all(f.rule == "compile-manifest" for f in findings)


def test_compile_manifest_names_rogue_kernel_bucket():
    """ISSUE 16 satellite: the kernel policy is part of the program cache
    key: a verify T bucket minted with the kernels on outside the pinned
    set must fail the gate BY NAME (kernel=1 in the key), never alias onto
    the kernel-off pin. The factory call alone records the build (jit
    traces lazily), so the test costs no compile."""
    from distributed_llama_tpu.analysis import compile_audit
    from distributed_llama_tpu.models.params import init_random_params
    from distributed_llama_tpu.parallel.mesh import make_mesh
    from distributed_llama_tpu.quants import FloatType
    from distributed_llama_tpu.runtime import device_loop

    pinned = compile_audit.load_manifest()
    assert pinned is not None
    spec = compile_audit.scenario_spec()
    params = init_random_params(spec, FloatType.Q40, seed=11)
    audit = compile_audit.CompileAudit()
    with audit:
        device_loop.make_batched_verify_loop(
            spec, make_mesh(tp=1), params, 9, mode="greedy",
            attn_window=None, use_pallas=True, kv_block_tokens=16)
    findings = compile_audit.diff_manifest(audit.manifest(), pinned)
    assert findings, "gate missed the rogue kernel-on T bucket"
    key = "verify[t=9,mode=greedy,window=None,paged=16,kernel=1]"
    assert any(key in f.message for f in findings), \
        [f.message for f in findings]


def test_compile_manifest_catches_block_table_shape_creep():
    """ISSUE 12 satellite: block-table shapes must be padded/bucketed so
    per-request table growth never mints a fresh XLA lowering. Inject a
    dispatch whose table widened by one entry (the bug a per-request table
    shape would cause) through the SAME record path real dispatches hit —
    the gate must fail naming the offending cache key and the drifted
    signature."""
    import numpy as np

    from distributed_llama_tpu.analysis import compile_audit

    pinned = compile_audit.load_manifest()
    assert pinned is not None
    key = "batched_scan[k=4,mode=greedy,window=None,paged=16]"
    good = pinned["programs"][key]["signatures"][0]
    audit = compile_audit.CompileAudit()
    audit.record_call(key, (np.zeros((2, 5), np.int32),))  # table grew 4 -> 5
    findings = compile_audit.diff_manifest(audit.manifest(), pinned)
    assert findings and all(f.rule == "compile-manifest" for f in findings)
    msg = findings[0].message
    assert key in msg and "int32(2, 5)" in msg, msg
    # the pinned width stays clean through the same path
    clean_audit = compile_audit.CompileAudit()
    clean_audit.programs[key] = {"builds": 0, "signatures": {good}}
    assert compile_audit.diff_manifest(clean_audit.manifest(), pinned) == []
