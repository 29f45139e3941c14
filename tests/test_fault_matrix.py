"""Tier-1 wiring for perf/fault_matrix.py (ISSUE 4 satellite, the
test_smoke_lint.py pattern): the full injection-point x fault-kind matrix
runs against the CPU-mesh engines, one case a family of
`fault_matrix.FAMILIES`, and each must produce ZERO invariant violations —
no scheduler-thread death, no slot/lease leak, no unusable engine after an
injected fault — over exactly the cells the family declares."""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "perf"))

import fault_matrix  # noqa: E402


@pytest.mark.parametrize("family", list(fault_matrix.FAMILIES))
def test_fault_matrix_no_scheduler_death_or_slot_leak(family):
    expected, run = fault_matrix.FAMILIES[family]
    cells, problems = run()
    assert cells == expected, (cells, expected)
    assert not problems, "\n".join(problems)


def test_matrix_covers_documented_inventory():
    """Every runtime injection point named in docs/ROBUSTNESS.md must be in
    the matrix — adding a fire() site without matrix coverage is exactly the
    silent-cap failure mode this wrapper exists to prevent."""
    covered = set(fault_matrix.BATCH_POINTS + fault_matrix.SPEC_POINTS
                  + fault_matrix.ENGINE_POINTS
                  + fault_matrix.PAGED_POINTS + fault_matrix.ROUTER_POINTS
                  + fault_matrix.DISAGG_POINTS
                  + fault_matrix.DISAGG_PLAN_POINTS
                  + fault_matrix.DRAFT_POINTS
                  + fault_matrix.CONSTRAIN_POINTS
                  + (fault_matrix.FUSED_POINT,))
    doc = open(os.path.join(os.path.dirname(__file__), "..", "docs",
                            "ROBUSTNESS.md")).read()
    for point in covered:
        assert f"`{point}`" in doc, f"{point} missing from docs/ROBUSTNESS.md"