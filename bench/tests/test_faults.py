"""A run whose timed path is broken underneath has to come out not
correct: one run per fault the cell can have, on the CPU."""
import pytest

from bench.tests._proc import rehearse

ONE_CHIP = ["stale_state", "half_work", "altered_answer"]


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("faults")
    args = [a for f in ONE_CHIP for a in ("--fault", f)]
    return {
        cell: {r["fault"]: r for r in rehearse(tmp, cell, points, *args)}
        for cell, points in [("syn64.count.e16", 1500), ("syn16.pairs.e03", 3000)]
    }


@pytest.mark.parametrize("cell", ["syn64.count.e16", "syn16.pairs.e03"])
@pytest.mark.parametrize("fault", ONE_CHIP)
def test_fault_is_not_correct(runs, cell, fault):
    out = runs[cell][fault]
    assert out["correct"] is False
    assert out["failed"] == out["attempted"] >= 1
    assert any(c["value"] > c["limit"] for c in out["checks"].values())
