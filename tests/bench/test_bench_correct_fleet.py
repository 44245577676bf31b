"""``correct`` for the fleet cell: true for the served path, false for its
control (the reference backend in bfloat16) and for each planted fault."""
import pytest

import bench_case
from bench import faults, harness

CELLS = ["fleet.saturate"]


@pytest.mark.parametrize("workload", CELLS)
def test_served_path_is_correct(workload):
    out = bench_case.run(workload)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    for v in out["checks"].values():
        assert v["value"] <= v["limit"]


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload):
    out = bench_case.run(workload, control=True)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
@pytest.mark.parametrize("workload", CELLS)
def test_planted_fault_is_not_correct(workload, fault):
    kind = harness.resolve(bench_case.SPEC, workload)["kind"]
    with faults.planted(fault, kind):
        out = bench_case.run(workload)
    assert not out["correct"], out["checks"]
