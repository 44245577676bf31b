"""``correct`` for the batched least-squares cell at a CPU size that still
takes the blocked route: true for the served path, false for its control
(the reference backend in bfloat16) and for each planted fault."""
import pytest

import bench_case
from bench import faults, harness

CELL = "tall512.saturate"


def _cut():
    """256 x 128 (the smallest blocked shape), 24 clients, all checked."""
    cfg = dict(harness.resolve(bench_case.SPEC, CELL)["config"])
    cfg.update(rows=256, clients=24, check_clients=24, pool=8)
    return cfg


def _run(control=False):
    return harness.run(CELL, bench_case.SEED, 1.0, False, config=_cut(),
                       require_tpu=False, control=control,
                       log=lambda msg: None)


def test_cut_takes_the_blocked_route():
    from repro.solvers import lstsq_route

    cfg = _cut()
    assert lstsq_route(cfg["rows"], cfg["cols"]) == "blocked"


def test_served_path_is_correct():
    out = _run()
    assert out["correct"], out["checks"]
    assert list(out["checks"]) == ["solve_gap", "resid_gap"]
    for v in out["checks"].values():
        assert v["value"] <= v["limit"]


def test_control_is_not_correct():
    out = _run(control=True)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
def test_planted_fault_is_not_correct(fault):
    kind = harness.resolve(bench_case.SPEC, CELL)["kind"]
    with faults.planted(fault, kind):
        out = _run()
    assert not out["correct"], out["checks"]
