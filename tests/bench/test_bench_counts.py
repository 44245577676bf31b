"""The benchmark's copied work model against the program's own."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import counts, harness  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("n,p,w", [(9, 9, 10), (128, 8, 129), (6, 3, 7),
                                   (1, 1, 1), (16, 8, 17), (128, 1, 128)])
def test_copied_mults_equal_core_counts(n, p, w):
    from repro.core.counts import ggr_append_mults, mults_to_flops

    assert counts.ggr_append_mults(n, p, w) == ggr_append_mults(n, p, w)
    assert counts.mults_to_flops(7) == mults_to_flops(7)


def test_fleet_work_matches_the_dispatcher_formula():
    from repro.obs import ggr_append_flops

    r = harness.resolve(SPEC, "fleet.saturate")
    cfg = r["config"]
    n, w, p = cfg["state_dim"], cfg["noise_dim"], cfg["meas_dim"]
    work = r["kind"].work(cfg)
    # repro.serve.dispatch counts a Kalman step as this append sweep
    assert work["flops"] == ggr_append_flops(w + n, n + p, w + n + 1)
    assert work["bytes"] == 348          # R, d, z in; R', d' out; float32


@pytest.mark.parametrize("workload", CELLS)
def test_roofline_is_hbm_bound_at_v5e_peaks(workload):
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    v5e = peaks["devices"]["TPU v5 lite"]
    r = harness.resolve(SPEC, workload)
    work = r["kind"].work(r["config"])
    assert work["bytes"] / v5e["hbm_bytes_per_s"] > \
        work["flops"] / v5e["bf16_flops_per_s"]
