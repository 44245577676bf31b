"""``correct`` on the four-chip path (the dispatcher's batch mesh), on four
virtual CPU devices in a child process: true for the sharded served path,
false for its control and for each planted fault.  No committed cell takes
four chips yet, so the test adds one of its own to a copy of the spec: the
harness takes the mesh from the cell's ``chips`` alone."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

CHILD = """
import copy, json, sys
sys.path[:0] = [{here!r}, {src!r}]
import bench_case
from bench import faults, harness
spec = copy.deepcopy(bench_case.SPEC)
spec["workloads"].append({{"name": "fleet-x4.saturate", "config": "track-dwna3d",
                          "traffic": "saturate", "chips": 4, "why": "x"}})
cell = "fleet-x4.saturate"
kind = harness.resolve(spec, cell)["kind"]
out = {{"sound": bench_case.run(cell, spec=spec),
        "control": bench_case.run(cell, spec=spec, control=True)}}
for f in ("unchanged", "half", "altered"):
    with faults.planted(f, kind):
        out[f] = bench_case.run(cell, spec=spec)
print(json.dumps({{k: [v["correct"], v["device"]["count"]] for k, v in out.items()}}))
"""


def test_sharded_path_correct_only_when_served_right():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = textwrap.dedent(CHILD.format(here=str(HERE), src=str(ROOT / "src")))
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["sound"] == [True, 4]
    for fault in ("control", "unchanged", "half", "altered"):
        assert res[fault][0] is False, fault
