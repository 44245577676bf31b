"""Tiny runs of a benchmark cell on the CPU for the tests beside this file:
the harness without its look for a chip, at sizes a test can hold."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

SPEC = harness.load_json(ROOT / "BENCHMARK.json")
SEED = 2**31 + 77


def tiny(workload: str, spec: dict = SPEC, root: Path = ROOT):
    """The cell's configuration cut to a CPU test's size."""
    cfg = dict(harness.resolve(spec, workload, root)["config"])
    cfg["clients"] = cfg["check_clients"] = 64
    return cfg


def run(workload: str, *, spec: dict = SPEC, root: Path = ROOT,
        control: bool = False, seconds: float = 1.0):
    return harness.run(workload, SEED, seconds, False, root=root, spec=spec,
                       config=tiny(workload, spec, root), require_tpu=False,
                       control=control, log=lambda msg: None)
