"""BENCHMARK.json: every name resolves to its files, and every name and
unit keeps to the allowed characters.  A new cell, configuration, traffic
mix and metric are added with new files and entries only."""
import json
import re
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TOP = {"command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"}


def test_top_level_keys_and_entry_keys():
    assert set(SPEC) == TOP
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}


def test_names_units_and_lines():
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    names = [x["name"] for x in SPEC["configs"] + SPEC["workloads"] + metrics]
    for n in names + [w["traffic"] for w in SPEC["workloads"]] \
            + [k for c in SPEC["configs"] for k in c["reduced"]]:
        assert NAME.match(n), n
    for group in (SPEC["configs"], SPEC["workloads"], metrics):
        assert len({x["name"] for x in group}) == len(group)
    for m in metrics:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        if m["name"].endswith("_roofline") or "_roofline." in m["name"]:
            assert m["unit"] == "%"
    for text in [x["why"] for x in SPEC["configs"] + SPEC["workloads"]] \
            + [c["source"] for c in SPEC["configs"]] \
            + [m["layer"] for m in SPEC["per_layer"]]:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    assert len(json.dumps(SPEC)) <= 64 * 1024


def test_every_cell_reports_setup_another_e2e_and_a_layer_metric():
    for w in SPEC["workloads"]:
        r = harness.resolve(SPEC, w["name"])
        e2e = {m["name"] for m in r["end_to_end"]}
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert r["per_layer"], w["name"]
        for m in r["per_layer"]:
            assert m["moves"] in e2e


def test_four_chip_cells_at_most_half():
    four = sum(1 for w in SPEC["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(SPEC["workloads"]) // 2)


def test_run_seconds_fit_the_check_with_24_cells():
    rs = SPEC["run_seconds"]
    assert 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_each_workload_resolves_its_files_by_name(workload):
    r = harness.resolve(SPEC, workload)
    for key in ("client", "reference"):
        assert r[key].is_file(), r[key]
    for name in ("REQUEST_KIND", "STATE_ARGS", "REFERENCE", "CHECKS",
                 "Clients", "work", "check"):
        assert hasattr(r["kind"], name), name
    for name, path in r["readers"].items():
        assert path.is_file(), path
        assert callable(harness.load_module(path).read)
    cfg = r["config"]
    assert cfg["name"] == r["cell"]["config"]
    assert set(cfg["limits"]) == set(r["kind"].CHECKS)
    entry = {c["name"]: c for c in SPEC["configs"]}[cfg["name"]]
    assert entry["file"].startswith("bench/configs/")
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])


def test_paths_hold_the_benchmark_and_the_command_stays_inside():
    assert SPEC["command"][1] == "bench/run.py"
    for p in SPEC["paths"]:
        assert re.match(r"^[A-Za-z0-9_./-]{1,200}$", p) and ".." not in p
        assert (ROOT / p).is_dir()
    for word in SPEC["command"]:
        assert not word.startswith("/") and ".." not in word


NEW_KIND = '''"""One-shot least-squares solves, each request its own."""
import numpy as np

from bench import counts

REQUEST_KIND = "lstsq"
STATE_ARGS = ()
REFERENCE = "lstsq_np"
CHECKS = ("solve_gap",)


def work(cfg):
    m, n = cfg["rows"], cfg["cols"]
    mults = counts.ggr_append_mults(n, m - n, n + 1)
    return {"flops": counts.mults_to_flops(mults), "bytes": 4 * (m * n + m + n)}


def check(clients, ref, chains):
    gaps = [np.linalg.norm(np.ravel(out[0]) - x) / np.linalg.norm(x)
            for c, outs in chains.items() for k, out in enumerate(outs, 1)
            for x in [ref.solve(*clients.inputs(c, k))]]
    return {"solve_gap": max(gaps, default=np.inf), "checked": len(gaps)}


class Clients:
    def __init__(self, cfg, count, seed, dtype):
        self.count, self.seed, self.dtype = count, seed, dtype
        self.m, self.n = cfg["rows"], cfg["cols"]

    def device_model(self, jnp):
        return {}

    def start(self, c):
        return ()

    def inputs(self, c, k):
        rng = np.random.default_rng([self.seed, c, k])
        return (rng.standard_normal((self.m, self.n)).astype(np.float32),
                rng.standard_normal(self.m).astype(np.float32))

    def request(self, c, k, state):
        return tuple(a.astype(self.dtype) for a in self.inputs(c, k))
'''

NEW_REFERENCE = '''import numpy as np


def solve(A, b):
    return np.linalg.lstsq(np.float64(A), np.float64(b), rcond=None)[0]
'''


def test_new_kind_config_traffic_and_metric_by_files_only(tmp_path):
    """Copy the benchmark and add a client kind of its own (one-shot solves,
    another request kind, work model and check), with its reference,
    configuration, traffic mix and metric, as files and entries only: the
    harness finds each by name and runs the new cell end to end."""
    import bench_case

    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = tmp_path / "bench"
    (b / "clients" / "oneshot_lstsq.py").write_text(NEW_KIND)
    (b / "reference" / "lstsq_np.py").write_text(NEW_REFERENCE)
    (b / "configs" / "lstsq-tiny.json").write_text(json.dumps(
        {"name": "lstsq-tiny", "kind": "oneshot_lstsq", "dtype": "float32",
         "rows": 16, "cols": 4, "clients": 64, "check_clients": 16,
         "kernel_pattern": "lstsq", "reduced": [],
         "limits": {"solve_gap": 1e-3}}))
    (b / "traffic" / "brief.json").write_text(json.dumps(
        {"loop": "closed", "warm_steps": 1}))
    (b / "metrics" / "solved_per_s.py").write_text(
        "def read(ctx):\n    return ctx.served / ctx.window_s\n")
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({"name": "lstsq-tiny", "source": "x",
                            "file": "bench/configs/lstsq-tiny.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "tiny.brief", "config": "lstsq-tiny",
                              "traffic": "brief", "chips": 1, "why": "x"})
    spec["end_to_end"].append({"name": "solved_per_s", "unit": "1/s",
                               "better": "higher", "bound": 0.05,
                               "source": "host_clock",
                               "workloads": ["tiny.brief"]})
    r = harness.resolve(spec, "tiny.brief", root=tmp_path)
    assert r["client"] == b / "clients" / "oneshot_lstsq.py"
    assert r["reference"] == b / "reference" / "lstsq_np.py"
    assert {m["name"] for m in r["end_to_end"]} == {"solved_per_s", "setup_s"}
    assert r["kind"].work(r["config"])["bytes"] == 4 * (64 + 16 + 4)

    out = bench_case.run("tiny.brief", spec=spec, root=tmp_path)
    assert out["correct"], out["checks"]
    assert list(out["checks"]) == ["solve_gap"]
    assert out["metrics"]["solved_per_s"]["value"] > 0
