"""The program's spans in a trace: self times and idle-gap attribution."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import spans as sp  # noqa: E402
from bench import trace as tr  # noqa: E402

NS = 1e-9
# one batch close on one thread: stack, then the front end with the kernel
# launch inside it, then the result slices
CLOSE = [("repro/serve/close", 0, 100), ("repro/serve/stack", 10, 20),
         ("repro/kalman/front", 20, 50), ("repro/kernels/ggr_update", 30, 40),
         ("repro/serve/results", 60, 70)]


def _self(out):
    return {k: round(v["self_s"] / NS, 6) for k, v in out.items()}


def test_self_times_subtract_direct_children_only():
    out = sp.self_times(CLOSE, 0, 200)
    assert _self(out) == {"repro/serve/close": 50, "repro/serve/stack": 10,
                          "repro/kalman/front": 20,
                          "repro/kernels/ggr_update": 10,
                          "repro/serve/results": 10}
    assert all(v["n"] == 1 for v in out.values())
    # self times add up to the outermost span's time
    assert sum(_self(out).values()) == 100


def test_self_times_clip_to_the_window_and_count_repeats():
    two = CLOSE + [(n, s + 100, e + 100) for n, s, e in CLOSE]
    out = sp.self_times(two, 15, 165)
    assert _self(out) == {"repro/serve/close": 10 + 50,
                          "repro/serve/stack": 5 + 10,
                          "repro/kalman/front": 20 + 20,
                          "repro/kernels/ggr_update": 10 + 10,
                          "repro/serve/results": 10 + 5}
    assert out["repro/serve/close"]["n"] == 2
    assert sum(_self(out).values()) == 150
    assert sp.self_times(two, 300, 400) == {}


def test_self_times_keep_threads_apart():
    spans = [("repro/serve/close", 0, 100, "main"),
             ("repro/host/gc", 40, 60, "other")]
    assert _self(sp.self_times(spans, 0, 100)) == {"repro/serve/close": 100,
                                                   "repro/host/gc": 20}
    same = [s[:3] for s in spans]
    assert _self(sp.self_times(same, 0, 100)) == {"repro/serve/close": 80,
                                                  "repro/host/gc": 20}


def test_idle_gaps_go_to_the_innermost_program_span():
    busy = [(0, 12), (21, 33), (38, 62), (68, 72), (80, 104), (108, 150)]
    dev = {0: [("op", s, e) for s, e in busy]}
    harness = [("bench/flush", 0, 102), ("bench/submit", 102, 110)]
    g = tr.longest_gaps(dev, harness + CLOSE, 0, 200)
    assert [name for name, _ in g] == [
        "outside any bench span",     # 150..200
        "repro/serve/stack",          # 12..21
        "repro/serve/close",          # 72..80, in no stage of the close
        "repro/serve/results",        # 62..68
        "repro/kernels/ggr_update",   # 33..38, inside the front end
        "bench/submit"]               # 104..108, no program span
    assert [round(t / NS) for _, t in g] == [50, 9, 8, 6, 5, 4]
    # without the program's spans the harness span takes every close gap
    assert [name for name, _ in tr.longest_gaps(dev, harness, 0, 200)] == [
        "outside any bench span"] + ["bench/flush"] * 4 + ["bench/submit"]


def test_summary_reads_program_spans_from_a_recorded_trace(tmp_path):
    """A real trace taken here (CPU): the program's spans, nested under a
    harness span, come back with their self times and the close's ``n``."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    from repro import obs

    x = jnp.ones((64, 64))
    jax.block_until_ready(x @ x)
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("bench/window"):
        for cycle in range(2):
            with TraceAnnotation("bench/flush"):
                with obs.span("repro/serve/close", kind="kalman",
                              cycle=cycle, n=8):
                    with obs.span("repro/serve/stack"):
                        jax.block_until_ready(x @ x)
                    with obs.span("repro/kernels/ggr_update"):
                        jax.block_until_ready(x @ x)
    jax.profiler.stop_trace()
    out = sp.summary(str(tmp_path), chips=1)
    prog = out["program"]
    assert set(prog) == {"repro/serve/close", "repro/serve/stack",
                         "repro/kernels/ggr_update"}
    assert all(v["n"] == 2 and v["self_s"] > 0 for v in prog.values())
    assert out["closed"] == 16
    assert 0 < out["flush_program_s"] <= out["flush_s"]
    assert out["flush_program_s"] == pytest.approx(
        sum(v["self_s"] for v in prog.values()))
    assert out["idle_gaps"]  # no device here: the window is one gap


def test_load_refuses_a_directory_without_trace(tmp_path):
    with pytest.raises(FileNotFoundError):
        sp.load(str(tmp_path))
