"""The trace-to-numbers reduction, on synthetic and on recorded traces."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace as tr  # noqa: E402


def test_merge_and_busy_union_overlaps_once():
    ev = [("a", 0, 10), ("b", 5, 15), ("c", 20, 30), ("d", 21, 22)]
    assert tr.merge(ev) == [(0, 15), (20, 30)]
    assert tr.busy_ns(ev, 0, 40) == 25
    # clipped to the window
    assert tr.busy_ns(ev, 8, 25) == 7 + 5


def test_gaps_cover_window_less_busy():
    ev = [("a", 10, 20), ("b", 30, 35)]
    g = tr.gaps(ev, 0, 50)
    assert g == [(0, 10), (20, 30), (35, 50)]
    assert sum(e - s for s, e in g) + tr.busy_ns(ev, 0, 50) == 50


def test_idle_share_of_an_empty_chip_is_whole_window():
    assert tr.busy_ns([], 0, 100) == 0
    assert tr.gaps([], 0, 100) == [(0, 100)]


def test_covering_span_takes_innermost():
    spans = [("bench/poll", 0, 100), ("bench/flush", 40, 60)]
    assert tr.covering_span(spans, 50) == "bench/flush"
    assert tr.covering_span(spans, 10) == "bench/poll"
    assert tr.covering_span(spans, 200) == "outside any bench span"


def test_kernel_events_match_by_name_or_stats():
    ev = [("fusion.1", 0, 5), ("custom-call.3", 5, 9), ("copy", 9, 10)]
    texts = ["fusion.1", "custom-call.3 _batched_update_kernel", "copy"]
    hit = tr.matching(ev, texts, "batched_update")
    assert hit == [("custom-call.3", 5, 9)]


def test_top_ops_and_longest_gaps():
    dev = {0: [("k", 0, 10), ("s", 10, 12), ("k", 20, 30)],
           1: [("k", 0, 5)]}
    top = tr.top_ops(dev)
    assert top[0][0] == "k" and abs(top[0][1] - 25e-9) < 1e-18
    spans = [("bench/submit", 12, 20), ("bench/poll", 5, 40)]
    g = tr.longest_gaps(dev, spans, 0, 40, k=2)
    assert g[0] == ["bench/poll", 35e-9]     # chip 1: idle 5..40
    assert g[1] == ["bench/poll", 10e-9]     # chip 0: idle 30..40 (tie 12..20 later)


def test_load_reads_harness_spans_from_a_recorded_trace(tmp_path):
    """A real profiler trace taken here (CPU): the window and the harness's
    spans come back on one clock; no TPU plane means no device ops."""
    import jax
    import jax.numpy as jnp

    x = jnp.ones((64, 64))
    jax.block_until_ready(x @ x)
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench/window"):
        with jax.profiler.TraceAnnotation("bench/submit"):
            jax.block_until_ready(x @ x)
    jax.profiler.stop_trace()
    t = tr.load(str(tmp_path))
    assert t["window"] is not None
    lo, hi = t["window"]
    names = {n for n, _, _ in t["spans"]}
    assert "bench/submit" in names
    (s, e), = [(s, e) for n, s, e in t["spans"] if n == "bench/submit"]
    assert lo <= s <= e <= hi
    assert t["device"] == {}


def test_load_refuses_a_directory_without_trace(tmp_path):
    with pytest.raises(FileNotFoundError):
        tr.load(str(tmp_path))
