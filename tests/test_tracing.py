"""Host spans of the served path, and the collector on the served path.

* every stage of a batch close shows in a profiler trace as a
  ``repro/...`` span, nested under the close that caused it and carrying
  its ``kind``/``cycle``/``n``;
* the spans change no lowered program, so serving under them compiles no
  more than serving without them;
* an installed collector counts JAX's compile events, and never makes
  ``submit`` or a batch close block on the device or copy a factor to the
  host.
"""
import contextlib
import gc
import glob
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import obs
from repro.obs import _state
from repro.serve import ContinuousBatcher, Dispatcher

N, W, P = 6, 3, 3  # a 3-D tracking fleet's state, noise and plot sizes
CLOSE_STAGES = ("repro/serve/stack", "repro/kalman/front",
                "repro/kernels/ggr_update", "repro/serve/results")


class _Fleet:
    """``count`` tracks sharing one model, stepped through an engine."""

    def __init__(self, count: int, seed: int = 0):
        rng = np.random.default_rng(seed)
        self.F = jnp.asarray(np.eye(N) + np.eye(N, k=3), jnp.float32)
        self.G = jnp.asarray(np.vstack([0.5 * np.eye(3), np.eye(3)]),
                             jnp.float32)
        self.Qi = jnp.eye(W, dtype=jnp.float32)
        self.H = jnp.asarray(np.hstack([np.eye(3), np.zeros((3, 3))]),
                             jnp.float32)
        self.rng = rng
        self.state = [(jnp.eye(N, dtype=jnp.float32),
                       jnp.asarray(rng.standard_normal(N), jnp.float32))
                      for _ in range(count)]

    def step(self, engine) -> None:
        """Submit one step of every track, close, and take the results."""
        tickets = [engine.submit("kalman", R, d, self.F, self.Qi, self.H,
                                 jnp.asarray(self.rng.standard_normal(P),
                                             jnp.float32), self.G)
                   for R, d in self.state]
        engine.flush()
        engine.drain()
        engine.poll()
        self.state = [engine.result(t) for t in tickets]


def _engine(backend: str = "reference", admit_max=None):
    return ContinuousBatcher(
        Dispatcher(backend=backend, max_batch=8, double_buffer=True),
        admit_max=admit_max, retain_cycles=None)


def _host_spans(profile_dir: str) -> list:
    """``(name, start_ns, end_ns, line, args)`` of every ``repro/`` host
    event in the trace written under ``profile_dir``."""
    from jax.profiler import ProfileData

    path = max(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for i, ln in enumerate(plane.lines):
            out.extend((e.name, e.start_ns, e.end_ns, (plane.name, i),
                        dict(e.stats))
                       for e in ln.events if e.name.startswith("repro/"))
    return out


def test_served_path_spans_nest_under_their_close(tmp_path):
    fleet, engine = _Fleet(8), _engine()
    fleet.step(engine)  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(2):
            fleet.step(engine)
        gc.collect()
    spans = _host_spans(str(tmp_path))
    names = {s[0] for s in spans}
    for name in ("repro/serve/submit", "repro/serve/close", *CLOSE_STAGES,
                 "repro/serve/pump", "repro/host/gc"):
        assert name in names, name
    closes = [s for s in spans if s[0] == "repro/serve/close"]
    assert [c[4] for c in closes] == [{"kind": "kalman", "cycle": k, "n": 8}
                                      for k in (1, 2)]
    stages = [s for s in spans if s[0] in CLOSE_STAGES]
    assert len(stages) == 2 * len(CLOSE_STAGES)
    for name, s, e, line, args in stages:
        # each stage lies inside one close, on its thread, and carries the
        # close's arguments; the results split adds its chunk's real and
        # padded sizes
        (close,) = [c for c in closes
                    if c[3] == line and c[1] <= s and e <= c[2]]
        if name == "repro/serve/results":
            assert args == {**close[4], "n": 8, "padded": 8}
        else:
            assert args == close[4]
    # submits and pumps are not part of a close
    for name, s, e, line, args in spans:
        if name in ("repro/serve/submit", "repro/serve/pump"):
            assert not any(c[1] <= s and e <= c[2] for c in closes)
            assert "cycle" not in args
    gen = [s[4] for s in spans if s[0] == "repro/host/gc"]
    assert any(a.get("generation") == 2 for a in gen)


def test_spans_change_no_lowered_program(tmp_path):
    """A live host span enters no ``jax.named_scope``: JAX's name stack
    stays empty, and a jitted call under it hits the same executable."""
    from jax._src import source_info_util

    f = jax.jit(lambda x: jnp.sin(x) * 2.0)
    x = jnp.ones(5)
    f(x)
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("repro/serve/close", kind="kalman", cycle=0, n=1):
            assert str(source_info_util.current_name_stack()) == ""
            f(x)
            text = f.lower(x).as_text(debug_info=True)
    assert f._cache_size() == 1
    assert "repro/serve/close" not in text


def test_spans_are_inert_without_a_profiler_trace():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert obs.span("repro/serve/close", n=1) is obs.span("repro/serve/stack")


def _backend_compiles(reg) -> float:
    m = reg.find("jax.compile_events", stage="backend_compile")
    return 0.0 if m is None else m.value


def test_serving_under_spans_compiles_no_more(tmp_path, monkeypatch):
    """After warm-up, batches served under live spans trigger no more
    backend compiles than the same batches with the spans patched out."""
    fleet, engine = _Fleet(8), _engine()
    with obs.collecting() as reg, jax.profiler.trace(str(tmp_path)):
        for _ in range(2):
            fleet.step(engine)
        before = _backend_compiles(reg)
        for _ in range(3):
            fleet.step(engine)
        with_spans = _backend_compiles(reg) - before

        monkeypatch.setattr(obs, "span",
                            lambda *a, **k: contextlib.nullcontext())
        for _ in range(2):
            fleet.step(engine)
        before = _backend_compiles(reg)
        for _ in range(3):
            fleet.step(engine)
        without = _backend_compiles(reg) - before
    assert with_spans <= without


def test_result_split_compiles_once_per_padded_size(monkeypatch):
    """Closes of 9 and then 13 requests both pad to 16: the second close's
    results split compiles nothing, and a collector counts one split per
    chunk."""
    from repro.serve import dispatch

    real = dispatch._split_rows
    split_compiles = []

    def counting(*a, **k):
        before = _backend_compiles(reg)
        out = real(*a, **k)
        split_compiles.append(_backend_compiles(reg) - before)
        return out

    monkeypatch.setattr(dispatch, "_split_rows", counting)
    engine = ContinuousBatcher(
        Dispatcher(backend="reference", max_batch=16, double_buffer=True),
        retain_cycles=None)
    with obs.collecting() as reg:
        _Fleet(9, seed=1).step(engine)
        cached = real._cache_size()
        _Fleet(13, seed=2).step(engine)
        assert reg.find("serve.result_splits", kind="kalman").value == 2
    assert real._cache_size() == cached
    assert len(split_compiles) == 2 and split_compiles[1] == 0


def test_collector_counts_compiles_while_installed():
    from jax._src import monitoring

    with obs.collecting() as reg:
        jax.jit(lambda x: x * 3.0 + 1.0)(jnp.ones(7))  # a fresh function
        assert _state._compile_event in \
            monitoring.get_event_duration_listeners()
    for stage in ("jaxpr_trace", "jaxpr_to_mlir_module", "backend_compile"):
        assert reg.find("jax.compile_events", stage=stage).value >= 1
        assert reg.find("jax.compile_seconds", stage=stage).value > 0.0
    assert _state._compile_event not in \
        monitoring.get_event_duration_listeners()
    n = _backend_compiles(reg)
    jax.jit(lambda x: x * 5.0)(jnp.ones(7))
    assert _backend_compiles(reg) == n


@pytest.mark.parametrize("backend", ["pallas", "reference"])
def test_collector_never_blocks_submit_or_close(backend, monkeypatch):
    """With a collector installed, neither ``submit`` nor a batch close
    waits on the device or copies a factor to the host; the factor-health
    gauges still appear once the chunks are drained."""
    calls = {"block": 0, "health": 0, "inside": 0}
    inside = []

    def counting(name, fn):
        def wrapped(*a, **k):
            calls[name] += 1
            calls["inside"] += bool(inside)
            return fn(*a, **k)
        return wrapped

    def marking(fn):
        def wrapped(*a, **k):
            inside.append(1)
            try:
                return fn(*a, **k)
            finally:
                inside.pop()
        return wrapped

    monkeypatch.setattr(jax, "block_until_ready",
                        counting("block", jax.block_until_ready))
    monkeypatch.setattr(obs, "factor_health",
                        counting("health", obs.factor_health))
    for meth in ("submit", "_close"):
        monkeypatch.setattr(ContinuousBatcher, meth,
                            marking(getattr(ContinuousBatcher, meth)))
    fleet, engine = _Fleet(12), _engine(backend, admit_max=8)
    with obs.collecting() as reg:
        for _ in range(2):  # one close inside submit, one by flush
            fleet.step(engine)
    assert calls["inside"] == 0
    assert calls["health"] >= 2 and calls["block"] >= 1
    for gauge in ("serve.r_diag_min", "serve.r_diag_max",
                  "serve.r_cond_estimate"):
        assert reg.find(gauge, kind="kalman") is not None
