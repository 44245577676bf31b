"""The served least-squares kinds on the blocked route.

* ``lstsq`` and ``lstsq_pivoted`` requests above ``lstsq_route``'s blocked
  size, served through ``ContinuousBatcher`` over ``Dispatcher``, agree with
  numpy's float64 solve under both schedules of the blocked driver;
* ``backend="reference"`` runs the solve kinds with no Pallas call, and
  agrees with the kernel path;
* a ``degraded_mode`` override applied after a shape was first served
  reaches the compiled solve;
* ``lstsq_route`` is the one routing rule, and ``solvers.lstsq_solves``
  counts one per solve under a collector and nothing without one.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels.backend import degraded_mode
from repro.serve import ContinuousBatcher, Dispatcher
from repro.serve.dispatch import _batched_solve, _solve_key
from repro.solvers import ggr_lstsq, lstsq_route

M, N, B = 256, 128, 4  # the smallest blocked shape; a 4-request batch


def _problems(seed: int = 0, m: int = M, n: int = N, count: int = B):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((count, m, n)).astype(np.float32),
            rng.standard_normal((count, m)).astype(np.float32))


def _serve(A, b, kind="lstsq", backend="pallas"):
    """Serve one batch of solves; the per-request results on the host."""
    eng = ContinuousBatcher(Dispatcher(backend=backend, double_buffer=True),
                            admit_max=None, retain_cycles=None)
    tickets = [eng.submit(kind, Ai, bi) for Ai, bi in zip(A, b)]
    eng.flush()
    eng.drain()
    eng.poll()
    return [tuple(np.asarray(a) for a in eng.result(t)) for t in tickets]


def _numpy(A, b):
    A, b = np.float64(A), np.float64(b)
    x = np.stack([np.linalg.lstsq(Ai, bi, rcond=None)[0]
                  for Ai, bi in zip(A, b)])
    return x, np.linalg.norm(np.einsum("bmn,bn->bm", A, x) - b, axis=1)


@pytest.mark.parametrize("kind", ["lstsq", "lstsq_pivoted"])
@pytest.mark.parametrize("schedule", ["tree", "fused"])
def test_blocked_route_serves_numpy_solutions(kind, schedule):
    """Tree is the CPU default; fused, the chip's, is forced (the kernels
    run in interpret mode here)."""
    A, b = _problems()
    assert lstsq_route(M, N) == "blocked"
    with degraded_mode(schedule=schedule):
        out = _serve(A, b, kind)
    x_np, r_np = _numpy(A, b)
    x = np.stack([o[0] for o in out])
    np.testing.assert_allclose(x, x_np, rtol=0, atol=2e-5)
    np.testing.assert_allclose([o[1] for o in out], r_np, rtol=2e-5)
    if kind == "lstsq_pivoted":
        assert [int(o[2]) for o in out] == [N] * B


@pytest.mark.parametrize("pivoted", [False, True])
def test_reference_backend_calls_no_kernel(pivoted):
    A, b = _problems(1)
    Ab, bb = jnp.asarray(A), jnp.asarray(b)

    def jaxpr(backend):
        key = _solve_key(backend, M, N)
        return str(jax.make_jaxpr(functools.partial(
            _batched_solve, pivoted=pivoted, route=key[0], schedule=key[1],
            interpret=key[2]))(Ab, bb))

    assert "pallas_call" not in jaxpr("reference")
    assert "pallas_call" in jaxpr("pallas")
    kind = "lstsq_pivoted" if pivoted else "lstsq"
    ref, ker = _serve(A, b, kind, "reference"), _serve(A, b, kind, "pallas")
    for r, k in zip(ref, ker):
        np.testing.assert_allclose(r[0], k[0], rtol=0, atol=2e-5)
        np.testing.assert_allclose(r[1], k[1], rtol=2e-5)


def test_solve_key_separates_backends_and_rungs():
    assert _solve_key("reference", M, N) == ("sweep", None, None)
    assert _solve_key("pallas", 12, 3) == ("sweep", None, None)
    assert _solve_key("pallas", M, N) == ("blocked", None, True)
    with degraded_mode(schedule="tree", interpret=False):
        assert _solve_key("pallas", M, N) == ("blocked", "tree", False)


def test_degraded_schedule_reaches_the_compiled_solve():
    """The ladder's schedule rung, entered after the shape was first served
    on the other schedule, gets a solve compiled for its schedule, not the
    cached one; leaving it brings the first solve back."""
    A, b = _problems(2)
    first = np.stack([o[0] for o in _serve(A, b)])           # tree
    with degraded_mode(schedule="fused"):
        fused = np.stack([o[0] for o in _serve(A, b)])
    again = np.stack([o[0] for o in _serve(A, b)])
    assert not np.array_equal(fused, first)
    np.testing.assert_array_equal(again, first)
    np.testing.assert_allclose(fused, _numpy(A, b)[0], rtol=0, atol=2e-5)


def test_triangularize_auto_routes_by_lstsq_route(monkeypatch):
    from repro.core import blocked
    from repro.solvers import lstsq

    seen, ran = [], []
    real_blocked = blocked.ggr_triangularize_blocked
    monkeypatch.setattr(lstsq, "lstsq_route",
                        lambda m, n: seen.append((m, n)) or "blocked")
    monkeypatch.setattr(blocked, "ggr_triangularize_blocked",
                        lambda X, n: ran.append(X.shape) or real_blocked(X, n))
    X = jnp.asarray(np.random.default_rng(3).standard_normal((12, 4)),
                    jnp.float32)
    lstsq._triangularize_auto(X, 3)
    assert seen == [(12, 3)] and ran == [(12, 4)]


def test_lstsq_solves_counted_per_route():
    A, b = _problems(4)
    As, bs = _problems(4, m=12, n=3, count=3)
    _serve(A, b)
    _serve(As, bs)
    ggr_lstsq(jnp.asarray(As[0]), jnp.asarray(bs[0]))
    assert not obs.enabled() and obs.registry().collect() == []
    with obs.collecting() as reg:
        _serve(A, b)
        _serve(As, bs, "lstsq_pivoted")
        ggr_lstsq(jnp.asarray(As[0]), jnp.asarray(bs[0]))
    assert reg.find("solvers.lstsq_solves", route="blocked").value == B
    assert reg.find("solvers.lstsq_solves", route="sweep").value == 3 + 1
