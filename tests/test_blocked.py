"""Blocked panel-pipeline driver: adversarial-shape correctness, schedule
equivalence, padding helpers, backend autodetection, and the compile-once
regression (the panel loop must not Python-unroll with the tile grid)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

jax.config.update("jax_enable_x64", True)

from repro.core import (
    ggr_qr2,
    ggr_qr_blocked,
    ggr_qr_blocked_reference,
    ggr_triangularize,
    ggr_triangularize_blocked,
)
from repro.kernels import batched_geqrt, default_interpret, pad_to_tile

SCHEDULES = ["tree", "fused"]


def _rand(shape, seed=0, dtype=np.float64):
    return np.random.default_rng(seed).standard_normal(shape).astype(dtype)


# ---------------------------------------------------------------------------
# correctness: blocked == unblocked == numpy on adversarial shapes
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("m,n,tile", [
    (32, 32, 8),      # square, tile divides
    (100, 52, 32),    # neither dim a tile multiple
    (40, 90, 16),     # wide (m < n), non-multiples
    (129, 65, 64),    # tall, odd row tile count
    (33, 7, 8),       # thin tail panel
    (65, 64, 32),     # one extra row
])
@pytest.mark.parametrize("schedule", SCHEDULES)
def test_blocked_matches_unblocked(m, n, tile, schedule):
    A = _rand((m, n), seed=m * 1000 + n)
    R = np.asarray(ggr_qr_blocked(jnp.asarray(A), tile=tile, schedule=schedule))
    R2 = np.asarray(ggr_qr2(jnp.asarray(A)))
    kk = min(m, n)
    # same factor up to row signs (degenerate last-row pivots may flip)
    np.testing.assert_allclose(np.abs(R[:kk]), np.abs(R2[:kk]), atol=1e-12)
    Rnp = np.linalg.qr(A, mode="r")
    np.testing.assert_allclose(np.abs(R[:kk]), np.abs(Rnp[:kk]), atol=1e-12)
    assert np.allclose(np.tril(R, -1), 0.0)


@pytest.mark.parametrize("schedule", SCHEDULES)
def test_blocked_matches_reference_driver(schedule):
    A = _rand((128, 128), seed=3)
    R = np.asarray(ggr_qr_blocked(jnp.asarray(A), tile=32, schedule=schedule))
    Rref = np.asarray(ggr_qr_blocked_reference(jnp.asarray(A), tile=32))
    np.testing.assert_allclose(np.abs(R), np.abs(Rref), atol=1e-11)


@pytest.mark.parametrize("schedule", SCHEDULES)
def test_schedules_agree(schedule):
    """tree and fused are different orthogonal reductions of the same matrix:
    identical R up to roundoff."""
    A = _rand((96, 80), seed=11)
    R = np.asarray(ggr_qr_blocked(jnp.asarray(A), tile=16, schedule=schedule))
    Rt = np.asarray(ggr_qr_blocked(jnp.asarray(A), tile=16, schedule="tree"))
    np.testing.assert_allclose(np.abs(R), np.abs(Rt), atol=1e-12)


@pytest.mark.parametrize("schedule", SCHEDULES)
def test_rank_deficient_safe(schedule):
    """Zero and duplicate columns: rows beyond the rank are arbitrary
    orthogonal mixes of roundoff, so the meaningful invariants are
    finiteness, triangularity, the Gram identity R^T R = A^T A, and the
    exactly-zero column staying exactly zero."""
    A = _rand((48, 24), seed=13)
    A[:, 7] = 0.0
    A[:, 15] = A[:, 3]
    R = np.asarray(ggr_qr_blocked(jnp.asarray(A), tile=8, schedule=schedule))
    assert np.isfinite(R).all()
    assert np.allclose(np.tril(R, -1), 0.0)
    np.testing.assert_allclose(R.T @ R, A.T @ A, atol=1e-11)
    assert np.abs(R[8:, 7]).max() == 0.0  # zero pivot column: exact no-op


@pytest.mark.parametrize("schedule", SCHEDULES)
def test_graded_rows(schedule):
    """1e±8 row scaling: the safe-Givens max-abs column scaling keeps the
    factorization accurate across 16 orders of magnitude."""
    rng = np.random.default_rng(17)
    scale = 10.0 ** rng.uniform(-8.0, 8.0, size=64)
    A = rng.standard_normal((64, 32)) * scale[:, None]
    R = np.asarray(ggr_qr_blocked(jnp.asarray(A), tile=16, schedule=schedule))
    Rnp = np.linalg.qr(A, mode="r")
    denom = np.abs(Rnp).max()
    assert np.isfinite(R).all()
    np.testing.assert_allclose(np.abs(R[:32]) / denom, np.abs(Rnp) / denom,
                               atol=1e-13)


def test_blocked_f32_larger():
    A = _rand((256, 192), seed=19, dtype=np.float32)
    R = np.asarray(ggr_qr_blocked(jnp.asarray(A), tile=64))
    Rnp = np.linalg.qr(A.astype(np.float64), mode="r")
    np.testing.assert_allclose(np.abs(R[:192]), np.abs(Rnp), atol=5e-3)


@pytest.mark.parametrize("schedule", SCHEDULES)
def test_triangularize_rhs_rides(schedule):
    """Trailing rhs columns come back as Q^T-transformed data: the normal
    equations invariant R^T d = A^T b holds and the residual block keeps
    its column norms."""
    A = _rand((80, 40), seed=23)
    b = _rand((80, 3), seed=24)
    X = jnp.asarray(np.concatenate([A, b], axis=1))
    Xb = np.asarray(ggr_triangularize_blocked(X, 40, tile=16, schedule=schedule))
    Xu = np.asarray(ggr_triangularize(X, 40))
    np.testing.assert_allclose(np.abs(Xb[:40, :40]), np.abs(Xu[:40, :40]),
                               atol=1e-12)
    np.testing.assert_allclose(Xb[:40, :40].T @ Xb[:40, 40:], A.T @ b,
                               atol=1e-11)
    np.testing.assert_allclose(np.linalg.norm(Xb[40:, 40:], axis=0),
                               np.linalg.norm(Xu[40:, 40:], axis=0), atol=1e-11)


def test_lstsq_blocked_routing():
    """Above the size threshold ggr_lstsq dispatches to the blocked driver
    and still solves the problem."""
    from repro.solvers import ggr_lstsq
    from repro.solvers.lstsq import _BLOCKED_MIN_PIVOTS, _BLOCKED_MIN_ROWS

    m, n = _BLOCKED_MIN_ROWS + 44, _BLOCKED_MIN_PIVOTS + 12
    A = _rand((m, n), seed=29)
    b = _rand((m,), seed=30)
    fit = ggr_lstsq(jnp.asarray(A), jnp.asarray(b))
    x_np, res, *_ = np.linalg.lstsq(A, b, rcond=None)
    np.testing.assert_allclose(np.asarray(fit.x), x_np, atol=1e-9)
    np.testing.assert_allclose(float(fit.resid), np.sqrt(res[0]), rtol=1e-9)


# ---------------------------------------------------------------------------
# compile-once regression: jaxpr size must not scale with the tile grid
# ---------------------------------------------------------------------------
def _count_eqns(jaxpr):
    n = len(jaxpr.eqns)
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            if hasattr(v, "jaxpr"):  # closed sub-jaxprs (fori_loop bodies...)
                n += _count_eqns(v.jaxpr)
    return n


def test_panel_loop_not_unrolled():
    """4x more panels must not grow the jaxpr: panels run under fori_loop
    over dynamic slices (only O(log) frame phases are staged out)."""
    def trace(n):
        fn = lambda A: ggr_qr_blocked(A, tile=8, schedule="tree", interpret=True)
        x = jax.ShapeDtypeStruct((64, n), jnp.float32)
        return jax.make_jaxpr(fn)(x).jaxpr

    small, big = _count_eqns(trace(64)), _count_eqns(trace(256))
    assert big <= small + 8, (
        f"panel loop appears Python-unrolled: {small} eqns at 8 panels vs "
        f"{big} at 32 panels")


def test_reference_driver_does_unroll():
    """The baseline driver really is Python-unrolled (what the regression
    above protects against)."""
    def trace(n):
        fn = lambda A: ggr_qr_blocked_reference(A, tile=8)
        x = jax.ShapeDtypeStruct((64, n), jnp.float32)
        return jax.make_jaxpr(fn)(x).jaxpr

    small, big = _count_eqns(trace(64)), _count_eqns(trace(256))
    assert big > small + 1000, f"expected unrolled growth, got {small} -> {big}"


# ---------------------------------------------------------------------------
# satellites: pad_to_tile, default_interpret, the batched GEQRT tile kernel
# ---------------------------------------------------------------------------
def test_pad_to_tile():
    x = jnp.ones((5, 13))
    p = pad_to_tile(x, (8, 8))
    assert p.shape == (8, 16)
    assert float(p[:5, :13].min()) == 1.0 and float(p.sum()) == 65.0
    assert pad_to_tile(x, 13, axes=(1,)) is x  # exact multiple: no copy
    assert pad_to_tile(x, (4,), axes=(0,)).shape == (8, 13)
    with pytest.raises(ValueError):
        pad_to_tile(x, (0,))
    with pytest.raises(ValueError):
        pad_to_tile(x, (4, 4), axes=(0,))


def test_default_interpret_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert default_interpret() is False
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert default_interpret() is True
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET")
    # no override: CPU hosts interpret, device backends compile
    assert default_interpret() == (jax.default_backend() == "cpu")


def test_batched_geqrt_tile_kernel():
    """[T | I] -> [R | Qt] per tile: Qt orthogonal, Qt @ T = R, R triangular;
    an all-zero tile is a bitwise fixed point with Qt = I."""
    rng = np.random.default_rng(31)
    b = 16
    T = rng.standard_normal((5, b, b))
    T[3] = 0.0  # zero tile
    stacked = jnp.asarray(np.concatenate(
        [T, np.broadcast_to(np.eye(b), (5, b, b))], axis=2))
    out = np.asarray(batched_geqrt(stacked, n_pivots=b, interpret=True))
    R, Qt = out[:, :, :b], out[:, :, b:]
    for i in range(5):
        np.testing.assert_allclose(Qt[i] @ Qt[i].T, np.eye(b), atol=1e-10)
        np.testing.assert_allclose(Qt[i] @ T[i], R[i], atol=1e-10)
        assert np.allclose(np.tril(R[i], -1), 0.0, atol=1e-12)
    assert (Qt[3] == np.eye(b)).all() and (R[3] == 0.0).all()


def test_revcumsum_native_matches_doubling():
    from repro.kernels.ggr_panel import _revcumsum

    x = jnp.asarray(_rand((9, 7, 5), seed=37))
    for axis in range(3):
        np.testing.assert_allclose(
            np.asarray(jax.lax.associative_scan(jnp.add, x, axis=axis,
                                                reverse=True)),
            np.asarray(_revcumsum(x, axis=axis)), atol=1e-12)
        ref = np.flip(np.cumsum(np.flip(np.asarray(x), axis), axis=axis), axis)
        np.testing.assert_allclose(
            np.asarray(_revcumsum(x, axis=axis)), ref, atol=1e-12)
