"""repro.ranks: pivoted QR, rank estimation, guards, monitor, sketch solve."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

jax.config.update("jax_enable_x64", True)

from repro.core.ggr import ggr_qr2
from repro.kernels.ref import ref_pivoted_panel_factor
from repro.ranks import (
    ConditionMonitor,
    DowndateGuard,
    cond_estimate,
    countsketch,
    estimate_rank,
    ggr_qr_pivoted,
    lsqr,
    lstsq_pivoted,
    sketch_lstsq,
    sketch_qr,
    srht,
)
from repro.testing import (
    gram_residual,
    rank_deficient_matrix,
    rank_deficient_suite,
    sign_align,
)

try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - CI installs hypothesis
    HAVE_HYPOTHESIS = False


# ------------------------------------------------------------- pivoted QR
def test_pivoted_factor_equals_unpivoted_of_permuted():
    """QRCP contract: the pivoted R IS the GGR R of A[:, perm]."""
    rng = np.random.default_rng(0)
    for m, n in ((6, 6), (12, 5), (4, 7)):
        A = jnp.asarray(rng.standard_normal((m, n)))
        st_ = ggr_qr_pivoted(A)
        R_ref = ggr_qr2(A[:, np.asarray(st_.perm)])
        mm = min(m, n)
        assert np.allclose(np.abs(np.asarray(st_.R)),
                           np.abs(np.asarray(jnp.triu(R_ref[:mm]))),
                           atol=1e-12)
        assert sorted(np.asarray(st_.perm)) == list(range(n))


def test_pivoted_matches_sequential_oracle():
    """Panel pivot order matches the sequential kernels/ref.py QRCP oracle."""
    rng = np.random.default_rng(1)
    A = jnp.asarray(rng.standard_normal((10, 6)))
    R_ref, perm_ref = ref_pivoted_panel_factor(A)
    st_ = ggr_qr_pivoted(A)
    assert np.array_equal(np.asarray(st_.perm), np.asarray(perm_ref))
    # two-stage (reduce-then-pivot) and direct sweeps may disagree on the
    # final row's sign freedom — compare after alignment
    assert np.allclose(sign_align(st_.R, R_ref[:6]),
                       np.triu(np.asarray(R_ref[:6], np.float64)), atol=1e-12)


def test_pivoted_diag_decays_on_graded_spectra():
    for case in rank_deficient_suite(shapes=((48, 24),), conds=(1e4, 1e12)):
        st_ = ggr_qr_pivoted(jnp.asarray(case.A))
        diag = np.abs(np.diag(np.asarray(st_.R)))
        assert np.all(diag[:-1] >= diag[1:] - 1e-12 * diag[0]), case.name


def test_pivoted_rhs_rides_along():
    rng = np.random.default_rng(2)
    A = jnp.asarray(rng.standard_normal((20, 6)))
    b = jnp.asarray(rng.standard_normal((20, 2)))
    st_ = ggr_qr_pivoted(A, b)
    # d must be Q^T b for the SAME Q that triangularized A[:, perm]
    R_ref, Q = ggr_qr2(A[:, np.asarray(st_.perm)], want_q=True)
    d_ref = (Q.T @ b)[:6]
    # a row-sign flip of R flips the matching row of Q^T b identically
    flip = np.sign(np.diag(np.asarray(st_.R))) * np.sign(
        np.diag(np.asarray(jnp.triu(R_ref[:6]))))
    flip = np.where(flip == 0.0, 1.0, flip)
    assert np.allclose(np.asarray(st_.d) * flip[:, None],
                       np.asarray(d_ref), atol=1e-10)


# ---------------------------------------------------------- rank estimation
def test_estimate_rank_exact_on_rank_deficient_suite():
    """Detected rank == constructed rank across cond 1e0..1e12 (f64)."""
    for case in rank_deficient_suite(shapes=((48, 24), (32, 8))):
        st_ = ggr_qr_pivoted(jnp.asarray(case.A))
        r = int(estimate_rank(st_.R))
        assert r == case.rank, f"{case.name}: got {r}"
        assert r == np.linalg.matrix_rank(case.A)


def test_estimate_rank_matches_scipy_pivoted_qr():
    scipy_linalg = pytest.importorskip("scipy.linalg")
    for case in rank_deficient_suite(shapes=((48, 24),)):
        st_ = ggr_qr_pivoted(jnp.asarray(case.A))
        _, R_sp, p_sp = scipy_linalg.qr(case.A, pivoting=True, mode="economic")
        # same pivot-relative diag cut on both factors -> same rank
        rcond = max(case.A.shape) * np.finfo(np.float64).eps
        d_sp = np.abs(np.diag(R_sp))
        rank_sp = int(np.sum(d_sp > rcond * d_sp.max()))
        assert int(estimate_rank(st_.R)) == rank_sp == case.rank, case.name


def test_estimate_rank_full_rank_graded():
    from repro.testing import matrix_suite

    for case in matrix_suite(shapes=((48, 24),), conds=(1e0, 1e4, 1e8)):
        st_ = ggr_qr_pivoted(jnp.asarray(case.A))
        assert int(estimate_rank(st_.R)) == 24, case.name


def test_estimate_rank_is_jit_safe():
    A = jnp.asarray(rank_deficient_matrix(16, 8, rank=3))

    @jax.jit
    def f(A):
        return estimate_rank(ggr_qr_pivoted(A).R)

    assert int(f(A)) == 3


# ------------------------------------------------------------ min-norm solve
def test_lstsq_pivoted_matches_numpy_min_norm():
    rng = np.random.default_rng(3)
    A = rank_deficient_matrix(40, 12, rank=5, cond=1e3, seed=4)
    b = rng.standard_normal((40, 2))
    fit = lstsq_pivoted(jnp.asarray(A), jnp.asarray(b))
    x_ref, _, rank_ref, _ = np.linalg.lstsq(A, b, rcond=None)
    assert int(fit.rank) == rank_ref == 5
    assert np.allclose(np.asarray(fit.x), x_ref, atol=1e-10)
    r_ref = np.linalg.norm(A @ x_ref - b, axis=0)
    assert np.allclose(np.asarray(fit.resid), r_ref, atol=1e-10)


def test_lstsq_pivoted_wide_matrix_min_norm():
    rng = np.random.default_rng(5)
    A = rng.standard_normal((6, 14))
    b = rng.standard_normal(6)
    fit = lstsq_pivoted(jnp.asarray(A), jnp.asarray(b))
    x_ref, _, _, _ = np.linalg.lstsq(A, b, rcond=None)
    assert np.allclose(np.asarray(fit.x), x_ref, atol=1e-10)
    # min-norm: no smaller-norm solution exists
    assert np.linalg.norm(fit.x) <= np.linalg.norm(x_ref) * (1 + 1e-12)


def test_ggr_lstsq_raises_on_rank_deficiency_and_rcond_recovers():
    """Satellite regression: rank-3 cond-1e12 input must fail loudly by
    default and solve min-norm when rcond is passed."""
    from repro.solvers import ggr_lstsq

    rng = np.random.default_rng(6)
    m, n = 32, 8
    U, _ = np.linalg.qr(rng.standard_normal((m, n)))
    V, _ = np.linalg.qr(rng.standard_normal((n, n)))
    s = np.zeros(n)
    s[:3] = [1.0, 1e-6, 1e-12]  # rank 3, cond 1e12 across the nonzero part
    A = (U * s) @ V.T
    b = rng.standard_normal(m)
    with pytest.raises(ValueError, match="rank-deficient"):
        ggr_lstsq(jnp.asarray(A), jnp.asarray(b))
    # rcond below the smallest kept direction: both conventions keep rank 3
    out = ggr_lstsq(jnp.asarray(A), jnp.asarray(b), rcond=1e-13)
    x_ref, _, rank_ref, _ = np.linalg.lstsq(A, b, rcond=1e-13)
    assert rank_ref == 3
    scale = np.linalg.norm(x_ref)
    assert np.linalg.norm(np.asarray(out.x) - x_ref) <= 1e-2 * scale
    # residual agreement is eps-amplified by the 1e12 spread of kept
    # directions — 1e-4 relative is the honest bound here
    assert np.isclose(float(out.resid),
                      np.linalg.norm(A @ x_ref - b), rtol=1e-4)
    # mid-gap rcond truncates to rank 2 (diag and sval conventions agree
    # because the gap is 6 orders wide)
    fit2 = lstsq_pivoted(jnp.asarray(A), jnp.asarray(b), rcond=1e-9)
    assert int(fit2.rank) == 2


def test_ggr_lstsq_well_conditioned_path_unchanged():
    from repro.solvers import ggr_lstsq

    rng = np.random.default_rng(7)
    A = rng.standard_normal((24, 6))
    b = rng.standard_normal(24)
    out = ggr_lstsq(jnp.asarray(A), jnp.asarray(b))
    x_ref, *_ = np.linalg.lstsq(A, b, rcond=None)
    assert np.allclose(np.asarray(out.x), x_ref, atol=1e-10)


# ------------------------------------------------------- condition monitor
def test_cond_estimate_tracks_true_condition():
    from repro.testing import graded_matrix

    for cond in (1e2, 1e6):
        A = graded_matrix(48, 16, cond, seed=8)
        R = np.linalg.qr(A, mode="r")
        est = cond_estimate(jnp.asarray(R), iters=8)
        truth = np.linalg.cond(R)
        assert 0.5 * truth <= float(est.cond) <= 1.05 * truth


def test_cond_estimate_incremental_carry():
    from repro.testing import graded_matrix

    A = graded_matrix(48, 16, 1e4, seed=9)
    R = jnp.asarray(np.linalg.qr(A, mode="r"))
    full = cond_estimate(R, iters=8)
    warm = cond_estimate(R, state=full, iters=1)  # one refresh round
    assert float(warm.cond) == pytest.approx(float(full.cond), rel=1e-2)


def test_cond_estimate_survives_singular_factor():
    R = jnp.asarray(np.diag([1.0, 1e-3, 0.0]))
    est = cond_estimate(R, iters=4)
    assert np.isfinite(float(est.cond)) and float(est.cond) > 1e6


def test_condition_monitor_records_and_alarms():
    from repro import obs

    mon = ConditionMonitor(layer="rls", alarm_cond=1e3, iters=8)
    with obs.collecting() as reg:
        c1 = mon.observe(jnp.asarray(np.diag([1.0, 0.5, 0.25])))
        c2 = mon.observe(jnp.asarray(np.diag([1.0, 0.5, 1e-5])))
    assert c1 < 1e3 < c2
    assert mon.alarms == 1
    assert reg.find("rls.cond_estimate").value == pytest.approx(c2)
    assert reg.find("rls.cond_alarms").value == 1
    # tracers are ignored, not crashed on
    jax.jit(lambda r: (mon.observe(r), r)[1])(jnp.eye(3))


# --------------------------------------------------------- downdate guard
def _rls_near_cliff():
    """RLS window plus a row whose removal would cross the rank cliff:
    scaled so its leverage ||R^-T u||^2 lands at exactly 1.5 > 1."""
    from repro.solvers import RecursiveLS

    rls = RecursiveLS(n=3, delta=1e-10)
    state = rls.init(jnp.float64)
    rng = np.random.default_rng(10)
    rows = rng.standard_normal((4, 3))
    for r in rows:
        state = rls.observe(state, jnp.asarray(r), jnp.asarray(r.sum()))
    lev = float(rls.residual_gram(state, jnp.asarray(rows[0])))
    bad = np.sqrt(1.5 / lev) * rows[0]
    return rls, state, rows, bad


def test_downdate_guard_refuse_keeps_state():
    rls, state, rows, bad = _rls_near_cliff()
    guard = DowndateGuard(tau=1e-6, mode="refuse")
    out = rls.forget(state, jnp.asarray(bad), jnp.asarray(bad.sum()),
                     guard=guard)
    assert np.allclose(np.asarray(out.R), np.asarray(state.R))


def test_downdate_guard_damp_bounds_collapse():
    rls, state, rows, bad = _rls_near_cliff()
    guard = DowndateGuard(tau=1e-6, mode="damp")
    out = rls.forget(state, jnp.asarray(bad), jnp.asarray(bad.sum()),
                     guard=guard)
    smin = np.linalg.svd(np.asarray(out.R), compute_uv=False).min()
    assert np.isfinite(np.asarray(out.R)).all() and smin > 1e-12


def test_downdate_guard_raise_mode():
    rls, state, rows, bad = _rls_near_cliff()
    guard = DowndateGuard(tau=1e-6, mode="raise")
    with pytest.raises(FloatingPointError):
        rls.forget(state, jnp.asarray(bad), jnp.asarray(bad.sum()),
                   guard=guard)


def test_downdate_guard_inert_on_safe_downdates():
    rls, state, rows, _ = _rls_near_cliff()
    guard = DowndateGuard(tau=1e-6, mode="damp")
    a = rls.forget(state, jnp.asarray(rows[0]), jnp.asarray(rows[0].sum()),
                   guard=guard)
    b = rls.forget(state, jnp.asarray(rows[0]), jnp.asarray(rows[0].sum()))
    assert np.allclose(np.asarray(a.R), np.asarray(b.R), atol=1e-12)


def test_downdate_guard_validates_config():
    with pytest.raises(ValueError):
        DowndateGuard(tau=2.0).validate()
    with pytest.raises(ValueError):
        DowndateGuard(mode="explode").validate()


# ------------------------------------------------------------------ sketch
def test_countsketch_and_srht_are_subspace_embeddings():
    rng = np.random.default_rng(11)
    A = jnp.asarray(rng.standard_normal((512, 16)))
    for op in (countsketch, srht):
        SA = op(A, 128, seed=3)
        assert SA.shape == (128, 16)
        # singular values of the sketch stay within a modest distortion
        s_full = np.linalg.svd(np.asarray(A), compute_uv=False)
        s_sk = np.linalg.svd(np.asarray(SA), compute_uv=False)
        assert s_sk[0] <= 2.0 * s_full[0]
        assert s_sk[-1] >= 0.3 * s_full[-1]


def test_sketch_qr_preconditioner_flattens_condition():
    from repro.testing import graded_matrix

    A = jnp.asarray(graded_matrix(1024, 32, 1e8, seed=12))
    R = sketch_qr(A)
    AR = np.asarray(A) @ np.linalg.inv(np.triu(np.asarray(R)))
    assert np.linalg.cond(AR) < 10.0


def test_sketch_lstsq_converges_where_plain_lsqr_cannot():
    """The Blendenpik trade on a cond-1e8 tall-skinny problem (f64)."""
    from repro.testing import graded_matrix

    m, n = 2048, 48
    A = graded_matrix(m, n, 1e8, seed=13)
    rng = np.random.default_rng(14)
    x0 = rng.standard_normal(n)
    # residual orthogonal to range(A) by construction -> exact oracle:
    # the true solution is x0 and the optimal residual norm is ||r0||
    Q, _ = np.linalg.qr(A)
    r0 = rng.standard_normal(m)
    r0 -= Q @ (Q.T @ r0)
    r0 *= 0.1 / np.linalg.norm(r0)
    b = A @ x0 + r0
    Aj, bj = jnp.asarray(A), jnp.asarray(b)

    fit = sketch_lstsq(Aj, bj, iters=50, tol=1e-12, seed=15)
    assert int(fit.iters) <= 50
    # THE acceptance metric: oracle residual reached within 1e-6 relative
    # inside the 50-iteration budget (it lands at ~machine precision)
    assert float(fit.resid) == pytest.approx(np.linalg.norm(r0), rel=1e-6)
    # x agrees up to the intrinsic tol*cond amplification of the problem
    assert np.linalg.norm(np.asarray(fit.x) - x0) <= 1e-2 * np.linalg.norm(x0)

    # unpreconditioned LSQR at the same budget misses both marks
    x_plain, _, rn_plain, _ = lsqr(Aj, bj, iters=50, tol=1e-12)
    assert abs(float(rn_plain) - np.linalg.norm(r0)) > 1e-6 * np.linalg.norm(r0)
    assert np.linalg.norm(np.asarray(x_plain) - x0) > 0.1 * np.linalg.norm(x0)


def test_sketch_lstsq_well_conditioned_reaches_oracle_residual():
    """The same oracle at cond 1e2 and 2048 x 64: the sketched solve is not
    only for hard problems, it reaches the optimal residual within 1e-6
    relative in at most 50 iterations here too (f64)."""
    from repro.testing import graded_matrix

    m, n = 2048, 64
    A = graded_matrix(m, n, 1e2, seed=11)
    rng = np.random.default_rng(211)
    x0 = rng.standard_normal(n)
    Q, _ = np.linalg.qr(A)
    r0 = rng.standard_normal(m)
    r0 -= Q @ (Q.T @ r0)
    r0 *= 0.1 / np.linalg.norm(r0)

    fit = sketch_lstsq(jnp.asarray(A), jnp.asarray(A @ x0 + r0),
                       iters=50, tol=1e-12)
    assert int(fit.iters) <= 50
    assert float(fit.resid) == pytest.approx(np.linalg.norm(r0), rel=1e-6)


def test_sketch_lstsq_srht_and_sharded_paths_agree():
    from repro.testing import graded_matrix

    A = jnp.asarray(graded_matrix(1024, 24, 1e6, seed=16))
    rng = np.random.default_rng(17)
    b = jnp.asarray(np.asarray(A) @ rng.standard_normal(24))
    base = sketch_lstsq(A, b, iters=50, tol=1e-12)
    for kw in (dict(kind="srht"), dict(shards=4)):
        fit = sketch_lstsq(A, b, iters=50, tol=1e-12, **kw)
        assert np.allclose(np.asarray(fit.x), np.asarray(base.x), atol=1e-8)


def test_sketch_lstsq_rejects_wide_and_matrix_rhs_loops():
    rng = np.random.default_rng(18)
    with pytest.raises(ValueError):
        sketch_lstsq(jnp.asarray(rng.standard_normal((4, 8))),
                     jnp.asarray(rng.standard_normal(4)))
    A = jnp.asarray(rng.standard_normal((64, 8)))
    B = jnp.asarray(rng.standard_normal((64, 3)))
    fit = sketch_lstsq(A, B, iters=50, tol=1e-12)
    x_ref, *_ = np.linalg.lstsq(np.asarray(A), np.asarray(B), rcond=None)
    assert fit.x.shape == (8, 3)
    assert np.allclose(np.asarray(fit.x), x_ref, atol=1e-8)


# ----------------------------------------------------------------- serving
def test_serve_lstsq_pivoted_round_trip():
    from repro.launch.serve_qr import QRServer

    rng = np.random.default_rng(19)
    server = QRServer(backend="reference", max_batch=8)
    probs, ticks = [], []
    for i in range(5):
        A = rank_deficient_matrix(24, 6, rank=3, cond=10.0,
                                  seed=20 + i).astype(np.float32)
        b = rng.standard_normal((24, 1)).astype(np.float32)
        probs.append((A, b))
        ticks.append(server.submit_lstsq_pivoted(A, b))
    assert server.flush() == 5
    server.drain()
    for (A, b), t in zip(probs, ticks):
        x, resid, rank = server.result(t)
        assert int(rank) == 3
        x_ref, *_ = np.linalg.lstsq(np.asarray(A, np.float64),
                                    np.asarray(b, np.float64), rcond=1e-5)
        assert np.allclose(np.asarray(x), x_ref, atol=1e-4)


def test_make_workload_emits_rank_deficient_pivoted_requests():
    from repro.launch.serve_qr import make_workload

    reqs = make_workload(16, n=6, rows=3, k=1, seed=21)
    piv = [r for r in reqs if r[0] == "lstsq_pivoted"]
    assert len(piv) == 2
    for _, A, b in piv:
        assert np.linalg.matrix_rank(np.asarray(A, np.float64), tol=1e-4) == 3


# ------------------------------------------------------------- properties
if HAVE_HYPOTHESIS:
    _settings = dict(
        max_examples=20,
        deadline=None,
        suppress_health_check=[HealthCheck.too_slow,
                               HealthCheck.data_too_large],
    )

    @st.composite
    def _problems(draw):
        m = draw(st.integers(2, 16))
        n = draw(st.integers(1, 12))
        seed = draw(st.integers(0, 2**31 - 1))
        return m, n, seed

    @given(_problems())
    @settings(**_settings)
    def test_perm_round_trip_property(prob):
        """A[:, perm] == Q R for the pivoted factor, via LAPACK's |R|."""
        m, n, seed = prob
        A = np.random.default_rng(seed).standard_normal((m, n))
        st_ = ggr_qr_pivoted(jnp.asarray(A))
        perm = np.asarray(st_.perm)
        assert sorted(perm) == list(range(n))
        R_ref = np.linalg.qr(A[:, perm], mode="r")
        assert np.allclose(np.abs(np.asarray(st_.R)), np.abs(R_ref),
                           atol=1e-9 * max(1.0, np.abs(A).max()))
        assert gram_residual(A[:, perm], st_.R) < 1e-12

    @given(_problems(), st.integers(1, 4))
    @settings(**_settings)
    def test_rank_monotone_under_appended_rows(prob, p):
        """Appending rows can only grow (never shrink) the detected rank."""
        m, n, seed = prob
        rng = np.random.default_rng(seed)
        r = rng.integers(1, min(m, n) + 1)
        A = rng.standard_normal((m, r)) @ rng.standard_normal((r, n))
        E = rng.standard_normal((p, n))
        r0 = int(estimate_rank(ggr_qr_pivoted(jnp.asarray(A)).R))
        r1 = int(estimate_rank(
            ggr_qr_pivoted(jnp.asarray(np.vstack([A, E]))).R))
        assert r1 >= r0
