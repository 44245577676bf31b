"""repro.solvers.kalman: SRIF vs dense f64 covariance-form Kalman oracles.

Coverage layers:
* algebraic: ``info_sqrt`` / ``kf_init`` round-trips;
* per-step: ``kf_predict`` / ``kf_observe`` vs the textbook covariance-form
  time/measurement updates on random LTI systems (f64);
* sequence: innovation consistency (mean NIS ~ measurement dim) and the RTS
  smoother vs a dense oracle;
* batched: ``kf_step_batched`` reference backend is *bitwise* the sequential
  per-filter ``kf_step`` (the acceptance contract), pallas agrees to roundoff;
* serving: ``QRServer`` kalman round trip plus a subprocess 4-way host-mesh
  sharded flush matching the single-device flush bitwise.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.solvers import (
    KalmanState,
    info_sqrt,
    kf_cov,
    kf_filter,
    kf_init,
    kf_mean,
    kf_observe,
    kf_predict,
    kf_smooth,
    kf_step,
    kf_step_batched,
    whiten_measurement,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spd(k, seed, scale=1.0):
    A = np.random.default_rng(seed).standard_normal((k, k + 3))
    return scale * (A @ A.T / (k + 3)) + 0.1 * np.eye(k)


def _lti(n, w, p, seed):
    """Random stable LTI system (F, G, Q, H, Rn) in f64."""
    rng = np.random.default_rng(seed)
    F = rng.standard_normal((n, n))
    F = 0.9 * F / max(abs(np.linalg.eigvals(F)))
    G = rng.standard_normal((n, w))
    Q = _spd(w, seed + 1)
    H = rng.standard_normal((p, n))
    Rn = _spd(p, seed + 2)
    return F, G, Q, H, Rn


def _prior(n, seed):
    rng = np.random.default_rng(seed)
    return rng.standard_normal(n), _spd(n, seed + 1, scale=2.0)


# ----------------------------------------------------------------- algebraic

def test_info_sqrt_properties():
    M = _spd(6, 0)
    U = info_sqrt(jnp.asarray(M))
    np.testing.assert_allclose(np.asarray(U.T @ U), np.linalg.inv(M),
                               rtol=1e-9, atol=1e-11)
    np.testing.assert_allclose(np.asarray(jnp.tril(U, -1)), 0.0, atol=1e-14)
    assert bool(jnp.all(jnp.diagonal(U) >= 0))  # GGR sign convention


def test_kf_init_round_trip():
    x0, P0 = _prior(5, 3)
    st = kf_init(jnp.asarray(x0), jnp.asarray(P0))
    np.testing.assert_allclose(np.asarray(kf_mean(st)), x0, rtol=1e-10, atol=1e-12)
    np.testing.assert_allclose(np.asarray(kf_cov(st)), P0, rtol=1e-9, atol=1e-11)
    assert int(st.step) == 0


# ------------------------------------------------------------------ per-step

@pytest.mark.parametrize("n,w,p,with_G", [(4, 4, 2, False), (5, 3, 2, True),
                                          (7, 7, 4, True)])
def test_kf_predict_matches_covariance_oracle(n, w, p, with_G):
    F, G, Q, H, Rn = _lti(n, w, p, 10)
    if not with_G:
        G, Q = None, _spd(n, 11)
    x0, P0 = _prior(n, 12)
    st = kf_init(jnp.asarray(x0), jnp.asarray(P0))
    Qi = info_sqrt(jnp.asarray(Q))
    pred = kf_predict(st, jnp.asarray(F), Qi,
                      None if G is None else jnp.asarray(G))
    Po = F @ P0 @ F.T + (Q if G is None else G @ Q @ G.T)
    np.testing.assert_allclose(np.asarray(kf_mean(pred)), F @ x0,
                               rtol=1e-8, atol=1e-10)
    np.testing.assert_allclose(np.asarray(kf_cov(pred)), Po,
                               rtol=1e-8, atol=1e-10)
    assert int(pred.step) == 1


def test_kf_observe_matches_covariance_oracle():
    n, p = 5, 3
    F, _, _, H, Rn = _lti(n, n, p, 20)
    x0, P0 = _prior(n, 21)
    z = np.random.default_rng(22).standard_normal(p)
    st = kf_init(jnp.asarray(x0), jnp.asarray(P0))
    Hw, zw = whiten_measurement(jnp.asarray(Rn), jnp.asarray(H), jnp.asarray(z))
    post = kf_observe(st, Hw, zw)
    S = H @ P0 @ H.T + Rn
    K = P0 @ H.T @ np.linalg.inv(S)
    xo = x0 + K @ (z - H @ x0)
    Po = (np.eye(n) - K @ H) @ P0
    np.testing.assert_allclose(np.asarray(kf_mean(post)), xo, rtol=1e-8, atol=1e-10)
    np.testing.assert_allclose(np.asarray(kf_cov(post)), Po, rtol=1e-8, atol=1e-10)
    assert int(post.step) == 0  # observe does not advance time


def test_kf_step_fused_matches_modular():
    n, w, p = 5, 3, 2
    F, G, Q, H, Rn = _lti(n, w, p, 30)
    x0, P0 = _prior(n, 31)
    z = np.random.default_rng(32).standard_normal(p)
    st = kf_init(jnp.asarray(x0), jnp.asarray(P0))
    Qi = info_sqrt(jnp.asarray(Q))
    Hw, zw = whiten_measurement(jnp.asarray(Rn), jnp.asarray(H), jnp.asarray(z))
    fused = kf_step(st, jnp.asarray(F), Qi, Hw, zw, jnp.asarray(G))
    modular = kf_observe(kf_predict(st, jnp.asarray(F), Qi, jnp.asarray(G)),
                         Hw, zw)
    np.testing.assert_allclose(np.asarray(fused.R), np.asarray(modular.R),
                               rtol=1e-9, atol=1e-11)
    np.testing.assert_allclose(np.asarray(fused.d), np.asarray(modular.d),
                               rtol=1e-9, atol=1e-11)
    assert int(fused.step) == 1


# ------------------------------------------------------------------ sequence

def _simulate(F, G, Q, H, Rn, x0, T, seed):
    rng = np.random.default_rng(seed)
    Lq, Lr = np.linalg.cholesky(Q), np.linalg.cholesky(Rn)
    x = x0.copy()
    xs, zs = np.zeros((T, x0.size)), np.zeros((T, H.shape[0]))
    for t in range(T):
        x = F @ x + G @ (Lq @ rng.standard_normal(Q.shape[0]))
        xs[t] = x
        zs[t] = H @ x + Lr @ rng.standard_normal(H.shape[0])
    return xs, zs


def test_kf_filter_innovation_consistency():
    """Normalized innovation squared (NIS) averages to the measurement dim
    over a long run of a correctly-specified filter — the standard
    consistency check for tracking filters."""
    n, w, p, T = 4, 4, 2, 300
    F, G, Q, H, Rn = _lti(n, w, p, 40)
    x0, P0 = _prior(n, 41)
    xs, zs = _simulate(F, G, Q, H, Rn, x0, T, 42)
    st = kf_init(jnp.asarray(x0), jnp.asarray(P0))
    Qi = info_sqrt(jnp.asarray(Q))
    W = info_sqrt(jnp.asarray(Rn))
    Hw = W @ jnp.asarray(H)
    zw = (W @ jnp.asarray(zs).T).T
    _, traj = kf_filter(st, jnp.asarray(F), Qi, Hw, zw, jnp.asarray(G))

    eye = np.eye(n)
    nis = []
    for t in range(T):
        Rp = np.asarray(traj.Rp[t])
        xp = np.linalg.solve(Rp, np.asarray(traj.dp[t]))
        Kp = np.linalg.solve(Rp, eye)
        Pp = Kp @ Kp.T
        e = zs[t] - H @ xp
        S = H @ Pp @ H.T + Rn
        nis.append(e @ np.linalg.solve(S, e))
    mean_nis = np.mean(nis)
    assert 0.7 * p < mean_nis < 1.3 * p, mean_nis


def test_kf_smooth_matches_dense_rts_oracle():
    n, w, p, T = 4, 2, 2, 30
    F, G, Q, H, Rn = _lti(n, w, p, 50)
    x0, P0 = _prior(n, 51)
    xs_true, zs = _simulate(F, G, Q, H, Rn, x0, T, 52)
    st = kf_init(jnp.asarray(x0), jnp.asarray(P0))
    Qi = info_sqrt(jnp.asarray(Q))
    W = info_sqrt(jnp.asarray(Rn))
    _, traj = kf_filter(st, jnp.asarray(F), Qi, W @ jnp.asarray(H),
                        (W @ jnp.asarray(zs).T).T, jnp.asarray(G))
    xs_sm, Ps_sm = kf_smooth(traj, jnp.asarray(F))

    # dense covariance-form filter + RTS backward pass
    GQG = G @ Q @ G.T
    xf = np.zeros((T, n)); Pf = np.zeros((T, n, n))
    xp = np.zeros((T, n)); Pp = np.zeros((T, n, n))
    xc, Pc = x0.copy(), P0.copy()
    for t in range(T):
        xpr, Ppr = F @ xc, F @ Pc @ F.T + GQG
        S = H @ Ppr @ H.T + Rn
        K = Ppr @ H.T @ np.linalg.inv(S)
        xc = xpr + K @ (zs[t] - H @ xpr)
        Pc = (np.eye(n) - K @ H) @ Ppr
        xf[t], Pf[t], xp[t], Pp[t] = xc, Pc, xpr, Ppr
    xo, Po = xf.copy(), Pf.copy()
    for t in range(T - 2, -1, -1):
        C = Pf[t] @ F.T @ np.linalg.inv(Pp[t + 1])
        xo[t] = xf[t] + C @ (xo[t + 1] - xp[t + 1])
        Po[t] = Pf[t] + C @ (Po[t + 1] - Pp[t + 1]) @ C.T

    np.testing.assert_allclose(np.asarray(xs_sm), xo, rtol=1e-7, atol=1e-9)
    np.testing.assert_allclose(np.asarray(Ps_sm), Po, rtol=1e-7, atol=1e-9)
    # smoothing must not be worse than filtering on the true trajectory
    assert np.sqrt(((np.asarray(xs_sm) - xs_true) ** 2).mean()) <= \
        np.sqrt(((xf - xs_true) ** 2).mean()) + 1e-12


# ------------------------------------------------------------------- batched

def _batch_problem(B, n, w, p, seed, dtype=jnp.float64):
    rng = np.random.default_rng(seed)
    F, G, Q, H, Rn = _lti(n, w, p, seed)
    Qi = info_sqrt(jnp.asarray(Q, dtype))
    W = info_sqrt(jnp.asarray(Rn, dtype))
    Hw = W @ jnp.asarray(H, dtype)
    Rb, db = [], []
    for i in range(B):
        x0, P0 = _prior(n, seed + 7 * i)
        st = kf_init(jnp.asarray(x0, dtype), jnp.asarray(P0, dtype))
        Rb.append(st.R); db.append(st.d)
    zb = (W @ jnp.asarray(rng.standard_normal((B, p)), dtype).T).T
    return (jnp.stack(Rb), jnp.stack(db), jnp.asarray(F, dtype), Qi, Hw, zb,
            jnp.asarray(G, dtype))


@pytest.mark.parametrize("B", [1, 5, 12])
def test_kf_step_batched_reference_bitwise_vs_sequential(B):
    """The acceptance contract: the batched path IS the per-filter step,
    bit for bit (reference backend vmaps the identical stacked sweep)."""
    Rb, db, F, Qi, Hw, zb, G = _batch_problem(B, 4, 2, 2, 60)
    Rn, dn = kf_step_batched(Rb, db, F, Qi, Hw, zb, G, backend="reference")
    for i in range(B):
        st = kf_step(KalmanState(Rb[i], db[i], jnp.zeros((), jnp.int32)),
                     F, Qi, Hw, zb[i], G)
        np.testing.assert_array_equal(np.asarray(Rn[i]), np.asarray(st.R))
        np.testing.assert_array_equal(np.asarray(dn[i]), np.asarray(st.d))


def test_kf_step_batched_per_filter_models_bitwise():
    """Per-filter (B, n, n) dynamics also stay bitwise vs the loop."""
    B = 6
    Rb, db, F, Qi, Hw, zb, G = _batch_problem(B, 4, 2, 2, 61)
    Fb = jnp.stack([F * (1.0 + 0.01 * i) for i in range(B)])
    Rn, dn = kf_step_batched(Rb, db, Fb, Qi, Hw, zb, G, backend="reference")
    for i in range(B):
        st = kf_step(KalmanState(Rb[i], db[i], jnp.zeros((), jnp.int32)),
                     Fb[i], Qi, Hw, zb[i], G)
        np.testing.assert_array_equal(np.asarray(Rn[i]), np.asarray(st.R))
        np.testing.assert_array_equal(np.asarray(dn[i]), np.asarray(st.d))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 5e-5), (jnp.float64, 1e-11)])
def test_kf_step_batched_pallas_matches_reference(dtype, tol):
    B = 7  # prime: exercises pad_batch inside the kernel dispatch
    Rb, db, F, Qi, Hw, zb, G = _batch_problem(B, 4, 2, 2, 62, dtype)
    Rp, dp = kf_step_batched(Rb, db, F, Qi, Hw, zb, G, backend="pallas",
                             interpret=True)
    Rr, dr = kf_step_batched(Rb, db, F, Qi, Hw, zb, G, backend="reference")
    assert Rp.dtype == dtype
    np.testing.assert_allclose(np.asarray(Rp), np.asarray(Rr), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(dp), np.asarray(dr), rtol=tol, atol=tol)


def test_kf_step_batched_no_G():
    B, n = 4, 3
    Rb, db, F, Qi, Hw, zb, _ = _batch_problem(B, n, n, 2, 63)
    Rn, dn = kf_step_batched(Rb, db, F, Qi, Hw, zb, backend="reference")
    st = kf_step(KalmanState(Rb[0], db[0], jnp.zeros((), jnp.int32)),
                 F, Qi, Hw, zb[0])
    np.testing.assert_array_equal(np.asarray(Rn[0]), np.asarray(st.R))
    np.testing.assert_array_equal(np.asarray(dn[0]), np.asarray(st.d))


# ------------------------------------------------------------------- serving

def test_qr_server_kalman_round_trip():
    from repro.launch.serve_qr import QRServer

    B = 9
    Rb, db, F, Qi, Hw, zb, G = _batch_problem(B, 4, 2, 2, 70, jnp.float32)
    server = QRServer(backend="pallas", max_batch=4, interpret=True)
    tickets = [server.submit_kalman(Rb[i], db[i], F, Qi, Hw, zb[i], G)
               for i in range(B)]
    assert server.pending() == B
    assert server.flush(kind="kalman") == B
    for i, tk in enumerate(tickets):
        Rn, dn = server.result(tk)
        st = kf_step(KalmanState(Rb[i], db[i], jnp.zeros((), jnp.int32)),
                     F, Qi, Hw, zb[i], G)
        np.testing.assert_allclose(np.asarray(Rn), np.asarray(st.R),
                                   rtol=5e-5, atol=5e-5)
        np.testing.assert_allclose(np.asarray(dn), np.asarray(st.d),
                                   rtol=5e-5, atol=5e-5)


def test_qr_server_kalman_groups_by_dtype_and_shape():
    from repro.launch.serve_qr import QRServer

    Rb, db, F, Qi, Hw, zb, G = _batch_problem(2, 4, 2, 2, 71, jnp.float32)
    R64 = Rb[0].astype(jnp.float64)
    server = QRServer(backend="reference")
    t32 = server.submit_kalman(Rb[0], db[0], F, Qi, Hw, zb[0], G)
    t64 = server.submit_kalman(R64, db[0].astype(jnp.float64),
                               F.astype(jnp.float64), Qi.astype(jnp.float64),
                               Hw.astype(jnp.float64), zb[0].astype(jnp.float64),
                               G.astype(jnp.float64))
    assert t32.group != t64.group
    server.flush()
    assert server.result(t32)[0].dtype == jnp.float32
    assert server.result(t64)[0].dtype == jnp.float64


def test_qr_server_sharded_kalman_flush_subprocess():
    """4-way host-mesh sharded kalman flush == single-device flush, bitwise
    (groups pad to shards x block_b, every shard runs an identical grid)."""
    code = """
    import numpy as np, jax, jax.numpy as jnp
    from repro.launch.serve_qr import QRServer
    from repro.parallel.sharding import make_batch_mesh
    from tests.test_kalman import _batch_problem
    assert jax.device_count() == 4, jax.device_count()
    jax.config.update("jax_enable_x64", True)
    B = 11  # prime: pads to 4 shards x 8 block_b on the mesh path
    Rb, db, F, Qi, Hw, zb, G = _batch_problem(B, 4, 2, 2, 72, jnp.float32)
    sharded = QRServer(backend="pallas", interpret=True, mesh=make_batch_mesh(4))
    single = QRServer(backend="pallas", interpret=True)
    ts = [sharded.submit_kalman(Rb[i], db[i], F, Qi, Hw, zb[i], G) for i in range(B)]
    t1 = [single.submit_kalman(Rb[i], db[i], F, Qi, Hw, zb[i], G) for i in range(B)]
    assert sharded.flush() == B and single.flush() == B
    for a, b in zip(ts, t1):
        for xa, xb in zip(sharded.result(a), single.result(b)):
            np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))
    print("KALMAN_SHARDED_OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(_REPO, "src") + os.pathsep + _REPO
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "KALMAN_SHARDED_OK" in out.stdout


@pytest.mark.parametrize("B", [13, 64])
@pytest.mark.parametrize("model", ["shared", "per_filter"])
@pytest.mark.parametrize("with_G", [True, False])
def test_step_matrices_bitwise_vs_eager_vmap(with_G, model, B):
    """The compiled front's stacked step matrices are bitwise the eager
    ``jax.vmap(_step_stacked)`` over the broadcast operands, with shared
    (2-D) or per-filter (3-D) models, with and without ``G``."""
    from repro.solvers.kalman import _step_matrices, _step_stacked

    n, p = 6, 3
    w = 3 if with_G else n  # no G: the noise enters as identity, w = n
    rng = np.random.default_rng(64 + B)
    f32 = lambda x: jnp.asarray(np.asarray(x, np.float32))  # noqa: E731
    R = np.triu(rng.standard_normal((B, n, n)))
    R[:, range(n), range(n)] = np.abs(R[:, range(n), range(n)]) + 1.0
    R, d, z = f32(R), f32(rng.standard_normal((B, n))), f32(
        rng.standard_normal((B, p)))
    lead = (B,) if model == "per_filter" else ()
    F = f32(np.eye(n) + 0.1 * rng.standard_normal(lead + (n, n)))
    Qi = f32(np.triu(rng.standard_normal(lead + (w, w))) + 2.0 * np.eye(w))
    H = f32(rng.standard_normal(lead + (p, n)))
    G = f32(rng.standard_normal(lead + (n, w))) if with_G else None
    bcast = lambda M: M if M is None or M.ndim == 3 else jnp.broadcast_to(  # noqa: E731
        M, (B,) + M.shape)
    expect = jax.vmap(_step_stacked)(R, d, bcast(F), bcast(Qi), bcast(H), z,
                                     bcast(G))
    got = _step_matrices(R, d, F, Qi, H, z, G)
    assert got.dtype == expect.dtype == jnp.float32
    assert got.shape == expect.shape == (B, w + 2 * n + p, w + n + 1)
    assert np.asarray(got).tobytes() == np.asarray(expect).tobytes()
