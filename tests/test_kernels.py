"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracles."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import ops, ref

SHAPES = [(8, 4), (32, 8), (64, 16), (128, 32), (96, 8)]
DTYPES = [jnp.float32, jnp.float64]
TOL = {jnp.float32: 5e-5, jnp.float64: 1e-11}


def _rand(shape, seed, dtype):
    x = np.random.default_rng(seed).standard_normal(shape)
    return jnp.asarray(x, dtype)


@pytest.mark.parametrize("m,b", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_panel_kernel_matches_ref(m, b, dtype):
    pan = _rand((m, b), m + b, dtype)
    R, V, T = ops.panel_qr(pan, interpret=True)
    Rr, Vr, Tr = ref.ref_panel_factor(pan)
    tol = TOL[dtype] * max(1, m // 16)
    np.testing.assert_allclose(np.asarray(R), np.asarray(Rr), atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(V), np.asarray(Vr), atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(T), np.asarray(Tr), atol=tol, rtol=tol)


@pytest.mark.parametrize("m,b", [(16, 4), (64, 8), (128, 16)])
@pytest.mark.parametrize("w", [8, 32, 64])
@pytest.mark.parametrize("dtype", DTYPES)
def test_apply_kernel_matches_ref(m, b, w, dtype):
    pan = _rand((m, b), 5, dtype)
    C = _rand((m, w), 6, dtype)
    _, V, T = ref.ref_panel_factor(pan)
    out = ops.apply_panel(V, T, C, block_w=min(32, w), interpret=True)
    outr = ref.ref_apply_factors(V, T, C)
    tol = TOL[dtype] * max(1, m // 16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(outr), atol=tol, rtol=tol)


@pytest.mark.parametrize("pivot0", [0, 4, 13])
def test_panel_kernel_pivot_offsets(pivot0):
    pan = _rand((48, 8), 7, jnp.float32)
    R, V, T = ops.panel_qr(pan, pivot0=pivot0, interpret=True)
    Rr, Vr, Tr = ref.ref_panel_factor(pan, pivot0)
    np.testing.assert_allclose(np.asarray(R), np.asarray(Rr), atol=1e-4)
    np.testing.assert_allclose(np.asarray(V), np.asarray(Vr), atol=1e-4)


def test_tsqrt_matches_numpy():
    rng = np.random.default_rng(8)
    R_top = np.triu(rng.standard_normal((8, 8))).astype(np.float32)
    B = rng.standard_normal((24, 8)).astype(np.float32)
    R_new, V, T = ops.tsqrt(jnp.array(R_top), jnp.array(B), interpret=True)
    Rnp = np.linalg.qr(np.concatenate([R_top, B]), mode="r")
    np.testing.assert_allclose(np.abs(np.asarray(R_new)), np.abs(Rnp), atol=1e-4)


@pytest.mark.parametrize("m,n,panel", [(32, 32, 8), (64, 32, 16), (128, 64, 32)])
def test_full_pallas_qr(m, n, panel):
    A = np.random.default_rng(m + n).standard_normal((m, n)).astype(np.float32)
    R = np.asarray(ops.ggr_qr_pallas(jnp.array(A), panel=panel, interpret=True))
    Rnp = np.linalg.qr(A.astype(np.float64), mode="r")
    np.testing.assert_allclose(np.abs(R[:n]), np.abs(Rnp), atol=5e-3)


def test_degenerate_panel_zero_column():
    pan = np.random.default_rng(9).standard_normal((32, 8)).astype(np.float32)
    pan[:, 3] = 0.0
    R, V, T = ops.panel_qr(jnp.array(pan), interpret=True)
    Rr, Vr, Tr = ref.ref_panel_factor(jnp.array(pan))
    assert np.isfinite(np.asarray(R)).all()
    np.testing.assert_allclose(np.asarray(R), np.asarray(Rr), atol=1e-4)


def _append_stack(n_piv, p, w, seed):
    rng = np.random.default_rng(seed)
    top = np.triu(rng.standard_normal((n_piv, w)))
    np.fill_diagonal(top, np.abs(np.diag(top)) + 1.0)
    return np.concatenate([top, rng.standard_normal((p, w))])


@pytest.mark.parametrize("case", ["panel", "apply", "geqrt", "update_append",
                                  "update_kalman"])
def test_chip_body_at_smoke_widths_matches_ref(case):
    """The kernel bodies Mosaic compiles, run in interpret mode at the
    widths chip_smoke.py serves (n=128 appends and Kalman steps, 512x128
    blocked solves with 64-wide panels), against the pure-jnp oracles."""
    from repro.core.ggr import ggr_triangularize
    from repro.kernels.ggr_update import batched_update_pallas

    rng = np.random.default_rng(11)
    if case in ("panel", "apply"):
        pan = jnp.asarray(rng.standard_normal((512, 64)), jnp.float32)
        if case == "panel":
            got = ops.panel_qr(pan, interpret=True)
            want = ref.ref_panel_factor(pan.astype(jnp.float64))
        else:
            _, V, T = ref.ref_panel_factor(pan.astype(jnp.float64))
            C = rng.standard_normal((512, 129))
            got = [ops.apply_panel(V.astype(jnp.float32), T.astype(jnp.float32),
                                   jnp.asarray(C, jnp.float32), block_w=129,
                                   interpret=True)]
            want = [ref.ref_apply_factors(V, T, jnp.asarray(C))]
    else:
        n_piv, p, w = {"geqrt": (64, 0, 128), "update_append": (128, 8, 129),
                       "update_kalman": (256, 136, 257)}[case]
        if case == "geqrt":
            X = rng.standard_normal((2, 64, w))
            got = [ops.batched_geqrt(jnp.asarray(X, jnp.float32), n_piv,
                                     interpret=True)]
        else:
            X = np.stack([_append_stack(n_piv, p, w, s) for s in (1, 2)])
            got = [batched_update_pallas(jnp.asarray(X, jnp.float32), n_piv,
                                         interpret=True)[:, :n_piv]]
        want = [jnp.stack([ggr_triangularize(jnp.asarray(x), n_piv)
                           for x in X])[:, :len(got[0][0])]]
    for g, r in zip(got, want):
        g, r = np.asarray(g, np.float64), np.asarray(r)
        assert np.isfinite(g).all()
        assert np.linalg.norm(g - r) <= 1e-5 * np.linalg.norm(r), case
