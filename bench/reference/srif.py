"""Plain float64 square-root information filter step (Bierman's SRIF).

State ``(R, d)`` with ``R^T R`` the information matrix and ``d = R x``.
One step predicts through ``x' = F x + G v``, ``v ~ N(0, Q)``, and folds in
the whitened plot ``z = H x' + e``, ``e ~ N(0, I)``: a QR of

    [ Qi        0    | 0 ]
    [ -R F^-1 G R F^-1 | d ]
    [ 0         H    | z ]

whose rows ``w .. w + n`` are the posterior.  numpy only; batched over the
leading axis of ``R``, ``d`` and ``z``.
"""
from __future__ import annotations

import numpy as np


def step(R, d, z, model):
    """Posterior ``(R', d')`` of a batch: R (B, n, n), d (B, n), z (B, p).
    ``model`` holds F (n, n), G (n, w), Qi (w, w) and H (p, n)."""
    F, G, Qi, H = (np.asarray(model[k], np.float64) for k in ("F", "G", "Qi", "H"))
    R, d, z = (np.asarray(a, np.float64) for a in (R, d, z))
    B, n = d.shape
    w, p = Qi.shape[0], H.shape[0]
    Rd = np.linalg.solve(F.T, R.transpose(0, 2, 1)).transpose(0, 2, 1)
    X = np.zeros((B, w + n + p, w + n + 1))
    X[:, :w, :w] = Qi
    X[:, w:w + n, :w] = -Rd @ G
    X[:, w:w + n, w:w + n] = Rd
    X[:, w:w + n, -1] = d
    X[:, w + n:, w:w + n] = H
    X[:, w + n:, -1] = z
    T = np.linalg.qr(X, mode="r")
    return T[:, w:w + n, w:w + n], T[:, w:w + n, -1]


def chain(R, d, zs, model):
    """Run ``step`` over ``zs`` (K, B, p) from ``(R, d)``; returns the
    final pair."""
    for z in zs:
        R, d = step(R, d, z, model)
    return R, d
