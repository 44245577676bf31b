"""Plain float64 least squares (numpy only).

``solve(A, B)`` gives, for each column ``b`` of ``B``, the minimiser of
``||A x - b||`` and that residual norm, by ``numpy.linalg.lstsq``.
"""
from __future__ import annotations

import numpy as np


def solve(A, B):
    """``(X, r)`` for A (m, n) of full column rank and B (m, k): X (n, k)
    the least-squares solutions, r (k,) the residual norms ``||A X - B||``
    of each column."""
    A = np.asarray(A, np.float64)
    B = np.asarray(B, np.float64)
    X = np.linalg.lstsq(A, B, rcond=None)[0]
    return X, np.linalg.norm(A @ X - B, axis=0)
