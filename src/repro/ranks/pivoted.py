"""Column-pivoted GGR QR, numerical rank, and min-norm least squares.

The paper's eq. 3 sweep computes suffix column norms as its own rotation
coefficients, so greedy column pivoting (QRCP) costs one extra reverse
cumulative sum + argmax per elimination step — the pivot selector reads row
``c`` of the ``core.blocked.suffix_col_norms`` matrix, swaps the winning
column in, and the ordinary ``ggr_column_step_at`` annihilates it.  No new
datapath, which is the co-design point of the companion Householder paper
(arXiv:1612.04470): pivoting rides the existing blocked structure.

Tall problems are reduced first: ``[A | rhs]`` goes through the *unpivoted*
blocked driver down to its top ``(n, n+k)`` block, and the pivoted sweep
runs on that small block only.  This is exact, not an approximation —
``QRCP(A) = Q1 · QRCP(R0)`` because the reduction is orthogonal and
therefore preserves every trailing column norm the pivot selection reads.

State convention: ``PivotedQR(R, d, perm, tail)`` with ``A[:, perm] = Q R``;
``R`` keeps GGR's non-negative-diagonal-up-to-last-row convention so it is
directly comparable with ``ggr_qr2(A[:, perm])``.  ``estimate_rank`` is the
rcond-relative diag-of-R test (QRCP orders ``|r_ii|`` to decay, so the diag
is a cheap spectrum proxy); ``lstsq_pivoted`` turns the state into the
min-norm solution via a complete orthogonal decomposition (QR of the masked
``R^T``), jit-safe with a *traced* rank.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.blocked import suffix_col_norms
from repro.core.ggr import ggr_column_step_at, ggr_qr2
from repro.solvers.lstsq import _triangularize_auto, solve_triangular

__all__ = [
    "PivotedQR",
    "PivotedLstsq",
    "estimate_rank",
    "ggr_qr_pivoted",
    "lstsq_pivoted",
]


class PivotedQR(NamedTuple):
    """Permutation-carrying compact factor state: ``A[:, perm] = Q R``.

    R: (min(m, n), n) upper triangular (trapezoidal when m < n)
    d: (min(m, n), k) top rows of Q^T rhs, or None when no rhs rode along
    perm: (n,) int32 column permutation (pivot order)
    tail: (k,) squared rhs norms from the reduced-away rows below R, or
        None — ``resid^2 = tail + sum_{i >= rank} d_i^2`` without Q.
    """

    R: jax.Array
    d: jax.Array | None
    perm: jax.Array
    tail: jax.Array | None


class PivotedLstsq(NamedTuple):
    x: jax.Array       # (n, k) min-norm solution
    resid: jax.Array   # (k,) residual 2-norms ||A x - b||
    rank: jax.Array    # () int32 numerical rank used for the solve
    R: jax.Array       # pivoted factor state (see PivotedQR)
    d: jax.Array
    perm: jax.Array


@functools.partial(jax.jit, static_argnames=("n_pivots",))
def _pivoted_sweep(X: jax.Array, n_pivots: int):
    """Greedy QRCP sweep over the first ``n_pivots`` columns of X.

    Per step: row ``c`` of the suffix-column-norm matrix (the eq. 3 DOT_k
    macro-op, one reverse cumsum for ALL candidates) -> argmax over the
    not-yet-pivoted columns -> column swap -> ``ggr_column_step_at``.
    Trailing columns (>= n_pivots, e.g. an rhs) ride along unswapped.
    """
    m, w = X.shape
    steps = min(m, n_pivots)
    cols = jnp.arange(n_pivots)

    def body(c, carry):
        X, perm = carry
        t2 = suffix_col_norms(X[:, :n_pivots])
        trail = jax.lax.dynamic_slice(t2, (c, 0), (1, n_pivots))[0]
        j = jnp.argmax(jnp.where(cols >= c, trail, -1.0))
        idx = jnp.arange(w).at[c].set(j).at[j].set(c)
        X = jnp.take(X, idx, axis=1)
        perm = jnp.take(perm, idx[:n_pivots])
        # the last row needs no annihilation (matches ggr_qr2's step count,
        # so the pivoted factor equals ggr_qr2(A[:, perm]) bit-for-bit
        # including the sign freedom of the final diagonal entry)
        X = jax.lax.cond(c < m - 1,
                         lambda x: ggr_column_step_at(x, c), lambda x: x, X)
        return X, perm

    return jax.lax.fori_loop(0, steps, body,
                             (X, jnp.arange(n_pivots, dtype=jnp.int32)))


def ggr_qr_pivoted(A: jax.Array, rhs: jax.Array | None = None) -> PivotedQR:
    """Column-pivoted GGR QR of A with an optional rhs riding along.

    Tall A is first reduced unpivoted through the size-routed blocked driver
    (column norms are preserved by the orthogonal reduction, so pivoting on
    the small top block is exact QRCP); the pivoted sweep then runs on the
    ``(min(m, n), n [+ k])`` block.  ``rhs`` may be ``(m,)`` or ``(m, k)``.
    """
    m, n = A.shape
    k = 0
    X = A
    if rhs is not None:
        B = rhs[:, None] if rhs.ndim == 1 else rhs
        k = B.shape[1]
        X = jnp.concatenate([A, B.astype(A.dtype)], axis=1)
    tail = None
    if m > n:
        X = _triangularize_auto(X, n)
        if rhs is not None:
            tail = jnp.sum(X[n:, n:].astype(
                jnp.promote_types(X.dtype, jnp.float32)) ** 2, axis=0)
        X = X[:n]
    elif rhs is not None:
        tail = jnp.zeros((k,), jnp.promote_types(X.dtype, jnp.float32))
    X, perm = _pivoted_sweep(X, n)
    R = jnp.triu(X[:, :n])
    d = X[:, n:] if rhs is not None else None
    return PivotedQR(R=R, d=d, perm=perm, tail=tail)


def estimate_rank(R: jax.Array, rcond: float | None = None) -> jax.Array:
    """Numerical rank of a (pivoted) triangular factor: the rcond-relative
    diag test ``#{i : |r_ii| > rcond * max_j |r_jj|}``.

    QRCP orders the diagonal to decay, so this is the standard cheap
    estimator (same convention as ``numpy.linalg.lstsq``'s cutoff applied
    to the R diagonal).  Default rcond is ``max(R.shape) * eps(dtype)``.
    jit-safe; returns a traced int32 scalar.
    """
    diag = jnp.abs(jnp.diagonal(R))
    if rcond is None:
        rcond = max(R.shape) * float(jnp.finfo(R.dtype).eps)
    dmax = jnp.max(diag) if diag.size else jnp.zeros((), R.dtype)
    return jnp.sum(diag > jnp.asarray(rcond, diag.dtype) * dmax).astype(jnp.int32)


def _min_norm_from_state(R, d, perm, tail, rank):
    """Min-norm solve from a pivoted state with a *traced* rank.

    Complete orthogonal decomposition with ``where``-masking instead of
    shape slicing: rows of (R, d) at or beyond ``rank`` are zeroed, the
    masked ``R^T`` gets its own GGR QR (``R_r^T = Q2 T``), and the
    triangular solves' eps-guarded diagonals keep every beyond-rank
    component exactly zero — so one compiled program serves every rank.
    """
    mm, n = R.shape
    keep = (jnp.arange(mm) < rank)[:, None]
    Rm = jnp.where(keep, R, 0.0)
    dm = jnp.where(keep, d, 0.0)
    T, Q2 = ggr_qr2(Rm.T, want_q=True)      # (n, mm) triu, (n, n)
    z = solve_triangular(jnp.triu(T[:mm]), dm, trans=True)
    hi = jax.lax.Precision.HIGHEST  # the TPU's default is one bf16 pass
    y = jnp.matmul(Q2[:, :mm], z, precision=hi)  # min-norm x, permuted coords
    x = jnp.zeros((n, d.shape[1]), y.dtype).at[perm].set(y)
    # honest residual: the dropped rows of the *unmasked* state still hold
    # (small) mass — score y against them, plus the reduced-away tail
    f32 = jnp.promote_types(R.dtype, jnp.float32)
    rrows = (d - jnp.matmul(R, y, precision=hi)).astype(f32)
    resid = jnp.sqrt(jnp.sum(rrows * rrows, axis=0) + tail)
    return x, resid.astype(R.dtype)


def lstsq_pivoted(A: jax.Array, b: jax.Array,
                  rcond: float | None = None) -> PivotedLstsq:
    """Rank-aware min ||Ax - b||: pivoted QR + min-norm solve.

    Unlike ``solvers.ggr_lstsq`` this never divides by a collapsed pivot:
    the numerical rank r comes from ``estimate_rank(R, rcond)`` and the
    solution is the minimum-norm x over the rank-r truncation — the same
    contract as ``numpy.linalg.lstsq`` (whose ``rcond`` this mirrors),
    computed without an SVD.  Accepts m < n as well.
    """
    vec = b.ndim == 1
    st = ggr_qr_pivoted(A, b)
    rank = estimate_rank(st.R, rcond)
    x, resid = _min_norm_from_state(st.R, st.d, st.perm, st.tail, rank)
    if vec:
        return PivotedLstsq(x=x[:, 0], resid=resid[0], rank=rank,
                            R=st.R, d=st.d[:, 0], perm=st.perm)
    return PivotedLstsq(x=x, resid=resid, rank=rank,
                        R=st.R, d=st.d, perm=st.perm)
