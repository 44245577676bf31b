"""Least-squares solvers on top of GGR QR: one-shot and streaming.

* ``solve_triangular`` — scan-based substitution (all four lower/upper ×
  trans variants reduce to one forward-substitution core via flips).
* ``ggr_lstsq`` — one-shot min ||Ax - b||: GGR sweep over the augmented
  ``[A | b]`` (so Q is never formed — the rhs rides along through the DET2
  grids), then a triangular solve.
* ``RecursiveLS`` — the streaming state machine: ``observe`` (row append,
  optionally with exponential forgetting), ``forget`` (sliding-window
  downdate) and ``solve``, all O(n^2) per event and jit/scan-friendly.
  State is the compact ``(R, d)`` pair — never the Gram matrix, never Q.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.ggr import ggr_triangularize

from .qr_update import _tri_solve_lower, qr_append_rows, qr_downdate_row

__all__ = ["LstsqResult", "RLSState", "RecursiveLS", "count_solves",
           "ggr_lstsq", "lstsq_route", "solve_triangular", "state_integrity"]


def state_integrity(state, max_cond: float | None = None) -> tuple[bool, str]:
    """Integrity gate for a streaming factor state (``RLSState``,
    ``KalmanState``, or any ``(R, d)``-carrying pytree).

    Returns ``(ok, reason)``: every inexact leaf must be finite, and — when
    ``max_cond`` is given and the state exposes an ``R`` attribute — the
    triangular factor's ``cond_estimate`` must not exceed it.  This is what
    ``repro.serve.StateVault`` runs at restore time so a corrupted snapshot
    is rejected instead of resurrected; it is eager/host-side by design
    (never call it under jit).
    """
    for leaf in jax.tree_util.tree_leaves(state):
        a = jnp.asarray(leaf)
        if (jnp.issubdtype(a.dtype, jnp.inexact)
                and not bool(jnp.isfinite(a).all())):
            return False, "non-finite leaf"
    R = getattr(state, "R", None)
    if max_cond is not None and R is not None:
        from repro.ranks.monitor import cond_estimate  # lazy: ranks -> solvers

        cond = float(cond_estimate(jnp.asarray(R)).cond)
        if not cond <= max_cond:
            return False, (f"cond estimate {cond:.3e} exceeds "
                           f"bound {max_cond:.3e}")
    return True, "ok"

# Above this problem size the one-shot solvers dispatch their augmented sweep
# to the blocked panel driver (``core.blocked.ggr_triangularize_blocked``):
# batched tile kernels + tree coupling + GEMM trailing updates win once the
# column loop of the unblocked sweep stops fitting the machine, while small
# streaming problems keep the cheap single-sweep path.
_BLOCKED_MIN_ROWS = 256
_BLOCKED_MIN_PIVOTS = 128


def lstsq_route(m: int, n_pivots: int) -> str:
    """How an (m, ·) augmented sweep with ``n_pivots`` pivots is run:
    ``"blocked"`` (the panel driver and its Pallas kernels) or ``"sweep"``
    (the plain-``jnp`` column sweep, ``core.ggr.ggr_triangularize``)."""
    if m >= _BLOCKED_MIN_ROWS and n_pivots >= _BLOCKED_MIN_PIVOTS:
        return "blocked"
    return "sweep"


def count_solves(route: str, n: int = 1) -> None:
    """Count ``n`` solves that took ``route`` as ``solvers.lstsq_solves``
    (a no-op without an installed collector).  Called on the host, never
    under trace: by eager ``ggr_lstsq``, and per chunk by the serving
    executors."""
    if obs.enabled():
        obs.counter("solvers.lstsq_solves", route=route).inc(n)


def _triangularize_auto(X: jax.Array, n_pivots: int,
                        route: str | None = None) -> jax.Array:
    """Augmented triangularization on ``route`` (None: ``lstsq_route``)."""
    if route is None:
        route = lstsq_route(X.shape[0], n_pivots)
    if route == "blocked":
        from repro.core.blocked import ggr_triangularize_blocked

        return ggr_triangularize_blocked(X, n_pivots)
    if route != "sweep":
        raise ValueError(f"unknown lstsq route {route!r}")
    return ggr_triangularize(X, n_pivots)


def solve_triangular(R: jax.Array, b: jax.Array, *, lower: bool = False,
                     trans: bool = False) -> jax.Array:
    """Solve R x = b (or R^T x = b) for triangular R; b is (n,) or (n, k).

    Upper-triangular systems are solved by the anti-diagonal flip
    ``flip(L_solve(flip(R), flip(b)))`` so a single forward-substitution
    scan serves every variant.
    """
    vec = b.ndim == 1
    B = b[:, None] if vec else b
    A = R.T if trans else R
    eff_lower = lower != trans  # transposing swaps triangle orientation
    if eff_lower:
        X = _tri_solve_lower(A, B)
    else:
        X = _tri_solve_lower(A[::-1, ::-1], B[::-1])[::-1]
    return X[:, 0] if vec else X


class LstsqResult(NamedTuple):
    x: jax.Array       # (n, k) solution
    resid: jax.Array   # (k,) residual 2-norms ||A x - b||
    R: jax.Array       # (n, n) triangular factor
    d: jax.Array       # (n, k) Q^T b (top rows)


# A collapsed pivot sits at roundoff level relative to the largest one;
# anything below this many eps is rank-collapse junk, not data.  Kept well
# under 1/cond of any problem the unpivoted solver can honestly handle.
_RANK_COLLAPSE_EPS_MULT = 32.0


def ggr_lstsq(A: jax.Array, b: jax.Array,
              rcond: float | None = None, *,
              route: str | None = None) -> LstsqResult:
    """min ||Ax - b|| for full-column-rank A (m >= n) via augmented GGR.

    One sweep triangularizes ``[A | b]`` to ``[R | d; 0 | r]``; x solves
    R x = d and ||r|| is the residual norm — b never needs a separate
    Q^T multiply, it is just extra trailing columns in the DET2 grids.

    ``rcond`` is the rank-deficiency escape hatch: when given, the solve
    routes to the pivoted min-norm path (``repro.ranks.lstsq_pivoted``) and
    the returned ``(R, d)`` are the *pivoted* factors — ``R`` is the QRCP
    factor of ``A[:, perm]``, so streaming updates must not assume original
    column order.  With ``rcond=None`` (the default) a rank-collapsed pivot
    raises a diagnostic ``ValueError`` on eager calls instead of silently
    dividing noise by it (the historical behaviour); traced/jitted calls
    cannot inspect values and keep the unchecked fast path.

    ``route`` (``"blocked"`` | ``"sweep"``) overrides ``lstsq_route``'s
    size rule for the augmented sweep, here and on the pivoted path.
    """
    m, n = A.shape
    if m < n:
        raise ValueError(f"ggr_lstsq requires m >= n, got {A.shape}")
    if rcond is not None:
        from repro.ranks import lstsq_pivoted  # lazy: breaks the import cycle

        fit = lstsq_pivoted(A, b, rcond=rcond, route=route)
        return LstsqResult(x=fit.x, resid=fit.resid, R=fit.R, d=fit.d)
    vec = b.ndim == 1
    B = b[:, None] if vec else b
    if route is None:
        route = lstsq_route(m, n)
    X = _triangularize_auto(jnp.concatenate([A, B], axis=1), n, route)
    R = jnp.triu(X[:n, :n])
    d = X[:n, n:]
    if not isinstance(R, jax.core.Tracer):
        count_solves(route)
        diag = jnp.abs(jnp.diagonal(R))
        dmin, dmax = float(jnp.min(diag)), float(jnp.max(diag))
        cliff = _RANK_COLLAPSE_EPS_MULT * float(jnp.finfo(R.dtype).eps)
        if dmin <= dmax * cliff:
            raise ValueError(
                f"ggr_lstsq: rank-deficient input — min |diag R| = {dmin:.3e} "
                f"vs max {dmax:.3e} (below {_RANK_COLLAPSE_EPS_MULT:g}*eps "
                "relative).  The triangular solve would amplify noise by "
                "1/|r_ii|.  Pass rcond= to get the pivoted min-norm solution "
                "(repro.ranks.lstsq_pivoted), e.g. rcond=1e-10 for f64.")
    # numerical-health sensors (no-ops unless a collector is installed, and
    # under jit/vmap tracing; the orthogonality audit is sampled — see
    # repro.obs.health)
    obs.factor_health(R, "lstsq")
    obs.maybe_sample_orthogonality(A, R, "lstsq")
    x = solve_triangular(R, d)
    resid = jnp.sqrt(jnp.sum(X[n:, n:] ** 2, axis=0))
    if vec:
        return LstsqResult(x=x[:, 0], resid=resid[0], R=R, d=d[:, 0])
    return LstsqResult(x=x, resid=resid, R=R, d=d)


class RLSState(NamedTuple):
    """Compact streaming least-squares state.

    Invariants over the (weighted) observation stream:
        R^T R = delta·I + sum_i w_i u_i u_i^T      (upper-tri, diag >= 0)
        R^T d = sum_i w_i u_i y_i
    """

    R: jax.Array  # (n, n)
    d: jax.Array  # (n, k)
    count: jax.Array  # scalar int32 — observations currently in the window


class RecursiveLS:
    """Streaming recursive least squares via QR up/downdating.

    Functional-JAX style: the instance holds static config (feature dim n,
    rhs width k, forgetting factor lam, ridge seed delta); every method is a
    pure ``state -> state`` map, safe under jit/scan/vmap.

        rls = RecursiveLS(n=8)
        state = rls.init()
        state = rls.observe(state, u, y)        # new observation row
        state = rls.forget(state, u_old, y_old) # slide the window
        x = rls.solve(state)

    ``lam < 1`` applies exponential forgetting at each observe (the
    sqrt(lam)-scaling of (R, d) keeps the Gram invariant G <- lam·G + u u^T).
    """

    def __init__(self, n: int, k: int = 1, lam: float = 1.0, delta: float = 1e-8):
        if not 0.0 < lam <= 1.0:
            raise ValueError("forgetting factor lam must be in (0, 1]")
        self.n = n
        self.k = k
        self.lam = lam
        self.delta = delta

    def init(self, dtype=jnp.float32) -> RLSState:
        """Fresh state: R = sqrt(delta)·I (ridge seed keeps R invertible)."""
        R0 = jnp.sqrt(jnp.asarray(self.delta, dtype)) * jnp.eye(self.n, dtype=dtype)
        return RLSState(R=R0, d=jnp.zeros((self.n, self.k), dtype),
                        count=jnp.zeros((), jnp.int32))

    def _as_rows(self, u, y):
        U = u[None, :] if u.ndim == 1 else u
        Y = jnp.asarray(y, U.dtype).reshape(U.shape[0], self.k)
        return U, Y

    def observe(self, state: RLSState, u: jax.Array, y: jax.Array) -> RLSState:
        """Fold in observation row(s): u (n,) or (p, n), y (k,)/(p, k)."""
        U, Y = self._as_rows(u, y)
        g = jnp.asarray(self.lam, state.R.dtype) ** (0.5 * U.shape[0])
        R, d = qr_append_rows(g * state.R, U, g * state.d, Y)
        return RLSState(R=R, d=d, count=state.count + U.shape[0])

    def forget(self, state: RLSState, u: jax.Array, y: jax.Array,
               guard=None) -> RLSState:
        """Remove a previously-observed row (sliding-window downdate).

        Only meaningful with lam == 1.0 (with exponential forgetting the old
        row's weight has decayed, so the unscaled downdate would overshoot).
        ``guard`` (a ``repro.ranks.DowndateGuard``) bounds the hyperbolic
        step away from the rank cliff — a shrinking window over nearly
        collinear features is exactly where an unguarded forget destroys
        the factor; see ``qr_downdate_row``.
        """
        y_row = jnp.asarray(y, state.R.dtype).reshape(self.k)
        R, d = qr_downdate_row(state.R, u, state.d, y_row, guard=guard)
        return RLSState(R=R, d=d, count=state.count - 1)

    def solve(self, state: RLSState) -> jax.Array:
        """Current weights x = R^{-1} d, shape (n, k) (or (n,) when k == 1)."""
        x = solve_triangular(state.R, state.d)
        return x[:, 0] if self.k == 1 else x

    def predict(self, state: RLSState, u: jax.Array) -> jax.Array:
        """y_hat = u @ x for a feature row or batch of rows."""
        x = solve_triangular(state.R, state.d)
        out = u @ x
        return out[..., 0] if self.k == 1 else out

    def residual_gram(self, state: RLSState, u: jax.Array) -> jax.Array:
        """||R^{-T} u||^2 — the leverage of u under the current window
        (used by the downdate: 1 - leverage must stay positive)."""
        q = _tri_solve_lower(state.R.T.astype(jnp.promote_types(state.R.dtype,
                                                                jnp.float32)),
                             u[:, None])[:, 0]
        return q @ q
