"""Square-root information filtering (SRIF) on GGR — Kalman as triangularization.

The square-root information filter (Bierman/Dyer-McReynolds) keeps the state
estimate as the compact pair ``(R, d)`` with ``R^T R = P^{-1}`` (upper
triangular, non-negative diagonal — the GGR sign convention) and ``d = R x``.
Both filter steps are then *exactly* augmented QR triangularizations, which is
why this module is a thin front-end over the repo's GGR engine:

* **observe** — a whitened measurement ``z = H x + v`` is one appended
  data-equation row per measurement: ``qr_append_rows(R, H, d, z)``.  Same
  macro-op sweep as streaming least squares.
* **predict** — with dynamics ``x' = F x + G w``, ``w ~ N(0, Q)``, substitute
  ``x = F^{-1}(x' - G w)`` into the data equation ``R x = d - nu`` and stack
  the process-noise data equation ``Qi w = 0 - nu_w`` (``Qi^T Qi = Q^{-1}``):

      [ Qi        0    | 0 ]        GGR sweep        [ *   *     | *  ]
      [ -Rd G     Rd   | d ]   ----------------->    [ 0   R'    | d' ]

  with ``Rd = R F^{-1}``.  Triangularizing the first ``w + n`` columns
  marginalizes the noise ``w`` out; rows ``w..w+n`` are the predicted pair.
* **step** (predict + observe fused) — append the whitened measurement rows
  ``[0 | H | z]`` to the same stack and insert an all-zero pivot block so the
  top ``w + n`` rows stay upper triangular:

      [ Qi      0     | 0 ]   <- w pivot rows (triangular)
      [ 0       0     | 0 ]   <- n zero pivot rows (diag picked up below)
      [ -Rd G   Rd    | d ]   <- n appended rows
      [ 0       H     | z ]   <- p appended rows

  One sweep over ``w + n`` pivots yields the *posterior* pair in the zero
  block's rows.  Crucially this is the ``[R_tri | rhs; appended]`` shape the
  batched Pallas row-append kernel (``kernels.ggr_update``) already handles,
  so ``kf_step_batched`` advances thousands of independent filters per fused
  kernel dispatch — the multi-target tracking / fleet-telemetry workload.

Smoothing: ``kf_filter`` stores the per-step predicted/filtered factors;
``kf_smooth`` runs the RTS backward pass on them (covariances recovered by
triangular solves against the stored ``R`` factors — never by re-inverting an
information matrix from scratch).

Conventions: ``Qi = info_sqrt(Q)`` and measurement rows pre-whitened with
``whiten_measurement`` (or pass ``info_sqrt(R_noise)`` yourself).  All inputs
follow the module-wide non-negative-diagonal upper-triangular convention.

Serving front-door: ``repro.launch.serve_qr.QRServer.submit_kalman`` queues
single-filter steps and flushes each group through ``kf_step_batched`` (one
fused — optionally ``shard_map``-sharded — dispatch per group).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.ggr import ggr_qr2, ggr_triangularize

from .lstsq import solve_triangular
from .qr_update import (
    _sharded_update_fn,
    _update_stacked,
    qr_append_rows,
)

# the prediction's matmuls at full f32 (the TPU's default is one bf16 pass)
_HIGHEST = jax.lax.Precision.HIGHEST

__all__ = [
    "KalmanState",
    "KalmanTrajectory",
    "info_sqrt",
    "kf_init",
    "kf_mean",
    "kf_cov",
    "kf_predict",
    "kf_observe",
    "kf_step",
    "kf_step_batched",
    "kf_filter",
    "kf_smooth",
    "whiten_measurement",
]


class KalmanState(NamedTuple):
    """Square-root information state: ``R^T R = P^{-1}``, ``d = R x``.

    R: (n, n) upper triangular, non-negative diagonal (GGR convention)
    d: (n,)   information rhs — the state mean is ``solve(R, d)``
    step: scalar int32 — number of predict steps applied so far
    """

    R: jax.Array
    d: jax.Array
    step: jax.Array


class KalmanTrajectory(NamedTuple):
    """Stored per-step factors from ``kf_filter`` (inputs to ``kf_smooth``).

    Rp/dp: (T, n, n) / (T, n) predicted (prior) pairs, one per time step
    Rf/df: (T, n, n) / (T, n) filtered (posterior) pairs
    """

    Rp: jax.Array
    dp: jax.Array
    Rf: jax.Array
    df: jax.Array


def info_sqrt(M: jax.Array) -> jax.Array:
    """Upper-triangular ``U`` with ``U^T U = M^{-1}`` for symmetric PD ``M``.

    Cholesky ``M = L L^T`` followed by a GGR QR of ``L^{-1}``: the R factor
    of ``L^{-1} = Theta U`` satisfies ``U^T U = L^{-T} L^{-1} = M^{-1}`` and
    carries the module-wide non-negative-diagonal convention.  This is the
    canonical converter from covariance inputs (process noise Q, measurement
    noise R) to the information square roots the SRIF stacks consume.
    """
    M = jnp.asarray(M)
    L = jnp.linalg.cholesky(M)
    Linv = solve_triangular(L, jnp.eye(M.shape[0], dtype=M.dtype), lower=True)
    return ggr_qr2(Linv)


def whiten_measurement(R_noise: jax.Array, H: jax.Array, z: jax.Array):
    """Whiten a measurement model: returns ``(W H, W z)``, ``W^T W = R_noise^{-1}``.

    After whitening, each measurement row has unit noise and folds into the
    information state as a plain data-equation row (``kf_observe``).
    """
    W = info_sqrt(R_noise)
    return W @ H, W @ z


def kf_init(x0: jax.Array, P0: jax.Array) -> KalmanState:
    """State from a prior mean ``x0`` and covariance ``P0``: R = info_sqrt(P0)."""
    R0 = info_sqrt(P0)
    return KalmanState(R=R0, d=R0 @ x0, step=jnp.zeros((), jnp.int32))


def kf_mean(state: KalmanState) -> jax.Array:
    """Current state estimate ``x = R^{-1} d`` (one triangular solve)."""
    return solve_triangular(state.R, state.d)


def kf_cov(state: KalmanState) -> jax.Array:
    """Current covariance ``P = R^{-1} R^{-T}`` via a triangular solve."""
    K = solve_triangular(state.R, jnp.eye(state.R.shape[0], dtype=state.R.dtype))
    return K @ K.T


def _apply_F_inv(R, F):
    """``Rd = R F^{-1}`` via the repo's own engine — F is never inverted.

    GGR-factor ``F^T = Theta U`` (orthogonal x upper triangular), then
    ``Rd^T = U^{-1} (Theta^T R^T)`` is a matmul plus one triangular solve.
    Deliberately not ``jnp.linalg.solve``: the LAPACK batched-LU path picks a
    different accumulation order under vmap, which would break the
    batched == sequential bitwise contract of ``kf_step_batched``.
    """
    U, Theta = ggr_qr2(F.T, want_q=True)
    return solve_triangular(U, jnp.matmul(Theta.T, R.T, precision=_HIGHEST)).T


def _predict_blocks(R, d, F, Qi, G):
    """The two SRIF prediction rows: ``[Qi | 0 | 0]`` and ``[-Rd G | Rd | d]``."""
    n = R.shape[0]
    w = Qi.shape[0]
    Rd = _apply_F_inv(R, F)
    RdG = Rd if G is None else jnp.matmul(Rd, G, precision=_HIGHEST)
    top = jnp.concatenate([Qi, jnp.zeros((w, n + 1), R.dtype)], axis=1)
    mid = jnp.concatenate([-RdG, Rd, d[:, None]], axis=1)
    return top, mid


def kf_predict(state: KalmanState, F: jax.Array, Qi: jax.Array,
               G: jax.Array | None = None) -> KalmanState:
    """SRIF time update for ``x' = F x + G w``, ``w ~ N(0, Q)``.

    ``Qi = info_sqrt(Q)`` is the (w, w) upper-triangular process-noise
    information square root; ``G`` is the (n, w) noise input map (default:
    identity, w = n).  One ``ggr_triangularize`` sweep over the stacked
    ``(w + n, w + n + 1)`` matrix (see module docstring) marginalizes the
    process noise; rows ``w..`` hold the predicted ``(R, d)``.
    """
    n = state.R.shape[0]
    w = Qi.shape[0]
    top, mid = _predict_blocks(state.R, state.d, F, Qi, G)
    out = ggr_triangularize(jnp.concatenate([top, mid], axis=0), w + n)
    return KalmanState(R=jnp.triu(out[w:, w:w + n]), d=out[w:, w + n],
                       step=state.step + 1)


def kf_observe(state: KalmanState, H: jax.Array, z: jax.Array) -> KalmanState:
    """SRIF measurement update: fold in whitened rows ``z = H x + v``, v ~ N(0, I).

    Delegates to ``qr_append_rows`` — each measurement is literally an
    appended observation row of the information least-squares system.  ``H``
    is (p, n), ``z`` is (p,); whiten correlated noise first with
    ``whiten_measurement``.
    """
    z = jnp.asarray(z)
    R, d = qr_append_rows(state.R, H, state.d[:, None], z[:, None])
    return KalmanState(R=R, d=d[:, 0], step=state.step)


def _step_stacked(R, d, F, Qi, H, z, G):
    """Fused predict+observe stack, shape ``(w + 2n + p, w + n + 1)``.

    Top ``w + n`` rows are upper triangular by construction (Qi block plus an
    all-zero pivot block), so this is directly consumable by both
    ``ggr_triangularize`` and the batched row-append kernel; the posterior
    pair lands in rows ``w..w+n`` after the sweep.
    """
    n = R.shape[0]
    w = Qi.shape[0]
    p = H.shape[0]
    top, mid = _predict_blocks(R, d, F, Qi, G)
    zero_piv = jnp.zeros((n, w + n + 1), R.dtype)
    obs = jnp.concatenate([jnp.zeros((p, w), R.dtype), H, z[:, None]], axis=1)
    return jnp.concatenate([top, zero_piv, mid, obs], axis=0)


def kf_step(state: KalmanState, F: jax.Array, Qi: jax.Array, H: jax.Array,
            z: jax.Array, G: jax.Array | None = None) -> KalmanState:
    """One fused predict+observe sweep (the unit ``kf_step_batched`` batches).

    Same posterior as ``kf_observe(kf_predict(state, F, Qi, G), H, z)`` up to
    rotation order (both yield the unique non-negative-diagonal factor, so
    they agree to roundoff); bit-identical to one lane of the batched
    reference path, which vmaps exactly this stacked sweep.
    """
    n = state.R.shape[0]
    w = Qi.shape[0]
    X = _step_stacked(state.R, state.d, F, Qi, H, jnp.asarray(z), G)
    out = ggr_triangularize(X, w + n)
    R_new = jnp.triu(out[w:w + n, w:w + n])
    # posterior-factor health: with a collector installed the gauge now
    # carries the real incremental condition estimate (repro.obs.health /
    # repro.ranks.monitor); no-op under scan/jit tracing.  Long-running
    # fleets wanting per-track trend + alarms should attach a
    # ``repro.ranks.ConditionMonitor`` to their flush results instead.
    obs.factor_health(R_new, "kalman")
    return KalmanState(R=R_new, d=out[w:w + n, w + n],
                       step=state.step + 1)


@jax.jit
def _step_matrices(R, d, F, Qi, H, z, G):
    """The B stacked step matrices of ``kf_step_batched``: shared (2-D)
    model operands and a shared (1-D) ``z`` broadcast over the batch, then
    ``_step_stacked`` vmapped over it.

    Compiled per padded shape and model layout (which operands are shared,
    whether ``G`` is None), so a chunk dispatches one call and traces
    nothing.  Each lane runs ``_step_stacked`` as the eager vmap does, and
    the matrices are bitwise the eager vmap's.
    """
    B = R.shape[0]

    def bcast(M, ndim):
        if M is None or M.ndim > ndim:
            return M
        return jnp.broadcast_to(M, (B,) + M.shape)

    return jax.vmap(_step_stacked)(R, d, *(bcast(M, 2) for M in (F, Qi, H)),
                                   bcast(z, 1), bcast(G, 2))


def kf_step_batched(R: jax.Array, d: jax.Array, F: jax.Array, Qi: jax.Array,
                    H: jax.Array, z: jax.Array, G: jax.Array | None = None,
                    *, backend: str = "pallas", interpret: bool | None = None,
                    block_b: int = 8, mesh=None, mesh_axis: str = "batch",
                    precision=None):
    """Advance B independent SRIF filters one predict+observe step at once.

    R: (B, n, n), d: (B, n), z: (B, p); the model matrices ``F`` (n, n),
    ``Qi`` (w, w), ``H`` (p, n), ``G`` (n, w) may be shared (2-D, broadcast
    across the batch — the multi-target-tracking case of one dynamics model
    and many tracks) or per-filter (leading B dimension).  Returns
    ``(R', d')`` of the same batch shapes.

    The B stacked step matrices ride the batched row-append kernel's
    batch-tiled grid (``backend="pallas"``) — one fused dispatch per call,
    block_b problems VMEM-resident per grid step — or a vmapped
    ``ggr_triangularize`` (``backend="reference"``).  With ``mesh=`` the
    batch is zero-padded to ``shards x block_b`` and dispatched through
    ``shard_map`` over ``mesh_axis``, exactly like
    ``qr_append_rows_batched``: sharded and single-device results agree
    bitwise.

    The step matrices are built in one compiled call (``_step_matrices``),
    compiled per padded shape and model layout: the operands' shapes and
    dtypes, which model operands are shared, and whether ``G`` is given.

    ``precision``: mixed-precision policy (``Precision`` / name / None).
    The stacked step matrices run at the policy's compute dtype with wide
    in-kernel accumulation; the returned ``(R', d')`` carry compute dtype.
    """
    B, n = R.shape[0], R.shape[2]
    w = Qi.shape[-1]
    if precision is not None:
        from repro.kernels import resolve_precision  # solvers -> kernels edge

        precision = resolve_precision(precision)

    with obs.span("repro/kalman/front"):
        stacked = _step_matrices(R, d, F, Qi, H, z, G)

    n_piv = w + n
    if mesh is None:
        out = _update_stacked(stacked, n_piv, backend, interpret, block_b,
                              precision=precision)
    else:
        from repro.kernels import pad_batch  # deferred: solvers -> kernels edge

        shards = mesh.shape[mesh_axis]
        padded = pad_batch(stacked, shards * block_b)
        fn = _sharded_update_fn(mesh, mesh_axis, n_piv, backend, interpret,
                                block_b, precision)
        with obs.span("repro/kernels/ggr_update"):
            out = fn(padded)
        out = out[:B]
    # no factor-health gauge here: it would copy R' to the host and wait for
    # the kernel; the serving engine records it once the chunk is ready
    R_new = jnp.triu(out[:, w:w + n, w:w + n])
    return R_new, out[:, w:w + n, w + n]


def kf_filter(state: KalmanState, F: jax.Array, Qi: jax.Array, H: jax.Array,
              zs: jax.Array, G: jax.Array | None = None):
    """Run the filter over a (T, p) measurement sequence under ``lax.scan``.

    Returns ``(final_state, KalmanTrajectory)`` — the trajectory stores each
    step's predicted and filtered ``(R, d)`` factors so ``kf_smooth`` can run
    its backward pass without re-filtering.
    """

    def one(st, z):
        pred = kf_predict(st, F, Qi, G)
        post = kf_observe(pred, H, z)
        return post, (pred.R, pred.d, post.R, post.d)

    final, (Rp, dp, Rf, df) = jax.lax.scan(one, state, zs)
    return final, KalmanTrajectory(Rp=Rp, dp=dp, Rf=Rf, df=df)


def kf_smooth(traj: KalmanTrajectory, F: jax.Array):
    """RTS (Rauch-Tung-Striebel) backward pass on stored SRIF factors.

    For each step the smoother gain is ``C_t = P_f[t] F^T P_p[t+1]^{-1}``
    with ``P_p^{-1} = Rp^T Rp`` read directly off the stored predicted factor
    (no matrix inversion beyond triangular solves against the stored ``R``s):

        x_s[t] = x_f[t] + C_t (x_s[t+1] - x_p[t+1])
        P_s[t] = P_f[t] + C_t (P_s[t+1] - P_p[t+1]) C_t^T

    Returns ``(xs, Ps)`` of shapes (T, n) and (T, n, n).
    """
    Rp, dp, Rf, df = traj
    n = df.shape[1]
    eye = jnp.eye(n, dtype=Rf.dtype)

    def mean_cov(R, d):
        K = solve_triangular(R, eye)
        return solve_triangular(R, d), K @ K.T

    xf, Pf = jax.vmap(mean_cov)(Rf, df)
    xp, Pp = jax.vmap(mean_cov)(Rp, dp)

    def back(carry, inp):
        xs_next, Ps_next = carry
        xf_t, Pf_t, xp_n, Pp_n, Rp_n = inp
        C = Pf_t @ F.T @ (Rp_n.T @ Rp_n)
        xs_t = xf_t + C @ (xs_next - xp_n)
        Ps_t = Pf_t + C @ (Ps_next - Pp_n) @ C.T
        return (xs_t, Ps_t), (xs_t, Ps_t)

    inputs = (xf[:-1], Pf[:-1], xp[1:], Pp[1:], Rp[1:])
    _, (xs_head, Ps_head) = jax.lax.scan(back, (xf[-1], Pf[-1]), inputs,
                                         reverse=True)
    xs = jnp.concatenate([xs_head, xf[-1:]], axis=0)
    Ps = jnp.concatenate([Ps_head, Pf[-1:]], axis=0)
    return xs, Ps
