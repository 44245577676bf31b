"""repro.solvers — streaming QR updates and least squares on GGR.

The factorization library's consumer layer: instead of re-factorizing an
ever-growing matrix, maintain a compact ``(R, d)`` state and apply
Givens-based up/downdates — the workload the paper's fused GGR macro-ops
(suffix sums + DET2 grids) were built for, at streaming granularity.

Quick tour::

    import jax.numpy as jnp
    from repro.solvers import ggr_lstsq, qr_append_rows, RecursiveLS

    # one-shot least squares (augmented GGR sweep, Q never formed)
    fit = ggr_lstsq(A, b)              # fit.x, fit.resid, fit.R, fit.d

    # incremental: fold 4 new rows into an existing factor in O(n^2·p)
    R2, d2 = qr_append_rows(fit.R, U_new, fit.d[:, None], Y_new)

    # streaming state machine (observe / forget / solve)
    rls = RecursiveLS(n=A.shape[1])
    st = rls.init()
    st = rls.observe(st, u_t, y_t)     # new sample
    st = rls.forget(st, u_old, y_old)  # slide the window
    x = rls.solve(st)

    # fleet of independent small updates -> one fused Pallas launch
    from repro.solvers import qr_append_rows_batched
    R_batch2 = qr_append_rows_batched(R_batch, U_batch, backend="pallas")

    # state estimation: square-root Kalman filtering is the same sweep
    from repro.solvers import kf_init, kf_predict, kf_observe, kf_step_batched
    st = kf_init(x0, P0)               # (R, d) information square root
    st = kf_predict(st, F, Qi)         # time update = augmented GGR sweep
    st = kf_observe(st, H, z)          # measurement update = row append
    Rb, db = kf_step_batched(R_b, d_b, F, Qi, H, z_b)  # many filters, one launch

Serving front-door (micro-batching dispatcher): ``repro.launch.serve_qr``.
Kernel: ``repro.kernels.ggr_update`` (grid over batch, VMEM-resident sweep).
Docs: ``docs/solvers.md`` (API guide), ``docs/architecture.md`` (paper map).
"""
from .kalman import (
    KalmanState,
    KalmanTrajectory,
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
from .lstsq import (
    LstsqResult,
    RecursiveLS,
    RLSState,
    ggr_lstsq,
    lstsq_route,
    solve_triangular,
)
from .qr_update import (
    qr_append_rows,
    qr_append_rows_batched,
    qr_downdate_row,
    qr_rank1_update,
)

__all__ = [
    "KalmanState",
    "KalmanTrajectory",
    "LstsqResult",
    "RLSState",
    "RecursiveLS",
    "ggr_lstsq",
    "info_sqrt",
    "kf_cov",
    "kf_filter",
    "kf_init",
    "kf_mean",
    "kf_observe",
    "kf_predict",
    "kf_smooth",
    "kf_step",
    "kf_step_batched",
    "lstsq_route",
    "qr_append_rows",
    "qr_append_rows_batched",
    "qr_downdate_row",
    "qr_rank1_update",
    "solve_triangular",
    "whiten_measurement",
]
