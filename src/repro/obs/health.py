"""Numerical-health gauges for factor states (ROADMAP item 5's sensors).

Two signals, both cheap relative to the solves they watch:

* **Condition estimate** — min/max ``|diag(R)|`` of a triangular factor,
  plus a real 2-norm condition estimate (``condition_estimate``: a few
  power-iteration rounds for ``smax`` and inverse-iteration rounds through
  triangular solves for ``smin``, host f64), a far tighter watch than the
  bare ``max|r_ii| / min|r_ii|`` ratio, which only *lower-bounds*
  ``cond_2(R)``.  For batched factors the per-member diag ratio screens
  for the worst member, and only that one pays the O(n^2-per-iter)
  estimate.  The jit-safe incremental
  variant for streaming states lives in ``repro.ranks.monitor``.
* **Orthogonality loss** — ``max |Q^T Q - I|`` with ``Q = A R^{-1}``
  reconstructed implicitly (Q is never formed by the GGR paths, so this is
  the only way to audit it).  It is O(m n^2) — as expensive as the solve —
  so it is *sampled*: ``maybe_sample_orthogonality`` fires every
  ``REPRO_OBS_ORTHO_EVERY``-th eligible call (default 16).

Tracer-safety: all recorders silently skip when handed tracers (solvers are
routinely vmapped/jitted; only eager calls with concrete arrays can report
host-side gauges — batched serving records from its concrete flush results
instead).  Everything no-ops under the null registry, before any device
transfer happens.
"""
from __future__ import annotations

import itertools
import os

import numpy as np

from ._state import _active

__all__ = [
    "condition_estimate",
    "factor_health",
    "orthogonality_loss",
    "ortho_tolerance",
    "maybe_sample_orthogonality",
]

_ortho_clock = itertools.count()


def _concrete(*arrays) -> bool:
    import jax

    return not any(isinstance(a, jax.core.Tracer) for a in arrays)


def condition_estimate(R, iters: int = 6) -> float:
    """2-norm condition estimate of one triangular factor (host f64).

    A few rounds of power iteration on ``R^T R`` estimate ``smax``; inverse
    iteration (two triangular solves per round) estimates ``smin``; the
    report is ``||R v_max|| / ||R v_min||``.  Deterministic alternating-ramp
    seeds (LINPACK-style), so the gauge is reproducible.  Converges from
    below, so it slightly *under*-estimates — still a far tighter watch
    than the old ``max|r_ii|/min|r_ii|`` lower bound, which can be off by
    orders of magnitude on graded spectra.  An exactly-zero pivot returns
    ``inf`` directly (the factor is singular; no iteration needed)."""
    Rf = np.triu(np.asarray(R, dtype=np.float64))
    n = Rf.shape[-1]
    if Rf.shape[0] > n:
        Rf = Rf[:n]
    if n == 0:
        return float("nan")
    if not np.all(np.abs(np.diag(Rf)) > 0.0):
        return float(np.inf)
    i = np.arange(n)
    v = np.where(i % 2 == 0, 1.0, -1.0) * (1.0 + i / n)
    vmax = v / np.linalg.norm(v)
    vmin = vmax[::-1].copy()
    for _ in range(iters):
        w = Rf.T @ (Rf @ vmax)
        vmax = w / max(np.linalg.norm(w), np.finfo(np.float64).tiny)
        y = np.linalg.solve(Rf.T, vmin)
        z = np.linalg.solve(Rf, y)
        vmin = z / max(np.linalg.norm(z), np.finfo(np.float64).tiny)
    smax = np.linalg.norm(Rf @ vmax)
    smin = np.linalg.norm(Rf @ vmin)
    return float(smax / max(smin, np.finfo(np.float64).tiny))


def factor_health(R, layer: str, **labels) -> None:
    """Record min/max ``|diag(R)|`` + condition gauges for a triangular
    factor (or a (B, n, n) batch of them — the batch-wide excursion is what
    serving wants).  ``<layer>.r_cond_estimate`` carries the
    ``condition_estimate`` value (batches: the member with the worst diag
    ratio is estimated — the screen is free, the estimate is O(n^2)/iter).
    Skips under tracing or the null registry."""
    reg = _active()
    if not reg.enabled or not _concrete(R):
        return
    Rf = np.asarray(R, dtype=np.float64)
    diag = np.abs(np.diagonal(Rf, axis1=-2, axis2=-1))
    if diag.size == 0:
        return
    dmin, dmax = float(diag.min()), float(diag.max())
    reg.gauge(f"{layer}.r_diag_min", **labels).set(dmin)
    reg.gauge(f"{layer}.r_diag_max", **labels).set(dmax)
    if Rf.ndim == 3:
        with np.errstate(divide="ignore"):
            ratios = np.where(diag.min(axis=-1) > 0.0,
                              diag.max(axis=-1) / diag.min(axis=-1), np.inf)
        Rf = Rf[int(np.argmax(ratios))]
    cond = condition_estimate(Rf)
    reg.gauge(f"{layer}.r_cond_estimate", **labels).set(cond)


def orthogonality_loss(A, R) -> float:
    """``max |Q^T Q - I|`` for the implicit ``Q = A R^{-1}`` (float64 host
    computation; A is (m, n), R the (n, n) upper factor of its QR — a full
    (m, n) triangularized matrix is cut to its top (n, n) block)."""
    Af = np.asarray(A, dtype=np.float64)
    Rf = np.triu(np.asarray(R, dtype=np.float64))
    n = Rf.shape[-1]
    if Rf.shape[0] > n:
        Rf = Rf[:n]
    # Q^T = R^{-T} A^T: one triangular-ish solve, no explicit inverse
    Qt = np.linalg.solve(Rf.T, Af.T)
    G = Qt @ Qt.T
    return float(np.abs(G - np.eye(n)).max())


def ortho_tolerance(n: int, dtype) -> float:
    """Alarm threshold for ``orthogonality_loss``: ``64 * n * eps(dtype)``.

    Scaled by the *compute* dtype's machine epsilon so the same audit is
    honest across precision policies — a loss of 1e-3 is an alarm for an
    f32 factorization (eps ~1.2e-7) but entirely healthy for bf16
    (eps ~7.8e-3).  The constant 64 gives ~10x headroom over losses
    observed on well-conditioned problems."""
    import jax.numpy as jnp

    return 64.0 * float(n) * float(jnp.finfo(jnp.dtype(dtype)).eps)


def maybe_sample_orthogonality(A, R, layer: str, *, dtype=None,
                               **labels) -> float | None:
    """Sampled orthogonality audit: every N-th eligible call (N from
    ``REPRO_OBS_ORTHO_EVERY``, default 16) computes ``orthogonality_loss``
    and records it as ``<layer>.orthogonality_loss``; returns the loss when
    sampled, else None.

    Each sample is judged against ``ortho_tolerance(n, dtype)`` (``dtype``
    defaults to R's own dtype — pass the policy's compute dtype when R was
    down-cast for storage); breaches increment
    ``<layer>.orthogonality_alarms``."""
    reg = _active()
    if not reg.enabled or not _concrete(A, R):
        return None
    every = int(os.environ.get("REPRO_OBS_ORTHO_EVERY", "16"))
    tick = next(_ortho_clock)
    if every > 1 and tick % every:
        return None
    loss = orthogonality_loss(A, R)
    reg.gauge(f"{layer}.orthogonality_loss", **labels).set(loss)
    reg.counter(f"{layer}.orthogonality_samples", **labels).inc()
    tol = ortho_tolerance(np.asarray(R).shape[-1],
                          np.asarray(R).dtype if dtype is None else dtype)
    reg.gauge(f"{layer}.orthogonality_tolerance", **labels).set(tol)
    if loss > tol:
        reg.counter(f"{layer}.orthogonality_alarms", **labels).inc()
    return loss
