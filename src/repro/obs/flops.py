"""Flop accounting: achieved-GFLOP/s per dispatch from the analytic models.

The paper's performance claims are flops-per-watt claims, so the repo's own
trajectory metric must be *achieved* flop rate, not speedup-over-self.  The
flop numbers here come from the ``core.counts`` analytic multiplication
models (eqs. 3-5 and their rectangular/append generalizations,
``ggr_sweep_mults`` / ``ggr_append_mults``) — the same models
``bench_counts`` validates against measured jaxpr counts — converted with
``mults_to_flops`` (each macro-op multiplication pairs with one add in the
DET2/FMA grids).

``record_dispatch`` is the single chokepoint every instrumented dispatch
site funnels through: it observes ``<layer>.dispatch_seconds`` and bumps
``<layer>.dispatches`` and the per-dtype ``<layer>.flops_total``; where the
seconds are a blocked device time (``rate=True``) it also observes
``<layer>.achieved_gflops``, one sample per dispatch.  The serving engine
passes ``rate=False``: its seconds run from the batch close to the pump
seeing the chunk ready, host time that a flop rate would misread.

``repro.core.counts`` is imported lazily: ``core.blocked`` imports
``repro.obs`` at module scope, and the ``repro.core`` package init imports
``core.blocked`` — a top-level counts import here would close that cycle.
"""
from __future__ import annotations

from ._state import _active

__all__ = [
    "ggr_sweep_flops",
    "ggr_append_flops",
    "lstsq_flops",
    "flops_by_dtype",
    "record_dispatch",
]


def ggr_sweep_flops(m: int, w: int, n_pivots: int | None = None) -> int:
    """Flops of one dense GGR triangularization sweep on an (m, w) matrix."""
    from repro.core.counts import ggr_sweep_mults, mults_to_flops

    return mults_to_flops(ggr_sweep_mults(m, w, n_pivots))


def ggr_append_flops(n: int, p: int, w: int) -> int:
    """Flops of one compact active-set row-append sweep: (n, n) triangular R
    plus p appended rows, total width w (>= n when rhs columns ride along)."""
    from repro.core.counts import ggr_append_mults, mults_to_flops

    return mults_to_flops(ggr_append_mults(n, p, w))


def lstsq_flops(m: int, n: int, k: int) -> int:
    """Flops of one augmented least-squares solve: the dense sweep over
    ``[A | b]`` (m, n+k) with n pivots plus the (n^2 k)-flop back solve."""
    return ggr_sweep_flops(m, n + k, n) + n * n * k


def flops_by_dtype(flops: float, compute_dtype="float32",
                   accum_dtype=None) -> dict:
    """Split a total dispatch flop count by execution dtype.

    Thin adapter over :func:`repro.core.counts.flops_by_dtype` (which works
    in model *mults* = flops/2): multiplies run at the tile compute dtype,
    their paired adds at the accumulator dtype, values sum to ``flops``."""
    from repro.core.counts import flops_by_dtype as _split

    return _split(int(flops) // 2, compute_dtype, accum_dtype)


def record_dispatch(layer: str, flops: float, seconds: float, *,
                    by_dtype: dict | None = None, rate: bool = True,
                    **labels) -> None:
    """Record one timed dispatch: duration (and achieved GFLOP/s) histograms.

    ``rate=True`` says ``seconds`` came from a blocked timer
    (``obs.device_timer``), so ``flops / seconds`` is a rate; pass
    ``rate=False`` for any other clock.  ``by_dtype`` (``{dtype_name:
    flops}``, e.g. from :func:`flops_by_dtype`) additionally bumps
    per-dtype ``<layer>.flops_total`` counters so mixed-precision dispatches
    do not launder bf16 multiplies as f32 throughput.  No-op under the null
    registry.
    """
    reg = _active()
    if not reg.enabled:
        return
    reg.counter(f"{layer}.dispatches", **labels).inc()
    reg.histogram(f"{layer}.dispatch_seconds", **labels).observe(seconds)
    if rate and seconds > 0.0:
        reg.histogram(f"{layer}.achieved_gflops", **labels).observe(
            flops / seconds / 1e9)
    if by_dtype:
        for dt, f in by_dtype.items():
            reg.counter(f"{layer}.flops_total", dtype=str(dt),
                        **labels).inc(float(f))
