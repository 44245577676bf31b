"""Distributed GGR QR — the REDEFINE K x K tile-array scheme mapped to a JAX mesh.

Three entry points:

* ``distributed_ggr_qr_1d`` — 1-D block-cyclic panel QR over a mesh axis
  (the paper's scheme-1: panel factor on the owning CE, factors broadcast over
  the NoC → here a masked ``psum`` broadcast over ICI, trailing updates local).

* ``tsqr`` — communication-avoiding tall-skinny QR: local GGR factor + a
  binary ``ppermute`` reduction tree of stacked-R GGR factorizations.  This is
  a *beyond-paper* optimization (CAQR); the paper's TSQRT tile op is its
  two-input reduction step.

* ``distributed_orthogonalize`` — Q = A · R⁻¹ from ``tsqr`` (+ one optional
  refinement) — the primitive the Orthant optimizer uses for model-sharded
  weight matrices.

All functions are written against a single logical axis name so callers can
pass any mesh axis (or a flattened ("data","model") product axis).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .blocked import ggr_geqrt
from .ggr import apply_ggr_factors, ggr_column_step_at, ggr_factor_column

__all__ = [
    "distributed_ggr_qr_1d",
    "tsqr",
    "distributed_orthogonalize",
]


def _panel_factor_local(panel: jax.Array, global_row0: int = 0):
    """Factor an (m x b) panel; return (R_panel, V, T) compact GGR factors."""
    m, b = panel.shape
    steps = min(m - 1, b)

    def body(c, carry):
        X, V, T = carry
        f = ggr_factor_column(X, c)
        X = ggr_column_step_at(X, c)
        V = V.at[:, c].set(f.v)
        T = T.at[:, c].set(f.t)
        return X, V, T

    V0 = jnp.zeros((m, b), panel.dtype)
    T0 = jnp.zeros((m, b), panel.dtype)
    R, V, T = jax.lax.fori_loop(0, steps, body, (panel, V0, T0))
    return R, V, T


def cyclic_perm(n: int, nP: int, panel: int):
    """Permutation: logical column order -> block-cyclic storage order.

    Storage layout = concat over devices d of panels (d, d+nP, d+2nP, ...),
    i.e. device d owns logical panels {p : p % nP == d} (paper scheme-1 load
    balancing: as the factorization shrinks, work stays spread across CEs).
    Returns (perm, inv) index arrays with ``stored = logical[:, perm]``.
    """
    npanels = n // panel
    order = []
    for d in range(nP):
        for p in range(d, npanels, nP):
            order.extend(range(p * panel, (p + 1) * panel))
    import numpy as _np

    perm = _np.asarray(order)
    inv = _np.empty_like(perm)
    inv[perm] = _np.arange(n)
    return perm, inv


def distributed_ggr_qr_1d(
    A: jax.Array, mesh: Mesh, axis: str, panel: int = 32, layout: str = "logical"
):
    """QR of an (m, n) matrix, columns block-cyclic over mesh axis ``axis``.

    ``layout="logical"``: ``A`` is in logical column order (any sharding); the
    cyclic redistribution happens internally (one gather each way) and R comes
    back in logical order.  ``layout="cyclic"``: ``A`` is ALREADY stored
    block-cyclic and R is returned cyclic — skips both permutation gathers,
    which measure as ~half the total collective bytes at N=8k/P=64 (§Perf C2);
    use when producer and consumer both live in cyclic layout (e.g. the
    Orthant optimizer state).

    Per panel p: owner (p mod P) factors its local panel in one fused GGR
    sweep, the compact factors (V, T) are broadcast with one masked all-reduce
    (the NoC broadcast of the paper), every device updates its own later
    panels — compute parallel, communication O(m·panel) per step.
    """
    m, n = A.shape
    nP = mesh.shape[axis]
    assert n % panel == 0, "pad columns to a panel multiple"
    npanels = n // panel
    assert npanels % nP == 0, "panel count must divide evenly for SPMD shapes"
    local_panels = npanels // nP
    perm, inv = cyclic_perm(n, nP, panel)

    def kernel(Al):  # Al: (m, local_panels*panel) on each device
        me = jax.lax.axis_index(axis)

        def step(p, Al):
            owner = p % nP
            slot = p // nP
            pivot0 = p * panel  # global pivot row of this panel

            local = jax.lax.dynamic_slice(Al, (0, slot * panel), (m, panel))
            Rp, V, T = _panel_factor_local_masked(local, pivot0)
            is_owner = (me == owner).astype(Al.dtype)
            # NoC broadcast ≡ masked all-reduce (owner contributes, rest zero)
            V = jax.lax.psum(V * is_owner, axis)
            T = jax.lax.psum(T * is_owner, axis)
            # owner writes back its factored panel
            Al = jax.lax.cond(
                me == owner,
                lambda Al: jax.lax.dynamic_update_slice(Al, Rp, (0, slot * panel)),
                lambda Al: Al,
                Al,
            )
            # every device updates its local panels that come after panel p
            def upd_slot(s, Al):
                gp = s * nP + me  # global panel index of local slot s
                C = jax.lax.dynamic_slice(Al, (0, s * panel), (m, panel))
                C2 = _apply_panel_factors_pivot(V, T, C, pivot0)
                C2 = jnp.where(gp > p, C2, C)
                return jax.lax.dynamic_update_slice(Al, C2, (0, s * panel))

            return jax.lax.fori_loop(0, local_panels, upd_slot, Al)

        return jax.lax.fori_loop(0, npanels, step, Al)

    def _panel_factor_local_masked(local, pivot0):
        steps = panel

        def body(c, carry):
            X, V, T = carry
            f = ggr_factor_column(X, c, pivot0 + c)
            X = ggr_column_step_at(X, c, pivot0 + c)
            V = V.at[:, c].set(f.v)
            T = T.at[:, c].set(f.t)
            return X, V, T

        V0 = jax.lax.pcast(jnp.zeros((m, panel), local.dtype), (axis,),
                           to="varying")
        T0 = jax.lax.pcast(jnp.zeros((m, panel), local.dtype), (axis,),
                           to="varying")
        return jax.lax.fori_loop(0, steps, body, (local, V0, T0))

    fn = jax.shard_map(
        kernel, mesh=mesh, in_specs=P(None, axis), out_specs=P(None, axis)
    )
    if layout == "cyclic":
        return fn(A)  # caller owns the layout; no permutation collectives
    stored = jax.jit(
        lambda X: X[:, perm],
        out_shardings=jax.sharding.NamedSharding(mesh, P(None, axis)),
    )(A)
    R_stored = fn(stored)
    return jax.jit(lambda X: jnp.triu(X[:, inv]))(R_stored)


def _apply_panel_factors_pivot(V, T, C, pivot0):
    from .ggr import GGRFactors

    b = V.shape[1]

    def body(c, C):
        return apply_ggr_factors(GGRFactors(v=V[:, c], t=T[:, c]), C, pivot0 + c)

    return jax.lax.fori_loop(0, b, body, C)


# ---------------------------------------------------------------------------
# TSQR (communication-avoiding tall-skinny QR) — beyond-paper optimization
# ---------------------------------------------------------------------------
def tsqr_local_r(A_local: jax.Array) -> jax.Array:
    """Local GGR factor of the row-shard; returns the (n x n) R factor."""
    m, n = A_local.shape
    R, _ = ggr_geqrt(A_local)
    return R[:n, :]


def tsqr(A: jax.Array, mesh: Mesh, axis: str) -> jax.Array:
    """All-reduce-style TSQR: returns the global R (replicated on every device).

    A is (m, n) row-sharded over ``axis``.  log2(P) rounds; round r exchanges
    R factors with the neighbor 2^r away (ppermute) and re-factors the stacked
    2n x n — the paper's TSQRT tile op as the reduction operator.
    """
    nP = mesh.shape[axis]
    assert nP & (nP - 1) == 0, "tsqr requires power-of-two axis size"
    rounds = nP.bit_length() - 1

    def kernel(Al):
        n = Al.shape[1]
        R = tsqr_local_r(Al)
        for r in range(rounds):
            stride = 1 << r
            perm_fwd = [(i, i ^ stride) for i in range(nP)]
            R_nbr = jax.lax.ppermute(R, axis, perm_fwd)
            me = jax.lax.axis_index(axis)
            lo = (me & stride) == 0
            top = jnp.where(lo, R, R_nbr)
            bot = jnp.where(lo, R_nbr, R)
            stacked = jnp.concatenate([top, bot], axis=0)
            Rs, _ = ggr_geqrt(stacked)
            R = Rs[:n, :]
        return R

    # After the reduction tree every device holds the same R; replication is
    # not statically inferable from ppermute, so disable the vma check.
    fn = jax.shard_map(
        kernel, mesh=mesh, in_specs=P(axis, None), out_specs=P(), check_vma=False
    )
    return fn(A)


def distributed_orthogonalize(
    A: jax.Array, mesh: Mesh, axis: str, eps: float = 1e-7, refine: bool = True
) -> jax.Array:
    """Orthonormalize columns of a row-sharded tall matrix: Q = A · R⁻¹.

    R from communication-avoiding GGR TSQR; triangular solve is local (R is
    replicated).  One optional re-orthogonalization pass ("twice is enough").
    Used by the Orthant optimizer for model-parallel parameters.
    """
    n = A.shape[1]

    def solve_q(Al, R):
        ct = jnp.promote_types(Al.dtype, jnp.float32)
        scale = jnp.max(jnp.abs(jnp.diagonal(R))) + jnp.asarray(1e-30, ct)
        Rs = (R + (eps * scale) * jnp.eye(n, dtype=R.dtype)).astype(ct)
        q = jax.scipy.linalg.solve_triangular(Rs, Al.astype(ct).T, lower=False, trans=1)
        return q.T.astype(Al.dtype)

    R1 = tsqr(A, mesh, axis)
    q = jax.shard_map(
        lambda Al, R: solve_q(Al, R),
        mesh=mesh,
        in_specs=(P(axis, None), P()),
        out_specs=P(axis, None),
    )(A, R1)
    if refine:
        R2 = tsqr(q, mesh, axis)
        q = jax.shard_map(
            lambda Al, R: solve_q(Al, R),
            mesh=mesh,
            in_specs=(P(axis, None), P()),
            out_specs=P(axis, None),
        )(q, R2)
    return q
