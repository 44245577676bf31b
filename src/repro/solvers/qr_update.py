"""QR up/downdating of a stored ``(R, d)`` least-squares state.

Givens rotations are *the* canonical tool for factorization updating — this
module expresses all three update kinds in the paper's macro-op vocabulary
(suffix/prefix sums + elementwise DET2 FMA), so the same fused Pallas path
that accelerates factorization accelerates streaming updates:

* ``qr_append_rows`` — add p observation rows: one GGR sweep over the stacked
  ``[R | d; U | Y]`` matrix (``ggr_triangularize``); the zero gap between R's
  diagonal and the appended rows costs nothing extra in the fused form.
* ``qr_downdate_row`` — remove a row (sliding window).  The LINPACK ``dchdd``
  rotation cascade collapses to closed form: with ``q = R^{-T} u`` and
  ``t_k = sqrt(alpha^2 + sum_{j>=k} q_j^2)`` (a *seeded suffix norm*,
  ``alpha^2 = 1 - |q|^2``), the downdated rows are exactly a DET2 grid

      R'_k = l_k R_k - k_k S_k,   k_k = q_k/(t_k t_{k+1}),  l_k = t_{k+1}/t_k

  with S the exclusive suffix dots of q against R's rows — the same
  coefficients as ``core.ggr`` with the annihilation sign flipped.  The rhs
  downdate is a prefix-dot recurrence (derivation in ``_downdate_core``).
* ``qr_rank1_update`` — symmetric Gram update R^T R + w·v v^T: dispatches to
  append (w >= 0) or downdate (w < 0) with the scaled row sqrt(|w|)·v.

State convention: R upper triangular with **non-negative diagonal** (GGR
produces this; downdating re-normalizes), d = Q^T b restricted to the top n
rows.  Invariants maintained: ``R^T R = sum_i u_i u_i^T`` and
``R^T d = sum_i u_i y_i`` over the observation stream.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.ggr import _eps_for, ggr_triangularize

__all__ = [
    "qr_append_rows",
    "qr_append_rows_batched",
    "qr_downdate_row",
    "qr_rank1_update",
]


def _tri_solve_lower(L: jax.Array, B: jax.Array) -> jax.Array:
    """Forward substitution L x = B for lower-triangular L; B is (n, k).

    Row-sequential scan (n steps of an n·k DOT each) — the DOT-chain dual of
    the suffix-sum sweeps used everywhere else; no LAPACK dependency.  The
    DOTs ask for full f32 precision: the TPU's default is one bf16 pass.
    """
    n = L.shape[0]
    f32 = jnp.promote_types(L.dtype, jnp.float32)
    La, Ba = L.astype(f32), B.astype(f32)
    eps = _eps_for(f32)
    diag = jnp.diagonal(La)
    safe_diag = jnp.where(jnp.abs(diag) > eps, diag, 1.0)

    def body(i, X):
        # x_i = (b_i - L[i, :] @ x) / L_ii ; x_j = 0 for j >= i so the full
        # row dot only picks up already-solved entries.
        s = jnp.matmul(La[i], X, precision=jax.lax.Precision.HIGHEST)
        xi = (Ba[i] - s) / safe_diag[i]
        return X.at[i].set(xi)

    X = jax.lax.fori_loop(0, n, body, jnp.zeros_like(Ba))
    return X.astype(B.dtype)


def _stack_update(R, U, d, Y):
    """Stack [R | d; U | Y] for the augmented append sweep (rhs optional)."""
    if d is None:
        return jnp.concatenate([R, U], axis=0)
    top = jnp.concatenate([R, d], axis=1)
    bot = jnp.concatenate([U, Y], axis=1)
    return jnp.concatenate([top, bot], axis=0)


def qr_append_rows(R: jax.Array, U: jax.Array, d: jax.Array | None = None,
                   Y: jax.Array | None = None):
    """Update R (and rhs state d) for p appended observation rows U (and Y).

    Pure-JAX reference path: one GGR sweep over the (n+p, n[+k]) stacked
    matrix.  Returns R' or (R', d').  Cost O(n^2 (n+p)) vs O(n^2 m) for
    re-factorizing the full m-row history — independent of stream length.
    """
    n = R.shape[1]
    if (d is None) != (Y is None):
        raise ValueError("pass both d and Y, or neither")
    X = ggr_triangularize(_stack_update(R, U, d, Y), n)
    R_new = jnp.triu(X[:n, :n])
    if d is None:
        return R_new
    return R_new, X[:n, n:]


def _update_stacked(stacked: jax.Array, n: int, backend: str,
                    interpret: bool | None, block_b: int,
                    precision=None) -> jax.Array:
    """Single-device batched sweep over stacked (B, n+p, w) problems.

    ``precision`` must already be resolved (a ``kernels.Precision`` or None)
    so it stays hashable through ``_sharded_update_fn``'s lru_cache.  The
    reference backend casts to the compute dtype and relies on
    ``ggr_triangularize``'s own float32-promoted accumulation.  Spanned as
    ``repro/kernels/ggr_update``: the launch of the update kernel.
    """
    with obs.span("repro/kernels/ggr_update"):
        if backend == "reference":
            if precision is not None:
                stacked = stacked.astype(precision.compute)
            return jax.vmap(lambda X: ggr_triangularize(X, n))(stacked)
        if backend != "pallas":
            raise ValueError(f"unknown backend {backend!r}")
        from repro.kernels import batched_update  # deferred: layer edge

        return batched_update(stacked, n_pivots=n, block_b=block_b,
                              interpret=interpret, precision=precision)


@functools.lru_cache(maxsize=32)
def _sharded_update_fn(mesh, mesh_axis: str, n: int, backend: str,
                       interpret: bool | None, block_b: int,
                       precision=None):
    """jit'd shard_map dispatch, cached per (mesh, schedule) so repeated
    flushes of the same group shape reuse one executable instead of
    re-tracing the mapped kernel every call (Mesh is hashable).  Bounded:
    an unbounded cache would pin every ``Mesh`` a long-lived server ever
    cycled through (the serving layer's per-server ``ExecutableCache`` in
    ``repro.serve.dispatch`` is the primary cache; this is the backstop)."""
    from jax.sharding import PartitionSpec as P

    # check_vma off: pallas_call has no replication rule; the map is
    # trivially element-wise over shards (no collectives), so safe.
    return jax.jit(jax.shard_map(
        lambda x: _update_stacked(x, n, backend, interpret, block_b,
                                  precision=precision),
        mesh=mesh,
        in_specs=P(mesh_axis),
        out_specs=P(mesh_axis),
        check_vma=False,
    ))


def qr_append_rows_batched(R: jax.Array, U: jax.Array,
                           d: jax.Array | None = None,
                           Y: jax.Array | None = None,
                           *, backend: str = "pallas",
                           interpret: bool | None = None,
                           block_b: int = 8,
                           mesh=None, mesh_axis: str = "batch",
                           precision=None):
    """Batch of independent row-append updates in one fused kernel launch.

    R: (B, n, n) upper triangular, U: (B, p, n), optional d: (B, n, k),
    Y: (B, p, k).  backend "pallas" runs the batch-tiled VMEM-resident kernel
    (whose compact active-set schedule *relies* on R's triangularity);
    "reference" vmaps the pure-JAX stacked sweep.  Both produce the unique
    non-negative-diagonal factor, agreeing to roundoff.

    Sharded mode: pass a ``jax.sharding.Mesh`` and the name of its batch axis
    (default "batch") to split the batch over the mesh with one kernel
    launch per shard (``jax.shard_map``).  The batch is zero-padded up to
    ``shards x block_b`` — every shard gets an identical, full-granularity
    grid — and the padding is sliced off afterwards, so any batch size
    (including prime sizes and B < shards) is legal and numerically
    identical to the single-device dispatch.
    """
    n = R.shape[2]
    if (d is None) != (Y is None):
        raise ValueError("pass both d and Y, or neither")
    if precision is not None:
        from repro.kernels import resolve_precision

        # resolved here so the cached sharded path sees only hashable values
        precision = resolve_precision(precision)
    stacked = jax.vmap(_stack_update, in_axes=(0, 0, 0 if d is not None else None,
                                              0 if Y is not None else None))(R, U, d, Y)
    if mesh is None:
        out = _update_stacked(stacked, n, backend, interpret, block_b,
                              precision=precision)
    else:
        from repro.kernels import pad_batch

        B = stacked.shape[0]
        shards = mesh.shape[mesh_axis]
        padded = pad_batch(stacked, shards * block_b)
        fn = _sharded_update_fn(mesh, mesh_axis, n, backend, interpret,
                                block_b, precision)
        with obs.span("repro/kernels/ggr_update"):
            out = fn(padded)
        out = out[:B]
    R_new = jnp.triu(out[:, :n, :n])
    if d is None:
        return R_new
    return R_new, out[:, :n, n:]


def _downdate_core(R, u, d, y, guard=None):
    """Closed-form Givens downdate (macro-op form).  See module docstring.

    Solving R^T q = u places the removed row in the rotation cascade's last
    column; the cascade's compound coefficients telescope into GGR's own
    (k, l) form because prod_{i<j} c_i = t_j / t_0.  The rhs recurrence
    zeta_k = (zeta_{k-1} - s_k d_k)/c_k telescopes the same way into a
    prefix dot:  zeta_{k-1} = (t_0 y - sum_{j<k} q_j d_j) / t_k.

    ``guard`` (a ``repro.ranks.DowndateGuard``) intercepts the hyperbolic
    blow-up: ``alpha^2 = 1 - ||q||^2`` measures the distance to the rank
    cliff, and the guard damps the removed row, refuses the downdate, or
    raises before the cascade divides by a vanishing ``alpha``.
    """
    n = R.shape[0]
    f32 = jnp.promote_types(R.dtype, jnp.float32)
    Ra = R.astype(f32)
    qv = _tri_solve_lower(Ra.T, u.astype(f32)[:, None])[:, 0]
    eps = _eps_for(f32)
    triggered = None
    if guard is not None:
        # lazy: solvers <-> ranks would otherwise be a load-time cycle
        from repro.ranks.monitor import _record_guard_trigger, guard_downdate_q

        guard.validate()
        qq0 = qv @ qv
        if guard.mode == "raise" and not isinstance(qq0, jax.core.Tracer):
            if float(1.0 - qq0) < guard.tau:
                raise FloatingPointError(
                    f"downdate rejected by guard: alpha^2 = 1 - ||R^-T u||^2 "
                    f"= {float(1.0 - qq0):.3e} < tau = {guard.tau:.1e} — "
                    "removing this row would push the factor across the rank "
                    "cliff.  Re-factorize the window, or use "
                    "DowndateGuard(mode='damp'/'refuse').")
        qv, triggered = guard_downdate_q(qv, guard)
        _record_guard_trigger(triggered)
    alpha2 = jnp.maximum(1.0 - qv @ qv, eps)  # <=0 means u not in the factorization
    suff = jnp.cumsum((qv * qv)[::-1])[::-1]
    t = jnp.sqrt(alpha2 + suff)  # seeded suffix norms, t_n = alpha
    t_next = jnp.concatenate([t[1:], jnp.sqrt(alpha2)[None]])
    kk = qv / (t * t_next)
    ll = t_next / t

    P = jnp.cumsum((qv[:, None] * Ra)[::-1], axis=0)[::-1]  # inclusive suffix dots
    S = jnp.concatenate([P[1:], jnp.zeros_like(P[:1])], axis=0)  # exclusive
    R_new = ll[:, None] * Ra - kk[:, None] * S  # DET2 grid, annihilation sign flipped

    d_new = None
    if d is not None:
        da, ya = d.astype(f32), y.astype(f32)
        Pd = jnp.cumsum(qv[:, None] * da, axis=0)
        Pd_excl = jnp.concatenate([jnp.zeros_like(Pd[:1]), Pd[:-1]], axis=0)
        zeta_prev = (t[0] * ya[None, :] - Pd_excl) / t[:, None]
        d_new = (t[:, None] * da - qv[:, None] * zeta_prev) / t_next[:, None]

    # canonical non-negative diagonal (makes downdate the exact inverse of
    # append, which always produces sigma·t >= 0 pivots)
    sg = jnp.sign(jnp.diagonal(R_new))
    sg = jnp.where(sg == 0, 1.0, sg)
    R_new = jnp.triu(sg[:, None] * R_new)
    if d_new is not None:
        d_new = sg[:, None] * d_new
    if triggered is not None and guard.mode in ("refuse", "raise"):
        # refuse (and raise-under-tracing, which cannot throw): keep the
        # original state when the guard fired — a jit-safe select
        R_new = jnp.where(triggered, Ra, R_new)
        if d_new is not None:
            d_new = jnp.where(triggered, d.astype(d_new.dtype), d_new)
    return R_new.astype(R.dtype), None if d is None else d_new.astype(R.dtype)


def qr_downdate_row(R: jax.Array, u: jax.Array, d: jax.Array | None = None,
                    y: jax.Array | None = None, *, guard=None):
    """Remove observation row (u, y) from the state — sliding-window forget.

    ``u`` must be a row previously incorporated into R (a downdate of a row
    not in the span is clamped, not detected).  Returns R' or (R', d').

    ``guard``: an optional ``repro.ranks.DowndateGuard``.  Downdating is
    hyperbolic — it removes information — and a row that carries (nearly)
    all remaining mass in some direction drives ``alpha^2 = 1 - ||R^-T u||^2``
    to zero, after which the factor is numerically singular.  The guard
    bounds ``alpha^2`` from below by ``tau``: ``mode="damp"`` shrinks the
    removed row to sit exactly at the floor, ``"refuse"`` keeps the state
    unchanged, ``"raise"`` throws a ``FloatingPointError`` diagnostic
    (eager calls only; under tracing it degrades to refuse).
    """
    if (d is None) != (y is None):
        raise ValueError("pass both d and y, or neither")
    R_new, d_new = _downdate_core(R, u, d, y, guard=guard)
    if d is None:
        return R_new
    return R_new, d_new


def qr_rank1_update(R: jax.Array, v: jax.Array, weight: jax.Array | float,
                    d: jax.Array | None = None, y: jax.Array | None = None,
                    *, guard=None):
    """Symmetric rank-1 Gram update: R'^T R' = R^T R + weight·v v^T.

    With rhs state: R'^T d' = R^T d + weight·v y.  ``weight >= 0`` appends the
    scaled row sqrt(w)·v; ``weight < 0`` downdates it (branch via lax.cond so
    the sign may be a traced value — e.g. an exponential-forgetting schedule).
    ``guard`` protects the downdate branch (see ``qr_downdate_row``); avoid
    ``mode="raise"`` here — the branch runs under ``lax.cond`` tracing, where
    raise degrades to refuse.
    """
    if (d is None) != (y is None):
        raise ValueError("pass both d and y, or neither")
    w = jnp.asarray(weight, dtype=R.dtype)
    s = jnp.sqrt(jnp.abs(w))
    u = s * v

    if d is None:
        def up(_):
            return qr_append_rows(R, u[None, :])

        def down(_):
            return qr_downdate_row(R, u, guard=guard)

        return jax.lax.cond(w >= 0, up, down, None)

    yr = (s * y)[None, :]

    def up(_):
        return qr_append_rows(R, u[None, :], d, yr)

    def down(_):
        return qr_downdate_row(R, u, d, yr[0], guard=guard)

    return jax.lax.cond(w >= 0, up, down, None)
