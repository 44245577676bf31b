"""Pallas GEQRT kernels: fused GGR panel factorization, VMEM-resident.

TPU co-design notes (the paper's RDP mapping, §4.2 / fig. 12):

* the whole (m, b) panel lives in VMEM for the entire factorization — the
  analogue of keeping the working set in the PE's Local Memory;
* per column: suffix norms (DOT-chain) + suffix dots + DET2 grid are all
  computed in ONE pass, i.e. the paper's merged UPDATE_ROW1/UPDATE schedule —
  no HBM round-trip between the 2-norm, k/l-vector and trailing updates;
* every value inside a kernel body is at least 2-D — rows on sublanes,
  columns on lanes.  Columns are read with a masked lane reduction, pivot
  rows with a masked sublane reduction (or a dynamic-row ref load in the
  update kernel), and written back with ``where``: no 1-D vectors, no
  one-hot matmuls and no dynamic lane slicing, none of which Mosaic lowers;
* the reverse cumulative sums use log2(m) shift-add doubling steps built
  from ``jnp.roll`` and a row mask.

Interpret mode (CPU) runs exactly the bodies that Mosaic compiles.

Two kernels:

``panel_factor_pallas``
    (R, V, T) for one (m, b) panel: the factored panel plus the compact GGR
    factors consumed by ``ggr_apply`` for trailing updates.

``batched_geqrt_pallas``
    Grid-batched dense GEQRT sweeps: a (B, t, w) batch of independent tiles,
    each triangularized in its first ``n_pivots`` columns while the remaining
    ``w - n_pivots`` columns ride along through the DET2 grids.  Riding an
    identity block turns each output into the tile's explicit transform Qt —
    the building block of the blocked driver's MXU schedule, where trailing
    updates are plain GEMMs with those small Qt tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import resolve_interpret, resolve_precision

__all__ = ["panel_factor_pallas", "batched_geqrt_pallas"]

_EPS = 1e-30


def _accum_dt(X: jax.Array, accum_dtype: str | None) -> jnp.dtype:
    """Accumulation dtype for a kernel body: ``accum_dtype`` or X's own.

    ``None`` keeps the historical behaviour — everything at tile dtype — so
    the uniform-precision path is bit-identical to the pre-precision kernels.
    """
    return X.dtype if accum_dtype is None else jnp.dtype(accum_dtype)


# Bytes of VMEM one grid step's tiles may plan for: the 16 MiB scoped
# default of a v5e TensorCore, less headroom for Mosaic's own scratch.
_VMEM_BUDGET = 12 * 2**20


def _fit_block(bb: int, io_rows: int, act_rows: int, width: int) -> int:
    """Halve ``bb`` until a grid step's tiles fit ``_VMEM_BUDGET``.

    Per problem: the (io_rows, width) in/out tiles, double-buffered, plus
    about a dozen (act_rows, width) temporaries of the column step, each
    padded to whole (8, 128) f32 vector tiles.  Grid tiling never changes
    a result — every problem is swept on its own.
    """
    lanes = -(-width // 128) * 128

    def tile(rows):
        return -(-rows // 8) * 8 * lanes * 4

    per_problem = 4 * tile(io_rows) + 12 * tile(act_rows)
    while bb > 1 and bb * per_problem > _VMEM_BUDGET:
        bb //= 2
    return bb


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis % len(shape))


def _revcumsum(x: jax.Array, axis: int = 0) -> jax.Array:
    """Reverse cumulative sum along ``axis``: log2(m) shift-add doubling
    steps, each a static ``jnp.roll`` masked to zero past the end."""
    m = x.shape[axis]
    idx = _iota(x.shape, axis)
    d = 1
    while d < m:
        # x[i] += x[i + d]  (zero beyond the end)
        x = x + jnp.where(idx < m - d, jnp.roll(x, -d, axis), 0)
        d *= 2
    return x


def _shift_up(x: jax.Array, axis: int) -> jax.Array:
    """``x[i + 1]`` along ``axis``, zero past the end."""
    m = x.shape[axis]
    return jnp.where(_iota(x.shape, axis) < m - 1, jnp.roll(x, -1, axis), 0)


def _extract_col(X: jax.Array, at_col: jax.Array) -> jax.Array:
    """The column of X (..., m, w) selected by ``at_col``, as (..., m, 1).

    A masked lane reduction: exact (one nonzero term per row) and free of
    the one-hot matmul and dynamic lane slice that Mosaic refuses.
    """
    return jnp.sum(jnp.where(at_col, X, 0), axis=-1, keepdims=True)


def _scaled_column(col: jax.Array, ad) -> tuple[jax.Array, jax.Array]:
    """(v / sigma, sigma): the active column scaled by its max-abs along the
    rows (safe-Givens, ref [26] of the paper); all update formulas are
    scale-invariant, so the trailing matrix needs no rescaling."""
    v = col.astype(ad)
    sigma = jnp.max(jnp.abs(v), axis=-2, keepdims=True)
    return v / jnp.where(sigma > 0, sigma, 1.0), sigma


def _column_step(X, v, t, pivot, rows):
    """One fused GGR column transform applied to X (..., m, w).

    ``v`` (..., m, 1) is the scaled column (zero above ``pivot``) and ``t``
    its suffix norms, both at accumulation dtype; ``rows`` is a row iota
    broadcastable against X.  Suffix dots, the pivot row's eq. 2 DOT and the
    DET2 grid below it are computed in one pass at v's dtype and stored at
    X's.  Returns ``(out, do_any, t_piv)``; ``do_any`` is False where the
    column was already zero, and ``out`` is then X unchanged.
    """
    cd = X.dtype
    Xa = X.astype(v.dtype)
    P = _revcumsum(v * Xa, axis=-2)  # inclusive suffix dots
    # exclusive suffix via shift (P - prod would cancel catastrophically)
    S = _shift_up(P, -2)
    t_next = _shift_up(t, -2)
    valid = t_next > _EPS
    safe_t = jnp.where(t > _EPS, t, 1.0)
    safe_tn = jnp.where(valid, t_next, 1.0)
    k = v / (safe_t * safe_tn)
    l = safe_tn / safe_t

    at_piv = rows == pivot
    t_piv = jnp.sum(jnp.where(at_piv, t, 0), axis=-2, keepdims=True)
    P_piv = jnp.sum(jnp.where(at_piv, P, 0), axis=-2, keepdims=True)
    do_any = t_piv > _EPS
    pivot_new = (P_piv / jnp.where(do_any, t_piv, 1.0)).astype(cd)

    # row i's DET2 result is the new row i + 1; it applies where t[i+1] > eps
    det2 = jnp.roll((k * S - l * Xa).astype(cd), 1, axis=-2)
    below = jnp.where(t > _EPS, det2, X)
    out = jnp.where(rows < pivot, X, jnp.where(at_piv, pivot_new, below))
    return jnp.where(do_any, out, X), do_any, t_piv


def _annihilated_col(col, rows, pivot, tp, do_any):
    """The exact new pivot column: ``tp`` (= sigma·t) at the pivot, zero
    below, untouched above — or ``col`` itself when nothing was rotated."""
    new = jnp.where(rows == pivot, tp.astype(col.dtype),
                    jnp.where(rows < pivot, col, 0))
    return jnp.where(do_any, new, col)


def _panel_kernel(a_ref, r_ref, v_ref, t_ref, *, pivot0: int,
                  accum_dtype: str | None = None):
    X = a_ref[...]
    m, b = X.shape
    ad = _accum_dt(X, accum_dtype)
    rows = _iota((m, 1), 0)
    cols = _iota((1, b), 1)

    def body(c, carry):
        X, V, T = carry
        at_col = cols == c
        piv = pivot0 + c
        col = _extract_col(X, at_col)
        v, sigma = _scaled_column(jnp.where(rows >= piv, col, 0), ad)
        t = jnp.sqrt(_revcumsum(v * v, axis=0))
        out, do_any, t_piv = _column_step(X, v, t, piv, rows)
        newcol = _annihilated_col(col, rows, piv, sigma * t_piv, do_any)
        X = jnp.where(at_col, newcol, out)
        V = jnp.where(at_col, v.astype(X.dtype), V)
        T = jnp.where(at_col, t.astype(X.dtype), T)
        return X, V, T

    V0 = jnp.zeros((m, b), X.dtype)
    T0 = jnp.zeros((m, b), X.dtype)
    R, V, T = jax.lax.fori_loop(0, b, body, (X, V0, T0))
    r_ref[...] = R
    v_ref[...] = V
    t_ref[...] = T


@functools.partial(jax.jit,
                   static_argnames=("pivot0", "interpret", "accum_dtype"))
def _panel_factor_call(panel: jax.Array, pivot0: int, interpret: bool,
                       accum_dtype: str | None = None):
    m, b = panel.shape
    kern = functools.partial(_panel_kernel, pivot0=pivot0,
                             accum_dtype=accum_dtype)
    out_shapes = (
        jax.ShapeDtypeStruct((m, b), panel.dtype),
        jax.ShapeDtypeStruct((m, b), panel.dtype),
        jax.ShapeDtypeStruct((m, b), panel.dtype),
    )
    return pl.pallas_call(
        kern,
        out_shape=out_shapes,
        in_specs=[pl.BlockSpec((m, b), lambda: (0, 0))],
        out_specs=(
            pl.BlockSpec((m, b), lambda: (0, 0)),
            pl.BlockSpec((m, b), lambda: (0, 0)),
            pl.BlockSpec((m, b), lambda: (0, 0)),
        ),
        interpret=interpret,
        name="ggr_panel_factor",
    )(panel)


def panel_factor_pallas(panel: jax.Array, pivot0: int = 0,
                        interpret: bool | None = None, precision=None):
    """Factor an (m, b) panel in one fused VMEM-resident Pallas kernel.

    ``interpret=None`` resolves via ``backend.default_interpret()`` — True
    only on CPU hosts, so TPU/GPU backends compile the Mosaic kernel.
    ``precision`` (``Precision`` / policy name / None) selects the tile
    compute dtype and the in-kernel accumulation dtype; ``None`` keeps the
    panel at its own dtype with same-width accumulation (legacy behaviour).
    """
    if precision is None:
        return _panel_factor_call(panel, pivot0, resolve_interpret(interpret))
    prec = resolve_precision(precision)
    return _panel_factor_call(panel.astype(prec.compute), pivot0,
                              resolve_interpret(interpret),
                              accum_dtype=prec.accum_dtype)


# ---------------------------------------------------------------------------
# Batched dense GEQRT sweeps (the blocked driver's tile kernel)
# ---------------------------------------------------------------------------
def _batched_geqrt_kernel(x_ref, o_ref, *, n_pivots: int,
                          accum_dtype: str | None = None):
    X = x_ref[...]  # (bb, t, w) — this grid step's tiles
    bb, t, w = X.shape
    ad = _accum_dt(X, accum_dtype)
    rows = _iota((1, t, 1), 1)
    cols = _iota((1, 1, w), 2)

    def body(c, X):
        at_col = cols == c
        col = _extract_col(X, at_col)  # (bb, t, 1)
        v, sigma = _scaled_column(jnp.where(rows >= c, col, 0), ad)
        ts = jnp.sqrt(_revcumsum(v * v, axis=1))
        out, do_any, t_piv = _column_step(X, v, ts, c, rows)
        newcol = _annihilated_col(col, rows, c, sigma * t_piv, do_any)
        return jnp.where(at_col, newcol, out)

    o_ref[...] = jax.lax.fori_loop(0, n_pivots, body, X)


@functools.partial(jax.jit, static_argnames=("n_pivots", "block_b",
                                             "interpret", "accum_dtype"))
def _batched_geqrt_call(tiles: jax.Array, n_pivots: int, block_b: int,
                        interpret: bool, accum_dtype: str | None = None):
    from .ggr_update import pad_batch  # deferred: sibling-module edge

    B, t, w = tiles.shape
    bb = _fit_block(min(block_b, B), t, t, w)
    padded = pad_batch(tiles, bb)
    Bpad = padded.shape[0]
    kern = functools.partial(_batched_geqrt_kernel, n_pivots=n_pivots,
                             accum_dtype=accum_dtype)
    out = pl.pallas_call(
        kern,
        grid=(Bpad // bb,),
        out_shape=jax.ShapeDtypeStruct((Bpad, t, w), tiles.dtype),
        in_specs=[pl.BlockSpec((bb, t, w), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((bb, t, w), lambda i: (i, 0, 0)),
        interpret=interpret,
        name="ggr_batched_geqrt",
    )(padded)
    return out[:B]


def batched_geqrt_pallas(tiles: jax.Array, n_pivots: int, block_b: int = 8,
                         interpret: bool | None = None, precision=None):
    """Dense GEQRT sweep of a (B, t, w) tile batch, one fused launch.

    Each tile's first ``n_pivots`` columns are triangularized (pivot row c for
    column c); columns >= ``n_pivots`` ride along through the DET2 grids.
    Riding an identity block yields the explicit tile transform: for
    ``tiles = [T | I]`` the output is ``[R | Qt]`` with ``Qt @ T = R`` and
    ``Qt`` orthogonal.  ``block_b`` tiles are VMEM-resident per grid step;
    non-multiple batches are zero-padded (``pad_batch``) and sliced back.
    All-zero tiles are exact fixed points (every divisor is eps-guarded), so
    padding tiles — and the zero row-tiles of a taller-than-the-matrix frame —
    come back bit-identical with ``Qt = I``.

    ``precision`` selects tile compute dtype + in-kernel accumulation dtype
    (``None`` = legacy: tiles at their own dtype, same-width accumulation).
    """
    if precision is None:
        return _batched_geqrt_call(tiles, n_pivots, block_b,
                                   resolve_interpret(interpret))
    prec = resolve_precision(precision)
    return _batched_geqrt_call(tiles.astype(prec.compute), n_pivots, block_b,
                               resolve_interpret(interpret),
                               accum_dtype=prec.accum_dtype)
