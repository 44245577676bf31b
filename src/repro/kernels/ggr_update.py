"""Pallas batched row-append update kernel: many small QR updates, one launch.

The streaming-solver workload (RLS / Kalman / sliding-window regression) is
millions of *independent small* updates, not one big factorization.  Per
request the work is a GGR sweep over a stacked ``[R | d; U | Y]`` matrix —
far too small to fill a TPU core on its own.  This kernel amortizes it:

* grid over batch tiles (mirroring ``ggr_apply``'s residency scheme: each
  grid step's block of ``block_b`` stacked problems is VMEM-resident for the
  whole sweep — no HBM traffic between columns);
* per column the kernel exploits the append structure: R is upper triangular,
  so annihilating column c of ``[R; U]`` only rotates pivot row c against the
  p appended rows.  The active set is (p+1) rows, not (n+p) — the fused
  suffix-norm + suffix-dot + DET2 schedule (the paper's merged
  UPDATE_ROW1/UPDATE) runs on that compact block, ~(n+p)/(p+1)x less work
  than a blind sweep of the stacked matrix;
* rhs columns (>= n_pivots) ride along through the DET2 grids, so (R, d)
  solver states update in one pass.

Semantics contract: bit-for-bit this is a *different rotation order* than
``jax.vmap(ggr_triangularize)`` over the stacked matrix, but both produce the
unique non-negative-diagonal triangular factor of the same Gram update, so
they agree to roundoff (validated in tests).

Batch granularity: the grid tiles the batch in ``block_b``-problem steps.
Arbitrary batch sizes (prime, odd, smaller than ``block_b``) are handled by
zero-padding the batch up to the next ``block_b`` multiple and slicing the
output back (``pad_batch`` — also the padding primitive the sharded serving
path uses to round flushed groups up to ``shards x block_b``).  An all-zero
problem is a fixed point of the sweep — every divisor is eps-guarded — so
padding never produces NaNs and costs at most one extra grid step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import resolve_interpret, resolve_precision
from .ggr_panel import (_accum_dt, _annihilated_col, _column_step,
                        _extract_col, _fit_block, _iota, _revcumsum,
                        _scaled_column)

__all__ = ["batched_update_pallas", "pad_batch", "pad_to_tile"]


def pad_batch(x: jax.Array, multiple: int) -> jax.Array:
    """Zero-pad dim 0 of ``x`` up to the next multiple of ``multiple``.

    The padding primitive of the batched-update stack: the kernel uses it so
    any batch size runs at full ``block_b`` granularity (no degradation to
    one-problem grid steps for prime batches), and the sharded serving path
    reuses it to round flushed request groups up to ``shards x block_b``.
    Zero problems pass through the eps-guarded sweep unchanged, so callers
    simply slice ``out[:B]`` to drop them.
    """
    if multiple <= 0:
        raise ValueError(f"pad multiple must be positive, got {multiple}")
    return pad_to_tile(x, (multiple,), axes=(0,))


def pad_to_tile(x: jax.Array, tiles, axes=None) -> jax.Array:
    """Zero-pad ``x`` so the given axes become multiples of the given tiles.

    The general-rank sibling of ``pad_batch`` (which pads dim 0 only):
    ``tiles`` is an int or a sequence of ints, ``axes`` the matching axis
    indices (default: the last ``len(tiles)`` axes).  The blocked QR driver
    uses it to round row/column extents up to the tile grid, which is what
    lets it accept arbitrary (m, n) instead of asserting ``m % tile == 0``:
    zero rows/columns are exact fixed points of every eps-guarded GGR sweep,
    so callers simply slice the padding back off.
    """
    if isinstance(tiles, int):
        tiles = (tiles,)
    tiles = tuple(int(t) for t in tiles)
    if axes is None:
        axes = tuple(range(x.ndim - len(tiles), x.ndim))
    axes = tuple(int(a) % x.ndim for a in axes)
    if len(axes) != len(tiles):
        raise ValueError(f"{len(tiles)} tiles for {len(axes)} axes")
    if any(t <= 0 for t in tiles):
        raise ValueError(f"pad tiles must be positive, got {tiles}")
    widths = [(0, 0)] * x.ndim
    for a, t in zip(axes, tiles):
        widths[a] = (0, -(-x.shape[a] // t) * t - x.shape[a])
    if all(w == (0, 0) for w in widths):
        return x
    return jnp.pad(x, widths)


def _batched_update_kernel(x_ref, o_ref, *, n_pivots: int,
                           accum_dtype: str | None = None):
    """Sweep a (bb, m, w) block; rows ``n_pivots - 1 ..`` form the active set.

    The active block A (bb, m - n_pivots + 1, w) is carried through the
    loop: row 0 is the pivot slot, rows 1.. the appended rows (plus the
    zero rows the wrapper pads with).  Step c loads R's row c into the slot
    by a dynamic row read of the output ref, rotates it against the rest of
    A, and stores the new pivot row back to row c.  The last step's pivot
    row is row ``n_pivots - 1`` itself, so writing the final A back at that
    offset completes the output.
    """
    bb, m, w = x_ref.shape
    ad = _accum_dt(x_ref, accum_dtype)
    o_ref[...] = x_ref[...]
    rows = _iota((1, m - n_pivots + 1, 1), 1)
    cols = _iota((1, 1, w), 2)

    def body(c, A):
        A = jnp.where(rows == 0, o_ref[:, pl.ds(c, 1), :], A)
        at_col = cols == c
        col = _extract_col(A, at_col)  # active column: [R_cc; U[:, c]]
        v, sigma = _scaled_column(col, ad)
        t = jnp.sqrt(_revcumsum(v * v, axis=1))
        out, do_any, t_piv = _column_step(A, v, t, 0, rows)
        A = jnp.where(at_col,
                      _annihilated_col(col, rows, 0, sigma * t_piv, do_any),
                      out)
        o_ref[:, pl.ds(c, 1), :] = A[:, :1, :]
        return A

    A = jax.lax.fori_loop(0, n_pivots, body, x_ref[:, n_pivots - 1:, :])
    o_ref[:, n_pivots - 1:, :] = A


@functools.partial(jax.jit, static_argnames=("n_pivots", "block_b", "interpret",
                                             "accum_dtype"))
def _batched_update_call(stacked: jax.Array, n_pivots: int,
                         block_b: int, interpret: bool,
                         accum_dtype: str | None = None):
    """Triangularize the first ``n_pivots`` columns of each stacked problem.

    stacked: (B, n_pivots + p, w) batch of ``[R | d; U | Y]`` matrices, R
    upper triangular (rows n_pivots.. are the appended observation rows).
    Returns the (B, m, w) updated batch; callers slice ``[:, :n, :n]``
    (updated R) and ``[:, :n, n:]`` (updated rhs).  Each grid step sweeps
    ``block_b`` problems, or fewer where their tiles would overflow VMEM
    (``_fit_block``); batches that are not a multiple of that tile are
    zero-padded (``pad_batch``) and sliced back.
    """
    B, m, w = stacked.shape
    if m < n_pivots:
        raise ValueError(f"stacked rows {m} < n_pivots {n_pivots}")
    if m == n_pivots:  # no appended rows — nothing to annihilate
        return stacked
    # the active set (pivot slot + p appended rows) is rounded up to whole
    # sublane tiles with zero rows, which every eps-guarded sweep leaves alone
    act = -(-(m - n_pivots + 1) // 8) * 8
    mp = n_pivots - 1 + act
    bb = _fit_block(min(block_b, B), mp, act, w)
    padded = pad_to_tile(pad_batch(stacked, bb), (mp,), axes=(1,))
    Bpad = padded.shape[0]
    kern = functools.partial(_batched_update_kernel, n_pivots=n_pivots,
                             accum_dtype=accum_dtype)
    out = pl.pallas_call(
        kern,
        grid=(Bpad // bb,),
        out_shape=jax.ShapeDtypeStruct((Bpad, mp, w), stacked.dtype),
        in_specs=[pl.BlockSpec((bb, mp, w), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((bb, mp, w), lambda i: (i, 0, 0)),
        interpret=interpret,
        name="ggr_batched_update",
    )(padded)
    return out[:B, :m]


def batched_update_pallas(stacked: jax.Array, n_pivots: int,
                          block_b: int = 8, interpret: bool | None = None,
                          precision=None):
    """Batched row-append sweep; see ``_batched_update_call`` for semantics.

    ``interpret=None`` resolves via ``backend.default_interpret()`` (True only
    on CPU hosts) before entering the jitted core, so the resolved value —
    never ``None`` — is the jit cache key.  ``precision`` selects tile compute
    + in-kernel accumulation dtypes (``None`` = legacy: the stacked batch at
    its own dtype with same-width accumulation).
    """
    if precision is None:
        return _batched_update_call(stacked, n_pivots, block_b,
                                    resolve_interpret(interpret))
    prec = resolve_precision(precision)
    return _batched_update_call(stacked.astype(prec.compute), n_pivots,
                                block_b, resolve_interpret(interpret),
                                accum_dtype=prec.accum_dtype)
