"""Pallas trailing-update kernel: the fused DET2 grid (the paper's hot loop).

This is the RDP's ``UPDATE`` made TPU-native: for a stored panel of b GGR
column transforms (V, T), replay all b of them over a trailing tile while it
stays resident in VMEM.  Per column: one suffix-dot doubling pass + one DET2
grid; the trailing tile never touches HBM between columns — b-fold VMEM reuse,
arithmetic intensity ≈ 3b/12 flops/byte (vs 3/12 for the naive per-column
dgeqr2ggr schedule the paper implements on GPGPUs, where exactly this
serialization is what caps performance).

Grid: 1-D over trailing-width tiles; V/T blocks are index-invariant so Mosaic
keeps them resident across grid steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import resolve_interpret, resolve_precision
from .ggr_panel import _accum_dt, _column_step, _extract_col, _iota

__all__ = ["apply_factors_pallas"]


def _apply_kernel(v_ref, t_ref, c_ref, o_ref, *, pivot0: int,
                  accum_dtype: str | None = None):
    V = v_ref[...]
    T = t_ref[...]
    C = c_ref[...]
    m, b = V.shape
    ad = _accum_dt(C, accum_dtype)
    rows = _iota((m, 1), 0)
    cols = _iota((1, b), 1)

    def body(c, C):
        at_col = cols == c
        v = _extract_col(V, at_col).astype(ad)  # (m, 1)
        t = _extract_col(T, at_col).astype(ad)
        return _column_step(C, v, t, pivot0 + c, rows)[0]

    o_ref[...] = jax.lax.fori_loop(0, b, body, C)


@functools.partial(jax.jit, static_argnames=("pivot0", "block_w", "interpret",
                                             "accum_dtype"))
def _apply_factors_call(V: jax.Array, T: jax.Array, C: jax.Array,
                        pivot0: int, block_w: int, interpret: bool,
                        accum_dtype: str | None = None):
    m, b = V.shape
    w = C.shape[1]
    bw = min(block_w, w)
    assert w % bw == 0, "pad trailing width to the block multiple"
    kern = functools.partial(_apply_kernel, pivot0=pivot0, accum_dtype=accum_dtype)
    return pl.pallas_call(
        kern,
        grid=(w // bw,),
        out_shape=jax.ShapeDtypeStruct((m, w), C.dtype),
        in_specs=[
            pl.BlockSpec((m, b), lambda j: (0, 0)),  # V resident across grid
            pl.BlockSpec((m, b), lambda j: (0, 0)),  # T resident across grid
            pl.BlockSpec((m, bw), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((m, bw), lambda j: (0, j)),
        interpret=interpret,
        name="ggr_apply_factors",
    )(V, T, C)


def apply_factors_pallas(
    V: jax.Array,
    T: jax.Array,
    C: jax.Array,
    pivot0: int = 0,
    block_w: int = 256,
    interpret: bool | None = None,
    precision=None,
):
    """Apply b stored GGR transforms to trailing columns C ((m, w)).

    ``interpret=None`` resolves via ``backend.default_interpret()``.
    ``precision`` selects tile compute + accumulation dtypes (``None`` =
    legacy: everything at the operands' own dtype).
    """
    if precision is None:
        return _apply_factors_call(V, T, C, pivot0, block_w,
                                   resolve_interpret(interpret))
    prec = resolve_precision(precision)
    return _apply_factors_call(V.astype(prec.compute), T.astype(prec.compute),
                               C.astype(prec.compute), pivot0, block_w,
                               resolve_interpret(interpret),
                               accum_dtype=prec.accum_dtype)
