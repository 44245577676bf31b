"""Blocked GGR QR — ``dgeqrfggr`` as a panel pipeline over the Pallas kernels.

The driver (``ggr_qr_blocked`` / ``ggr_triangularize_blocked``) is a
right-looking panel algorithm executed by ``lax.fori_loop`` over dynamic
frame slices, so compile time does not scale with the tile grid.  Two
schedules share that loop:

``schedule="tree"`` — the MXU schedule (default on CPU hosts)
    Per panel: every row tile of the panel is factored independently by one
    grid-batched GEQRT Pallas launch (``kernels.batched_geqrt``, identity
    riding along so each tile also emits its explicit b x b transform Qt);
    the per-tile R factors are then coupled through a TSQR-style *binary
    tree* — log2(p) rounds of batched triangular-vs-triangular couplings via
    ``kernels.batched_update`` (the compact (b+1)-row active-set sweep),
    replacing the old serial per-row-tile TSQRT chain — and every transform
    is replayed onto the trailing matrix as batched GEMMs with the small Qt
    tiles: this is where the MXU earns its keep.  The explicit-Q choice is
    deliberate: GGR's per-column transform is Hessenberg-structured, so there
    is no rank-b compact WY form; at tile size 64-128 an explicit Qt is
    small, VMEM-resident, and turns every trailing update into an MXU-shaped
    matmul.

``schedule="fused"`` — the VMEM-residency schedule (default on TPU/GPU)
    Per panel: one fused ``kernels.panel_qr`` GEQRT launch factors the whole
    (F, b) panel and stores its compact (V, T) factors, then ONE
    ``kernels.apply_panel`` grid launch replays all b transforms over the
    entire trailing width while each width block stays VMEM-resident —
    b-fold reuse instead of per-tile GEMMs, the paper's merged
    UPDATE_ROW1/UPDATE schedule at panel granularity.

Both schedules share the *frame trick*: panel k operates on a dynamic row
slice starting at its first pivot row, so in-frame pivots are always rows
0..b-1 — static, which is what lets one compiled panel body serve every loop
iteration.  Frames shrink by halves across O(log) phases as rows finalize,
and ``kernels.pad_to_tile`` rounds arbitrary (m, n) up to the tile grid
(zero rows/cols are exact fixed points of the eps-guarded sweeps), so there
is no ``m % tile == 0`` restriction.

``ggr_geqrt`` / ``ggr_tsqrt`` are the original explicit-Q tile primitives
(still used by ``core.distributed`` and the Orthant optimizer), and
``ggr_qr_blocked_reference`` is the previous Python-unrolled driver with its
serial TSQRT chain — kept as an oracle the tests check against.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels.backend import Precision, resolve_interpret, resolve_precision
from repro.kernels.backend import forced_schedule as backend_forced_schedule
from repro.kernels.ggr_apply import apply_factors_pallas
from repro.kernels.ggr_panel import batched_geqrt_pallas, panel_factor_pallas
from repro.kernels.ggr_update import batched_update_pallas, pad_to_tile

from .ggr import apply_ggr_factors, ggr_column_step_at, ggr_factor_column

__all__ = [
    "ggr_geqrt",
    "ggr_tsqrt",
    "ggr_qr_blocked",
    "ggr_qr_blocked_reference",
    "ggr_triangularize_blocked",
    "suffix_col_norms",
]


def ggr_geqrt(tile: jax.Array):
    """Factor one (m x b) tile; returns (R_tile, Qt) with Qt @ tile = R."""
    m, b = tile.shape
    steps = min(m - 1, b)

    def body(c, carry):
        R, Qt = carry
        f = ggr_factor_column(R, c)
        R = ggr_column_step_at(R, c)
        Qt = apply_ggr_factors(f, Qt, c)
        return R, Qt

    # eye + 0*tile keeps the carry's varying-manual-axes consistent when this
    # runs inside shard_map (e.g. as the TSQR reduction operator)
    qt0 = jnp.eye(m, dtype=tile.dtype) + 0.0 * tile[:, :1]
    R, Qt = jax.lax.fori_loop(0, steps, body, (tile, qt0))
    return jnp.triu(R), Qt


def ggr_tsqrt(R_top: jax.Array, B: jax.Array):
    """Stacked factorization of [R_top; B] (R_top upper-triangular b x b).

    Returns (R_new, Qt_stacked) with Qt_stacked @ [R_top; B] = [R_new; 0].
    """
    b = R_top.shape[1]
    stacked = jnp.concatenate([R_top, B], axis=0)
    R, Qt = ggr_geqrt(stacked)
    return R[:b, :], Qt


def suffix_col_norms(X: jax.Array) -> jax.Array:
    """Squared suffix column norms ``t2[i, j] = sum_{r>=i} X[r, j]^2``.

    The matrix-wide form of the paper's eq. 3 DOT_k macro-op: one reverse
    cumulative sum yields every candidate column's trailing norm at every
    elimination depth.  The per-column sweeps already compute these suffix
    sums for their own (k, l) coefficients, which is why greedy column
    pivoting (``repro.ranks.ggr_qr_pivoted`` reads row ``c`` of this matrix
    to select pivot ``c``) adds no new datapath to the blocked driver.
    f32-promoted accumulation, matching ``core.ggr.suffix_norms``.
    """
    acc = X.astype(jnp.promote_types(X.dtype, jnp.float32))
    return jnp.cumsum((acc * acc)[::-1], axis=0)[::-1]


@functools.partial(jax.jit, static_argnames=("tile",))
def ggr_qr_blocked_reference(A: jax.Array, tile: int = 128) -> jax.Array:
    """The previous blocked driver: Python-unrolled (p x q) tile loops with a
    serial per-row-tile TSQRT chain and one small GEMM per (i, j) tile.

    Kept as a test oracle and as a compact executable statement of the
    PLASMA-style tile algorithm (§4.1.1).
    """
    m, n = A.shape
    assert m % tile == 0 and n % tile == 0, "pad to tile multiples first"
    p, q = m // tile, n // tile
    t = tile

    def get(X, i, j):
        return jax.lax.dynamic_slice(X, (i * t, j * t), (t, t))

    def put(X, blk, i, j):
        return jax.lax.dynamic_update_slice(X, blk, (i * t, j * t))

    R = A
    for k in range(min(p, q)):
        # 1) diagonal tile factor
        diag = get(R, k, k)
        r_kk, Qt = ggr_geqrt(diag)
        R = put(R, r_kk, k, k)
        # 2) row update: apply Qt to tiles right of the diagonal (GEMM)
        for j in range(k + 1, q):
            R = put(R, Qt @ get(R, k, j), k, j)
        # 3) couple every tile below the diagonal + paired trailing updates
        for i in range(k + 1, p):
            r_new, Qt2 = ggr_tsqrt(get(R, k, k), get(R, i, k))
            R = put(R, r_new, k, k)
            R = put(R, jnp.zeros((t, t), R.dtype), i, k)
            for j in range(k + 1, q):
                top = get(R, k, j)
                bot = get(R, i, j)
                stacked = jnp.concatenate([top, bot], axis=0)
                upd = Qt2 @ stacked  # (2t x 2t) @ (2t x t) on the MXU
                R = put(R, upd[:t], k, j)
                R = put(R, upd[t:], i, j)
    return jnp.triu(R)


# ---------------------------------------------------------------------------
# The panel pipeline
# ---------------------------------------------------------------------------
def _tree_levels(p: int):
    """Static binary-tree pairing over p row tiles: [(ai, bi), ...] per round.

    Round r couples nodes ``ai[j]`` (survivor, receives the coupled R) with
    ``bi[j]``; node 0 — the tile holding the pivot rows — survives every
    round, so the final panel R lands in tile 0.  Odd leftovers propagate to
    the next round: log2(p) depth instead of the serial chain's p - 1.
    """
    levels = []
    nodes = list(range(p))
    while len(nodes) > 1:
        pairs = list(zip(nodes[0::2], nodes[1::2]))
        levels.append((np.asarray([a for a, _ in pairs]),
                       np.asarray([b for _, b in pairs])))
        nodes = sorted([a for a, _ in pairs]
                       + (nodes[-1:] if len(nodes) % 2 else []))
    return levels


def _phase_schedule(m: int, b: int, nk: int):
    """[(k_start, k_end, F)]: frame heights shrink by halves as rows finalize.

    Panel k only involves rows >= k*b; a single static frame tall enough for
    panel 0 would waste ~2x on the later panels, so the fori_loop is split
    into O(log) phases whose static frame height F halves once the active
    height fits in F/2.  F is always a tile multiple and at least 2b.
    """
    phases = []
    F = -(-max(m, b) // b) * b
    k = 0
    while k < nk:
        if F <= 2 * b:
            k_end = nk
        else:
            k_end = min(nk, max(k + 1, -(-(m - F // 2) // b)))
        phases.append((k, k_end, F))
        k = k_end
        F = max(2 * b, -(-(F // 2) // b) * b)
    return phases


def _gemm(lhs, rhs, accum_dtype):
    """Batched tile GEMM; low-precision operands accumulate at accum_dtype.

    ``accum_dtype=None`` is the legacy path (operand-dtype accumulation).
    With an accumulation dtype the contraction asks XLA for wide partials
    (``preferred_element_type``) and rounds the result back to tile dtype —
    the GEMM analogue of the kernels' in-body accumulation policy.
    """
    hi = jax.lax.Precision.HIGHEST  # f32 tiles: not one bf16 pass on TPU
    if accum_dtype is None:
        return jnp.einsum("pij,pjw->piw", lhs, rhs, precision=hi)
    return jnp.einsum("pij,pjw->piw", lhs, rhs, precision=hi,
                      preferred_element_type=jnp.dtype(accum_dtype)
                      ).astype(lhs.dtype)


def _panel_step_tree(Xp, k, *, b, F, W, block_b, interpret, accum_dtype=None):
    """One tree-scheduled panel: batched tile GEQRT -> log-depth coupling ->
    GEMM trailing updates, all on the (F, W) frame starting at the pivot row."""
    p = F // b
    dtype = Xp.dtype
    prec = (None if accum_dtype is None
            else Precision(str(dtype), accum_dtype, str(dtype)))
    eye = jnp.eye(b, dtype=dtype)
    c0 = k * b
    frame = jax.lax.dynamic_slice(Xp, (c0, 0), (F, W))
    pan = jax.lax.dynamic_slice(frame, (0, c0), (F, b)).reshape(p, b, b)

    # level 0: factor every row tile independently, identity riding -> Qt_i
    with obs.named_span("repro/blocked/panel"):
        tiles = jnp.concatenate([pan, jnp.broadcast_to(eye, (p, b, b))], axis=2)
        out0 = batched_geqrt_pallas(tiles, n_pivots=b,
                                    block_b=block_b or p, interpret=interpret,
                                    precision=prec)
        R = out0[:, :, :b]
    with obs.named_span("repro/blocked/trailing"):
        C = _gemm(out0[:, :, b:], frame.reshape(p, b, W), accum_dtype)

    # binary-tree coupling of the per-tile R factors (log2(p) rounds);
    # each round is ONE batched compact-active-set sweep + ONE batched GEMM
    for ai, bi in _tree_levels(p):
        npair = len(ai)
        with obs.named_span("repro/blocked/coupling"):
            E = jnp.broadcast_to(eye, (npair, b, b))
            Z = jnp.zeros((npair, b, b), dtype)
            stacked = jnp.concatenate(
                [jnp.concatenate([R[ai], E, Z], axis=2),
                 jnp.concatenate([R[bi], Z, E], axis=2)], axis=1)
            out = batched_update_pallas(stacked, n_pivots=b,
                                        block_b=block_b or npair,
                                        interpret=interpret, precision=prec)
            R = R.at[ai].set(out[:, :b, :b])
            Qt = out[:, :, b:]  # (npair, 2b, 2b) node transform
        with obs.named_span("repro/blocked/trailing"):
            Ct = jnp.concatenate([C[ai], C[bi]], axis=1)
            Ct = _gemm(Qt, Ct, accum_dtype)
            C = C.at[ai].set(Ct[:, :b]).at[bi].set(Ct[:, b:])

    frame = C.reshape(F, W)
    # exact panel-column write: [R; 0] (keeps finalized columns exactly zero
    # below their pivots, which is what makes later frames' GEMMs exact
    # no-ops on them)
    Rpan = jnp.concatenate([jnp.triu(R[0]), jnp.zeros((F - b, b), dtype)], axis=0)
    frame = jax.lax.dynamic_update_slice(frame, Rpan, (0, c0))
    return jax.lax.dynamic_update_slice(Xp, frame, (c0, 0))


def _panel_step_fused(Xp, k, *, b, F, W, nk, pure_qr, block_w, interpret,
                      accum_dtype=None):
    """One fused-scheduled panel: monolithic GEQRT kernel + one full-width
    DET2-grid apply launch (V/T resident across the width grid)."""
    c0 = k * b
    prec = (None if accum_dtype is None
            else Precision(str(Xp.dtype), accum_dtype, str(Xp.dtype)))
    frame = jax.lax.dynamic_slice(Xp, (c0, 0), (F, W))
    pan = jax.lax.dynamic_slice(frame, (0, c0), (F, b))
    with obs.named_span("repro/blocked/panel"):
        Rp, V, T = panel_factor_pallas(pan, pivot0=0, interpret=interpret,
                                       precision=prec)

    bw = W if block_w is None else max(1, min(block_w, W))
    while W % bw:
        bw //= 2

    def apply(fr):
        with obs.named_span("repro/blocked/trailing"):
            return apply_factors_pallas(V, T, fr, pivot0=0, block_w=bw,
                                        interpret=interpret, precision=prec)

    if pure_qr:
        # last panel of a pure QR has no trailing columns to update
        frame = jax.lax.cond(k < nk - 1, apply, lambda fr: fr, frame)
    else:
        frame = apply(frame)
    frame = jax.lax.dynamic_update_slice(frame, Rp, (0, c0))
    return jax.lax.dynamic_update_slice(Xp, frame, (c0, 0))


@functools.partial(
    jax.jit,
    static_argnames=("n_pivots", "tile", "schedule", "interpret",
                     "block_w", "block_b", "accum_dtype"),
)
def _triangularize_blocked_impl(X, n_pivots, tile, schedule, interpret,
                                block_w, block_b, accum_dtype=None):
    m, w = X.shape
    b = min(tile, -(-n_pivots // 8) * 8)
    np_pad = -(-n_pivots // b) * b
    nk = np_pad // b

    # pad the pivot block up to a tile multiple (zero columns between the
    # pivots and any trailing rhs columns — exact no-op sweeps)
    if np_pad != n_pivots:
        if n_pivots == w:
            X = pad_to_tile(X, (b,), axes=(1,))
        else:
            X = jnp.concatenate(
                [X[:, :n_pivots],
                 jnp.zeros((m, np_pad - n_pivots), X.dtype),
                 X[:, n_pivots:]], axis=1)
    W = X.shape[1]

    phases = _phase_schedule(m, b, nk)
    # rows: frames slide down b per panel, so the tail needs zero rows out to
    # the last frame's bottom edge (zero rows are exact sweep fixed points)
    total = max(F + (e - 1) * b for (_, e, F) in phases)
    Xp = jnp.pad(X, ((0, total - m), (0, 0)))

    pure_qr = W == np_pad
    for s, e, F in phases:
        if schedule == "tree":
            body = functools.partial(_panel_step_tree, b=b, F=F, W=W,
                                     block_b=block_b, interpret=interpret,
                                     accum_dtype=accum_dtype)
        else:
            body = functools.partial(_panel_step_fused, b=b, F=F, W=W, nk=nk,
                                     pure_qr=pure_qr, block_w=block_w,
                                     interpret=interpret,
                                     accum_dtype=accum_dtype)
        Xp = jax.lax.fori_loop(s, e, lambda k, Xc: body(Xc, k), Xp)

    out = Xp[:m]
    if np_pad != n_pivots:
        out = jnp.concatenate([out[:, :n_pivots], out[:, np_pad:]], axis=1)
    return out


def ggr_triangularize_blocked(X: jax.Array, n_pivots: int | None = None,
                              tile: int = 64, schedule: str = "auto",
                              interpret: bool | None = None,
                              block_w: int | None = None,
                              block_b: int | None = None,
                              precision=None) -> jax.Array:
    """Blocked GGR sweeps annihilating columns 0..n_pivots-1 below their
    diagonals; trailing columns (rhs) ride along as ``Q^T``-transformed data.

    The blocked sibling of ``core.ggr.ggr_triangularize``: same semantics,
    panel-pipeline execution (see module docstring).  Accepts arbitrary
    (m, w) — tile padding is internal.

    schedule: ``"tree"`` (batched tile GEQRT + log-depth coupling + GEMM
    trailing — the MXU schedule), ``"fused"`` (monolithic panel kernel + one
    full-width DET2 apply launch — the VMEM-residency schedule), or
    ``"auto"``: tree on interpret/CPU backends, fused where Mosaic compiles.

    precision: mixed-precision policy (``Precision`` / name / None).  The
    input is cast to the policy's compute dtype at entry; suffix-norm and
    DET2 accumulation inside the kernels — and the trailing-GEMM partials of
    the tree schedule — run at the policy's (wider) accumulation dtype.  The
    result is returned at compute dtype.  ``None`` keeps everything at the
    input dtype (legacy, bit-identical).
    """
    m, w = X.shape
    if n_pivots is None:
        n_pivots = min(m, w)
    if not 0 < n_pivots <= w:
        raise ValueError(f"n_pivots {n_pivots} out of range for width {w}")
    if schedule not in ("auto", "tree", "fused"):
        raise ValueError(f"unknown schedule {schedule!r}")
    itp = resolve_interpret(interpret)
    forced = backend_forced_schedule()
    if forced is not None:
        # degraded-mode override (see kernels.backend.degraded_mode): the
        # serving ladder's fused -> tree rung reaches through call layers
        # that do not thread a schedule argument
        sched = forced
    else:
        sched = schedule if schedule != "auto" else ("tree" if itp else "fused")
    accum_dtype = None
    if precision is not None:
        prec = resolve_precision(precision)
        X = X.astype(prec.compute)
        accum_dtype = prec.accum_dtype
    rec = obs.enabled() and not isinstance(X, jax.core.Tracer)
    if not rec:
        return _triangularize_blocked_impl(X, n_pivots, tile, sched, itp,
                                           block_w, block_b,
                                           accum_dtype=accum_dtype)
    with obs.span("repro/blocked/triangularize"):
        t0 = time.perf_counter()
        out = _triangularize_blocked_impl(X, n_pivots, tile, sched, itp,
                                          block_w, block_b,
                                          accum_dtype=accum_dtype)
        jax.block_until_ready(out)
        sweep_flops = obs.ggr_sweep_flops(m, w, n_pivots)
        obs.record_dispatch("blocked", sweep_flops,
                            time.perf_counter() - t0, schedule=sched,
                            by_dtype=obs.flops_by_dtype(
                                sweep_flops, str(X.dtype), accum_dtype),
                            precision=str(X.dtype))
    return out


def ggr_qr_blocked(A: jax.Array, tile: int = 64, schedule: str = "auto",
                   interpret: bool | None = None,
                   block_w: int | None = None,
                   block_b: int | None = None,
                   precision=None) -> jax.Array:
    """Blocked GGR QR of an arbitrary (m, n) matrix; returns the (m, n) R.

    Panel pipeline over the Pallas GEQRT/DET2 kernels with tree-coupled row
    tiles — see the module docstring for the two schedules.  Unlike the
    reference driver there is no ``m % tile == 0`` restriction.
    """
    m, n = A.shape
    if min(m, n) == 0:
        return jnp.triu(A)
    R = ggr_triangularize_blocked(A, min(m, n), tile=tile, schedule=schedule,
                                  interpret=interpret, block_w=block_w,
                                  block_b=block_b, precision=precision)
    return jnp.triu(R)
