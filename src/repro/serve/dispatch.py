"""Batch executors and the device-dispatch layer of the serving engine.

One ``Dispatcher`` owns everything between "a closed batch of typed
requests" and "per-request results": per-kind executors (append / lstsq /
kalman / lstsq_pivoted), the shard_map + ``pad_batch`` sharded path, the per-server
executable cache, and the double-buffering that overlaps host-side stacking
of batch k+1 with batch k's device dispatch.

**Padding before jit.**  Every chunk is zero-padded on the host to the
granularity its kernel path actually runs at (``padded_chunk``: mesh →
``shards x block_b``, single-device pallas → ``block_b``) *before* the
jitted entry point sees it.  Two chunk sizes that round to the same padded
batch therefore hit ONE executable — which is also what makes the
``serve.executable_cache_miss`` accounting honest: it keys on the padded
size, not the raw chunk size (the old monolithic server keyed on the raw
size and double-counted).  Zero problems are exact fixed points of the
eps-guarded sweeps, so the pad rows are sliced off afterwards unchanged.

**Double buffering.**  jax dispatch is asynchronous: calling a jitted
executor enqueues device work and returns array futures.  In
``double_buffer=True`` mode the dispatcher never blocks at dispatch time —
it records an ``InFlight`` handle per chunk and the caller (the continuous
batcher) finalizes handles later (``pump`` polls readiness without
blocking, ``drain`` blocks), so the host stacks the next batch while the
device chews the previous one.  ``double_buffer=False`` reproduces the
legacy closed-loop timing: each chunk is finalized (and, under an installed
``repro.obs`` collector, blocked and timed) before the next is stacked.
With double buffering on, even a collector never makes a close wait on the
device: finalizing (metrics, and the served factor's health gauges, which
copy it to the host) happens once ``pump`` sees a chunk ready, or in
``drain``.

**Spans.**  Each executor spans its stages on the profiler's clock:
``repro/serve/stack`` (stacking and padding the requests' operands) and
``repro/serve/results`` (splitting the per-request results, with
arguments ``n`` and ``padded``); between them the solvers span the
front end and the kernel launch, and the solve kinds span their solve's
launch as ``repro/lstsq/solve`` (``m``, ``n``, ``route``).  ``pump`` is
``repro/serve/pump``.

**Solves.**  ``lstsq`` and ``lstsq_pivoted`` share one executor and one
compiled solve (``_batched_solve``), keyed on what it runs: the route
(blocked panel kernels or the plain sweep; ``backend="reference"`` always
takes the sweep, which calls no kernel) and, for the blocked route, the
schedule override and interpret mode in force (``_solve_key``).

**Executable cache.**  Sharded solve dispatch functions (per kind and
mesh) are built through a
bounded per-server LRU (``ExecutableCache``) instead of a module-level
``functools.lru_cache(maxsize=None)`` — a long-lived server that cycles
meshes no longer pins dead ``Mesh`` objects (and their device buffers)
forever.  The ``(group, padded-batch)`` signatures seen by
``serve.executable_cache_miss`` are the per-(kind, padded-shape) view of
the underlying jit caches.
"""
from __future__ import annotations

import functools
import time
from collections import OrderedDict
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from repro import obs

__all__ = ["Dispatcher", "DrainError", "ExecutableCache", "InFlight"]


class DrainError(RuntimeError):
    """Aggregate of per-chunk finalization failures from ``pump``/``drain``.

    ``failures`` is ``[(InFlight, exception), ...]`` — every failed chunk,
    not just the first: a raise from one in-flight chunk must never orphan
    the other double-buffered chunks' tickets, so pump/drain finalize every
    chunk they can and report the casualties together afterwards.
    """

    def __init__(self, failures: list):
        self.failures = list(failures)
        detail = "; ".join(
            f"{infl.key[0]}[{infl.nb}]: {type(e).__name__}: {e}"
            for infl, e in self.failures)
        super().__init__(
            f"{len(self.failures)} in-flight chunk(s) failed to finalize: "
            f"{detail}")


class ExecutableCache:
    """Bounded LRU of built executables, keyed by hashable signatures.

    ``get(key, build)`` returns the cached value or builds, inserts, and
    evicts the least-recently-used entry past ``maxsize``.  Eviction drops
    the only reference the serving layer holds, so jitted closures over
    retired meshes become collectable.
    """

    def __init__(self, maxsize: int = 32):
        if maxsize < 1:
            raise ValueError(f"maxsize must be >= 1, got {maxsize}")
        self.maxsize = maxsize
        self.hits = 0
        self.misses = 0
        self._entries: OrderedDict = OrderedDict()

    def get(self, key, build):
        try:
            value = self._entries[key]
            self._entries.move_to_end(key)
            self.hits += 1
            return value
        except KeyError:
            pass
        self.misses += 1
        value = build()
        self._entries[key] = value
        while len(self._entries) > self.maxsize:
            self._entries.popitem(last=False)
        return value

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key) -> bool:
        return key in self._entries

    def keys(self):
        return list(self._entries)

    def clear(self) -> None:
        """Drop every cached executable (rebuilt on next use).  The chaos
        harness's eviction-storm injector calls this; hit/miss counters are
        deliberately kept — a storm shows up as a miss spike, not a reset."""
        self._entries.clear()


def _solve_key(backend: str, m: int, n: int) -> tuple:
    """``(route, schedule, interpret)``: what one batched (m, n) solve runs,
    resolved on the host so that it keys the compiled solve.

    ``backend="reference"`` takes the plain-``jnp`` sweep, which calls no
    kernel; so does a problem below ``solvers.lstsq_route``'s blocked size.
    A blocked solve carries the ``kernels.backend.degraded_mode`` schedule
    override (None: the driver's own choice) and the interpret mode, both
    otherwise read only while tracing: a rung of the resilience ladder
    then gets a solve compiled for it, not the one cached before it.
    """
    from repro.kernels import default_interpret
    from repro.kernels.backend import forced_schedule
    from repro.solvers import lstsq_route

    route = "sweep" if backend == "reference" else lstsq_route(m, n)
    if route == "sweep":
        return route, None, None
    return route, forced_schedule(), default_interpret()


@functools.partial(jax.jit,
                   static_argnames=("pivoted", "route", "schedule", "interpret"))
def _batched_solve(Ab, bb, pivoted: bool, route: str,
                   schedule: str | None, interpret: bool | None):
    """The vmapped least-squares solve of a padded batch: ``(x, resid)``,
    or with ``pivoted`` the rank-revealing ``(x, resid, rank)``.

    Compiled per padded shape and ``_solve_key``; the key's schedule and
    interpret mode are what the blocked driver's kernels are traced with.
    The padded lanes are all-zero problems, exact fixed points of both
    sweeps (the pivoted one gives rank 0, x = 0), so slicing them off is
    lossless.
    """
    from repro.kernels.backend import degraded_mode
    from repro.ranks import lstsq_pivoted
    from repro.solvers import ggr_lstsq

    def one(A, b):
        if pivoted:
            fit = lstsq_pivoted(A, b, route=route)
            return fit.x, fit.resid, fit.rank
        return tuple(ggr_lstsq(A, b, route=route)[:2])

    with degraded_mode(interpret=interpret, schedule=schedule):
        return jax.vmap(one)(Ab, bb)


def _build_sharded_solve(mesh, mesh_axis: str, pivoted: bool):
    """jit'd shard_map solve dispatch for one mesh (cached per server),
    compiled per padded shape and ``_solve_key`` as ``_batched_solve`` is."""
    from jax.sharding import PartitionSpec as P

    spec = P(mesh_axis)

    @functools.partial(jax.jit,
                       static_argnames=("route", "schedule", "interpret"))
    def run(Ab, bb, route, schedule, interpret):
        # check_vma off, as in ``solvers.qr_update``: the map runs each
        # shard's problems on their own (no collectives), and neither
        # pallas_call nor the solvers' loop carries are annotated with
        # varying axes
        return jax.shard_map(
            functools.partial(_batched_solve, pivoted=pivoted, route=route,
                              schedule=schedule, interpret=interpret),
            mesh=mesh, in_specs=(spec, spec),
            out_specs=(spec,) * (3 if pivoted else 2),
            check_vma=False,
        )(Ab, bb)

    return run


def _pad_to(x: jax.Array, batch: int) -> jax.Array:
    """Zero-pad dim 0 up to exactly ``batch`` rows (no-op when already
    there)."""
    if x.shape[0] == batch:
        return x
    from repro.kernels import pad_batch

    return pad_batch(x, batch)


@functools.partial(jax.jit, static_argnames="dtypes")
def _split_rows(fields: tuple, dtypes: tuple) -> tuple[tuple, tuple]:
    """``(batches, rows)`` of one chunk's batched outputs: each field cast
    to its dtype in ``dtypes`` (``None`` keeps it), and the tuple of its
    rows.

    Compiled per padded shape and dtypes, never per real request count:
    callers keep the first ``nb`` rows and drop the pad lanes.  One call a
    chunk, where eager row slices would each be a dispatch of their own.
    On a mesh the rows come back replicated over it, as eager slices of the
    batch-sharded outputs do.
    """
    batches = tuple(x if dt is None else x.astype(dt)
                    for x, dt in zip(fields, dtypes))
    return batches, tuple(tuple(x[i] for i in range(x.shape[0]))
                          for x in batches)


@dataclass
class InFlight:
    """One enqueued chunk awaiting finalization (blocking + accounting)."""

    key: tuple             # group signature
    nb: int                # real (un-padded) request count in the chunk
    t0: float              # host perf_counter at stack start
    outs: list             # per-request results (arrays or tuples of arrays)
    flops: float           # analytic useful-work flops for the chunk
    r_factor: object       # padded batched R for factor-health gauges, or None
    record: bool           # obs was collecting at dispatch time
    done_at: float | None = None
    finalized: bool = False

    def _leaves(self):
        for o in self.outs:
            if isinstance(o, tuple):
                yield from o
            else:
                yield o

    def ready(self) -> bool:
        """True when every result buffer is device-complete (non-blocking)."""
        return all(getattr(x, "is_ready", lambda: True)()
                   for x in self._leaves())

    def block(self) -> None:
        # resilient dispatch stores typed ServeError objects in failed
        # result slots; only array leaves can (and need to) be blocked on
        jax.block_until_ready(
            [x for x in self._leaves() if not isinstance(x, Exception)])


@dataclass
class Dispatcher:
    """Chunked, padded, optionally sharded executor for closed batches.

    Mirrors the legacy ``QRServer`` dispatch knobs: ``backend`` ("pallas" |
    "reference"), ``max_batch`` chunk granularity, ``interpret`` /
    ``block_b`` kernel knobs, optional ``mesh``/``mesh_axis`` for shard_map
    dispatch.  ``double_buffer`` selects async (see module docstring).
    """

    backend: str = "pallas"
    max_batch: int = 64
    interpret: bool | None = None
    mesh: object | None = None
    mesh_axis: str = "batch"
    block_b: int = 8
    double_buffer: bool = False
    cache_size: int = 32
    precision: object | None = None  # Precision | policy name | None
    executables: ExecutableCache = None  # built in __post_init__
    _seen_dispatch: set = field(default_factory=set)  # (group, padded_B)
    _inflight: list = field(default_factory=list)

    def __post_init__(self):
        if self.executables is None:
            self.executables = ExecutableCache(self.cache_size)
        if self.precision is not None:
            from repro.kernels import resolve_precision

            self.precision = resolve_precision(self.precision)

    # ------------------------------------------------------------ precision
    def block_b_for(self, dtype) -> int:
        """Storage-scaled batch granularity for one group's at-rest dtype.

        2-byte storage (bf16/f16) halves per-problem VMEM residency, so those
        groups run — and pad — at double ``block_b``: twice the filters per
        dispatch for the same resident footprint.  4/8-byte dtypes keep the
        configured granularity, so existing f32 padding behaviour (and the
        cache-miss accounting built on it) is unchanged.
        """
        try:
            scale = 2 if jnp.dtype(dtype).itemsize <= 2 else 1
        except TypeError:
            scale = 1
        return self.block_b * scale

    def _chunk_precision(self, store_dtype: str):
        """``(compute_dtype, kernel_precision)`` for a group stored at
        ``store_dtype``.

        No policy installed: compute at storage dtype, legacy kernels.  With
        a policy, the chunk computes at ``promote_types(store, policy)`` —
        bf16 groups up-cast to f32 under the default policy (and results
        down-cast back to storage on return); under an explicit bf16/f16
        policy the low-precision groups stay at tile dtype and the kernels
        get the mixed policy (wide accumulation); f64 groups always pass
        through untouched.
        """
        if self.precision is None:
            return store_dtype, None
        cd = jnp.promote_types(jnp.dtype(store_dtype), self.precision.compute)
        if cd.itemsize <= 2:
            from repro.kernels import Precision

            return str(cd), Precision(str(cd), self.precision.accum_dtype,
                                      store_dtype)
        return str(cd), None

    # ------------------------------------------------------------- padding
    def padded_chunk(self, nb: int, kind: str, dtype=None) -> int:
        """Batch size a dispatch of ``nb`` requests actually runs at, after
        pad_batch rounding (mesh: shards x block_b, lstsq shards; single
        device: block_b for every kind and backend).  ``dtype`` is the
        group's storage dtype: 2-byte groups round at ``block_b_for``'s
        doubled granularity.

        Rounding *every* single-device path to ``block_b`` — not just the
        pallas kernel that needs the granularity — is what makes continuous
        batching viable: deadline closes produce arbitrary chunk sizes, and
        an unpadded jit would compile one executable per distinct size.
        Zero problems are exact fixed points of the eps-guarded sweeps, so
        pad lanes come back unchanged and are sliced off."""
        bb = self.block_b if dtype is None else self.block_b_for(dtype)
        if self.mesh is not None:
            gran = self.mesh.shape[self.mesh_axis] * (
                1 if kind in ("lstsq", "lstsq_pivoted") else bb)
        else:
            gran = bb
        return -(-nb // gran) * gran

    # ----------------------------------------------------------- executors
    def _kernel_opts(self, store_dtype: str | None = None) -> dict:
        bb = (self.block_b if store_dtype is None
              else self.block_b_for(store_dtype))
        kp = (None if store_dtype is None
              else self._chunk_precision(store_dtype)[1])
        return dict(backend=self.backend, interpret=self.interpret,
                    block_b=bb, mesh=self.mesh,
                    mesh_axis=self.mesh_axis, precision=kp)

    def _exec_append(self, chunk):
        """Stack + pad one append chunk, dispatch the fused batched kernel."""
        from repro.solvers import qr_append_rows_batched

        nb = len(chunk)
        store_dt = str(chunk[0].arrays[0].dtype)
        compute_dt, _ = self._chunk_precision(store_dt)
        P = self.padded_chunk(nb, "append", store_dt)
        has_rhs = chunk[0].arrays[2] is not None

        def stack(i):
            x = _pad_to(jnp.stack([r.arrays[i] for r in chunk]), P)
            return x if compute_dt == store_dt else x.astype(compute_dt)

        with obs.span("repro/serve/stack"):
            cols = [stack(i) for i in range(4 if has_rhs else 2)]
        n, p = cols[0].shape[2], cols[1].shape[1]
        w = n + (cols[3].shape[2] if has_rhs else 0)
        out = qr_append_rows_batched(*cols, **self._kernel_opts(store_dt))
        fields = tuple(out) if has_rhs else (out,)
        (Rn, *_), outs = self._results("append", nb, fields,
                                       (store_dt,) * len(fields))
        return outs, nb * obs.ggr_append_flops(n, p, w), Rn

    def _exec_lstsq(self, chunk):
        """One lstsq chunk: ``(x, resid)`` per request (``_exec_solve``)."""
        return self._exec_solve("lstsq", chunk)

    def _exec_solve(self, kind: str, chunk):
        """Stack + pad one ``lstsq`` or ``lstsq_pivoted`` chunk and launch
        the vmapped solve (``_batched_solve``, shard_mapped over the mesh
        when one is set), keyed on ``_solve_key``.

        ``lstsq_pivoted`` runs the QRCP min-norm solve
        (``repro.ranks.lstsq_pivoted``); its per-request result is ``(x,
        resid, rank)``, rank kept int32, never down-cast to the storage
        dtype.  Under a collector each chunk counts its ``nb`` solves as
        ``solvers.lstsq_solves{route}``.
        """
        from repro.solvers.lstsq import count_solves

        pivoted = kind == "lstsq_pivoted"
        nb = len(chunk)
        store_dt = str(chunk[0].arrays[0].dtype)
        compute_dt, _ = self._chunk_precision(store_dt)
        P = self.padded_chunk(nb, kind, store_dt)
        with obs.span("repro/serve/stack"):
            Ab = _pad_to(jnp.stack([r.arrays[0] for r in chunk]), P)
            bb = _pad_to(jnp.stack([r.arrays[1] for r in chunk]), P)
            if compute_dt != store_dt:
                Ab, bb = Ab.astype(compute_dt), bb.astype(compute_dt)
        m, n = Ab.shape[1], Ab.shape[2]
        k = bb.shape[2] if bb.ndim > 2 else 1
        key = _solve_key(self.backend, m, n)
        with obs.span("repro/lstsq/solve", m=m, n=n, route=key[0]):
            if self.mesh is None:
                fields = _batched_solve(Ab, bb, pivoted, *key)
            else:
                fn = self.executables.get(
                    (kind, self.mesh, self.mesh_axis),
                    lambda: _build_sharded_solve(self.mesh, self.mesh_axis,
                                                 pivoted))
                fields = fn(Ab, bb, *key)
        count_solves(key[0], nb)
        dtypes = (store_dt, store_dt, None) if pivoted else (store_dt,) * 2
        _, outs = self._results(kind, nb, fields, dtypes)
        flops = nb * obs.lstsq_flops(m, n, k)
        # pivoting adds the per-step suffix-norm matrix + swap on top of the
        # plain augmented sweep: ~2x the unpivoted macro-op count
        return outs, 2.0 * flops if pivoted else flops, None

    def _exec_kalman(self, chunk):
        """Stack + pad one kalman chunk, dispatch the fused SRIF step.

        Model operands (F, Qi, H, z, G) that are the SAME array object
        across the whole chunk — one dynamics model, many tracks — stay 2-D
        and broadcast inside ``kf_step_batched`` instead of stacking B
        redundant copies; per-filter models stack (and pad) normally.
        """
        from repro.solvers.kalman import kf_step_batched

        nb = len(chunk)
        store_dt = str(chunk[0].arrays[0].dtype)
        compute_dt, _ = self._chunk_precision(store_dt)
        P = self.padded_chunk(nb, "kalman", store_dt)
        has_G = chunk[0].arrays[6] is not None
        nfields = 7 if has_G else 6

        def fld(i):
            if i >= 2 and all(r.arrays[i] is chunk[0].arrays[i]
                              for r in chunk):
                x = chunk[0].arrays[i]  # shared: broadcast, don't stack
            else:
                x = _pad_to(jnp.stack([r.arrays[i] for r in chunk]), P)
            return x if compute_dt == store_dt else x.astype(compute_dt)

        with obs.span("repro/serve/stack"):
            cols = [fld(i) for i in range(nfields)]
        # per-filter state must always carry the padded batch dim
        n, w, p = cols[0].shape[-1], cols[3].shape[-1], cols[4].shape[-2]
        Rn, dn = kf_step_batched(cols[0], cols[1], cols[2], cols[3],
                                 cols[4], cols[5],
                                 cols[6] if has_G else None,
                                 **self._kernel_opts(store_dt))
        (Rn, _), outs = self._results("kalman", nb, (Rn, dn),
                                      (store_dt, store_dt))
        # fused SRIF stack: (w + 2n + p, w + n + 1) with w + n pivots
        # -> n + p rows ride below the (triangular-by-construction) top
        flops = nb * obs.ggr_append_flops(w + n, n + p, w + n + 1)
        return outs, flops, Rn

    def _exec_lstsq_pivoted(self, chunk):
        """One rank-revealing chunk: ``(x, resid, rank)`` per request
        (``_exec_solve``)."""
        return self._exec_solve("lstsq_pivoted", chunk)

    def _results(self, kind: str, nb: int, fields: tuple,
                 dtypes: tuple) -> tuple[tuple, list]:
        """Split one chunk's batched, padded ``fields`` into per-request
        results, in one compiled call (``_split_rows``).

        Returns ``(batches, outs)``: each field cast to its storage dtype
        at its padded size, and the first ``nb`` requests' results (a
        tuple of fields each, or the one field's array).
        """
        with obs.span("repro/serve/results", n=nb,
                      padded=fields[0].shape[0]):
            batches, rows = _split_rows(fields, dtypes)
            if len(rows) == 1:
                outs = list(rows[0][:nb])
            else:
                outs = list(zip(*(r[:nb] for r in rows)))
        if obs.enabled():
            obs.counter("serve.result_splits", kind=kind).inc()
        return batches, outs

    _EXECUTORS = {"append": _exec_append, "lstsq": _exec_lstsq,
                  "kalman": _exec_kalman,
                  "lstsq_pivoted": _exec_lstsq_pivoted}

    # ------------------------------------------------------------ dispatch
    def dispatch(self, key: tuple, reqs: list,
                 cycle: int = 0) -> tuple[list, list[InFlight]]:
        """Dispatch one closed batch in ``max_batch`` chunks.

        Returns ``(outs, handles)``: per-request results in submission
        order, plus one ``InFlight`` handle per chunk.  In double-buffer
        mode the handles are un-finalized (the caller pumps/drains them);
        otherwise they are finalized here, chunk by chunk, before the next
        chunk is stacked — the legacy closed-loop behavior.

        ``cycle`` is the batch cycle being dispatched — unused here, but
        part of the signature so ``ResilientDispatcher`` can key its
        per-(group, cycle) provenance records.
        """
        kind = key[0]
        exec_one = self._EXECUTORS[kind]
        outs: list = []
        handles: list[InFlight] = []
        for lo in range(0, len(reqs), self.max_batch):
            chunk = reqs[lo:lo + self.max_batch]
            rec = obs.enabled()
            t0 = time.perf_counter() if rec else 0.0
            chunk_outs, flops, r_factor = exec_one(self, chunk)
            outs.extend(chunk_outs)
            infl = InFlight(key, len(chunk), t0, chunk_outs, flops,
                            r_factor, rec)
            if rec:
                # compilation happens at enqueue: count the miss now, keyed
                # on the PADDED batch (what the jit cache actually keys on)
                sig = (key, self.padded_chunk(len(chunk), kind, key[2]))
                if sig not in self._seen_dispatch:
                    self._seen_dispatch.add(sig)
                    obs.counter("serve.executable_cache_miss",
                                kind=kind).inc()
            if self.double_buffer:
                self._inflight.append(infl)
            else:
                self.finalize(infl)
            handles.append(infl)
        return outs, handles

    # -------------------------------------------------------- finalization
    def finalize(self, infl: InFlight) -> None:
        """Record one chunk's dispatch metrics, if accounting.

        Waits for the chunk only with double buffering off; with it on,
        ``pump`` finalizes chunks already ready and ``drain`` blocks first.
        Blocking on a ready chunk waits for nothing, but raises a deferred
        device error here, in the chunk's failure domain.
        ``serve.dispatch_seconds`` is host time from the chunk's stacking
        to its results being seen ready, not device time.
        """
        if infl.finalized:
            return
        infl.finalized = True
        if not infl.record:
            if infl.done_at is None and infl.ready():
                infl.done_at = time.perf_counter()
            return
        if not self.double_buffer or infl.ready():
            infl.block()
        if infl.done_at is None:
            infl.done_at = time.perf_counter()
        kind = infl.key[0]
        store_dt = infl.key[2]  # first required operand's dtype string
        compute_dt, kernel_prec = self._chunk_precision(store_dt)
        accum_dt = (kernel_prec.accum_dtype if kernel_prec is not None
                    else compute_dt)
        obs.record_dispatch("serve", infl.flops, infl.done_at - infl.t0,
                            by_dtype=obs.flops_by_dtype(infl.flops,
                                                        compute_dt, accum_dt),
                            rate=False, kind=kind, precision=compute_dt)
        padded = self.padded_chunk(infl.nb, kind, store_dt)
        obs.gauge("serve.padding_waste", kind=kind).set(
            (padded - infl.nb) / padded if padded else 0.0)
        if infl.r_factor is not None:
            # the executors hand over the padded batch: gauge the real rows
            obs.factor_health(infl.r_factor[:infl.nb], "serve", kind=kind)

    def pump(self) -> int:
        """Finalize every in-flight chunk whose buffers are ready
        (non-blocking).  Returns the number finalized cleanly; chunk
        finalization failures are aggregated into one ``DrainError`` after
        every ready chunk has been attempted (a bad chunk never blocks its
        neighbors' finalization)."""
        with obs.span("repro/serve/pump"):
            done = [i for i in self._inflight if i.ready()]
            failures = []
            ok = 0
            for infl in done:
                if infl.done_at is None:
                    infl.done_at = time.perf_counter()
                try:
                    self.finalize(infl)
                    ok += 1
                except Exception as e:  # noqa: BLE001 — aggregated below
                    infl.finalized = True  # terminal: don't re-finalize later
                    failures.append((infl, e))
            self._inflight = [i for i in self._inflight if not i.finalized]
            if failures:
                raise DrainError(failures)
            return ok

    def drain(self) -> int:
        """Block on and finalize ALL in-flight chunks.

        Returns the count finalized cleanly.  Every chunk is attempted even
        when an earlier one raises (a deferred device error in one
        double-buffered chunk must not orphan the other chunk's tickets);
        failures are re-raised together as one ``DrainError`` at the end.
        """
        pending = self._inflight
        self._inflight = []
        failures = []
        ok = 0
        for infl in pending:
            try:
                infl.block()
                if infl.done_at is None:
                    infl.done_at = time.perf_counter()
                self.finalize(infl)
                ok += 1
            except Exception as e:  # noqa: BLE001 — aggregated below
                infl.finalized = True
                failures.append((infl, e))
        if failures:
            raise DrainError(failures)
        return ok
