"""QR solver serving front-door: micro-batched solve/update dispatch.

The realistic heavy-traffic QR workload is millions of *small* independent
requests (RLS/Kalman state updates, windowed regressions), not one giant
factorization.  ``QRServer`` is the closed-loop batching facade over the
layered serving engine in ``repro.serve`` (typed requests -> continuous
batcher -> padded/sharded dispatch -> admission policy): requests
accumulate in per-(kind, shape, dtype) groups; ``flush()`` stacks each
group and dispatches ONE fused call per group — the batched Pallas update
kernel for row-appends and SRIF Kalman steps, a vmapped augmented-GGR sweep
for one-shot lstsq — then scatters results back to submission order.
``backend="reference"`` runs identical pure-JAX semantics for A/B checking.

Request kinds: ``append`` (row-append a compact ``(R, d)`` state), ``lstsq``
(one-shot solve), ``kalman`` (one square-root information filter
predict+observe step — ``repro.solvers.kalman.kf_step`` — batched through
``kf_step_batched``'s fused stacked sweep; the millions-of-small-trackers
workload), and ``lstsq_pivoted`` (rank-revealing one-shot solve for
ill-posed traffic — batched ``repro.ranks.lstsq_pivoted``, returning
``(x, resid, rank)``).

Sharded serving: pass ``mesh=`` (a 1-D device mesh, e.g. from
``repro.parallel.sharding.make_batch_mesh``) and every flushed group is
dispatched through ``shard_map`` over the mesh's batch axis — the fused
kernel runs once per shard on its slice of the stacked requests.  Groups are
zero-padded up to ``shards x block_b`` (the ``pad_batch`` primitive) so every
shard sees an identical full-granularity grid; results are sliced back, so
sharded and single-device flushes agree bit-for-bit.  This is the paper's
co-design thesis at the serving layer: the fused sweep stays resident per
device, throughput scales with device count.

    PYTHONPATH=src python -m repro.launch.serve_qr --requests 64 \
        --n 16 --rows 8 --backend pallas

    # 4-way sharded flush on a CPU host (fake devices):
    XLA_FLAGS=--xla_force_host_platform_device_count=4 PYTHONPATH=src \
        python -m repro.launch.serve_qr --requests 67 --mesh 4

emits one CSV line per run with throughput; ``--check`` folds a cross-backend
max-error into the ``derived`` column (rows always have exactly 3 fields).

Open-loop serving (continuous batching, per-kind deadlines, admission
control, double-buffered dispatch) lives one layer down: compose
``repro.serve.ContinuousBatcher`` directly — see ``docs/serving.md`` and
``benchmarks/bench_serve_async.py`` for the Poisson load-generator
evidence.  This module stays the stable closed-loop API.

Observability: the serving layers are instrumented with ``repro.obs`` —
per-kind queue-depth gauges, submit->flush queue-wait and flush-duration
histograms, batch-size, batch-close-reason, and padding-waste tracking,
executable-cache-miss counters, and per-dispatch achieved-GFLOP/s derived
from the ``core.counts`` analytic models.  All of it is a no-op until a
collector is installed (``obs.install``/``obs.collecting``); ``--metrics
PREFIX`` installs one for the CLI run and writes ``PREFIX.jsonl`` +
``PREFIX.prom`` snapshots (also triggered by the ``REPRO_OBS_SNAPSHOT``
env var).  Catalog: ``docs/observability.md``.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.launch.cache import use_compile_cache
from repro.serve import (ContinuousBatcher, Dispatcher, ResilientDispatcher,
                         Ticket)
from repro.serve.requests import KINDS as _KINDS

__all__ = ["QRServer", "make_workload"]

_Ticket = Ticket  # legacy alias: tickets are now repro.serve.requests.Ticket


@dataclass
class QRServer:
    """Micro-batching dispatcher for QR solve/update requests.

    Thin closed-loop facade over ``repro.serve``: submits admit into the
    engine's per-group open batches, and only ``flush()`` closes them (no
    deadlines, unbounded admission, latest-cycle result retention) — the
    exact legacy semantics.

    backend: "pallas" (fused batched kernel) or "reference" (vmapped jnp).
    max_batch: dispatch granularity — each group is flushed in chunks of at
    most this many stacked requests (bounds the kernel's VMEM block count).
    mesh/mesh_axis: optional 1-D device mesh; when set, each chunk is
    dispatched through ``shard_map`` over ``mesh_axis`` with the batch padded
    to ``shards x block_b`` (appends/kalman) or ``shards`` (lstsq kinds) and sliced
    back.  Requests of the same shape but different dtypes land in
    *different* groups — stacking never silently promotes a request's dtype.
    """

    backend: str = "pallas"
    max_batch: int = 64
    interpret: bool | None = None
    mesh: object | None = None   # jax.sharding.Mesh; object-typed to keep the
    mesh_axis: str = "batch"     # dataclass importable before jax device init
    block_b: int = 8
    precision: object | None = None  # Precision | policy name | None
    resilient: bool = False  # fault-tolerant dispatch (repro.serve.resilience)

    def __post_init__(self):
        dispatcher_cls = ResilientDispatcher if self.resilient else Dispatcher
        self._engine = ContinuousBatcher(
            dispatcher_cls(backend=self.backend, max_batch=self.max_batch,
                           interpret=self.interpret, mesh=self.mesh,
                           mesh_axis=self.mesh_axis, block_b=self.block_b,
                           double_buffer=False, precision=self.precision),
            admit_max=None, retain_cycles=1)

    # -------------------------------------------------- legacy introspection
    @property
    def _queues(self) -> dict:
        """Open per-group request lists (legacy debugging surface)."""
        return {k: b.requests for k, b in self._engine._open.items()}

    @property
    def _submit_times(self) -> dict:
        """Pending per-group submit timestamps (empty when uninstrumented)."""
        return {k: b.submit_times for k, b in self._engine._open.items()
                if b.submit_times}

    @property
    def _seen_dispatch(self) -> set:
        """(group, padded-batch) signatures already compiled (obs-only)."""
        return self._engine.dispatcher._seen_dispatch

    # ------------------------------------------------------------- submits
    def submit_append(self, R, U, d=None, Y=None) -> Ticket:
        """Queue a row-append update of one (R[, d]) state."""
        return self._engine.submit("append", R, U, d, Y)

    def submit_lstsq(self, A, b) -> Ticket:
        """Queue a one-shot least-squares solve min ||Ax - b||."""
        return self._engine.submit("lstsq", A, b)

    def submit_lstsq_pivoted(self, A, b) -> Ticket:
        """Queue a rank-revealing least-squares solve (ill-posed traffic).

        Dispatches the batched column-pivoted GGR path
        (``repro.ranks.lstsq_pivoted``): the result is ``(x, resid, rank)``
        with ``x`` the min-norm solution over the detected numerical rank
        and ``rank`` an int32 scalar.  Use this kind when ``A`` may be
        rank-deficient — the plain ``lstsq`` kind would amplify noise by
        1/|r_ii| on collapsed pivots.
        """
        return self._engine.submit("lstsq_pivoted", A, b)

    def submit_kalman(self, R, d, F, Qi, H, z, G=None) -> Ticket:
        """Queue one SRIF predict+observe step of a ``(R, d)`` Kalman state.

        Arguments follow ``repro.solvers.kalman.kf_step``: dynamics ``F``,
        upper-triangular process-noise information square root ``Qi``
        (``info_sqrt(Q)``), whitened measurement model ``(H, z)`` and
        optional noise input map ``G``.  Requests sharing shapes/dtypes land
        in one group and advance in a single fused ``kf_step_batched``
        dispatch at the next flush; the result is the stepped ``(R', d')``.
        Passing the *same* jax array object for a model operand across
        requests lets the executor broadcast it instead of stacking copies.
        """
        return self._engine.submit("kalman", R, d, F, Qi, H, z, G)

    # ------------------------------------------------------------ serving
    def pending(self) -> int:
        """Number of submitted requests not yet dispatched by a flush."""
        return self._engine.pending()

    def flush(self, kind: str | None = None) -> int:
        """Dispatch queued groups; returns the number of requests served.

        ``kind`` (None | "append" | "lstsq" | "kalman" | "lstsq_pivoted")
        restricts the flush
        to matching groups — e.g. a latency-sensitive deployment can flush
        one-shot solves more often than state updates.  Results become
        available via ``result(ticket)``; flushed queues reset and each
        flushed group's cycle counter advances (tickets are single-cycle
        *per group*: a later flush of the same group expires them, flushes
        of other groups don't).
        """
        return self._engine.flush(kind)

    def drain(self) -> int:
        """Block until every stored flush result is device-complete.

        ``flush`` returns as soon as the last dispatch is *enqueued*; a
        throughput measurement that only blocks on one ticket is flattered
        by every other group still in flight.  Returns the number of
        results waited on.
        """
        return self._engine.drain()

    def result(self, ticket: Ticket):
        """Fetch a flushed request's result.

        Raises KeyError if the ticket's group has not been flushed since the
        request was queued (still pending — including when flushes of *other*
        groups have happened meanwhile), or if a later flush of the same
        group already replaced the result.
        """
        return self._engine.result(ticket)


def make_workload(num: int, n: int, rows: int, k: int, seed: int = 0):
    """Synthetic request mix covering all four kinds and their edge forms:
    row-append updates (1/2, every 4th of them a bare no-rhs append — the
    result-is-one-array case the ``--check`` normalization must handle),
    SRIF Kalman steps (1/4, alternating fleet-shared model matrices — the
    broadcast case — with per-track models), one-shot solves (1/4, split
    between well-conditioned plain ``lstsq`` and deliberately
    rank-deficient ``lstsq_pivoted`` requests — rank ``ceil(n/2)`` factors,
    the ill-posed traffic the rank-revealing path exists for)."""
    rng = np.random.default_rng(seed)

    def _triu_spd(size):
        T = np.triu(rng.standard_normal((size, size))).astype(np.float32)
        np.fill_diagonal(T, np.abs(np.diag(T)) + 1.0)
        return T

    def _models():
        F = np.eye(n, dtype=np.float32) + 0.1 * rng.standard_normal(
            (n, n)).astype(np.float32)
        Qi = _triu_spd(n)
        H = rng.standard_normal((rows, n)).astype(np.float32)
        return F, Qi, H

    # ONE shared set of jax-array model matrices: submit_kalman's asarray is
    # a no-op on them, so every shared-model request carries the *same*
    # objects and the executor broadcasts instead of stacking copies
    F_sh, Qi_sh, H_sh = (jnp.asarray(M) for M in _models())

    reqs = []
    for i in range(num):
        if i % 4 == 3:
            if i % 8 == 3:
                # rank-deficient by construction: tall x thin product
                r = -(-n // 2)
                A = (rng.standard_normal((4 * n, r)) @
                     rng.standard_normal((r, n))).astype(np.float32)
                b = rng.standard_normal((4 * n, k)).astype(np.float32)
                reqs.append(("lstsq_pivoted", A, b))
                continue
            A = rng.standard_normal((4 * n, n)).astype(np.float32)
            b = rng.standard_normal((4 * n, k)).astype(np.float32)
            reqs.append(("lstsq", A, b))
        elif i % 4 == 1:
            R = _triu_spd(n)
            d = rng.standard_normal(n).astype(np.float32)
            z = rng.standard_normal(rows).astype(np.float32)
            if i % 8 == 1:
                reqs.append(("kalman", R, d, F_sh, Qi_sh, H_sh, z))
            else:
                reqs.append(("kalman", R, d, *_models(), z))
        else:
            R = _triu_spd(n)
            U = rng.standard_normal((rows, n)).astype(np.float32)
            if i % 8 == 4:
                reqs.append(("append", R, U))  # no-rhs: R-only update
                continue
            d = rng.standard_normal((n, k)).astype(np.float32)
            Y = rng.standard_normal((rows, k)).astype(np.float32)
            reqs.append(("append", R, U, d, Y))
    return reqs


def _submit_all(server, reqs):
    tickets = []
    for r in reqs:
        if r[0] == "lstsq":
            tickets.append(server.submit_lstsq(r[1], r[2]))
        elif r[0] == "lstsq_pivoted":
            tickets.append(server.submit_lstsq_pivoted(r[1], r[2]))
        elif r[0] == "kalman":
            tickets.append(server.submit_kalman(*r[1:]))
        else:
            tickets.append(server.submit_append(*r[1:]))
    return tickets


def _as_tuple(res) -> tuple:
    """Normalize a ticket result to a tuple of arrays.

    No-rhs appends resolve to ONE bare array; lstsq/kalman/rhs-append
    resolve to tuples.  Comparison code that ``zip``s two results would
    silently iterate matrix *rows* for the bare-array case — always
    normalize first.
    """
    return res if isinstance(res, tuple) else (res,)


def main(argv=None):
    """Serving CLI: run a synthetic workload through one timed flush.

    Emits one 3-field CSV row (name, req_per_s, derived); ``--mesh N``
    shards flushed groups over an N-device batch mesh, ``--check`` folds a
    cross-backend max-error into the derived column, and ``--metrics P``
    (or ``REPRO_OBS_SNAPSHOT=P``) collects ``repro.obs`` metrics for the
    run and writes ``P.jsonl`` + ``P.prom`` snapshots.
    """
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--n", type=int, default=16)
    ap.add_argument("--rows", type=int, default=8)
    ap.add_argument("--nrhs", type=int, default=1)
    ap.add_argument("--backend", default="pallas", choices=["pallas", "reference"])
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--mesh", type=int, default=1, metavar="N",
                    help="shard flushed groups over an N-device batch mesh "
                         "(on CPU set XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N)")
    ap.add_argument("--check", action="store_true",
                    help="cross-check a sample of results against the other backend")
    ap.add_argument("--resilient", action="store_true",
                    help="serve through the fault-tolerant dispatcher "
                         "(failure domains, retry/degrade, quarantine; "
                         "byte-compatible with the plain path when nothing "
                         "fails)")
    ap.add_argument("--metrics", default=os.environ.get("REPRO_OBS_SNAPSHOT"),
                    metavar="PREFIX",
                    help="collect obs metrics and write PREFIX.jsonl + "
                         "PREFIX.prom snapshots (default: $REPRO_OBS_SNAPSHOT)")
    args = ap.parse_args(argv)
    use_compile_cache()

    mesh = None
    if args.mesh > 1:
        from repro.parallel.sharding import make_batch_mesh

        try:
            mesh = make_batch_mesh(args.mesh)
        except ValueError as e:
            sys.exit(str(e))

    reg = None
    if args.metrics:
        reg = obs.MetricsRegistry()
        obs.install(reg)

    reqs = make_workload(args.requests, args.n, args.rows, args.nrhs)
    server = QRServer(backend=args.backend, max_batch=args.max_batch,
                      mesh=mesh, resilient=args.resilient)

    tickets = _submit_all(server, reqs)  # warmup flush compiles the kernels
    server.flush()
    server.drain()

    tickets = _submit_all(server, reqs)
    t0 = time.perf_counter()
    served = server.flush()
    server.drain()  # block on ALL flushed groups, not just the last ticket
    dt = time.perf_counter() - t0

    check = ""
    if args.check:
        other = QRServer(backend="pallas" if args.backend == "reference"
                         else "reference", max_batch=args.max_batch)
        oticks = _submit_all(other, reqs)
        other.flush()
        err = 0.0
        for tk, ot in list(zip(tickets, oticks))[:: max(1, len(tickets) // 8)]:
            a, b = _as_tuple(server.result(tk)), _as_tuple(other.result(ot))
            err = max(err, max(float(jnp.abs(x - y).max()) for x, y in zip(a, b)))
        check = f";xbackend_maxerr={err:.2e}"

    # derived column is ';'-separated key=val pairs — rows stay 3 CSV fields
    print("name,req_per_s,derived")
    print(f"serve_qr_{args.backend}_n{args.n}_p{args.rows},{served / dt:.1f},"
          f"max_batch={args.max_batch};mesh={args.mesh}{check}")

    if reg is not None:
        meta = {"cli": "serve_qr", "backend": args.backend, "mesh": args.mesh,
                "requests": args.requests, "n": args.n, "rows": args.rows,
                "req_per_s": served / dt}
        obs.write_jsonl(f"{args.metrics}.jsonl", reg, meta)
        obs.write_prometheus(f"{args.metrics}.prom", reg)
        obs.uninstall()
        print(f"serve_qr: wrote {args.metrics}.jsonl and {args.metrics}.prom",
              file=sys.stderr)


if __name__ == "__main__":
    main()
