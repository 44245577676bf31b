"""Open-loop async serving benchmark: continuous batching vs closed loop.

A Poisson load generator drives the SAME pre-drawn arrival schedule through
two serving stacks at equal ``max_batch``:

* ``async`` — the layered engine composed directly
  (``repro.serve.ContinuousBatcher`` over a double-buffered ``Dispatcher``):
  batches close on ``admit_max`` or per-kind deadlines, dispatch never
  blocks the arrival loop (the host stacks batch k+1 while batch k is in
  flight), and per-request completion is read off the pumped ``InFlight``
  handles (``engine.done_at``).
* ``sync`` — the legacy closed-loop ``QRServer`` facade: every
  ``max_batch`` arrivals it calls ``flush()`` + ``drain()`` and the arrival
  loop stalls for the full stack->dispatch->block cycle.

Open loop means arrivals do NOT wait for completions — exactly the regime
where the closed loop's head-of-line blocking shows up as tail latency.
Per-mode req/s (arrival start -> last completion) and p50/p99 request
latency (submit -> device-complete) are recorded to
``BENCH_serve_async.json`` next to ``BENCH_blocked.json``.

``--check`` shrinks the run to a fixed-seed smoke, asserts the async
engine's results match the facade's bit-for-bit-or-roundoff, and (with
``--metrics``) runs a tiny admission drill so the snapshot carries every
``repro.obs.REQUIRED_ASYNC_SERVE_FAMILIES`` family for the CI gate:

    PYTHONPATH=src python benchmarks/bench_serve_async.py --check \\
        --metrics OBS_serve_async
    PYTHONPATH=src python -m repro.obs.export \\
        --validate OBS_serve_async.jsonl --preset async
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

from repro import obs  # noqa: E402
from repro.launch.cache import use_compile_cache  # noqa: E402
from repro.launch.serve_qr import QRServer, _as_tuple, make_workload  # noqa: E402
from repro.serve import (  # noqa: E402
    AdmissionPolicy,
    ContinuousBatcher,
    Dispatcher,
    LatencyTier,
    Rejected,
)


def _percentiles(lat_s: list) -> dict:
    a = np.asarray(lat_s, dtype=np.float64) * 1e3  # -> ms
    return {"p50_ms": float(np.percentile(a, 50)),
            "p99_ms": float(np.percentile(a, 99)),
            "mean_ms": float(a.mean())}


def _wait_until(target: float, engine=None) -> None:
    """Spin-sleep to the arrival time; poll the engine while waiting (the
    serve loop's heartbeat: deadline closes + in-flight pumping)."""
    while True:
        now = time.perf_counter()
        if now >= target:
            return
        if engine is not None:
            engine.poll()
        time.sleep(min(2e-4, target - now))


def run_async(reqs, arrivals, args):
    """Open-loop run through the double-buffered continuous batcher."""
    tiers = {k: LatencyTier(deadline=args.deadline)
             for k in ("append", "lstsq", "kalman", "lstsq_pivoted")}
    engine = ContinuousBatcher(
        Dispatcher(backend=args.backend, max_batch=args.max_batch,
                   double_buffer=True),
        AdmissionPolicy(tiers=tiers),
        admit_max=args.max_batch, retain_cycles=None)

    tickets, submit_ts = [], []
    t0 = time.perf_counter()
    for r, dt in zip(reqs, arrivals):
        _wait_until(t0 + dt, engine)
        submit_ts.append(time.perf_counter())
        tickets.append(engine.submit(r[0], *r[1:]))
    engine.flush()
    engine.drain()
    done = [engine.done_at(t) for t in tickets]
    assert all(d is not None for d in done)
    lat = [d - s for d, s in zip(done, submit_ts)]
    stats = {"mode": "async", "req_per_s": len(reqs) / (max(done) - t0),
             **_percentiles(lat)}
    return stats, engine, tickets


def run_sync(reqs, arrivals, args):
    """Same arrival schedule through the closed-loop facade: flush+drain
    every ``max_batch`` arrivals (and at the end), stalling the loop."""
    server = QRServer(backend=args.backend, max_batch=args.max_batch)
    tickets, submit_ts, lat = [], [], [None] * len(reqs)
    pending: list[int] = []

    def _flush_drain():
        server.flush()
        server.drain()
        now = time.perf_counter()
        for i in pending:
            lat[i] = now - submit_ts[i]
        pending.clear()

    t0 = time.perf_counter()
    for i, (r, dt) in enumerate(zip(reqs, arrivals)):
        _wait_until(t0 + dt)
        submit_ts.append(time.perf_counter())
        if r[0] == "lstsq":
            tickets.append(server.submit_lstsq(r[1], r[2]))
        elif r[0] == "lstsq_pivoted":
            tickets.append(server.submit_lstsq_pivoted(r[1], r[2]))
        elif r[0] == "kalman":
            tickets.append(server.submit_kalman(*r[1:]))
        else:
            tickets.append(server.submit_append(*r[1:]))
        pending.append(i)
        if len(pending) >= args.max_batch:
            _flush_drain()
    if pending:
        _flush_drain()
    end = time.perf_counter()
    stats = {"mode": "sync", "req_per_s": len(reqs) / (end - t0),
             **_percentiles(lat)}
    return stats, server, tickets


def _admission_drill(backend: str) -> None:
    """Exercise reject + shed once so an instrumented run's snapshot
    carries both admission families (the measured run never overloads)."""
    reqs = make_workload(3, n=4, rows=2, k=1, seed=99)
    lstsq = [r for r in reqs if r[0] == "lstsq"] or [reqs[0]]
    r = lstsq[0]
    rej = ContinuousBatcher(
        Dispatcher(backend=backend),
        AdmissionPolicy(tiers={r[0]: LatencyTier(max_queue=1)}))
    rej.submit(r[0], *r[1:])
    try:
        rej.submit(r[0], *r[1:])
    except Rejected:
        pass
    rej.flush()
    shed = ContinuousBatcher(
        Dispatcher(backend=backend),
        AdmissionPolicy(tiers={r[0]: LatencyTier(
            max_queue=1, on_full="shed_oldest")}),
        retain_cycles=None)
    shed.submit(r[0], *r[1:])
    shed.submit(r[0], *r[1:])
    shed.flush()


def _check_results(engine, tickets, reqs, args) -> float:
    """Async results must match a fresh closed-loop facade's: bitwise for
    the kernel kinds, roundoff for lstsq (deadline closes make its vmap
    width nondeterministic)."""
    oracle = QRServer(backend=args.backend, max_batch=args.max_batch)
    oticks = []
    for r in reqs:
        if r[0] == "lstsq":
            oticks.append(oracle.submit_lstsq(r[1], r[2]))
        elif r[0] == "lstsq_pivoted":
            oticks.append(oracle.submit_lstsq_pivoted(r[1], r[2]))
        elif r[0] == "kalman":
            oticks.append(oracle.submit_kalman(*r[1:]))
        else:
            oticks.append(oracle.submit_append(*r[1:]))
    oracle.flush()
    err = 0.0
    for r, ta, to in zip(reqs, tickets, oticks):
        a = _as_tuple(engine.result(ta))
        b = _as_tuple(oracle.result(to))
        for xa, xb in zip(a, b):
            d = float(np.abs(np.asarray(xa) - np.asarray(xb)).max())
            err = max(err, d)
            if d > 1e-4:
                sys.exit(f"bench_serve_async --check FAILED: {r[0]} result "
                         f"diverges from the closed-loop facade by {d:.2e}")
    return err


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=192)
    ap.add_argument("--rate", type=float, default=600.0,
                    help="Poisson arrival rate, req/s")
    ap.add_argument("--n", type=int, default=8)
    ap.add_argument("--rows", type=int, default=4)
    ap.add_argument("--nrhs", type=int, default=1)
    ap.add_argument("--backend", default="reference",
                    choices=["pallas", "reference"])
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-kind open-batch deadline, seconds (default: "
                         "the time max_batch arrivals take at --rate, x2 "
                         "for the per-group split — batches mostly fill "
                         "before the latency bound closes them)")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--check", action="store_true",
                    help="fixed-seed smoke: small run, assert async results "
                         "match the closed-loop facade, hard-fail otherwise")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="JSON output path (default ./BENCH_serve_async.json)")
    ap.add_argument("--metrics", default=os.environ.get("REPRO_OBS_SNAPSHOT"),
                    metavar="PREFIX",
                    help="collect repro.obs metrics for the async run and "
                         "write PREFIX.jsonl + PREFIX.prom snapshots")
    args = ap.parse_args(argv)
    use_compile_cache()
    if args.check:
        args.requests = min(args.requests, 48)
        args.rate = min(args.rate, 600.0)
    if args.deadline is None:
        # traffic splits over ~4 request groups: give an open batch about
        # two full-batch windows of its group's arrivals before the
        # latency bound closes it short (a too-tight deadline degenerates
        # continuous batching into tiny padded dispatches)
        args.deadline = 2.0 * 4.0 * args.max_batch / args.rate

    reg = None
    if args.metrics:
        reg = obs.MetricsRegistry()
        obs.install(reg)

    rng = np.random.default_rng(args.seed)
    reqs = make_workload(args.requests, args.n, args.rows, args.nrhs,
                         seed=args.seed)
    arrivals = np.cumsum(rng.exponential(1.0 / args.rate, args.requests))

    # warmup: compile every (group, padded-batch) executable outside the
    # measured window so neither mode pays tracing during its run
    warm = QRServer(backend=args.backend, max_batch=args.max_batch)
    for r in reqs:
        if r[0] == "lstsq":
            warm.submit_lstsq(r[1], r[2])
        elif r[0] == "lstsq_pivoted":
            warm.submit_lstsq_pivoted(r[1], r[2])
        elif r[0] == "kalman":
            warm.submit_kalman(*r[1:])
        else:
            warm.submit_append(*r[1:])
    warm.flush()
    warm.drain()

    sync_stats, _, _ = run_sync(reqs, arrivals, args)
    async_stats, engine, tickets = run_async(reqs, arrivals, args)
    speedup = async_stats["req_per_s"] / sync_stats["req_per_s"]

    err = None
    if args.check:
        err = _check_results(engine, tickets, reqs, args)
    if reg is not None:
        _admission_drill(args.backend)

    out = {
        "bench": "bench_serve_async", "check": args.check,
        "config": {"requests": args.requests, "rate": args.rate,
                   "n": args.n, "rows": args.rows, "nrhs": args.nrhs,
                   "backend": args.backend, "max_batch": args.max_batch,
                   "deadline": args.deadline, "seed": args.seed},
        "results": [async_stats, sync_stats],
        "speedup_req_per_s": speedup,
    }
    if err is not None:
        out["xfacade_maxerr"] = err
    path = args.out or os.path.join(os.getcwd(), "BENCH_serve_async.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)

    print("name,req_per_s,derived")
    for s in (async_stats, sync_stats):
        print(f"serve_async_{s['mode']}_{args.backend}_n{args.n},"
              f"{s['req_per_s']:.1f},"
              f"p50_ms={s['p50_ms']:.2f};p99_ms={s['p99_ms']:.2f}")
    print(f"serve_async_speedup,0,async_vs_sync={speedup:.2f}x;path={path}")

    if reg is not None:
        meta = {"bench": "bench_serve_async", "backend": args.backend,
                "requests": args.requests, "rate": args.rate,
                "async_req_per_s": async_stats["req_per_s"],
                "sync_req_per_s": sync_stats["req_per_s"]}
        obs.write_jsonl(f"{args.metrics}.jsonl", reg, meta)
        obs.write_prometheus(f"{args.metrics}.prom", reg)
        obs.uninstall()
        print(f"bench_serve_async: wrote {args.metrics}.jsonl and "
              f"{args.metrics}.prom", file=sys.stderr)


if __name__ == "__main__":
    main()
