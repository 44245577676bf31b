"""Chaos benchmark: the serving engine under injected faults.

A Poisson open-loop workload (the ``make_workload`` mix, 1% of requests
NaN-poisoned) runs twice through the resilient serving stack
(``ContinuousBatcher`` over ``ResilientDispatcher``) on the SAME arrival
schedule and batch boundaries: once fault-free (the oracle), once under a
seeded :class:`repro.testing.faults.FaultPlan` (5% transient executor
failures by default).  Then three targeted drills:

* **ladder drill** — a scripted injector fails the first K attempts of a
  one-request dispatch, forcing it onto each rung of ``DEFAULT_LADDER`` in
  turn; asserts the provenance lands on the expected rung and the degraded
  result agrees with the native one.
* **purge drill** — a single-rung ladder plus a persistent injector errors
  a whole cycle; asserts the ticket resolves to ``ServeError`` and the
  cycle is eagerly purged (``serve.cycles_purged``).
* **postcheck drill** — ``precheck=False`` plus a NaN request exercises the
  post-dispatch quarantine: the poisoned lane resolves ``PoisonedError``,
  the healthy co-resident lane still gets its (re-dispatched) result.

``--check`` asserts the acceptance bar: availability >= 99% of non-poisoned
requests, every poisoned request quarantined in BOTH runs, non-faulted
(native-rung) results bitwise-identical to the fault-free run, degraded
results within roundoff, p99 latency under degradation below the ceiling,
and at least one recorded degraded dispatch onto every drilled rung.

    PYTHONPATH=src python benchmarks/bench_chaos.py --check \\
        --metrics OBS_chaos
    PYTHONPATH=src python -m repro.obs.export \\
        --validate OBS_chaos.jsonl --preset chaos

Results land in ``BENCH_chaos.json`` next to the other benchmark artifacts.
"""
from __future__ import annotations

import argparse
import contextlib
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
    DEFAULT_LADDER,
    ContinuousBatcher,
    PoisonedError,
    ResilientDispatcher,
    RetryPolicy,
    Rung,
    ServeError,
)
from repro.testing.faults import (  # noqa: E402
    FaultPlan,
    ScriptedInjector,
    inject,
    poison_workload,
)

_NO_SLEEP = lambda s: None  # noqa: E731 — drills don't wait out backoffs


def _percentiles(lat_s: list) -> dict:
    a = np.asarray(lat_s, dtype=np.float64) * 1e3  # -> ms
    return {"p50_ms": float(np.percentile(a, 50)),
            "p99_ms": float(np.percentile(a, 99)),
            "mean_ms": float(a.mean())}


def _wait_until(target: float) -> None:
    while True:
        now = time.perf_counter()
        if now >= target:
            return
        time.sleep(min(2e-4, target - now))


def _counter_sum(reg, name: str, **labels) -> float:
    total = 0.0
    for m in reg.collect():
        if m.name != name:
            continue
        have = dict(m.labels)
        if all(have.get(k) == v for k, v in labels.items()):
            total += m.value
    return total


def run_chaos(reqs, arrivals, args, plan: FaultPlan | None):
    """One open-loop pass; identical batch boundaries with or without a
    fault plan (admit_max-only closes — no deadlines — so the chunking, and
    therefore every vmap width, is a pure function of the arrival order)."""
    dispatcher = ResilientDispatcher(backend=args.backend,
                                     max_batch=args.max_batch)
    engine = ContinuousBatcher(dispatcher, admit_max=args.max_batch,
                               retain_cycles=None)
    context = inject(plan) if plan is not None else contextlib.nullcontext()
    tickets, submit_ts = [], []
    t0 = time.perf_counter()
    with context as injector:
        for r, dt in zip(reqs, arrivals):
            _wait_until(t0 + dt)
            submit_ts.append(time.perf_counter())
            tickets.append(engine.submit(r[0], *r[1:]))
        engine.flush()
        engine.drain()
    end = time.perf_counter()

    outcomes = []
    for t in tickets:
        try:
            outcomes.append(("ok", engine.result(t)))
        except PoisonedError as e:
            outcomes.append(("poisoned", e))
        except ServeError as e:
            outcomes.append(("error", e))
    done = [engine.done_at(t) for t in tickets]
    lat = [d - s for d, s in zip(done, submit_ts) if d is not None]
    counts = {k: sum(1 for o in outcomes if o[0] == k)
              for k in ("ok", "poisoned", "error")}
    stats = {"mode": "faulted" if plan is not None else "baseline",
             "req_per_s": len(reqs) / (end - t0), **_percentiles(lat),
             "outcomes": counts,
             "injected": dict(injector.counts) if plan is not None else {}}
    return stats, engine, tickets, outcomes


# ------------------------------------------------------------------- drills
def _drill_problem(args, seed: int = 1234):
    rng = np.random.default_rng(seed)
    R = np.triu(rng.standard_normal((args.n, args.n))).astype(np.float32)
    np.fill_diagonal(R, np.abs(np.diag(R)) + 1.0)
    U = rng.standard_normal((args.rows, args.n)).astype(np.float32)
    return R, U


def ladder_drill(args) -> list[str]:
    """Force every rung once; returns the drilled rung names."""
    R, U = _drill_problem(args)
    baseline = None
    drilled = []
    for k in range(len(DEFAULT_LADDER)):
        dispatcher = ResilientDispatcher(
            backend=args.backend, max_batch=8,
            retry=RetryPolicy(max_attempts=1, backoff=0.0),
            sleep=_NO_SLEEP)
        engine = ContinuousBatcher(dispatcher)
        with inject(ScriptedInjector(set(range(k)))):
            ticket = engine.submit("append", R, U)
            engine.flush()
        Rn = np.asarray(engine.result(ticket))
        prov = dispatcher.provenance[(ticket.group, ticket.cycle)][0]
        expected = DEFAULT_LADDER[k].name
        if prov.rung != expected:
            sys.exit(f"bench_chaos ladder drill FAILED: forced {k} failures "
                     f"but served from rung {prov.rung!r}, not {expected!r}")
        if k == 0:
            baseline = Rn
        elif not np.allclose(Rn, baseline, rtol=1e-4, atol=1e-5):
            diff = float(np.abs(Rn - baseline).max())
            sys.exit(f"bench_chaos ladder drill FAILED: rung {expected!r} "
                     f"result diverges from native by {diff:.2e}")
        drilled.append(expected)
    return drilled


def purge_drill(args) -> None:
    """Exhaust a one-rung ladder: whole cycle errors, eagerly purged."""
    R, U = _drill_problem(args, seed=4321)
    dispatcher = ResilientDispatcher(
        backend=args.backend, ladder=(Rung("native"),),
        retry=RetryPolicy(max_attempts=1), sleep=_NO_SLEEP)
    engine = ContinuousBatcher(dispatcher)
    with inject(ScriptedInjector(set(range(64)))):
        ticket = engine.submit("append", R, U)
        engine.flush()
    try:
        engine.result(ticket)
    except ServeError:
        engine.drain()  # purged cycles must not break drain
        return
    sys.exit("bench_chaos purge drill FAILED: exhausted ladder did not "
             "resolve the ticket to a ServeError")


def postcheck_drill(args) -> None:
    """NaN past a disabled precheck: post-dispatch quarantine isolates the
    lane, the healthy co-resident request still completes correctly."""
    rng = np.random.default_rng(99)
    A = rng.standard_normal((4 * args.n, args.n)).astype(np.float32)
    b = rng.standard_normal((4 * args.n, 1)).astype(np.float32)
    A_bad = A.copy()
    A_bad[0, 0] = np.nan
    dispatcher = ResilientDispatcher(backend=args.backend, precheck=False,
                                     sleep=_NO_SLEEP)
    engine = ContinuousBatcher(dispatcher)
    t_bad = engine.submit("lstsq", A_bad, b)
    t_good = engine.submit("lstsq", A, b)
    engine.flush()
    try:
        engine.result(t_bad)
        sys.exit("bench_chaos postcheck drill FAILED: NaN request was not "
                 "quarantined")
    except PoisonedError:
        pass
    x, _ = engine.result(t_good)
    solo = QRServer(backend=args.backend)
    ts = solo.submit_lstsq(A, b)
    solo.flush()
    xs, _ = solo.result(ts)
    if not np.allclose(np.asarray(x), np.asarray(xs), rtol=1e-4, atol=1e-5):
        sys.exit("bench_chaos postcheck drill FAILED: healthy survivor's "
                 "result diverges after quarantine re-dispatch")


# -------------------------------------------------------------------- checks
def _check_runs(reqs, poisoned_idx, base, fault, args) -> dict:
    base_out, fault_out = base[3], fault[3]
    fault_engine, fault_tickets = fault[1], fault[2]
    poisoned = set(poisoned_idx)
    for i in poisoned:
        for label, out in (("baseline", base_out), ("faulted", fault_out)):
            if out[i][0] != "poisoned":
                sys.exit(f"bench_chaos --check FAILED: poisoned request {i} "
                         f"resolved {out[i][0]!r} in the {label} run")
    clean = [i for i in range(len(reqs)) if i not in poisoned]
    completed = sum(1 for i in clean if fault_out[i][0] == "ok")
    availability = completed / len(clean) if clean else 1.0
    if availability < args.availability_floor:
        sys.exit(f"bench_chaos --check FAILED: availability {availability:.4f}"
                 f" < floor {args.availability_floor}")
    provenance = fault_engine.dispatcher.provenance
    bitwise = degraded = 0
    for i in clean:
        if fault_out[i][0] != "ok" or base_out[i][0] != "ok":
            continue
        t = fault_tickets[i]
        prov = provenance[(t.group, t.cycle)][t.index]
        a = _as_tuple(base_out[i][1])
        b = _as_tuple(fault_out[i][1])
        if prov.rung == "native":
            bitwise += 1
            for x, y in zip(a, b):
                if not np.array_equal(np.asarray(x), np.asarray(y)):
                    sys.exit(f"bench_chaos --check FAILED: request {i} was "
                             "never degraded yet differs bitwise from the "
                             "fault-free run (cross-request corruption)")
        else:
            degraded += 1
            for x, y in zip(a, b):
                if not np.allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-4, atol=1e-5):
                    sys.exit(f"bench_chaos --check FAILED: request {i} "
                             f"(rung {prov.rung!r}) diverges from the "
                             "fault-free run beyond roundoff")
    p99 = fault[0]["p99_ms"] / 1e3
    if p99 > args.p99_limit:
        sys.exit(f"bench_chaos --check FAILED: faulted p99 {p99:.3f}s "
                 f"exceeds --p99-limit {args.p99_limit}s")
    return {"availability": availability, "bitwise_checked": bitwise,
            "degraded_checked": degraded}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=192)
    ap.add_argument("--rate", type=float, default=800.0,
                    help="Poisson arrival rate, req/s")
    ap.add_argument("--n", type=int, default=8)
    ap.add_argument("--rows", type=int, default=4)
    ap.add_argument("--nrhs", type=int, default=1)
    ap.add_argument("--backend", default="reference",
                    choices=["pallas", "reference"])
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--transient-rate", type=float, default=0.05,
                    help="injected transient executor failure rate "
                         "(per attempt)")
    ap.add_argument("--poison-rate", type=float, default=0.01,
                    help="fraction of requests NaN-poisoned")
    ap.add_argument("--availability-floor", type=float, default=0.99)
    ap.add_argument("--p99-limit", type=float, default=10.0,
                    help="--check ceiling on faulted-run p99, seconds")
    ap.add_argument("--check", action="store_true",
                    help="fixed-seed smoke asserting the acceptance bar "
                         "(availability, bitwise agreement, drills)")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="JSON output path (default ./BENCH_chaos.json)")
    ap.add_argument("--metrics", default=os.environ.get("REPRO_OBS_SNAPSHOT"),
                    metavar="PREFIX",
                    help="collect repro.obs metrics and write PREFIX.jsonl "
                         "+ PREFIX.prom snapshots")
    args = ap.parse_args(argv)
    use_compile_cache()
    if args.check:
        args.requests = min(args.requests, 96)
        args.rate = min(args.rate, 800.0)

    # --check assertions read counters, so always collect in check mode;
    # snapshots are only written when --metrics names a prefix
    reg = None
    if args.metrics or args.check:
        reg = obs.MetricsRegistry()
        obs.install(reg)

    rng = np.random.default_rng(args.seed)
    reqs = make_workload(args.requests, args.n, args.rows, args.nrhs,
                         seed=args.seed)
    reqs, poisoned_idx = poison_workload(reqs, args.poison_rate,
                                         seed=args.seed)
    arrivals = np.cumsum(rng.exponential(1.0 / args.rate, args.requests))

    # warmup compiles every (group, padded-batch) executable outside the
    # measured windows
    warm = ResilientDispatcher(backend=args.backend,
                               max_batch=args.max_batch)
    warm_engine = ContinuousBatcher(warm, admit_max=args.max_batch,
                                    retain_cycles=None)
    for r in reqs:
        warm_engine.submit(r[0], *r[1:])
    warm_engine.flush()
    warm_engine.drain()

    plan = FaultPlan(seed=args.seed, transient_rate=args.transient_rate)
    base = run_chaos(reqs, arrivals, args, plan=None)
    fault = run_chaos(reqs, arrivals, args, plan=plan)

    drilled = ladder_drill(args)
    purge_drill(args)
    postcheck_drill(args)

    checks = {}
    if args.check:
        checks = _check_runs(reqs, poisoned_idx, base, fault, args)
        # every drilled degraded rung must have left a counter trail
        for rung in drilled[1:]:
            if _counter_sum(reg, "serve.degraded_dispatches", to=rung) < 1:
                sys.exit(f"bench_chaos --check FAILED: no degraded dispatch "
                         f"recorded onto rung {rung!r}")
        if _counter_sum(reg, "serve.cycles_purged") < 1:
            sys.exit("bench_chaos --check FAILED: purge drill recorded no "
                     "serve.cycles_purged")

    out = {
        "bench": "bench_chaos", "check": args.check,
        "config": {"requests": args.requests, "rate": args.rate,
                   "n": args.n, "rows": args.rows, "nrhs": args.nrhs,
                   "backend": args.backend, "max_batch": args.max_batch,
                   "seed": args.seed, "transient_rate": args.transient_rate,
                   "poison_rate": args.poison_rate},
        "poisoned_requests": list(poisoned_idx),
        "results": [base[0], fault[0]],
        "drilled_rungs": drilled,
        **checks,
    }
    path = args.out or os.path.join(os.getcwd(), "BENCH_chaos.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)

    print("name,req_per_s,derived")
    for s in (base[0], fault[0]):
        o = s["outcomes"]
        print(f"chaos_{s['mode']}_{args.backend}_n{args.n},"
              f"{s['req_per_s']:.1f},"
              f"p99_ms={s['p99_ms']:.2f};ok={o['ok']};"
              f"poisoned={o['poisoned']};error={o['error']}")
    avail = checks.get("availability")
    print(f"chaos_summary,0,availability="
          f"{avail if avail is not None else 'n/a'};"
          f"rungs={'+'.join(drilled)};path={path}")

    if args.metrics and reg is not None:
        meta = {"bench": "bench_chaos", "backend": args.backend,
                "requests": args.requests,
                "transient_rate": args.transient_rate,
                "poison_rate": args.poison_rate, **checks}
        obs.write_jsonl(f"{args.metrics}.jsonl", reg, meta)
        obs.write_prometheus(f"{args.metrics}.prom", reg)
        print(f"bench_chaos: wrote {args.metrics}.jsonl and "
              f"{args.metrics}.prom", file=sys.stderr)
    if reg is not None:
        obs.uninstall()


if __name__ == "__main__":
    main()
