"""Smoke run of the GGR serving path on a TPU: one chip, or four with --chips 4.

Drives the closed-loop serving entry point (``repro.launch.serve_qr.QRServer``
-> ``repro.serve`` -> the compiled Pallas kernels) once, in this process, at
widths a solver service runs, and checks what comes out:

* 4096 mixed requests from ``make_workload`` at n=128, 8 appended rows and
  one rhs column: row appends (w = 129), 128-state SRIF Kalman steps, and
  512 x 128 ``lstsq`` / ``lstsq_pivoted`` solves, which go through the
  blocked fused driver under ``vmap``;
* a tracking fleet: 4096 Kalman steps at n=6, p=3 under one shared model.

Each workload gets a warm flush (which compiles), then a timed flush and
``drain()``; the times are host-clock wall times, printed for information.
A sample of results is checked: appends and Kalman steps against
``backend="reference"`` run under highest matmul precision, solves against
numpy's float64 ``lstsq`` (the reference backend runs the same kernels for
the solve kinds).  Tolerances are ``repro.testing.error_harness`` budgets.
The run fails on any interpret-mode kernel resolution, on any degraded
dispatch, and wherever JAX finds no TPU.

``--chips 4`` runs only the sharded path: the same 4096 requests flushed
over a 4-device batch mesh, and the single-device flush they must match.

    python chip_smoke.py [--chips 4]

The last line of stdout is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

N, ROWS, NRHS, REQUESTS, SEED = 128, 8, 1, 4096, 0
FLEET_N, FLEET_P = 6, 3
# checked requests: one in this many (odd, so every kind of the workload's
# period-8 mix is sampled)
SAMPLE_EVERY = 61
SHARD_SAMPLE_EVERY = 7


def _fail(msg: str):
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def _serve(server, reqs, label: str) -> list:
    """Warm flush, then a timed flush + drain; returns the timed tickets."""
    from repro.launch.serve_qr import _submit_all

    t0 = time.perf_counter()
    _submit_all(server, reqs)
    server.flush()
    server.drain()
    warm = time.perf_counter() - t0
    tickets = _submit_all(server, reqs)
    t0 = time.perf_counter()
    served = server.flush()
    server.drain()
    wall = time.perf_counter() - t0
    if served != len(reqs):
        _fail(f"{label}: served {served} of {len(reqs)} requests")
    print(f"{label}: served={served} wall_s={wall!r} "
          f"warmup_incl_compile_s={warm!r}", flush=True)
    return tickets


def fleet_workload(tracks: int, n: int, p: int, seed: int):
    """``tracks`` SRIF Kalman steps of one shared (F, Qi, H) model."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    F = jnp.asarray(np.eye(n) + 0.1 * rng.standard_normal((n, n)), jnp.float32)
    Qi = np.triu(rng.standard_normal((n, n)))
    np.fill_diagonal(Qi, np.abs(np.diag(Qi)) + 1.0)
    Qi = jnp.asarray(Qi, jnp.float32)
    H = jnp.asarray(rng.standard_normal((p, n)), jnp.float32)
    reqs = []
    for _ in range(tracks):
        R = np.triu(rng.standard_normal((n, n))).astype(np.float32)
        np.fill_diagonal(R, np.abs(np.diag(R)) + 1.0)
        reqs.append(("kalman", R, rng.standard_normal(n).astype(np.float32),
                     F, Qi, H, rng.standard_normal(p).astype(np.float32)))
    return reqs


def _f64(x) -> np.ndarray:
    return np.asarray(x, dtype=np.float64)


def _factor_gap(served, ref) -> float:
    """Gram agreement of two ``(R[, d])`` results, in float64.

    ``||Rs^T Rs - Rr^T Rr|| / ||Rr^T Rr||`` and, with an rhs,
    ``||Rs^T ds - Rr^T dr|| / (||Rr|| ||dr||)``: both factors reproduce the
    same Gram data, so each gap is at most twice one factorization's
    condition-free gram residual.
    """
    Rs, Rr = _f64(served[0]), _f64(ref[0])
    G = Rr.T @ Rr
    gap = np.linalg.norm(Rs.T @ Rs - G) / np.linalg.norm(G)
    if len(served) > 1:
        ds, dr = _f64(served[1]), _f64(ref[1])
        ds, dr = ds.reshape(len(Rs), -1), dr.reshape(len(Rr), -1)
        gap = max(gap, np.linalg.norm(Rs.T @ ds - Rr.T @ dr)
                  / (np.linalg.norm(Rr) * np.linalg.norm(dr)))
    return float(gap)


def _solve_gap(req, served) -> tuple[float, float]:
    """(gap, budget) of a solve against numpy's float64 least squares."""
    from repro.testing.error_harness import error_budget

    A, b = _f64(req[1]), _f64(req[2])
    m, n = A.shape
    s = np.linalg.svd(A, compute_uv=False)
    if req[0] == "lstsq_pivoted":
        rank = -(-n // 2)  # make_workload's construction rank
        if int(served[2]) != rank:
            _fail(f"lstsq_pivoted: served rank {int(served[2])} != {rank}")
        x, *_ = np.linalg.lstsq(A, b, rcond=1e-5)
        cond = s[0] / s[rank - 1]
    else:
        x, *_ = np.linalg.lstsq(A, b, rcond=None)
        cond = s[0] / s[-1]
    r = np.linalg.norm(A @ x - b, axis=0)
    gap = max(np.linalg.norm(_f64(served[0]).reshape(x.shape) - x)
              / np.linalg.norm(x),
              np.max(np.abs(_f64(served[1]).reshape(r.shape) - r))
              / np.max(r))
    return float(gap), error_budget("float32", "forward_error", m, n, cond)


def check_against_reference(reqs, tickets, server, label: str) -> None:
    """Check every SAMPLE_EVERY-th request of ``reqs`` (see module doc)."""
    import jax

    from repro.launch.serve_qr import QRServer, _as_tuple, _submit_all
    from repro.testing.error_harness import error_budget

    idx = range(0, len(reqs), SAMPLE_EVERY)
    factored = [i for i in idx if reqs[i][0] in ("append", "kalman")]
    ref = QRServer(backend="reference")
    with jax.default_matmul_precision("highest"):
        rticks = _submit_all(ref, [reqs[i] for i in factored])
        ref.flush()
        ref.drain()
    rmap = dict(zip(factored, rticks))
    worst: dict = {}
    for i in idx:
        req, out = reqs[i], _as_tuple(server.result(tickets[i]))
        if not all(np.isfinite(_f64(x)).all() for x in out):
            _fail(f"{label}: request {i} ({req[0]}) has non-finite output")
        if i in rmap:
            n = len(req[1])
            if req[0] == "append":
                m = n + len(req[2])
            else:  # SRIF stack: (w + 2n + p) rows, w + n pivots
                w = len(req[4])
                m, n = w + 2 * n + len(req[5]), w + n
            gap = _factor_gap(out, _as_tuple(ref.result(rmap[i])))
            budget = 2 * error_budget("float32", "gram_residual", m, n)
        else:
            gap, budget = _solve_gap(req, out)
        seen = worst.setdefault(req[0], [0, 0.0])
        seen[0] += 1
        seen[1] = max(seen[1], gap / budget)
        if gap > budget:
            _fail(f"{label}: request {i} ({req[0]}) differs from the "
                  f"reference by {gap!r}, budget {budget!r}")
    for k, (count, ratio) in sorted(worst.items()):
        print(f"{label}: reference check kind={k} samples={count} "
              f"max_gap_over_budget={ratio!r}", flush=True)


def run_one_chip() -> None:
    from repro import obs
    from repro.launch.serve_qr import QRServer, make_workload

    reqs = make_workload(REQUESTS, N, ROWS, NRHS, seed=SEED)
    fleet = fleet_workload(REQUESTS, FLEET_N, FLEET_P, seed=SEED + 1)
    with obs.collecting() as reg:
        server = QRServer(backend="pallas")
        tickets = _serve(server, reqs, f"mixed n={N} p={ROWS}")
        check_against_reference(reqs, tickets, server, "mixed")
        fserver = QRServer(backend="pallas")
        ftickets = _serve(fserver, fleet, f"fleet n={FLEET_N} p={FLEET_P}")
        check_against_reference(fleet, ftickets, fserver, "fleet")
    compiled = reg.find("kernels.interpret_resolutions", mode="compiled")
    interp = reg.find("kernels.interpret_resolutions", mode="interpret")
    degraded = sum(m.value for m in reg.collect()
                   if m.name == "serve.degraded_dispatches")
    print(f"kernel resolutions: compiled="
          f"{0 if compiled is None else int(compiled.value)} interpret="
          f"{0 if interp is None else int(interp.value)}; "
          f"degraded dispatches={int(degraded)}", flush=True)
    if compiled is None or compiled.value == 0:
        _fail("no compiled kernel was dispatched")
    if interp is not None and interp.value > 0:
        _fail(f"{int(interp.value)} kernel calls resolved to interpret mode")
    if degraded:
        _fail(f"{int(degraded)} dispatches were degraded")


def run_sharded(chips: int) -> None:
    import jax

    from repro.launch.serve_qr import QRServer, _as_tuple, make_workload
    from repro.parallel.sharding import make_batch_mesh

    if jax.device_count() < chips:
        _fail(f"--chips {chips} needs {chips} devices, JAX sees "
              f"{jax.device_count()}")
    reqs = make_workload(REQUESTS, N, ROWS, NRHS, seed=SEED)
    sharded = QRServer(backend="pallas", mesh=make_batch_mesh(chips))
    ts = _serve(sharded, reqs, f"sharded x{chips}")
    spans = {len(a.sharding.device_set) for a in jax.live_arrays()}
    if chips not in spans:
        _fail(f"no array of the sharded flush spans all {chips} devices")
    single = QRServer(backend="pallas")
    t1 = _serve(single, reqs, "single device")
    for i in range(0, len(reqs), SHARD_SAMPLE_EVERY):
        for a, b in zip(_as_tuple(sharded.result(ts[i])),
                        _as_tuple(single.result(t1[i]))):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)
    print(f"sharded x{chips} == single device on "
          f"{len(range(0, len(reqs), SHARD_SAMPLE_EVERY))} sampled requests",
          flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded flush and the "
                         "single-device flush it is compared with")
    args = ap.parse_args(argv)

    from repro.kernels import default_interpret
    from repro.launch.cache import use_compile_cache

    import jax

    use_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        _fail(f"no TPU found: JAX's first device is {dev.platform!r}")
    if os.environ.get("REPRO_PALLAS_INTERPRET") is not None or default_interpret():
        _fail("REPRO_PALLAS_INTERPRET is set: the kernels must compile")
    print(f"device: {dev.device_kind} x{jax.device_count()}", flush=True)
    if args.chips == 1:
        run_one_chip()
    else:
        run_sharded(args.chips)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}), flush=True)


if __name__ == "__main__":
    main()
