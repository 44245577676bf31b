"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (derived column carries the
figure-specific metric: ratios, Gflops, % of roofline, bytes).

Paper artifact map:
  bench_counts       -> eqs. 3-5 (multiplication-count models + measured)
  bench_routines     -> fig. 9 / fig. 13a (routine comparison, gemm-normalized)
  bench_pe_analogue  -> fig. 13b (fused-kernel roofline fraction vs dgemm)
  bench_kernels      -> fig. 12 (RDP macro-op kernels: panel / DET2 apply)
  bench_scaling      -> fig. 16 (parallel GGR scaling over mesh sizes)
  bench_update       -> streaming-solver case: batched row-append update
                        throughput vs per-matrix re-factorization
  bench_serve        -> sharded serving: QRServer flush req/s vs device
                        count (mesh-dispatched batched kernel)
  bench_kalman       -> SRIF state estimation: fused-batched kf_step_batched
                        vs dispatch-per-filter stepping
  bench_blocked      -> blocked-QR pipeline shootout: the tree-coupled panel
                        driver vs the reference tile driver, unblocked
                        ggr_qr2 and jnp.linalg.qr (GFLOP/s + speedups);
                        always writes BENCH_blocked.json
  bench_rrqr         -> rank-revealing QR overhead + sketch-preconditioned
                        LSQR iters/residual-gap vs plain LSQR across
                        cond 1e2..1e8; always writes BENCH_rrqr.json

Run all benches with no args, or name a subset: ``python run.py bench_update``.
``--check`` runs bench_blocked in small-shape smoke mode (correctness
asserted, nonzero exit on failure) — the tier-1 CI hook.
"""
from __future__ import annotations

import os
import subprocess
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "src"))

import jax
import jax.numpy as jnp

from repro.launch.cache import use_compile_cache


def _time(fn, *args, reps=5, warmup=2):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e6, out  # us


def bench_counts():
    """eqs. 3-5: model counts + empirically measured multiplication ratio."""
    from repro.core import alpha_ratio, cgr_mults, count_mults, gr_mults
    from repro.core.baselines import _rot_pair
    from repro.core.ggr import ggr_column_step

    rows = []
    for n in (8, 16, 32):
        m_ggr = m_gr = 0
        for c in range(n - 1):
            size = n - c
            A = jnp.zeros((size, size))
            m_ggr += count_mults(ggr_column_step, A)

            def gr_one(A, size=size):
                X = A
                for i in range(size - 1, 0, -1):
                    hi, lo = X[i - 1], X[i]
                    nh, nl = _rot_pair(hi, lo, 0)
                    X = X.at[i - 1].set(nh).at[i].set(nl)
                return X

            m_gr += count_mults(gr_one, A)
        rows.append(
            f"counts_n{n},0,"
            f"cgr_model={cgr_mults(n)};gr_model={gr_mults(n)};"
            f"alpha_model={alpha_ratio(n):.4f};measured_ratio={m_ggr/m_gr:.4f}"
        )
    return rows


def bench_routines():
    """fig. 9 / 13a: QR routine runtimes normalized to dgemm (paper's metric)."""
    from repro.core import (
        cgr_qr,
        ggr_qr2,
        ggr_qr_blocked,
        householder_qr2,
        householder_qrf,
        mht_qr,
    )

    rows = []
    for n in (64, 128, 256):
        A = jnp.asarray(np.random.default_rng(0).standard_normal((n, n)), jnp.float32)
        gemm = jax.jit(lambda x: x @ x)
        t_gemm, _ = _time(gemm, A)
        qr_flops = 4 / 3 * n**3

        for name, fn in [
            ("dgeqr2ggr", jax.jit(ggr_qr2)),
            ("cgr", jax.jit(cgr_qr)),
            ("dgeqr2", jax.jit(householder_qr2)),
            ("dgeqrf", jax.jit(lambda x: householder_qrf(x, block=32))),
            ("dgeqr2ht", jax.jit(lambda x: mht_qr(x, block=32))),
            ("dgeqrfggr", jax.jit(lambda x: ggr_qr_blocked(x, tile=32))),
        ]:
            t, _ = _time(fn, A, reps=3, warmup=1)
            gflops = qr_flops / t / 1e3
            rows.append(
                f"routine_{name}_n{n},{t:.0f},"
                f"gflops={gflops:.2f};vs_gemm_time={t/t_gemm:.2f}"
            )
    return rows


def bench_pe_analogue():
    """fig. 13b analogue: arithmetic intensity + implied v5e roofline fraction
    of the fused GGR trailing update vs dgemm on identical tiles.

    The fused DET2 kernel does 3 VPU flops/element/column with b-fold VMEM
    reuse; dgemm does 2 MXU flops/element/k.  Roofline fraction uses v5e
    constants (197 TFLOP/s MXU, VPU proxy at 1/8 MXU, 819 GB/s HBM).
    """
    HBM = 819e9
    MXU = 197e12
    VPU = MXU / 8
    rows = []
    for m, b, w in [(256, 32, 256), (512, 64, 512), (1024, 128, 512)]:
        flops = 3 * m * w * b + 2 * m * b  # DET2 grid + coeff vectors
        bytes_ = (2 * m * w + 2 * m * b) * 2  # C in+out, V/T in (bf16)
        ai = flops / bytes_
        rows.append(
            f"pe_ggr_apply_m{m}_b{b},0,"
            f"ai={ai:.1f}flops/B;roofline_frac={min(1.0, ai * HBM / VPU):.2f};unit=VPU"
        )
        gf = 2 * m * b * w
        gb = (m * b + b * w + m * w) * 2
        gai = gf / gb
        rows.append(
            f"pe_dgemm_m{m}_b{b},0,"
            f"ai={gai:.1f}flops/B;roofline_frac={min(1.0, gai * HBM / MXU):.2f};unit=MXU"
        )
    return rows


def bench_kernels():
    """fig. 12: RDP macro-op kernels (interpret mode) vs pure-jnp oracle."""
    from repro.kernels import ops, ref

    rows = []
    rng = np.random.default_rng(1)
    for m, b, w in [(128, 16, 64), (256, 32, 128)]:
        pan = jnp.asarray(rng.standard_normal((m, b)), jnp.float32)
        C = jnp.asarray(rng.standard_normal((m, w)), jnp.float32)

        t_pan, (R, V, T) = _time(
            lambda p: ops.panel_qr(p, interpret=True), pan, reps=3, warmup=1
        )
        Rr, Vr, Tr = ref.ref_panel_factor(pan)
        err = float(jnp.abs(R - Rr).max())
        rows.append(f"kernel_panel_m{m}_b{b},{t_pan:.0f},maxerr={err:.1e}")

        t_app, outk = _time(
            lambda V, T, C: ops.apply_panel(V, T, C, block_w=w, interpret=True),
            Vr, Tr, C, reps=3, warmup=1,
        )
        outr = ref.ref_apply_factors(Vr, Tr, C)
        err = float(jnp.abs(outk - outr).max())
        rows.append(f"kernel_apply_m{m}_b{b}_w{w},{t_app:.0f},maxerr={err:.1e}")
    return rows


def _device_counts(row_fn):
    """One ``row_fn(ndev)`` row per device count in (1, 2, 4).

    On a CPU host each count runs in a child process with that many forced
    host devices (the parent sees one CPU device).  On an accelerator the
    parent already holds the chips, and a child could not reach them, so
    every count runs here on the first ``ndev`` of ``jax.devices()``; counts
    above the host's are skipped.
    """
    rows = []
    for ndev in (1, 2, 4):
        if jax.default_backend() != "cpu":
            if ndev <= jax.device_count():
                rows.append(row_fn(ndev))
            continue
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(REPO, "src"), os.path.dirname(__file__)])
        code = f"import run; print('RES,' + run.{row_fn.__name__}({ndev}))"
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=900)
        line = [l for l in out.stdout.splitlines() if l.startswith("RES,")]
        rows.append(line[0][4:] if line else
                    f"{row_fn.__name__}_dev{ndev},0,"
                    f"error={out.stderr[-160:]!r}")
    return rows


def _scaling_row(ndev):
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    from repro.core.distributed import distributed_ggr_qr_1d
    from repro.launch.dryrun import collective_bytes

    mesh = jax.make_mesh((ndev,), ("x",), axis_types=(AxisType.Auto,))
    A = jnp.asarray(np.random.default_rng(0).standard_normal((256, 256)),
                    jnp.float32)
    Aj = jax.device_put(A, NamedSharding(mesh, P(None, "x")))
    fn = jax.jit(lambda X: distributed_ggr_qr_1d(X, mesh, "x", panel=16))
    comp = fn.lower(Aj).compile()
    cb = collective_bytes(comp.as_text())["total"]
    t, _ = _time(fn, Aj, reps=3, warmup=1)
    c = comp.cost_analysis()
    c = c[0] if isinstance(c, list) else c
    return (f"scaling_dev{ndev},{t:.0f},"
            f"per_device_flops={c.get('flops', 0):.3e};collective_bytes={cb}")


def bench_scaling():
    """fig. 16 analogue: distributed GGR QR across mesh sizes (on a CPU host
    the devices are forced host devices on shared cores, so the speedup
    evidence is the per-device compute share + collective bytes from the
    compiled SPMD program)."""
    return _device_counts(_scaling_row)


def bench_update():
    """Streaming update: batched Pallas row-append (one fused launch for the
    whole request batch) vs per-matrix re-factorization from scratch — the
    dispatch a solver service would otherwise issue per request.

    Shape (64->96, 32): each request holds R (32x32) from a 64x32 history and
    appends 32 rows; re-factorization redoes the full 96x32 GGR QR.
    """
    from repro.core import ggr_qr2
    from repro.solvers import qr_append_rows_batched

    rows = []
    rng = np.random.default_rng(2)
    m0, p, n = 64, 32, 32
    for B in (16, 64, 128):
        A = jnp.asarray(rng.standard_normal((B, m0, n)), jnp.float32)
        U = jnp.asarray(rng.standard_normal((B, p, n)), jnp.float32)
        R = jax.jit(jax.vmap(lambda a: ggr_qr2(a)[:n]))(A)
        full = jnp.concatenate([A, U], axis=1)  # (B, m0+p, n) — the redo input

        t_upd, _ = _time(
            lambda R, U: qr_append_rows_batched(R, U, backend="pallas",
                                                interpret=True),
            R, U, reps=5, warmup=2,
        )

        refactor_one = jax.jit(lambda a: ggr_qr2(a)[:n])
        _ = jax.block_until_ready(refactor_one(full[0]))  # compile once

        def refactor_loop(full):
            outs = [refactor_one(full[i]) for i in range(full.shape[0])]
            return outs[-1]

        t_ref, _ = _time(refactor_loop, full, reps=5, warmup=2)
        rows.append(
            f"update_append_B{B}_m{m0}to{m0 + p}_n{n},{t_upd:.0f},"
            f"refactor_us={t_ref:.0f};speedup={t_ref / t_upd:.1f}x;"
            f"per_req_us={t_upd / B:.1f}"
        )
    return rows


_SERVE_REQS = 67


def _serve_row(ndev):
    import contextlib
    import io

    from repro.launch import serve_qr

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        serve_qr.main(["--requests", str(_SERVE_REQS), "--n", "16",
                       "--rows", "8", "--mesh", str(ndev)])
    rps = float([l for l in buf.getvalue().splitlines()
                 if l.startswith("serve_qr_")][0].split(",")[1])
    return (f"serve_dev{ndev},{1e6 / rps:.0f},req_per_s={rps};"
            f"requests={_SERVE_REQS};"
            f"per_shard_batch<={-(-_SERVE_REQS // ndev)}")


def bench_serve():
    """Sharded serving: QRServer flush throughput vs device count.

    The per-shard batch share is the scaling evidence on a CPU host (each
    device's kernel sweeps ceil(B/ndev) problems); measured wall-clock is
    still recorded.  67 requests on purpose: the append group lands at a
    non-block_b-multiple size, so this row regresses the pad-to-multiple
    path (pre-fix the kernel degraded such batches toward one-problem grid
    steps).
    """
    return _device_counts(_serve_row)


def bench_kalman():
    """SRIF fleet stepping: one fused kf_step_batched dispatch for B filters
    vs the per-filter jit'd kf_step loop a naive tracker would issue.

    Constant-velocity 2-D tracking shape (n=4 state, p=2 position
    measurements, w=2 process-noise inputs) — the high-traffic
    state-estimation workload the serving front-door batches.
    """
    from repro.solvers import (
        KalmanState,
        info_sqrt,
        kf_init,
        kf_step,
        kf_step_batched,
    )

    rows = []
    rng = np.random.default_rng(3)
    dt = 0.1
    F = np.eye(4, dtype=np.float32)
    F[0, 2] = F[1, 3] = dt
    G = np.vstack([dt**2 / 2 * np.eye(2), dt * np.eye(2)]).astype(np.float32)
    Fj, Gj = jnp.asarray(F), jnp.asarray(G)
    Qi = info_sqrt(jnp.asarray(0.05 * np.eye(2), jnp.float32))
    H = np.hstack([np.eye(2), np.zeros((2, 2))]).astype(np.float32)
    W = info_sqrt(jnp.asarray(0.2 * np.eye(2), jnp.float32))
    Hw = W @ jnp.asarray(H)
    st0 = kf_init(jnp.zeros(4, jnp.float32),
                  jnp.asarray(np.diag([4.0, 4.0, 1.0, 1.0]), jnp.float32))

    step_one = jax.jit(lambda R, d, z: kf_step(
        KalmanState(R, d, jnp.zeros((), jnp.int32)), Fj, Qi, Hw, z, Gj)[:2])
    step_all = jax.jit(lambda R, d, z: kf_step_batched(
        R, d, Fj, Qi, Hw, z, Gj, backend="pallas", interpret=True))

    for B in (16, 64, 128):
        Rb = jnp.stack([st0.R] * B)
        db = jnp.stack([st0.d] * B)
        # whitened measurements — valid SRIF steps for the stated model
        zb = jnp.asarray(rng.standard_normal((B, 2)), jnp.float32) @ W.T

        t_bat, _ = _time(step_all, Rb, db, zb, reps=5, warmup=2)

        def per_filter(Rb, db, zb):
            outs = [step_one(Rb[i], db[i], zb[i]) for i in range(Rb.shape[0])]
            return outs[-1][0]

        t_loop, _ = _time(per_filter, Rb, db, zb, reps=5, warmup=2)
        rows.append(
            f"kalman_step_B{B}_n4_p2,{t_bat:.0f},"
            f"per_filter_us={t_loop:.0f};speedup={t_loop / t_bat:.1f}x;"
            f"per_req_us={t_bat / B:.1f}"
        )
    return rows


_CHECK = False  # set by --check: small shapes, assert correctness, hard-fail


def bench_blocked():
    """Blocked-QR pipeline shootout (the perf trajectory artifact).

    The tree-coupled panel driver (``ggr_qr_blocked``) against the previous
    Python-unrolled tile driver (``ggr_qr_blocked_reference``), the unblocked
    ``ggr_qr2`` sweep, the fused VMEM-residency schedule and ``jnp.linalg.qr``
    on square f32 problems.  Emits GFLOP/s (QR flops = 4/3 n^3), max |R| error
    vs a float64 LAPACK oracle, and the wall-clock speedup of the new driver
    over the reference tiles.  Always writes ``BENCH_blocked.json`` next to
    the CSV output so CI can track the trajectory; ``--check`` shrinks the
    shapes to smoke size and asserts correctness with a nonzero exit.
    """
    import json

    from repro.core import ggr_qr2, ggr_qr_blocked, ggr_qr_blocked_reference

    rows, records = [], []
    rng = np.random.default_rng(5)
    sizes = [256] if _CHECK else [512, 1024]
    reps, warmup = (1, 1) if _CHECK else (3, 1)
    failures = []
    for n in sizes:
        A = jnp.asarray(rng.standard_normal((n, n)), jnp.float32)
        Rnp = np.linalg.qr(np.asarray(A, np.float64), mode="r")
        flops = 4.0 / 3.0 * n**3
        ref_tile = 128 if n % 128 == 0 else 64
        entries = [
            ("blocked_tree", lambda x: ggr_qr_blocked(x, schedule="tree")),
            ("reference_tiles",
             lambda x: ggr_qr_blocked_reference(x, tile=ref_tile)),
            ("linalg_qr", jax.jit(lambda x: jnp.linalg.qr(x, mode="r"))),
        ]
        if n <= 512:  # the unblocked sweep and the fused interpret-mode
            entries.append(("ggr_qr2", jax.jit(ggr_qr2)))  # schedule are slow
            entries.append(("blocked_fused",
                            lambda x: ggr_qr_blocked(x, schedule="fused")))
        timings = {}
        for name, fn in entries:
            t, R = _time(fn, A, reps=reps, warmup=warmup)
            R = np.abs(np.asarray(R)[:n])
            err = float(np.abs(R - np.abs(Rnp)).max())
            gflops = flops / t / 1e3
            timings[name] = t
            rows.append(f"blocked_{name}_n{n},{t:.0f},"
                        f"gflops={gflops:.2f};maxerr={err:.1e}")
            records.append({"name": name, "n": n, "us_per_call": t,
                            "gflops": gflops, "maxerr": err})
            if err > 5e-3:
                failures.append(f"{name} n={n}: maxerr {err:.2e}")
        speedup = timings["reference_tiles"] / timings["blocked_tree"]
        rows.append(f"blocked_speedup_n{n},0,"
                    f"tree_vs_reference={speedup:.2f}x")
        records.append({"name": "speedup_tree_vs_reference", "n": n,
                        "value": speedup})
    out = {"bench": "bench_blocked", "check": _CHECK, "results": records}
    path = os.path.join(os.getcwd(), "BENCH_blocked.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    rows.append(f"blocked_json,0,path={path}")
    if _CHECK and failures:
        sys.exit("bench_blocked --check FAILED: " + "; ".join(failures))
    return rows


def bench_precision():
    """Error-vs-throughput curves across precision policies.

    For each graded problem (controlled condition number via
    ``repro.testing.graded_matrix``) the blocked driver runs under the
    ``f32`` and ``bf16`` (f32-accumulation) policies; each point records
    achieved GFLOP/s next to the harness error metrics, so the artifact
    answers "what does bf16 buy and what does it cost" in one table.  The
    serving section records the dispatch-block scaling bf16 storage earns
    (``Dispatcher.block_b_for``) per shape class plus measured ``QRServer``
    flush throughput per store dtype.  Always writes
    ``BENCH_precision.json``; ``--check`` asserts the documented error
    budgets AND that bf16 storage rides >= 2x the f32 dispatch block on at
    least one serving shape class.
    """
    import json

    from repro.core.blocked import ggr_triangularize_blocked
    from repro.launch.serve_qr import QRServer
    from repro.obs import ggr_sweep_flops
    from repro.serve import Dispatcher
    from repro.testing import error_budget, factorization_errors, graded_matrix

    rows, records, failures = [], [], []
    shapes = [(96, 80)] if _CHECK else [(256, 192), (384, 256)]
    conds = (1e0, 1e8) if _CHECK else (1e0, 1e4, 1e8)
    reps, warmup = (1, 1) if _CHECK else (3, 1)
    policies = [("f32", "float32"), ("bf16", "bfloat16")]
    for m, n in shapes:
        flops = ggr_sweep_flops(m, n, n)
        for cond in conds:
            A = graded_matrix(m, n, cond, seed=17)
            A32 = jnp.asarray(A, jnp.float32)
            for policy, dtype in policies:
                t, R = _time(
                    lambda x, p=policy: ggr_triangularize_blocked(
                        x, precision=p),
                    A32, reps=reps, warmup=warmup)
                errs = factorization_errors(A, R)
                gflops = flops / t / 1e3
                gram = errs["gram_residual"]
                rows.append(
                    f"precision_{policy}_m{m}n{n}_cond{cond:.0e},{t:.0f},"
                    f"gflops={gflops:.2f};gram={gram:.2e}")
                records.append({"name": "blocked", "policy": policy,
                                "m": m, "n": n, "cond": cond,
                                "us_per_call": t, "gflops": gflops, **errs})
                budget = error_budget(dtype, "gram_residual", m, n, cond)
                if gram > budget:
                    failures.append(f"{policy} {m}x{n} cond={cond:.0e}: "
                                    f"gram {gram:.2e} > budget {budget:.2e}")

    # serving: dispatch-block scaling per shape class + flush throughput
    disp = Dispatcher(block_b=8)
    block_ratios = {}
    for kind in ("append", "lstsq", "kalman"):
        b32 = disp.padded_chunk(1, kind, "float32")
        b16 = disp.padded_chunk(1, kind, "bfloat16")
        block_ratios[kind] = b16 / b32
        rows.append(f"precision_block_{kind},0,f32={b32};bf16={b16}")
        records.append({"name": "dispatch_block", "kind": kind,
                        "padded_f32": b32, "padded_bf16": b16,
                        "ratio": b16 / b32})
    if not any(r >= 2.0 for r in block_ratios.values()):
        failures.append(f"no serving shape class gives bf16 storage a >=2x "
                        f"dispatch block (ratios {block_ratios})")

    rng = np.random.default_rng(23)
    nserve, pserve, breqs = 16, 4, 32
    Rs = np.triu(rng.standard_normal((nserve, nserve))) + 2 * np.eye(nserve)
    Us = rng.standard_normal((pserve, nserve))
    for store, policy in (("float32", None), ("bfloat16", "bf16")):
        server = QRServer(backend="pallas", interpret=True, precision=policy)
        Rj = jnp.asarray(Rs, jnp.dtype(store))
        Uj = jnp.asarray(Us, jnp.dtype(store))
        for _ in range(breqs):  # warm the executable cache
            server.submit_append(Rj, Uj)
        server.flush()
        server.drain()
        t0 = time.perf_counter()
        for _ in range(breqs):
            server.submit_append(Rj, Uj)
        server.flush()
        server.drain()
        dt = time.perf_counter() - t0
        rps = breqs / dt
        rows.append(f"precision_serve_{store},{dt * 1e6 / breqs:.0f},"
                    f"reqs_per_s={rps:.0f}")
        records.append({"name": "serve_append", "store_dtype": store,
                        "policy": policy or "none", "n": nserve, "p": pserve,
                        "reqs_per_s": rps})

    out = {"bench": "bench_precision", "check": _CHECK, "results": records}
    path = os.path.join(os.getcwd(), "BENCH_precision.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    rows.append(f"precision_json,0,path={path}")
    if _CHECK and failures:
        sys.exit("bench_precision --check FAILED: " + "; ".join(failures))
    return rows


def bench_rrqr():
    """Rank-revealing QR + sketch-preconditioned least-squares trade curves.

    Section 1 — pivoting overhead: ``ggr_qr_pivoted`` vs the same unpivoted
    size-routed driver it reduces through, plus rank correctness on a
    rank-deficient input (``estimate_rank`` vs the constructed truth).
    Section 2 — ``sketch_lstsq`` vs plain (unpreconditioned) LSQR across
    cond 1e2..1e8 on tall-skinny problems built with a known residual
    (``b = A x0 + r0`` with ``r0`` projected out of range(A), so the oracle
    residual is exactly ``||r0||``): iterations taken and the relative
    residual gap to the oracle.  Full mode adds the acceptance shape
    (100k x 256 at cond 1e8) where plain LSQR cannot converge in the same
    iteration budget; ``--check`` asserts the identical contracts on small
    shapes — sketch gap <= 1e-6 within 50 iterations, plain LSQR gap
    > 1e-6 at cond 1e8, exact rank recovery.  Always writes
    ``BENCH_rrqr.json``.  Enables x64 (f64 oracles) for the rest of the
    process, so it runs last in the default bench order.
    """
    import json

    jax.config.update("jax_enable_x64", True)

    from repro.ranks import estimate_rank, ggr_qr_pivoted, lsqr, sketch_lstsq
    from repro.solvers.lstsq import _triangularize_auto
    from repro.testing import graded_matrix, rank_deficient_matrix

    rows, records, failures = [], [], []
    reps, warmup = (1, 1) if _CHECK else (3, 1)

    # --- section 1: pivoting overhead + rank correctness -------------------
    shapes = [(256, 64)] if _CHECK else [(1024, 128), (2048, 256)]
    for m, n in shapes:
        A = jnp.asarray(graded_matrix(m, n, 1e4, seed=5))
        unpiv = jax.jit(lambda x, n=n: jnp.triu(_triangularize_auto(x, n)[:n]))
        t_u, _ = _time(unpiv, A, reps=reps, warmup=warmup)
        t_p, st = _time(lambda x: ggr_qr_pivoted(x), A,
                        reps=reps, warmup=warmup)
        overhead = t_p / t_u
        rows.append(f"rrqr_pivot_m{m}n{n},{t_p:.0f},"
                    f"unpivoted_us={t_u:.0f};overhead={overhead:.2f}x")
        records.append({"name": "pivot_overhead", "m": m, "n": n,
                        "us_pivoted": t_p, "us_unpivoted": t_u,
                        "overhead": overhead})

        true_rank = n // 2
        Ad = jnp.asarray(rank_deficient_matrix(m, n, true_rank,
                                               cond=1e6, seed=7))
        rk = int(estimate_rank(ggr_qr_pivoted(Ad).R))
        rows.append(f"rrqr_rank_m{m}n{n},0,est={rk};true={true_rank}")
        records.append({"name": "rank_recovery", "m": m, "n": n,
                        "rank_true": true_rank, "rank_est": rk})
        if rk != true_rank:
            failures.append(f"rank {m}x{n}: est {rk} != true {true_rank}")

    # --- section 2: sketch-preconditioned vs plain LSQR --------------------
    conds = (1e2, 1e8) if _CHECK else (1e2, 1e4, 1e6, 1e8)
    sk_shapes = [(2048, 64)] if _CHECK else [(16384, 128)]
    cases = [(m, n, c) for m, n in sk_shapes for c in conds]
    if not _CHECK:
        cases.append((100_000, 256, 1e8))  # the acceptance shape
    for m, n, cond in cases:
        A64 = graded_matrix(m, n, cond, seed=11)
        rng = np.random.default_rng(211)
        x0 = rng.standard_normal(n)
        r0 = rng.standard_normal(m)
        Q, _ = np.linalg.qr(A64)
        r0 -= Q @ (Q.T @ r0)           # r0 _|_ range(A): oracle resid = ||r0||
        r0 *= 0.1 / np.linalg.norm(r0)
        oracle = float(np.linalg.norm(r0))
        Aj = jnp.asarray(A64)
        bj = jnp.asarray(A64 @ x0 + r0)

        r = 1 if m >= 100_000 else reps
        t_s, fit = _time(lambda a, b: sketch_lstsq(a, b, iters=50, tol=1e-12),
                         Aj, bj, reps=r, warmup=1)
        gap_s = abs(float(fit.resid) - oracle) / oracle
        it_s = int(fit.iters)
        _, it_p, rn_p, _ = lsqr(Aj, bj, iters=50, tol=1e-12)
        gap_p = abs(float(rn_p) - oracle) / oracle
        rows.append(f"rrqr_sketch_m{m}n{n}_cond{cond:.0e},{t_s:.0f},"
                    f"iters={it_s};gap={gap_s:.1e};"
                    f"plain_iters={int(it_p)};plain_gap={gap_p:.1e}")
        records.append({"name": "sketch_lstsq", "m": m, "n": n, "cond": cond,
                        "us_per_call": t_s, "iters": it_s, "resid_gap": gap_s,
                        "plain_iters": int(it_p), "plain_resid_gap": gap_p,
                        "oracle_resid": oracle})
        if gap_s > 1e-6:
            failures.append(f"sketch {m}x{n} cond={cond:.0e}: "
                            f"resid gap {gap_s:.2e} > 1e-6 in {it_s} iters")
        if cond >= 1e8 and gap_p <= 1e-6:
            failures.append(f"plain LSQR {m}x{n} cond={cond:.0e}: "
                            f"converged (gap {gap_p:.2e}) — preconditioning "
                            f"advantage not exercised")

    out = {"bench": "bench_rrqr", "check": _CHECK, "results": records}
    path = os.path.join(os.getcwd(), "BENCH_rrqr.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    rows.append(f"rrqr_json,0,path={path}")
    if _CHECK and failures:
        sys.exit("bench_rrqr --check FAILED: " + "; ".join(failures))
    return rows


BENCHES = [bench_counts, bench_routines, bench_pe_analogue, bench_kernels,
           bench_scaling, bench_update, bench_serve, bench_kalman,
           bench_blocked, bench_precision, bench_rrqr]


def main() -> None:
    global _CHECK
    args = sys.argv[1:]
    if "--check" in args:
        _CHECK = True
        args = [a for a in args if a != "--check"]
    wanted = args
    if _CHECK and not wanted:
        wanted = ["bench_blocked"]
    by_name = {b.__name__: b for b in BENCHES}
    unknown = [w for w in wanted if w not in by_name]
    if unknown:
        sys.exit(f"unknown bench(es) {unknown}; choose from {sorted(by_name)}")
    benches = [by_name[w] for w in wanted] if wanted else BENCHES
    use_compile_cache()
    print("name,us_per_call,derived")
    failed = []
    for bench in benches:
        try:
            for row in bench():
                print(row, flush=True)
                if ",error=" in row:
                    failed.append(row.split(",")[0])
        except SystemExit:
            raise
        except Exception as e:  # pragma: no cover
            print(f"{bench.__name__},0,error={type(e).__name__}:{e}",
                  flush=True)
            failed.append(bench.__name__)
    if failed:
        sys.exit(f"benchmarks failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
