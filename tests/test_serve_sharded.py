"""Sharded batch serving: mesh-dispatched flushes vs single-device truth.

Two layers of coverage:

* in-process tests that require a multi-device runtime — they skip on the
  default single-device tier-1 run and execute in the dedicated CI job that
  sets ``XLA_FLAGS=--xla_force_host_platform_device_count=4``;
* subprocess tests that force a 4-device host platform themselves, so the
  sharded path is exercised on every tier-1 run (per the dry-run isolation
  rule the main pytest process must keep the single real CPU device).
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, ndev: int = 4, args=()):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code), *args],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


def _mesh_or_skip(n=4):
    if jax.device_count() < n:
        pytest.skip(f"needs {n} devices (run the multi-device CI job: "
                    f"XLA_FLAGS=--xla_force_host_platform_device_count={n})")
    from repro.parallel.sharding import make_batch_mesh

    return make_batch_mesh(n)


# ------------------------------------------------------- in-process (>=4 dev)

@pytest.mark.parametrize("B", [1, 7, 67])
def test_sharded_append_matches_single_device(B):
    """Sharded flush must be numerically identical to the one-device kernel
    (same padded grid per shard => bitwise-equal interpret-mode results)."""
    from repro.solvers import qr_append_rows_batched

    mesh = _mesh_or_skip(4)
    n, p, k = 6, 3, 2
    rng = np.random.default_rng(50 + B)
    Rb = jnp.asarray(np.triu(rng.standard_normal((B, n, n))), jnp.float32)
    Ub = jnp.asarray(rng.standard_normal((B, p, n)), jnp.float32)
    db = jnp.asarray(rng.standard_normal((B, n, k)), jnp.float32)
    Yb = jnp.asarray(rng.standard_normal((B, p, k)), jnp.float32)
    Rs, ds = qr_append_rows_batched(Rb, Ub, db, Yb, backend="pallas",
                                    interpret=True, mesh=mesh)
    R1, d1 = qr_append_rows_batched(Rb, Ub, db, Yb, backend="pallas",
                                    interpret=True)
    np.testing.assert_array_equal(np.asarray(Rs), np.asarray(R1))
    np.testing.assert_array_equal(np.asarray(ds), np.asarray(d1))


def test_sharded_server_round_trip():
    from repro.launch.serve_qr import QRServer, make_workload, _submit_all

    mesh = _mesh_or_skip(4)
    reqs = make_workload(13, n=6, rows=3, k=1, seed=51)
    sharded = QRServer(backend="pallas", interpret=True, mesh=mesh)
    single = QRServer(backend="pallas", interpret=True)
    ts, t1 = _submit_all(sharded, reqs), _submit_all(single, reqs)
    assert sharded.flush() == len(reqs) and single.flush() == len(reqs)
    for a, b in zip(ts, t1):
        ra, rb = sharded.result(a), single.result(b)
        for xa, xb in zip(ra, rb):
            np.testing.assert_allclose(np.asarray(xa), np.asarray(xb),
                                       rtol=1e-6, atol=1e-6)


def test_sharded_reference_backend():
    from repro.solvers import qr_append_rows_batched

    mesh = _mesh_or_skip(4)
    rng = np.random.default_rng(52)
    Rb = jnp.asarray(np.triu(rng.standard_normal((10, 5, 5))), jnp.float32)
    Ub = jnp.asarray(rng.standard_normal((10, 2, 5)), jnp.float32)
    Rs = qr_append_rows_batched(Rb, Ub, backend="reference", mesh=mesh)
    R1 = qr_append_rows_batched(Rb, Ub, backend="reference")
    np.testing.assert_array_equal(np.asarray(Rs), np.asarray(R1))


# ------------------------------------------------------ subprocess (any host)

def test_sharded_flush_matches_single_device_subprocess():
    """End-to-end: a 4-way sharded QRServer flush of a mixed 19-request
    workload (odd group sizes => padding on every path) agrees with the
    single-device flush to roundoff."""
    _run(
        """
        import numpy as np, jax
        from repro.launch.serve_qr import QRServer, make_workload, _submit_all
        from repro.parallel.sharding import make_batch_mesh
        assert jax.device_count() == 4, jax.device_count()
        mesh = make_batch_mesh(4)
        reqs = make_workload(19, n=6, rows=3, k=1, seed=53)
        sharded = QRServer(backend="pallas", interpret=True, mesh=mesh)
        single = QRServer(backend="pallas", interpret=True)
        ts, t1 = _submit_all(sharded, reqs), _submit_all(single, reqs)
        assert sharded.flush() == 19 and single.flush() == 19
        for a, b in zip(ts, t1):
            for xa, xb in zip(sharded.result(a), single.result(b)):
                np.testing.assert_allclose(np.asarray(xa), np.asarray(xb),
                                           rtol=1e-6, atol=1e-6)
        print("SHARDED_OK")
        """
    )


def test_serve_qr_cli_csv_well_formed():
    """--check must emit exactly-3-field CSV rows (the xbackend error folds
    into the derived column) with no stray spaces."""
    out = _run(
        """
        import sys
        from repro.launch.serve_qr import main
        main(sys.argv[1:])
        """,
        ndev=4,
        args=["--requests", "11", "--n", "6", "--rows", "3",
              "--mesh", "4", "--check"],
    )
    lines = [l for l in out.strip().splitlines() if "," in l]
    assert lines[0] == "name,req_per_s,derived"
    assert len(lines) == 2
    row = lines[1].split(",")
    assert len(row) == 3, row
    assert " " not in lines[1], lines[1]
    assert row[0].startswith("serve_qr_pallas_n6_p3")
    float(row[1])  # throughput parses
    derived = dict(kv.split("=") for kv in row[2].split(";"))
    assert derived["mesh"] == "4" and derived["max_batch"] == "64"
    float(derived["xbackend_maxerr"])


def test_serve_qr_cli_rejects_oversized_mesh():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve_qr", "--mesh", "8"],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode != 0
    assert "8-device batch mesh" in out.stderr


# ------------------------------------------- continuous batching (PR 7 layers)

def test_kind_restricted_flush_keeps_other_groups_live():
    """flush(kind=...) must dispatch ONLY matching groups: other kinds stay
    queued (still-pending KeyError), and their tickets must NOT be expired —
    they resolve normally once their own kind flushes."""
    from repro.launch.serve_qr import QRServer, make_workload, _submit_all

    reqs = make_workload(9, n=5, rows=2, k=1, seed=54)
    server = QRServer(backend="reference")
    tickets = _submit_all(server, reqs)
    by_kind = {}
    for r, t in zip(reqs, tickets):
        by_kind.setdefault(r[0], []).append(t)
    assert set(by_kind) == {"append", "lstsq", "kalman", "lstsq_pivoted"}

    served = server.flush(kind="kalman")
    assert served == len(by_kind["kalman"])
    for t in by_kind["kalman"]:
        server.result(t)
    for t in (by_kind["append"] + by_kind["lstsq"]
              + by_kind["lstsq_pivoted"]):
        with pytest.raises(KeyError, match="not yet flushed"):
            server.result(t)

    server.flush(kind="lstsq")
    for t in by_kind["lstsq"]:
        server.result(t)
    # the kalman tickets are STILL live: other-kind flushes never advance
    # their group's cycle
    for t in by_kind["kalman"]:
        server.result(t)
    server.flush()
    for t in by_kind["append"] + by_kind["lstsq_pivoted"]:
        server.result(t)


def test_deadline_close_resolves_like_explicit_flush():
    """A deadline-closed batch must store results exactly like flush():
    same tickets, same cycle, bitwise-equal arrays."""
    from repro.launch.serve_qr import make_workload
    from repro.serve import (AdmissionPolicy, ContinuousBatcher, Dispatcher,
                             LatencyTier)

    class Clock:
        t = 0.0

        def __call__(self):
            return self.t

    reqs = make_workload(8, n=5, rows=2, k=1, seed=55)
    clock = Clock()
    tiers = {k: LatencyTier(deadline=1.0) for k in ("append", "lstsq",
                                                    "kalman",
                                                    "lstsq_pivoted")}
    by_deadline = ContinuousBatcher(Dispatcher(backend="reference"),
                                    AdmissionPolicy(tiers=tiers),
                                    retain_cycles=None, clock=clock)
    by_flush = ContinuousBatcher(Dispatcher(backend="reference"),
                                 retain_cycles=None)
    td = [by_deadline.submit(r[0], *r[1:]) for r in reqs]
    tf = [by_flush.submit(r[0], *r[1:]) for r in reqs]
    clock.t = 2.0
    n_groups = len({t.group for t in td})
    assert by_deadline.poll() == n_groups  # one deadline close per group
    assert by_deadline.pending() == 0
    by_flush.flush()
    for a, b in zip(td, tf):
        assert (a.group, a.index, a.cycle) == (b.group, b.index, b.cycle)
        ra, rb = by_deadline.result(a), by_flush.result(b)
        ra = ra if isinstance(ra, tuple) else (ra,)
        rb = rb if isinstance(rb, tuple) else (rb,)
        for xa, xb in zip(ra, rb):
            np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))


def test_sharded_continuous_batching_matches_single_device_subprocess():
    """Continuous batching (admit_max auto-close + double buffering) over a
    4-way mesh agrees with the single-device engine on interpret-mode
    pallas: the kernel kinds (append/kalman) bitwise — the padded grid per
    shard is identical — and lstsq to roundoff (its padded vmap width
    differs between mesh and no-mesh, so XLA may vectorize lanes
    differently).  The async layers preserve the sharded-equals-single
    contract."""
    _run(
        """
        import numpy as np, jax
        from repro.launch.serve_qr import make_workload
        from repro.parallel.sharding import make_batch_mesh
        from repro.serve import ContinuousBatcher, Dispatcher
        assert jax.device_count() == 4, jax.device_count()
        mesh = make_batch_mesh(4)
        reqs = make_workload(19, n=6, rows=3, k=1, seed=56)

        def engine(mesh):
            return ContinuousBatcher(
                Dispatcher(backend="pallas", interpret=True, mesh=mesh,
                           max_batch=4, double_buffer=True),
                admit_max=4, retain_cycles=None)

        sharded, single = engine(mesh), engine(None)
        ts = [sharded.submit(r[0], *r[1:]) for r in reqs]
        t1 = [single.submit(r[0], *r[1:]) for r in reqs]
        sharded.flush(); single.flush()
        assert sharded.drain() >= 19 and single.drain() >= 19
        for r, a, b in zip(reqs, ts, t1):
            ra, rb = sharded.result(a), single.result(b)
            ra = ra if isinstance(ra, tuple) else (ra,)
            rb = rb if isinstance(rb, tuple) else (rb,)
            for xa, xb in zip(ra, rb):
                if r[0] == "lstsq":
                    np.testing.assert_allclose(np.asarray(xa),
                                               np.asarray(xb),
                                               rtol=1e-6, atol=1e-6)
                else:
                    np.testing.assert_array_equal(np.asarray(xa),
                                                  np.asarray(xb))
        assert all(sharded.done_at(t) is not None for t in ts)
        print("ASYNC_SHARDED_OK")
        """
    )


def test_sharded_result_split_matches_eager_slices_subprocess():
    """On a 4-way mesh the compiled results split hands every request the
    bits and the sharding (replicated over the mesh) of the eager slices
    ``out[:nb].astype(store)[i]`` of the batch-sharded outputs, and the
    kernel kinds' results equal the single-device engine's bitwise."""
    _run(
        """
        import numpy as np, jax
        from repro.launch.serve_qr import make_workload
        from repro.parallel.sharding import make_batch_mesh
        from repro.serve import ContinuousBatcher, Dispatcher, dispatch
        assert jax.device_count() == 4, jax.device_count()
        mesh = make_batch_mesh(4)
        reqs = make_workload(52, n=6, rows=3, k=1, seed=57)
        real, seen = dispatch._split_rows, []

        def recording(fields, dtypes):
            seen.append((fields, dtypes))
            return real(fields, dtypes)

        def serve(mesh):
            eng = ContinuousBatcher(
                Dispatcher(backend="pallas", interpret=True, mesh=mesh),
                retain_cycles=None)
            ts = [eng.submit(r[0], *r[1:]) for r in reqs]
            eng.flush()
            res = [eng.result(t) for t in ts]
            return ts, [r if isinstance(r, tuple) else (r,) for r in res]

        dispatch._split_rows = recording
        ts, sharded = serve(mesh)
        dispatch._split_rows = real
        _, single = serve(None)
        by_group = {}
        for t, res in zip(ts, sharded):
            by_group.setdefault(t.group, []).append((t.index, res))
        assert len(seen) == len(by_group) >= 5
        for (fields, dtypes), lanes in zip(seen, by_group.values()):
            nb = len(lanes)
            assert nb < fields[0].shape[0]  # every group carries pad lanes
            for i, res in lanes:
                for g, f, dt in zip(res, fields, dtypes):
                    e = (f[:nb] if dt is None else f[:nb].astype(dt))[i]
                    assert g.sharding == e.sharding, (g.sharding, e.sharding)
                    assert g.dtype == e.dtype
                    assert np.asarray(g).tobytes() == np.asarray(e).tobytes()
        for r, a, b in zip(reqs, sharded, single):
            if r[0] in ("append", "kalman"):
                for xa, xb in zip(a, b):
                    np.testing.assert_array_equal(np.asarray(xa),
                                                  np.asarray(xb))
        print("SPLIT_SHARDED_OK")
        """
    )


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_dir_follows_env(monkeypatch, tmp_path, env_dir):
    """``JAX_COMPILATION_CACHE_DIR`` wins and is left to JAX; without it the
    cache goes to the one fixed ``.jax_cache/`` of the checkout."""
    from repro.launch.cache import CHECKOUT_CACHE_DIR, use_compile_cache

    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(CHECKOUT_CACHE_DIR)
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    try:
        assert use_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == (
            before if env_dir else want)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert CHECKOUT_CACHE_DIR == Path(_REPO) / ".jax_cache"
