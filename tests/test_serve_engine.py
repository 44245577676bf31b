"""The layered serving engine: requests, batcher, dispatch, policy.

Unit + integration coverage for ``repro.serve``:

* group signatures stay byte-compatible with the legacy ``QRServer`` keys
  and reject malformed operand combinations;
* continuous batching closes on max_batch / deadline / flush with the
  right ``serve.batch_close`` reasons, cycle bookkeeping, and retention;
* admission control: bounded queues reject or shed with the promised
  metric families and ticket errors;
* the executable cache is bounded per server (mesh cycling cannot pin dead
  meshes) and the cache-miss accounting keys on the PADDED batch shape —
  the regression the old raw-chunk-size keying double-counted.
"""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import obs
from repro.serve import (
    AdmissionPolicy,
    ContinuousBatcher,
    Dispatcher,
    ExecutableCache,
    LatencyTier,
    Rejected,
    ShedError,
    make_request,
)


class FakeClock:
    """Deterministic batch-age clock for deadline tests."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _lstsq_args(rng, m=12, n=3, k=1):
    return (rng.standard_normal((m, n)).astype(np.float32),
            rng.standard_normal((m, k)).astype(np.float32))


def _append_args(rng, n=6, p=3):
    R = np.triu(rng.standard_normal((n, n))).astype(np.float32)
    np.fill_diagonal(R, np.abs(np.diag(R)) + 1.0)
    return R, rng.standard_normal((p, n)).astype(np.float32)


def _counter_sum(reg, name, **labels):
    return sum(m.value for m in reg.collect()
               if m.name == name
               and all(dict(m.labels).get(k) == v for k, v in labels.items()))


def _submit_reqs(eng, reqs):
    """Drive make_workload tuples straight into the engine's submit()."""
    return [eng.submit(r[0], *r[1:]) for r in reqs]


# ------------------------------------------------------------------ requests
def test_group_signatures_match_legacy_key_layout():
    rng = np.random.default_rng(0)
    R, U = _append_args(rng)
    d = rng.standard_normal((6, 2)).astype(np.float32)
    Y = rng.standard_normal((3, 2)).astype(np.float32)
    r = make_request("append", R, U, d, Y)
    assert r.group == ("append", (6, 6), "float32", (3, 6), "float32",
                       ((6, 2), "float32", (3, 2), "float32"))
    r_bare = make_request("append", R, U)
    assert r_bare.group == ("append", (6, 6), "float32", (3, 6), "float32",
                           None)
    assert r_bare.arrays[2] is None and not r_bare.has_optional

    A, b = _lstsq_args(rng)
    assert make_request("lstsq", A, b).group == (
        "lstsq", (12, 3), "float32", (12, 1), "float32")

    n, w, p = 4, 4, 2
    mats = [rng.standard_normal(s).astype(np.float32)
            for s in ((n, n), (n,), (n, n), (w, w), (p, n), (p,))]
    rk = make_request("kalman", *mats)
    assert rk.group[0] == "kalman" and rk.group[-1] is None
    G = rng.standard_normal((n, w)).astype(np.float32)
    rg = make_request("kalman", *mats, G=G)
    assert rg.group[-1] == ((n, w), "float32")
    # dtype is part of the key: same shapes, other dtype -> other group
    r16 = make_request("lstsq", A.astype(np.float16), b.astype(np.float16))
    assert r16.group != make_request("lstsq", A, b).group


def test_make_request_rejects_malformed_operands():
    rng = np.random.default_rng(1)
    R, U = _append_args(rng)
    with pytest.raises(ValueError, match="unknown request kind"):
        make_request("downdate", R, U)
    with pytest.raises(ValueError, match="both d and Y"):
        make_request("append", R, U, np.zeros((6, 1), np.float32))
    with pytest.raises(TypeError, match="missing operands"):
        make_request("lstsq", R)
    with pytest.raises(TypeError, match="no operand"):
        make_request("lstsq", R, U, nonsense=U)


# ----------------------------------------------------------------- batcher
def test_max_batch_close_is_continuous():
    """admit_max closes mid-stream: early submitters' results exist before
    any flush, under a fresh cycle per closed batch."""
    rng = np.random.default_rng(2)
    eng = ContinuousBatcher(Dispatcher(backend="reference", max_batch=4),
                            admit_max=4, retain_cycles=None)
    A, b = _lstsq_args(rng)
    tickets = [eng.submit("lstsq", A, b) for _ in range(10)]
    # two full batches auto-closed, 2 requests still open
    assert eng.pending() == 2
    assert [t.cycle for t in tickets] == [0] * 4 + [1] * 4 + [2] * 2
    x0 = eng.result(tickets[0])[0]  # available without any flush
    assert eng.flush() == 2
    eng.drain()
    xs = [np.asarray(eng.result(t)[0]) for t in tickets]
    oracle = np.linalg.lstsq(A, b, rcond=None)[0]
    for x in xs:
        np.testing.assert_allclose(x, oracle, rtol=1e-3, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(x0), xs[0])


def test_deadline_close_fires_on_poll_and_admit():
    rng = np.random.default_rng(3)
    clock = FakeClock()
    eng = ContinuousBatcher(
        Dispatcher(backend="reference"),
        AdmissionPolicy(tiers={"lstsq": LatencyTier(deadline=0.5)}),
        retain_cycles=None, clock=clock)
    A, b = _lstsq_args(rng)
    t1 = eng.submit("lstsq", A, b)
    clock.t = 0.4
    assert eng.poll() == 0 and eng.pending() == 1  # not due yet
    clock.t = 0.6
    assert eng.poll() == 1 and eng.pending() == 0  # deadline close
    eng.result(t1)
    # deadline check also piggybacks on the next admit
    t2 = eng.submit("lstsq", A, b)
    clock.t = 2.0
    t3 = eng.submit("lstsq", A, b)  # admit-time poll closed t2's batch first
    assert t3.cycle == t2.cycle + 1
    eng.result(t2)


def test_batch_close_reasons_are_counted():
    rng = np.random.default_rng(4)
    clock = FakeClock()
    eng = ContinuousBatcher(
        Dispatcher(backend="reference", max_batch=2),
        AdmissionPolicy(tiers={"lstsq": LatencyTier(deadline=1.0)}),
        admit_max=2, retain_cycles=None, clock=clock)
    A, b = _lstsq_args(rng)
    with obs.collecting() as reg:
        eng.submit("lstsq", A, b)
        eng.submit("lstsq", A, b)   # -> max_batch close
        eng.submit("lstsq", A, b)
        clock.t = 1.5
        eng.poll()                  # -> deadline close
        eng.submit("lstsq", A, b)
        eng.flush()                 # -> flush close
    for reason in ("max_batch", "deadline", "flush"):
        assert _counter_sum(reg, "serve.batch_close", kind="lstsq",
                            reason=reason) == 1, reason


def test_retention_latest_only_expires_like_legacy():
    rng = np.random.default_rng(5)
    eng = ContinuousBatcher(Dispatcher(backend="reference"), retain_cycles=1)
    A, b = _lstsq_args(rng)
    t_old = eng.submit("lstsq", A, b)
    eng.flush()
    t_new = eng.submit("lstsq", A, b)
    eng.flush()
    with pytest.raises(KeyError, match="expired by a later flush"):
        eng.result(t_old)
    eng.result(t_new)


# ------------------------------------------------------------------ policy
def test_admission_reject_bound_and_metric():
    rng = np.random.default_rng(6)
    eng = ContinuousBatcher(
        Dispatcher(backend="reference"),
        AdmissionPolicy(tiers={"lstsq": LatencyTier(max_queue=2)}))
    A, b = _lstsq_args(rng)
    with obs.collecting() as reg:
        eng.submit("lstsq", A, b)
        eng.submit("lstsq", A, b)
        with pytest.raises(Rejected):
            eng.submit("lstsq", A, b)
        # other kinds are not affected by the lstsq bound
        R, U = _append_args(rng)
        eng.submit("append", R, U)
        # a flush empties the queue and admission recovers
        eng.flush(kind="lstsq")
        eng.submit("lstsq", A, b)
    assert _counter_sum(reg, "serve.admission_rejected", kind="lstsq") == 1


def test_admission_shed_oldest_drops_stale_batch():
    rng = np.random.default_rng(7)
    eng = ContinuousBatcher(
        Dispatcher(backend="reference"),
        AdmissionPolicy(tiers={"lstsq": LatencyTier(
            max_queue=2, on_full="shed_oldest")}),
        retain_cycles=None)
    A, b = _lstsq_args(rng)
    with obs.collecting() as reg:
        t1 = eng.submit("lstsq", A, b)
        t2 = eng.submit("lstsq", A, b)
        t3 = eng.submit("lstsq", A, b)  # sheds the open batch holding t1, t2
    assert eng.pending() == 1
    assert t3.cycle == t1.cycle + 1
    with pytest.raises(ShedError):
        eng.result(t1)
    with pytest.raises(ShedError):
        eng.result(t2)
    eng.flush()
    eng.result(t3)
    assert _counter_sum(reg, "serve.requests_shed", kind="lstsq") == 2


def test_policy_validation():
    with pytest.raises(ValueError):
        LatencyTier(on_full="explode")
    with pytest.raises(ValueError):
        LatencyTier(deadline=-1.0)
    with pytest.raises(ValueError):
        LatencyTier(max_queue=0)


# ---------------------------------------------------------- executable cache
def test_executable_cache_lru_eviction():
    cache = ExecutableCache(maxsize=2)
    built = []

    def build(k):
        return lambda: built.append(k) or k

    assert cache.get("a", build("a")) == "a"
    assert cache.get("b", build("b")) == "b"
    assert cache.get("a", build("a")) == "a"   # refresh a's recency
    assert cache.get("c", build("c")) == "c"   # evicts b (LRU), not a
    assert len(cache) == 2 and "a" in cache and "b" not in cache
    assert cache.get("b", build("b")) == "b"   # rebuilt after eviction
    assert built == ["a", "b", "c", "b"]
    assert cache.hits == 1 and cache.misses == 4
    with pytest.raises(ValueError):
        ExecutableCache(maxsize=0)


def test_dispatcher_cache_is_per_server_and_bounded():
    """Cycling meshes through one server must not grow its executable cache
    beyond the bound (dead meshes become collectable), and two servers never
    share cache entries."""
    rng = np.random.default_rng(8)
    mesh_a = jax.make_mesh((1,), ("batch",))
    mesh_b = jax.make_mesh((1,), ("batch2",))
    d1 = Dispatcher(backend="reference", mesh=mesh_a, cache_size=1)
    d2 = Dispatcher(backend="reference", mesh=mesh_a)
    assert d1.executables is not d2.executables
    eng = ContinuousBatcher(d1, retain_cycles=None)
    A, b = _lstsq_args(rng)
    eng.submit("lstsq", A, b)
    eng.flush()
    assert ("lstsq", mesh_a, "batch") in d1.executables
    # retire mesh_a, serve on mesh_b: the bound evicts the dead mesh's entry
    d1.mesh, d1.mesh_axis = mesh_b, "batch2"
    eng.submit("lstsq", A, b)
    eng.flush()
    assert len(d1.executables) == 1
    assert ("lstsq", mesh_b, "batch2") in d1.executables
    assert ("lstsq", mesh_a, "batch") not in d1.executables
    assert len(d2.executables) == 0


# ------------------------------------------------- padded-shape miss keying
def test_cache_miss_accounting_keys_on_padded_batch():
    """Regression: nb=5 and nb=7 both pad to 8 at block_b=8, hitting ONE
    compiled executable — the miss counter must record exactly one miss
    (the old raw-size keying counted two)."""
    from repro.launch.serve_qr import QRServer

    rng = np.random.default_rng(9)
    server = QRServer(backend="pallas", interpret=True, block_b=8)
    with obs.collecting() as reg:
        for _ in range(5):
            server.submit_append(*_append_args(rng))
        server.flush()
        for _ in range(7):
            server.submit_append(*_append_args(rng))
        server.flush()
    assert _counter_sum(reg, "serve.executable_cache_miss", kind="append") == 1
    # padding waste was accounted against the padded grid both times
    pw = [m for m in reg.collect() if m.name == "serve.padding_waste"]
    assert pw and math.isclose(pw[0].min, 1 / 8) and math.isclose(
        pw[0].max, 3 / 8)


def test_cache_miss_accounting_reference_lstsq_pads_to_block_b():
    """reference-backend lstsq pads to block_b too: nb=5 and nb=7 share one
    padded-8 executable (one miss); nb=11 pads to 16 and is a second."""
    from repro.launch.serve_qr import QRServer

    rng = np.random.default_rng(10)
    server = QRServer(backend="reference", block_b=8)
    A, b = _lstsq_args(rng)
    with obs.collecting() as reg:
        for nb in (5, 7, 11):
            for _ in range(nb):
                server.submit_lstsq(A, b)
            server.flush()
    assert _counter_sum(reg, "serve.executable_cache_miss", kind="lstsq") == 2


def test_mixed_dtype_same_shape_requests_land_in_distinct_batches():
    """bf16-store and f32-store requests of identical shapes must never be
    stacked together: the group signature carries the dtype, so each dtype
    gets its own batch, executable, and (scaled) padding grid."""
    from repro.launch.serve_qr import QRServer

    rng = np.random.default_rng(11)
    R, U = _append_args(rng)
    server = QRServer(backend="pallas", interpret=True, block_b=8)
    t32 = server.submit_append(jnp.asarray(R, jnp.float32),
                               jnp.asarray(U, jnp.float32))
    t16 = server.submit_append(jnp.asarray(R, jnp.bfloat16),
                               jnp.asarray(U, jnp.bfloat16))
    assert t32.group != t16.group
    assert t32.group[2] == "float32" and t16.group[2] == "bfloat16"
    with obs.collecting() as reg:
        server.flush()
    # one dispatch per dtype group, each accounted at its own precision
    assert _counter_sum(reg, "serve.dispatches", kind="append",
                        precision="float32") == 1
    assert _counter_sum(reg, "serve.dispatches", kind="append",
                        precision="bfloat16") == 1
    # bf16 storage rides a 2x dispatch block: padded grids differ
    d = server._engine.dispatcher
    assert d.padded_chunk(1, "append", "float32") == 8
    assert d.padded_chunk(1, "append", "bfloat16") == 16


def test_mixed_dtype_round_trip_is_bitwise_per_store_dtype():
    """Each store dtype must round-trip bitwise against a server fed only
    that dtype — co-resident other-dtype groups cannot perturb results."""
    from repro.launch.serve_qr import QRServer

    rng = np.random.default_rng(12)
    R, U = _append_args(rng)
    ops = [(jnp.asarray(R, jnp.float32), jnp.asarray(U, jnp.float32)),
           (jnp.asarray(R, jnp.bfloat16), jnp.asarray(U, jnp.bfloat16))]

    mixed = QRServer(backend="pallas", interpret=True)
    tickets = [mixed.submit_append(Ri, Ui) for Ri, Ui in ops]
    mixed.flush()
    mixed.drain()
    got = [mixed.result(t) for t in tickets]

    for (Ri, Ui), out in zip(ops, got):
        solo = QRServer(backend="pallas", interpret=True)
        t = solo.submit_append(Ri, Ui)
        solo.flush()
        solo.drain()
        expect = solo.result(t)
        assert out.dtype == Ri.dtype
        np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


# ------------------------------------------------------- double buffering
def test_double_buffered_dispatch_matches_facade():
    """Async double-buffered continuous batching returns the same numbers
    as the legacy closed-loop facade, chunk for chunk."""
    from repro.launch.serve_qr import QRServer, _submit_all, make_workload

    reqs = make_workload(11, n=6, rows=3, k=1, seed=60)
    eng = ContinuousBatcher(
        Dispatcher(backend="reference", max_batch=4, double_buffer=True),
        admit_max=4, retain_cycles=None)
    facade = QRServer(backend="reference", max_batch=4)
    t_async = _submit_reqs(eng, reqs)
    t_sync = _submit_all(facade, reqs)
    eng.flush()
    facade.flush()
    assert eng.drain() >= len(reqs) and facade.drain() >= len(reqs)
    for ta, ts in zip(t_async, t_sync):
        ra, rb = eng.result(ta), facade.result(ts)
        ra = ra if isinstance(ra, tuple) else (ra,)
        rb = rb if isinstance(rb, tuple) else (rb,)
        for xa, xb in zip(ra, rb):
            np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))
    # drain finalized every in-flight chunk: completion clocks exist
    assert all(eng.done_at(t) is not None for t in t_async)
    assert not eng.dispatcher._inflight


def test_engine_submit_entrypoint_matches_submit_star():
    """ContinuousBatcher.submit(kind, ...) accepts the same workload tuples
    the facade's submit_* methods route."""
    from repro.launch.serve_qr import make_workload

    reqs = make_workload(8, n=5, rows=2, k=1, seed=61)
    kinds = [r[0] for r in reqs]
    assert set(kinds) == {"append", "lstsq", "kalman", "lstsq_pivoted"}
    eng = ContinuousBatcher(Dispatcher(backend="reference"))
    tickets = _submit_reqs(eng, reqs)
    assert [t.kind for t in tickets] == kinds
    assert eng.flush() == len(reqs)
    for t in tickets:
        eng.result(t)


def test_make_workload_kalman_mix_and_shared_models():
    from repro.launch.serve_qr import make_workload

    reqs = make_workload(32, n=6, rows=3, k=1, seed=62)
    kal = [r for r in reqs if r[0] == "kalman"]
    assert len(kal) == 8
    shared = [r for r in kal if r[3] is kal[0][3]]
    # half the kalman requests reuse ONE model-matrix object (broadcast
    # case), the rest carry per-track models
    assert len(shared) == 4
    assert all(isinstance(r[3], jax.Array) for r in shared)
    shared_ids = {id(r) for r in shared}
    per_track = [r for r in kal if id(r) not in shared_ids]
    assert all(r[3] is not kal[0][3] for r in per_track)
    # appends still cover the bare no-rhs form
    appends = [r for r in reqs if r[0] == "append"]
    assert any(len(r) == 3 for r in appends)
    assert any(len(r) == 5 for r in appends)


# ---------------------------------------------------- per-request results
def _kind_args(kind, rng, dt):
    """One request of ``kind`` (``append_rhs``: append with a right-hand
    side) with operands stored at ``dt``."""
    a = lambda x: jnp.asarray(x, dt)  # noqa: E731
    if kind in ("append", "append_rhs"):
        R, U = _append_args(rng)
        if kind == "append":
            return "append", a(R), a(U)
        return ("append", a(R), a(U), a(rng.standard_normal((6, 2))),
                a(rng.standard_normal((3, 2))))
    if kind == "kalman":
        R, H = _append_args(rng)
        F = np.eye(6) + 0.1 * rng.standard_normal((6, 6))
        return ("kalman", a(R), a(rng.standard_normal(6)), a(F),
                a(np.eye(6)), a(H), a(rng.standard_normal(3)))
    A, b = _lstsq_args(rng)
    if kind == "lstsq_pivoted":
        A = A[:, :2] @ rng.standard_normal((2, 3)).astype(np.float32)
    return kind, a(A), a(b)


@pytest.mark.parametrize("nb", [13, 64])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", ["append", "append_rhs", "lstsq", "kalman",
                                  "lstsq_pivoted"])
def test_served_results_equal_eager_row_slices(kind, dtype, nb, monkeypatch):
    """Every executor's per-request results are bitwise the eager
    ``out[:nb].astype(store)[i]`` of its padded batched outputs, one
    ``jax.Array`` per field at the storage dtype; ``lstsq_pivoted``'s rank
    stays int32.  Under the f32 policy a bf16 group computes in f32 and
    casts back on return."""
    from repro.serve import dispatch

    real = dispatch._split_rows
    seen = []

    def recording(fields, dtypes):
        seen.append((fields, dtypes))
        return real(fields, dtypes)

    monkeypatch.setattr(dispatch, "_split_rows", recording)
    rng = np.random.default_rng(70 + nb)
    eng = ContinuousBatcher(Dispatcher(backend="reference", precision="f32"),
                            retain_cycles=None)
    tickets = [eng.submit(*_kind_args(kind, rng, dtype)) for _ in range(nb)]
    eng.flush()
    ((fields, dtypes),) = seen
    padded = eng.dispatcher.padded_chunk(nb, tickets[0].kind, dtype)
    assert all(f.shape[0] == padded for f in fields)
    want_dt = ([dtype] * len(fields) if kind != "lstsq_pivoted"
               else [dtype, dtype, "int32"])
    for i, t in enumerate(tickets):
        got = eng.result(t)
        got = got if isinstance(got, tuple) else (got,)
        assert len(got) == len(fields)
        for g, f, dt, w in zip(got, fields, dtypes, want_dt):
            expect = (f[:nb] if dt is None else f[:nb].astype(dt))[i]
            assert isinstance(g, jax.Array)
            assert g.dtype == expect.dtype == jnp.dtype(w)
            assert g.shape == expect.shape
            assert np.asarray(g).tobytes() == np.asarray(expect).tobytes()


# ---------------------------------------------------- stacked operands
def _recording_stack(monkeypatch) -> list:
    """Record every ``_stack_rows`` call as ``(rows, dtype, batches)``."""
    from repro.serve import dispatch

    real, seen = dispatch._stack_rows, []

    def recording(rows, dtype):
        out = real(rows, dtype)
        seen.append((rows, dtype, out))
        return out

    monkeypatch.setattr(dispatch, "_stack_rows", recording)
    return seen


@pytest.mark.parametrize("nb", [13, 64])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", ["append", "append_rhs", "lstsq", "kalman",
                                  "lstsq_pivoted"])
def test_stacked_batches_equal_eager_stack(kind, dtype, nb, monkeypatch):
    """Every executor stacks a chunk's operands in one compiled call whose
    batches, as they go on to the solve or kernel, are bitwise the eager
    ``pad_batch(jnp.stack(rows), P).astype(compute)``, with its dtype and
    shape; the fill lanes are exactly zero.  Under the f32 policy a bf16
    group's batches are f32."""
    from repro.kernels import pad_batch

    seen = _recording_stack(monkeypatch)
    rng = np.random.default_rng(90 + nb)
    eng = ContinuousBatcher(Dispatcher(backend="reference", precision="f32"),
                            retain_cycles=None)
    reqs = [_kind_args(kind, rng, dtype) for _ in range(nb)]
    tickets = [eng.submit(*r) for r in reqs]
    eng.flush()
    ((rows, dtype_arg, batches),) = seen
    P = eng.dispatcher.padded_chunk(nb, tickets[0].kind, dtype)
    assert P > nb or nb == 64
    # no operand is one shared array here, so every operand is stacked
    assert len(rows) == len(batches) == len(reqs[0]) - 1
    assert dtype_arg == (None if dtype == "float32" else "float32")
    for f, (col, batch) in enumerate(zip(rows, batches)):
        assert len(col) == P
        assert all(col[i] is r[f + 1] for i, r in enumerate(reqs))
        expect = pad_batch(jnp.stack(col[:nb]), P).astype(jnp.float32)
        assert batch.dtype == expect.dtype == jnp.float32
        assert batch.shape == expect.shape
        assert np.asarray(batch).tobytes() == np.asarray(expect).tobytes()
        assert not np.asarray(batch[nb:]).view(np.uint8).any()


@pytest.mark.parametrize("kind", ["append", "lstsq"])
def test_stack_compiles_once_per_padded_size(kind):
    """With ``block_b`` 8, chunks of 9, 13 and 16 requests all stack at the
    padded size 16 through one ``_stack_rows`` executable, and a chunk of
    64 adds one more: the stack keys on the padded size, never on the real
    request count."""
    from repro.serve import dispatch

    rng = np.random.default_rng(11)
    # shapes no other test here stacks, so each new executable is this one's
    args = (_append_args(rng, n=7, p=2) if kind == "append"
            else _lstsq_args(rng, m=11, n=2))
    eng = ContinuousBatcher(Dispatcher(backend="reference", block_b=8),
                            retain_cycles=None)
    start = dispatch._stack_rows._cache_size()
    added = []
    with obs.collecting() as reg:
        for nb in (9, 13, 16, 64):
            tickets = [eng.submit(kind, *args) for _ in range(nb)]
            eng.flush()
            assert all(eng.result(t) is not None for t in tickets)
            added.append(dispatch._stack_rows._cache_size() - start)
        assert reg.find("serve.batch_stacks", kind=kind).value == 4
    assert added == [1, 1, 1, 2]


# ---------------------------------------------------- kalman front end
_COMPILE_STAGES = ("jaxpr_trace", "jaxpr_to_mlir_module", "backend_compile")


@pytest.mark.parametrize("model", ["shared", "per_filter_F"])
def test_kalman_chunks_compile_nothing_after_the_first(model):
    """Kalman chunks of one padded size, with a shared model or per-filter
    ``F``, trace and compile nothing after the first chunk: the step
    matrices are one compiled call, keyed on shapes and model layout, and
    the stack, kernel and split reuse theirs."""
    rng = np.random.default_rng(31)
    f32 = lambda x: jnp.asarray(np.asarray(x, np.float32))  # noqa: E731
    # shapes no other test serves, so the first chunk's executables are new
    n, w, p, nb = 5, 2, 3, 13
    F = np.eye(n) + np.eye(n, k=2)
    G, Qi = f32(rng.standard_normal((n, w))), f32(np.eye(w))
    H = f32(np.hstack([np.eye(p), np.zeros((p, n - p))]))
    Fs = f32(F)
    chunks = []
    for _ in range(4):
        reqs = []
        for _ in range(nb):
            R, _ = _append_args(rng, n=n, p=p)
            Fi = (Fs if model == "shared"
                  else f32(F + 0.01 * rng.standard_normal((n, n))))
            reqs.append((f32(R), f32(rng.standard_normal(n)), Fi, Qi, H,
                         f32(rng.standard_normal(p)), G))
        chunks.append(reqs)
    eng = ContinuousBatcher(Dispatcher(backend="pallas", block_b=8),
                            retain_cycles=None)
    counts = []
    with obs.collecting() as reg:
        for reqs in chunks:
            tickets = [eng.submit("kalman", *r) for r in reqs]
            eng.flush()
            assert all(eng.result(t) is not None for t in tickets)
            counts.append(tuple(
                _counter_sum(reg, "jax.compile_events", stage=s)
                for s in _COMPILE_STAGES))
        assert reg.find("serve.batch_stacks", kind="kalman").value == 4
    assert counts[0][0] > 0  # the first chunk traced what it runs
    assert counts[1:] == counts[:1] * 3, counts
