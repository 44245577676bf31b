"""Fault-tolerant serving: failure domains, retry/degrade, quarantine.

Coverage for ``repro.serve.resilience`` plus the fault-injection harness in
``repro.testing.faults``:

* failure classification (``serve_classification`` attr, FloatingPointError,
  XLA-runtime-by-name, the fatal default) and the typed ``ServeError`` that
  tickets resolve to instead of exceptions escaping the serve loop;
* ``RetryPolicy`` deterministic backoff/jitter and per-kind retry budgets;
* ``CircuitBreaker`` closed -> open -> half-open lifecycle on a fake clock,
  and rung-skipping when a breaker is open;
* the degradation ladder: one rung per consumed attempt budget, provenance
  records, ``serve.degraded_dispatches`` counters, and agreement of every
  degraded result with the native one;
* poisoned-batch quarantine at all three stages (precheck, postcheck,
  bisection) with the healthy remainder re-dispatched at the ORIGINAL
  padded width so its bits match the fault-free run;
* ``Dispatcher.drain``/``pump`` aggregating per-chunk failures into
  ``DrainError`` after attempting every chunk (satellite 1) and the
  batcher's eager purge of fully-errored cycles (satellite 2);
* ``StateVault`` snapshot cadence, integrity-gated restore fallback, and
  ``IntegrityError`` when no snapshot validates;
* the chaos injectors themselves: per-seed determinism and
  ``poison_workload`` never mutating its input.

The zero-fault byte-compatibility bar (resilient results identical to the
plain ``Dispatcher``) is asserted here; the seeded chaos run's bar is in
``tests/test_serve_gates.py``.
"""
import contextlib
import os
import time

import numpy as np
import pytest

import jax.numpy as jnp

from repro import obs
from repro.launch.serve_qr import QRServer, _as_tuple, make_workload
from repro.serve import (
    DEFAULT_LADDER,
    CircuitBreaker,
    ContinuousBatcher,
    Dispatcher,
    DrainError,
    IntegrityError,
    PoisonedError,
    ResilientDispatcher,
    RetryPolicy,
    Rung,
    ServeError,
    StateVault,
    classify_failure,
)
from repro.solvers.lstsq import RLSState, state_integrity
from repro.testing.faults import (
    FaultInjector,
    FaultPlan,
    InjectedFatal,
    InjectedPoison,
    InjectedTransient,
    ScriptedInjector,
    inject,
    poison_workload,
)

_NO_SLEEP = lambda s: None  # noqa: E731


def _counter_sum(reg, name, **labels):
    return sum(m.value for m in reg.collect()
               if m.name == name
               and all(dict(m.labels).get(k) == v for k, v in labels.items()))


def _append_args(rng, n=6, p=3):
    R = np.triu(rng.standard_normal((n, n))).astype(np.float32)
    np.fill_diagonal(R, np.abs(np.diag(R)) + 1.0)
    return R, rng.standard_normal((p, n)).astype(np.float32)


def _fast(**kw):
    kw.setdefault("backend", "reference")
    kw.setdefault("sleep", _NO_SLEEP)
    return ResilientDispatcher(**kw)


# ---------------------------------------------------------- classification
class TestClassification:
    def test_attribute_wins(self):
        assert classify_failure(InjectedTransient("x")) == "transient"
        assert classify_failure(InjectedPoison("x")) == "poisoned"
        assert classify_failure(InjectedFatal("x")) == "fatal"

    def test_floating_point_error_is_poisoned(self):
        assert classify_failure(FloatingPointError("nan")) == "poisoned"

    def test_xla_runtime_by_name(self):
        XlaRuntimeError = type("XlaRuntimeError", (RuntimeError,), {})
        assert classify_failure(XlaRuntimeError("RESOURCE_EXHAUSTED")) == \
            "transient"
        assert classify_failure(MemoryError()) == "transient"

    def test_default_fatal(self):
        assert classify_failure(ValueError("shape mismatch")) == "fatal"

    def test_serve_error_carries_context(self):
        err = ServeError(kind="lstsq", classification="transient",
                         reason="retries exhausted",
                         cause=InjectedTransient("boom"))
        assert err.kind == "lstsq"
        assert err.classification == "transient"
        assert isinstance(err, RuntimeError)
        assert issubclass(PoisonedError, ServeError)


# ------------------------------------------------------------- retry policy
class TestRetryPolicy:
    def test_delay_grows_and_is_deterministic(self):
        pol = RetryPolicy(max_attempts=4, backoff=0.01,
                          backoff_factor=2.0, jitter=0.0)
        d = [pol.delay(a, salt=42) for a in (1, 2, 3)]
        assert d == [pol.delay(a, salt=42) for a in (1, 2, 3)]
        assert d[1] > d[0] and d[2] > d[1]

    def test_jitter_bounded_and_varies_by_salt(self):
        pol = RetryPolicy(backoff=0.01, jitter=0.5)
        assert pol.delay(1, salt=1) != pol.delay(1, salt=2)
        for salt in range(32):
            d = pol.delay(1, salt=salt)
            assert 0.005 <= d <= 0.015  # base * [1-jitter, 1+jitter]

    def test_zero_backoff(self):
        assert RetryPolicy(backoff=0.0).delay(5, salt=9) == 0.0


# ---------------------------------------------------------- circuit breaker
class TestCircuitBreaker:
    def test_lifecycle(self):
        t = [0.0]
        br = CircuitBreaker(failure_threshold=2, cooldown=10.0,
                            clock=lambda: t[0])
        assert br.state == "closed" and br.allow()
        br.record_failure()
        assert br.state == "closed"
        br.record_failure()
        assert br.state == "open" and not br.allow()
        t[0] = 11.0
        assert br.state == "half_open" and br.allow()
        br.record_failure()  # half-open failure trips straight back
        assert br.state == "open"
        t[0] = 22.0
        assert br.state == "half_open"
        br.record_success()
        assert br.state == "closed" and br.allow()

    def test_success_resets_failure_count(self):
        br = CircuitBreaker(failure_threshold=2, clock=lambda: 0.0)
        br.record_failure()
        br.record_success()
        br.record_failure()
        assert br.state == "closed"

    def test_open_breaker_skips_rung(self):
        rng = np.random.default_rng(0)
        with obs.collecting() as reg:
            d = _fast(retry=RetryPolicy(max_attempts=1),
                      breaker_threshold=1, breaker_cooldown=1e9)
            eng = ContinuousBatcher(d)
            # one fatal trips the (append, rung 0) breaker instantly
            with inject(ScriptedInjector({0}, exc=InjectedFatal)):
                t0 = eng.submit("append", *_append_args(rng))
                eng.flush()
            with pytest.raises(ServeError):
                eng.result(t0)
            # next dispatch must skip the open native rung
            t1 = eng.submit("append", *_append_args(rng))
            eng.flush()
            eng.result(t1)
        prov = d.provenance[(t1.group, t1.cycle)][0]
        assert prov.rung == DEFAULT_LADDER[1].name
        assert _counter_sum(reg, "serve.degraded_dispatches",
                            reason="breaker_open") >= 1
        assert _counter_sum(reg, "serve.breaker_state") >= 0  # family exists


# ------------------------------------------------------- retry then degrade
class TestRetryAndDegrade:
    def test_transient_retried_then_succeeds(self):
        rng = np.random.default_rng(1)
        with obs.collecting() as reg:
            d = _fast(retry=RetryPolicy(max_attempts=3, backoff=0.0))
            eng = ContinuousBatcher(d)
            with inject(ScriptedInjector({0})):
                t = eng.submit("append", *_append_args(rng))
                eng.flush()
            R = eng.result(t)
        assert np.isfinite(np.asarray(R)).all()
        prov = d.provenance[(t.group, t.cycle)][0]
        assert prov.rung == "native" and prov.attempts == 2
        assert _counter_sum(reg, "serve.retries") == 1
        assert _counter_sum(reg, "serve.chunk_failures") == 1

    @pytest.mark.parametrize("k", range(1, len(DEFAULT_LADDER)))
    def test_each_rung_reachable_and_agrees(self, k):
        rng = np.random.default_rng(2)
        R, U = _append_args(rng, n=8, p=4)
        d0 = _fast(retry=RetryPolicy(max_attempts=1))
        e0 = ContinuousBatcher(d0)
        t0 = e0.submit("append", R, U)
        e0.flush()
        native = np.asarray(e0.result(t0))
        with obs.collecting() as reg:
            d = _fast(retry=RetryPolicy(max_attempts=1))
            eng = ContinuousBatcher(d)
            with inject(ScriptedInjector(set(range(k)))):
                t = eng.submit("append", R, U)
                eng.flush()
            out = np.asarray(eng.result(t))
        prov = d.provenance[(t.group, t.cycle)][0]
        assert prov.rung == DEFAULT_LADDER[k].name
        np.testing.assert_allclose(out, native, rtol=1e-4, atol=1e-5)
        assert _counter_sum(reg, "serve.degraded_dispatches",
                            to=DEFAULT_LADDER[k].name) >= 1

    def test_ladder_exhausted_resolves_serve_error(self):
        rng = np.random.default_rng(3)
        d = _fast(ladder=(Rung("native"),),
                  retry=RetryPolicy(max_attempts=2, backoff=0.0))
        eng = ContinuousBatcher(d)
        with inject(ScriptedInjector(set(range(16)))):
            t = eng.submit("append", *_append_args(rng))
            eng.flush()
        with pytest.raises(ServeError) as ei:
            eng.result(t)
        assert ei.value.classification == "transient"
        prov = d.provenance[(t.group, t.cycle)][0]
        assert prov.error is not None

    def test_kind_budget_caps_retries(self):
        rng = np.random.default_rng(4)
        d = _fast(retry=RetryPolicy(max_attempts=5, backoff=0.0,
                                    kind_budget=1))
        eng = ContinuousBatcher(d)
        with inject(ScriptedInjector(set(range(3)))):
            t = eng.submit("append", *_append_args(rng))
            eng.flush()
        eng.result(t)
        prov = d.provenance[(t.group, t.cycle)][0]
        # budget of 1 retry: attempt 2 fails -> degrade (not retry again)
        assert prov.rung != "native"

    def test_double_buffer_rejected(self):
        with pytest.raises(ValueError):
            ResilientDispatcher(backend="reference", double_buffer=True)


# --------------------------------------------------------------- quarantine
class TestQuarantine:
    def test_precheck_rejects_nonfinite_operand(self):
        rng = np.random.default_rng(5)
        R, U = _append_args(rng)
        U_bad = U.copy()
        U_bad[0, 0] = np.inf
        with obs.collecting() as reg:
            eng = ContinuousBatcher(_fast())
            t_bad = eng.submit("append", R, U_bad)
            t_ok = eng.submit("append", R, U)
            eng.flush()
            with pytest.raises(PoisonedError):
                eng.result(t_bad)
            assert np.isfinite(np.asarray(eng.result(t_ok))).all()
        assert _counter_sum(reg, "serve.quarantined", stage="precheck") == 1

    def test_precheck_leaves_factor_gauges_to_live_lanes(self):
        """After a precheck quarantine the chunk's factor-health gauges
        read the survivors' factors alone, never a pad lane's zero R."""
        rng = np.random.default_rng(15)
        R, U = _append_args(rng)
        U_bad = U.copy()
        U_bad[0, 0] = np.inf
        with obs.collecting() as reg:
            eng = ContinuousBatcher(_fast())
            eng.submit("append", R, U_bad)
            t_ok = eng.submit("append", R, U)
            eng.flush()
            R_ok = np.asarray(eng.result(t_ok))
        assert reg.find("serve.r_diag_min", kind="append").value == \
            np.abs(np.diag(R_ok)).min() > 0.0

    def test_postcheck_isolates_nan_lane(self):
        rng = np.random.default_rng(6)
        A = rng.standard_normal((12, 3)).astype(np.float32)
        b = rng.standard_normal((12, 1)).astype(np.float32)
        A_bad = A.copy()
        A_bad[0, 0] = np.nan
        with obs.collecting() as reg:
            eng = ContinuousBatcher(_fast(precheck=False))
            t_bad = eng.submit("lstsq", A_bad, b)
            t_ok = eng.submit("lstsq", A, b)
            eng.flush()
            with pytest.raises(PoisonedError):
                eng.result(t_bad)
            x, _ = eng.result(t_ok)
        solo = QRServer(backend="reference")
        ts = solo.submit_lstsq(A, b)
        solo.flush()
        xs, _ = solo.result(ts)
        np.testing.assert_allclose(np.asarray(x), np.asarray(xs),
                                   rtol=1e-4, atol=1e-5)
        assert _counter_sum(reg, "serve.quarantined", stage="postcheck") >= 1

    def test_bisection_isolates_poisoned_request(self):
        """An executor-raised poison (no NaN operand, so precheck cannot
        see it) is pinned to ONE request by bisection; neighbours keep
        their results."""
        rng = np.random.default_rng(7)
        reqs = [_append_args(rng) for _ in range(6)]
        marked = reqs[3][1]

        class MarkedPoison:
            def on_dispatch(self, kind, rung, dispatcher, chunk=None):
                if chunk and any(r.arrays[1] is not None
                                 and r.arrays[1].shape == marked.shape
                                 and np.array_equal(np.asarray(r.arrays[1]),
                                                    marked)
                                 for r in chunk):
                    raise InjectedPoison("marked request present")

        with obs.collecting() as reg:
            eng = ContinuousBatcher(_fast())
            with inject(MarkedPoison()):
                tickets = [eng.submit("append", R, U) for R, U in reqs]
                eng.flush()
            for i, t in enumerate(tickets):
                if i == 3:
                    with pytest.raises(PoisonedError):
                        eng.result(t)
                else:
                    assert np.isfinite(np.asarray(eng.result(t))).all()
        assert _counter_sum(reg, "serve.quarantined", stage="bisect") == 1

    def test_quarantine_remainder_keeps_original_padded_bits(self):
        """Survivors of a precheck quarantine must be re-padded to the
        ORIGINAL chunk width so their bits match the fault-free run."""
        rng = np.random.default_rng(8)
        A = [rng.standard_normal((12, 3)).astype(np.float32)
             for _ in range(3)]
        b = [rng.standard_normal((12, 1)).astype(np.float32)
             for _ in range(3)]
        clean = ContinuousBatcher(_fast())
        t_clean = [clean.submit("lstsq", Ai, bi) for Ai, bi in zip(A, b)]
        clean.flush()
        want = [np.asarray(_as_tuple(clean.result(t))[0]) for t in t_clean]

        A_bad = A[1].copy()
        A_bad[0, 0] = np.nan
        eng = ContinuousBatcher(_fast())
        t0 = eng.submit("lstsq", A[0], b[0])
        tb = eng.submit("lstsq", A_bad, b[1])
        t2 = eng.submit("lstsq", A[2], b[2])
        eng.flush()
        with pytest.raises(PoisonedError):
            eng.result(tb)
        for t, ref in ((t0, want[0]), (t2, want[2])):
            got = np.asarray(_as_tuple(eng.result(t))[0])
            assert np.array_equal(got, ref)

    @pytest.mark.parametrize("stage", ["precheck", "bisect"])
    def test_fill_rows_keep_survivor_bits(self, stage, monkeypatch):
        """One poisoned request in a chunk of 9, caught by the pre-check (a
        NaN operand) or isolated by bisection (an executor-raised poison):
        every dispatch of the survivors stacks at the original padded width
        16, not their own 8 or less, its missing lanes filled with zero
        rows, and the survivors' results
        are bitwise the fault-free run's.  Nothing is donated: no request
        operand is deleted after dispatch."""
        from repro.serve import dispatch

        real, widths = dispatch._stack_rows, []

        def recording(rows, dtype):
            widths.append({len(col) for col in rows})
            return real(rows, dtype)

        rng = np.random.default_rng(16)
        A = [jnp.asarray(rng.standard_normal((12, 3)), jnp.float32)
             for _ in range(9)]
        b = [jnp.asarray(rng.standard_normal((12, 1)), jnp.float32)
             for _ in range(9)]
        clean = ContinuousBatcher(_fast())
        t_clean = [clean.submit("lstsq", *ab) for ab in zip(A, b)]
        clean.flush()
        want = [_as_tuple(clean.result(t)) for t in t_clean]

        bad = 5
        A_run = list(A)
        if stage == "precheck":
            A_run[bad] = A[bad].at[0, 0].set(jnp.nan)

        class MarkedPoison:
            def on_dispatch(self, kind, rung, dispatcher, chunk=None):
                if any(r.arrays[0] is A_run[bad] for r in chunk or ()):
                    raise InjectedPoison("marked request present")

        monkeypatch.setattr(dispatch, "_stack_rows", recording)
        with obs.collecting() as reg:
            eng = ContinuousBatcher(_fast())
            with (inject(MarkedPoison()) if stage == "bisect"
                  else contextlib.nullcontext()):
                tickets = [eng.submit("lstsq", *ab) for ab in zip(A_run, b)]
                eng.flush()
        assert _counter_sum(reg, "serve.quarantined", stage=stage) == 1
        assert widths and all(w == {16} for w in widths)
        for i, t in enumerate(tickets):
            if i == bad:
                with pytest.raises(PoisonedError):
                    eng.result(t)
                continue
            for got, ref in zip(_as_tuple(eng.result(t)), want[i]):
                assert np.asarray(got).tobytes() == np.asarray(ref).tobytes()
        assert not any(x.is_deleted() for x in (*A, *A_run, *b))


# ------------------------------------------------- satellite 1: drain/pump
class TestDrainAggregation:
    def test_drain_attempts_every_chunk(self, monkeypatch):
        rng = np.random.default_rng(9)
        d = Dispatcher(backend="reference", max_batch=2, double_buffer=True)
        eng = ContinuousBatcher(d, admit_max=2, retain_cycles=None)
        tickets = [eng.submit("append", *_append_args(rng))
                   for _ in range(6)]
        flights = list(d._inflight)
        assert len(flights) == 3
        boom = RuntimeError("deferred device error")

        def bad_block():
            raise boom

        monkeypatch.setattr(flights[0], "block", bad_block)
        with pytest.raises(DrainError) as ei:
            eng.drain()
        assert [e for _, e in ei.value.failures] == [boom]
        assert "1 in-flight chunk(s)" in str(ei.value)
        # every chunk was attempted — the failure orphaned nobody
        assert d._inflight == []
        assert all(f.finalized for f in flights)
        for t in tickets:
            assert np.isfinite(np.asarray(eng.result(t))).all()

    def test_pump_failure_does_not_block_neighbors(self, monkeypatch):
        rng = np.random.default_rng(20)
        with obs.collecting():
            d = Dispatcher(backend="reference", max_batch=2,
                           double_buffer=True)
            eng = ContinuousBatcher(d, admit_max=2, retain_cycles=None)
            for _ in range(4):
                eng.submit("append", *_append_args(rng))
            flights = list(d._inflight)
            boom = RuntimeError("deferred device error")

            def bad_block():
                raise boom

            monkeypatch.setattr(flights[0], "block", bad_block)
            deadline = time.time() + 30.0
            while not all(f.ready() for f in flights):
                assert time.time() < deadline
                time.sleep(0.01)
            with pytest.raises(DrainError) as ei:
                d.pump()
        assert [e for _, e in ei.value.failures] == [boom]
        assert all(f.finalized for f in flights)
        assert d._inflight == []

    def test_drain_clean_path_unchanged(self):
        rng = np.random.default_rng(10)
        d = Dispatcher(backend="reference", double_buffer=True)
        eng = ContinuousBatcher(d)
        t = eng.submit("append", *_append_args(rng))
        eng.flush()
        eng.drain()
        assert np.isfinite(np.asarray(eng.result(t))).all()
        assert d._inflight == []


# ----------------------------------------------- satellite 2: eager purge
class TestCyclePurge:
    def test_fully_errored_cycle_purged(self):
        rng = np.random.default_rng(11)
        with obs.collecting() as reg:
            d = _fast(ladder=(Rung("native"),),
                      retry=RetryPolicy(max_attempts=1))
            eng = ContinuousBatcher(d)
            with inject(ScriptedInjector(set(range(16)))):
                t = eng.submit("append", *_append_args(rng))
                eng.flush()
            with pytest.raises(ServeError):
                eng.result(t)
            with pytest.raises(ServeError):
                eng.result(t)  # purged entry keeps resolving, not KeyError
            eng.drain()  # purged cycles must not break drain
        assert _counter_sum(reg, "serve.cycles_purged") == 1

    def test_mixed_cycle_not_purged(self):
        rng = np.random.default_rng(12)
        R, U = _append_args(rng)
        U_bad = U.copy()
        U_bad[0, 0] = np.nan
        with obs.collecting() as reg:
            eng = ContinuousBatcher(_fast())
            t_bad = eng.submit("append", R, U_bad)
            t_ok = eng.submit("append", R, U)
            eng.flush()
            with pytest.raises(PoisonedError):
                eng.result(t_bad)
            assert np.isfinite(np.asarray(eng.result(t_ok))).all()
        assert _counter_sum(reg, "serve.cycles_purged") == 0


# ----------------------------------------------------- zero-fault identity
class TestByteCompatibility:
    @staticmethod
    def _submit(server, r):
        return getattr(server, f"submit_{r[0]}")(*r[1:])

    def test_resilient_matches_plain_dispatcher(self):
        reqs = make_workload(24, 8, 4, 1, seed=13)
        plain = QRServer(backend="reference")
        resil = QRServer(backend="reference", resilient=True)
        tp = [self._submit(plain, r) for r in reqs]
        tr = [self._submit(resil, r) for r in reqs]
        plain.flush()
        resil.flush()
        for a, b in zip(tp, tr):
            for x, y in zip(_as_tuple(plain.result(a)),
                            _as_tuple(resil.result(b))):
                assert np.array_equal(np.asarray(x), np.asarray(y))

    def test_provenance_records_native_single_attempt(self):
        rng = np.random.default_rng(14)
        d = _fast()
        eng = ContinuousBatcher(d)
        t = eng.submit("append", *_append_args(rng))
        eng.flush()
        eng.result(t)
        prov = d.provenance[(t.group, t.cycle)][0]
        assert prov.rung == "native" and prov.attempts == 1
        assert prov.error is None and not prov.quarantined


# -------------------------------------------------------------- state vault
class TestStateVault:
    def _state(self, rng, n=4, k=1):
        A = rng.standard_normal((8, n)).astype(np.float32)
        R = np.triu(np.linalg.qr(A)[1]).astype(np.float32)
        return RLSState(R=jnp.asarray(R),
                        d=jnp.asarray(
                            rng.standard_normal((n, k)).astype(np.float32)),
                        count=jnp.asarray(8, dtype=jnp.int32))

    def test_snapshot_cadence_and_gc(self, tmp_path):
        rng = np.random.default_rng(15)
        vault = StateVault(root=str(tmp_path), interval=2, keep=2)
        for _ in range(6):
            vault.snapshot("m", self._state(rng))
        steps = sorted(os.listdir(tmp_path / "m"))
        assert len(steps) == 2  # gc kept the newest `keep`

    def test_restore_falls_back_past_corruption(self, tmp_path):
        rng = np.random.default_rng(16)
        vault = StateVault(root=str(tmp_path), interval=1, keep=4)
        good = self._state(rng)
        vault.snapshot("m", good)
        bad = good._replace(R=good.R.at[0, 0].set(jnp.nan))
        vault.snapshot("m", bad)
        restored, step = vault.restore_latest("m", like=good)
        np.testing.assert_array_equal(np.asarray(restored.R),
                                      np.asarray(good.R))
        assert step == 1  # fell back past the newest (corrupt) snapshot

    def test_all_corrupt_raises_integrity_error(self, tmp_path):
        rng = np.random.default_rng(17)
        vault = StateVault(root=str(tmp_path), interval=1)
        good = self._state(rng)
        bad = good._replace(R=good.R.at[0, 0].set(jnp.nan))
        vault.snapshot("m", bad)
        with pytest.raises(IntegrityError):
            vault.restore_latest("m", like=good)

    def test_state_integrity_cond_gate(self):
        rng = np.random.default_rng(18)
        ok_state = self._state(rng)
        ok, _ = state_integrity(ok_state)
        assert ok
        ill = ok_state._replace(R=ok_state.R.at[-1, -1].set(1e-12))
        ok, reason = state_integrity(ill, max_cond=1e3)
        assert not ok and "cond" in reason


# ------------------------------------------------------------ the injectors
class TestFaultHarness:
    def test_plan_deterministic_per_seed(self):
        def trace(seed):
            inj = FaultInjector(FaultPlan(seed=seed, transient_rate=0.5),
                                sleep=_NO_SLEEP)
            out = []
            for _ in range(32):
                try:
                    inj.on_dispatch(kind="append", rung="native",
                                    dispatcher=None)
                    out.append(0)
                except InjectedTransient:
                    out.append(1)
            return out

        assert trace(3) == trace(3)
        assert trace(3) != trace(4)

    def test_transient_limit(self):
        inj = FaultInjector(FaultPlan(seed=0, transient_rate=1.0,
                                      transient_limit=2), sleep=_NO_SLEEP)
        raised = 0
        for _ in range(8):
            try:
                inj.on_dispatch(kind="append", rung="native",
                                dispatcher=None)
            except InjectedTransient:
                raised += 1
        assert raised == 2

    def test_kind_filter(self):
        inj = FaultInjector(FaultPlan(seed=0, transient_rate=1.0,
                                      kinds=("lstsq",)), sleep=_NO_SLEEP)
        inj.on_dispatch(kind="append", rung="native", dispatcher=None)
        with pytest.raises(InjectedTransient):
            inj.on_dispatch(kind="lstsq", rung="native", dispatcher=None)

    def test_poison_workload_pure(self):
        reqs = make_workload(8, 6, 3, 1, seed=19)
        before = [np.asarray(r[1]).copy() for r in reqs]
        poisoned, idx = poison_workload(reqs, 0.25, seed=19)
        assert len(idx) == 2
        for r, b in zip(reqs, before):
            assert np.array_equal(np.asarray(r[1]), b)  # input untouched
        for i in idx:
            assert not np.isfinite(np.asarray(poisoned[i][1])).all()

    def test_injector_install_is_scoped(self):
        from repro.serve import resilience
        sentinel = ScriptedInjector(set())
        with inject(sentinel) as got:
            assert got is sentinel
            assert resilience.get_injector() is sentinel
        assert resilience.get_injector() is not sentinel
