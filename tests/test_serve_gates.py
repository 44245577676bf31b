"""Serving acceptance gates: a seeded chaos run, and the metric families an
instrumented serving run must leave in its snapshot.

The chaos run serves one ``make_workload`` mix twice through
``ContinuousBatcher`` over ``ResilientDispatcher`` (reference backend) on
the same batch boundaries (``admit_max`` closes only): once fault-free, once
under a seeded ``FaultPlan`` of 5% transient executor failures.  1% of the
requests carry a NaN.  Under the same collector, a ladder drill forces one
dispatch onto every rung of ``DEFAULT_LADDER`` and a purge drill exhausts a
one-rung ladder.

The snapshot gates write a run's registry with ``obs.write_jsonl`` and
check the file as ``python -m repro.obs.export --validate`` does, one case
per preset: ``serve`` (the closed-loop facade), ``async`` (double-buffered
continuous batching plus an admission drill that rejects and sheds) and
``chaos`` (the chaos run above).
"""
import contextlib
import types

import numpy as np
import pytest

from repro import obs
from repro.launch.serve_qr import QRServer, _as_tuple, _submit_all, make_workload
from repro.obs import export
from repro.serve import (
    DEFAULT_LADDER,
    AdmissionPolicy,
    ContinuousBatcher,
    Dispatcher,
    LatencyTier,
    PoisonedError,
    Rejected,
    ResilientDispatcher,
    RetryPolicy,
    Rung,
    ServeError,
)
from repro.serve.requests import KINDS
from repro.testing.faults import (
    FaultPlan,
    ScriptedInjector,
    inject,
    poison_workload,
)

SEED = 7
MAX_BATCH = 16
_NO_SLEEP = lambda s: None  # noqa: E731 — retries need not wait out backoff


def _counter_sum(reg, name, **labels):
    return sum(m.value for m in reg.collect()
               if m.name == name
               and all(dict(m.labels).get(k) == v for k, v in labels.items()))


def _same_bits(x, y):
    x, y = np.asarray(x), np.asarray(y)
    return (x.dtype == y.dtype and x.shape == y.shape
            and x.tobytes() == y.tobytes())


# ---------------------------------------------------------------- chaos run
def _serve_once(reqs, plan=None):
    dispatcher = ResilientDispatcher(backend="reference", max_batch=MAX_BATCH,
                                     sleep=_NO_SLEEP)
    engine = ContinuousBatcher(dispatcher, admit_max=MAX_BATCH,
                               retain_cycles=None)
    with (inject(plan) if plan is not None
          else contextlib.nullcontext()) as injector:
        tickets = [engine.submit(r[0], *r[1:]) for r in reqs]
        engine.flush()
        engine.drain()
    outcomes = []
    for t in tickets:
        try:
            outcomes.append(("ok", _as_tuple(engine.result(t))))
        except PoisonedError:
            outcomes.append(("poisoned", None))
        except ServeError:
            outcomes.append(("error", None))
    return types.SimpleNamespace(dispatcher=dispatcher, tickets=tickets,
                                 outcomes=outcomes, injector=injector)


def _drill_problem(seed):
    rng = np.random.default_rng(seed)
    R = np.triu(rng.standard_normal((8, 8))).astype(np.float32)
    np.fill_diagonal(R, np.abs(np.diag(R)) + 1.0)
    return R, rng.standard_normal((4, 8)).astype(np.float32)


def _ladder_drill():
    """Fail the first k attempts of one dispatch, for each rung k: returns
    (expected rung, served rung, result) per rung."""
    R, U = _drill_problem(1234)
    drilled = []
    for k, rung in enumerate(DEFAULT_LADDER):
        dispatcher = ResilientDispatcher(
            backend="reference", max_batch=8,
            retry=RetryPolicy(max_attempts=1, backoff=0.0), sleep=_NO_SLEEP)
        engine = ContinuousBatcher(dispatcher)
        with inject(ScriptedInjector(set(range(k)))):
            t = engine.submit("append", R, U)
            engine.flush()
        served = dispatcher.provenance[(t.group, t.cycle)][0].rung
        drilled.append((rung.name, served, np.asarray(engine.result(t))))
    return drilled


def _purge_drill():
    """Exhaust a one-rung ladder: the ticket resolves to a ServeError."""
    dispatcher = ResilientDispatcher(
        backend="reference", ladder=(Rung("native"),),
        retry=RetryPolicy(max_attempts=1), sleep=_NO_SLEEP)
    engine = ContinuousBatcher(dispatcher)
    with inject(ScriptedInjector(set(range(64)))):
        t = engine.submit("append", *_drill_problem(4321))
        engine.flush()
    with pytest.raises(ServeError):
        engine.result(t)
    engine.drain()  # purged cycles must not break drain


@pytest.fixture(scope="module")
def chaos():
    reqs = make_workload(96, 8, 4, 1, seed=SEED)
    reqs, poisoned = poison_workload(reqs, 0.01, seed=SEED)
    with obs.collecting() as reg:
        base = _serve_once(reqs)
        fault = _serve_once(reqs, FaultPlan(seed=SEED, transient_rate=0.05))
        drilled = _ladder_drill()
        _purge_drill()
    return types.SimpleNamespace(reg=reg, reqs=reqs, poisoned=set(poisoned),
                                 base=base, fault=fault, drilled=drilled)


def test_chaos_poisoned_requests_quarantined_in_both_runs(chaos):
    assert chaos.poisoned
    for run in (chaos.base, chaos.fault):
        for i in chaos.poisoned:
            assert run.outcomes[i][0] == "poisoned", i
    # with no faults injected every clean request is served
    assert all(o[0] == "ok" for i, o in enumerate(chaos.base.outcomes)
               if i not in chaos.poisoned)


def test_chaos_availability_of_clean_requests(chaos):
    clean = [i for i in range(len(chaos.reqs)) if i not in chaos.poisoned]
    served = sum(chaos.fault.outcomes[i][0] == "ok" for i in clean)
    assert served / len(clean) >= 0.99


def test_chaos_native_results_bitwise_degraded_within_roundoff(chaos):
    """A request the faults never degraded, retried or not, gets the
    fault-free run's bits; a degraded one agrees within roundoff."""
    assert chaos.fault.injector.counts["transient"] >= 1
    provenance = chaos.fault.dispatcher.provenance
    retried = 0
    for t, (got, ours), (want, theirs) in zip(
            chaos.fault.tickets, chaos.fault.outcomes, chaos.base.outcomes):
        if got != "ok" or want != "ok":
            continue
        prov = provenance[(t.group, t.cycle)][t.index]
        for x, y in zip(ours, theirs, strict=True):
            if prov.rung == "native":
                assert _same_bits(x, y), prov
            else:
                np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                           rtol=1e-4, atol=1e-5)
        retried += prov.rung == "native" and prov.attempts > 1
    assert retried >= 1  # the bitwise check saw re-dispatched chunks


def test_chaos_drills_reach_every_rung_and_purge(chaos):
    (_, _, native), *degraded = chaos.drilled
    for expected, served, result in chaos.drilled:
        assert served == expected
        np.testing.assert_allclose(result, native, rtol=1e-4, atol=1e-5)
    for expected, _, _ in degraded:
        assert _counter_sum(chaos.reg, "serve.degraded_dispatches",
                            to=expected) >= 1, expected
    assert _counter_sum(chaos.reg, "serve.cycles_purged") >= 1


# ---------------------------------------------------------- snapshot gates
def _serve_run():
    """The closed-loop facade on the pallas backend: one warm flush, one
    served flush."""
    reqs = make_workload(32, 8, 4, 1)
    server = QRServer(backend="pallas")
    with obs.collecting() as reg:
        for _ in range(2):
            _submit_all(server, reqs)
            server.flush()
            server.drain()
    return reg


def _admission_drill(reg):
    """Reject once and shed once, so both admission families appear."""
    r = make_workload(1, n=4, rows=2, k=1, seed=99)[0]
    rejecting = ContinuousBatcher(
        Dispatcher(backend="reference"),
        AdmissionPolicy(tiers={r[0]: LatencyTier(max_queue=1)}))
    rejecting.submit(*r)
    with pytest.raises(Rejected):
        rejecting.submit(*r)
    rejecting.flush()
    shedding = ContinuousBatcher(
        Dispatcher(backend="reference"),
        AdmissionPolicy(tiers={r[0]: LatencyTier(
            max_queue=1, on_full="shed_oldest")}),
        retain_cycles=None)
    shedding.submit(*r)
    shedding.submit(*r)
    shedding.flush()
    assert _counter_sum(reg, "serve.admission_rejected") == 1
    assert _counter_sum(reg, "serve.requests_shed") == 1


def _async_run():
    """Double-buffered continuous batching with per-kind deadlines, polled
    between submits, then the admission drill."""
    reqs = make_workload(48, 8, 4, 1, seed=SEED)
    engine = ContinuousBatcher(
        Dispatcher(backend="reference", max_batch=MAX_BATCH,
                   double_buffer=True),
        AdmissionPolicy(tiers={k: LatencyTier(deadline=0.2) for k in KINDS}),
        admit_max=MAX_BATCH, retain_cycles=None)
    with obs.collecting() as reg:
        tickets = []
        for r in reqs:
            engine.poll()
            tickets.append(engine.submit(*r))
        engine.flush()
        engine.drain()
        _admission_drill(reg)
    assert all(engine.done_at(t) is not None for t in tickets)
    return reg


_PRESETS = {
    "serve": obs.REQUIRED_SERVE_FAMILIES,
    "async": obs.REQUIRED_ASYNC_SERVE_FAMILIES,
    "chaos": obs.REQUIRED_SERVE_FAMILIES + obs.REQUIRED_RESILIENCE_FAMILIES,
}


@pytest.mark.parametrize("preset", sorted(_PRESETS))
def test_snapshot_carries_required_families(preset, request, tmp_path):
    if preset == "serve":
        reg = _serve_run()
    elif preset == "async":
        reg = _async_run()
    else:
        reg = request.getfixturevalue("chaos").reg
    path = str(tmp_path / f"{preset}.jsonl")
    obs.write_jsonl(path, reg, {"preset": preset})
    snaps = obs.load_jsonl(path)
    assert len(snaps) == 1
    assert obs.missing_families(snaps[-1], _PRESETS[preset]) == []
    export.main(["--validate", path, "--preset", preset])  # exits on a miss
