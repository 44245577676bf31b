"""Mixed-precision GGR under the numerical-error tracking harness.

Coverage layers (ROADMAP item 8):

* policy algebra: ``resolve_precision`` aliases, canonicalization,
  validation (accumulator may never be narrower than compute);
* regression: ``precision="f32"`` is *bitwise* the legacy no-policy path
  through every kernel and both blocked schedules — the policy plumbing
  must be invisible when it is not asked for;
* graded suites: bf16 tiles + f32 accumulation meet the documented
  dtype-eps-scaled error budgets against the f64/f32 oracles on matrices
  with controlled condition numbers 1e0..1e8 (the gram residual stays
  condition-independent; cond-amplified metrics are asserted only where
  ``budget_is_meaningful`` says they still discriminate);
* discrimination: the mixed policy (f32 accumulators) must beat a
  deliberately broken all-bf16 policy — the regression that would pass a
  loose tolerance but means the wide accumulation was lost;
* serving: bf16 storage states round-trip through ``QRServer`` at their
  own dtype while a precision policy governs compute, and bf16 storage
  doubles the dispatch block;
* filters: a bf16-state Kalman fleet stays innovation-consistent
  (mean NIS ~ p), single-device and under a 4-way host mesh.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.blocked import ggr_triangularize_blocked
from repro.kernels import (
    Precision,
    batched_geqrt,
    batched_update,
    panel_qr,
    resolve_precision,
)
from repro.serve import Dispatcher
from repro.launch.serve_qr import QRServer
from repro.solvers import qr_append_rows_batched
from repro.testing import (
    budget_is_meaningful,
    dtype_eps,
    error_budget,
    factorization_errors,
    fleet_nis,
    graded_matrix,
    gram_residual,
    matrix_suite,
)

BF16 = Precision("bfloat16", "float32", "bfloat16")


# ------------------------------------------------------------ policy algebra

def test_resolve_none_is_f32_everywhere():
    p = resolve_precision(None)
    assert p == Precision("float32", "float32", "float32")
    assert not p.is_mixed


@pytest.mark.parametrize("name,expect", [
    ("f32", Precision("float32", "float32", "float32")),
    ("f64", Precision("float64", "float64", "float64")),
    ("bf16", BF16),
    ("bfloat16", BF16),
    ("mixed_bf16", BF16),
    ("f16", Precision("float16", "float32", "float16")),
    ("mixed_f16", Precision("float16", "float32", "float16")),
])
def test_resolve_aliases(name, expect):
    assert resolve_precision(name) == expect


def test_low_precision_aliases_accumulate_wide():
    for name in ("bf16", "f16", "mixed_bf16", "mixed_f16"):
        p = resolve_precision(name)
        assert p.accum_dtype == "float32" and p.is_mixed


def test_resolve_canonicalizes_shorthand_fields():
    p = resolve_precision(Precision("bf16", "f32", "bf16"))
    assert p == BF16
    assert p.compute == jnp.dtype(jnp.bfloat16)
    assert p.accum == jnp.dtype(jnp.float32)


def test_resolve_is_idempotent():
    p = resolve_precision("bf16")
    assert resolve_precision(p) == p


def test_resolve_rejects_unknown_name():
    with pytest.raises(ValueError):
        resolve_precision("int8")


def test_resolve_rejects_narrowing_accumulator():
    with pytest.raises(ValueError):
        resolve_precision(Precision("float32", "bfloat16", "float32"))


# ------------------------------------------------- f32 bitwise no-regression

@pytest.mark.parametrize("schedule", ["tree", "fused"])
def test_blocked_f32_policy_is_bitwise_legacy(schedule):
    A = jnp.asarray(graded_matrix(96, 80, 1e3, seed=11), jnp.float32)
    legacy = ggr_triangularize_blocked(A, tile=32, schedule=schedule)
    policy = ggr_triangularize_blocked(A, tile=32, schedule=schedule,
                                       precision="f32")
    np.testing.assert_array_equal(np.asarray(legacy), np.asarray(policy))


def test_kernel_f32_policy_is_bitwise_legacy():
    rng = np.random.default_rng(12)
    panel = jnp.asarray(rng.standard_normal((64, 16)), jnp.float32)
    R0, V0, T0 = panel_qr(panel)
    R1, V1, T1 = panel_qr(panel, precision="f32")
    for a, b in [(R0, R1), (V0, V1), (T0, T1)]:
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    tiles = jnp.asarray(rng.standard_normal((4, 32, 16)), jnp.float32)
    g0 = batched_geqrt(tiles, n_pivots=16)
    g1 = batched_geqrt(tiles, n_pivots=16, precision="f32")
    for a, b in zip(g0 if isinstance(g0, tuple) else (g0,),
                    g1 if isinstance(g1, tuple) else (g1,)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    stacked = jnp.asarray(rng.standard_normal((3, 24, 16)), jnp.float32)
    u0 = batched_update(stacked, n_pivots=16)
    u1 = batched_update(stacked, n_pivots=16, precision="f32")
    np.testing.assert_array_equal(np.asarray(u0), np.asarray(u1))


# ------------------------------------------------------------- graded suites

_CASES = list(matrix_suite(shapes=((96, 80),), seed=7))
_EXTRA = list(matrix_suite(shapes=((64, 48),), conds=(1e0, 1e8), seed=21))


@pytest.mark.parametrize("schedule", ["tree", "fused"])
@pytest.mark.parametrize("case", _CASES + _EXTRA, ids=lambda c: c.name)
def test_blocked_bf16_meets_budgets(case, schedule):
    m, n = case.A.shape
    A32 = jnp.asarray(case.A, jnp.float32)
    R = ggr_triangularize_blocked(A32, tile=32, schedule=schedule,
                                  precision="bf16")
    assert R.dtype == jnp.bfloat16
    errs = factorization_errors(case.A, R, R_ref=np.linalg.qr(case.A)[1])
    for metric, value in errs.items():
        if not budget_is_meaningful("bfloat16", metric, m, n, case.cond):
            continue
        budget = error_budget("bfloat16", metric, m, n, case.cond)
        assert value < budget, (case.name, metric, value, budget)
    # gram residual must always be meaningful and within budget: it is the
    # one condition-independent contract the policy documents
    assert budget_is_meaningful("bfloat16", "gram_residual", m, n, case.cond)


@pytest.mark.parametrize("case", _CASES, ids=lambda c: c.name)
def test_blocked_f32_meets_budgets(case):
    m, n = case.A.shape
    R = ggr_triangularize_blocked(jnp.asarray(case.A, jnp.float32), tile=32)
    errs = factorization_errors(case.A, R, R_ref=np.linalg.qr(case.A)[1])
    for metric, value in errs.items():
        if not budget_is_meaningful("float32", metric, m, n, case.cond):
            continue
        assert value < error_budget("float32", metric, m, n, case.cond), (
            case.name, metric, value)


def test_mixed_accumulation_beats_all_bf16():
    """f32 accumulators are the point of the policy: a deliberately broken
    all-bf16 policy must be measurably worse, so losing wide accumulation
    can never hide inside a loose tolerance."""
    A = graded_matrix(96, 80, 1.0, seed=7)
    A32 = jnp.asarray(A, jnp.float32)
    mixed = gram_residual(A, ggr_triangularize_blocked(A32, precision="bf16"))
    broken = gram_residual(A, ggr_triangularize_blocked(
        A32, precision=Precision("bfloat16", "bfloat16", "bfloat16")))
    assert mixed * 1.5 < broken, (mixed, broken)


def test_cliff_spectrum_survives_bf16():
    """Half the spectrum at 1, half at 1/cond — near-rank-deficiency must
    not blow up the condition-independent gram residual."""
    A = graded_matrix(96, 64, 1e8, seed=5, spectrum="cliff")
    R = ggr_triangularize_blocked(jnp.asarray(A, jnp.float32),
                                  precision="bf16")
    assert gram_residual(A, R) < error_budget("bfloat16", "gram_residual",
                                              96, 64)


# ------------------------------------------------------------- kernel layer

def test_panel_qr_bf16_budget():
    A = graded_matrix(64, 16, 1e2, seed=31)
    R, V, T = panel_qr(jnp.asarray(A, jnp.float32), precision="bf16")
    assert R.dtype == jnp.bfloat16
    assert gram_residual(A, R) < error_budget("bfloat16", "gram_residual",
                                              64, 16)


def test_batched_geqrt_bf16_budget():
    tiles = np.stack([graded_matrix(32, 16, 10.0 ** i, seed=40 + i)
                      for i in range(4)])
    out = batched_geqrt(jnp.asarray(tiles, jnp.float32), n_pivots=16,
                        precision="bf16")
    tri = out[0] if isinstance(out, tuple) else out
    assert tri.dtype == jnp.bfloat16
    for b in range(4):
        assert gram_residual(tiles[b], tri[b]) < error_budget(
            "bfloat16", "gram_residual", 32, 16), b


def test_batched_update_bf16_budget():
    """Row-append sweeps (triangular R + p new rows — the kernel's contract)
    stay within the bf16 gram budget."""
    rng = np.random.default_rng(50)
    n, p = 16, 8
    stacked = np.stack([
        np.concatenate([np.triu(rng.standard_normal((n, n))) + 2 * np.eye(n),
                        rng.standard_normal((p, n))])
        for _ in range(3)])
    out = batched_update(jnp.asarray(stacked, jnp.float32), n_pivots=n,
                         precision="bf16")
    assert out.dtype == jnp.bfloat16
    for b in range(3):
        assert gram_residual(stacked[b], out[b]) < error_budget(
            "bfloat16", "gram_residual", n + p, n), b


def test_qr_append_bf16_carries_compute_dtype():
    rng = np.random.default_rng(60)
    B, n, p = 5, 8, 3
    Rb = jnp.asarray(np.triu(rng.standard_normal((B, n, n)))
                     + 2 * np.eye(n), jnp.float32)
    Ub = jnp.asarray(rng.standard_normal((B, p, n)), jnp.float32)
    Rn = qr_append_rows_batched(Rb, Ub, precision="bf16")
    assert Rn.dtype == jnp.bfloat16
    Rf = qr_append_rows_batched(Rb, Ub)
    for b in range(B):
        stacked = np.concatenate([np.asarray(Rb[b]), np.asarray(Ub[b])])
        assert gram_residual(stacked, Rn[b]) < error_budget(
            "bfloat16", "gram_residual", n + p, n), b
    rel = (np.linalg.norm(np.asarray(Rn, np.float64) - np.asarray(Rf, np.float64))
           / np.linalg.norm(np.asarray(Rf, np.float64)))
    assert rel < 8 * dtype_eps("bfloat16")


# ------------------------------------------------------------------ serving

def test_bf16_storage_doubles_dispatch_block():
    d = Dispatcher(block_b=8)
    assert d.block_b_for("float32") == 8
    assert d.block_b_for("float64") == 8
    assert d.block_b_for("bfloat16") == 16
    assert d.block_b_for("float16") == 16
    assert d.padded_chunk(3, "append", "float32") == 8
    assert d.padded_chunk(3, "append", "bfloat16") == 16
    assert d.padded_chunk(17, "append", "bfloat16") == 32


def test_chunk_precision_policy_table():
    d32 = Dispatcher(precision="f32")
    dbf = Dispatcher(precision="bf16")
    dnone = Dispatcher()
    # f32 policy: bf16 storage is up-cast to f32 compute, no kernel policy
    assert d32._chunk_precision("bfloat16") == ("float32", None)
    assert d32._chunk_precision("float32") == ("float32", None)
    # bf16 policy: bf16 storage computes in bf16 with f32 accumulation
    cd, kp = dbf._chunk_precision("bfloat16")
    assert cd == "bfloat16" and kp == BF16
    # ...but f32 storage is never silently down-cast by a policy
    assert dbf._chunk_precision("float32") == ("float32", None)
    assert dbf._chunk_precision("float64") == ("float64", None)
    # no policy: storage dtype passes straight through
    assert dnone._chunk_precision("bfloat16") == ("bfloat16", None)


@pytest.mark.parametrize("policy", [None, "f32", "bf16"])
def test_server_bf16_storage_round_trip(policy):
    """bf16 (R, d) states come back as bf16 whatever the compute policy,
    and close to the f32-served oracle."""
    rng = np.random.default_rng(70)
    n, p = 8, 3
    R = np.triu(rng.standard_normal((n, n))) + 2 * np.eye(n)
    U = rng.standard_normal((p, n))
    server = QRServer(backend="pallas", interpret=True, precision=policy)
    t16 = server.submit_append(jnp.asarray(R, jnp.bfloat16),
                               jnp.asarray(U, jnp.bfloat16))
    t32 = server.submit_append(jnp.asarray(R, jnp.float32),
                               jnp.asarray(U, jnp.float32))
    server.flush()
    server.drain()
    R16 = server.result(t16)
    R32 = server.result(t32)
    assert R16.dtype == jnp.bfloat16
    assert R32.dtype == jnp.float32
    rel = (np.linalg.norm(np.asarray(R16, np.float64) - np.asarray(R32, np.float64))
           / np.linalg.norm(np.asarray(R32, np.float64)))
    assert rel < 8 * dtype_eps("bfloat16"), rel


# ------------------------------------------------------------------- kalman

def test_kalman_fleet_bf16_nis_consistent():
    p = 2
    nis = fleet_nis(B=4, n=4, w=4, p=p, T=100, seed=3, precision="bf16",
                    backend="pallas", interpret=True)
    assert np.all(0.7 * p < nis) and np.all(nis < 1.3 * p), nis


def test_kalman_fleet_bf16_nis_consistent_sharded():
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices (multi-device CI job sets "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=4)")
    from repro.parallel.sharding import make_batch_mesh

    p = 2
    nis = fleet_nis(B=8, n=4, w=4, p=p, T=60, seed=9, precision="bf16",
                    backend="pallas", interpret=True, block_b=2,
                    mesh=make_batch_mesh(4))
    assert np.all(0.7 * p < nis) and np.all(nis < 1.3 * p), nis
