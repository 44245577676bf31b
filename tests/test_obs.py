"""repro.obs: registry semantics, exporters, and end-to-end serving metrics.

Three layers of coverage:

* registry unit semantics — histogram exact quantiles, gauge excursions,
  label-series separation, the no-op default's zero-allocation contract;
* exporter round-trips — JSONL snapshot schema in/out, the CI
  required-families gate, Prometheus text exposition shape;
* integration — a real ``QRServer`` workload flushed under a collector must
  emit the full serving metric contract (queue-wait, flush-duration,
  padding-waste, dispatch time, ...) on both backends, and on a sharded
  host mesh when one is available.
"""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import obs
from repro.launch.serve_qr import QRServer, _submit_all, make_workload


# --------------------------------------------------------------- registry
def test_counter_monotone():
    reg = obs.MetricsRegistry()
    c = reg.counter("x.events", kind="a")
    c.inc()
    c.inc(3)
    assert c.value == 4
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_tracks_excursion():
    reg = obs.MetricsRegistry()
    g = reg.gauge("x.level")
    for v in (0.5, 2.0, -1.0):
        g.set(v)
    assert g.value == -1.0 and g.min == -1.0 and g.max == 2.0 and g.updates == 3


def test_histogram_exact_quantiles():
    reg = obs.MetricsRegistry()
    h = reg.histogram("x.latency")
    for v in range(1, 101):
        h.observe(float(v))
    assert h.count == 100 and h.min == 1.0 and h.max == 100.0
    assert h.quantile(0.0) == 1.0 and h.quantile(1.0) == 100.0
    assert abs(h.quantile(0.5) - 50.5) < 1e-9  # midpoint interpolation
    assert abs(h.quantile(0.99) - 99.01) < 1e-9
    with pytest.raises(ValueError):
        h.quantile(1.5)
    # cumulative buckets: monotone, +Inf bucket == count
    bks = h.buckets((10.0, 50.0))
    assert bks == [(10.0, 10), (50.0, 50), (math.inf, 100)]


def test_label_series_are_separate():
    reg = obs.MetricsRegistry()
    a = reg.counter("serve.reqs", kind="append")
    b = reg.counter("serve.reqs", kind="lstsq")
    a.inc(5)
    assert b.value == 0
    assert reg.find("serve.reqs", kind="append") is a
    assert reg.find("serve.reqs", kind="nope") is None
    assert reg.families() == {"serve.reqs"}
    # one name cannot be two metric kinds
    with pytest.raises(TypeError):
        reg.gauge("serve.reqs", kind="append")


def test_null_default_is_shared_noop():
    """With no collector installed nothing is recorded OR allocated: every
    handle is one shared singleton and the active registry stays empty."""
    assert not obs.enabled()
    h1 = obs.histogram("x.a", k="1")
    h2 = obs.counter("y.b")
    assert h1 is h2  # the shared _NullMetric
    h1.observe(1.0)
    h2.inc()
    obs.gauge("z").set(3.0)
    assert obs.registry().collect() == []
    assert math.isnan(h1.quantile(0.5))


def test_collecting_installs_and_restores():
    assert not obs.enabled()
    with obs.collecting() as reg:
        assert obs.enabled() and obs.registry() is reg
        obs.counter("t.c").inc()
        # nested explicit install stacks correctly
        inner = obs.MetricsRegistry()
        with obs.collecting(inner):
            assert obs.registry() is inner
        assert obs.registry() is reg
    assert not obs.enabled()
    assert reg.find("t.c").value == 1


def test_device_timer_blocks_on_dispatch():
    x = jnp.ones((64, 64))
    f = jax.jit(lambda a: a @ a)
    jax.block_until_ready(f(x))
    with obs.device_timer() as t:
        t.stop(f(x))
    assert t.seconds > 0.0


def test_health_recorders_are_tracer_safe():
    R = jnp.asarray(np.diag([4.0, 2.0, 1.0]), jnp.float32)
    with obs.collecting() as reg:
        obs.factor_health(R, "unit")
        # under tracing: must silently skip, not crash or record garbage
        jax.jit(lambda r: (obs.factor_health(r, "traced"), r)[1])(R)
    assert reg.find("unit.r_diag_min").value == 1.0
    assert reg.find("unit.r_diag_max").value == 4.0
    # the iterative condition estimate converges from below
    assert (reg.find("unit.r_cond_estimate").value
            == pytest.approx(4.0, rel=1e-5))
    assert reg.find("unit.r_cond_proxy") is None  # the alias is gone
    assert reg.find("traced.r_diag_min") is None


def test_orthogonality_loss_detects_good_and_bad():
    rng = np.random.default_rng(0)
    A = jnp.asarray(rng.standard_normal((32, 8)), jnp.float32)
    R = jnp.linalg.qr(A, mode="r")
    assert obs.orthogonality_loss(A, R) < 1e-4
    assert obs.orthogonality_loss(A, R * 1.5) > 0.1  # wrong factor -> loud


def test_orthogonality_sampling_respects_env(monkeypatch):
    monkeypatch.setenv("REPRO_OBS_ORTHO_EVERY", "1")
    rng = np.random.default_rng(1)
    A = jnp.asarray(rng.standard_normal((16, 4)), jnp.float32)
    R = jnp.linalg.qr(A, mode="r")
    with obs.collecting() as reg:
        loss = obs.maybe_sample_orthogonality(A, R, "unit")
    assert loss is not None and loss < 1e-4
    assert reg.find("unit.orthogonality_samples").value == 1


def test_ortho_tolerance_scales_with_dtype_eps():
    """The audit threshold is 64*n*eps of the *compute* dtype — a hardcoded
    f32 constant would page on every healthy bf16 factorization."""
    n = 8
    assert obs.ortho_tolerance(n, "float32") == pytest.approx(
        64 * n * float(jnp.finfo(jnp.float32).eps))
    assert obs.ortho_tolerance(n, "bfloat16") == pytest.approx(
        64 * n * float(jnp.finfo(jnp.bfloat16).eps))
    assert obs.ortho_tolerance(n, "bfloat16") > 1e4 * obs.ortho_tolerance(
        n, "float32")


def test_orthogonality_alarm_keyed_to_dtype(monkeypatch):
    """A healthy bf16-stored factor breaches the f32 tolerance but must not
    alarm when judged at its own precision; a truly wrong factor alarms at
    any precision."""
    monkeypatch.setenv("REPRO_OBS_ORTHO_EVERY", "1")
    rng = np.random.default_rng(2)
    A = jnp.asarray(rng.standard_normal((32, 8)), jnp.float32)
    R = jnp.linalg.qr(A, mode="r")
    R16 = R.astype(jnp.bfloat16)
    loss16 = obs.orthogonality_loss(A, R16)
    assert loss16 > obs.ortho_tolerance(8, "float32")  # the old-style page
    with obs.collecting() as reg:
        obs.maybe_sample_orthogonality(A, R16, "unit")  # dtype from R: bf16
    assert reg.find("unit.orthogonality_alarms") is None
    assert reg.find("unit.orthogonality_tolerance").value == pytest.approx(
        obs.ortho_tolerance(8, "bfloat16"))
    # explicit dtype override: judge the same sample at f32 -> alarm
    with obs.collecting() as reg:
        obs.maybe_sample_orthogonality(A, R16, "unit", dtype="float32")
    assert reg.find("unit.orthogonality_alarms").value == 1
    # a genuinely wrong factor alarms even at bf16 tolerance (an
    # undersized R inflates Q: loss ~ 1/s^2 - 1 >> 64*n*eps(bf16))
    with obs.collecting() as reg:
        obs.maybe_sample_orthogonality(A, R / 30.0, "unit", dtype="bfloat16")
    assert reg.find("unit.orthogonality_alarms").value == 1


def test_orthogonality_loss_accepts_full_triangularized_matrix():
    """(m, n) inputs (full triangularized matrices, zeros below the top
    square) audit identically to their top (n, n) block."""
    rng = np.random.default_rng(3)
    A = rng.standard_normal((24, 6))
    Rfull = np.linalg.qr(A, mode="complete")[1]  # (24, 6), zero rows below
    Rsq = Rfull[:6]
    assert obs.orthogonality_loss(A, Rfull) == pytest.approx(
        obs.orthogonality_loss(A, Rsq))


# --------------------------------------------------------------- exporters
def test_jsonl_snapshot_roundtrip(tmp_path):
    with obs.collecting() as reg:
        reg.counter("a.count", kind="x").inc(7)
        reg.gauge("a.level").set(0.25)
        for v in (0.1, 0.2, 0.3):
            reg.histogram("a.lat").observe(v)
    path = str(tmp_path / "snap.jsonl")
    obs.write_jsonl(path, reg, meta={"run": "t1"})
    obs.write_jsonl(path, reg, meta={"run": "t2"})  # append mode
    snaps = obs.load_jsonl(path)
    assert len(snaps) == 2
    snap = snaps[-1]
    assert snap["schema"] == "repro.obs/v1" and snap["meta"]["run"] == "t2"
    by_name = {m["name"]: m for m in snap["metrics"]}
    assert by_name["a.count"]["value"] == 7
    assert by_name["a.count"]["labels"] == {"kind": "x"}
    assert by_name["a.level"]["value"] == 0.25
    h = by_name["a.lat"]
    assert h["count"] == 3 and abs(h["sum"] - 0.6) < 1e-9
    assert abs(h["quantiles"]["0.5"] - 0.2) < 1e-9
    # the CI gate sees these families as present, others as missing
    assert obs.missing_families(snap, ("a.count", "a.lat")) == []
    assert obs.missing_families(snap, ("a.count", "b.nope")) == ["b.nope"]


def test_load_jsonl_rejects_wrong_schema(tmp_path):
    path = tmp_path / "bad.jsonl"
    path.write_text('{"schema": "other/v9", "metrics": []}\n')
    with pytest.raises(ValueError):
        obs.load_jsonl(str(path))


def test_prometheus_text_exposition():
    with obs.collecting() as reg:
        reg.counter("serve.requests_served", kind="append").inc(3)
        reg.histogram("serve.queue_wait_seconds", kind="append").observe(0.02)
    text = obs.prometheus_text(reg)
    assert '# TYPE serve_requests_served counter' in text
    assert 'serve_requests_served{kind="append"} 3.0' in text
    assert '# TYPE serve_queue_wait_seconds histogram' in text
    assert 'serve_queue_wait_seconds_bucket{kind="append",le="+Inf"} 1' in text
    assert 'serve_queue_wait_seconds_count{kind="append"} 1' in text
    # dots sanitized everywhere, no stray family names with dots
    assert "serve.queue" not in text


# ------------------------------------------------------------- integration
def _flush_under_collector(backend, mesh=None, num=12):
    reqs = make_workload(num, 8, 4, 1)
    server = QRServer(backend=backend, max_batch=8, mesh=mesh)
    with obs.collecting() as reg:
        _submit_all(server, reqs)
        served = server.flush()
        server.drain()
    return reg, served, num


def _assert_serving_contract(reg, served, num):
    submitted = sum(m.value for m in reg.collect()
                    if m.name == "serve.requests_submitted")
    done = sum(m.value for m in reg.collect()
               if m.name == "serve.requests_served")
    assert submitted == done == served == num
    # every request saw the queue: queue-wait observations cover the workload
    qwaits = [m for m in reg.collect() if m.name == "serve.queue_wait_seconds"]
    assert qwaits and sum(h.count for h in qwaits) == num
    assert all(h.min >= 0.0 for h in qwaits)
    # one flush-duration observation per flushed group, sane batch sizes
    fls = [m for m in reg.collect() if m.name == "serve.flush_duration_seconds"]
    assert fls and all(h.min > 0.0 for h in fls)
    bss = [m for m in reg.collect() if m.name == "serve.batch_size"]
    assert bss and all(1 <= h.min and h.max <= num for h in bss)
    # per-dispatch accounting: padding-waste fraction, the host time from
    # close to seen-ready and the work done (no flop rate: that time is not
    # device time)
    pads = [m for m in reg.collect() if m.name == "serve.padding_waste"]
    assert pads and all(0.0 <= g.min and g.max < 1.0 for g in pads)
    dts = [m for m in reg.collect() if m.name == "serve.dispatch_seconds"]
    assert dts and all(h.min > 0.0 for h in dts)
    assert sum(m.value for m in reg.collect()
               if m.name == "serve.flops_total") > 0
    assert not any(m.name == "serve.achieved_gflops" for m in reg.collect())
    # first dispatch of each (group, chunk) signature is a compile
    misses = sum(m.value for m in reg.collect()
                 if m.name == "serve.executable_cache_miss")
    assert misses >= 1
    # all queues drained by the end of the flush
    depths = [m for m in reg.collect() if m.name == "serve.queue_depth"]
    assert depths and all(g.value == 0.0 for g in depths)
    # factor-health gauges ride along for R-producing kinds
    assert any(m.name == "serve.r_cond_estimate" for m in reg.collect())


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_qrserver_flush_emits_serving_metrics(backend):
    reg, served, num = _flush_under_collector(backend)
    _assert_serving_contract(reg, served, num)


def test_qrserver_flush_metrics_on_host_mesh():
    from repro.parallel.sharding import make_batch_mesh

    try:
        mesh = make_batch_mesh(min(4, jax.device_count()))
    except ValueError:
        pytest.skip("needs a multi-device (or forced host-device) mesh")
    if math.prod(mesh.devices.shape) < 2:
        pytest.skip("needs >= 2 devices")
    reg, served, num = _flush_under_collector("pallas", mesh=mesh, num=16)
    _assert_serving_contract(reg, served, num)
    # sharded pad_batch rounds chunks up to shards x block_b: with 16
    # requests over mixed kinds some group must have been padded
    pads = [m for m in reg.collect() if m.name == "serve.padding_waste"]
    assert any(g.max > 0.0 for g in pads)


def test_uninstrumented_flush_records_nothing():
    """The no-collector serving path must leave the null registry untouched
    (the <5%-overhead contract is enforced by never doing the work)."""
    assert not obs.enabled()
    reqs = make_workload(6, 8, 4, 1)
    server = QRServer(backend="reference", max_batch=8)
    _submit_all(server, reqs)
    server.flush()
    server.drain()
    assert obs.registry().collect() == []
    assert not server._submit_times and not server._seen_dispatch
