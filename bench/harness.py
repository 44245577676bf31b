"""One run of one benchmark cell.

Everything that belongs to one configuration, traffic mix or metric is
found by name from ``BENCHMARK.json``:

* ``bench/configs/<config>.json`` - sizes, source, ``kind`` and limits;
* ``bench/clients/<kind>.py`` - the kind's clients made from the seed, the
  request kind they are served as, the work of one request (``work``) and
  the numbers that decide ``correct`` (``CHECKS``, ``check``);
* ``bench/reference/<name>.py`` - the plain float64 reference that the
  kind names (``REFERENCE``);
* ``bench/traffic/<mix>.json`` - the loop's parameters;
* ``bench/metrics/<metric>.py`` - one reader per metric.

The run drives ``repro.serve.ContinuousBatcher`` over
``repro.serve.Dispatcher(backend="pallas", double_buffer=True)``, with the
program's own defaults for every engine knob no deployment sets.  It never
installs the ``repro.obs`` collector.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
TRACE_AT, TRACE_S = 0.25, 3.0


class NoChip(RuntimeError):
    """The run cannot measure here: no TPU, too few chips, or kernels that
    would run in interpret mode."""


def load_module(path: Path):
    """Import one file by path (names may hold dots and dashes)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(spec: dict, workload: str, root: Path = ROOT) -> dict:
    """The cell, its configuration, its traffic, its client kind (loaded)
    and the paths of its reference and metric readers, each found by name
    under ``root``."""
    bench = root / "bench"
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg = load_json(root / configs[cell["config"]]["file"])
    traffic = load_json(bench / "traffic" / f"{cell['traffic']}.json")

    def applies(m, e2e_names):
        if "workloads" in m:
            return workload in m["workloads"]
        return m.get("moves") is None or m["moves"] in e2e_names

    e2e = [m for m in spec["end_to_end"] if applies(m, ())]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"] if applies(m, names)]
    client = bench / "clients" / f"{cfg['kind']}.py"
    kind = load_module(client)
    return {"cell": cell, "config": cfg, "traffic": traffic,
            "end_to_end": e2e, "per_layer": per_layer,
            "client": client, "kind": kind,
            "reference": bench / "reference" / f"{kind.REFERENCE}.py",
            "readers": {m["name"]: bench / "metrics" / f"{m['name']}.py"
                        for m in e2e + per_layer}}


def check_device(jax, chips: int, bench: Path) -> None:
    from repro.kernels import default_interpret

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
    if os.environ.get("REPRO_PALLAS_INTERPRET") is not None or default_interpret():
        raise NoChip("the kernels would run in interpret mode")
    kind = devs[0].device_kind
    if kind not in load_json(bench / "peaks.json")["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")


class CompileCounter:
    """Counts backend compiles while ``on``."""

    def __init__(self, jax):
        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, *_, **__):
        if self.on and name == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def close(self, jax) -> None:
        jax.monitoring.unregister_event_duration_listener(self._event)


class Profiler:
    """Traces ``[t0 + TRACE_AT * seconds, + TRACE_S]`` of the window."""

    def __init__(self, jax, seconds: float):
        self.jax = jax
        self.span = min(TRACE_S, 0.5 * seconds)
        self.start_after = TRACE_AT * seconds
        self.t0 = self.lo = self.hi = None
        self.dir = None
        self.ann = None

    def tick(self, now: float) -> None:
        if self.t0 is None:
            self.t0 = now
        if self.lo is None and now >= self.t0 + self.start_after:
            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = self.jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            self.jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.ann = self.jax.profiler.TraceAnnotation("bench/window")
            self.ann.__enter__()
            self.lo = time.perf_counter()
        elif self.lo is not None and self.hi is None and now >= self.lo + self.span:
            self.finish()

    def finish(self) -> None:
        """Stop tracing, if it started and has not stopped."""
        if self.lo is not None and self.hi is None:
            self.hi = time.perf_counter()
            self.ann.__exit__(None, None, None)
            self.jax.profiler.stop_trace()


def make_engine(chips: int, retain: int, control: bool):
    """The served path: a continuous batcher over a double-buffered Pallas
    dispatcher, batch-sharded over a mesh of ``chips`` when more than one.
    Batches are closed by the harness at ``max_batch``, so that closes sit
    in harness spans.  ``control`` puts the program's bfloat16 reference
    path in its place."""
    from repro.serve import AdmissionPolicy, ContinuousBatcher, Dispatcher

    mesh = None
    if chips > 1:
        from repro.parallel.sharding import make_batch_mesh

        mesh = make_batch_mesh(chips)
    if control:
        # the control: the program's pure-JAX reference backend in place of
        # the kernels, on bfloat16 operands (its Pallas bfloat16 path does
        # not compile for the chip; see PERF.md)
        disp = Dispatcher(backend="reference", double_buffer=True, mesh=mesh,
                          precision="bf16")
    else:
        disp = Dispatcher(backend="pallas", double_buffer=True, mesh=mesh)
    return ContinuousBatcher(disp, AdmissionPolicy(), admit_max=None,
                             retain_cycles=retain)


def fetch_chains(jax, chains: dict) -> dict:
    """Served results of the sampled clients, on the host in float64."""
    keys = list(chains)
    host = jax.device_get([chains[c] for c in keys])
    return {c: [jax.tree.map(lambda a: np.asarray(a, np.float64), s)
                for s in states] for c, states in zip(keys, host)}


def trace_summary(prof: Profiler, chips: int, cfg: dict) -> dict:
    """Device ops, harness spans and the window from the run's trace."""
    from bench import trace as tr

    t = tr.load(prof.dir)
    shutil.rmtree(prof.dir, ignore_errors=True)
    lo, hi = t["window"] if t["window"] else (None, None)
    if lo is None:
        raise RuntimeError("the trace holds no bench/window span")
    dev = {i: tr.clip(t["device"].get(i, []), lo, hi) for i in range(chips)}
    pattern = cfg["kernel_pattern"]
    kernel = {}
    for i in range(chips):
        evs, txt = t["device"].get(i, []), t["texts"].get(i, [])
        kernel[i] = tr.clip(tr.matching(evs, txt, pattern), lo, hi)
    return {"lo": lo, "hi": hi, "window_s": (hi - lo) * 1e-9,
            "busy_s": [tr.busy_ns(dev[i], lo, hi) * 1e-9 for i in range(chips)],
            "kernel_s": sum(e - s for evs in kernel.values()
                            for _, s, e in evs) * 1e-9,
            "device_ops": tr.top_ops(dev),
            "idle_gaps": tr.longest_gaps(dev, t["spans"], lo, hi),
            "layout": t["layout"]}


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float | None = None, root: Path = ROOT,
        spec: dict | None = None, config: dict | None = None,
        traffic: dict | None = None, require_tpu: bool = True,
        control: bool = False, log=None) -> dict:
    """Run one cell and return its result line as a dict.

    ``root`` is the checkout whose ``bench/`` holds the cell's files.
    ``spec``, ``config`` and ``traffic`` stand in for ``BENCHMARK.json``
    and the cell's configuration and traffic files, and ``require_tpu=False``
    skips the look for a chip: the tests run the harness so, at small
    sizes.  ``control`` runs the control of ``correct`` in place of the
    kernels.
    """
    t_start = time.perf_counter() if t_start is None else t_start
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    spec = spec if spec is not None else load_json(root / "BENCHMARK.json")
    bench = root / "bench"
    r = resolve(spec, workload, root)
    cell, kind = r["cell"], r["kind"]
    traffic = traffic if traffic is not None else r["traffic"]
    cfg = config if config is not None else r["config"]
    chips = cell["chips"]
    if traffic.get("loop") != "closed":
        raise ValueError(f"traffic {cell['traffic']!r}: the generator runs "
                         "closed loops only")

    import jax
    import jax.numpy as jnp

    if require_tpu:
        check_device(jax, chips, bench)
    compiles = CompileCounter(jax)

    ref = load_module(r["reference"])
    if control:
        import ml_dtypes

        dtype = np.dtype(ml_dtypes.bfloat16)
    else:
        dtype = np.dtype(cfg["dtype"])

    count = int(cfg["clients"])
    clients = kind.Clients(cfg, count, seed, dtype)
    clients.device_model(jnp)
    sample = np.random.default_rng([31, seed]).choice(
        count, min(count, cfg["check_clients"]), replace=False)

    from repro.serve import Dispatcher

    max_batch = Dispatcher().max_batch  # the program's default
    engine = make_engine(chips, -(-count // max_batch) + 2, control)

    from bench import loops

    spans = loops.Spans(trace)
    prof = Profiler(jax, seconds) if trace else None
    first = []

    def tick(now):
        """Called by the loop at every turn inside the window and after."""
        if not first:
            first.append(now)
        compiles.on = now < first[0] + seconds
        if prof is not None:
            prof.tick(now)

    runner = loops.Runner(engine, clients, kind.REQUEST_KIND, max_batch,
                          sample, spans, tick=tick)
    t0, t_end = loops.closed_loop(runner, traffic["warm_steps"], seconds)
    compiles.on = False
    compiles.close(jax)
    if prof is not None:
        prof.finish()
    setup_s = t0 - t_start

    devices = jax.devices()[:chips]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)

    # ----------------------------------------------------- what came back
    done = runner.done
    served_in = sum(1 for _, _, _, td in done if t0 <= td <= t_end)
    failed_in = sum(1 for _, t, _ in runner.failed if t0 <= t <= t_end)
    attempted = sum(1 for _, _, ts, _ in done if t0 <= ts <= t_end) \
        + failed_in + runner.outstanding
    failed = failed_in + runner.outstanding
    # an engine error, or an answer still missing after the drain, is not
    # correct
    never = len(runner.failed) + runner.outstanding
    log(f"window: {seconds!r} s, {served_in} served, {attempted} attempted, "
        f"{failed} failed; {compiles.n} compiles in the window")

    tsum = trace_summary(prof, chips, cfg) if trace else None
    if tsum is not None:
        log("trace planes: " + "; ".join(
            f"{name} [{', '.join(lines[:6])}]" for name, lines in tsum["layout"]
            if not name.startswith("/host")))
        log(f"trace: kernel {tsum['kernel_s']!r} s, busy {tsum['busy_s']!r} s "
            f"of {tsum['window_s']!r} s")
        log("spans in the window: " + ", ".join(
            f"{k} {v!r} s / {spans.count[k]}" for k, v in sorted(spans.total.items())))
    traced_done = 0
    if prof is not None:
        traced_done = sum(1 for _, _, _, td in done if prof.lo <= td <= prof.hi)

    # -------------------------------------------- correctness, engine freed
    chains = fetch_chains(jax, runner.chains)
    del runner, engine
    gc.collect()
    checks = kind.check(clients, ref, chains)
    limits = cfg["limits"]
    numbers = {k: {"value": checks[k], "limit": limits[k]} for k in kind.CHECKS}
    correct = (never == 0 and checks["checked"] > 0
               and all(v["value"] <= v["limit"] for v in numbers.values()))

    # ------------------------------------------------------------ metrics
    dev_kind = devices[0].device_kind
    peaks = load_json(bench / "peaks.json")["devices"]
    ctx = SimpleNamespace(
        setup_s=setup_s, window_s=t_end - t0, served=served_in,
        spans=spans, trace=tsum, traced_done=traced_done,
        work=kind.work(cfg), peaks=peaks.get(dev_kind), chips=chips, log=log)
    wanted = r["per_layer"] if trace else r["end_to_end"]
    metrics = {}
    for m in wanted:
        value = load_module(r["readers"][m["name"]]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {"platform": devices[0].platform, "kind": dev_kind,
              "count": jax.device_count(), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if tsum is not None:
        device["busy_s"] = sum(tsum["busy_s"]) / chips
        device["window_s"] = tsum["window_s"]
        out["breakdown"] = {"device_ops": tsum["device_ops"],
                            "idle_gaps": tsum["idle_gaps"]}
    for k, v in numbers.items():
        log(f"check {k}: {v['value']!r} limit {v['limit']!r}")
    out["checks"] = numbers
    return out
