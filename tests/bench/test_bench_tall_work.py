"""The batched least-squares kind's copied work model against the
program's own, and the kernels its kernel pattern finds."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

SPEC = harness.load_json(ROOT / "BENCHMARK.json")
CELL = "tall512.saturate"


@pytest.mark.parametrize("m,w,p", [(512, 129, 128), (256, 129, 128),
                                   (12, 4, 3), (5, 9, 9), (1, 1, 1),
                                   (300, 150, 140)])
def test_copied_sweep_mults_equal_core_counts(m, w, p):
    from repro.core.counts import ggr_sweep_mults

    kind = harness.resolve(SPEC, CELL)["kind"]
    assert kind.sweep_mults(m, w, p) == ggr_sweep_mults(m, w, p)


def test_tall_work_at_512_by_128():
    from repro.obs import ggr_sweep_flops

    r = harness.resolve(SPEC, CELL)
    work = r["kind"].work(r["config"])
    assert work["flops"] == ggr_sweep_flops(512, 129, 128) == 23_609_088
    assert work["bytes"] == 264_708     # A, b in; x, residual out; float32


def test_the_cells_solve_calls_the_panel_and_apply_kernels_only():
    """The cell's solve, traced as the chip traces it, makes two Pallas
    calls: the fused schedule's panel and apply kernels.  So the cell's
    ``kernel_pattern``, the custom-call target every Pallas kernel has on
    the TPU, finds those two kernels and nothing else (the harness matches
    device events by their HLO text, which also names each op's operands:
    a substring of a kernel's name would find the kernel's consumers too).
    ``tests/test_tpu_compile.py`` checks the pattern on the v5e program."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax._src import core

    from repro.kernels.backend import degraded_mode
    from repro.serve.dispatch import _batched_solve, _solve_key

    cfg = harness.resolve(SPEC, CELL)["config"]
    assert cfg["kernel_pattern"] == "tpu_custom_call"
    m, n = cfg["rows"], cfg["cols"]
    with degraded_mode(interpret=False):
        key = _solve_key("pallas", m, n)
    assert key == ("blocked", None, False)
    with degraded_mode(interpret=False):  # the chip's schedule: fused
        jaxpr = jax.make_jaxpr(functools.partial(
            _batched_solve, pivoted=False, route=key[0], schedule=key[1],
            interpret=key[2]))(jnp.zeros((8, m, n)), jnp.zeros((8, m)))

    def kernels(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["name"]
            for sub in core.jaxprs_in_params(eqn.params):
                yield from kernels(sub)

    assert sorted(kernels(jaxpr.jaxpr)) == ["ggr_apply_factors",
                                            "ggr_panel_factor"]
