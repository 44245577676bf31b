"""Compile the main-path kernels for a described TPU v5e, at chip_smoke widths.

Nothing runs: each case lowers and compiles for a v5e chip that is described,
not attached, so a kernel Mosaic would refuse fails here instead of on the
chip.  Each case also finds its kernels' stable ``name=`` in the lowered
text, which is what a profiler trace names them by.  The topology is
described inside a fixture (never at import time) and the persistent
compilation cache is off around these compiles.
"""
import functools

import pytest

import jax
import jax.numpy as jnp

N, ROWS = 128, 8  # chip_smoke.py's widths


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent cache off and x64 off (as on
    the chip: the suite's x64 turns loop counters and Python scalars inside
    kernels into int64/float64, which Mosaic does not lower)."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    saved = {k: getattr(jax.config, k) for k in
             ("jax_enable_compilation_cache", "jax_enable_x64")}
    for k in saved:
        jax.config.update(k, False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def _served(fn):
    """A serving executor traced as the chip would trace it: kernel calls
    resolve to compiled mode, whatever backend this process has."""
    def traced(*args):
        from repro.kernels import backend

        with backend.degraded_mode(interpret=False):
            return fn(*args)
    return traced


def _solve(pivoted):
    """The served solve of a padded batch, keyed as the executor keys it."""
    def solve(Ab, bb):
        from repro.serve.dispatch import _batched_solve, _solve_key

        key = _solve_key("pallas", Ab.shape[1], Ab.shape[2])
        return _batched_solve(Ab, bb, pivoted, *key)
    return solve


UPDATE, GEQRT = "ggr_batched_update", "ggr_batched_geqrt"
PANEL, APPLY = "ggr_panel_factor", "ggr_apply_factors"


def _cases():
    """``{case: (fn, operand shapes, kernel names it must lower to)}``."""
    from repro.kernels.ggr_apply import _apply_factors_call
    from repro.kernels.ggr_panel import _batched_geqrt_call, _panel_factor_call
    from repro.kernels.ggr_update import _batched_update_call

    m_ls = 4 * N  # make_workload's lstsq rows
    kal = (N + 2 * N + ROWS, 2 * N + 1)  # SRIF stack, w = n
    return {
        "update_append": (functools.partial(_batched_update_call, n_pivots=N,
                                            block_b=8, interpret=False),
                          [(64, N + ROWS, N + 1)], [UPDATE]),
        "update_kalman": (functools.partial(_batched_update_call,
                                            n_pivots=2 * N, block_b=8,
                                            interpret=False),
                          [(64,) + kal], [UPDATE]),
        # n=512 appends overflow the 16 MiB scoped VMEM at block_b=8 unless
        # the kernel narrows its batch tile (ggr_panel._fit_block)
        "update_wide": (functools.partial(_batched_update_call, n_pivots=512,
                                          block_b=8, interpret=False),
                        [(64, 512 + ROWS, 513)], [UPDATE]),
        "update_fleet": (functools.partial(_batched_update_call, n_pivots=12,
                                           block_b=8, interpret=False),
                         [(64, 21, 13)], [UPDATE]),
        "batched_geqrt": (functools.partial(_batched_geqrt_call, n_pivots=64,
                                            block_b=8, interpret=False),
                          [(8, 64, 128)], [GEQRT]),
        "panel": (functools.partial(_panel_factor_call, pivot0=0,
                                    interpret=False),
                  [(m_ls, 64)], [PANEL]),
        "apply": (functools.partial(_apply_factors_call, pivot0=0,
                                    block_w=N + 1, interpret=False),
                  [(m_ls, 64), (m_ls, 64), (m_ls, N + 1)], [APPLY]),
        "lstsq_served": (_served(_solve(False)),
                         [(64, m_ls, N), (64, m_ls, 1)], [PANEL, APPLY]),
        "lstsq_pivoted_served": (_served(_solve(True)),
                                 [(8, m_ls, N), (8, m_ls, 1)],
                                 [PANEL, APPLY]),
    }


@pytest.mark.parametrize("case", ["update_append", "update_kalman",
                                  "update_wide", "update_fleet",
                                  "batched_geqrt", "panel", "apply",
                                  "lstsq_served", "lstsq_pivoted_served"])
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, shapes, names = _cases()[case]
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    lowered = jax.jit(fn).lower(*args)
    text = lowered.as_text()
    for name in names:
        assert f'kernel_name = "{name}"' in text
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_tall_cell_kernel_pattern_finds_its_two_kernels_only(one_chip):
    """The ``tall512.saturate`` cell's ``kernel_pattern`` occurs, in the v5e
    program of its solve (64 problems of 512 x 128), in the instructions of
    the panel and apply kernels and in no other: the profiler names each
    device op by its instruction's text, which is what the benchmark
    matches."""
    import json
    from pathlib import Path

    cfg = json.loads((Path(__file__).resolve().parents[1] / "bench"
                      / "configs" / "lstsq-tall512.json").read_text())
    m, n = cfg["rows"], cfg["cols"]
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in [(64, m, n), (64, m)]]
    text = jax.jit(_served(_solve(False))).lower(*args).compile().as_text()
    hit = [line.split(" = ")[0].split()[-1].lstrip("%")
           for line in text.splitlines()
           if " = " in line and cfg["kernel_pattern"] in line]
    assert sorted(h.split(".")[0] for h in hit) == [APPLY, PANEL]
