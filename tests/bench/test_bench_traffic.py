"""Traffic and client data come from the seed alone."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

BIG = 2**31 + 12345  # seeds may pass 32 bits


def _clients(config, count, seed, **over):
    cfg = dict(json.loads((ROOT / "bench" / "configs" / f"{config}.json").read_text()))
    cfg.update(over)
    mod = harness.load_module(ROOT / "bench" / "clients" / f"{cfg['kind']}.py")
    return mod.Clients(cfg, count, seed, np.dtype(np.float32))


def _draw(cl, steps):
    out = [np.concatenate([np.ravel(a) for a in cl.start(c)]) for c in range(cl.count)]
    for k in steps:
        for c in range(cl.count):
            out.extend(np.ravel(a) for a in cl.inputs(c, k))
    return np.concatenate(out)


CASES = [("track-dwna3d", {})]


@pytest.mark.parametrize("config,over", CASES)
def test_same_seed_same_inputs(config, over):
    a = _draw(_clients(config, 8, BIG, **over), [1, 2, 40])
    b = _draw(_clients(config, 8, BIG, **over), [1, 2, 40])
    np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("config,over", CASES)
def test_other_seed_other_inputs(config, over):
    a = _draw(_clients(config, 8, BIG, **over), [1, 2])
    b = _draw(_clients(config, 8, BIG + 1, **over), [1, 2])
    assert a.shape == b.shape and not np.allclose(a, b)


@pytest.mark.parametrize("config,over", CASES)
def test_inputs_do_not_depend_on_how_far_a_run_got(config, over):
    """Data is made in blocks on demand; a run that serves more steps sees
    the same inputs for the steps it shares with a shorter one."""
    short = _clients(config, 4, 7, **over)
    first = [short.inputs(c, 3) for c in range(4)]
    long = _clients(config, 4, 7, **over)
    long.inputs(0, 70)
    for c in range(4):
        for x, y in zip(first[c], long.inputs(c, 3)):
            np.testing.assert_array_equal(x, y)


def test_fleet_start_is_two_point_differencing():
    cl = _clients("track-dwna3d", 3, 5)
    R0, d0 = cl.start(0)
    x = np.linalg.solve(R0.astype(np.float64), d0.astype(np.float64))
    # the start estimate sits near the first plots in position (100 m noise)
    z1 = cl.inputs(0, 1)[0] * 100.0      # plot 2, un-whitened
    assert np.all(np.abs(x[:3] - z1) < 5e3)
    assert np.allclose(R0, np.triu(R0)) and np.all(np.diag(R0) > 0)
