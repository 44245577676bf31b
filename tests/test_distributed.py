"""Multi-device distributed QR tests — run in subprocesses so the main pytest
process keeps the single real CPU device (per the dry-run isolation rule)."""
import os
import subprocess
import sys
import textwrap

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, ndev: int = 4):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


@pytest.mark.slow
def test_distributed_qr_1d_4dev():
    _run(
        """
        import numpy as np, jax, jax.numpy as jnp
        jax.config.update("jax_enable_x64", True)
        from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
        from repro.core.distributed import distributed_ggr_qr_1d
        mesh = jax.make_mesh((4,), ("x",), axis_types=(AxisType.Auto,))
        A = np.random.default_rng(0).standard_normal((64, 32))
        Aj = jax.device_put(jnp.array(A), NamedSharding(mesh, P(None, "x")))
        R = np.asarray(distributed_ggr_qr_1d(Aj, mesh, "x", panel=4))
        Rnp = np.linalg.qr(A, mode="r")
        assert np.allclose(np.abs(R[:32]), np.abs(Rnp), atol=1e-9)
        """
    )


@pytest.mark.slow
def test_tsqr_and_orthogonalize_8dev():
    _run(
        """
        import numpy as np, jax, jax.numpy as jnp
        jax.config.update("jax_enable_x64", True)
        from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
        from repro.core.distributed import tsqr, distributed_orthogonalize
        mesh = jax.make_mesh((8,), ("x",), axis_types=(AxisType.Auto,))
        B = np.random.default_rng(1).standard_normal((128, 16))
        Bj = jax.device_put(jnp.array(B), NamedSharding(mesh, P("x", None)))
        Rt = np.asarray(tsqr(Bj, mesh, "x"))
        assert np.allclose(np.abs(Rt), np.abs(np.linalg.qr(B, mode="r")), atol=1e-9)
        # eps-regularized triangular solve bounds orthogonality at ~eps level
        Q = np.asarray(distributed_orthogonalize(Bj, mesh, "x"))
        assert np.abs(Q.T @ Q - np.eye(16)).max() < 1e-6
        """,
        ndev=8,
    )


@pytest.mark.slow
def test_tsqr_collectives_present():
    """The lowered distributed QR must actually contain collectives."""
    out = _run(
        """
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
        from repro.core.distributed import tsqr
        mesh = jax.make_mesh((4,), ("x",), axis_types=(AxisType.Auto,))
        B = jnp.zeros((64, 8), jnp.float32)
        Bj = jax.device_put(B, NamedSharding(mesh, P("x", None)))
        lowered = jax.jit(lambda X: tsqr(X, mesh, "x")).lower(Bj)
        txt = lowered.compile().as_text()
        print("HAS_PERMUTE", "collective-permute" in txt or "all-to-all" in txt or "all-gather" in txt)
        """
    )
    assert "HAS_PERMUTE True" in out
