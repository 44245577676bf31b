"""Distributed GGR QR — the REDEFINE scheme-1 mapping on a JAX mesh.

Run with fake devices (the script sets them up itself):

    PYTHONPATH=src python examples/distributed_qr.py
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8 " + os.environ.get("XLA_FLAGS", "")

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.core.distributed import (
    distributed_ggr_qr_1d,
    distributed_orthogonalize,
    tsqr,
)
from repro.launch.dryrun import collective_bytes


def main():
    mesh = jax.make_mesh((8,), ("x",), axis_types=(AxisType.Auto,))
    rng = np.random.default_rng(0)

    # 1) block-cyclic panel QR (paper §5, scheme 1)
    A = jnp.asarray(rng.standard_normal((256, 128)), jnp.float32)
    Aj = jax.device_put(A, NamedSharding(mesh, P(None, "x")))
    fn = jax.jit(lambda X: distributed_ggr_qr_1d(X, mesh, "x", panel=16))
    R = np.asarray(fn(Aj))
    Rnp = np.linalg.qr(np.asarray(A, np.float64), mode="r")
    print("block-cyclic QR matches numpy:",
          bool(np.allclose(np.abs(R[:128]), np.abs(Rnp), atol=1e-2)))
    coll = collective_bytes(fn.lower(Aj).compile().as_text())
    print(f"collectives: {coll['count']} ops, {coll['total']/1e6:.2f} MB "
          f"(panel-factor broadcast over the 'NoC')")

    # 2) communication-avoiding TSQR (beyond-paper: the TSQRT tile op as a
    #    ppermute reduction tree)
    B = jnp.asarray(rng.standard_normal((512, 32)), jnp.float32)
    Bj = jax.device_put(B, NamedSharding(mesh, P("x", None)))
    Rt = np.asarray(tsqr(Bj, mesh, "x"))
    print("tsqr matches numpy:",
          bool(np.allclose(np.abs(Rt), np.abs(np.linalg.qr(np.asarray(B, np.float64), mode='r')), atol=1e-2)))

    # 3) the Orthant optimizer's primitive: distributed orthogonalization
    Q = np.asarray(distributed_orthogonalize(Bj, mesh, "x"))
    print("orthogonalized |QtQ - I|:", float(np.abs(Q.T @ Q - np.eye(32)).max()))


if __name__ == "__main__":
    main()
