"""A fleet of 3-D tracks under the discrete white-noise acceleration model.

Truth, plots and track starts come from the seed alone.  State is
``x = (position, velocity)`` in metres and metres per second; the model is

    F = [[I, T I], [0, I]],  G = [T^2/2 I; T I],  v ~ N(0, q I),
    z = [I 0] x + e,  e ~ N(0, sigma^2 I),

served in information form with ``Qi = I / sqrt(q)`` and the plots
whitened by ``sigma``.  Each track is started by two-point differencing
of its first two plots; served step k folds in plot k + 1.  Plots are
generated in blocks of steps for every track at once, and a block depends
only on the seed and its index, so a run that serves more steps draws the
same plots for the steps it shares with a shorter one.
"""
from __future__ import annotations

import numpy as np

from bench import checks, counts

REQUEST_KIND = "kalman"       # the served request kind
STATE_ARGS = (0, 1)           # positions of (R, d) among its operands
REFERENCE = "srif"            # bench/reference/srif.py
CHECKS = ("step_gap", "chain_gap")
_BLOCK = 32


def work(cfg: dict, itemsize: int = 4) -> dict:
    """One fused SRIF predict+observe step: the stacked ``(w + 2n + p,
    w + n + 1)`` matrix with ``w + n`` pivots, of which ``n + p`` rows ride
    below the triangular top (as ``repro.serve.dispatch`` counts it).
    Bytes: R, d and z in, R' and d' out; the fleet's shared model matrices
    are not counted per request."""
    n, w, p = cfg["state_dim"], cfg["noise_dim"], cfg["meas_dim"]
    flops = counts.mults_to_flops(counts.ggr_append_mults(w + n, n + p, w + n + 1))
    floats = (n * n + n + p) + (n * n + n)
    return {"flops": flops, "bytes": floats * itemsize}


def check(clients, ref, chains: dict) -> dict:
    """The numbers that decide ``correct`` (``bench/checks.py``)."""
    return checks.state_chains(
        clients, lambda R, d, cs, ks: clients.ref_step(ref, R, d, cs, ks), chains)


class Clients:
    def __init__(self, cfg: dict, count: int, seed: int, dtype):
        n, w, p = cfg["state_dim"], cfg["noise_dim"], cfg["meas_dim"]
        if (n, w, p) != (6, 3, 3):
            raise ValueError("srif_track models 3-D position and velocity: "
                             "state_dim 6, noise_dim 3, meas_dim 3")
        self.count, self.seed, self.dtype = count, seed, dtype
        T, q, sig = cfg["scan_period_s"], cfg["accel_var"], cfg["meas_sigma_m"]
        I3, Z3 = np.eye(3), np.zeros((3, 3))
        F = np.block([[I3, T * I3], [Z3, I3]])
        G = np.vstack([T * T / 2 * I3, T * I3])
        Qi = I3 / np.sqrt(q)
        H = np.hstack([I3, Z3]) / sig
        self.model = {k: v.astype(np.float32)
                      for k, v in dict(F=F, G=G, Qi=Qi, H=H).items()}
        self._F, self._G, self._q, self._sig = F, G, q, sig

        rng = np.random.default_rng([11, seed])
        half = cfg["area_m"] / 2
        pos = np.column_stack([rng.uniform(-half, half, (count, 2)),
                               rng.uniform(*cfg["altitude_m"], count)])
        speed = rng.uniform(*cfg["speed_mps"], count)
        head = rng.uniform(0, 2 * np.pi, count)
        vel = np.column_stack([speed * np.cos(head), speed * np.sin(head),
                               rng.normal(0, 2.0, count)])
        x0 = np.hstack([pos, vel])
        z0 = x0[:, :3] + sig * rng.standard_normal((count, 3))
        x1 = self._propagate(x0, rng)
        z1 = x1[:, :3] + sig * rng.standard_normal((count, 3))
        self._truth = x1
        self._plots = np.zeros((count, 0, 3), np.float32)
        self._blocks = 0

        # two-point start at the time of plot 1
        xh = np.hstack([z1, (z1 - z0) / T])
        s2 = sig * sig
        P = np.block([[s2 * I3, s2 / T * I3], [s2 / T * I3, 2 * s2 / T**2 * I3]])
        R0 = np.linalg.cholesky(np.linalg.inv(P)).T
        self._R0 = R0.astype(np.float32)
        self._d0 = (xh @ R0.T).astype(np.float32)

    def _propagate(self, x, rng):
        v = np.sqrt(self._q) * rng.standard_normal((len(x), 3))
        return x @ self._F.T + v @ self._G.T

    def _ensure(self, k: int) -> None:
        """Make plots for served steps ``1 .. k`` of every track."""
        while self._plots.shape[1] < k:
            rng = np.random.default_rng([12, self.seed, self._blocks])
            block = np.empty((self.count, _BLOCK, 3), np.float32)
            x = self._truth
            for s in range(_BLOCK):
                x = self._propagate(x, rng)
                block[:, s] = (x[:, :3] + self._sig
                               * rng.standard_normal((self.count, 3))) / self._sig
            self._truth = x
            self._plots = np.concatenate([self._plots, block], axis=1)
            self._blocks += 1

    # ------------------------------------------------------------ serving
    def device_model(self, jnp):
        """The fleet's model as device arrays, one object each, shared by
        every request (the executor's broadcast path)."""
        self._dev = {k: jnp.asarray(v, self.dtype) for k, v in self.model.items()}
        self._dev["R0"] = jnp.asarray(self._R0, self.dtype)
        return self._dev

    def start(self, c: int):
        """Track ``c``'s start ``(R0, d0)`` on the host, in float32."""
        return self._R0, self._d0[c]

    def inputs(self, c: int, k: int):
        """Host inputs of track ``c``'s served step ``k`` (1-based)."""
        self._ensure(k)
        return (self._plots[c, k - 1],)

    def request(self, c: int, k: int, state):
        """Operands of step ``k``; ``state`` is the last served ``(R, d)``,
        or None at step 1, where every track shares one R0 array."""
        if state is None:
            state = (self._dev["R0"], self._d0[c].astype(self.dtype))
        (z,) = self.inputs(c, k)
        m = self._dev
        return (state[0], state[1], m["F"], m["Qi"], m["H"],
                z.astype(self.dtype), m["G"])

    # ---------------------------------------------------------- reference
    def ref_step(self, ref, R, d, cs, ks):
        """Reference step for tracks ``cs`` at steps ``ks`` from ``(R, d)``."""
        self._ensure(int(max(ks)))
        z = self._plots[cs, np.asarray(ks) - 1]
        return ref.step(R, d, z, self.model)
