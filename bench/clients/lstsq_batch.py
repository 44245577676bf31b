"""Batched least squares: many independent dense tall problems, each solved
once, ``min ||A x - b||`` with A (rows, cols) and one right-hand side.

A is picked per (client, step) from a pool of ``pool`` matrices made from
the seed in set-up, and b is drawn per request, both from the seed alone;
entries are N(0, 1).  The client sends A and b from the host, as a caller
of a solver service does, and the served result is ``(x, ||A x - b||)``.
"""
from __future__ import annotations

import math
from collections import defaultdict

import numpy as np

REQUEST_KIND = "lstsq"        # the served request kind
STATE_ARGS = ()               # one-shot: no served state is fed back
REFERENCE = "lstsq_f64"       # bench/reference/lstsq_f64.py
CHECKS = ("solve_gap", "resid_gap")


def sweep_mults(m: int, w: int, n_pivots: int) -> int:
    """Multiplications of one dense GGR sweep of an (m, w) matrix over its
    first ``n_pivots`` columns: column step c rotates ``m - c`` active rows
    over ``w - c`` columns.  A copy of ``repro.core.counts.ggr_sweep_mults``,
    kept here so that the yardstick cannot move with the program."""
    steps = max(0, min(n_pivots, m - 1, w))
    return sum(3 * ((m - c) * (w - c) - 1) for c in range(steps))


def work(cfg: dict, itemsize: int = 4) -> dict:
    """One solve: the augmented sweep over ``[A | b]`` with ``cols``
    pivots, each multiplication paired with one add.  Bytes: A and b in, x
    and the residual norm out."""
    m, n, k = cfg["rows"], cfg["cols"], cfg["rhs"]
    flops = 2 * sweep_mults(m, n + k, n)
    return {"flops": flops, "bytes": (m * n + m * k + n * k + k) * itemsize}


def _result(out, n: int):
    """``(x, r)`` of one served result in float64, or None if malformed."""
    try:
        x = np.asarray(out[0], np.float64).reshape(n)
        r = float(np.asarray(out[1], np.float64).reshape(()))
    except (IndexError, TypeError, ValueError):
        return None
    return x, r


def check(clients, ref, chains: dict) -> dict:
    """``solve_gap``: the widest ``||x_s - x_ref|| / ||x_ref||`` over every
    served result of the sampled clients; ``resid_gap``: the widest
    ``|r_s - r_ref| / ||b||``; ``checked``: results compared.  The
    reference solves each pool matrix once, for all the right-hand sides
    served with it.  A result that is malformed or missing, or not finite,
    reads as ``inf``."""
    n = clients.n
    by_matrix: dict = defaultdict(list)
    bad = 0
    for c, outs in chains.items():
        for k, out in enumerate(outs, 1):
            got = _result(out, n)
            if got is None:
                bad += 1
                continue
            idx, b = clients.draw(c, k)
            by_matrix[idx].append((b, *got))
    checked = sum(len(v) for v in by_matrix.values())
    if bad or not checked:
        return {"solve_gap": math.inf, "resid_gap": math.inf,
                "checked": checked}
    solve = resid = 0.0
    for idx, items in by_matrix.items():
        B = np.stack([b for b, _, _ in items], axis=1).astype(np.float64)
        X, r = ref.solve(clients.pool[idx], B)
        xs = np.stack([x for _, x, _ in items], axis=1)
        rs = np.array([r_s for _, _, r_s in items])
        sg = np.linalg.norm(xs - X, axis=0) / np.linalg.norm(X, axis=0)
        rg = np.abs(rs - r) / np.linalg.norm(B, axis=0)
        solve = max(solve, float(np.max(np.nan_to_num(sg, nan=math.inf))))
        resid = max(resid, float(np.max(np.nan_to_num(rg, nan=math.inf))))
    return {"solve_gap": solve, "resid_gap": resid, "checked": checked}


class Clients:
    def __init__(self, cfg: dict, count: int, seed: int, dtype):
        if cfg["rhs"] != 1:
            raise ValueError("lstsq_batch serves one right-hand side")
        self.count, self.seed, self.dtype = count, seed, np.dtype(dtype)
        self.m, self.n = cfg["rows"], cfg["cols"]
        rng = np.random.default_rng([21, seed])
        self.pool = rng.standard_normal(
            (cfg["pool"], self.m, self.n), dtype=np.float32)
        self._served = (self.pool if self.dtype == self.pool.dtype
                        else self.pool.astype(self.dtype))

    def device_model(self, jnp):
        """Nothing is shared between requests."""
        return {}

    def draw(self, c: int, k: int):
        """``(pool index, b)`` of client ``c``'s request ``k`` (1-based)."""
        rng = np.random.default_rng([22, self.seed, c, k])
        idx = int(rng.integers(len(self.pool)))
        return idx, rng.standard_normal(self.m, dtype=np.float32)

    def request(self, c: int, k: int, state):
        """Operands of request ``k``: A and b on the host, in the run's
        dtype."""
        idx, b = self.draw(c, k)
        return self._served[idx], b.astype(self.dtype, copy=False)
