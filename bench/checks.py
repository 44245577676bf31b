"""Comparisons against the plain float64 reference, shared by the client
kinds whose served state is a square-root factor ``(R, d)``.

A client kind names the numbers it compares (``CHECKS``) and computes them
in its own ``check``; a kind with this kind of state calls
``state_chains`` there.
"""
from __future__ import annotations

import math

import numpy as np


def _as_batch(d):
    return d.reshape(d.shape[0], d.shape[1], -1)


def gram_gap(Rs, ds, Rr, dr) -> np.ndarray:
    """Per item: the larger of ``|Rs'Rs - Rr'Rr| / |Rr'Rr|`` and
    ``|Rs'ds - Rr'dr| / (|Rr| |dr|)`` (Frobenius norms, float64).  Both
    are blind to the signs of R's rows, in which factors may differ."""
    Rs, Rr = np.asarray(Rs, np.float64), np.asarray(Rr, np.float64)
    ds, dr = _as_batch(np.asarray(ds, np.float64)), _as_batch(np.asarray(dr, np.float64))
    G = np.einsum("bki,bkj->bij", Rr, Rr)
    Gs = np.einsum("bki,bkj->bij", Rs, Rs)
    g1 = np.linalg.norm(Gs - G, axis=(1, 2)) / np.linalg.norm(G, axis=(1, 2))
    b = np.einsum("bki,bkj->bij", Rr, dr)
    bs = np.einsum("bki,bkj->bij", Rs, ds)
    den = np.linalg.norm(Rr, axis=(1, 2)) * np.maximum(
        np.linalg.norm(dr, axis=(1, 2)), np.finfo(np.float64).tiny)
    g2 = np.linalg.norm(bs - b, axis=(1, 2)) / den
    return np.maximum(g1, g2)


def state_chains(clients, ref_step, chains: dict) -> dict:
    """``step_gap``: the widest gap of one served step from the reference
    step taken from the same served previous state and the same inputs.
    ``chain_gap``: the widest gap of a client's last served state from the
    reference chain run from the client's start over all its steps.
    ``checked``: served steps compared.

    ``chains`` maps a client to its served ``(R, d)`` in step order;
    ``clients.start(c)`` is its first state and ``ref_step(R, d, cs, ks)``
    the reference step of clients ``cs`` at steps ``ks`` (batched).
    """
    prevR, prevd, curR, curd, cs, ks = [], [], [], [], [], []
    for c, states in chains.items():
        prev = tuple(np.asarray(a, np.float64) for a in clients.start(c))
        for k, st in enumerate(states, 1):
            prevR.append(prev[0]), prevd.append(prev[1])
            curR.append(st[0]), curd.append(st[1])
            cs.append(c), ks.append(k)
            prev = st
    if not cs:
        return {"step_gap": math.inf, "chain_gap": math.inf, "checked": 0}
    Rr, dr = ref_step(np.stack(prevR), np.stack(prevd), cs, ks)
    step = gram_gap(np.stack(curR), np.stack(curd), Rr, dr)

    live = [c for c, s in chains.items() if s]
    R = np.stack([np.asarray(clients.start(c)[0], np.float64) for c in live])
    d = np.stack([np.asarray(clients.start(c)[1], np.float64) for c in live])
    K = np.array([len(chains[c]) for c in live])
    for k in range(1, int(K.max()) + 1):
        on = np.flatnonzero(K >= k)
        R[on], d[on] = ref_step(R[on], d[on], [live[i] for i in on], [k] * len(on))
    chain = gram_gap(np.stack([chains[c][-1][0] for c in live]),
                     np.stack([chains[c][-1][1] for c in live]), R, d)
    return {"step_gap": float(step.max()), "chain_gap": float(chain.max()),
            "checked": len(cs)}
