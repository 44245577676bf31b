"""Faults planted under the timed path, to show that ``correct`` catches
them.  ``planted`` wraps the dispatcher's executor of a client kind's
request kind (``repro.serve.dispatch.Dispatcher._EXECUTORS``) for its
extent, so the engine, batching and result handling run as served.

* ``unchanged``: every request's state comes back as it went in;
* ``half``: the second half of each batch is left out, and answered with
  the first half's results;
* ``altered``: the first answer of each batch is altered where it is made.
"""
from __future__ import annotations

import contextlib


def _unchanged(kind, chunk, outs):
    return [tuple(r.arrays[i] for i in kind.STATE_ARGS) for r in chunk]


def _half(kind, chunk, outs):
    h = len(outs) // 2
    return outs[:len(outs) - h] + outs[:h]


def _altered(kind, chunk, outs):
    return [tuple(a * 1.01 for a in outs[0])] + list(outs[1:])


FAULTS = {"unchanged": _unchanged, "half": _half, "altered": _altered}


@contextlib.contextmanager
def planted(fault: str, kind):
    """Break the executor that serves client kind ``kind`` (a module of
    ``bench/clients``) with ``fault`` inside the block."""
    from repro.serve.dispatch import Dispatcher

    table = Dispatcher._EXECUTORS
    served = kind.REQUEST_KIND
    real = table[served]
    bend = FAULTS[fault]

    def broken(self, chunk):
        outs, flops, r_factor = real(self, chunk)
        return bend(kind, chunk, outs), flops, r_factor

    table[served] = broken
    try:
        yield
    finally:
        table[served] = real
