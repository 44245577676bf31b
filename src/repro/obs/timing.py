"""Wall-clock timers that block correctly around asynchronous dispatch.

JAX dispatch is asynchronous: ``out = fn(x)`` returns as soon as the work is
*enqueued*, so ``perf_counter()`` around the call measures dispatch latency,
not compute.  Every timer here therefore takes the dispatch **output** and
calls ``jax.block_until_ready`` on it before reading the clock — the only
honest way to attribute device time to a dispatch site.

Tracer-safety: inside ``jit``/``scan`` the "output" is a tracer and there is
nothing to block on (and timing a trace would be meaningless anyway);
``block_ready`` detects tracers and skips, returning False, so instrumented
library functions stay safe to call under a surrounding ``jit``.
"""
from __future__ import annotations

import contextlib
import time

import jax

__all__ = ["block_ready", "device_timer"]


def _has_tracer(tree) -> bool:
    return any(isinstance(leaf, jax.core.Tracer)
               for leaf in jax.tree_util.tree_leaves(tree))


def block_ready(out) -> bool:
    """``jax.block_until_ready(out)`` unless ``out`` contains tracers.

    Returns True iff it actually blocked — callers skip recording wall-clock
    metrics when tracing (the concrete outer dispatch records instead).
    """
    if _has_tracer(out):
        return False
    jax.block_until_ready(out)
    return True


class _Timer:
    """Handle yielded by ``device_timer``; ``stop(out)`` ends the region."""

    __slots__ = ("t0", "seconds", "blocked")

    def __init__(self):
        self.t0 = time.perf_counter()
        self.seconds: float | None = None
        self.blocked = False

    def stop(self, out=None) -> float:
        """Block on ``out`` (if concrete), record and return elapsed seconds."""
        self.blocked = block_ready(out) if out is not None else False
        self.seconds = time.perf_counter() - self.t0
        return self.seconds


@contextlib.contextmanager
def device_timer():
    """Time a dispatch region, blocking on its result::

        with device_timer() as t:
            out = kernel(x)
            t.stop(out)            # block_until_ready(out), then read clock
        hist.observe(t.seconds)

    If ``stop`` is never called the exit path stops without blocking (host
    wall-clock only).
    """
    t = _Timer()
    yield t
    if t.seconds is None:
        t.stop()

