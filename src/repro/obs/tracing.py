"""Trace spans under one naming convention.

Span names are slash-paths ``repro/<layer>/<stage>`` — e.g.
``repro/serve/close``, ``repro/blocked/panel`` — so a profile groups by
layer first and pipeline stage second (see ``docs/observability.md`` for
the catalog and how to read a profile).

Two kinds of span, because JAX has two timelines:

* ``span(name, **args)`` — a **host** span: a
  ``jax.profiler.TraceAnnotation`` on the profiler's own clock, and nothing
  else.  It never enters ``jax.named_scope``, so it cannot change a lowered
  program or a jit cache key, and it costs one flag read when no profiler
  trace is active.  ``args`` become the annotation's arguments and are
  inherited by every span opened inside it: the stages of one batch close
  carry the close's ``kind``, ``cycle`` and ``n``.
* ``named_span(name)`` — the **in-graph** half only (``jax.named_scope``).
  Use inside jitted/scanned code: it is a trace-time annotation with zero
  runtime cost after compilation, and it is what lets a device profile
  attribute kernel time to pipeline stages (panel factor vs tree coupling
  vs trailing update).

``install_gc_spans`` adds a ``repro/host/gc`` span around every pass of
Python's garbage collector, so that a host stall shows in the trace.
"""
from __future__ import annotations

import contextlib
import contextvars
import gc

import jax
from jax.profiler import TraceAnnotation

__all__ = ["span", "named_span", "install_gc_spans"]

_ARGS: contextvars.ContextVar[dict] = contextvars.ContextVar(
    "repro_span_args", default={})
_OFF = contextlib.nullcontext()


class _Span:
    __slots__ = ("_ann", "_args", "_token")

    def __init__(self, name: str, args: dict):
        outer = _ARGS.get()
        self._args = {**outer, **args} if args else None
        self._ann = TraceAnnotation(name, **(self._args or outer))

    def __enter__(self):
        if self._args is not None:
            self._token = _ARGS.set(self._args)
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        if self._args is not None:
            _ARGS.reset(self._token)
        return False


def span(name: str, **args):
    """Host span (a ``TraceAnnotation`` only); a shared no-op context when
    no profiler trace is active."""
    if not TraceAnnotation.is_enabled():
        return _OFF
    return _Span(name, args)


def named_span(name: str):
    """In-graph-only span for jit/scan bodies (zero runtime cost)."""
    return jax.named_scope(name)


_gc_open: list = []  # the span of the collector pass under way, if any


def _gc_span(phase: str, info: dict) -> None:
    if phase == "start":
        s = span("repro/host/gc", generation=info["generation"])
        if s is not _OFF:
            s.__enter__()
            _gc_open.append(s)
    elif _gc_open:
        _gc_open.pop().__exit__(None, None, None)


def install_gc_spans() -> None:
    """Span every garbage-collector pass as ``repro/host/gc`` (idempotent:
    one hook per process)."""
    if _gc_span not in gc.callbacks:
        gc.callbacks.append(_gc_span)
