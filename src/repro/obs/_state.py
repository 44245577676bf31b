"""The process-wide active registry (module-private; use ``repro.obs``).

Kept out of ``__init__`` so sibling modules (``flops``, ``health``) can
import the active-registry accessor without importing the package init —
no intra-package cycles, and the accessor stays one dict lookup + attribute
read, cheap enough for uninstrumented hot paths.

While a collecting registry is installed, JAX's compile events are counted
as ``jax.compile_events{stage}`` and ``jax.compile_seconds{stage}``
(``stage`` is ``jaxpr_trace``, ``jaxpr_to_mlir_module`` or
``backend_compile``; a persistent-cache fetch counts as a
``backend_compile``).  The listener is registered on install and removed
on uninstall, so the no-op default pays nothing.
"""
from __future__ import annotations

import contextlib

import jax

from .registry import MetricsRegistry, NULL

__all__ = ["install", "uninstall", "_active", "collecting"]

_REGISTRY = NULL
_COMPILE_PREFIX = "/jax/core/compile/"
_COMPILE_STAGES = ("jaxpr_trace", "jaxpr_to_mlir_module", "backend_compile")
_listening = False


def _compile_event(event: str, seconds: float, **_) -> None:
    if not event.startswith(_COMPILE_PREFIX):
        return
    stage = event[len(_COMPILE_PREFIX):].removesuffix("_duration")
    if stage in _COMPILE_STAGES:
        _REGISTRY.counter("jax.compile_events", stage=stage).inc()
        _REGISTRY.counter("jax.compile_seconds", stage=stage).inc(seconds)


def install(registry) -> None:
    """Make ``registry`` the process-wide collector.

    Pass a ``MetricsRegistry`` to start collecting; instrumentation sites
    pick it up on their next call (there is no buffering — metrics recorded
    before install are gone, which is the point of the no-op default).
    """
    global _REGISTRY, _listening
    _REGISTRY = registry
    if registry.enabled and not _listening:
        jax.monitoring.register_event_duration_secs_listener(_compile_event)
        _listening = True
    elif not registry.enabled and _listening:
        jax.monitoring.unregister_event_duration_listener(_compile_event)
        _listening = False


def uninstall() -> None:
    """Restore the no-op default registry."""
    install(NULL)


def _active():
    """The active registry (the ``NULL`` no-op unless one was installed)."""
    return _REGISTRY


@contextlib.contextmanager
def collecting(registry=None):
    """Install a collecting registry for the scope of a ``with`` block::

        with obs.collecting() as reg:
            server.flush()
        print(obs.prometheus_text(reg))

    Restores whatever was installed before (usually the no-op default).
    """
    reg = MetricsRegistry() if registry is None else registry
    prev = _REGISTRY
    install(reg)
    try:
        yield reg
    finally:
        install(prev)
