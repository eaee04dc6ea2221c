"""Process hooks: JAX compiles and Python's garbage collector, the
program work that runs in any window whatever the caller does
(DESIGN.md §14).

- **Compiles.**  ``jax.monitoring`` reports each compile phase with its
  duration and ``fun_name``.  Every report feeds the metrics registry,
  tracer on or off: ``jax_compiles_total{phase}`` and
  ``jax_compile_seconds_total{phase}`` (phases ``trace``, ``lower`` and
  ``backend``, the last a backend compile or a persistent-cache load),
  and ``jax_compile_cache_total{result}`` (``hit``, ``miss``).  While
  the tracer is enabled, each phase is also a ``compile`` record
  (``phase``, ``fun_name``) ending when it is reported, placed on the
  profiler's clock by its mark.
- **Garbage collection.**  While the tracer is enabled, each pass of the
  collector is a live ``gc`` span (``generation``, ``collected``),
  mirrored as ``gram_exec:gc``.  The tracer is looked up on every call:
  it can be swapped or switched off at any time.

:func:`install` registers both once; ``import repro.obs`` calls it.
"""
from __future__ import annotations

import gc
import time
from typing import Optional

from jax import monitoring

from . import metrics as _metrics
from . import trace as _trace

__all__ = ["COMPILE_PHASES", "CACHE_RESULTS", "install"]

COMPILE_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
CACHE_RESULTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}

_installed = False
_gc_span: Optional[_trace.Span] = None     # the collection in progress


def _on_duration(event: str, duration: float, **kw) -> None:
    phase = COMPILE_PHASES.get(event)
    if phase is None:
        return
    now = time.perf_counter()
    _metrics.counter("jax_compiles_total",
                     "JAX compile phases run, by phase").inc(phase=phase)
    _metrics.counter("jax_compile_seconds_total",
                     "seconds in JAX compile phases, by phase").inc(
        duration, phase=phase)
    _trace.add_span("compile", now - duration, now, phase=phase,
                    fun_name=str(kw.get("fun_name", "")))


def _on_event(event: str, **kw) -> None:
    result = CACHE_RESULTS.get(event)
    if result is not None:
        _metrics.counter("jax_compile_cache_total",
                         "persistent compile-cache lookups, by result").inc(
            result=result)


def _on_gc(phase: str, info: dict) -> None:
    global _gc_span
    if phase == "start":
        tracer = _trace.get_tracer()
        if tracer.enabled:
            _gc_span = tracer.span("gc", generation=info["generation"])
            _gc_span.__enter__()
    elif _gc_span is not None:
        span, _gc_span = _gc_span, None
        span.annotate(collected=info["collected"])
        span.__exit__(None, None, None)


def install() -> None:
    """Register the compile listeners and the GC callback, once."""
    global _installed
    if _installed:
        return
    _installed = True
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)
    gc.callbacks.append(_on_gc)
