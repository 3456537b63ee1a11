"""Program spans on the profiler's clock, host time per phase, compiles by span.

``span(name, **ids)`` opens a ``jax.profiler.TraceAnnotation`` (a
``StepTraceAnnotation`` when ``step_num`` is given): while a profiler trace
runs, the span lands on the host plane of the same trace as the device's
operations, so every device idle gap can be named by the program phase
behind it.  ``ids`` (``rid``, ``sid``, ``slot``, ...) become the event's
stats; ``set(**ids)`` adds some once they are known.  With no profiler
running a span costs about a microsecond.

Spans nest on the host thread.  Each thread keeps a stack of its open
spans, and the compile listener (:func:`install_compile_counter`) charges
every backend compile to the innermost open span (``none`` outside every
span) in the process-global registry's ``serving_compiles_total{span}`` and
``serving_compile_seconds_total{span}``.

``phases=`` takes a counter labelled ``phase``: the span then adds its own
host wall time — its time less that of the spans nested in it — under the
span's name without its first dotted part (``serving.step.grow`` counts as
``step.grow``).  Own time means the phases of one thread add up without
counting a nested phase twice.
"""
from __future__ import annotations

import threading
import time

from repro.obs.metrics import get_global

__all__ = ["span", "install_compile_counter"]

_local = threading.local()
_profiler = None  # jax.profiler, imported at the first span
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_install_lock = threading.Lock()
_installed = False


def _stack() -> list:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


class span:
    """Context manager: one profiler span, and optionally one phase of host
    wall time (see the module docstring)."""

    __slots__ = ("name", "_ann", "_phases", "_phase", "_t0", "_nested")

    def __init__(self, name: str, *, phases=None, step_num: int | None = None,
                 **ids):
        global _profiler
        if _profiler is None:
            from jax import profiler as _profiler
        self.name = name
        self._ann = (_profiler.TraceAnnotation(name, **ids) if step_num is None
                     else _profiler.StepTraceAnnotation(
                         name, step_num=step_num, **ids))
        self._phases = phases
        if phases is not None:
            self._phase = (name.partition(".")[2],)
        self._nested = 0.0

    def set(self, **ids) -> None:
        """Add stats to the span once their values are known."""
        self._ann.set_metadata(**ids)

    def __enter__(self) -> "span":
        _stack().append(self)
        self._ann.__enter__()
        if self._phases is not None:
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        stack = _stack()
        if self._phases is not None:
            dt = time.perf_counter() - self._t0
            self._phases.inc_values(self._phase, max(0.0, dt - self._nested))
            if len(stack) > 1:
                stack[-2]._nested += dt
        self._ann.__exit__(*exc)
        stack.pop()


def _on_duration(event: str, duration_secs: float, **_) -> None:
    if event != _COMPILE_EVENT:
        return
    stack = _stack()
    where = stack[-1].name if stack else "none"
    reg = get_global()
    reg.counter("serving_compiles_total",
                "backend compiles by the innermost open program span",
                labels=("span",)).inc(1, span=where)
    reg.counter("serving_compile_seconds_total",
                "backend compile seconds by the innermost open program span",
                labels=("span",)).inc(duration_secs, span=where)


def install_compile_counter() -> None:
    """Register the compile listener once per process (idempotent)."""
    global _installed
    with _install_lock:
        if _installed:
            return
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _installed = True
