"""Kernel dispatch policy: compiled Pallas on TPU, interpreter elsewhere.

Every kernel in this package takes ``interpret: bool | None = None``.  ``None``
resolves through :func:`default_interpret` at trace time — compiled Mosaic
when the default jax backend is TPU, the Pallas interpreter on CPU/GPU — so
one call site runs correctly on the production accelerator and in local/CI
containers alike.  Pass an explicit bool to override (e.g. ``interpret=True``
on TPU to debug a kernel numerically).
"""
from __future__ import annotations

import jax

__all__ = ["default_interpret", "resolve_interpret", "record_launch",
           "record_lcc_launch", "record_moe_launch", "launch_count",
           "reset_launch_count"]


def default_interpret() -> bool:
    """True when the default backend cannot run compiled Pallas TPU kernels."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    return default_interpret() if interpret is None else bool(interpret)


# ---------------------------------------------------------------------------
# launch accounting
# ---------------------------------------------------------------------------
#
# Every decode-path kernel wrapper calls ``record_launch()`` from plain Python
# *before* entering its jitted implementation, so the counter advances once
# per ``pallas_call`` that a trace emits (inner jit caches never hide a call
# site: the un-jitted wrapper body runs on every trace-time invocation).
# Within one jitted decode step the trace-time count equals the runtime
# launches per step (``ServingEngine.pallas_launches_per_step``).

_launch_count = 0
_launch_metric = None  # lazily resolved obs counter (process-global registry)
_lcc_metric = None
_moe_metric = None


def record_launch(n: int = 1) -> None:
    """Count ``n`` Pallas launches emitted by the current (trace-time) call.

    Also published as the live ``pallas_launches_total`` counter in the
    process-global :mod:`repro.obs` registry (resolved lazily so importing
    this module stays free of any obs setup cost)."""
    global _launch_count, _launch_metric
    _launch_count += n
    if _launch_metric is None:
        from repro.obs import get_global
        _launch_metric = get_global().counter(
            "pallas_launches_total",
            "Pallas launches recorded at trace time, process-wide")
    _launch_metric.inc(n)


def record_lcc_launch(launch: str, body: str) -> None:
    """Count one LCC launch named ``launch`` (its site group's name in the
    device trace) under the ``body`` that runs it: ``row_gather`` (the
    compiled body), ``row_gather+dense_first`` (the same, its first factor
    as one-hot tiles on the MXU) or ``interpret`` (the interpreter's
    gather).  Published
    as ``lcc_launches_total{launch, body}`` in the process-global registry,
    at trace time like :func:`record_launch`."""
    global _lcc_metric
    if _lcc_metric is None:
        from repro.obs import get_global
        _lcc_metric = get_global().counter(
            "lcc_launches_total",
            "LCC kernel launches recorded at trace time, by launch and body",
            labels=("launch", "body"))
    _lcc_metric.inc(launch=launch, body=body)


def record_moe_launch(launch: str, body: str) -> None:
    """Count one grouped expert launch named ``launch``
    (``_moe_group_matmul_gate_up`` or ``_moe_group_matmul_down``) under the
    ``body`` that runs it: ``compiled`` (Mosaic) or ``interpret``.  Published
    as ``moe_launches_total{launch, body}`` in the process-global registry, at
    trace time like :func:`record_launch`."""
    global _moe_metric
    if _moe_metric is None:
        from repro.obs import get_global
        _moe_metric = get_global().counter(
            "moe_launches_total",
            "grouped expert launches recorded at trace time, by launch and "
            "body", labels=("launch", "body"))
    _moe_metric.inc(launch=launch, body=body)


def launch_count() -> int:
    """Cumulative launches recorded since import (or the last reset)."""
    return _launch_count


def reset_launch_count() -> None:
    global _launch_count
    _launch_count = 0
