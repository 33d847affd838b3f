"""Process-wide count of the programs JAX lowers.

Every jitted cell, and every eager operation at a shape not seen before,
is traced, lowered to an MLIR module and compiled (or read from the
persistent compilation cache).  One ``jax.monitoring`` listener,
installed once per process by :func:`install`, counts the lowerings
(``/jax/core/compile/jaxpr_to_mlir_module_duration`` events) and sums the
seconds spent tracing, lowering and compiling.  ``ContinuousEngine``
publishes the totals as the ``engine.lowerings`` and
``engine.lowering_seconds`` gauges.

Recorders passed to :func:`watch` also get one ``jax.lower`` span per
compiled program, timed ``[now - secs, now]`` on the recorder's clock
(``secs`` = its trace + lowering + compile seconds) and parented to the
tick or phase span open in the thread that lowered it.  A lowering
outside any open span is counted but not recorded.
"""
from __future__ import annotations

import threading
import weakref
from typing import Dict

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_lock = threading.Lock()
_installed = False
_totals = {"lowerings": 0, "seconds": 0.0}
_recorders: "weakref.WeakSet" = weakref.WeakSet()
# per thread: seconds traced / lowered since the last compile
_pending = threading.local()


def totals() -> Dict[str, float]:
    """``{"lowerings", "seconds"}`` since :func:`install`."""
    with _lock:
        return dict(_totals)


def watch(recorder) -> None:
    """Record ``jax.lower`` spans into ``recorder`` (a
    ``repro.obs.TraceRecorder``; held weakly)."""
    with _lock:
        _recorders.add(recorder)


def _on_duration(event: str, secs: float, **kw) -> None:
    if event not in (TRACE_EVENT, LOWER_EVENT, COMPILE_EVENT):
        return
    with _lock:
        _totals["seconds"] += secs
        if event == LOWER_EVENT:
            _totals["lowerings"] += 1
        recorders = list(_recorders)
    if event != COMPILE_EVENT:
        key = "trace_s" if event == TRACE_EVENT else "lower_s"
        setattr(_pending, key, getattr(_pending, key, 0.0) + secs)
        return
    trace_s = getattr(_pending, "trace_s", 0.0)
    lower_s = getattr(_pending, "lower_s", 0.0)
    _pending.trace_s = _pending.lower_s = 0.0
    if not lower_s:
        return
    total = trace_s + lower_s + secs
    for rec in recorders:
        parent = rec.innermost()
        if parent is None:
            continue
        now = rec.clock()
        rec.record("jax.lower", "jax", now - total, dur=total,
                   parent=parent, fun=kw.get("fun_name"),
                   trace_s=trace_s, lower_s=lower_s, compile_s=secs)


def install() -> None:
    """Register the listener (once per process; later calls do
    nothing)."""
    global _installed
    with _lock:
        if _installed:
            return
        _installed = True
    import jax
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
