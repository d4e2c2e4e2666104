"""Process-wide counters of JAX compilation and Python garbage collection.

``install()`` (idempotent; the serving engine and ``prune_model`` call it)
registers:

* ``jax.monitoring`` listeners counting ``traces`` (jaxpr traces),
  ``lowerings`` (jaxpr → MLIR modules), ``backend_compiles`` and
  ``persistent_cache_hits``.  A program loaded from the persistent cache
  still counts a lowering and a backend compile, so ``lowerings`` is the
  number that shows a retrace, cached or not;
* a ``gc.callbacks`` hook counting collections (``gc_pauses``), their
  total and longest pause (``gc_pause_s``, ``gc_pause_max_s``), and opening
  a ``python.gc`` profiler span (argument ``generation``) over each one, so
  a collection that stalls a decode step shows on the trace's clock.

``jit_counts()`` / ``gc_counts()`` return copies; a reader takes the
difference of two.  The HTTP front end's ``GET /stats`` exports both.
"""
from __future__ import annotations

import gc
import threading
import time

from jax.profiler import TraceAnnotation

# the duration events jax/_src/dispatch.py records, and the event
# jax/_src/compiler.py records when the persistent cache serves a program
_DURATION_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "traces",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowerings",
    "/jax/core/compile/backend_compile_duration": "backend_compiles",
}
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_lock = threading.Lock()
_jit = {"traces": 0, "lowerings": 0, "backend_compiles": 0,
        "persistent_cache_hits": 0}
_gc = {"gc_pauses": 0, "gc_pause_s": 0.0, "gc_pause_max_s": 0.0}
_collecting: list = []       # [span, t0] of the collection in progress
_installed = False


def _on_duration(event: str, duration: float, **kw) -> None:
    key = _DURATION_EVENTS.get(event)
    if key is not None:
        with _lock:
            _jit[key] += 1


def _on_event(event: str, **kw) -> None:
    if event == _CACHE_HIT_EVENT:
        with _lock:
            _jit["persistent_cache_hits"] += 1


def _on_gc(phase: str, info: dict, _clock=time.perf_counter,
           _span=TraceAnnotation) -> None:
    # takes no lock: a collection can start inside any allocation, also
    # one made while this thread holds ``_lock``.  Collections do not
    # nest, so one slot holds the one in progress.  Default-argument
    # bindings: collections still run at interpreter shutdown, when
    # module globals may already be gone
    if phase == "start":
        span = _span("python.gc", generation=info["generation"])
        span.__enter__()
        _collecting[:] = [span, _clock()]
    elif _collecting:
        span, t0 = _collecting
        _collecting.clear()
        pause = _clock() - t0
        span.__exit__(None, None, None)
        _gc["gc_pauses"] += 1
        _gc["gc_pause_s"] += pause
        _gc["gc_pause_max_s"] = max(_gc["gc_pause_max_s"], pause)


def install() -> None:
    """Register the listeners and the collection hook, once per process."""
    global _installed
    with _lock:
        if _installed:
            return
        _installed = True
    import jax

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    gc.callbacks.append(_on_gc)


def jit_counts() -> dict[str, int]:
    with _lock:
        return dict(_jit)


def gc_counts() -> dict[str, float]:
    return dict(_gc)
