"""How often JAX traced, lowered and compiled in this process.

One ``jax.monitoring`` duration listener, registered once per process on
import, counts three of JAX's events and their seconds:
``/jax/core/compile/jaxpr_trace_duration`` (a Python function traced to a
jaxpr), ``.../jaxpr_to_mlir_module_duration`` (a jaxpr lowered) and
``.../backend_compile_duration`` (an XLA compile, or a load from the
persistent compile cache).  A count that rises while a loop is in steady
state says that a step retraces or recompiles on every call.  The cost
is a dict update per event: nothing on a call that traces nothing.
"""
from __future__ import annotations

import threading
from typing import Dict

import jax

__all__ = ["jax_compile_counts"]

_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
           "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowerings",
           "/jax/core/compile/backend_compile_duration": "compiles"}
_counts: Dict[str, float] = {}
for _kind in _EVENTS.values():
    _counts[_kind] = 0
    _counts[_kind + "_s"] = 0.0
_lock = threading.Lock()


def _listener(event: str, duration_secs: float, **kwargs) -> None:
    kind = _EVENTS.get(event)
    if kind is not None:
        with _lock:
            _counts[kind] += 1
            _counts[kind + "_s"] += duration_secs


jax.monitoring.register_event_duration_secs_listener(_listener)


def jax_compile_counts() -> Dict[str, float]:
    """The process's counts so far: ``traces``, ``lowerings`` and
    ``compiles``, each with its seconds under ``<kind>_s``."""
    with _lock:
        return dict(_counts)
