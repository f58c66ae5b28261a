"""Unified observability: metrics export, trace spans, profiler hooks,
and counts of JAX's traces and compiles.

One recorder object (:class:`Recorder`, default :class:`NullRecorder`)
is the emit point for every layer — engine sweeps, the supervised
runtime, the serving pool, benchmarks.  Its spans open a
``jax.profiler.TraceAnnotation`` under either recorder, so they land in
any profiler capture on the device trace's clock; ``jax_compile_counts``
counts the process's JAX traces, lowerings and compiles.  Design
invariant: nothing in this package adds a host sync to the sweep path;
metrics snapshots and span closes happen only at host-sync boundaries
the caller already has (DESIGN.md §observability).

Typical wiring::

    from repro import obs
    rec = obs.configure(metrics_dir="m", trace_path="m/trace.json")
    labels = rec.register_engine(eng, workload="hetero-pairs-24", chains=16)
    with rec.span("sweep_chunk", **labels):
        state, tel = chunk(state, tel)
        ok = bool(tel_ready(tel))          # the existing host read
    rec.snapshot()                         # piggybacks that read
    rec.close()
"""
from .compiles import jax_compile_counts
from .metrics import MetricsRegistry, prometheus_escape
from .trace import TraceBuffer
from .recorder import (Recorder, NullRecorder, configure, get_recorder,
                       set_recorder, using)

__all__ = ["MetricsRegistry", "prometheus_escape", "TraceBuffer",
           "Recorder", "NullRecorder", "configure", "get_recorder",
           "set_recorder", "using", "jax_compile_counts"]
