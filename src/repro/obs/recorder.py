"""The one emit point: spans, events, and metrics behind a single object.

``Recorder`` composes a :class:`~repro.obs.trace.TraceBuffer` and a
:class:`~repro.obs.metrics.MetricsRegistry` and is what the engine,
runtime, serving, and benchmark layers talk to.  ``NullRecorder`` is the
default: every method returns immediately but ``span``, which opens a
``jax.profiler.TraceAnnotation`` of the span's name and nothing else.  So
every program span lands in a profiler capture on the device trace's
clock whichever recorder is active, an uninstrumented run pays about what
a ``nullcontext`` costs, and, by the overhead tests, the instrumented
sweep path pays no host sync and ≤5% wall clock.

Module-level plumbing (``get_recorder``/``set_recorder``/``using``/
``configure``) keeps call sites one import away from the active
recorder without threading it through every signature.
"""
from __future__ import annotations

import json
import os
import threading
import time
from contextlib import contextmanager, nullcontext
from typing import Dict, Optional

from jax.profiler import TraceAnnotation

from .compiles import jax_compile_counts
from .metrics import MetricsRegistry
from .trace import TraceBuffer

__all__ = ["Recorder", "NullRecorder", "get_recorder", "set_recorder",
           "using", "configure"]


class NullRecorder:
    """The default when observability is off: no files, no metrics; its
    spans reach only a running profiler capture."""

    enabled = False

    def span(self, name: str, **args):
        """A host span in any profiler capture (a near no-op without one)."""
        return TraceAnnotation(name)

    def complete(self, name, ts_us, dur_us, **args):
        pass

    def now_us(self) -> float:
        return 0.0

    def instant(self, name: str, **args):
        pass

    def event(self, kind: str, **info):
        pass

    def count(self, name: str, value: float = 1.0, **labels):
        pass

    def gauge(self, name: str, value: float, **labels):
        pass

    def histogram(self, name: str, value: float, **labels):
        pass

    def register_engine(self, eng, *, workload: str = "",
                        chains: int = 0) -> Dict[str, str]:
        return {"engine": getattr(eng, "name", ""),
                "backend": getattr(eng, "backend", ""),
                "schedule": "", "workload": workload}

    def snapshot(self):
        pass

    def profile(self):
        return nullcontext()

    def close(self):
        pass


class Recorder(NullRecorder):
    """Active recorder writing trace + metrics files.

    ``metrics_dir``  directory for ``metrics.jsonl`` (one snapshot per
                     line) and ``metrics.prom`` (rewritten each snapshot)
                     and ``events.jsonl`` (one structured event per line).
    ``trace_path``   Chrome trace-event JSON output (written on close and
                     after every snapshot, atomically).
    ``profile_dir``  enables ``profile()`` → ``jax.profiler.trace``.
    """

    enabled = True
    # the process's JAX counts (``jax_compile_counts`` keys), exported by
    # ``snapshot`` as counters
    _COMPILE_SERIES = {
        "traces": ("jax_traces_total", "JAX traces"),
        "traces_s": ("jax_traces_seconds_total", "seconds in JAX traces"),
        "lowerings": ("jax_lowerings_total", "JAX lowerings to MLIR"),
        "lowerings_s": ("jax_lowerings_seconds_total",
                        "seconds in JAX lowerings to MLIR"),
        "compiles": ("jax_compiles_total",
                     "XLA compiles, persistent-cache loads included"),
        "compiles_s": ("jax_compiles_seconds_total",
                       "seconds in XLA compiles and cache loads"),
    }

    def __init__(self, metrics_dir: Optional[str] = None,
                 trace_path: Optional[str] = None,
                 profile_dir: Optional[str] = None,
                 process_name: str = "repro"):
        self.metrics = MetricsRegistry()
        self.trace = TraceBuffer(process_name=process_name)
        self.metrics_dir = metrics_dir
        self.trace_path = trace_path
        self.profile_dir = profile_dir
        self._io_lock = threading.Lock()
        self._compiles_seen = dict.fromkeys(self._COMPILE_SERIES, 0.0)
        if metrics_dir:
            os.makedirs(metrics_dir, exist_ok=True)

    # -- tracing ----------------------------------------------------------
    @contextmanager
    def span(self, name: str, **args):
        """Host-side span: trace event + seconds/calls accumulators, and
        the profiler's annotation as on the null recorder."""
        t0 = self.trace.now_us()
        try:
            with TraceAnnotation(name):
                yield
        finally:
            dur = self.trace.now_us() - t0
            self.trace.complete(name, t0, dur, **args)
            self.metrics.count("span_seconds_total", dur / 1e6,
                               help="total wall seconds inside span",
                               span=name)
            self.metrics.count("span_calls_total", 1,
                               help="span entry count", span=name)

    def complete(self, name, ts_us, dur_us, **args):
        self.trace.complete(name, ts_us, dur_us, **args)
        self.metrics.count("span_seconds_total", dur_us / 1e6, span=name)
        self.metrics.count("span_calls_total", 1, span=name)

    def now_us(self) -> float:
        return self.trace.now_us()

    def instant(self, name: str, **args):
        self.trace.instant(name, **args)

    def event(self, kind: str, **info):
        """A structured incident: instant trace event + counter + one
        ``events.jsonl`` line (the unified successor of the supervisor's
        ``incidents.jsonl``)."""
        self.trace.instant(kind, **info)
        self.metrics.count("events_total", 1,
                           help="structured incident events", kind=kind)
        if self.metrics_dir:
            line = json.dumps({"ts_us": self.trace.now_us(), "kind": kind,
                               **info}, default=str)
            with self._io_lock:
                with open(os.path.join(self.metrics_dir,
                                       "events.jsonl"), "a") as f:
                    f.write(line + "\n")

    # -- metrics ----------------------------------------------------------
    def count(self, name: str, value: float = 1.0, **labels):
        self.metrics.count(name, value, **labels)

    def gauge(self, name: str, value: float, **labels):
        self.metrics.gauge(name, value, **labels)

    def histogram(self, name: str, value: float, **labels):
        self.metrics.histogram(name, value, **labels)

    def register_engine(self, eng, *, workload: str = "",
                        chains: int = 0) -> Dict[str, str]:
        """Publish an engine's identity + analytic cost gauges; returns the
        standard label set callers attach to their own series."""
        labels = {"engine": eng.name, "backend": eng.backend,
                  "schedule": eng.schedule.describe(), "workload": workload}
        self.metrics.gauge("engine_updates_per_call", eng.updates_per_call,
                           help="site updates per sweep call", **labels)
        if chains:
            self.metrics.gauge("engine_chains", chains,
                               help="resident chains", **labels)
        n = int(eng.graph.n)
        cost = _sweep_cost(eng, chains or 1, n)
        self.metrics.gauge("sweep_flops_per_call", cost["flops_per_call"],
                           help="analytic flops per sweep call", **labels)
        self.metrics.gauge("sweep_bytes_per_call", cost["bytes_per_call"],
                           help="analytic bytes per sweep call", **labels)
        foot = _psum_footprint(eng, chains or 1, n)
        self.metrics.gauge("psum_payload_bytes", foot["psum_payload_bytes"],
                           help="dist collective payload per sweep call",
                           **labels)
        self.metrics.gauge("collectives_per_sweep",
                           foot["collectives_per_sweep"],
                           help="collectives per sweep call", **labels)
        return labels

    # -- export -----------------------------------------------------------
    def _count_compiles(self):
        """Bring the ``jax_*_total`` counters up to the process's counts."""
        now = jax_compile_counts()
        for key, (series, help_) in self._COMPILE_SERIES.items():
            self.metrics.count(series, now[key] - self._compiles_seen[key],
                               help=help_)
        self._compiles_seen = now

    def snapshot(self):
        """Flush current metric values to disk (JSONL append + .prom
        rewrite) and refresh the trace file.  Called only at existing
        host-sync boundaries — never from inside the sweep path."""
        self._count_compiles()
        if self.metrics_dir:
            series = self.metrics.snapshot()
            with self._io_lock:
                with open(os.path.join(self.metrics_dir,
                                       "metrics.jsonl"), "a") as f:
                    f.write(json.dumps({"ts": time.time(),
                                        "series": series}) + "\n")
                prom = self.metrics.to_prometheus()
                path = os.path.join(self.metrics_dir, "metrics.prom")
                tmp = path + ".tmp"
                with open(tmp, "w") as f:
                    f.write(prom)
                os.replace(tmp, path)
        if self.trace_path:
            self.trace.write(self.trace_path)

    def profile(self):
        """Opt-in ``jax.profiler.trace`` capture (requires profile_dir)."""
        if not self.profile_dir:
            return nullcontext()
        import jax
        return jax.profiler.trace(self.profile_dir)

    def close(self):
        self.snapshot()


# -- cost helpers (tolerant: identity gauges must never break a run) -------

def _sweep_cost(eng, chains: int, n: int) -> Dict[str, float]:
    from .costmodel import sweep_cost
    try:
        return sweep_cost(eng.name, chains=chains, n=n, D=eng.graph.D,
                          sweep=eng.updates_per_call, params=eng.params)
    except Exception:
        return {"flops_per_call": 0.0, "bytes_per_call": 0.0}


def _psum_footprint(eng, chains: int, n: int) -> Dict[str, float]:
    if eng.backend != "dist":
        return {"collectives_per_sweep": 0, "psum_payload_bytes": 0}
    try:
        from ..runtime.dist_gibbs import psum_footprint
        desc = eng.schedule.describe()
        if desc.startswith("chromatic"):
            return psum_footprint("chromatic", C=chains, D=eng.graph.D,
                                  n=n, n_colors=eng.schedule.n_colors)
        sweep = getattr(eng.schedule, "sweep_len", eng.updates_per_call)
        return psum_footprint(eng.name, C=chains, D=eng.graph.D, S=sweep)
    except Exception:
        return {"collectives_per_sweep": 0, "psum_payload_bytes": 0}


# -- module-level active recorder ------------------------------------------

_active: NullRecorder = NullRecorder()


def get_recorder() -> NullRecorder:
    """The process-wide active recorder (NullRecorder unless configured)."""
    return _active


def set_recorder(rec) -> NullRecorder:
    global _active
    prev, _active = _active, rec
    return prev


@contextmanager
def using(rec):
    """Scope ``rec`` as the active recorder for a ``with`` block."""
    prev = set_recorder(rec)
    try:
        yield rec
    finally:
        set_recorder(prev)


def configure(metrics_dir: Optional[str] = None,
              trace_path: Optional[str] = None,
              profile_dir: Optional[str] = None,
              process_name: str = "repro"):
    """Build and activate a Recorder when any output is requested;
    otherwise leave/restore the NullRecorder.  Returns the active one."""
    if not (metrics_dir or trace_path or profile_dir):
        set_recorder(NullRecorder())
        return get_recorder()
    rec = Recorder(metrics_dir=metrics_dir, trace_path=trace_path,
                   profile_dir=profile_dir, process_name=process_name)
    set_recorder(rec)
    return rec
