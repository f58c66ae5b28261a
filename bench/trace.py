"""The trace reducer: from a JAX profiler trace of one measured window to
the numbers the per-layer metrics read.

What it reads, as a TPU trace lays it out: each ``/device:TPU:<k>`` plane
has an ``XLA Modules`` line (one event per program run on the chip, named
after the jitted function, such as ``jit_mgpmh_sweep(...)``), an ``XLA
Ops`` line (one event per HLO op, named by its HLO text, such as
``%mgpmh_sweep_pallas.1 = ... custom-call(...)``) and an ``Async XLA Ops``
line (copies that overlap compute).  The benchmark's own host spans
(``window``, ``dispatch``, ``wait``, ``submit``, ``generate``), written with
``jax.profiler.TraceAnnotation``, sit on the host plane on the same clock.

Reduction: the window is the ``window`` span.  A device is busy where any
of its ops runs; the idle gaps are the rest of the window, each part of
a gap filed under the host span open over it (``no span`` where none
is).  Each op is filed under the module run that contains it.
"""
from __future__ import annotations

import bisect
import glob
import os
import shutil
import tempfile
from typing import Dict, List, Tuple

HOST_SPANS = ("window", "dispatch", "wait", "submit", "generate")
NO_SPAN = "no span"


def span(name: str):
    """A host span in the profiler's trace (near free when it is off)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


class Tracer:
    """Profile one window into a temporary directory and reduce it."""

    def start(self) -> None:
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> dict:
        import jax
        jax.profiler.stop_trace()
        try:
            paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not paths:
                raise RuntimeError("the profiler wrote no trace")
            return reduce(from_xplane(paths[0]))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def from_xplane(path: str) -> dict:
    """The events the reducer reads, from an ``.xplane.pb`` file:
    ``{"host": [[name, t0, t1]], "devices": [{"modules": [...], "ops":
    [...], "async": [...]}]}`` with times in nanoseconds."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        host.append([e.name, e.start_ns,
                                     e.start_ns + e.duration_ns])
        elif plane.name.startswith("/device:TPU:") \
                and plane.name[len("/device:TPU:"):].isdigit():
            dev = {"modules": [], "ops": [], "async": []}
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops",
                       "Async XLA Ops": "async"}.get(line.name)
                if key:
                    dev[key] = [[e.name, e.start_ns,
                                 e.start_ns + e.duration_ns]
                                for e in line.events]
            devices.append(dev)
    return {"host": host, "devices": devices}


def op_name(hlo: str) -> str:
    """``%fusion.10 = f32[...] fusion(...)`` -> ``fusion.10``."""
    return hlo.split(" = ", 1)[0].lstrip("%").strip()


def module_name(name: str) -> str:
    """``jit_mgpmh_sweep(123)`` -> ``jit_mgpmh_sweep``."""
    return name.split("(", 1)[0]


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(iv, w0, w1):
    return [(max(a, w0), min(b, w1)) for a, b in iv if b > w0 and a < w1]


def _split(gap, spans, starts, out) -> None:
    """Add the parts of ``gap`` each host span covers to ``out`` by span
    name, and the rest under ``NO_SPAN`` (the spans are disjoint and
    sorted by start)."""
    a, b = gap
    covered = 0.0
    k = max(bisect.bisect_right(starts, a) - 1, 0)
    while k < len(spans) and spans[k][1] < b:
        name, s0, s1 = spans[k]
        ov = min(s1, b) - max(s0, a)
        if ov > 0:
            out[name] = out.get(name, 0.0) + ov
            covered += ov
        k += 1
    if b - a - covered > 0:
        out[NO_SPAN] = out.get(NO_SPAN, 0.0) + (b - a - covered)


def reduce(ev: dict, top: int = 10) -> dict:
    """Reduce the events of one traced window (see the module doc).

    Returns ``window_s``, ``busy_s`` (averaged over the devices), the ops
    of device 0 as ``[module, op, seconds]`` totals, the module totals,
    the idle seconds of device 0 by host span, and ``breakdown`` (the
    ``top`` device ops by time and the idle seconds by host span, longest
    first)."""
    windows = [(a, b) for n, a, b in ev["host"] if n == "window"]
    if len(windows) != 1:
        raise ValueError(f"expected one window span, found {len(windows)}")
    w0, w1 = windows[0]
    spans = sorted(((n, a, b) for n, a, b in ev["host"] if n != "window"),
                   key=lambda sp: sp[1])
    starts = [a for _, a, _ in spans]
    if not ev["devices"]:
        raise ValueError("the trace holds no TPU device plane")
    busy, idle_by = [], {}
    for k, dev in enumerate(ev["devices"]):
        iv = _clip([(a, b) for _, a, b in dev["ops"] + dev["async"]], w0, w1)
        merged = _union(iv)
        busy.append(sum(b - a for a, b in merged))
        if k:
            continue
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                _split((a, b), spans, starts, idle_by)
    dev0 = ev["devices"][0]
    mods = sorted((a, b, module_name(n)) for n, a, b in dev0["modules"])
    starts = [a for a, _, _ in mods]
    ops: Dict[Tuple[str, str], float] = {}
    for n, a, b in dev0["ops"]:
        if b <= w0 or a >= w1:
            continue
        k = bisect.bisect_right(starts, a) - 1
        mod = mods[k][2] if k >= 0 and a < mods[k][1] + 1 else "?"
        key = (mod, op_name(n))
        ops[key] = ops.get(key, 0.0) + (min(b, w1) - max(a, w0)) * 1e-9
    modules: Dict[str, float] = {}
    runs: Dict[str, int] = {}
    for a, b, m in mods:
        if b > w0 and a < w1:
            modules[m] = modules.get(m, 0.0) + (min(b, w1) - max(a, w0)) \
                * 1e-9
            runs[m] = runs.get(m, 0) + 1
    ranked = sorted(ops.items(), key=lambda kv: -kv[1])
    gaps = sorted(((k, v * 1e-9) for k, v in idle_by.items()),
                  key=lambda kv: -kv[1])
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "ops": [[m, o, s] for (m, o), s in ranked],
        "modules": modules, "module_runs": runs,
        "idle_by_span": dict(gaps),
        "breakdown": {
            "device_ops": [[f"{m}/{o}", s] for (m, o), s in ranked[:top]],
            "idle_gaps": [[lab, s] for lab, s in gaps[:top]]},
    }
