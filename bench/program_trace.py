"""The program's own spans in a profiler trace of one window, and a command
that records one offline cell's window and prints what they read.

  python -m bench.program_trace --workload <cell> --seed <n> --seconds <s>

From the root of a checkout, on the chip, for a cell of the offline
traffic kind.  The program opens its spans through ``repro.obs`` (every
host event named ``repro.*``: ``repro.sweep`` in ``Engine.sweep``, and
its children ``repro.sweep.draws`` and ``repro.sweep.kernel`` in the
fused-Pallas sweep builders), each on the line of the thread that opened
it and on the device trace's clock.  ``bench/trace.py`` reduces the
benchmark's own host spans and leaves these out; this module reads them
from the same ``.xplane.pb`` file:

- ``events`` is ``bench.trace.from_xplane`` plus ``program``, the program
  spans as ``[name, t0, t1, line]`` in nanoseconds;
- ``program`` gives, for each span name, ``count`` (the spans that overlap
  the ``window`` span), ``host_s`` (their time in it), ``self_s`` (less
  that of the child program spans on the same line) and ``idle_s``
  (device 0's idle time in the window while it is the innermost open
  program span, the one opened last);
- ``readings`` turns that, with the window's calls and the program's
  counts of JAX traces (``repro.obs.jax_compile_counts``, read just
  outside the window), into host milliseconds a call in each span, the
  share of the window the device idles under the draws, and traces a
  call.

The command runs the cell's set-up and window as ``bench.run`` does, with
no check of the outputs, and prints one JSON line: the readings, the
window's ``program`` reduction, and ``bench.trace.reduce``'s
``idle_by_span`` and ``busy_s`` / ``window_s`` of the same trace.
"""
from __future__ import annotations

import argparse
import glob
import heapq
import json
import os
import shutil
import sys
from typing import Dict, List, Tuple

from bench import trace as tracing

PREFIX = "repro."


def events(path: str) -> dict:
    """``bench.trace.from_xplane(path)`` with the program's spans added as
    ``program``: ``[[name, t0, t1, line]]``, times in nanoseconds,
    ``line`` numbering the host lines (threads)."""
    from jax.profiler import ProfileData
    ev = tracing.from_xplane(path)
    program, lines = [], 0
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        program.append([e.name, e.start_ns,
                                        e.start_ns + e.duration_ns, lines])
                lines += 1
    ev["program"] = program
    return ev


def _window(ev: dict) -> Tuple[float, float]:
    windows = [(a, b) for n, a, b in ev["host"] if n == "window"]
    if len(windows) != 1:
        raise ValueError(f"expected one window span, found {len(windows)}")
    return windows[0]


def _gaps(dev: dict, w0: float, w1: float) -> List[Tuple[float, float]]:
    """The device's idle stretches of ``[w0, w1]``, in order."""
    busy = tracing._union(tracing._clip(
        [(a, b) for _, a, b in dev["ops"] + dev["async"]], w0, w1))
    edges = [w0] + [x for ab in busy for x in ab] + [w1]
    return [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]


def _innermost(spans, w0, w1) -> List[Tuple[float, float, str]]:
    """Disjoint ``(a, b, name)`` stretches of ``[w0, w1]`` in which some
    program span is open, each under the innermost one: the one opened
    last (of two opened at once, the one that closes first)."""
    live = [k for k, sp in enumerate(spans) if sp[2] > sp[1]]
    marks = sorted([(spans[k][1], 1, k) for k in live]
                   + [(spans[k][2], 0, k) for k in live])
    out, open_, heap, t = [], set(), [], w0
    for x, opening, k in marks:
        while heap and heap[0][2] not in open_:
            heapq.heappop(heap)
        a, b = max(t, w0), min(x, w1)
        if heap and b > a:
            out.append((a, b, spans[heap[0][2]][0]))
        t = x
        if opening:
            open_.add(k)
            heapq.heappush(heap, (-spans[k][1], spans[k][2], k))
        else:
            open_.discard(k)
    return out


def program(ev: dict) -> Dict[str, dict]:
    """Per program span name, its ``count``, ``host_s``, ``self_s`` and
    ``idle_s`` in the window (see the module doc); empty where the events
    hold no program span."""
    w0, w1 = _window(ev)
    if not ev["devices"]:
        raise ValueError("the trace holds no TPU device plane")
    gaps = _gaps(ev["devices"][0], w0, w1)
    spans = [sp for sp in ev.get("program", []) if sp[2] > w0 and sp[1] < w1]
    inside = [min(b, w1) - max(a, w0) for _, a, b, _ in spans]
    child = [0.0] * len(spans)
    by_line: Dict[int, List[int]] = {}
    for k, sp in enumerate(spans):
        by_line.setdefault(sp[3], []).append(k)
    for ks in by_line.values():
        stack: List[int] = []           # the open spans, outermost first
        for k in sorted(ks, key=lambda k: (spans[k][1], -spans[k][2])):
            while stack and spans[stack[-1]][2] <= spans[k][1]:
                stack.pop()
            if stack:
                child[stack[-1]] += inside[k]
            stack.append(k)
    out: Dict[str, dict] = {}
    for k, sp in enumerate(spans):
        r = out.setdefault(sp[0], {"count": 0, "host_s": 0.0, "self_s": 0.0,
                                   "idle_s": 0.0})
        r["count"] += 1
        r["host_s"] += inside[k]
        r["self_s"] += inside[k] - child[k]
    k = 0
    for a, b, name in _innermost(spans, w0, w1):
        while k < len(gaps) and gaps[k][1] <= a:
            k += 1
        j = k
        while j < len(gaps) and gaps[j][0] < b:
            out[name]["idle_s"] += min(b, gaps[j][1]) - max(a, gaps[j][0])
            j += 1
    for r in out.values():
        for key in ("host_s", "self_s", "idle_s"):
            r[key] *= 1e-9
    return out


def readings(prog: Dict[str, dict], calls: int, window_s: float,
             traces: int = None) -> Dict[str, float]:
    """What the program's spans and trace count read over a window of
    ``calls`` sweep calls; a reading whose span or count is absent is
    left out."""
    out = {}
    if not calls:
        return out
    for key, name in (("sweep_host_ms", "repro.sweep"),
                      ("draws_host_ms", "repro.sweep.draws"),
                      ("kernel_launch_host_ms", "repro.sweep.kernel")):
        if name in prog:
            out[key] = 1e3 * prog[name]["host_s"] / calls
    if "repro.sweep.draws" in prog and window_s > 0:
        out["draws_idle_share"] = \
            100.0 * prog["repro.sweep.draws"]["idle_s"] / window_s
    if traces is not None:
        out["traces_per_call"] = traces / calls
    return out


def record(cell: dict, seed: int, seconds: float) -> dict:
    """Set up the offline ``cell``, profile one window of it and return
    its readings (see the module doc)."""
    import jax
    from bench.spec import plugin
    from repro.obs import jax_compile_counts
    kind = cell["work"]["traffic"]["kind"]
    if kind != "offline":
        raise ValueError(f"{cell['entry']['name']} is of traffic kind "
                         f"{kind!r}; this reads offline cells only")
    s = plugin("traffic", "offline").Sampler(cell["work"], cell["config"])
    st = s.start(seed)
    tracer = tracing.Tracer()
    tracer.start()
    try:
        c0 = jax_compile_counts()
        got = s.window(st, seconds, False, seed)
        c1 = jax_compile_counts()
        jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(tracer.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise RuntimeError("the profiler wrote no trace")
        ev = events(paths[0])
    finally:
        shutil.rmtree(tracer.dir, ignore_errors=True)
    red = tracing.reduce(ev)
    prog = program(ev)
    counts = {k: c1[k] - c0[k] for k in c0}
    return {"workload": cell["entry"]["name"], "seed": seed,
            "calls": got["calls"],
            "readings": readings(prog, got["calls"], red["window_s"],
                                 counts["traces"]),
            "jax_counts": counts, "program": prog,
            "idle_by_span": red["idle_by_span"], "busy_s": red["busy_s"],
            "window_s": red["window_s"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m bench.program_trace",
                                 description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from bench import run
    from bench import spec as spec_mod
    top = spec_mod.root()
    sys.path.insert(0, os.path.join(top, "src"))
    spec = spec_mod.load(top)
    cell = spec_mod.cell(spec, args.workload, top)
    if run._devices(int(cell["entry"]["chips"])) is None:
        return 2
    run._compile_cache(top)
    print(json.dumps(record(cell, args.seed, args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
