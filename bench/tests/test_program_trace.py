"""The program's spans in a profiler trace (``bench/program_trace.py``):
count, time, self time and the idle under the innermost span on hand-made
traces; the readings and the attribution of the ``dispatch`` idle on a
recorded chip trace; and the spans collected from a real capture."""
import glob
import json
import os

import jax
import pytest

from bench import program_trace as P
from bench import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))

OLD_KEYS = {"window_s", "busy_s", "ops", "modules", "module_runs",
            "idle_by_span", "breakdown"}


def recorded(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)


def test_hand_made_program_spans():
    """Nested ``window`` > ``dispatch`` > ``repro.sweep`` > {draws, kernel}
    spans, one call cut by the window's start: count, time in the window,
    self time and the idle under the innermost program span."""
    ev = {"host": [["window", 0, 100], ["dispatch", 0, 60],
                   ["wait", 60, 70], ["dispatch", 70, 90], ["wait", 90, 100]],
          "program": [["repro.sweep", -10, 3, 0],
                      ["repro.sweep.kernel", -5, 2, 0],
                      ["repro.sweep", 5, 55, 0],
                      ["repro.sweep.draws", 10, 40, 0],
                      ["repro.sweep.kernel", 42, 50, 0],
                      ["repro.sweep", 72, 88, 0],
                      ["repro.sweep.draws", 73, 80, 0],
                      ["repro.sweep.kernel", 81, 85, 0],
                      ["repro.sweep", 120, 130, 0]],      # after the window
          "devices": [{"modules": [], "async": [],
                       "ops": [["%a = f32[] add()", 20, 30],
                               ["%k = f32[] custom-call()", 45, 65],
                               ["%b = f32[] mul()", 84, 95]]}]}
    r = T.reduce(ev)
    # idle: [0,20], [30,45], [65,84], [95,100]
    assert r["idle_by_span"] == pytest.approx(
        {"dispatch": 49e-9, "wait": 10e-9})
    p = P.program(ev)
    assert set(p) == {"repro.sweep", "repro.sweep.draws",
                      "repro.sweep.kernel"}
    assert {k: v["count"] for k, v in p.items()} == {
        "repro.sweep": 3, "repro.sweep.draws": 2, "repro.sweep.kernel": 3}
    want = {"repro.sweep": (69, 18, 10), "repro.sweep.draws": (37, 37, 27),
            "repro.sweep.kernel": (14, 14, 8)}
    for name, (host, self_, idle) in want.items():
        assert p[name]["host_s"] == pytest.approx(host * 1e-9), name
        assert p[name]["self_s"] == pytest.approx(self_ * 1e-9), name
        assert p[name]["idle_s"] == pytest.approx(idle * 1e-9), name
    # the idle under `dispatch` and under no program span: [3,5], [70,72]
    assert sum(v["idle_s"] for v in p.values()) + 4e-9 == pytest.approx(
        r["idle_by_span"]["dispatch"])


def test_program_spans_on_two_lines():
    """Self time subtracts only the children on the span's own line; the
    idle goes to the innermost span open, whichever line holds it."""
    ev = {"host": [["window", 0, 100]],
          "program": [["repro.sweep", 0, 100, 0],
                      ["repro.pool", 20, 60, 1],
                      ["repro.sweep.draws", 40, 80, 0]],
          "devices": [{"modules": [], "async": [], "ops": []}]}
    p = P.program(ev)
    assert p["repro.sweep"]["self_s"] == pytest.approx(60e-9)
    assert p["repro.pool"]["self_s"] == pytest.approx(40e-9)
    assert p["repro.sweep"]["idle_s"] == pytest.approx(40e-9)
    assert p["repro.pool"]["idle_s"] == pytest.approx(20e-9)
    assert p["repro.sweep.draws"]["idle_s"] == pytest.approx(40e-9)


def test_program_spans_leave_the_trace_reduction_alone():
    """A trace without program spans reads no program span and no
    reading; adding program spans changes nothing ``bench.trace.reduce``
    returns."""
    ev = recorded("trace_mgpmh_pallas.json")
    r = T.reduce(ev)
    assert set(r) == OLD_KEYS
    assert P.program(ev) == {}
    assert P.readings({}, 10, r["window_s"]) == {}
    w1 = ev["host"][0][2]
    ev["program"] = [["repro.sweep", 0.0, w1 / 2, 0],
                     ["repro.sweep.draws", 1e6, w1 / 4, 0]]
    assert T.reduce(ev) == r
    assert set(P.program(ev)) == {"repro.sweep", "repro.sweep.draws"}


def _length(iv):
    return sum(b - a for a, b in T._union(iv))


def _meet(xs, ys):
    """The intersection of two lists of intervals, as intervals."""
    return [(max(a, c), min(b, d)) for a, b in T._union(xs)
            for c, d in T._union(ys) if min(b, d) > max(a, c)]


def test_readings_on_the_recorded_spans_trace():
    """A chip trace of the mgpmh cell with the program's spans: every
    reading reads a number, and the program spans' idle plus the idle
    under `dispatch` and under no program span is the `dispatch` idle."""
    ev = recorded("trace_mgpmh_pallas_spans.json")
    r = T.reduce(ev)
    p = P.program(ev)
    assert {"repro.sweep", "repro.sweep.draws",
            "repro.sweep.kernel"} <= set(p)
    calls = p["repro.sweep"]["count"]
    read = P.readings(p, calls, r["window_s"], traces=7 * calls)
    assert set(read) == {"sweep_host_ms", "draws_host_ms",
                         "kernel_launch_host_ms", "draws_idle_share",
                         "traces_per_call"}
    assert 0 < read["kernel_launch_host_ms"] < read["draws_host_ms"] \
        < read["sweep_host_ms"]
    assert 0 < read["draws_idle_share"] <= 100 * (
        1 - r["busy_s"] / r["window_s"]) + 1e-9
    assert read["traces_per_call"] == 7
    assert "traces_per_call" not in P.readings(p, calls, r["window_s"])
    # the sweep's children lie inside it, on its line
    assert p["repro.sweep"]["self_s"] == pytest.approx(
        p["repro.sweep"]["host_s"] - p["repro.sweep.draws"]["host_s"]
        - p["repro.sweep.kernel"]["host_s"])
    w0, w1 = P._window(ev)
    idle = P._gaps(ev["devices"][0], w0, w1)
    dispatch = T._clip([(a, b) for n, a, b in ev["host"]
                        if n == "dispatch"], w0, w1)
    prog = T._clip([(a, b) for _, a, b, _ in ev["program"]], w0, w1)
    idle_dispatch = _meet(idle, dispatch)
    dispatch_only = _length(idle_dispatch) - _length(_meet(idle_dispatch,
                                                         prog))
    # every program span lies inside a dispatch span
    assert _length(_meet(prog, dispatch)) == pytest.approx(_length(prog))
    total = sum(v["idle_s"] for v in p.values()) + dispatch_only * 1e-9
    assert total == pytest.approx(r["idle_by_span"]["dispatch"], abs=1e-6)


def test_events_collect_the_program_spans_of_a_capture(tmp_path):
    """A profiler capture on the CPU: ``events`` holds the benchmark's
    host spans as ``bench.trace.from_xplane`` reads them, and the
    ``repro.*`` spans, nested, on the line of the thread that opened
    them."""
    from repro.obs import NullRecorder
    rec = NullRecorder()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with T.span("window"):
            with rec.span("repro.sweep"):
                with rec.span("repro.sweep.draws"):
                    jax.numpy.ones(3).block_until_ready()
                with rec.span("repro.sweep.kernel"):
                    pass
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    ev = P.events(path)
    assert {k: v for k, v in ev.items() if k != "program"} == \
        T.from_xplane(path)
    assert [h[0] for h in ev["host"]] == ["window"]
    spans = sorted(ev["program"], key=lambda e: e[1])
    assert [s[0] for s in spans] == ["repro.sweep", "repro.sweep.draws",
                                     "repro.sweep.kernel"]
    assert len({s[3] for s in spans}) == 1
    (_, s0, s1, _), (_, d0, d1, _), (_, k0, k1, _) = spans
    w0, w1 = ev["host"][0][1:]
    assert w0 <= s0 <= d0 < d1 <= k0 <= k1 <= s1 <= w1
