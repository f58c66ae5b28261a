"""Observability layer (repro/obs): metrics registry semantics, Chrome
trace-event output, Prometheus exposition, recorder wiring through the
supervised runtime / checkpoint / serving layers, the sweep's spans in a
profiler capture, the JAX trace/compile counter, and the overhead
contracts — the null recorder adds zero host syncs to the fused sweep
path and the instrumented path stays within the 5% wall-clock budget."""
import glob
import json
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine as engine_lib
from repro.obs import (MetricsRegistry, NullRecorder, Recorder, TraceBuffer,
                       configure, get_recorder, jax_compile_counts,
                       set_recorder, using)
from repro.runtime.faultinject import Fault, FaultPlan
from repro.runtime.supervisor import SupervisedRun, SupervisorConfig

GRAPH = engine_lib.make_workload("hetero-pairs-24").graph


# -- metrics registry --------------------------------------------------------

def test_metrics_counter_accumulates_and_gauge_overwrites():
    m = MetricsRegistry()
    m.count("hits", 2, engine="gibbs")
    m.count("hits", 3, engine="gibbs")
    m.count("hits", 1, engine="mgpmh")
    m.gauge("depth", 4.0)
    m.gauge("depth", 7.0)
    assert m.value("hits", engine="gibbs") == 5
    assert m.value("hits", engine="mgpmh") == 1
    assert m.value("depth") == 7.0
    assert m.value("missing") is None


def test_metrics_rejects_kind_mixing():
    m = MetricsRegistry()
    m.count("x", 1)
    with pytest.raises(ValueError):
        m.gauge("x", 1.0)


def test_prometheus_exposition_parses_and_escapes():
    m = MetricsRegistry()
    m.count("sweeps_total", 5, engine="gibbs", backend="jnp")
    m.gauge("acceptance", 0.5, schedule='uniform-sites(S=4)',
            note='quote " and \\ back\nline')
    text = m.to_prometheus()
    assert '# TYPE repro_sweeps_total counter' in text
    assert '# TYPE repro_acceptance gauge' in text
    assert ('repro_sweeps_total{backend="jnp",engine="gibbs"} 5'
            in text)
    # escaped label values survive the round trip
    assert '\\n' in text and '\\"' in text
    sample = re.compile(r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{.*\})? '
                        r'[-+0-9.eE]+$')
    for line in text.strip().splitlines():
        assert line.startswith("#") or sample.match(line), line


# -- trace buffer ------------------------------------------------------------

def test_trace_buffer_writes_chrome_trace_json(tmp_path):
    tb = TraceBuffer(process_name="repro.test")
    t0 = tb.now_us()
    with_dur = tb.now_us() - t0
    tb.complete("sweep_chunk", t0, max(with_dur, 1.0), engine="gibbs")
    tb.instant("fault", step=3)
    out = tmp_path / "trace.json"
    tb.write(str(out))
    doc = json.loads(out.read_text())
    evs = doc["traceEvents"]
    assert evs[0]["ph"] == "M" and evs[0]["name"] == "process_name"
    assert evs[0]["args"]["name"] == "repro.test"
    x = [e for e in evs if e["ph"] == "X"]
    i = [e for e in evs if e["ph"] == "i"]
    assert x[0]["name"] == "sweep_chunk" and x[0]["args"]["engine"] == "gibbs"
    assert x[0]["dur"] >= 1.0 and "ts" in x[0]
    assert i[0]["name"] == "fault" and i[0]["s"] == "p"


# -- recorder ----------------------------------------------------------------

def test_configure_null_by_default_and_using_restores(tmp_path):
    assert configure().enabled is False
    rec = configure(metrics_dir=str(tmp_path))
    assert rec.enabled and get_recorder() is rec
    with using(NullRecorder()):
        assert not get_recorder().enabled
    assert get_recorder() is rec
    set_recorder(NullRecorder())


def test_register_engine_publishes_identity_and_cost_gauges():
    eng = engine_lib.make("mgpmh", GRAPH, sweep=8, backend="jnp")
    rec = Recorder()
    labels = rec.register_engine(eng, workload="hetero-pairs-24", chains=4)
    assert labels == {"engine": "mgpmh", "backend": "jnp",
                      "schedule": eng.schedule.describe(),
                      "workload": "hetero-pairs-24"}
    assert rec.metrics.value("engine_chains", **labels) == 4
    assert rec.metrics.value("sweep_flops_per_call", **labels) > 0
    assert rec.metrics.value("sweep_bytes_per_call", **labels) > 0
    # non-dist engines move no collective payload
    assert rec.metrics.value("psum_payload_bytes", **labels) == 0


def test_register_engine_dist_psum_gauges_match_footprint():
    from repro.runtime.dist_gibbs import psum_footprint

    class _Sched:
        sweep_len = 16

        def describe(self):
            return "uniform-sites(S=16)"

    class _Eng:
        name, backend = "mgpmh", "dist"
        schedule, graph = _Sched(), GRAPH
        updates_per_call = 16
        params = {"lam": 32.0, "capacity": 64}

    rec = Recorder()
    labels = rec.register_engine(_Eng(), workload="w", chains=8)
    foot = psum_footprint("mgpmh", C=8, D=GRAPH.D, S=16)
    assert (rec.metrics.value("psum_payload_bytes", **labels)
            == foot["psum_payload_bytes"])
    assert (rec.metrics.value("collectives_per_sweep", **labels)
            == foot["collectives_per_sweep"])


# -- overhead contracts ------------------------------------------------------

def _warm_engine(sweep=8, chains=4):
    eng = engine_lib.make("gibbs", GRAPH, sweep=sweep, backend="jnp")
    st = eng.init(jax.random.PRNGKey(0), chains)
    st = eng.sweep(st)
    jax.block_until_ready(st.x)
    return eng, st


def test_null_recorder_sweep_path_has_zero_host_syncs():
    """With the default NullRecorder the instrumented sweep path must not
    read anything back from the device: the whole dispatch loop runs under
    ``jax.transfer_guard_device_to_host("disallow")``.  (Host-to-device
    movement of tiny dispatch scalars predates the obs layer and is
    async; a device-to-host read is what would stall the pipeline.)"""
    eng, st = _warm_engine()
    assert not get_recorder().enabled
    with jax.transfer_guard_device_to_host("disallow"):
        for _ in range(3):
            st = eng.sweep(st)
    jax.block_until_ready(st.x)


def test_active_recorder_spans_add_no_host_syncs():
    """An active Recorder's spans are host-side timers only — the guarded
    loop (span + sweep dispatch) still performs zero device reads."""
    eng, st = _warm_engine()
    rec = Recorder()
    labels = rec.register_engine(eng, workload="hetero-pairs-24", chains=4)
    with using(rec):
        with jax.transfer_guard_device_to_host("disallow"):
            with rec.span("sweep_chunk", **labels):
                for _ in range(3):
                    st = eng.sweep(st)
    jax.block_until_ready(st.x)
    assert rec.metrics.value("span_calls_total", span="sweep_chunk") == 1


def test_instrumentation_adds_no_device_ops():
    """The jaxpr of a sweep chunk is identical under the null and active
    recorders: all instrumentation lives host-side."""
    eng = engine_lib.make("gibbs", GRAPH, sweep=4, backend="jnp")
    st = eng.init(jax.random.PRNGKey(0), 2)

    def chunk(s):
        rec = get_recorder()
        with rec.span("sweep_chunk"):
            for _ in range(2):
                s = eng.sweep(s)
        return s

    with using(NullRecorder()):
        null_jaxpr = jax.make_jaxpr(chunk)(st)
    with using(Recorder()):
        live_jaxpr = jax.make_jaxpr(chunk)(st)
    assert len(null_jaxpr.eqns) == len(live_jaxpr.eqns)


SWEEP_SPANS = ("repro.sweep", "repro.sweep.draws", "repro.sweep.kernel")


def test_instrumented_sweep_within_overhead_budget():
    """The spans one sweep call opens (``repro.sweep`` and, on the fused
    Pallas path, its draws and kernel children) cost under 5% of the
    least wall time of one warm sweep call, under the null recorder (no
    profiler running) and under an active Recorder.  The spans are timed
    alone over many iterations: two blocks of whole sweeps differ by less
    than the noise of a shared host."""
    eng, st0 = _warm_engine(sweep=24, chains=8)

    def one_call():
        jax.block_until_ready(eng.sweep(st0).x)

    def spans(rec):
        for _ in range(200):
            with rec.span(SWEEP_SPANS[0]):
                with rec.span(SWEEP_SPANS[1]):
                    pass
                with rec.span(SWEEP_SPANS[2]):
                    pass

    def best_of(fn, n):
        best = float("inf")
        for _ in range(n):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    t_call = best_of(one_call, 20)
    for rec in (NullRecorder(), Recorder()):
        t_spans = best_of(lambda: spans(rec), 7) / 200
        assert t_spans <= 0.05 * t_call, (type(rec).__name__, t_spans,
                                          t_call)


def _host_spans(profile_dir):
    """``(line, name, t0, t1)`` of every ``repro.*`` host event in the
    profiler capture under ``profile_dir``."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host"):
            for k, line in enumerate(plane.lines):
                out += [((plane.name, k), e.name, e.start_ns,
                         e.start_ns + e.duration_ns) for e in line.events
                        if e.name.startswith("repro.")]
    return out


def test_sweep_spans_reach_a_profiler_capture(tmp_path):
    """Two fused-Pallas gibbs sweeps under a profiler capture, with no
    Recorder configured: each call's ``repro.sweep`` span holds its
    ``repro.sweep.draws`` span and then its ``repro.sweep.kernel`` span,
    all on one host line."""
    eng = engine_lib.make("gibbs", GRAPH, sweep=4, backend="pallas")
    st = eng.init(jax.random.PRNGKey(0), 4)
    st = jax.block_until_ready(eng.sweep(st))
    assert not get_recorder().enabled
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            st = eng.sweep(st)
        jax.block_until_ready(st)
    finally:
        jax.profiler.stop_trace()
    spans = sorted(_host_spans(str(tmp_path)), key=lambda e: e[2])
    assert [n for _, n, _, _ in spans] == list(SWEEP_SPANS) * 2
    assert len({line for line, _, _, _ in spans}) == 1
    for k in (0, 3):
        (_, _, s0, s1), (_, _, d0, d1), (_, _, k0, k1) = spans[k:k + 3]
        assert s0 <= d0 < d1 <= k0 < k1 <= s1


def test_jax_compile_counts_count_one_trace_per_fresh_jit():
    """A ``jax.jit`` of a fresh function traces, lowers and compiles once;
    the cached call counts nothing.  An active Recorder exports the
    process's counts at ``snapshot``.  (Primitives only: a ``jnp``
    function is a jit of its own and adds traces of its own.)"""
    x = jnp.arange(4.0)
    f = jax.jit(lambda v: jax.lax.add(jax.lax.mul(v, v), v))
    c0 = jax_compile_counts()
    jax.block_until_ready(f(x))
    c1 = jax_compile_counts()
    jax.block_until_ready(f(x))
    c2 = jax_compile_counts()
    assert c1["traces"] - c0["traces"] == 1
    assert c1["lowerings"] - c0["lowerings"] == 1
    assert c1["compiles"] - c0["compiles"] == 1
    assert c1["traces_s"] > c0["traces_s"]
    assert c2 == c1
    rec = Recorder()
    rec.snapshot()
    for kind in ("traces", "lowerings", "compiles"):
        assert rec.metrics.value(f"jax_{kind}_total") == c2[kind]
        assert rec.metrics.value(f"jax_{kind}_seconds_total") == \
            pytest.approx(c2[kind + "_s"])
    assert "# TYPE repro_jax_traces_total counter" in \
        rec.metrics.to_prometheus()


def test_recorder_span_reaches_a_profiler_capture(tmp_path):
    """An active Recorder's span lands in its own trace and metrics and in
    a running profiler capture."""
    rec = Recorder()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with rec.span("repro.test", step=1):
            jnp.ones(3).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    assert [n for _, n, _, _ in _host_spans(str(tmp_path))] == \
        ["repro.test"]
    assert rec.metrics.value("span_calls_total", span="repro.test") == 1
    assert [e["name"] for e in rec.trace.events() if e["ph"] == "X"] == \
        ["repro.test"]


# -- supervised runtime golden files -----------------------------------------

def _supervised_with_recorder(tmp_path, plan=None):
    def make_engine(name, devices, **params):
        return engine_lib.make(name, GRAPH, sweep=4, backend="jnp",
                               **params)

    cfg = SupervisorConfig(outer_steps=6, sweeps_per_outer=4, chains=8,
                           seed=0, ckpt_dir=str(tmp_path / "ckpt"),
                           backoff_base=0.0, workload="hetero-pairs-24")
    rec = Recorder(metrics_dir=str(tmp_path / "metrics"),
                   trace_path=str(tmp_path / "trace.json"))
    with using(rec):
        run = SupervisedRun("mgpmh", make_engine, cfg, plan,
                            sleep_fn=lambda s: None)
        res = run.run()
        rec.close()
    return res, rec, tmp_path


REQUIRED_LABELS = ("engine", "backend", "schedule", "workload")


def test_supervised_trace_and_metrics_golden(tmp_path):
    plan = FaultPlan([Fault(step=2, kind="nan", target="x")])
    res, rec, root = _supervised_with_recorder(tmp_path, plan)
    assert res.rollbacks >= 1

    doc = json.loads((root / "trace.json").read_text())
    evs = doc["traceEvents"]
    assert evs[0]["ph"] == "M"          # Perfetto process_name metadata
    names = {}
    for e in evs[1:]:
        names.setdefault(e["name"], []).append(e)
    assert "sweep_chunk" in names and "checkpoint/save" in names
    assert "rollback_recover" in names
    assert "health" in names and "fault" in names
    for e in names["sweep_chunk"]:
        assert e["ph"] == "X" and e["dur"] >= 0
        for k in REQUIRED_LABELS:
            assert k in e["args"], (k, e)
        assert e["args"]["engine"] == "mgpmh"
        assert e["args"]["workload"] == "hetero-pairs-24"

    prom = (root / "metrics" / "metrics.prom").read_text()
    for series in ("repro_acceptance", "repro_sweeps_total",
                   "repro_updates_total", "repro_rollbacks_total",
                   "repro_heartbeat_step", "repro_psum_payload_bytes",
                   "repro_checkpoint_saves_total",
                   "repro_checkpoint_bytes_total", "repro_events_total"):
        assert series in prom, series
    acc = [l for l in prom.splitlines()
           if l.startswith("repro_acceptance{")]
    assert acc
    for k in REQUIRED_LABELS:
        assert f'{k}="' in acc[0]

    lines = (root / "metrics" / "metrics.jsonl").read_text().splitlines()
    assert lines
    snap = json.loads(lines[-1])
    assert {s["name"] for s in snap["series"]} >= {"sweeps_total",
                                                   "rollbacks_total"}


def test_events_jsonl_is_the_incident_stream(tmp_path):
    """The unified events.jsonl carries the supervisor's full incident
    stream (the legacy incidents.jsonl shim is gone — nothing writes it)."""
    plan = FaultPlan([Fault(step=2, kind="nan", target="x")])
    res, rec, root = _supervised_with_recorder(tmp_path, plan)
    ev_kinds = [json.loads(l)["kind"] for l in
                (root / "metrics" / "events.jsonl").read_text().splitlines()]
    assert not (root / "ckpt" / "incidents.jsonl").exists()
    assert ev_kinds == [i["kind"] for i in res.incidents]
    assert "fault" in ev_kinds and "health" in ev_kinds
    assert ev_kinds.count("health") == len(
        [i for i in res.incidents if i["kind"] == "health"])


# -- checkpoint metrics ------------------------------------------------------

def test_checkpoint_save_restore_emit_spans_and_counters(tmp_path):
    from repro.checkpoint import checkpoint as ckpt

    tree = {"x": jnp.arange(12, dtype=jnp.int32).reshape(3, 4),
            "k": jax.random.PRNGKey(0)}
    rec = Recorder(trace_path=str(tmp_path / "trace.json"))
    with using(rec):
        ckpt.save(str(tmp_path / "c"), 1, tree)
        assert ckpt.verify(str(tmp_path / "c"), 1) == []
        out = ckpt.restore(str(tmp_path / "c"), 1, tree)
    assert np.array_equal(np.asarray(out["x"]), np.asarray(tree["x"]))
    assert rec.metrics.value("checkpoint_saves_total") == 1
    nbytes = rec.metrics.value("checkpoint_bytes_total")
    assert nbytes >= sum(np.asarray(v).nbytes for v in tree.values())
    spans = {e.get("name") for e in rec.trace.events()}
    assert {"checkpoint/save", "checkpoint/verify",
            "checkpoint/restore"} <= spans


# -- serving metrics ---------------------------------------------------------

def test_serving_emits_query_spans_and_freshness_metrics(tmp_path):
    from repro.diagnostics.freshness import FreshnessPolicy
    from repro.launch.serve import serve_batch
    from repro.serving import Query

    rec = Recorder(metrics_dir=str(tmp_path / "m"),
                   trace_path=str(tmp_path / "trace.json"))
    queries = [Query("hetero-pairs-24"),
               Query("hetero-pairs-24", evidence=((0, 1),)),
               Query("hetero-pairs-24")]
    with using(rec):
        res = serve_batch(
            "hetero-pairs-24", queries, engine="gibbs", backend="jnp",
            chains=8, sweep=12, chunk=4, max_extra_sweeps=200,
            policy=FreshnessPolicy(max_rhat=10.0, min_ess_per_site=1.0,
                                   min_samples=2))
    assert res["n_queries"] == 3
    labels = dict(engine="gibbs", backend="jnp",
                  schedule=res["engine"]["schedule"],
                  workload="hetero-pairs-24")
    assert rec.metrics.value("queries_total", fresh=True, **labels) >= 1
    assert rec.metrics.value("pool_lanes", **labels) == 2
    assert rec.metrics.value("sweeps_to_fresh_count", **labels) >= 1
    assert rec.metrics.value("sweeps_total", **labels) > 0
    names = {e.get("name") for e in rec.trace.events()}
    assert {"query", "queue_wait", "freshness_sweeps",
            "lane_fork"} <= names
    prom = (tmp_path / "m" / "metrics.prom").read_text()
    assert "repro_queries_total" in prom
    assert "repro_sweeps_to_fresh_total" in prom
