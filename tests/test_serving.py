"""Marginal-inference serving subsystem (serving/ + the evidence paths).

Four layers:
  * exact conditional references — `exact_conditional_marginals` agrees
    with whole-graph enumeration on small graphs, with the analytic pair
    formula on the registered pair workload, and validates its inputs;
  * engine evidence clamping — every gibbs-family engine keeps observed
    sites clamped through its sweep, clamped and unclamped evidence share
    ONE jit trace, and non-supporting engines refuse;
  * pool correctness — clamped answers match exact conditionals on
    `hetero-pairs-24` (gibbs + mgpmh, jnp), the freshness gate refuses
    before its thresholds and serves after, serving does not perturb the
    resident chain (bit-exact vs an unserved control pool), the chunk
    compiles exactly once across clamped + unclamped traffic;
  * lane management — conditioned lanes are keyed by normalized evidence,
    LRU-evicted, and reject invalid evidence.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import engine
from repro.core.factor_graph import make_pair_ising
from repro.diagnostics import (FreshnessPolicy, freshness_report,
                               exact_marginals, exact_conditional_marginals)
from repro.serving import ChainPool, Query

WL = "hetero-pairs-24"
POLICY = FreshnessPolicy(max_rhat=1.2, min_ess_per_site=16.0, min_samples=8)


def _graph():
    return engine.make_workload(WL).graph


# ---------------------------------------------------------------------------
# exact conditional marginals
# ---------------------------------------------------------------------------

def test_exact_conditional_matches_full_enumeration():
    g = make_pair_ising(1, 2, 3.5, 0.25)        # 6 sites: enumerable whole
    assert np.allclose(exact_conditional_marginals(g, [], []),
                       exact_marginals(g), atol=1e-12)


def test_exact_conditional_pair_formula():
    g = _graph()                                 # 2^24 whole-graph states
    m = exact_conditional_marginals(g, [0], [1])
    p = np.exp(3.5) / (np.exp(3.5) + 1.0)        # p(x1 = x0 | x0), w = 3.5
    assert m[0].tolist() == [0.0, 1.0]           # observed: delta
    assert m[1, 1] == pytest.approx(p, abs=1e-12)
    assert m[5, 0] == pytest.approx(0.5, abs=1e-12)   # other pairs untouched


def test_exact_conditional_validates():
    g = _graph()
    with pytest.raises(ValueError, match="duplicate"):
        exact_conditional_marginals(g, [0, 0], [1, 1])
    with pytest.raises(ValueError, match="sites out of range"):
        exact_conditional_marginals(g, [g.n], [0])
    with pytest.raises(ValueError, match="values out of range"):
        exact_conditional_marginals(g, [0], [g.D])
    with pytest.raises(ValueError, match="exceed"):
        exact_conditional_marginals(g, [], [], max_states=2)


# ---------------------------------------------------------------------------
# engine-level evidence clamping
# ---------------------------------------------------------------------------

def _evidence(g, site=0, val=1):
    mask = np.zeros(g.n, np.float32)
    vals = np.zeros(g.n, np.int32)
    mask[site] = 1.0
    vals[site] = val
    return jnp.asarray(mask), jnp.asarray(vals)


@pytest.mark.parametrize("name", ["gibbs", "mgpmh", "min-gibbs", "doublemin"])
def test_engine_evidence_clamps_one_trace(name):
    g = _graph()
    eng = engine.make(name, g, sweep=8, backend="jnp")
    assert eng.supports_evidence
    ev = _evidence(g)
    zero = (jnp.zeros(g.n, jnp.float32), jnp.zeros(g.n, jnp.int32))
    st = eng.clamp(jax.random.PRNGKey(1),
                   eng.init(jax.random.PRNGKey(0), 4), ev)
    f = jax.jit(lambda s, m, v: eng.sweep(s, evidence=(m, v)))
    for _ in range(3):
        st = f(st, *ev)
    assert np.all(np.asarray(st.x)[:, 0] == 1)   # observed site never moves
    f(st, *zero)                                 # unclamped: same trace
    assert f._cache_size() == 1


@pytest.mark.parametrize("schedule", ["chromatic", "adaptive"])
def test_engine_evidence_other_schedules(schedule):
    wl = engine.make_workload(WL)
    g = wl.graph
    sched = (engine.ChromaticBlocks(wl.colors) if schedule == "chromatic"
             else engine.AdaptiveScan(24))
    eng = engine.make("gibbs", g, schedule=sched, backend="jnp")
    ev = _evidence(g)
    st = eng.clamp(jax.random.PRNGKey(1),
                   eng.init(jax.random.PRNGKey(0), 4), ev)
    f = jax.jit(lambda s, m, v: eng.sweep(s, evidence=(m, v)))
    for _ in range(3):
        st = f(st, *ev)
    assert np.all(np.asarray(st.x)[:, 0] == 1)
    f(st, (jnp.zeros(g.n, jnp.float32), jnp.zeros(g.n, jnp.int32))[0],
      jnp.zeros(g.n, jnp.int32))
    assert f._cache_size() == 1


def test_unsupported_engine_refuses_evidence():
    g = _graph()
    eng = engine.make("local-gibbs", g, sweep=8, backend="jnp")
    st = eng.init(jax.random.PRNGKey(0), 2)
    with pytest.raises(ValueError, match="does not support evidence"):
        eng.sweep(st, evidence=_evidence(g))


# ---------------------------------------------------------------------------
# pool: clamped answers vs exact conditionals (the acceptance criterion)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["gibbs", "mgpmh"])
def test_clamped_marginals_match_exact_conditionals(name):
    g = _graph()
    pool = ChainPool(policy=POLICY, seed=0)
    pool.register(WL, engine=name, backend="jnp", chains=32, sweep=24,
                  sweeps_per_chunk=16)
    q = Query(WL, evidence=((0, 1),))
    ans = pool.submit([q], max_extra_sweeps=30_000)[0]
    assert ans.fresh, ans.report
    exact = exact_conditional_marginals(g, [0], [1])
    m = ans.marginals
    assert m[0].tolist() == [0.0, 1.0]           # observed: exact delta
    # the clamped partner's conditional is served from iid-ish draws at
    # p ~ 0.97; the loosest sites are the slow-mixing unclamped strong
    # pairs, so the per-site bound is loose and the mean bound tight
    assert abs(m[1, 1] - exact[1, 1]) < 0.05, (m[1], exact[1])
    tv = 0.5 * np.abs(m - exact).sum(-1)
    assert tv.mean() < 0.06, tv.mean()
    assert tv.max() < 0.25, tv.max()
    assert pool.compiled_cache_size(WL) == 1


def test_no_recompile_between_clamped_and_unclamped():
    pool = ChainPool(policy=POLICY, seed=0)
    pool.register(WL, engine="gibbs", backend="jnp", chains=8, sweep=24,
                  sweeps_per_chunk=4)
    pool.submit([Query(WL), Query(WL, evidence=((0, 1),)),
                 Query(WL, evidence=((2, 0), (5, 1)))],
                max_extra_sweeps=30_000)
    assert pool.compiled_cache_size(WL) == 1


# ---------------------------------------------------------------------------
# freshness gating
# ---------------------------------------------------------------------------

def test_freshness_gate_refuses_then_serves():
    pool = ChainPool(policy=POLICY, seed=0)
    pool.register(WL, engine="gibbs", backend="jnp", chains=16, sweep=24,
                  sweeps_per_chunk=8)
    q = Query(WL)
    cold = pool.submit([q], max_extra_sweeps=0)[0]
    assert not cold.fresh
    # a cold lane no longer refuses outright: the degradation ladder falls
    # through to exact conditional enumeration (tractable on this workload)
    assert cold.status == "ok" and cold.source == "exact"
    exact = exact_conditional_marginals(
        engine.make_workload(WL).graph, [], [])
    np.testing.assert_allclose(cold.marginals, exact, atol=1e-12)
    assert cold.report["reason"]
    warm = pool.submit([q], max_extra_sweeps=30_000)[0]
    assert warm.fresh
    assert warm.report["max_rhat"] <= POLICY.max_rhat
    assert warm.report["min_ess"] >= POLICY.min_ess_per_site
    assert warm.marginals.shape == (24, 2)
    # serve_stale returns the estimate but keeps the honest verdict
    q2 = Query(WL, evidence=((3, 0),))
    stale = pool.submit([q2], max_extra_sweeps=0, serve_stale=True)[0]
    assert not stale.fresh and stale.marginals is not None


def test_freshness_report_masks_observed_sites():
    g = _graph()
    eng = engine.make("gibbs", g, sweep=24, backend="jnp")
    ev = _evidence(g)
    st = eng.clamp(jax.random.PRNGKey(1),
                   eng.init(jax.random.PRNGKey(0), 16), ev)
    tel = eng.init_telemetry(st)
    for _ in range(60):
        st, tel = eng.sweep(st, tel, evidence=ev)
    # unmasked: the frozen observed site has ESS 0 -> never fresh
    assert not freshness_report(tel, POLICY)["fresh"]
    mask = np.asarray(ev[0]) == 0.0
    assert freshness_report(tel, POLICY, site_mask=mask)["fresh"]


# ---------------------------------------------------------------------------
# non-perturbation: serving must not touch the resident chain
# ---------------------------------------------------------------------------

def test_pool_snapshot_reads_bit_exact_vs_unserved_control():
    kw = dict(engine="gibbs", backend="jnp", chains=16, sweep=24,
              sweeps_per_chunk=8)
    served = ChainPool(policy=POLICY, seed=0)
    served.register(WL, **kw)
    control = ChainPool(policy=POLICY, seed=0)
    control.register(WL, **kw)
    # interleave resident advances with serving traffic (snapshot reads +
    # conditioned-lane forks) on one pool, advance the other untouched
    for _ in range(3):
        served.advance(WL, chunks=2)
        served.submit([Query(WL), Query(WL, evidence=((0, 1),))],
                      max_extra_sweeps=0, serve_stale=True)
        served.snapshot(WL)
    chunks = served.workload(WL).resident.sweeps // 8
    control.advance(WL, chunks=chunks)
    a, b = served.snapshot(WL), control.snapshot(WL)
    assert np.array_equal(np.asarray(a.st.x), np.asarray(b.st.x))
    assert np.array_equal(np.asarray(a.st.key), np.asarray(b.st.key))
    assert np.array_equal(np.asarray(a.marg), np.asarray(b.marg))


# ---------------------------------------------------------------------------
# lanes + queries
# ---------------------------------------------------------------------------

def test_query_normalizes_evidence():
    a = Query(WL, evidence=((5, 1), (0, 1)))
    b = Query(WL, evidence=((0, 1), (5, 1)))
    assert a.signature == b.signature == ((0, 1), (5, 1))
    with pytest.raises(ValueError, match="duplicate"):
        Query(WL, evidence=((0, 1), (0, 0)))
    with pytest.raises(ValueError, match="kind"):
        Query(WL, kind="mean")


def test_pool_lane_lru_and_validation():
    pool = ChainPool(policy=POLICY, seed=0)
    w = pool.register(WL, engine="gibbs", backend="jnp", chains=4, sweep=8,
                      sweeps_per_chunk=2, max_conditioned=2)
    for s in range(3):
        pool.submit([Query(WL, evidence=((s, 1),))], max_extra_sweeps=0,
                    serve_stale=True)
    assert len(w.lanes) == 2                      # oldest lane evicted
    assert ((0, 1),) not in w.lanes
    with pytest.raises(ValueError, match="sites out of range"):
        pool.submit([Query(WL, evidence=((99, 0),))])
    with pytest.raises(ValueError, match="values out of range"):
        pool.submit([Query(WL, evidence=((0, 9),))])
    with pytest.raises(ValueError, match="every site"):
        pool.submit([Query(WL, evidence=tuple((s, 0)
                                              for s in range(24)))])
    with pytest.raises(ValueError, match="cannot serve"):
        pool.register("potts-20x20", engine="local-gibbs", backend="jnp")


def test_serve_main_exits_nonzero_on_error_answers(monkeypatch, capsys):
    """The pool turns a failing sweep into ``status='error'`` answers; the
    serve launcher then exits non-zero instead of reporting success."""
    import sys
    from repro.launch import serve

    def fail(self, *args, **kwargs):
        raise RuntimeError("sweep failed")

    monkeypatch.setattr(ChainPool, "_serve_group", fail)
    monkeypatch.setattr(serve, "use_compile_cache", lambda: "")
    monkeypatch.setattr(sys, "argv", [
        "serve", "--workload", "hetero-pairs-24", "--chains", "4",
        "--demo", "2"])
    with pytest.raises(SystemExit) as exc:
        serve.main()
    assert exc.value.code != 0
    assert "2 of 2 answers failed with status 'error'" in str(exc.value.code)
    assert "statuses={'error': 2}" in capsys.readouterr().out
