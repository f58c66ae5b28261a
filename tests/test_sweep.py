"""Fused multi-site sweep engine validation.

Five layers:
  * kernel parity — the fused Pallas sweep kernels (interpret mode on CPU)
    must make bit-identical decisions to their jnp oracles when fed the
    same pre-drawn uniforms, across padded/unaligned (C, S, K, D, n)
    shapes — for all four kernels (gibbs, mgpmh, min-gibbs, doublemin);
  * distributional agreement — `make_*_sweep` chains (both impls route
    through exact single-site updates) must converge to the exact
    marginals of enumerable graphs, like the single-site reference;
  * memory regression — the jnp min-gibbs/doublemin sweeps draw their
    minibatch streams inside the scan body, so peak temp bytes (XLA
    memory_analysis) must not scale with sweep length S;
  * draws — the fused-Pallas builders' draws are one jitted program: warm
    calls trace nothing, and under evidence the streams are the ones the
    benchmark's oracles replay;
  * integration — `run_marginal_experiment` consumes batched sweeps.  (The
    distributed sweeps — one psum per sweep for all four algorithms — are
    validated against exact marginals in tests/test_distributed.py.)
"""
import contextlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (engine, make_potts_graph, init_chains, init_state,
                        run_marginal_experiment, ChainState)
from repro.core.factor_graph import build_alias_table
from repro.kernels.ops import (mgpmh_sweep, gibbs_sweep, min_gibbs_sweep,
                               double_min_sweep)


# ---------------------------------------------------------------------------
# kernel parity vs the jnp oracle
# ---------------------------------------------------------------------------

def _random_graph_arrays(rng, n):
    A = rng.uniform(0.1, 1.0, (n, n))
    A = (A + A.T) / 2
    np.fill_diagonal(A, 0)
    rp = np.zeros((n, n), np.float32)
    ra = np.zeros((n, n), np.int32)
    for i in range(n):
        rp[i], ra[i] = build_alias_table(A[i])
    return jnp.asarray(A, jnp.float32), jnp.asarray(rp), jnp.asarray(ra)


def _random_node_table(rng, n):
    A = rng.uniform(0.1, 1.0, (n, n))
    A = (A + A.T) / 2
    np.fill_diagonal(A, 0)
    prob, alias = build_alias_table(A.sum(1))
    return jnp.asarray(prob), jnp.asarray(alias)


@pytest.mark.parametrize("C,S,K,D,n", [
    (4, 5, 17, 3, 11),      # everything unaligned
    (8, 8, 128, 10, 40),    # aligned K
    (3, 1, 1, 2, 5),        # degenerate sweep
    (5, 12, 33, 6, 20),
    (2, 3, 9, 129, 7),      # D above one lane tile
])
def test_mgpmh_sweep_kernel_parity(C, S, K, D, n):
    rng = np.random.default_rng(C * 100 + S * 10 + K + D + n)
    W, rp, ra = _random_graph_arrays(rng, n)
    x = jnp.asarray(rng.integers(0, D, (C, n)), jnp.int32)
    i_sites = jnp.asarray(rng.integers(0, n, (C, S)), jnp.int32)
    B = jnp.asarray(rng.integers(0, K + 1, (C, S)), jnp.int32)
    u1 = jnp.asarray(rng.uniform(size=(C, S, K)), jnp.float32)
    u2 = jnp.asarray(rng.uniform(size=(C, S, K)), jnp.float32)
    g = jnp.asarray(rng.gumbel(size=(C, S, D)), jnp.float32)
    lu = jnp.asarray(np.log(rng.uniform(size=(C, S))), jnp.float32)
    args = (x, W, rp, ra, i_sites, B, u1, u2, g, lu)
    xr, ar = mgpmh_sweep(*args, D=D, scale=0.7, impl="jnp")
    xp, ap = mgpmh_sweep(*args, D=D, scale=0.7, impl="pallas")
    np.testing.assert_array_equal(np.asarray(xr), np.asarray(xp))
    np.testing.assert_array_equal(np.asarray(ar), np.asarray(ap))


@pytest.mark.parametrize("C,S,D,n", [
    (4, 5, 3, 11), (8, 8, 10, 40), (3, 1, 2, 5),
])
def test_gibbs_sweep_kernel_parity(C, S, D, n):
    rng = np.random.default_rng(C + S + D + n)
    W, _, _ = _random_graph_arrays(rng, n)
    x = jnp.asarray(rng.integers(0, D, (C, n)), jnp.int32)
    i_sites = jnp.asarray(rng.integers(0, n, (C, S)), jnp.int32)
    g = jnp.asarray(rng.gumbel(size=(C, S, D)), jnp.float32)
    xr = gibbs_sweep(x, W, i_sites, g, D=D, impl="jnp")
    xp = gibbs_sweep(x, W, i_sites, g, D=D, impl="pallas")
    np.testing.assert_array_equal(np.asarray(xr), np.asarray(xp))


@pytest.mark.parametrize("C,S,K,D,n", [
    (4, 5, 17, 3, 11),      # everything unaligned
    (3, 1, 1, 2, 5),        # degenerate sweep
    (5, 7, 33, 4, 20),
])
def test_min_gibbs_sweep_kernel_parity(C, S, K, D, n):
    """The fused MIN-Gibbs kernel (interpret mode) is bit-identical to the
    jnp oracle on the host-rng path: same states AND same cached eps."""
    rng = np.random.default_rng(C * 100 + S * 10 + K + D + n)
    _, rp, ra = _random_graph_arrays(rng, n)
    npb, nab = _random_node_table(rng, n)
    x = jnp.asarray(rng.integers(0, D, (C, n)), jnp.int32)
    i_sites = jnp.asarray(rng.integers(0, n, (C, S)), jnp.int32)
    B = jnp.asarray(rng.integers(0, K + 1, (C, S, D)), jnp.int32)
    u4 = [jnp.asarray(rng.uniform(size=(C, S, D, K)), jnp.float32)
          for _ in range(4)]
    g = jnp.asarray(rng.gumbel(size=(C, S, D)), jnp.float32)
    cache = jnp.asarray(rng.uniform(0, 3, (C,)), jnp.float32)
    args = (x, npb, nab, rp, ra, i_sites, B, *u4, g, cache)
    xr, cr = min_gibbs_sweep(*args, D=D, lscale=0.37, impl="jnp")
    xp, cp = min_gibbs_sweep(*args, D=D, lscale=0.37, impl="pallas")
    np.testing.assert_array_equal(np.asarray(xr), np.asarray(xp))
    np.testing.assert_array_equal(np.asarray(cr), np.asarray(cp))


@pytest.mark.parametrize("C,S,K1,K2,D,n", [
    (4, 5, 17, 9, 3, 11),   # everything unaligned
    (3, 1, 1, 1, 2, 5),     # degenerate sweep
    (5, 7, 33, 21, 4, 20),
])
def test_double_min_sweep_kernel_parity(C, S, K1, K2, D, n):
    """The fused DoubleMIN kernel (interpret mode) is bit-identical to the
    jnp oracle: same states, cached xi, and acceptance counts."""
    rng = np.random.default_rng(C * 100 + S * 10 + K1 + K2 + D + n)
    _, rp, ra = _random_graph_arrays(rng, n)
    npb, nab = _random_node_table(rng, n)
    x = jnp.asarray(rng.integers(0, D, (C, n)), jnp.int32)
    i_sites = jnp.asarray(rng.integers(0, n, (C, S)), jnp.int32)
    B1 = jnp.asarray(rng.integers(0, K1 + 1, (C, S)), jnp.int32)
    u1 = jnp.asarray(rng.uniform(size=(C, S, K1)), jnp.float32)
    u2 = jnp.asarray(rng.uniform(size=(C, S, K1)), jnp.float32)
    g = jnp.asarray(rng.gumbel(size=(C, S, D)), jnp.float32)
    B2 = jnp.asarray(rng.integers(0, K2 + 1, (C, S)), jnp.int32)
    v4 = [jnp.asarray(rng.uniform(size=(C, S, K2)), jnp.float32)
          for _ in range(4)]
    lu = jnp.asarray(np.log(rng.uniform(size=(C, S))), jnp.float32)
    cache = jnp.asarray(rng.uniform(0, 3, (C,)), jnp.float32)
    args = (x, rp, ra, npb, nab, i_sites, B1, u1, u2, g, B2, *v4, lu, cache)
    xr, cr, ar = double_min_sweep(*args, D=D, scale1=0.7, lscale2=0.31,
                                  impl="jnp")
    xp, cp, ap = double_min_sweep(*args, D=D, scale1=0.7, lscale2=0.31,
                                  impl="pallas")
    np.testing.assert_array_equal(np.asarray(xr), np.asarray(xp))
    np.testing.assert_array_equal(np.asarray(cr), np.asarray(cp))
    np.testing.assert_array_equal(np.asarray(ar), np.asarray(ap))


# ---------------------------------------------------------------------------
# distributional agreement on enumerable graphs
# ---------------------------------------------------------------------------

from _helpers import exact_marginals as _exact_marginals
from _helpers import empirical_sweep_marginals


def _empirical_sweep_marginals(sweep, g, n_sweeps, n_chains=16, seed=0):
    st = init_chains(jax.random.PRNGKey(seed), g, n_chains,
                     lambda k, gg: init_state(k, gg, start="random"))
    return empirical_sweep_marginals(sweep, g, st, n_sweeps)


def test_gibbs_sweep_marginals():
    g = make_potts_graph(grid=2, beta=0.8, D=3)
    sweep = engine.make("gibbs", g, sweep=8, backend="jnp").sweep_fn
    emp = _empirical_sweep_marginals(sweep, g, 8000)
    assert np.abs(emp - _exact_marginals(g)).max() < 0.03


def test_mgpmh_sweep_marginals():
    """Distributional agreement of the sweep chain with the exact pi on the
    small Potts validator (i.e. with the single-site reference, which is
    validated against the same exact marginals in test_samplers.py)."""
    g = make_potts_graph(grid=2, beta=0.8, D=3)
    lam = float(4 * g.L ** 2)
    cap = int(lam + 6 * lam ** 0.5 + 16)
    sweep = engine.make("mgpmh", g, sweep=8, backend="jnp", lam=lam,
                        capacity=cap).sweep_fn
    emp = _empirical_sweep_marginals(sweep, g, 8000)
    assert np.abs(emp - _exact_marginals(g)).max() < 0.03


def test_mgpmh_sweep_kernel_impl_marginals():
    """The Pallas-kernel impl (interpret mode) is also a correct chain —
    short run, loose tolerance (the interpreter is slow)."""
    g = make_potts_graph(grid=2, beta=0.8, D=3)
    lam = float(4 * g.L ** 2)
    cap = int(lam + 6 * lam ** 0.5 + 16)
    sweep = engine.make("mgpmh", g, sweep=8, backend="pallas", lam=lam,
                        capacity=cap).sweep_fn
    emp = _empirical_sweep_marginals(sweep, g, 600, n_chains=32)
    assert np.abs(emp - _exact_marginals(g)).max() < 0.08


def test_min_gibbs_pallas_engine_marginals():
    """The Pallas-backed MIN-Gibbs engine (interpret mode) is a correct
    chain — short run, loose tolerance (the interpreter is slow)."""
    g = make_potts_graph(grid=2, beta=0.8, D=3)
    eng = engine.make("min-gibbs", g, sweep=8, backend="pallas",
                      lam=float(2 * g.psi ** 2))
    emp = _empirical_sweep_marginals(eng.sweep_fn, g, 500, n_chains=32)
    assert np.abs(emp - _exact_marginals(g)).max() < 0.08


def test_double_min_pallas_engine_marginals():
    g = make_potts_graph(grid=2, beta=0.8, D=3)
    eng = engine.make("doublemin", g, sweep=8, backend="pallas")
    emp = _empirical_sweep_marginals(eng.sweep_fn, g, 500, n_chains=32)
    assert np.abs(emp - _exact_marginals(g)).max() < 0.08


# ---------------------------------------------------------------------------
# memory regression: chunked jnp draw streams
# ---------------------------------------------------------------------------

def _sweep_temp_bytes(eng, n_chains=8):
    st = eng.init(jax.random.PRNGKey(0), n_chains)
    compiled = jax.jit(eng.sweep_fn).lower(st).compile()
    return int(compiled.memory_analysis().temp_size_in_bytes)


@pytest.mark.parametrize("name,params", [
    ("min-gibbs", dict(lam=64.0, capacity=96)),
    ("doublemin", dict(lam2=64.0, capacity2=96)),
])
def test_jnp_sweep_peak_memory_independent_of_sweep_len(name, params):
    """The jnp min-gibbs/doublemin sweeps generate their O(lam)-sized draw
    buffers inside the scan body, so XLA's peak temp allocation must not
    scale with S (pre-chunking it was ~8x from S=4 to S=32; the remaining
    growth is the lam-free O(C*S*D) gumbel/Poisson streams)."""
    g = make_potts_graph(grid=2, beta=0.8, D=3)
    temp = {S: _sweep_temp_bytes(
        engine.make(name, g, sweep=S, backend="jnp", **params))
        for S in (4, 32)}
    assert temp[32] < 2.0 * temp[4], temp


# ---------------------------------------------------------------------------
# the fused-Pallas builders' draws: one jitted program, the streams kept
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def _traced_functions():
    """Names of the functions JAX traces inside the block (nested jitted
    helpers included, as ``jax_compile_counts()["traces"]`` counts them)."""
    names = []

    def listen(event, duration_secs, **kw):
        if event == "/jax/core/compile/jaxpr_trace_duration":
            names.append(kw.get("fun_name", ""))
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield names
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


@pytest.mark.parametrize("evidence", [False, True])
@pytest.mark.parametrize("name", ["gibbs", "mgpmh", "min-gibbs",
                                  "doublemin"])
def test_pallas_sweep_retraces_nothing_when_warm(name, evidence):
    """Warm fused-Pallas sweeps trace nothing: the draws are one jitted
    program built with the engine.  The first call with ``evidence=``
    traces that program once more (with the jnp helpers inside it) and
    the kernel's not at all; later ones trace nothing."""
    from repro.obs import jax_compile_counts
    g = make_potts_graph(grid=2, beta=0.8, D=3)
    eng = engine.make(name, g, sweep=4, backend="pallas")
    st = eng.init(jax.random.PRNGKey(0), 4)
    ev = (jnp.zeros((g.n,), jnp.float32).at[1].set(1.0),
          jnp.zeros((g.n,), jnp.int32))
    kw = {"evidence": ev} if evidence else {}
    if evidence:
        st = eng.clamp(jax.random.PRNGKey(1), st, ev)
    for _ in range(2):
        st = eng.sweep(st)
    if evidence:
        with _traced_functions() as names:
            st = eng.sweep(st, **kw)
        programs = [f for f in names if f.endswith(("_draws", "_sweep"))]
        assert len(programs) == 1 and programs[0].endswith("_draws"), names
    before = jax_compile_counts()["traces"]
    for _ in range(5):
        st = eng.sweep(st, **kw)
    jax.block_until_ready(st)
    assert jax_compile_counts()["traces"] == before


@pytest.mark.parametrize("name", ["gibbs", "mgpmh"])
def test_pallas_sweep_with_evidence_replays_oracle(name):
    """The pallas gibbs and mgpmh sweeps under evidence draw the streams
    the benchmark's oracle replays (``bench/oracle/<engine>_pallas.py``):
    sites, accept counts and keys equal bit for bit, call after call."""
    from bench import check
    cfg = {"reference": {"family": "potts", "grid": 6, "beta": 4.6,
                         "D": 10, "gamma": 1.5}}
    g = make_potts_graph(grid=6, beta=4.6, D=10, gamma=1.5)
    eng = engine.make(name, g, sweep=4, backend="pallas")
    call = jax.jit(check.oracle({"engine": name, "backend": "pallas",
                                 "sweep": 4}, cfg, dict(eng.params),
                                "highest"))
    observed = jnp.array([0, 7, 20, 35])
    ev = (jnp.zeros((g.n,), jnp.float32).at[observed].set(1.0),
          jnp.zeros((g.n,), jnp.int32).at[observed].set(
              jnp.array([3, 9, 0, 5])))
    st = eng.clamp(jax.random.PRNGKey(1), eng.init(
        jax.random.PRNGKey(7), 8, start="random"), ev)
    for _ in range(3):
        want = call({"x": st.x, "key": st.key, "accepts": st.accepts}, ev)
        st = eng.sweep(st, evidence=ev)
        for field in ("x", "key", "accepts"):
            np.testing.assert_array_equal(np.asarray(getattr(st, field)),
                                          np.asarray(want[field]))
    assert np.all(np.asarray(st.x)[:, observed] == [3, 9, 0, 5])


# ---------------------------------------------------------------------------
# integration
# ---------------------------------------------------------------------------

def test_run_marginal_experiment_with_sweep():
    """The runner consumes sweep engines; iters counts site updates and
    the error trajectory decreases."""
    g = make_potts_graph(grid=4, beta=1.0, D=4)
    lam = float(4 * g.L ** 2)
    cap = int(lam + 6 * lam ** 0.5 + 16)
    eng = engine.make("mgpmh", g, sweep=16, backend="jnp", lam=lam,
                      capacity=cap)
    st = init_chains(jax.random.PRNGKey(0), g, 4, init_state)
    tr = run_marginal_experiment(eng, st, n_iters=8000, n_snapshots=4, D=4)
    iters = np.asarray(tr.iters)
    assert iters[-1] == 8000 and iters[0] == 2000  # site updates, not calls
    err = np.asarray(tr.error)
    assert err[-1] < err[0]
    assert isinstance(tr.final, ChainState)


def test_dist_sweep_template_shares_substeps():
    """The distributed sweep template consumes the same per-algorithm
    substep primitives as the jnp sweeps (one source of truth for the
    selection/acceptance rules) and reports its collective footprint."""
    from repro.core.samplers import gibbs_select, min_gibbs_select, mh_accept
    from repro.runtime import dist_gibbs as DG
    assert DG.gibbs_select is gibbs_select
    assert DG.min_gibbs_select is min_gibbs_select
    assert DG.mh_accept is mh_accept
    assert set(DG.DIST_ALGOS) == {"gibbs", "mgpmh", "min-gibbs", "doublemin"}
    for algo in DG.DIST_ALGOS:
        fp = DG.psum_footprint(algo, C=8, S=4, D=3)
        assert fp["collectives_per_sweep"] == 1
        assert fp["psum_payload_bytes"] > 0
    fp = DG.psum_footprint("chromatic", C=8, S=4, D=3, n=16, n_colors=2)
    assert fp["collectives_per_sweep"] == 2
