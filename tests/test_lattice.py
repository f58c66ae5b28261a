"""The neighbour-list layout (``NeighborGraph``) and its parallel chromatic
Gibbs sweep (``engine.make("gibbs", g, schedule="chromatic")``):

  * the build — equal to the dense lattice's edges and weights, 4
    neighbours a site when periodic, a bad colouring refused, the build
    span and table gauge recorded;
  * the sweep — equal bit for bit to the plain reference of its draw
    contract (``tests/_lattice_ref.py``) on seeded random per-edge weights,
    D = 2 and 3, with and without evidence, over several calls, both on
    gathers (a graph with no grid) and on shifts of a checked grid, where
    it also equals the gather program; a grid that its tables belie runs
    on gathers, as the ``chromatic_stencil`` gauge records; exact
    marginals of a 4x4 lattice by enumeration;
  * the contract around it — ``ChainPool`` serves it with one compiled
    chunk, and ``make`` refuses what the layout does not run.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _lattice_ref as ref
from repro.core import engine, samplers
from repro.core.factor_graph import (NeighborGraph, lattice_colors,
                                     make_lattice_graph, make_lattice_ising)
from repro.diagnostics import FreshnessPolicy
from repro.obs import Recorder, using
from repro.serving import ChainPool, Query


def _dense_rows(g):
    """Per site, its ``(neighbour, weight)`` pairs from the padded lists."""
    nbr, w = np.asarray(g.nbr), np.asarray(g.w)
    return [[(int(j), float(v)) for j, v in zip(nbr[i], w[i]) if v != 0.0]
            for i in range(g.n)]


@pytest.mark.parametrize("periodic", [False, True])
def test_neighbor_lists_match_lattice_edges(periodic):
    g = make_lattice_graph(8, 0.4, periodic=periodic)
    edges = ref.lattice_edges(8, periodic)
    want = ref.incident(64, edges, [np.float32(0.8)] * len(edges))
    assert _dense_rows(g) == [[(j, float(v)) for j, v in row]
                              for row in want]
    nbr, w = np.asarray(g.nbr), np.asarray(g.w)
    assert nbr.dtype == np.int32 and w.dtype == np.float32
    assert g.grid == (8, 8)
    if periodic:                      # every site has its 4 neighbours
        assert g.delta == 4 and np.all(w == np.float32(0.8))
    else:                             # the dense build's edges and weights
        dense = NeighborGraph.from_match_graph(make_lattice_ising(8, 0.4),
                                               lattice_colors(8))
        assert np.array_equal(np.asarray(dense.nbr), nbr)
        assert np.array_equal(np.asarray(dense.w), w)
        pad = w == 0.0                # padding points at the site itself
        assert np.all(nbr[pad] == np.nonzero(pad)[0])


@pytest.mark.parametrize("build, match", [
    (lambda: NeighborGraph.from_edges(4, [0, 1], [1, 2], [1.0, 1.0], D=2,
                                      colors=[0, 0, 1, 0]), "not a proper"),
    (lambda: NeighborGraph.from_edges(4, [0, 1], [1, 2], [1.0, 1.0], D=2,
                                      colors=[0, 1, 0, 3]), "empty"),
    (lambda: NeighborGraph.from_edges(3, [0, 1], [1, 0], [1.0, 1.0], D=2,
                                      colors=[0, 1, 0]), "joined twice"),
    (lambda: make_lattice_graph(5, 0.4), "even side"),
], ids=["same-colour-edge", "empty-class", "pair-twice", "odd-periodic"])
def test_bad_graph_is_refused(build, match):
    with pytest.raises(ValueError, match=match):
        build()


def test_build_records_span_and_table_bytes():
    rec = Recorder()
    with using(rec):
        g = make_lattice_graph(16, 0.44)
    assert rec.metrics.value("span_calls_total",
                             span="repro.graph.build") == 1
    assert rec.metrics.value("graph_table_bytes") == g.table_bytes \
        == 256 * 4 * 8


def _random_weight_graph(grid, periodic, D, seed, *, record_grid=False,
                         rewire=False):
    """The lattice with seeded random per-edge weights; ``record_grid``
    records its ``grid`` on the graph, and ``rewire`` moves edge (0, 1) to
    (0, 3), which keeps the colouring proper but is no lattice edge."""
    edges = ref.lattice_edges(grid, periodic)
    if rewire:
        edges = sorted(set(edges) - {(0, 1)} | {(0, 3)})
    w = np.random.default_rng(seed).uniform(0.1, 1.5, len(edges)).astype(
        np.float32)
    a, b = np.array(edges).T
    g = NeighborGraph.from_edges(grid * grid, a, b, w, D=D,
                                 colors=lattice_colors(grid),
                                 grid=(grid, grid) if record_grid else None)
    return g, edges, w


@pytest.mark.parametrize("D, periodic, with_evidence", [
    (2, True, False), (2, True, True), (3, False, False), (3, False, True)])
def test_sweep_equals_reference_bit_for_bit(D, periodic, with_evidence):
    g, edges, w = _random_weight_graph(6, periodic, D, seed=D)
    eng = engine.make("gibbs", g, sweep=g.n, schedule="chromatic",
                      backend="jnp")
    assert eng.updates_per_call == g.n and eng.supports_evidence
    st = eng.init(jax.random.PRNGKey(5), 3, start="random")
    ev = None
    if with_evidence:
        mask = np.zeros(g.n, np.float32)
        vals = np.zeros(g.n, np.int32)
        mask[[0, 7, 20]], vals[[0, 7, 20]] = 1.0, [1, D - 1, 0]
        ev = (jnp.asarray(mask), jnp.asarray(vals))
        st = eng.clamp(jax.random.PRNGKey(6), st, ev)
    x, keys = np.asarray(st.x), np.asarray(st.key)
    for _ in range(3):
        st = eng.sweep(st, evidence=ev)
        x, keys = ref.sweep(x, keys, g.n, D, edges, w, g.colors, ev)
        assert np.array_equal(np.asarray(st.x), x)
        assert np.array_equal(np.asarray(st.key), keys)
    assert np.all(np.asarray(st.accepts) == 0)


def _evidence(n, D):
    mask = np.zeros(n, np.float32)
    vals = np.zeros(n, np.int32)
    mask[[0, 9, 27, 62]], vals[[0, 9, 27, 62]] = 1.0, [1, D - 1, 0, 1]
    return jnp.asarray(mask), jnp.asarray(vals)


@pytest.mark.parametrize("D, periodic, with_evidence", [
    (2, True, False), (2, False, True), (3, True, True), (3, False, False)])
def test_stencil_sweep_equals_reference_and_gathers(D, periodic,
                                                    with_evidence):
    g, edges, w = _random_weight_graph(8, periodic, D, seed=10 + D,
                                      record_grid=True)
    assert samplers._grid_stencil_tables(g) is not None
    shifts = samplers._build_neighbor_chromatic_sweep(g)
    gathers = samplers._build_neighbor_chromatic_sweep(
        dataclasses.replace(g, grid=None))
    eng = engine.make("gibbs", g, schedule="chromatic", backend="jnp")
    ev = _evidence(g.n, D) if with_evidence else None
    st = eng.init(jax.random.PRNGKey(7), 3, start="random")
    other = st
    x, keys = np.asarray(st.x), np.asarray(st.key)
    for _ in range(4):
        st, other = shifts(st, evidence=ev), gathers(other, evidence=ev)
        x, keys = ref.sweep(x, keys, g.n, D, edges, w, g.colors, ev)
        assert np.array_equal(np.asarray(st.x), x)
        assert np.array_equal(np.asarray(st.key), keys)
        assert np.array_equal(np.asarray(other.x), x)
        assert np.array_equal(np.asarray(other.key), keys)


@pytest.mark.parametrize("rewire, grid_kept, stencil", [
    (False, True, 1.0), (True, True, 0.0), (False, False, 0.0)],
    ids=["grid", "rewired-edge", "no-grid"])
def test_stencil_gauge_follows_the_checked_tables(rewire, grid_kept,
                                                  stencil):
    g, edges, w = _random_weight_graph(8, True, 2, seed=3,
                                      record_grid=grid_kept, rewire=rewire)
    rec = Recorder()
    with using(rec):
        sweep = samplers._build_neighbor_chromatic_sweep(g)
    assert rec.metrics.value("chromatic_stencil") == stencil
    assert (samplers._grid_stencil_tables(g) is not None) == bool(stencil)
    eng = engine.make("gibbs", g, schedule="chromatic", backend="jnp")
    st = eng.init(jax.random.PRNGKey(2), 2, start="random")
    x, keys = ref.sweep(np.asarray(st.x), np.asarray(st.key), g.n, 2,
                        edges, w, g.colors)
    st = sweep(st)
    assert np.array_equal(np.asarray(st.x), x)
    assert np.array_equal(np.asarray(st.key), keys)


def test_marginals_match_enumeration():
    """4x4 periodic lattice at beta 0.44, site 0 observed at 1: the
    conditional site marginals and the edge agreement probabilities of
    64 chains x 600 sweeps (the first 100 dropped) against enumeration of
    the 2^15 free states.  The tolerance, 0.03, is twice the largest
    error six seeds read (0.003 to 0.014; the draws are correlated over
    several sweeps near the critical coupling)."""
    g = make_lattice_graph(4, 0.44)
    edges = ref.lattice_edges(4, True)
    w = [np.float32(0.88)] * len(edges)
    marg, agree = ref.exact_marginals(16, 2, edges, w, evidence=((0, 1),))
    eng = engine.make("gibbs", g, schedule="chromatic", backend="jnp")
    mask = jnp.zeros(16, jnp.float32).at[0].set(1.0)
    vals = jnp.zeros(16, jnp.int32).at[0].set(1)
    st = eng.clamp(jax.random.PRNGKey(0),
                   eng.init(jax.random.PRNGKey(1), 64, start="random"),
                   (mask, vals))
    a, b = np.array(edges).T

    @jax.jit
    def run(st):
        def body(s, _):
            s = eng.sweep(s, evidence=(mask, vals))
            return s, s.x
        return jax.lax.scan(body, st, None, length=600)[1]
    xs = np.asarray(run(st))[100:].reshape(-1, 16)
    assert np.all(xs[:, 0] == 1)
    assert np.abs(xs.mean(0) - marg[:, 1]).max() < 0.03
    assert np.abs((xs[:, a] == xs[:, b]).mean(0) - agree).max() < 0.03


def test_pool_serves_lattice_with_one_chunk():
    g = make_lattice_graph(8, 0.44)
    pool = ChainPool(policy=FreshnessPolicy(max_rhat=1.5,
                                            min_ess_per_site=4.0,
                                            min_samples=8), seed=0)
    pool.register("lattice8", graph=g, engine="gibbs", backend="jnp",
                  schedule="chromatic", chains=16, sweeps_per_chunk=8)
    plain = Query("lattice8", sites=(5, 9))
    cond = Query("lattice8", sites=(1, 8), evidence=((0, 1),))
    answers = pool.submit([plain, cond], max_extra_sweeps=4_000)
    assert [a.status for a in answers] == ["ok", "ok"], \
        [a.report for a in answers]
    assert answers[1].marginals.shape == (2, 2)
    assert pool.compiled_cache_size("lattice8") == 1


@pytest.mark.parametrize("name", ["mgpmh", "min-gibbs", "doublemin"])
def test_make_refuses_minibatch_engines(name):
    g = make_lattice_graph(4, 0.44)
    with pytest.raises(ValueError, match="dense MatchGraph"):
        engine.make(name, g, schedule="chromatic", backend="jnp")


@pytest.mark.parametrize("kw, match", [
    (dict(sweep=15, schedule="chromatic"), "sweep= must be n=16"),
    (dict(sweep=16), "chromatic schedule only"),
    (dict(schedule="chromatic", backend="pallas"), "'jnp' only"),
])
def test_make_refuses_what_the_layout_does_not_run(kw, match):
    g = make_lattice_graph(4, 0.44)
    with pytest.raises(ValueError, match=match):
        engine.make("gibbs", g, **{"backend": "jnp", **kw})


def test_chromatic_string_needs_a_coloured_graph():
    with pytest.raises(ValueError, match="carries its colouring"):
        engine.make("gibbs", make_lattice_ising(3, 0.4),
                    schedule="chromatic", backend="jnp")
