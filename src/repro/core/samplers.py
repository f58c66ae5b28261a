"""The paper's five sampling algorithms: single-chain reference steps plus
the fused multi-site *sweep* builders the `Engine` API assembles.

Layering (post engine redesign — see ``core/engine.py``):
  * ``make_*_step(graph, ...)`` — jit-able single-chain ``step(state) ->
    state`` reference implementations, one per paper algorithm.  They remain
    the distributional ground truth the sweep/distributed paths are tested
    against, and the building block for algorithms without a fused sweep.
  * ``_build_*_sweep(...)`` — *batched* ``sweep(state) -> state`` builders
    over the vmapped-layout ChainState (x of shape (C, n)): ``sweep_len``
    sequentially composed site updates per call, all sub-step randomness
    (sites, Poisson counts, alias-table and proposal uniforms) drawn up
    front in one batched pass, the x-dependent pipeline (gather -> bucket
    energy -> proposal -> MH accept) fused in one kernel launch
    (``kernels/fused_sweep.py``) or one jnp scan.  Each sub-step is exactly
    one iteration of the corresponding single-site chain at an
    i.i.d.-uniform site, so every sweep chain is *distributionally
    identical* to ``sweep_len`` applications of the reference step.
    MIN-Gibbs and DoubleMIN thread their cached energy estimate (Alg 2's
    eps / Thm 5's xi_x) through the sweep scan carry.
  * construction + metadata live in ``core/engine.py``: consumers call
    ``engine.make(name, graph, sweep=S, backend=...)`` and receive an
    ``Engine`` with explicit ``updates_per_call`` / ``backend`` metadata —
    nothing downstream sniffs attributes off bare functions anymore.

Algorithms (paper numbering):
  1  vanilla Gibbs                          O(D*Delta)   exact
  2  MIN-Gibbs (global bias-adjusted MB)    O(D*Psi^2)   unbiased, Thm 1/2
  3  Local Minibatch Gibbs                  O(D*B)       empirical only
  4  MGPMH (MB proposal + exact MH)         O(D*L^2+Delta) pi-stationary, Thm 3/4
  5  DoubleMIN-Gibbs (doubly minibatched)   O(D*L^2+Psi^2) Thm 5/6

The old public ``make_gibbs_sweep`` / ``make_mgpmh_sweep`` factories are
deprecation shims over ``engine.make`` and will be removed.
"""
from __future__ import annotations

import warnings
from contextlib import nullcontext
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .factor_graph import MatchGraph, alias_draw, build_alias_table
from .estimators import (draw_global_minibatch, draw_local_minibatch,
                         min_gibbs_estimate)
from ..kernels import ops as kernel_ops
from ..obs import get_recorder
# telemetry.py is pure jnp (no repro.core imports); the diagnostics package
# __init__ loads only it eagerly, so this import cannot cycle back here
from ..diagnostics.telemetry import SweepStats

__all__ = [
    "ChainState",
    "init_state",
    "make_gibbs_step",
    "make_min_gibbs_step",
    "make_local_gibbs_step",
    "make_mgpmh_step",
    "make_double_min_step",
    "make_gibbs_sweep",
    "make_mgpmh_sweep",
    "gibbs_select",
    "mh_accept",
    "min_gibbs_select",
    "evidence_cdf",
]


class ChainState(NamedTuple):
    """Augmented chain state.

    ``cache`` is the cached energy estimate: MIN-Gibbs's eps (Alg 2's state
    lives in Omega x R) or DoubleMIN's xi_x; unused (0) for the other
    samplers.  ``accepts`` counts MH acceptances (MGPMH / DoubleMIN).
    """
    x: jax.Array        # (n,) int32
    cache: jax.Array    # () float32
    key: jax.Array      # PRNG key
    accepts: jax.Array  # () int32


def init_state(key: jax.Array, graph: MatchGraph, *,
               start: str = "constant") -> ChainState:
    """Paper: "unmixed configuration where each site takes on the same
    state" (x(i)=1 for all i)."""
    if start == "constant":
        x = jnp.zeros((graph.n,), jnp.int32)
    elif start == "random":
        key, sub = jax.random.split(key)
        x = jax.random.randint(sub, (graph.n,), 0, graph.D, dtype=jnp.int32)
    else:
        raise ValueError(start)
    return ChainState(x=x, cache=jnp.float32(0.0), key=key,
                      accepts=jnp.int32(0))


# ---------------------------------------------------------------------------
# Algorithm 1 — vanilla Gibbs
# ---------------------------------------------------------------------------

def make_gibbs_step(graph: MatchGraph):
    def step(state: ChainState) -> ChainState:
        key, ki, kv = jax.random.split(state.key, 3)
        i = jax.random.randint(ki, (), 0, graph.n)
        eps = graph.cond_energies(state.x, i)          # (D,) exact
        v = jax.random.categorical(kv, eps)            # rho(v) ~ exp(eps_v)
        return state._replace(x=state.x.at[i].set(v.astype(jnp.int32)),
                              key=key)
    return step


# ---------------------------------------------------------------------------
# Algorithm 2 — MIN-Gibbs
# ---------------------------------------------------------------------------

def make_min_gibbs_step(graph: MatchGraph, lam: float, capacity: int):
    """Minibatch Gibbs with the bias-adjusted global estimator (eq. 2).

    For every candidate value u != x(i) an *independent* minibatch estimate
    eps_u ~ mu_{x; x_i<-u} is drawn; eps_{x(i)} is the cached energy from the
    previous iteration (the augmented-state trick of Alg 2).
    """
    def step(state: ChainState) -> ChainState:
        key, ki, kd, kv = jax.random.split(state.key, 4)
        i = jax.random.randint(ki, (), 0, graph.n)
        x = state.x

        # D independent global minibatches, one per candidate value u.
        idx, B = draw_global_minibatch(kd, graph, lam, capacity,
                                       shape=(graph.D,))   # (D,K), (D,)
        a = graph.pair_a[idx]                               # (D, K)
        b = graph.pair_b[idx]
        u = jnp.arange(graph.D, dtype=jnp.int32)[:, None]   # (D, 1)
        xa = jnp.where(a == i, u, x[a])
        xb = jnp.where(b == i, u, x[b])
        mask = jnp.arange(capacity)[None, :] < B[:, None]
        matches = jnp.sum((xa == xb) & mask, axis=1).astype(jnp.float32)
        eps = jnp.log1p(graph.psi / lam) * matches          # (D,)

        # cached energy for the current value (Alg 2: eps_{x(i)} <- eps).
        eps = eps.at[x[i]].set(state.cache)
        v = jax.random.categorical(kv, eps).astype(jnp.int32)
        return state._replace(x=x.at[i].set(v), cache=eps[v], key=key)
    return step


def init_min_gibbs_cache(key: jax.Array, graph: MatchGraph,
                         state: ChainState, lam: float,
                         capacity: int) -> ChainState:
    """Initialize the augmented-energy cache with one estimator draw."""
    idx, B = draw_global_minibatch(key, graph, lam, capacity)
    eps = min_gibbs_estimate(graph, state.x, idx, B, lam)
    return state._replace(cache=eps)


# ---------------------------------------------------------------------------
# Algorithm 3 — Local Minibatch Gibbs
# ---------------------------------------------------------------------------

def make_local_gibbs_step(graph: MatchGraph, batch_size: int):
    """One *shared* uniform minibatch S subset A[i], |S| = B, used for every
    candidate value u (the cancellation trick).  eps_u = |A[i]|/B * sum_S phi.
    Sampling is without replacement, matching the paper's uniform-subset
    statement."""
    n = graph.n

    def step(state: ChainState) -> ChainState:
        key, ki, ks, kv = jax.random.split(state.key, 4)
        i = jax.random.randint(ki, (), 0, n)
        # B distinct neighbors j != i: draw from {0..n-2} w/o replacement,
        # then skip over i.
        j0 = jax.random.choice(ks, n - 1, (batch_size,), replace=False)
        j = j0 + (j0 >= i)
        w = graph.W[i, j]                                   # (B,)
        onehot = jax.nn.one_hot(state.x[j], graph.D, dtype=w.dtype)
        scale = (n - 1) / batch_size                        # |A[i]| / |S|
        eps = scale * (w @ onehot)                          # (D,)
        v = jax.random.categorical(kv, eps).astype(jnp.int32)
        return state._replace(x=state.x.at[i].set(v), key=key)
    return step


# ---------------------------------------------------------------------------
# Algorithm 4 — MGPMH
# ---------------------------------------------------------------------------

def _mgpmh_proposal(graph: MatchGraph, x, i, kd, kv, lam: float,
                    capacity: int):
    """Shared proposal machinery of Algorithms 4 and 5.

    Returns (v proposed value, eps (D,) minibatch energies).
    eps_u = sum_phi s_phi L/(lam M_phi) phi(x_u) = (L/lam) * #{draws: x_j = u}
    for match graphs.
    """
    j, B = draw_local_minibatch(kd, graph, i, lam, capacity)
    mask = (jnp.arange(capacity) < B).astype(jnp.float32)
    onehot = jax.nn.one_hot(x[j], graph.D, dtype=jnp.float32)  # (K, D)
    eps = (graph.L / lam) * (mask @ onehot)                    # (D,)
    v = jax.random.categorical(kv, eps).astype(jnp.int32)
    return v, eps


def make_mgpmh_step(graph: MatchGraph, lam: float, capacity: int):
    def step(state: ChainState) -> ChainState:
        key, ki, kd, kv, ka = jax.random.split(state.key, 5)
        i = jax.random.randint(ki, (), 0, graph.n)
        x = state.x
        v, eps = _mgpmh_proposal(graph, x, i, kd, kv, lam, capacity)
        # Exact O(Delta) pass: sum_{phi in A[i]} phi(y) = exact[v], phi(x) =
        # exact[x(i)]  (cond_energies is independent of x(i) itself).
        exact = graph.cond_energies(x, i)                  # (D,)
        log_a = (exact[v] - exact[x[i]]) + (eps[x[i]] - eps[v])
        accept = jnp.log(jax.random.uniform(ka)) < log_a
        new_x = jnp.where(accept, x.at[i].set(v), x)
        return state._replace(x=new_x, key=key,
                              accepts=state.accepts + accept.astype(jnp.int32))
    return step


# ---------------------------------------------------------------------------
# Algorithm 5 — DoubleMIN-Gibbs
# ---------------------------------------------------------------------------

def make_double_min_step(graph: MatchGraph, lam1: float, capacity1: int,
                         lam2: float, capacity2: int):
    """MGPMH proposal + second (global, bias-adjusted) minibatch in the
    acceptance test: a = exp(xi_y - xi_x + eps_{x(i)} - eps_v).  The cached
    xi_x lives in ``state.cache`` (augmented state, Thm 5)."""
    def step(state: ChainState) -> ChainState:
        key, ki, kd, kv, kg, ka = jax.random.split(state.key, 6)
        i = jax.random.randint(ki, (), 0, graph.n)
        x = state.x
        v, eps = _mgpmh_proposal(graph, x, i, kd, kv, lam1, capacity1)
        y = x.at[i].set(v)
        idx, B = draw_global_minibatch(kg, graph, lam2, capacity2)
        xi_y = min_gibbs_estimate(graph, y, idx, B, lam2)
        log_a = (xi_y - state.cache) + (eps[x[i]] - eps[v])
        accept = jnp.log(jax.random.uniform(ka)) < log_a
        new_x = jnp.where(accept, y, x)
        new_cache = jnp.where(accept, xi_y, state.cache)
        return state._replace(x=new_x, cache=new_cache, key=key,
                              accepts=state.accepts + accept.astype(jnp.int32))
    return step


def init_double_min_cache(key: jax.Array, graph: MatchGraph,
                          state: ChainState, lam2: float,
                          capacity2: int) -> ChainState:
    idx, B = draw_global_minibatch(key, graph, lam2, capacity2)
    xi = min_gibbs_estimate(graph, state.x, idx, B, lam2)
    return state._replace(cache=xi)


# ---------------------------------------------------------------------------
# Fused multi-site sweeps (batched execution engine)
# ---------------------------------------------------------------------------

def _batch_keys(keys: jax.Array, num: int):
    """Split every chain's key: (C, 2) -> ``num`` keysets of shape (C, 2)."""
    ks = jax.vmap(lambda k: jax.random.split(k, num))(keys)
    return [ks[:, t] for t in range(num)]


def _master_key(keys: jax.Array):
    """(knew (C, 2), master key): every per-chain key advances, all batch
    draws derive from chain 0's spare split — one threefry stream feeding
    (C, ...) shaped draws is ~3x cheaper than C vmapped streams and
    statistically equivalent (splits are independent).  This is the RNG
    contract of every jnp sweep schedule below; the Pallas path keeps
    per-chain streams (equally valid, different bits)."""
    ks = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
    return ks[:, 0], ks[0, 1]


def _bucket_counts(vals: jax.Array, D: int) -> jax.Array:
    """(C, K) int values -> (C, D) float32 counts.  Values >= D (pad
    sentinels) land in no bucket.  D fused compare-reduce passes for small
    D (no (C, K, D) one-hot materialization); one-hot reduce above."""
    if D <= 32:
        return jnp.stack([jnp.sum(vals == d, axis=1) for d in range(D)],
                         axis=1).astype(jnp.float32)
    return jnp.sum(jax.nn.one_hot(vals, D, dtype=jnp.float32), axis=1)


def _alias_gather(prob, alias, key, shape, m):
    """``shape`` alias-table draws from a flat ``(m,)`` table: randint
    index + separate accept uniform (the reference `alias_draw` scheme).

    NOT the one-uniform trick: ``u*m`` in float32 has ulp >= 0.25 for
    m ~ 2^23 (the factor count of the large registered workloads), which
    quantizes the accept fraction and silently biases the draw; the
    per-row site tables (m = n) stay on the one-uniform fast path in the
    mgpmh/doublemin proposal schedules.
    """
    k1, k2 = jax.random.split(key)
    idx = jax.random.randint(k1, shape, 0, m)
    u = jax.random.uniform(k2, shape)
    return jnp.where(u < prob[idx], idx, alias[idx])


def _span(name: str, impl: str = "pallas"):
    """A host span around one phase of a fused-Pallas sweep:
    ``repro.sweep.draws`` (key splits and every random draw, dispatched
    eagerly before the kernel) or ``repro.sweep.kernel`` (the jitted
    kernel's dispatch).  None on jnp."""
    return get_recorder().span(name) if impl == "pallas" else nullcontext()


def _check_impl(impl: str):
    if impl not in ("jnp", "pallas"):
        raise ValueError(f"impl must be 'jnp' or 'pallas' (engine.make owns "
                         f"the 'auto' policy), got {impl!r}")


def _site_hits(i: jax.Array, n: int) -> jax.Array:
    """(C, S) site-index draws -> (n,) float32 visit counts (all chains)."""
    return jnp.zeros((n,), jnp.float32).at[i.reshape(-1)].add(1.0)


# ---------------------------------------------------------------------------
# Per-algorithm substep primitives, shared between the fused jnp sweeps
# below and the distributed sweep template (runtime/dist_gibbs.py).  Each is
# one algorithm's selection/acceptance rule over batched (C, D) energies —
# the part of a sub-step that is identical no matter how the energies were
# produced (full exact pass, delta-corrected psum partials, minibatch
# bucket counts).
# ---------------------------------------------------------------------------

def gibbs_select(eps: jax.Array, gumbel: jax.Array) -> jax.Array:
    """Categorical draw over (C, D) energies via Gumbel-argmax
    (``categorical(exp eps)`` == ``argmax(eps + gumbel)``) — the Gibbs /
    proposal selection every algorithm's substep starts from."""
    return jnp.argmax(eps + gumbel, axis=-1).astype(jnp.int32)


def mh_accept(logu: jax.Array, exact_diff: jax.Array, eps_xi: jax.Array,
              eps_v: jax.Array) -> jax.Array:
    """The MGPMH/DoubleMIN acceptance rule:
    ``log a = (exact(y) - exact(x)) + (eps_x - eps_v)`` — for DoubleMIN,
    ``exact_diff`` is the second-minibatch difference ``xi_y - xi_x``."""
    return logu < exact_diff + (eps_xi - eps_v)


def min_gibbs_select(eps: jax.Array, cache: jax.Array, xi: jax.Array,
                     gumbel: jax.Array, rows: jax.Array):
    """Alg 2's augmented-state recursion at one sub-step: overwrite the
    current-value slot with the cached estimate, Gumbel-argmax, cache the
    winner's estimate.  Returns ``(v, new_cache)``."""
    eps = eps.at[rows, xi].set(cache)
    v = gibbs_select(eps, gumbel)
    return v, eps[rows, v]


# Sweep builders below take three optional extensions to the plain
# ``sweep(state) -> state`` contract:
#   * ``collect_stats=True`` (build time): the sweep additionally returns a
#     :class:`SweepStats` with per-site proposal/acceptance counters — the
#     instrumented variant Engine.sweep uses when threading telemetry;
#   * ``sites=`` (call time): a (C, sweep_len) site-index
#     array overriding the builder's i.i.d.-uniform draw — the hook the
#     AdaptiveScan schedule drives with its non-uniform table.  The
#     default-path PRNG streams are unchanged either way.
#   * ``evidence=`` (call time): an ``(ev_mask (n,) float32, ev_vals (n,)
#     int32)`` pair of DATA arrays; site selection is redirected through
#     the masked inverse-CDF (:func:`evidence_cdf`) so observed sites are
#     never resampled — the serving layer's per-request clamping.  An
#     all-zero mask reproduces the uniform draw exactly, so clamped and
#     unclamped calls share one jit trace.  The caller must have clamped
#     ``state.x`` at the observed sites (``Engine.clamp``); the chromatic
#     sweep instead re-clamps x between color classes.


def evidence_cdf(ev_mask: jax.Array) -> jax.Array:
    """(n,) cumulative site-selection table, uniform over UNOBSERVED sites.

    ``ev_mask`` is (n,) float32 with 1.0 at observed (clamped) sites.  The
    cdf is normalized so its last entry is exactly 1.0 and zero-mass
    (observed) sites keep exact ties with their predecessor — a
    ``searchsorted(cdf, u, side="right")`` draw with u in [0, 1) can then
    never land on an observed site.  With an all-zero mask this is exactly
    the uniform cdf, so one compiled sweep serves clamped and unclamped
    requests (the same in-graph inverse-CDF pattern AdaptiveScan uses)."""
    c = jnp.cumsum(1.0 - ev_mask)
    return c / jnp.maximum(c[-1], 1e-30)


def _draw_sites(ki, C: int, S: int, n: int, sites, evidence, *,
                per_chain: bool):
    """(C, S) site indices for one sweep call: the explicit ``sites``
    override wins (AdaptiveScan); with ``evidence`` the draw is uniform
    over unobserved sites via the masked inverse-CDF; default is the plain
    i.i.d.-uniform draw.  ``per_chain``: ki is a (C, 2) keyset (vmapped
    per-chain streams, the pallas RNG contract) vs one master key feeding
    (C, S) draws (the jnp contract)."""
    if sites is not None:
        return sites
    with jax.named_scope("repro.phase/site_draws"):
        if evidence is not None:
            cdf = evidence_cdf(evidence[0])
            if per_chain:
                u = jax.vmap(lambda k: jax.random.uniform(k, (S,)))(ki)
            else:
                u = jax.random.uniform(ki, (C, S))
            i = jnp.searchsorted(cdf, u, side="right").astype(jnp.int32)
            return jnp.minimum(i, n - 1)
        if per_chain:
            return jax.vmap(
                lambda k: jax.random.randint(k, (S,), 0, n))(ki)
        return jax.random.randint(ki, (C, S), 0, n)


def _build_gibbs_sweep(graph: MatchGraph, sweep_len: int, *,
                       impl: str, collect_stats: bool = False):
    """``sweep_len`` sequential vanilla-Gibbs updates per call, one fused
    kernel launch (or jnp oracle) for the whole batch of chains.

    Returns a *batched* ``sweep(state, sites=None) -> state`` over a
    vmapped-layout ChainState (x of shape (C, n)); see the module docstring.
    impl: 'pallas' | 'jnp' — resolved by the caller (engine.make owns the
    'auto' policy).
    """
    _check_impl(impl)
    n, D = graph.n, graph.D
    if impl == "pallas":
        kernel_ops.check_sweep_vmem("gibbs", n=n, D=D, S=sweep_len)

    def sweep(state: ChainState, sites=None, evidence=None):
        with _span("repro.sweep.draws", impl):
            ki, kg, knew = _batch_keys(state.key, 3)
            i = _draw_sites(ki, state.x.shape[0], sweep_len, n, sites,
                            evidence, per_chain=True)          # (C, S)
            gumbel = jax.vmap(lambda k: jax.random.gumbel(
                k, (sweep_len, D)))(kg)                        # (C, S, D)
        with _span("repro.sweep.kernel", impl):
            x = kernel_ops.gibbs_sweep(state.x, graph.W, i, gumbel, D=D,
                                       impl=impl)
        new = state._replace(x=x, key=knew)
        if not collect_stats:
            return new
        hits = _site_hits(i, n)       # exact accept: every update counts
        return new, SweepStats(site_prop=hits, site_acc=hits)

    return sweep


def _build_mgpmh_sweep(graph: MatchGraph, lam: float, capacity: int,
                       sweep_len: int, *, impl: str,
                       collect_stats: bool = False):
    """``sweep_len`` sequential MGPMH updates (Algorithm 4 per sub-step)
    per call, one fused launch for the whole batch of chains.

    All randomness (sites, per-site Poisson totals via the footnote-7
    decomposition, alias-table uniforms, Gumbel proposal noise, MH accept
    uniforms) is drawn up front in one batched pass per sweep; the
    x-dependent pipeline runs fused.  Distributionally identical to
    ``sweep_len`` steps of ``make_mgpmh_step`` — Theorems 3/4 apply
    unchanged.

    impl: 'pallas' — the fused Pallas kernel (kernels/fused_sweep.py;
          interpret mode off-TPU: correctness path, slow);
          'jnp'    — a fused pure-jnp schedule of the same chain, tuned for
          CPU/GPU (packed alias-table gathers, per-value bucket counting,
          two-point exact pass).
    Resolved by the caller (engine.make owns the 'auto' policy).  The two
    impls consume different (equally valid) PRNG streams; each is
    distributionally exact (tests/test_sweep.py).
    """
    _check_impl(impl)
    if impl == "jnp":
        return _make_mgpmh_sweep_jnp(graph, lam, capacity, sweep_len,
                                     collect_stats=collect_stats)
    n, D = graph.n, graph.D
    scale = float(graph.L / lam)
    kernel_ops.check_sweep_vmem("mgpmh", n=n, D=D, S=sweep_len, K=capacity)

    def sweep(state: ChainState, sites=None, evidence=None):
        with _span("repro.sweep.draws"):
            ki, kb, k1, k2, kg, ka, knew = _batch_keys(state.key, 7)
            i = _draw_sites(ki, state.x.shape[0], sweep_len, n, sites,
                            evidence, per_chain=True)          # (C, S)
            lam_i = lam * graph.row_sum[i] / graph.L           # (C, S)
            B = jax.vmap(lambda k, l: jax.random.poisson(
                k, l, dtype=jnp.int32))(kb, lam_i)
            B = jnp.minimum(B, capacity)
            u_idx = jax.vmap(lambda k: jax.random.uniform(
                k, (sweep_len, capacity)))(k1)
            u_alias = jax.vmap(lambda k: jax.random.uniform(
                k, (sweep_len, capacity)))(k2)
            gumbel = jax.vmap(lambda k: jax.random.gumbel(
                k, (sweep_len, D)))(kg)
            logu = jnp.log(jax.vmap(lambda k: jax.random.uniform(
                k, (sweep_len,)))(ka))
        with _span("repro.sweep.kernel"):
            x, acc = kernel_ops.mgpmh_sweep(
                state.x, graph.W, graph.row_prob, graph.row_alias, i, B,
                u_idx, u_alias, gumbel, logu, D=D, scale=scale, impl=impl)
        new = state._replace(x=x, key=knew, accepts=state.accepts + acc)
        if not collect_stats:
            return new
        # acceptance stays inside the kernel: per-site acceptances are
        # reported as accepted *moves* (value changes) — a lower bound the
        # jnp schedule sharpens to exact counts
        moves = jnp.sum(state.x != x, axis=0, dtype=jnp.float32)
        return new, SweepStats(site_prop=_site_hits(i, n), site_acc=moves)

    return sweep


def _make_mgpmh_sweep_jnp(graph: MatchGraph, lam: float, capacity: int,
                          sweep_len: int, *, collect_stats: bool = False):
    """CPU/GPU-tuned fused jnp schedule of the MGPMH sweep chain.

    Same chain as the Pallas kernel, reorganized for a cache-hierarchy
    machine instead of the MXU:
      * prob/alias rows interleaved into one (n, n, 2) table so the
        per-draw gather touches one cache line instead of two arrays;
      * the classic one-uniform alias trick (index from ``floor(u*n)``,
        accept from the leftover fraction ``u*n - idx`` — exact, and
        halves the dominant threefry cost);
      * minibatch bucket energies as D fused compare-reduce passes over the
        draw window (no (C, K, D) one-hot materialization);
      * the exact MH pass evaluated only at the two energies the
        acceptance ratio needs (v and x_i) instead of all D.
    """
    n, D, S, K = graph.n, graph.D, sweep_len, capacity
    scale = float(graph.L / lam)
    packed = jnp.stack([graph.row_prob,
                        graph.row_alias.astype(jnp.float32)], axis=-1)

    def sweep(state: ChainState, sites=None, evidence=None):
        C = state.x.shape[0]
        rows = jnp.arange(C)
        knew, master = _master_key(state.key)
        ki, kb, k1, kg, ka = jax.random.split(master, 5)
        i = _draw_sites(ki, C, S, n, sites, evidence, per_chain=False)
        with jax.named_scope("repro.phase/minibatch_draws"):
            lam_i = lam * graph.row_sum[i] / graph.L
            B = jnp.minimum(
                jax.random.poisson(kb, lam_i, dtype=jnp.int32), K)
            un = jax.random.uniform(k1, (C, S, K)) * n
            idx = jnp.minimum(un.astype(jnp.int32), n - 1)
            pk = packed[i[..., None], idx]                     # (C, S, K, 2)
            j = jnp.where(un - idx < pk[..., 0], idx,
                          pk[..., 1].astype(jnp.int32))
            # sentinel n for draws past B: they gather the pad column
            # (value D) and land in no bucket
            j = jnp.where(
                jnp.arange(K)[None, None, :] < B[..., None], j, n)
            gumbel = jax.random.gumbel(kg, (C, S, D))
            logu = jnp.log(jax.random.uniform(ka, (C, S)))
        xp = jnp.pad(state.x, ((0, 0), (0, 1)), constant_values=D)

        def substep(carry, s):
            xp, acc, sa = carry
            i_s = i[:, s]
            vals = jnp.take_along_axis(xp, j[:, s, :], axis=1)  # (C, K)
            eps = scale * _bucket_counts(vals, D)               # (C, D)
            v = gibbs_select(eps, gumbel[:, s, :])
            xi = xp[rows, i_s]
            w_row = graph.W[i_s]                                # (C, n)
            x_body = xp[:, :n]
            exact_diff = jnp.sum(
                w_row * ((x_body == v[:, None]).astype(jnp.float32)
                         - (x_body == xi[:, None]).astype(jnp.float32)),
                axis=1)
            accept = mh_accept(logu[:, s], exact_diff,
                               eps[rows, xi], eps[rows, v])
            new_v = jnp.where(accept, v, xi)
            xp = xp.at[rows, i_s].set(new_v)
            if collect_stats:
                sa = sa.at[i_s].add(accept.astype(jnp.float32))
            return (xp, acc + accept.astype(jnp.int32), sa), None

        sa0 = jnp.zeros((n if collect_stats else 0,), jnp.float32)
        with jax.named_scope("repro.phase/substeps"):
            (xp, acc, sa), _ = jax.lax.scan(
                substep, (xp, jnp.zeros((C,), jnp.int32), sa0),
                jnp.arange(S))
        new = state._replace(x=xp[:, :n], key=knew,
                             accepts=state.accepts + acc)
        if not collect_stats:
            return new
        return new, SweepStats(site_prop=_site_hits(i, n), site_acc=sa)

    return sweep


# ---------------------------------------------------------------------------
# MIN-Gibbs sweep (Algorithm 2, batched): the cached energy estimate eps of
# the *current global state* rides the sweep scan carry — each sub-step
# overwrites the current-value slot with it and caches the winner's estimate,
# exactly Alg 2's augmented-state recursion, now at sweep granularity.
# ---------------------------------------------------------------------------

def _build_min_gibbs_sweep(graph: MatchGraph, lam: float, capacity: int,
                           sweep_len: int, *, impl: str,
                           collect_stats: bool = False):
    """``sweep_len`` sequential MIN-Gibbs updates per call, one fused launch
    per call.

    impl: 'pallas' — the fused Pallas kernel (kernels/fused_sweep.py;
          per-draw uniforms drawn host-side for the bit-exact-vs-oracle
          correctness path, in-kernel on the TPU ``*_rng`` bench path);
          'jnp'    — a fused jnp schedule with *chunked* draw streams: the
          per-candidate factor draws are generated inside the scan body
          (one sub-step at a time, from per-sub-step folded keys), so peak
          temp memory is O(C·D·lam) — independent of ``sweep_len`` — not
          the O(C·S·D·lam) of an upfront batch (asserted via XLA's
          memory_analysis in tests/test_sweep.py).
    Resolved by the caller (engine.make owns the 'auto' policy).  The two
    impls consume different (equally valid) PRNG streams; each is
    distributionally identical to ``sweep_len`` steps of
    ``make_min_gibbs_step`` (Thm 1/2 apply unchanged).  The cache must be
    initialized with ``init_min_gibbs_cache`` (engine.init does this).
    """
    _check_impl(impl)
    if impl == "pallas":
        return _build_min_gibbs_sweep_pallas(graph, lam, capacity,
                                             sweep_len,
                                             collect_stats=collect_stats)
    n, D, S, K = graph.n, graph.D, sweep_len, capacity
    F = int(graph.pair_a.shape[0])
    lscale = float(np.log1p(graph.psi / lam))

    def sweep(state: ChainState, sites=None, evidence=None):
        C = state.x.shape[0]
        rows = jnp.arange(C)
        knew, master = _master_key(state.key)
        ki, kb, kf, kg = jax.random.split(master, 4)
        i = _draw_sites(ki, C, S, n, sites, evidence, per_chain=False)
        # D independent global minibatches per sub-step, one per candidate;
        # only the O(C·S·D) Poisson totals are drawn upfront — the O(lam)-
        # sized factor-draw buffers are generated inside the scan body.
        B = jnp.minimum(jax.random.poisson(kb, lam, (C, S, D),
                                           dtype=jnp.int32), K)
        gumbel = jax.random.gumbel(kg, (C, S, D))
        u_cand = jnp.arange(D, dtype=jnp.int32)[None, :, None]   # (1, D, 1)
        k_mask = jnp.arange(K)[None, None, :]                    # (1, 1, K)

        def substep(carry, s):
            x, cache = carry
            i_s = i[:, s]
            f = _alias_gather(graph.pair_prob, graph.pair_alias,
                              jax.random.fold_in(kf, s), (C, D, K), F)
            a_s, b_s = graph.pair_a[f], graph.pair_b[f]     # (C, D, K)
            xa = x[rows[:, None, None], a_s]
            xb = x[rows[:, None, None], b_s]
            xa = jnp.where(a_s == i_s[:, None, None], u_cand, xa)
            xb = jnp.where(b_s == i_s[:, None, None], u_cand, xb)
            mask = k_mask < B[:, s, :, None]                # (C, D, K)
            matches = jnp.sum((xa == xb) & mask, axis=-1)
            eps = lscale * matches.astype(jnp.float32)      # (C, D)
            xi = x[rows, i_s]
            v, cache = min_gibbs_select(eps, cache, xi, gumbel[:, s, :],
                                        rows)
            x = x.at[rows, i_s].set(v)
            return (x, cache), None

        (x, cache), _ = jax.lax.scan(substep, (state.x, state.cache),
                                     jnp.arange(S))
        new = state._replace(x=x, cache=cache, key=knew)
        if not collect_stats:
            return new
        hits = _site_hits(i, n)       # Gibbs-type: every update accepted
        return new, SweepStats(site_prop=hits, site_acc=hits)

    return sweep


def _node_alias_table(graph: MatchGraph):
    """Alias table over sites with p_a = L_a / 2Psi — stage one of the
    two-stage global factor draw the Pallas kernels use (stage two is the
    per-row table; the product is exactly M_phi / Psi, see kernels/ref.py).
    """
    prob, alias = build_alias_table(np.asarray(graph.row_sum))
    return jnp.asarray(prob), jnp.asarray(alias)


def _build_min_gibbs_sweep_pallas(graph: MatchGraph, lam: float,
                                  capacity: int, sweep_len: int, *,
                                  collect_stats: bool = False):
    """Pallas schedule of the MIN-Gibbs sweep chain: host-drawn uniform
    streams feed ``kernel_ops.min_gibbs_sweep`` (bit-exact vs the jnp
    oracle — the interpret-mode correctness path); on TPU the
    ``min_gibbs_sweep_pallas_rng`` bench variant generates the same streams
    in-kernel so they never exist in HBM."""
    n, D, S, K = graph.n, graph.D, sweep_len, capacity
    kernel_ops.check_sweep_vmem("min-gibbs", n=n, D=D, S=S, K=K)
    lscale = float(np.log1p(graph.psi / lam))
    node_prob, node_alias = _node_alias_table(graph)

    def sweep(state: ChainState, sites=None, evidence=None):
        with _span("repro.sweep.draws"):
            ki, kb, k1, k2, k3, k4, kg, knew = _batch_keys(state.key, 8)
            i = _draw_sites(ki, state.x.shape[0], S, n, sites, evidence,
                            per_chain=True)                # (C, S)
            B = jnp.minimum(jax.vmap(lambda k: jax.random.poisson(
                k, lam, (S, D), dtype=jnp.int32))(kb), K)
            u1, u2, u3, u4 = (jax.vmap(lambda k: jax.random.uniform(
                k, (S, D, K)))(ks) for ks in (k1, k2, k3, k4))
            gumbel = jax.vmap(lambda k: jax.random.gumbel(k, (S, D)))(kg)
        with _span("repro.sweep.kernel"):
            x, cache = kernel_ops.min_gibbs_sweep(
                state.x, node_prob, node_alias, graph.row_prob,
                graph.row_alias, i, B, u1, u2, u3, u4, gumbel, state.cache,
                D=D, lscale=lscale, impl="pallas")
        new = state._replace(x=x, cache=cache, key=knew)
        if not collect_stats:
            return new
        hits = _site_hits(i, n)       # Gibbs-type: every update accepted
        return new, SweepStats(site_prop=hits, site_acc=hits)

    return sweep


# ---------------------------------------------------------------------------
# DoubleMIN-Gibbs sweep (Algorithm 5, batched): the cached second-minibatch
# estimate xi_x rides the scan carry, updated on every acceptance (Thm 5's
# augmented state at sweep granularity).
# ---------------------------------------------------------------------------

def _build_double_min_sweep(graph: MatchGraph, lam1: float, capacity1: int,
                            lam2: float, capacity2: int, sweep_len: int, *,
                            impl: str, collect_stats: bool = False):
    """``sweep_len`` sequential DoubleMIN updates per call: MGPMH proposal
    + a second global bias-adjusted minibatch in the acceptance test.

    impl: 'pallas' — the fused Pallas kernel (host-drawn streams for the
          bit-exact-vs-oracle path; ``double_min_sweep_pallas_rng`` on TPU
          keeps them out of HBM entirely);
          'jnp'    — the fused jnp schedule (packed alias gathers,
          bucket-count energies) with *chunked* draw streams: the proposal
          and second-batch draws are generated inside the scan body from
          per-sub-step folded keys, so peak temp memory is
          O(C·(lam1 + lam2)) — independent of ``sweep_len``.
    Resolved by the caller.  Distributionally identical to ``sweep_len``
    steps of ``make_double_min_step``; the cache must be initialized with
    ``init_double_min_cache`` (engine.init does this)."""
    _check_impl(impl)
    if impl == "pallas":
        return _build_double_min_sweep_pallas(
            graph, lam1, capacity1, lam2, capacity2, sweep_len,
            collect_stats=collect_stats)
    n, D, S = graph.n, graph.D, sweep_len
    K1, K2 = capacity1, capacity2
    F = int(graph.pair_a.shape[0])
    scale1 = float(graph.L / lam1)
    lscale2 = float(np.log1p(graph.psi / lam2))
    packed = jnp.stack([graph.row_prob,
                        graph.row_alias.astype(jnp.float32)], axis=-1)

    def sweep(state: ChainState, sites=None, evidence=None):
        C = state.x.shape[0]
        rows = jnp.arange(C)
        knew, master = _master_key(state.key)
        ki, kb1, k1, kg, kb2, kf, ka = jax.random.split(master, 7)
        i = _draw_sites(ki, C, S, n, sites, evidence, per_chain=False)
        # only the O(C·S) streams are drawn upfront; the O(lam)-sized draw
        # buffers are generated one sub-step at a time inside the scan
        lam_i = lam1 * graph.row_sum[i] / graph.L
        B1 = jnp.minimum(jax.random.poisson(kb1, lam_i, dtype=jnp.int32), K1)
        gumbel = jax.random.gumbel(kg, (C, S, D))
        B2 = jnp.minimum(jax.random.poisson(kb2, lam2, (C, S),
                                            dtype=jnp.int32), K2)
        logu = jnp.log(jax.random.uniform(ka, (C, S)))
        xp0 = jnp.pad(state.x, ((0, 0), (0, 1)), constant_values=D)

        def substep(carry, s):
            xp, cache, acc, sa = carry
            i_s = i[:, s]
            # proposal minibatch over A[i_s] (as in the MGPMH jnp schedule)
            un = jax.random.uniform(jax.random.fold_in(k1, s),
                                    (C, K1)) * n
            idx = jnp.minimum(un.astype(jnp.int32), n - 1)
            pk = packed[i_s[:, None], idx]                       # (C, K1, 2)
            j = jnp.where(un - idx < pk[..., 0], idx,
                          pk[..., 1].astype(jnp.int32))
            # sentinel n for draws past B1: they gather the pad column
            # (value D) and land in no bucket
            j = jnp.where(jnp.arange(K1)[None, :] < B1[:, s, None], j, n)
            vals = jnp.take_along_axis(xp, j, axis=1)            # (C, K1)
            eps = scale1 * _bucket_counts(vals, D)               # (C, D)
            v = gibbs_select(eps, gumbel[:, s, :])
            xi = xp[rows, i_s]
            # xi_y = eq.-(2) estimate at y = x[i_s <- v]
            f = _alias_gather(graph.pair_prob, graph.pair_alias,
                              jax.random.fold_in(kf, s), (C, K2), F)
            a_s, b_s = graph.pair_a[f], graph.pair_b[f]          # (C, K2)
            ya = xp[rows[:, None], a_s]
            yb = xp[rows[:, None], b_s]
            ya = jnp.where(a_s == i_s[:, None], v[:, None], ya)
            yb = jnp.where(b_s == i_s[:, None], v[:, None], yb)
            mask2 = jnp.arange(K2)[None, :] < B2[:, s, None]
            matches = jnp.sum((ya == yb) & mask2, axis=-1)
            xi_y = lscale2 * matches.astype(jnp.float32)
            accept = mh_accept(logu[:, s], xi_y - cache,
                               eps[rows, xi], eps[rows, v])
            xp = xp.at[rows, i_s].set(jnp.where(accept, v, xi))
            cache = jnp.where(accept, xi_y, cache)
            if collect_stats:
                sa = sa.at[i_s].add(accept.astype(jnp.float32))
            return (xp, cache, acc + accept.astype(jnp.int32), sa), None

        sa0 = jnp.zeros((n if collect_stats else 0,), jnp.float32)
        (xp, cache, acc, sa), _ = jax.lax.scan(
            substep, (xp0, state.cache, jnp.zeros((C,), jnp.int32), sa0),
            jnp.arange(S))
        new = state._replace(x=xp[:, :n], cache=cache, key=knew,
                             accepts=state.accepts + acc)
        if not collect_stats:
            return new
        return new, SweepStats(site_prop=_site_hits(i, n), site_acc=sa)

    return sweep


def _build_double_min_sweep_pallas(graph: MatchGraph, lam1: float,
                                   capacity1: int, lam2: float,
                                   capacity2: int, sweep_len: int, *,
                                   collect_stats: bool = False):
    """Pallas schedule of the DoubleMIN sweep chain (host-drawn streams
    feeding ``kernel_ops.double_min_sweep``; bit-exact vs the jnp oracle in
    interpret mode)."""
    n, D, S = graph.n, graph.D, sweep_len
    K1, K2 = capacity1, capacity2
    kernel_ops.check_sweep_vmem("doublemin", n=n, D=D, S=S, K=K1, K2=K2)
    scale1 = float(graph.L / lam1)
    lscale2 = float(np.log1p(graph.psi / lam2))
    node_prob, node_alias = _node_alias_table(graph)

    def sweep(state: ChainState, sites=None, evidence=None):
        with _span("repro.sweep.draws"):
            (ki, kb1, k1, k2, kg, kb2, k3, k4, k5, k6, ka,
             knew) = _batch_keys(state.key, 12)
            i = _draw_sites(ki, state.x.shape[0], S, n, sites, evidence,
                            per_chain=True)                # (C, S)
            lam_i = lam1 * graph.row_sum[i] / graph.L      # (C, S)
            B1 = jnp.minimum(jax.vmap(lambda k, l: jax.random.poisson(
                k, l, dtype=jnp.int32))(kb1, lam_i), K1)
            u_idx = jax.vmap(lambda k: jax.random.uniform(k, (S, K1)))(k1)
            u_alias = jax.vmap(lambda k: jax.random.uniform(k, (S, K1)))(k2)
            gumbel = jax.vmap(lambda k: jax.random.gumbel(k, (S, D)))(kg)
            B2 = jnp.minimum(jax.vmap(lambda k: jax.random.poisson(
                k, lam2, (S,), dtype=jnp.int32))(kb2), K2)
            v1, v2, v3, v4 = (jax.vmap(lambda k: jax.random.uniform(
                k, (S, K2)))(ks) for ks in (k3, k4, k5, k6))
            logu = jnp.log(jax.vmap(lambda k: jax.random.uniform(
                k, (S,)))(ka))
        with _span("repro.sweep.kernel"):
            x, cache, acc = kernel_ops.double_min_sweep(
                state.x, graph.row_prob, graph.row_alias, node_prob,
                node_alias, i, B1, u_idx, u_alias, gumbel, B2, v1, v2, v3,
                v4, logu, state.cache, D=D, scale1=scale1, lscale2=lscale2,
                impl="pallas")
        new = state._replace(x=x, cache=cache, key=knew,
                             accepts=state.accepts + acc)
        if not collect_stats:
            return new
        # acceptance stays inside the kernel: per-site acceptances are
        # reported as accepted *moves* (value changes) — a lower bound the
        # jnp schedule sharpens to exact counts
        moves = jnp.sum(state.x != x, axis=0, dtype=jnp.float32)
        return new, SweepStats(site_prop=_site_hits(i, n), site_acc=moves)

    return sweep


# ---------------------------------------------------------------------------
# Chromatic block sweep: color classes through the fused sweep kernel
# ---------------------------------------------------------------------------

def validate_coloring(graph: MatchGraph, colors) -> list:
    """Check ``colors`` is a proper coloring of ``graph`` (non-empty
    classes, no same-color factors) and return the color classes as numpy
    index arrays.  Shared by the fused and distributed chromatic paths."""
    colors = np.asarray(colors)
    n = graph.n
    if colors.shape != (n,):
        raise ValueError(f"colors must have shape ({n},), got {colors.shape}")
    n_colors = int(colors.max()) + 1
    classes = [np.flatnonzero(colors == c) for c in range(n_colors)]
    W = np.asarray(graph.W)
    for c, sites in enumerate(classes):
        if sites.size == 0:
            raise ValueError(f"color class {c} is empty")
        if np.any(W[np.ix_(sites, sites)] != 0.0):
            raise ValueError(
                f"colors is not a proper coloring: class {c} shares factors")
    return classes


def _build_chromatic_gibbs_sweep(graph: MatchGraph, colors, *,
                                 impl: str, collect_stats: bool = False):
    """One full chromatic Gibbs sweep per call: every color class updated as
    a block through the fused sweep kernel (``kernel_ops.gibbs_sweep``).

    Same-color sites share no factor (checked at build time), so the
    kernel's sequential S-loop over a class IS the parallel block update:
    W[i, j] = 0 for every earlier same-class site j means each in-class
    update reads energies of the frozen entry state.  Per color class c the
    draw protocol is bit-compatible with ``make_chromatic_gibbs_step``'s
    dense path — ``kv, = split(key_c, 1)``, full-lattice Gumbel noise
    ``gumbel(kv, (C, n, D))`` sliced at the class sites (``categorical``
    IS argmax(logits + gumbel)) — so the two paths match exactly.
    ``updates_per_call`` is n: one call updates every site once.

    ``evidence=`` (an ``(ev_mask, ev_vals)`` pair) re-clamps x after every
    color-class block: the fused kernel resamples whole classes (including
    any observed sites in them) and later classes condition on earlier
    ones, so the clamp must be restored *between* classes, not once at the
    end.  Same-color sites share no factor, so a temporarily-resampled
    observed site is never read by its own class; every unobserved update
    therefore sees exactly the evidence-clamped configuration.  An
    all-zero mask is the unconditional sweep (bitwise: ``where`` with a
    false mask is the identity), sharing one jit trace.
    """
    _check_impl(impl)
    n, D = graph.n, graph.D
    classes = [jnp.asarray(s, jnp.int32)
               for s in validate_coloring(graph, colors)]
    n_colors = len(classes)
    if impl == "pallas":
        kernel_ops.check_sweep_vmem("gibbs", n=n, D=D,
                                    S=max(int(c.shape[0]) for c in classes))

    def sweep(state: ChainState, evidence=None):
        C = state.x.shape[0]
        knew, master = _master_key(state.key)
        keys = jax.random.split(master, n_colors)
        x = state.x
        if evidence is not None:
            obs = evidence[0][None, :] > 0.0                  # (1, n)
            ev_x = jnp.broadcast_to(evidence[1][None, :], x.shape)
        for c, sites in enumerate(classes):   # static unroll over colors
            kv, = jax.random.split(keys[c], 1)
            gumbel = jax.random.gumbel(kv, (C, n, D))[:, sites, :]
            i_sites = jnp.broadcast_to(sites[None, :], (C, sites.shape[0]))
            x = kernel_ops.gibbs_sweep(x, graph.W, i_sites, gumbel, D=D,
                                       impl=impl)
            if evidence is not None:
                x = jnp.where(obs, ev_x, x)
        new = state._replace(x=x, key=knew)
        if not collect_stats:
            return new
        # one full sweep: every site updated exactly once per chain, all
        # updates exact block Gibbs (acceptance == 1)
        hits = jnp.full((n,), jnp.float32(1.0)) * C
        return new, SweepStats(site_prop=hits, site_acc=hits)

    return sweep


# ---------------------------------------------------------------------------
# Generic fallback: a batched sweep from any single-chain step
# ---------------------------------------------------------------------------

def _build_step_sweep(step, sweep_len: int):
    """``sweep_len`` scanned applications of the vmapped single-chain
    ``step`` — the sweep scaffold for algorithms without a fused schedule
    (currently local-gibbs)."""
    vstep = jax.vmap(step)

    def sweep(state: ChainState) -> ChainState:
        out, _ = jax.lax.scan(lambda s, _: (vstep(s), None), state, None,
                              length=sweep_len)
        return out

    return sweep


# ---------------------------------------------------------------------------
# Deprecation shims (pre-engine public factories)
# ---------------------------------------------------------------------------

def _deprecated_sweep(name: str, engine):
    warnings.warn(
        f"{name} is deprecated; use repro.core.engine.make(...) which "
        f"returns an Engine with explicit updates_per_call/backend metadata",
        DeprecationWarning, stacklevel=3)
    sweep = engine.sweep_fn
    sweep.batched = True                      # legacy markers; nothing in
    sweep.updates_per_call = engine.updates_per_call   # repo reads them now
    return sweep


def make_gibbs_sweep(graph: MatchGraph, sweep_len: int, *,
                     impl: str = "auto"):
    """Deprecated: use ``engine.make("gibbs", graph, sweep=S, backend=...)``."""
    from . import engine
    return _deprecated_sweep(
        "make_gibbs_sweep",
        engine.make("gibbs", graph, sweep=sweep_len, backend=impl))


def make_mgpmh_sweep(graph: MatchGraph, lam: float, capacity: int,
                     sweep_len: int, *, impl: str = "auto"):
    """Deprecated: use ``engine.make("mgpmh", graph, sweep=S, backend=...)``."""
    from . import engine
    return _deprecated_sweep(
        "make_mgpmh_sweep",
        engine.make("mgpmh", graph, sweep=sweep_len, backend=impl,
                    lam=lam, capacity=capacity))
