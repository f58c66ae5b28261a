"""The unified sampler Engine API: one contract over single-site,
fused-sweep, chromatic, and distributed execution paths.

De Sa et al.'s five algorithms differ only in their estimator and
acceptance rule; execution is always "advance every chain by some number of
site updates".  This module makes that the *only* surface consumers see:

  engine = make("mgpmh", graph, sweep=64, backend="auto")
  state  = engine.init(jax.random.PRNGKey(0), n_chains=256)
  state  = engine.sweep(state)          # always batched: x is (C, n)

An :class:`Engine` carries explicit metadata — ``updates_per_call``,
``marginal_samples_per_call``, ``backend``, ``schedule`` — so nothing
downstream sniffs ``batched`` / ``updates_per_call`` attributes off bare
functions (``chains.run_marginal_experiment`` accepts only Engines).

Schedules decide *which sites* a call updates:
  * :class:`UniformSites(S)` — S sequentially composed i.i.d.-uniform site
    updates per call (the paper's update loop, fused S-at-a-time);
  * :class:`ChromaticBlocks(colors)` — one full sweep per call: each color
    class updated as a parallel block through the fused sweep kernel
    (valid for proper colorings; exact block Gibbs).

Backends decide *where* the sweep runs:
  * ``"jnp"``    — fused pure-jnp schedules tuned for CPU/GPU;
  * ``"pallas"`` — the fused Pallas TPU kernel (interpret mode off-TPU);
    raises :class:`~repro.kernels.ops.KernelTooLarge` when the kernel's
    VMEM working set for the graph exceeds the chip's;
  * ``"dist"``   — shard_map over a (data, model) mesh (graph column-
    sharded, one psum per sweep; ``runtime/dist_gibbs.py``), pass ``mesh=``;
  * ``"auto"``   — pallas on TPU where the kernel fits VMEM, jnp elsewhere
    (``Engine.describe()`` names the backend it picked).

The registry (`register` / `make` / `names`) subsumes the previous three
divergent construction paths (``make_*_step``, ``make_*_sweep``,
``make_dist_*``); the single-host sweep factories survive only as
deprecation shims and the hand-written ``make_dist_*`` family is gone —
the distributed sweep-kernel template (``runtime/dist_gibbs.py``) builds
every dist engine.  The
workload registry (`WORKLOADS` / `make_workload`) names the paper's
experimental models plus the sparse lattice Ising where chromatic
scheduling applies.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from .factor_graph import (MatchGraph, NeighborGraph, make_ising_graph,
                           make_potts_graph, make_lattice_ising,
                           lattice_colors, make_pair_ising, pair_colors)
from .estimators import (recommended_capacity, draw_global_minibatch,
                         min_gibbs_estimate)
from . import samplers as S
from ..kernels.ops import KernelTooLarge
from ..obs import get_recorder

__all__ = [
    "Engine", "Schedule", "UniformSites", "ChromaticBlocks", "AdaptiveScan",
    "make", "names", "backends", "register",
    "Workload", "WORKLOADS", "make_workload", "workload_names",
]


# ---------------------------------------------------------------------------
# Schedules
# ---------------------------------------------------------------------------

class Schedule:
    """Site-selection policy of one ``sweep`` call."""

    def describe(self) -> str:
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class UniformSites(Schedule):
    """``sweep_len`` sequentially composed updates at i.i.d.-uniform sites
    per call — the paper's update loop, fused S at a time."""
    sweep_len: int = 1

    def __post_init__(self):
        if self.sweep_len < 1:
            raise ValueError(f"sweep_len must be >= 1, got {self.sweep_len}")

    def describe(self) -> str:
        return f"uniform-sites(S={self.sweep_len})"


@dataclasses.dataclass(frozen=True)
class ChromaticBlocks(Schedule):
    """One full chromatic sweep per call: every color class updated as a
    parallel block (through the fused sweep kernel — same-color sites share
    no factor, so the kernel's sequential loop IS the block update).

    ``colors`` is a per-site color id array; stored as a tuple so schedules
    are hashable (jit-static).  Exact for proper colorings (checked at
    engine build time).
    """
    colors: Tuple[int, ...]

    def __post_init__(self):
        object.__setattr__(self, "colors",
                           tuple(int(c) for c in np.asarray(self.colors)))

    @property
    def colors_array(self) -> np.ndarray:
        return np.asarray(self.colors, np.int32)

    @property
    def n_colors(self) -> int:
        return max(self.colors) + 1

    def describe(self) -> str:
        return f"chromatic-blocks(k={self.n_colors}, n={len(self.colors)})"


@dataclasses.dataclass(frozen=True)
class AdaptiveScan(Schedule):
    """``sweep_len`` fused updates per call at sites drawn from a *learned*
    non-uniform distribution (gibbs / mgpmh / min-gibbs / doublemin
    engines on every backend — the cached-estimator samplers thread their
    eps/xi augmented state through the adaptive wrapper unchanged, and on
    ``backend="dist"`` the cross-shard table reduction rides the sweep's
    one psum).

    The selection table is driven by the streaming per-site telemetry the
    sweep itself collects (``repro.diagnostics``): sites that rarely change
    value per update ("sticky" — slow-mixing under the conditional) are
    upweighted in proportion to their estimated persistence, equalizing
    *independent* samples per site instead of raw updates.  The cumulative
    table is refreshed in-graph every ``refresh_every`` sweeps (no host
    sync; between refreshes the hot path is the same fused sweep at given
    sites), mixed with ``uniform_mix`` of the uniform distribution so every
    site keeps positive probability — each inter-refresh segment is a valid
    random-scan chain with the target stationary distribution.

    ``smoothing`` regularizes the inverse-flip-rate weight (sites with few
    observations stay near uniform).  Construction lives in
    ``repro.diagnostics.adaptive``; ``engine.make`` routes there.
    """
    sweep_len: int = 16
    refresh_every: int = 8
    uniform_mix: float = 0.25
    smoothing: float = 0.05

    def __post_init__(self):
        if self.sweep_len < 1 or self.refresh_every < 1:
            raise ValueError("sweep_len and refresh_every must be >= 1")
        if not (0.0 < self.uniform_mix <= 1.0):
            raise ValueError("uniform_mix must be in (0, 1] (a zero floor "
                             "can starve sites and break ergodicity)")

    def describe(self) -> str:
        return (f"adaptive-scan(S={self.sweep_len}, K={self.refresh_every}, "
                f"mix={self.uniform_mix})")


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------

@dataclasses.dataclass(eq=False, frozen=True)
class Engine:
    """A constructed sampler: ``init`` makes a batched state, ``sweep``
    advances it, metadata says how much work one call does.

    ``updates_per_call``          site updates one ``sweep`` call performs.
    ``marginal_samples_per_call`` snapshot samples one call contributes to a
                                  running marginal estimate (1: snapshots
                                  are amortized over the whole sweep).
    ``backend``                   'jnp' | 'pallas' | 'dist' (resolved, never
                                  'auto').
    ``exact_accept``              True for Gibbs-type engines whose every
                                  update is accepted by construction (MH
                                  acceptance == 1 identically).
    Hash/eq are identity so an Engine can be a jit-static argument.
    """
    name: str
    backend: str
    schedule: Schedule
    updates_per_call: int
    marginal_samples_per_call: int
    graph: Any              # MatchGraph, or NeighborGraph (chromatic gibbs)
    params: Dict[str, Any] = dataclasses.field(repr=False)
    init_fn: Callable = dataclasses.field(repr=False)
    sweep_fn: Callable = dataclasses.field(repr=False)
    # instrumented sweep variant: ``(state) -> (state, SweepStats)`` with
    # exact per-site counters; None where the backend can't surface them
    # (dist, local-gibbs) — telemetry then falls back to state diffs.
    sweep_stats_fn: Optional[Callable] = dataclasses.field(
        default=None, repr=False)
    exact_accept: bool = False
    # evidence clamping (the serving layer's per-request conditioning):
    # True when sweep_fn/sweep_stats_fn accept ``evidence=(ev_mask,
    # ev_vals)`` — the jnp/pallas gibbs-family schedules.  dist and
    # local-gibbs do not; Engine.sweep raises rather than silently
    # sampling the unconditional chain.
    supports_evidence: bool = False
    # ``(key, ChainState) -> ChainState`` (single chain; vmapped by
    # Engine.clamp): re-draws the cached energy estimate at the CURRENT x
    # — the MIN-Gibbs eps / DoubleMIN xi cache estimates the energy of the
    # pre-clamp configuration and is stale after evidence overwrites x.
    refresh_cache_fn: Optional[Callable] = dataclasses.field(
        default=None, repr=False)
    # the dist backend's graph tables as placed on its mesh (name -> array
    # sharded over the model axis); None on the single-device backends
    device_tables: Optional[Dict[str, jax.Array]] = dataclasses.field(
        default=None, repr=False)

    def init(self, key: jax.Array, n_chains: int, **kwargs):
        """Batched initial state for ``n_chains`` chains (cached-estimator
        algorithms get their eps/xi cache initialized here)."""
        return self.init_fn(key, n_chains, **kwargs)

    def init_telemetry(self, state, half_at: Optional[int] = None,
                       lags: int = 8):
        """Zeroed :class:`~repro.diagnostics.telemetry.Telemetry` sized for
        ``state`` (pass ``half_at=total_snapshots // 2`` for split-R-hat;
        ``lags`` sets the depth of the ESS autocovariance ring)."""
        from ..diagnostics.telemetry import telemetry_init
        return telemetry_init(state.x, half_at=half_at, lags=lags)

    def sweep(self, state, telemetry=None, evidence=None):
        """Advance every chain by ``updates_per_call`` site updates.

        With ``telemetry=`` (a :class:`~repro.diagnostics.telemetry.
        Telemetry` carry from :meth:`init_telemetry`) the call returns
        ``(state, telemetry)``: the streaming convergence statistics are
        updated from the instrumented sweep where available and from state
        diffs otherwise — device-resident, no host sync, safe inside scan.

        With ``evidence=`` (an ``(ev_mask (n,) float32, ev_vals (n,)
        int32)`` pair of data arrays) the sweep samples the CONDITIONAL
        chain given ``x[i] = ev_vals[i]`` wherever ``ev_mask[i] == 1``:
        site selection is redirected through the masked inverse-CDF (the
        chromatic schedule re-clamps between color classes instead).
        Evidence is data, not structure — an all-zero mask is the
        unconditional chain and shares the same jit trace.  The state must
        already be clamped at the observed sites (:meth:`clamp`).  Raises
        for engines without ``supports_evidence`` (dist, local-gibbs).

        The 'dist' backend DONATES the input state (its buffers are dead
        after the call — rebind, don't reuse: ``st = eng.sweep(st)``); the
        jnp/pallas backends leave the input intact.
        """
        if evidence is not None and not self.supports_evidence:
            raise ValueError(
                f"engine {self.name!r} (backend {self.backend!r}, schedule "
                f"{self.schedule.describe()}) does not support evidence "
                f"clamping; serve conditioned queries from a jnp/pallas "
                f"gibbs-family engine")
        kw = {} if evidence is None else {"evidence": evidence}
        # the whole call's host time; the fused-Pallas builders open
        # ``repro.sweep.draws`` and ``repro.sweep.kernel`` inside it
        with get_recorder().span("repro.sweep"):
            if telemetry is None:
                return self.sweep_fn(state, **kw)
            from ..diagnostics.telemetry import telemetry_update
            old_x = state.x
            old_acc = getattr(state, "accepts", None)
            if self.backend == "dist":    # sweep donates the input buffers
                old_x = jnp.copy(old_x)
                old_acc = None if old_acc is None else jnp.copy(old_acc)
            if self.sweep_stats_fn is not None:
                new, stats = self.sweep_stats_fn(state, **kw)
            else:
                new, stats = self.sweep_fn(state, **kw), None
            delta = None if old_acc is None else new.accepts - old_acc
            # health hooks: the state's cached energy + the site domain feed
            # the in-graph guards (bad_state flag, windowed acceptance)
            # riding the telemetry carry — no host sync on this path
            telemetry = telemetry_update(
                telemetry, old_x, new.x, self.updates_per_call, delta,
                stats, cache=getattr(new, "cache", None),
                n_values=self.graph.D)
            return new, telemetry

    def clamp(self, key: jax.Array, state, evidence):
        """Overwrite the observed sites of every chain with their evidence
        values and return the clamped state.

        ``evidence = (ev_mask (n,) float32, ev_vals (n,) int32)``; sites
        with ``ev_mask == 1`` are set to ``ev_vals``, the rest keep their
        current value (so a conditioned chain forked from a warm resident
        snapshot starts from the resident's unobserved coordinates — a far
        better init than cold-start).  For engines with a cached energy
        estimate (MIN-Gibbs eps, DoubleMIN xi) the cache is re-drawn at the
        clamped configuration via ``refresh_cache_fn`` — the old cache
        estimates the pre-clamp energy and would bias the first accepts.
        Handles the AdaptiveScan state wrapper transparently.
        """
        ev_mask, ev_vals = evidence
        inner = getattr(state, "inner", None)
        st = state if inner is None else inner
        x = jnp.where(ev_mask[None, :] > 0.0,
                      ev_vals[None, :].astype(st.x.dtype), st.x)
        st = st._replace(x=x)
        if self.refresh_cache_fn is not None:
            ck = jax.random.split(key, x.shape[0])
            st = jax.vmap(self.refresh_cache_fn)(ck, st)
        return st if inner is None else state._replace(inner=st)

    def describe(self) -> Dict[str, Any]:
        """Machine-readable identity (benchmarks attach this to records).
        ``interpret`` is True when the pallas backend runs its kernels in
        interpret mode (off-TPU) instead of compiled with Mosaic."""
        from ..kernels.ops import interpret
        return {"engine": self.name, "backend": self.backend,
                "interpret": self.backend == "pallas" and interpret(),
                "schedule": self.schedule.describe(),
                "updates_per_call": self.updates_per_call}


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_BUILDERS: Dict[str, Tuple[Callable, Tuple[str, ...]]] = {}


def register(name: str, *, backends: Tuple[str, ...]):
    """Register an engine builder under ``name``.

    The builder is called as ``builder(graph, schedule=..., backend=...,
    mesh=..., **params)`` with ``backend`` already resolved and validated
    against ``backends``.
    """
    def deco(builder):
        _BUILDERS[name] = (builder, tuple(backends))
        return builder
    return deco


def names() -> Tuple[str, ...]:
    """Registered engine names."""
    return tuple(sorted(_BUILDERS))


def backends(name: str) -> Tuple[str, ...]:
    """Backends supported by engine ``name``."""
    return _BUILDERS[name][1]


def make(name: str, graph: MatchGraph, *, sweep: Optional[int] = None,
         schedule: Optional[Schedule] = None, backend: str = "auto",
         mesh=None, **params) -> Engine:
    """Build an :class:`Engine` by registry name.

    ``sweep=S`` is shorthand for ``schedule=UniformSites(S)``; pass a
    :class:`Schedule` for anything else — :class:`ChromaticBlocks` (gibbs)
    or :class:`AdaptiveScan` (gibbs/mgpmh/min-gibbs/doublemin,
    telemetry-driven non-uniform site selection; state carries its own
    diagnostics).  ``schedule="chromatic"`` is
    ``ChromaticBlocks(graph.colors)`` for a graph that carries its
    colouring; ``sweep=`` is then accepted only as n, the updates one call
    makes.  ``make`` dispatches on the graph's layout: a
    :class:`~repro.core.factor_graph.NeighborGraph` runs gibbs under the
    chromatic schedule on jnp, the parallel neighbour-list sweep; every
    other engine, schedule or backend refuses it.  ``backend`` is
    'auto' | 'pallas' | 'jnp' | 'dist' ('dist' needs ``mesh=``).  Algorithm
    parameters (lam, capacity, ...) are keyword ``params`` with
    paper-recipe defaults.
    """
    if name not in _BUILDERS:
        raise KeyError(f"unknown engine {name!r}; available: {list(names())}")
    builder, supported = _BUILDERS[name]
    if schedule == "chromatic":
        colors = getattr(graph, "colors", None)
        if colors is None:
            raise ValueError("schedule='chromatic' needs a graph that "
                             "carries its colouring (a NeighborGraph); pass "
                             "schedule=ChromaticBlocks(colors) for a dense "
                             "graph")
        if sweep is not None and sweep != graph.n:
            raise ValueError(f"schedule='chromatic' updates every site once "
                             f"a call: sweep= must be n={graph.n} or left "
                             f"out, got {sweep}")
        schedule, sweep = ChromaticBlocks(colors), None
    if schedule is None:
        schedule = UniformSites(sweep if sweep is not None else 1)
    elif sweep is not None:
        raise ValueError("pass either sweep= or schedule=, not both")
    if not isinstance(schedule, Schedule):
        raise TypeError(f"schedule must be a Schedule or 'chromatic', got "
                        f"{schedule!r}")
    if isinstance(graph, NeighborGraph):
        if name != "gibbs":
            raise ValueError(
                f"engine {name!r} needs the dense MatchGraph layout; the "
                f"neighbour-list layout runs gibbs only (minibatching buys "
                f"nothing at bounded degree, Delta={graph.delta})")
        if not isinstance(schedule, ChromaticBlocks):
            raise ValueError(f"the neighbour-list layout runs the chromatic "
                             f"schedule only, got {schedule.describe()}")
        if backend not in ("auto", "jnp"):
            raise ValueError(f"the neighbour-list layout runs on backend "
                             f"'jnp' only, got {backend!r}")
        backend = "jnp"
    if backend == "auto":
        if jax.default_backend() == "tpu" and "pallas" in supported:
            try:
                return builder(graph, schedule=schedule, backend="pallas",
                               mesh=mesh, **params)
            except KernelTooLarge:
                pass                  # the graph outgrows the kernel's VMEM
        backend = "jnp"
    if backend not in supported:
        raise ValueError(f"engine {name!r} supports backends {supported}, "
                         f"got {backend!r}")
    if backend == "dist" and mesh is None:
        raise ValueError("backend='dist' requires mesh=")
    return builder(graph, schedule=schedule, backend=backend, mesh=mesh,
                   **params)


# ---------------------------------------------------------------------------
# Shared construction pieces
# ---------------------------------------------------------------------------

def _chain_init(graph: MatchGraph, cache_init: Optional[Callable] = None):
    """Batched ChainState init; ``cache_init(key, state) -> state`` (vmapped
    here) seeds the augmented-energy cache when the algorithm has one."""
    def init(key: jax.Array, n_chains: int, *, start: str = "constant"):
        keys = jax.random.split(key, n_chains)
        st = jax.vmap(lambda k: S.init_state(k, graph, start=start))(keys)
        if cache_init is not None:
            ck = jax.random.split(jax.random.fold_in(key, 0x5eed), n_chains)
            st = jax.vmap(cache_init)(ck, st)
        return st
    return init


def _uniform_or_chromatic(graph, schedule, backend, uniform_builder):
    """Dispatch the gibbs-family schedule: UniformSites -> fused sweep of
    ``sweep_len``; ChromaticBlocks -> color-class blocks through the fused
    kernel.  ``uniform_builder(sweep_len, collect_stats)`` builds the plain
    and instrumented variants; returns (sweep_fn, stats_fn, upd)."""
    if isinstance(schedule, ChromaticBlocks):
        build = lambda cs: S._build_chromatic_gibbs_sweep(
            graph, schedule.colors_array, impl=backend, collect_stats=cs)
        return build(False), build(True), graph.n
    sl = schedule.sweep_len
    return (uniform_builder(sl, False), uniform_builder(sl, True), sl)


def _engine(name, backend, schedule, upd, graph, params, init_fn, sweep_fn,
            stats_fn=None, exact_accept=False, supports_evidence=False,
            refresh_cache=None):
    return Engine(name=name, backend=backend, schedule=schedule,
                  updates_per_call=upd, marginal_samples_per_call=1,
                  graph=graph, params=params, init_fn=init_fn,
                  sweep_fn=sweep_fn, sweep_stats_fn=stats_fn,
                  exact_accept=exact_accept,
                  supports_evidence=supports_evidence,
                  refresh_cache_fn=refresh_cache)


def _reject_unknown(name, params):
    if params:
        raise TypeError(f"engine {name!r} got unknown params "
                        f"{sorted(params)}")


# ---------------------------------------------------------------------------
# The five paper algorithms
# ---------------------------------------------------------------------------

@register("gibbs", backends=("jnp", "pallas", "dist"))
def _gibbs_builder(graph, *, schedule, backend, mesh, **params):
    _reject_unknown("gibbs", params)
    if isinstance(graph, NeighborGraph):      # make() admitted it: jnp
        build = lambda cs: S._build_neighbor_chromatic_sweep(
            graph, collect_stats=cs)
        return _engine("gibbs", backend, schedule, graph.n, graph, {},
                       _chain_init(graph), build(False), stats_fn=build(True),
                       exact_accept=True, supports_evidence=True)
    if backend == "dist":
        return _dist_engine("gibbs", graph, schedule, mesh, {})
    if isinstance(schedule, AdaptiveScan):
        from ..diagnostics.adaptive import make_adaptive_engine
        return make_adaptive_engine(
            "gibbs", graph, schedule, backend,
            core=S._build_gibbs_sweep(graph, schedule.sweep_len,
                                      impl=backend, collect_stats=True),
            chain_init=_chain_init(graph), params={}, exact_accept=True)
    sweep_fn, stats_fn, upd = _uniform_or_chromatic(
        graph, schedule, backend,
        lambda sl, cs: S._build_gibbs_sweep(graph, sl, impl=backend,
                                            collect_stats=cs))
    return _engine("gibbs", backend, schedule, upd, graph, {},
                   _chain_init(graph), sweep_fn, stats_fn=stats_fn,
                   exact_accept=True, supports_evidence=True)


@register("min-gibbs", backends=("jnp", "pallas", "dist"))
def _min_gibbs_builder(graph, *, schedule, backend, mesh, lam=None,
                       capacity=None, **params):
    _reject_unknown("min-gibbs", params)
    # paper recipe 2 Psi^2, capped: the sweep's per-sub-step draw buffers
    # are O(C*D*capacity) and capacity ~ lam, so an uncapped default still
    # OOMs on the large registered workloads; pass lam= explicitly to
    # exceed it (on TPU the in-kernel-PRNG kernel lifts the ceiling)
    lam = float(min(2.0 * graph.psi ** 2, 16384.0)) if lam is None \
        else float(lam)
    if backend == "dist":
        return _dist_engine("min-gibbs", graph, schedule, mesh,
                            dict(lam=lam, capacity=capacity))
    capacity = recommended_capacity(lam) if capacity is None else capacity
    cache_init = lambda k, st: S.init_min_gibbs_cache(k, graph, st, lam,
                                                      capacity)
    build = lambda cs: S._build_min_gibbs_sweep(
        graph, lam, capacity, schedule.sweep_len, impl=backend,
        collect_stats=cs)
    if isinstance(schedule, AdaptiveScan):
        from ..diagnostics.adaptive import make_adaptive_engine
        return make_adaptive_engine(
            "min-gibbs", graph, schedule, backend, core=build(True),
            chain_init=_chain_init(graph, cache_init),
            params=dict(lam=lam, capacity=capacity), exact_accept=True,
            refresh_cache=cache_init)
    _require_uniform("min-gibbs", schedule)
    return _engine(
        "min-gibbs", backend, schedule, schedule.sweep_len, graph,
        dict(lam=lam, capacity=capacity),
        _chain_init(graph, cache_init), build(False), stats_fn=build(True),
        exact_accept=True, supports_evidence=True,
        refresh_cache=cache_init)


@register("local-gibbs", backends=("jnp",))
def _local_gibbs_builder(graph, *, schedule, backend, mesh, batch_size=None,
                         **params):
    _reject_unknown("local-gibbs", params)
    _require_uniform("local-gibbs", schedule)
    batch_size = min(32, graph.n - 1) if batch_size is None else batch_size
    step = S.make_local_gibbs_step(graph, batch_size)
    return _engine(
        "local-gibbs", backend, schedule, schedule.sweep_len, graph,
        dict(batch_size=batch_size), _chain_init(graph),
        S._build_step_sweep(step, schedule.sweep_len), exact_accept=True)


@register("mgpmh", backends=("jnp", "pallas", "dist"))
def _mgpmh_builder(graph, *, schedule, backend, mesh, lam=None,
                   capacity=None, **params):
    _reject_unknown("mgpmh", params)
    lam = float(4.0 * graph.L ** 2) if lam is None else float(lam)
    if backend == "dist":
        return _dist_engine("mgpmh", graph, schedule, mesh,
                            dict(lam=lam, capacity=capacity))
    capacity = recommended_capacity(lam) if capacity is None else capacity
    if isinstance(schedule, AdaptiveScan):
        from ..diagnostics.adaptive import make_adaptive_engine
        return make_adaptive_engine(
            "mgpmh", graph, schedule, backend,
            core=S._build_mgpmh_sweep(graph, lam, capacity,
                                      schedule.sweep_len, impl=backend,
                                      collect_stats=True),
            chain_init=_chain_init(graph),
            params=dict(lam=lam, capacity=capacity))
    _require_uniform("mgpmh", schedule)
    build = lambda cs: S._build_mgpmh_sweep(
        graph, lam, capacity, schedule.sweep_len, impl=backend,
        collect_stats=cs)
    return _engine(
        "mgpmh", backend, schedule, schedule.sweep_len, graph,
        dict(lam=lam, capacity=capacity), _chain_init(graph),
        build(False), stats_fn=build(True), supports_evidence=True)


@register("doublemin", backends=("jnp", "pallas", "dist"))
def _doublemin_builder(graph, *, schedule, backend, mesh, lam1=None,
                       capacity1=None, lam2=None, capacity2=None, **params):
    _reject_unknown("doublemin", params)
    lam1 = float(4.0 * graph.L ** 2) if lam1 is None else float(lam1)
    # second-batch default: 2 Psi^2, capped so the (C, capacity2) factor-draw
    # buffer stays bounded on large graphs (matching accuracy is then
    # tail-bound- rather than recipe-limited)
    lam2 = float(min(2.0 * graph.psi ** 2, 16384.0)) if lam2 is None \
        else float(lam2)
    if backend == "dist":
        return _dist_engine("doublemin", graph, schedule, mesh,
                            dict(lam1=lam1, capacity1=capacity1,
                                 lam2=lam2, capacity2=capacity2))
    capacity1 = recommended_capacity(lam1) if capacity1 is None else capacity1
    capacity2 = recommended_capacity(lam2) if capacity2 is None else capacity2
    cache_init = lambda k, st: S.init_double_min_cache(k, graph, st, lam2,
                                                       capacity2)
    build = lambda cs: S._build_double_min_sweep(
        graph, lam1, capacity1, lam2, capacity2, schedule.sweep_len,
        impl=backend, collect_stats=cs)
    params_d = dict(lam1=lam1, capacity1=capacity1, lam2=lam2,
                    capacity2=capacity2)
    if isinstance(schedule, AdaptiveScan):
        from ..diagnostics.adaptive import make_adaptive_engine
        return make_adaptive_engine(
            "doublemin", graph, schedule, backend, core=build(True),
            chain_init=_chain_init(graph, cache_init), params=params_d,
            refresh_cache=cache_init)
    _require_uniform("doublemin", schedule)
    return _engine(
        "doublemin", backend, schedule, schedule.sweep_len, graph, params_d,
        _chain_init(graph, cache_init), build(False), stats_fn=build(True),
        supports_evidence=True, refresh_cache=cache_init)


def _require_uniform(name, schedule):
    if not isinstance(schedule, UniformSites):
        raise ValueError(f"engine {name!r} supports only the UniformSites "
                         f"schedule, got {schedule.describe()}")


# ---------------------------------------------------------------------------
# Distributed backend (shard_map over a (data, model) mesh)
# ---------------------------------------------------------------------------

def _dist_unsupported(name: str, schedule: Schedule) -> ValueError:
    """The ONE error the dist backend raises for an unsupported request,
    always naming the full supported (engine, schedule) table."""
    return ValueError(
        f"backend='dist' supports (engine, schedule) combinations: "
        f"gibbs/mgpmh/min-gibbs/doublemin x UniformSites(S >= 1), "
        f"gibbs/mgpmh/min-gibbs/doublemin x AdaptiveScan, and "
        f"gibbs x ChromaticBlocks; got engine {name!r} with schedule "
        f"{schedule.describe()}")


def _dist_engine(name: str, graph: MatchGraph, schedule: Schedule, mesh,
                 params: Dict[str, Any]) -> Engine:
    """Wrap the ``runtime/dist_gibbs`` sweep template: graph column-sharded
    over the model axis, chains over the data axes, state/marginals carried
    in a DistState (DistAdaptiveState under AdaptiveScan).  One jitted
    shard_map'd sweep per call — ONE psum per sweep on the uniform/adaptive
    schedules, one per color class on the chromatic schedule — with
    donated state."""
    from ..runtime import dist_gibbs as DG
    from ..launch.mesh import dp_axes, MP_AXIS

    mp = mesh.shape[MP_AXIS]
    dps = dp_axes(mesh)                       # ("data",) or ("pod", "data")
    dp_shape = tuple(mesh.shape[a] for a in dps)
    dp = int(np.prod(dp_shape))
    if graph.n % mp:
        raise ValueError(f"graph.n={graph.n} must divide into mp={mp} "
                         f"column shards")
    if name not in DG.DIST_ALGOS:
        raise _dist_unsupported(name, schedule)
    chromatic = isinstance(schedule, ChromaticBlocks)
    adaptive = isinstance(schedule, AdaptiveScan)
    if chromatic and name != "gibbs":
        raise _dist_unsupported(name, schedule)
    if not (chromatic or adaptive or isinstance(schedule, UniformSites)):
        raise _dist_unsupported(name, schedule)

    # shard only the graph tables this algorithm reads: the per-row alias
    # builds are n python loops per shard, prohibitive at lattice scale
    # for the algorithms (gibbs, chromatic) that never draw from them
    gs = DG.ShardedMatchGraph.from_graph(
        graph, mp, row_tables=name in ("mgpmh", "doublemin"),
        pair_tables=name in ("min-gibbs", "doublemin"))

    # paper-recipe defaults; capacities sized for the WORST per-shard
    # thinned rate (shard ownership can be skewed — sizing for the uniform
    # lam/mp silently truncates the hot shard's Poisson draws and biases
    # the estimator)
    def cap_rows(lam, explicit):
        if explicit is not None:
            return explicit
        frac = float(np.max(np.asarray(gs.row_sum))) / graph.L
        return recommended_capacity(max(lam * frac, 1.0)) + 8

    def cap_pairs(lam, explicit):
        if explicit is not None:
            return explicit
        frac = float(np.max(np.asarray(gs.psi_loc))) / graph.psi
        return recommended_capacity(max(lam * frac, 1.0)) + 8

    def global_cache_fn(lam_g):
        # seed the cached eps/xi with one full-rate estimator draw (same
        # estimator the per-shard thinned psum realizes; Engine.init's
        # cache contract holds on every backend)
        cap_full = recommended_capacity(lam_g)

        def cache_fn(k, x):
            idx, B = draw_global_minibatch(k, graph, lam_g, cap_full)
            return min_gibbs_estimate(graph, x, idx, B, lam_g)
        return cache_fn

    cache_fn = None
    if name == "gibbs":
        resolved, algo_params = {}, {}
    elif name == "mgpmh":
        lam = params["lam"]
        capacity = cap_rows(lam, params.get("capacity"))
        resolved = algo_params = dict(lam=lam, capacity=capacity)
    elif name == "min-gibbs":
        lam = params["lam"]
        capacity = cap_pairs(lam, params.get("capacity"))
        resolved = algo_params = dict(lam=lam, capacity=capacity)
        cache_fn = global_cache_fn(lam)
    else:  # doublemin
        lam1, lam2 = params["lam1"], params["lam2"]
        c1 = cap_rows(lam1, params.get("capacity1"))
        c2 = cap_pairs(lam2, params.get("capacity2"))
        resolved = dict(lam1=lam1, capacity1=c1, lam2=lam2, capacity2=c2)
        algo_params = dict(lam=lam1, capacity=c1, lam2=lam2, capacity2=c2)
        cache_fn = global_cache_fn(lam2)

    mesh_info = (dps, dp_shape, mp)
    if chromatic:
        S.validate_coloring(graph, schedule.colors_array)
        step = DG.make_dist_chromatic_sweep(gs, schedule.colors_array)
        upd = graph.n
        st_specs = DG.state_specs(dp_axes=dps)
    elif adaptive:
        step = DG.make_dist_adaptive_sweep(gs, name, schedule,
                                           mesh_info=mesh_info,
                                           **algo_params)
        upd = schedule.sweep_len
        st_specs = DG.adaptive_state_specs(dp_axes=dps)
    else:
        step = DG.make_dist_sweep(gs, name, schedule.sweep_len,
                                  mesh_info=mesh_info, **algo_params)
        upd = schedule.sweep_len
        st_specs = DG.state_specs(dp_axes=dps)

    sh_specs = DG.shard_specs()
    smapped = jax.shard_map(lambda st, sh: step(st, sh), mesh=mesh,
                            in_specs=(st_specs, sh_specs),
                            out_specs=st_specs, check_vma=False)
    # the graph tables are placed on the mesh once: each model shard's
    # device holds its column slab, and no call re-lays them out
    sh = {k: jax.device_put(getattr(gs, k), NamedSharding(mesh, spec))
          for k, spec in sh_specs.items()}
    # state donation: avoids double-buffering the (C, n, D) marginal sums
    # at scale; Engine.sweep documents the rebind-don't-reuse contract
    jstep = jax.jit(smapped, donate_argnums=(0,))

    def sweep_fn(state):
        with mesh:
            return jstep(state, sh)

    def init_fn(key: jax.Array, n_chains: int, *, start: str = "constant"):
        if start != "constant":
            raise ValueError("dist engines support start='constant' only")
        x = jnp.zeros((n_chains, graph.n), jnp.int32)
        cache = jnp.zeros((n_chains,), jnp.float32)
        if cache_fn is not None:
            ck = jax.random.split(jax.random.fold_in(key, 0x5eed), n_chains)
            cache = jax.vmap(cache_fn)(ck, x)
        st = DG.DistState(
            x=x, cache=cache,
            key=jax.random.split(key, dp),
            accepts=jnp.zeros((n_chains,), jnp.int32),
            marg=jnp.zeros((n_chains, graph.n, graph.D), jnp.float32),
            count=jnp.int32(0))
        if adaptive:
            n = graph.n
            st = DG.DistAdaptiveState(
                inner=st,
                cdf=jnp.cumsum(jnp.full((n,), 1.0 / n, jnp.float32)),
                flips=jnp.zeros((dp, n), jnp.float32),
                hits=jnp.zeros((dp, n), jnp.float32),
                calls=jnp.int32(0))
        return st

    eng = _engine(name, "dist", schedule, upd, graph, resolved,
                  init_fn, sweep_fn,
                  exact_accept=name in ("gibbs", "min-gibbs"))
    return dataclasses.replace(eng, device_tables=sh)


# ---------------------------------------------------------------------------
# Workload registry (the paper's experimental models + chromatic lattice)
# ---------------------------------------------------------------------------

WORKLOADS: Dict[str, Dict[str, Any]] = {
    "ising-20x20":        dict(kind="ising", grid=20, beta=1.0, D=2),
    "potts-20x20":        dict(kind="potts", grid=20, beta=4.6, D=10),
    "ising-128x128":      dict(kind="ising", grid=128, beta=1.0, D=2),
    "potts-64x64":        dict(kind="potts", grid=64, beta=4.6, D=10),
    # sparse nearest-neighbor lattice: the first-class chromatic workload
    # (2-colorable; Workload.colors feeds ChromaticBlocks)
    "lattice-ising-64x64": dict(kind="lattice", grid=64, beta=0.4, D=2),
    # heterogeneous pair-Ising: uniform exact marginals, strongly bimodal
    # site mixing times — the AdaptiveScan diagnostics workloads
    "hetero-pairs-24":   dict(kind="pairs", n_strong=2, n_weak=10,
                              w_strong=3.5, w_weak=0.25),
    "hetero-pairs-1024": dict(kind="pairs", n_strong=64, n_weak=448,
                              w_strong=3.5, w_weak=0.25),
}


@dataclasses.dataclass(frozen=True)
class Workload:
    """A named graph (plus its coloring when the graph is colorable, so
    ``ChromaticBlocks(workload.colors)`` is one line away)."""
    name: str
    graph: MatchGraph
    colors: Optional[np.ndarray] = None


def workload_names() -> Tuple[str, ...]:
    return tuple(sorted(WORKLOADS))


def make_workload(name: str) -> Workload:
    """Build a registered workload by name."""
    if name not in WORKLOADS:
        raise KeyError(f"unknown workload {name!r}; available: "
                       f"{list(workload_names())}")
    c = WORKLOADS[name]
    if c["kind"] == "ising":
        return Workload(name, make_ising_graph(c["grid"], c["beta"]))
    if c["kind"] == "potts":
        return Workload(name, make_potts_graph(c["grid"], c["beta"], c["D"]))
    if c["kind"] == "lattice":
        return Workload(name, make_lattice_ising(c["grid"], c["beta"]),
                        colors=lattice_colors(c["grid"]))
    if c["kind"] == "pairs":
        n_pairs = c["n_strong"] + c["n_weak"]
        return Workload(name, make_pair_ising(c["n_strong"], c["n_weak"],
                                              c["w_strong"], c["w_weak"]),
                        colors=pair_colors(n_pairs))
    raise ValueError(f"unknown workload kind {c['kind']!r}")
