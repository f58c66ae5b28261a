"""Distributed minibatch Gibbs: the ``backend="dist"`` implementation layer
of the unified Engine API (``core/engine.py``).

Consumers never build distributed steps by hand — ``engine.make(name,
graph, backend="dist", mesh=...)`` shards the graph, wraps the sweep in
shard_map with the canonical specs (`shard_specs` / `state_specs`), and
returns an Engine whose ``sweep(state)`` hides the collective plumbing.
This module owns the sharded graph layout and the **parametrized
distributed sweep-kernel template** that runs *inside* shard_map.

Parallelization (see DESIGN.md §dist for the full derivation):
* chains sharded over the data axes ("pod", "data") — embarrassing;
* the *graph* sharded over "model": each model shard owns a column slice of
  the interaction matrix W (and the factors whose higher endpoint falls in
  those columns); state x is replicated so every shard can evaluate its
  partial energies locally.

The template (:func:`make_dist_sweep`) mirrors the PR-4 fused-kernel
refactor: ONE driver computes the shard-local x-independent partial
energies plus the within-sweep delta-correction couplings for whichever
estimators the algorithm needs, fuses everything into ONE ``psum`` per
S-update sweep, then runs the per-algorithm accept/update recursion
replicated on every shard from shared PRNG keys (communication-free, and
*statistically identical* to S single-site updates of the reference
sampler).  The per-algorithm substeps are the same selection/acceptance
rules the jnp sweeps use (``core.samplers``: ``gibbs_select`` /
``min_gibbs_select`` / ``mh_accept``) — the algorithms are pluggable, the
collective schedule is shared.

  algorithm   partials in the one psum                      substep
  ---------   ------------------------------------------   -------------
  gibbs       exact0 (C,S,D), Wp (C,S,S)                   gibbs_select
  mgpmh       + eps0 (C,S,D), Cp (C,S,S)                   select+mh_accept
  min-gibbs   m0 (C,S,D), n1 (C,S,D,S,D), n2 (C,S,D,S,S)   min_gibbs_select
  doublemin   eps0, Cp + m0 (C,S), n1 (C,S,S,D),           select+mh_accept
              n2 (C,S,S,S)                                  (cached xi)

(:func:`psum_footprint` reports the payload analytically; the bench rows
record it.)  On top of the template:

* :func:`make_dist_chromatic_sweep` — block updates of whole color
  classes against the sharded graph (one psum per color class, i.e.
  ``n_colors`` collectives per full-lattice sweep of n updates);
  bit-exact vs the single-host chromatic path on the lattice workloads;
* :func:`make_dist_adaptive_sweep` — the AdaptiveScan schedule under
  sharding: per-dp-shard flip/hit counters, with the cross-shard table
  reduction folded INTO the existing sweep psum at refresh sweeps (a
  ``lax.cond`` widens that one collective from the "model" axis to the
  full mesh; no extra collective is ever issued).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..core.factor_graph import (MatchGraph, build_alias_table,
                                 make_lattice_ising, lattice_colors)
from ..core.samplers import gibbs_select, min_gibbs_select, mh_accept

__all__ = ["ShardedMatchGraph", "DistState", "DistAdaptiveState",
           "make_dist_sweep", "make_dist_chromatic_sweep",
           "make_dist_adaptive_sweep", "make_chromatic_gibbs_step",
           "make_lattice_ising", "lattice_colors", "dist_init_state",
           "shard_specs", "state_specs", "adaptive_state_specs",
           "psum_footprint", "DIST_ALGOS"]

# The energy contractions below are exact sums of weights picked by
# one-hots; at XLA's default TPU precision (one bf16 pass) the weights
# would round, so they contract at full f32 precision.
_EXACT = jax.lax.Precision.HIGHEST

DIST_ALGOS = ("gibbs", "mgpmh", "min-gibbs", "doublemin")


# ---------------------------------------------------------------------------
# Graph sharding
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardedMatchGraph:
    """MatchGraph pre-split into ``n_shards`` column slices.

    All arrays carry a leading shard axis that shard_map consumes:
      W_cols    (S, n, n_loc)   W[:, cols_s]
      row_prob  (S, n, n_loc)   per-row alias tables over local columns
      row_alias (S, n, n_loc)
      row_sum   (S, n)          L_i^loc = sum_{j in cols_s} W[i, j]
    Scalars (D, psi, L, n) are static.

    ``row_tables`` / ``pair_tables`` let algorithm builders skip the
    tables they never read (gibbs/chromatic need neither; min-gibbs only
    the pair tables): the skipped arrays are rank-preserving size-1
    placeholders, so the shard specs stay uniform.
    """
    W_cols: jax.Array
    row_prob: jax.Array
    row_alias: jax.Array
    row_sum: jax.Array
    # per-shard factor tables for global (eq.-2) estimators: unordered pair
    # {a,b} (a<b) is owned by the shard owning column b; padded to F_max.
    pair_a: jax.Array      # (S, F_max) int32 global ids
    pair_b: jax.Array      # (S, F_max)
    pair_prob: jax.Array   # (S, F_max) alias tables over local factors
    pair_alias: jax.Array  # (S, F_max)
    psi_loc: jax.Array     # (S,) sum of local M_phi
    D: int
    psi: float
    L: float
    n: int
    n_shards: int

    @property
    def n_loc(self) -> int:
        return self.W_cols.shape[-1]

    @staticmethod
    def from_graph(g: MatchGraph, n_shards: int, *, row_tables: bool = True,
                   pair_tables: bool = True) -> "ShardedMatchGraph":
        W = np.asarray(g.W)
        n = W.shape[0]
        assert n % n_shards == 0, (n, n_shards)
        n_loc = n // n_shards
        W_cols = np.stack([W[:, s * n_loc:(s + 1) * n_loc]
                           for s in range(n_shards)])
        if row_tables:
            rp = np.zeros((n_shards, n, n_loc), np.float32)
            ra = np.zeros((n_shards, n, n_loc), np.int32)
            for s in range(n_shards):
                for i in range(n):
                    rp[s, i], ra[s, i] = build_alias_table(W_cols[s, i])
        else:
            rp = np.zeros((n_shards, 1, 1), np.float32)
            ra = np.zeros((n_shards, 1, 1), np.int32)
        row_sum = W_cols.sum(-1)
        if pair_tables:
            # factor shards: pair {a,b} (a<b) owned by b's shard
            iu, ju = np.triu_indices(n, k=1)
            M = W[iu, ju]
            keep = M > 0
            iu, ju, M = iu[keep], ju[keep], M[keep]
            own = ju // n_loc
            F_max = max(int((own == s).sum()) for s in range(n_shards))
            pa = np.zeros((n_shards, F_max), np.int32)
            pb = np.zeros((n_shards, F_max), np.int32)
            pp = np.zeros((n_shards, F_max), np.float32)
            pl = np.zeros((n_shards, F_max), np.int32)
            psi_loc = np.zeros(n_shards, np.float32)
            for s in range(n_shards):
                m = own == s
                f = int(m.sum())
                pa[s, :f], pb[s, :f] = iu[m], ju[m]
                Ms = np.zeros(F_max); Ms[:f] = M[m]
                pp[s], pl[s] = build_alias_table(Ms)
                psi_loc[s] = Ms.sum()
        else:
            pa = pb = pl = np.zeros((n_shards, 1), np.int32)
            pp = np.zeros((n_shards, 1), np.float32)
            psi_loc = np.full(n_shards, g.psi / n_shards, np.float32)
        return ShardedMatchGraph(
            W_cols=jnp.asarray(W_cols, jnp.float32),
            row_prob=jnp.asarray(rp), row_alias=jnp.asarray(ra),
            row_sum=jnp.asarray(row_sum, jnp.float32),
            pair_a=jnp.asarray(pa), pair_b=jnp.asarray(pb),
            pair_prob=jnp.asarray(pp), pair_alias=jnp.asarray(pl),
            psi_loc=jnp.asarray(psi_loc),
            D=g.D, psi=g.psi, L=g.L, n=n, n_shards=n_shards)


class DistState(NamedTuple):
    x: jax.Array         # (C_loc, n) chain states — replicated over "model"
    cache: jax.Array     # (C_loc,) cached eps/xi (MIN-Gibbs / DoubleMIN)
    key: jax.Array       # per-dp-shard key (shared across model shards)
    accepts: jax.Array   # (C_loc,) int32
    marg: jax.Array      # (C_loc, n_loc, D) running one-hot sums (sharded)
    count: jax.Array     # () int32 samples accumulated


class DistAdaptiveState(NamedTuple):
    """DistState + the AdaptiveScan control state under sharding.

    ``cdf`` is the cumulative site-selection table, identical on every
    shard (it is rebuilt from the all-mesh-reduced counters); ``flips`` /
    ``hits`` are per-dp-shard cumulative counters over that shard's local
    chains (leading axis = flattened dp shards).  ``x`` / ``accepts`` /
    ``marg`` / ``count`` forward to ``inner`` so the launcher and
    ``Engine.sweep``'s telemetry path work unchanged.
    """
    inner: DistState
    cdf: jax.Array       # (n,) float32, replicated
    flips: jax.Array     # (dp, n) float32 per-dp-shard value changes
    hits: jax.Array      # (dp, n) float32 per-dp-shard site visits
    calls: jax.Array     # () int32, replicated

    @property
    def x(self):
        return self.inner.x

    @property
    def accepts(self):
        return self.inner.accepts

    @property
    def marg(self):
        return self.inner.marg

    @property
    def count(self):
        return self.inner.count


def dist_init_state(n_chains_loc: int, n: int, n_loc: int, D: int,
                    key: jax.Array) -> DistState:
    return DistState(
        x=jnp.zeros((n_chains_loc, n), jnp.int32),
        cache=jnp.zeros((n_chains_loc,), jnp.float32),
        key=key,
        accepts=jnp.zeros((n_chains_loc,), jnp.int32),
        marg=jnp.zeros((n_chains_loc, n_loc, D), jnp.float32),
        count=jnp.int32(0))


def shard_specs(mp_axis: str = "model"):
    """Canonical shard_map in_specs for the ShardedMatchGraph arrays (the
    leading shard axis of every array maps to the model axis)."""
    return {"W_cols": P(mp_axis, None, None),
            "row_prob": P(mp_axis, None, None),
            "row_alias": P(mp_axis, None, None),
            "row_sum": P(mp_axis, None),
            "pair_a": P(mp_axis, None), "pair_b": P(mp_axis, None),
            "pair_prob": P(mp_axis, None), "pair_alias": P(mp_axis, None),
            "psi_loc": P(mp_axis)}


def state_specs(dp_axes="data", mp_axis: str = "model") -> DistState:
    """Canonical shard_map specs for DistState: chains over the data axes,
    marginals column-sharded over the model axis, x replicated."""
    return DistState(x=P(dp_axes, None), cache=P(dp_axes), key=P(dp_axes),
                     accepts=P(dp_axes), marg=P(dp_axes, mp_axis, None),
                     count=P())


def adaptive_state_specs(dp_axes="data",
                         mp_axis: str = "model") -> DistAdaptiveState:
    """shard_map specs for DistAdaptiveState: the control table replicated,
    the flip/hit counters sharded over the data axes."""
    return DistAdaptiveState(
        inner=state_specs(dp_axes, mp_axis), cdf=P(None),
        flips=P(dp_axes, None), hits=P(dp_axes, None), calls=P())


def psum_footprint(algo: str, *, C: int, D: int, S: int = 0, n: int = 0,
                   n_colors: int = 0) -> dict:
    """Analytic collective count and float32 psum payload of ONE sweep call
    of the distributed template (per dp shard; the benchmark rows attach
    this to their records).

    ``algo`` is a template algorithm name or ``"chromatic"`` (``n`` /
    ``n_colors`` required there; one psum per color class).
    """
    if algo == "chromatic":
        return {"collectives_per_sweep": n_colors,
                "psum_payload_bytes": 4 * n_colors * C * n * D}
    elems = {
        "gibbs": C * S * D + C * S * S,
        "mgpmh": 2 * C * S * D + 2 * C * S * S,
        "min-gibbs": C * S * D + C * S * D * S * D + C * S * D * S * S,
        "doublemin": (C * S * D + C * S * S
                      + C * S + C * S * S * D + C * S * S * S),
    }[algo]
    return {"collectives_per_sweep": 1, "psum_payload_bytes": 4 * elems}


# ---------------------------------------------------------------------------
# shared pieces (run inside shard_map; 'model' axis bound)
# ---------------------------------------------------------------------------

def _split_key(state):
    """Per-dp-shard key arrives as (1, 2) under shard_map."""
    def norm(k):
        return k.reshape(state.key.shape)
    return norm, state.key.reshape(2)


def _x_cols(x, shard_idx, n_loc):
    """This shard's column slice of the replicated state."""
    return jax.lax.dynamic_slice_in_dim(x, shard_idx * n_loc, n_loc, axis=1)


def _accum_marg(state, x, shard_idx, n_loc, D):
    return state.marg + jax.nn.one_hot(
        _x_cols(x, shard_idx, n_loc), D, dtype=jnp.float32)


def _flat_dp_index(dp_axes: Tuple[str, ...], dp_shape: Tuple[int, ...]):
    """Flattened index of this shard along the data-parallel axes."""
    idx = jnp.int32(0)
    for a, size in zip(dp_axes, dp_shape):
        idx = idx * size + jax.lax.axis_index(a)
    return idx


# ---------------------------------------------------------------------------
# shard-local partials: everything the ONE psum carries
# ---------------------------------------------------------------------------

def _exact_partials(gs, sh, oh_loc, i, shard_idx):
    """x-independent exact energies against the sweep-entry state plus the
    within-sweep coupling matrix (DESIGN.md §dist):
      exact0[c,s,u] = sum_{j loc} W[i_s, j] d(x0_j, u)        (C, S, D)
      Wp[c,s,t]     = W[i_s, i_t] when shard owns column i_t  (C, S, S)
    """
    C, S = i.shape
    w_rows = sh["W_cols"][i]                            # (C, S, n_loc)
    exact0 = jnp.einsum("csn,cnd->csd", w_rows, oh_loc, precision=_EXACT)
    off = shard_idx * gs.n_loc
    owned = (i >= off) & (i < off + gs.n_loc)           # (C, S) site t
    loc_t = jnp.broadcast_to(
        jnp.clip(i - off, 0, gs.n_loc - 1)[:, None, :], (C, S, S))
    wp = jnp.take_along_axis(w_rows, loc_t, axis=2)
    wp = jnp.where(owned[:, None, :], wp, 0.0)
    return exact0, wp, (w_rows, owned, loc_t)


def _proposal_partials(gs, sh, oh_loc, i, key, lam, capacity, shard_idx,
                       exact_aux=None):
    """MGPMH/DoubleMIN proposal-minibatch energies via per-shard Poisson
    thinning, all S sub-steps at once:
      eps0[c,s,u] = (L/lam) sum_{draws k} d(x0_{j_k}, u)      (C, S, D)
      Cp[c,s,t]   = weighted draw count of sub-step s at i_t  (C, S, S)
    """
    C, S = i.shape
    kb, kj, ku = jax.random.split(jax.random.fold_in(key, shard_idx), 3)
    lam_loc = lam * sh["row_sum"][i] / gs.L             # (C, S)
    B = jnp.minimum(jax.random.poisson(kb, lam_loc, dtype=jnp.int32),
                    capacity)
    idx = jax.random.randint(kj, (C, S, capacity), 0, gs.n_loc)
    u = jax.random.uniform(ku, (C, S, capacity))
    prob = sh["row_prob"][i[..., None], idx]            # (C, S, K)
    alias = sh["row_alias"][i[..., None], idx]
    j_loc = jnp.where(u < prob, idx, alias)             # local col ids
    w = (gs.L / lam) * (jnp.arange(capacity)[None, None, :]
                        < B[..., None]).astype(jnp.float32)  # (C, S, K)
    # per-site draw counts by scatter-add (a one-hot bucket pass over
    # n_loc buckets would materialize a (C*S, K, n_loc) block)
    cnt_loc = jnp.zeros((C, S, gs.n_loc), jnp.float32).at[
        jnp.arange(C)[:, None, None], jnp.arange(S)[None, :, None],
        j_loc].add(w)
    # eps0[c,s,d] = sum_q cnt_loc[c,s,q] d(x0_loc[q], d): the counts
    # already hold the whole minibatch, no per-draw gather needed
    eps0 = jnp.einsum("csq,cqd->csd", cnt_loc, oh_loc, precision=_EXACT)
    if exact_aux is not None:
        _, owned, loc_t = exact_aux
    else:
        off = shard_idx * gs.n_loc
        owned = (i >= off) & (i < off + gs.n_loc)
        loc_t = jnp.broadcast_to(
            jnp.clip(i - off, 0, gs.n_loc - 1)[:, None, :], (C, S, S))
    cp = jnp.take_along_axis(cnt_loc, loc_t, axis=2)
    cp = jnp.where(owned[:, None, :], cp, 0.0)
    return eps0, cp


def _global_partials(gs, sh, x0, i, key, lam2, capacity2, shard_idx, U):
    """Global (eq.-2) estimator draws for all S sub-steps (and, for
    MIN-Gibbs, all ``U = D`` candidate values — independent minibatches per
    candidate, Alg 2) compressed into the delta-correction tensors the
    replicated recursion evaluates against the *current* state:

      m0[c,s(,u)]       matches among draws with NO endpoint in the sweep
                        site set {i_1..i_S} (x0 values — never change);
      n1[c,s(,u),t,d]   draws with exactly ONE endpoint at sweep slot t,
                        the free endpoint carrying x0-value d
                        (contributes 1[val_t == d] at recursion time);
      n2[c,s(,u),t1,t2] draws with BOTH endpoints in the sweep set
                        (contributes 1[val_t1 == val_t2]).

    Each shard draws from its own factor slice (Poisson thinning, rate
    lam2 * psi_loc / Psi) so the psum'd tensors realize exactly the
    full-graph estimator.  Returns float32 tensors shaped with a
    candidate axis of size U (squeeze U=1 at the call site).
    """
    C, S = i.shape
    kb, kj, ku = jax.random.split(jax.random.fold_in(key, shard_idx), 3)
    lam_loc = lam2 * sh["psi_loc"] / gs.psi
    B = jnp.minimum(jax.random.poisson(kb, lam_loc, (C, S, U),
                                       dtype=jnp.int32), capacity2)
    F = sh["pair_prob"].shape[0]
    shape = (C, S, U, capacity2)
    idx = jax.random.randint(kj, shape, 0, F)
    u = jax.random.uniform(ku, shape)
    f = jnp.where(u < sh["pair_prob"][idx], sh["pair_alias"][idx], idx)
    a = sh["pair_a"][f]                                 # (C, S, U, K) global
    b = sh["pair_b"][f]
    mask = jnp.arange(capacity2)[None, None, None, :] < B[..., None]
    w = mask.astype(jnp.float32)
    # map endpoints to sweep slots (first occurrence; vals_cur keeps
    # duplicate slots in sync so any consistent choice is valid)
    am = a[..., None] == i[:, None, None, None, :]      # (C, S, U, K, S)
    bm = b[..., None] == i[:, None, None, None, :]
    a_in, ta = am.any(-1), jnp.argmax(am, -1)
    b_in, tb = bm.any(-1), jnp.argmax(bm, -1)
    rows4 = jnp.arange(C)[:, None, None, None]
    x0a = x0[rows4, a]
    x0b = x0[rows4, b]
    free = ~a_in & ~b_in
    m0 = jnp.sum(w * (free & (x0a == x0b)), axis=-1)    # (C, S, U)
    ci = jnp.arange(C)[:, None, None, None]
    si = jnp.arange(S)[None, :, None, None]
    ui = jnp.arange(U)[None, None, :, None]
    n1 = jnp.zeros((C, S, U, S, gs.D), jnp.float32)
    n1 = n1.at[ci, si, ui, ta, x0b].add(w * (a_in & ~b_in))
    n1 = n1.at[ci, si, ui, tb, x0a].add(w * (b_in & ~a_in))
    n2 = jnp.zeros((C, S, U, S, S), jnp.float32).at[
        ci, si, ui, ta, tb].add(w * (a_in & b_in))
    return m0, n1, n2


def _global_matches(m0_s, n1_s, n2_s, vals_sub):
    """Evaluate the compressed global estimator at recursion time.

    ``vals_sub`` (..., S) holds the sweep-slot site values *after* the
    sub-step's substitution (candidate u for MIN-Gibbs, proposal v for
    DoubleMIN); leading axes broadcast against the (C[, U], S, ...) count
    tensors."""
    oh_sub = jax.nn.one_hot(vals_sub, n1_s.shape[-1], dtype=jnp.float32)
    eq_sub = (vals_sub[..., :, None] == vals_sub[..., None, :]).astype(
        jnp.float32)
    return (m0_s + jnp.sum(n1_s * oh_sub, axis=(-2, -1))
            + jnp.sum(n2_s * eq_sub, axis=(-2, -1)))


def _fused_psum(parts, mp_axis, ride=None, ride_on=None, mesh_info=None):
    """THE one collective of a sweep: psum ``parts`` over the model axis.

    With ``ride`` (the AdaptiveScan counters) and a traced ``ride_on``
    flag, refresh sweeps widen this same collective to the full mesh: the
    model-reduced operands are slotted into a dp-padded buffer so one
    all-axes psum yields both the per-dp-group energy sums and the
    all-chain counter reduction — in-graph refresh issues NO extra
    collective.  ``mesh_info = (dp_axes, dp_shape, mp_size)``.
    """
    if ride is None:
        return jax.lax.psum(parts, mp_axis), None
    dp_axes, dp_shape, mp_size = mesh_info
    dp = int(np.prod(dp_shape))
    axes = tuple(dp_axes) + (mp_axis,)
    dp_idx = _flat_dp_index(dp_axes, dp_shape)

    def fold(op):
        pt, rd = op
        padded = jax.tree_util.tree_map(
            lambda p: jnp.zeros((dp,) + p.shape, p.dtype).at[dp_idx].set(p),
            pt)
        padded, r = jax.lax.psum((padded, rd), axes)
        return (jax.tree_util.tree_map(lambda p: p[dp_idx], padded),
                jax.tree_util.tree_map(lambda x: x / mp_size, r))

    def plain(op):
        pt, rd = op
        return (jax.lax.psum(pt, mp_axis),
                jax.tree_util.tree_map(jnp.zeros_like, rd))

    return jax.lax.cond(ride_on, fold, plain, (parts, ride))


# ---------------------------------------------------------------------------
# THE template: one driver, pluggable per-algorithm substeps
# ---------------------------------------------------------------------------

def make_dist_sweep(gs: ShardedMatchGraph, algo: str, sweep_len: int, *,
                    lam: Optional[float] = None,
                    capacity: Optional[int] = None,
                    lam2: Optional[float] = None,
                    capacity2: Optional[int] = None,
                    mp_axis: str = "model", mesh_info=None):
    """``sweep_len`` sequential updates of ``algo`` per call with a single
    fused psum (the delta-correction scheme; DESIGN.md §dist).

    Statistically identical to ``sweep_len`` single-site updates of the
    reference sampler; marginals are accumulated once per sweep.  The
    returned ``step(state, sh, sites=None, ride=None, ride_on=None)`` runs
    inside shard_map; ``sites`` overrides the i.i.d.-uniform site draw
    (the AdaptiveScan hook), ``ride``/``ride_on`` fold extra all-mesh
    reductions into the sweep psum (see :func:`_fused_psum`).

    Parameters: ``lam``/``capacity`` are the proposal minibatch (mgpmh,
    doublemin's first batch); ``lam2``/``capacity2`` the global estimator
    batch (min-gibbs — where they arrive as ``lam``/``capacity`` from the
    engine and are mapped here — and doublemin's second batch).
    """
    if algo not in DIST_ALGOS:
        raise ValueError(f"unknown dist algorithm {algo!r}; "
                         f"supported: {DIST_ALGOS}")
    if algo == "min-gibbs":         # single-minibatch params = the global batch
        lam2, capacity2 = lam, capacity
        lam = capacity = None
    n, n_loc, D, S = gs.n, gs.n_loc, gs.D, sweep_len
    needs_exact = algo in ("gibbs", "mgpmh")
    needs_proposal = algo in ("mgpmh", "doublemin")
    n_global = {"min-gibbs": D, "doublemin": 1}.get(algo, 0)
    is_mh = algo in ("mgpmh", "doublemin")

    def step(state: DistState, sh, sites=None, ride=None,
             ride_on=None) -> DistState:
        shard_idx = jax.lax.axis_index(mp_axis)
        sh = {k: v[0] for k, v in sh.items()}   # strip size-1 shard axes
        norm, k0 = _split_key(state)
        key, ki, kd, kg, kv, ka = jax.random.split(k0, 6)
        C = state.x.shape[0]
        x0 = state.x                                        # replicated
        rows = jnp.arange(C)
        i = (jax.random.randint(ki, (C, S), 0, n) if sites is None
             else sites)                                    # shared sites

        # --- shard-local partials for the one fused psum ---
        parts = {}
        exact_aux = None
        if needs_exact or needs_proposal:
            # one-hot the shard's state columns once; it serves both the
            # exact and the proposal-minibatch partials
            oh_loc = jax.nn.one_hot(_x_cols(x0, shard_idx, n_loc), D,
                                    dtype=jnp.float32)      # (C, n_loc, D)
        if needs_exact:
            exact0, wp, exact_aux = _exact_partials(gs, sh, oh_loc, i,
                                                    shard_idx)
            parts["exact0"], parts["wp"] = exact0, wp
        if needs_proposal:
            parts["eps0"], parts["cp"] = _proposal_partials(
                gs, sh, oh_loc, i, kd, lam, capacity, shard_idx, exact_aux)
        if n_global:
            parts["m0"], parts["n1"], parts["n2"] = _global_partials(
                gs, sh, x0, i, kg, lam2, capacity2, shard_idx, n_global)

        parts, ride_out = _fused_psum(parts, mp_axis, ride, ride_on,
                                      mesh_info)

        # --- replicated sequential recursion (shared PRNG, no comms) ---
        gumbel = jax.random.gumbel(kv, (C, S, D))
        logu = jnp.log(jax.random.uniform(ka, (C, S)))
        # count each duplicated site once: first occurrence along t
        dup = jnp.tril(i[:, :, None] == i[:, None, :], k=-1).any(-1)  # (C,S)
        nodup = (~dup)[:, :, None].astype(jnp.float32)      # (C, S, 1)
        vals0_sites = jnp.take_along_axis(x0, i, axis=1)    # (C, S)
        oh0 = jax.nn.one_hot(vals0_sites, D, dtype=jnp.float32)
        u_cand = jnp.arange(D, dtype=jnp.int32)

        def delta_correct(base_s, coup_s, vals_cur):
            """base + coupling · (one-hot(current) − one-hot(entry))."""
            delta = (jax.nn.one_hot(vals_cur, D, dtype=jnp.float32)
                     - oh0) * nodup                         # (C, S, D)
            return base_s + jnp.einsum("ct,ctd->cd", coup_s, delta,
                                       precision=_EXACT)

        def substep(carry, s):
            x, vals_cur, cache, acc = carry
            i_s = i[:, s]
            xi = x[rows, i_s]
            same = i == i_s[:, None]                        # (C, S)
            if algo == "gibbs":
                exact_s = delta_correct(parts["exact0"][:, s],
                                        parts["wp"][:, s], vals_cur)
                new_v = gibbs_select(exact_s, gumbel[:, s])
                accept = None
            elif algo == "mgpmh":
                exact_s = delta_correct(parts["exact0"][:, s],
                                        parts["wp"][:, s], vals_cur)
                eps_s = delta_correct(parts["eps0"][:, s],
                                      parts["cp"][:, s], vals_cur)
                v = gibbs_select(eps_s, gumbel[:, s])
                accept = mh_accept(
                    logu[:, s], exact_s[rows, v] - exact_s[rows, xi],
                    eps_s[rows, xi], eps_s[rows, v])
                new_v = jnp.where(accept, v, xi)
            elif algo == "min-gibbs":
                # vals_sub[c,u,t]: slot values with candidate u at site i_s
                vals_sub = jnp.where(same[:, None, :],
                                     u_cand[None, :, None],
                                     vals_cur[:, None, :])  # (C, D, S)
                eps_s = float(np.log1p(gs.psi / lam2)) * _global_matches(
                    parts["m0"][:, s], parts["n1"][:, s, :, :, :],
                    parts["n2"][:, s, :, :, :], vals_sub)   # (C, D)
                new_v, cache = min_gibbs_select(eps_s, cache, xi,
                                                gumbel[:, s], rows)
                accept = None
            else:  # doublemin
                eps_s = delta_correct(parts["eps0"][:, s],
                                      parts["cp"][:, s], vals_cur)
                v = gibbs_select(eps_s, gumbel[:, s])
                vals_sub = jnp.where(same, v[:, None], vals_cur)  # (C, S)
                xi_y = float(np.log1p(gs.psi / lam2)) * _global_matches(
                    parts["m0"][:, s, 0], parts["n1"][:, s, 0],
                    parts["n2"][:, s, 0], vals_sub)
                accept = mh_accept(logu[:, s], xi_y - cache,
                                   eps_s[rows, xi], eps_s[rows, v])
                new_v = jnp.where(accept, v, xi)
                cache = jnp.where(accept, xi_y, cache)
            x = x.at[rows, i_s].set(new_v)
            vals_cur = jnp.where(same, new_v[:, None], vals_cur)
            if accept is not None:
                acc = acc + accept.astype(jnp.int32)
            return (x, vals_cur, cache, acc), None

        (x, _, cache, acc), _ = jax.lax.scan(
            substep, (x0, vals0_sites, state.cache,
                      jnp.zeros((C,), jnp.int32)), jnp.arange(S))
        new = state._replace(
            x=x, cache=cache, key=norm(key),
            accepts=state.accepts + (acc if is_mh else 0),
            marg=_accum_marg(state, x, shard_idx, n_loc, D),
            count=state.count + 1)
        return new if ride is None else (new, ride_out)
    return step


# ---------------------------------------------------------------------------
# Chromatic block schedule against the sharded graph (gibbs only)
# ---------------------------------------------------------------------------

def make_dist_chromatic_sweep(gs: ShardedMatchGraph, colors, *,
                              mp_axis: str = "model"):
    """One full chromatic sweep per call against the *sharded* graph:
    every color class updated as a parallel block, one psum per class
    (``n_colors`` collectives per n site updates — the changed-site set of
    a class is O(n), so the S²-coupling trick of the uniform template
    would need the full W row and degenerate to replicating the graph).

    Key/draw protocol mirrors the single-host chromatic paths exactly
    (per class ``kv, = split(keys[c], 1)``; full-lattice Gumbel noise;
    ``categorical`` == argmax(logits+gumbel)), so on graphs whose
    energies are exactly representable (small-integer multiples of beta —
    every registered lattice workload) the sharded sweep is bit-identical
    to ``make_chromatic_gibbs_step``.
    """
    colors_j = jnp.asarray(np.asarray(colors), jnp.int32)
    n_colors = int(np.asarray(colors).max()) + 1
    n, n_loc, D = gs.n, gs.n_loc, gs.D

    def step(state: DistState, sh) -> DistState:
        shard_idx = jax.lax.axis_index(mp_axis)
        sh = {k: v[0] for k, v in sh.items()}
        norm, k0 = _split_key(state)
        key, master = jax.random.split(k0)
        keys = jax.random.split(master, n_colors)
        C = state.x.shape[0]
        x = state.x
        for c in range(n_colors):       # static unroll over colors
            kv, = jax.random.split(keys[c], 1)
            oh_loc = jax.nn.one_hot(_x_cols(x, shard_idx, n_loc), D,
                                    dtype=jnp.float32)
            eps = jax.lax.psum(
                jnp.einsum("nl,cld->cnd", sh["W_cols"], oh_loc,
                           precision=_EXACT), mp_axis)
            gumbel = jax.random.gumbel(kv, (C, n, D))
            v = gibbs_select(eps, gumbel)
            x = jnp.where(colors_j[None, :] == c, v, x)
        return state._replace(
            x=x, key=norm(key),
            marg=_accum_marg(state, x, shard_idx, n_loc, D),
            count=state.count + 1)
    return step


# ---------------------------------------------------------------------------
# AdaptiveScan under sharding
# ---------------------------------------------------------------------------

def make_dist_adaptive_sweep(gs: ShardedMatchGraph, algo: str, schedule, *,
                             mesh_info, mp_axis: str = "model", **params):
    """AdaptiveScan over the distributed template: per-dp-shard flip/hit
    counters drive a site-selection table shared by the whole mesh.

    Sites are drawn per dp shard from the carried inverse-CDF table
    (replicated over model, so all model shards of a dp group agree).
    Every ``refresh_every``-th call the table is rebuilt from the
    counters of ALL chains: the cross-shard reduction rides the sweep's
    one fused psum (``ride``/``ride_on`` of :func:`make_dist_sweep` —
    the collective widens from the model axis to the full mesh for that
    call; no extra collective).  The refresh consumes statistics through
    the *previous* sweep — the current sweep's counters need the updated
    state, which only exists after the psum.  Between refreshes each
    segment is a fixed-distribution random-scan chain (same validity
    argument as the single-host AdaptiveScan).
    """
    from ..diagnostics.adaptive import refresh_cdf
    inner = make_dist_sweep(gs, algo, schedule.sweep_len, mp_axis=mp_axis,
                            mesh_info=mesh_info, **params)
    n, S, K = gs.n, schedule.sweep_len, schedule.refresh_every
    mix, r0 = schedule.uniform_mix, schedule.smoothing

    def step(ast: DistAdaptiveState, sh) -> DistAdaptiveState:
        st = ast.inner
        C = st.x.shape[0]
        k0 = st.key.reshape(2)
        u = jax.random.uniform(jax.random.fold_in(k0, 0x5c4e), (C, S))
        i = jnp.minimum(jnp.searchsorted(ast.cdf, u, side="right"),
                        n - 1).astype(jnp.int32)
        calls = ast.calls + 1
        refresh = calls % K == 0
        new, (gflips, ghits) = inner(st, sh, sites=i,
                                     ride=(ast.flips[0], ast.hits[0]),
                                     ride_on=refresh)
        flips = ast.flips + jnp.sum(new.x != st.x, axis=0,
                                    dtype=jnp.float32)[None]
        hits = ast.hits + jnp.zeros((n,), jnp.float32).at[
            i.reshape(-1)].add(1.0)[None]
        cdf = jax.lax.cond(
            refresh,
            lambda _: refresh_cdf(gflips, ghits, n, mix, r0),
            lambda _: ast.cdf, None)
        return DistAdaptiveState(inner=new, cdf=cdf, flips=flips, hits=hits,
                                 calls=calls)
    return step


# ---------------------------------------------------------------------------
# Chromatic block Gibbs, single-shard dense reference.  The lattice builders
# (`make_lattice_ising`, `lattice_colors`) live in core/factor_graph.py and
# are re-exported here for compatibility.  The engine-integrated paths are
# ``engine.make("gibbs", g, schedule=ChromaticBlocks(colors))`` (fused) and
# the same with ``backend="dist"`` (sharded); this dense step is their
# exact-parity reference.
# ---------------------------------------------------------------------------

def make_chromatic_gibbs_step(g: MatchGraph, colors: np.ndarray):
    """Update every variable of one color class simultaneously — exact for
    graphs where same-color variables share no factor.  Single-shard
    (replicated graph) variant; one step = one color class."""
    colors_j = jnp.asarray(colors)
    D = g.D

    def step(x, key, color):
        kv, = jax.random.split(key, 1)
        onehot = jax.nn.one_hot(x, D, dtype=jnp.float32)       # (C, n, D)
        eps = jnp.einsum("ij,cjd->cid", g.W, onehot,   # all cond energies
                         precision=_EXACT)
        v = jax.random.categorical(kv, eps, axis=-1).astype(jnp.int32)
        upd = (colors_j[None, :] == color)
        return jnp.where(upd, v, x)
    return step
