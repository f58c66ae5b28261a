"""Factor-graph representations for minibatch Gibbs sampling.

The paper's experimental models (Ising / Potts with a Gaussian-kernel
interaction matrix) are both *weighted-match* pairwise models:

  Potts:  phi_{ij}(x) = beta * A_ij * delta(x_i, x_j)          M_phi = b A_ij
  Ising:  phi_{ij}(x) = beta * A_ij * (s_i s_j + 1)            M_phi = 2 b A_ij
          (s = 2x-1 in {-1,+1};  s_i s_j + 1 = 2 delta(x_i,x_j))

with one factor per *unordered* pair {i,j} — this convention reproduces the
paper's reported constants (Ising: Psi=416.1, L=2.21; Potts: Psi=957.1,
L=5.09) exactly.  Both are ``phi_{ij}(x) = W_ij * delta(x_i, x_j)`` for a
symmetric non-negative match-weight matrix W.  This file defines:

* :class:`MatchGraph` — the dense weighted-match pairwise model with every
  Definition-1 quantity (``M_phi``, total max energy ``Psi``, local max
  energy ``L``, max degree ``Delta``) plus precomputed alias tables for O(1)
  categorical factor draws (the Poisson->multinomial trick of the paper's
  footnote 7).
* :class:`TabularPairwiseGraph` — general tabular pairwise factors used by
  the exact spectral-gap validators (small state spaces only).

All heavy arrays are JAX arrays so graphs can be donated to jitted samplers;
alias-table *construction* happens once in numpy (Vose's algorithm).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "MatchGraph",
    "TabularPairwiseGraph",
    "build_alias_table",
    "alias_draw",
    "gaussian_kernel_interactions",
    "make_ising_graph",
    "make_potts_graph",
    "make_lattice_ising",
    "lattice_colors",
    "make_pair_ising",
    "pair_colors",
]


# ---------------------------------------------------------------------------
# Alias tables (Vose) — O(1) categorical sampling, used to realize the
# paper's Poisson + multinomial decomposition with fixed shapes on TPU.
# ---------------------------------------------------------------------------

def build_alias_table(p: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Build a Vose alias table for probability vector ``p`` (need not be
    normalized).  Returns ``(prob, alias)`` with ``prob`` float32 in [0,1]
    and ``alias`` int32, each of shape ``p.shape``.
    """
    p = np.asarray(p, dtype=np.float64)
    m = p.shape[0]
    total = p.sum()
    if total <= 0:
        # Degenerate: uniform table.
        return np.ones(m, np.float32), np.arange(m, dtype=np.int32)
    q_arr = p * (m / total)
    # the pairing loop runs on python floats and lists: numpy scalar
    # indexing made it the set-up cost of every large dense graph
    q = q_arr.tolist()
    prob = [0.0] * m
    alias = [0] * m
    small = np.flatnonzero(q_arr < 1.0).tolist()
    large = np.flatnonzero(q_arr >= 1.0).tolist()
    while small and large:
        s = small.pop()
        l = large.pop()
        prob[s] = q[s]
        alias[s] = l
        q[l] = (q[l] + q[s]) - 1.0
        (small if q[l] < 1.0 else large).append(l)
    for i in large:
        prob[i] = 1.0
    for i in small:
        prob[i] = 1.0
    return np.asarray(prob, np.float32), np.asarray(alias, np.int32)


def alias_draw(key: jax.Array, prob: jax.Array, alias: jax.Array,
               shape: Tuple[int, ...]) -> jax.Array:
    """Draw ``shape`` iid samples from the alias table in O(1) each."""
    m = prob.shape[0]
    k1, k2 = jax.random.split(key)
    idx = jax.random.randint(k1, shape, 0, m)
    u = jax.random.uniform(k2, shape)
    take_alias = u >= prob[idx]
    return jnp.where(take_alias, alias[idx], idx).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Interaction matrices (paper Appendix B)
# ---------------------------------------------------------------------------

def gaussian_kernel_interactions(grid: int, gamma: float = 1.5) -> np.ndarray:
    """``A_ij = exp(-gamma * d_ij^2)`` for variables laid out on a
    ``grid x grid`` lattice (paper Appendix B).  Zero diagonal."""
    coords = np.stack(np.meshgrid(np.arange(grid), np.arange(grid),
                                  indexing="ij"), -1).reshape(-1, 2)
    d2 = ((coords[:, None, :] - coords[None, :, :]) ** 2).sum(-1)
    A = np.exp(-gamma * d2.astype(np.float64))
    np.fill_diagonal(A, 0.0)
    return A


# ---------------------------------------------------------------------------
# MatchGraph
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class MatchGraph:
    """Dense weighted-match pairwise factor graph.

    Factors are unordered pairs {i,j} with ``phi_ij(x) = W_ij d(x_i,x_j)``,
    ``M_phi = W_ij``.  All Definition-1 quantities are precomputed.

    Attributes
    ----------
    W        : (n, n) float32 symmetric, zero diagonal — match weights = M_phi.
    D        : domain size of every variable.
    psi      : total maximum energy  Psi = sum_{i<j} W_ij.
    L        : local maximum energy  L = max_i sum_j W_ij.
    delta    : max degree Delta = max_i |{j : W_ij > 0}|.
    row_sum  : (n,) L_i = sum_j W_ij.
    pair_a/b : (F,) endpoints of the F = n(n-1)/2 upper-triangle factors.
    pair_prob/pair_alias : alias table over factors, p_phi = M_phi / Psi.
    row_prob/row_alias   : (n, n) per-row alias tables, p_j = W_ij / L_i
                           (used by MGPMH's local minibatch over A[i]).
    """

    W: jax.Array
    D: int
    psi: float
    L: float
    delta: int
    row_sum: jax.Array
    pair_a: jax.Array
    pair_b: jax.Array
    pair_prob: jax.Array
    pair_alias: jax.Array
    row_prob: jax.Array
    row_alias: jax.Array

    # -- pytree plumbing (static: D, psi, L, delta) --
    def tree_flatten(self):
        leaves = (self.W, self.row_sum, self.pair_a, self.pair_b,
                  self.pair_prob, self.pair_alias, self.row_prob,
                  self.row_alias)
        aux = (self.D, self.psi, self.L, self.delta)
        return leaves, aux

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        D, psi, L, delta = aux
        (W, row_sum, pair_a, pair_b, pair_prob, pair_alias, row_prob,
         row_alias) = leaves
        return cls(W=W, D=D, psi=psi, L=L, delta=delta, row_sum=row_sum,
                   pair_a=pair_a, pair_b=pair_b, pair_prob=pair_prob,
                   pair_alias=pair_alias, row_prob=row_prob,
                   row_alias=row_alias)

    # -- properties --
    @property
    def n(self) -> int:
        return self.W.shape[0]

    @property
    def num_factors(self) -> int:
        return self.pair_a.shape[0]

    # -- energies --
    def energy(self, x: jax.Array) -> jax.Array:
        """Total energy zeta(x) = sum_{i<j} W_ij d(x_i, x_j).

        ``x``: (..., n) int32.  Returns (...,) float32.
        """
        match = (x[..., :, None] == x[..., None, :]).astype(self.W.dtype)
        return 0.5 * jnp.einsum("...ij,ij->...", match, self.W)

    def cond_energies(self, x: jax.Array, i: jax.Array) -> jax.Array:
        """Exact conditional energies eps_u = sum_{j != i} W_ij d(u, x_j)
        for all u (the O(D*Delta) inner loop of Algorithm 1).

        ``x``: (n,) int32, ``i``: scalar int32.  Returns (D,) float32.
        """
        w_row = self.W[i]  # (n,) ; diagonal is zero so j == i contributes 0
        onehot = jax.nn.one_hot(x, self.D, dtype=w_row.dtype)  # (n, D)
        return w_row @ onehot

    @staticmethod
    def from_interactions(A: np.ndarray, *, match_weight_scale: float,
                          D: int) -> "MatchGraph":
        """Build from a symmetric interaction matrix A, with
        ``W = match_weight_scale * A``."""
        A = np.asarray(A, np.float64)
        if not np.allclose(A, A.T):
            raise ValueError("interaction matrix must be symmetric")
        W = match_weight_scale * A
        np.fill_diagonal(W, 0.0)
        n = W.shape[0]
        iu, ju = np.triu_indices(n, k=1)
        M = W[iu, ju]                       # per-factor max energies M_phi
        psi = float(M.sum())
        row_sum = W.sum(1)
        L = float(row_sum.max())
        delta = int((W > 0).sum(1).max())
        pair_prob, pair_alias = build_alias_table(M)
        row_prob = np.zeros((n, n), np.float32)
        row_alias = np.zeros((n, n), np.int32)
        for i in range(n):
            row_prob[i], row_alias[i] = build_alias_table(W[i])
        return MatchGraph(
            W=jnp.asarray(W, jnp.float32), D=D, psi=psi, L=L, delta=delta,
            row_sum=jnp.asarray(row_sum, jnp.float32),
            pair_a=jnp.asarray(iu, jnp.int32), pair_b=jnp.asarray(ju, jnp.int32),
            pair_prob=jnp.asarray(pair_prob), pair_alias=jnp.asarray(pair_alias),
            row_prob=jnp.asarray(row_prob), row_alias=jnp.asarray(row_alias))


def make_ising_graph(grid: int = 20, beta: float = 1.0,
                     gamma: float = 1.5) -> MatchGraph:
    """Paper Section 2 validation model: fully-connected Ising on a
    ``grid x grid`` lattice, Gaussian-kernel interactions, D = 2.

    One factor per unordered pair {i,j}:
    phi_{ij} = beta A_ij (s_i s_j + 1) = 2 beta A_ij d(x_i, x_j) so the match
    weight is 2*beta*A and M_phi = 2 beta A_ij.  (For grid=20, beta=1,
    gamma=1.5 this yields Psi = 416.1 and L = 2.21 — exactly the paper's
    reported constants, which pins down this convention.)
    """
    A = gaussian_kernel_interactions(grid, gamma)
    return MatchGraph.from_interactions(A, match_weight_scale=2.0 * beta, D=2)


def make_potts_graph(grid: int = 20, beta: float = 4.6, D: int = 10,
                     gamma: float = 1.5) -> MatchGraph:
    """Paper Section 3 validation model: Potts, D = 10.

    One factor per unordered pair {i,j}: phi_{ij} = beta A_ij d(x_i, x_j) —
    match weight beta*A and M_phi = beta A_ij.  (grid=20, beta=4.6 yields
    Psi = 957.1, L = 5.09 — exactly the paper's constants.)
    """
    A = gaussian_kernel_interactions(grid, gamma)
    return MatchGraph.from_interactions(A, match_weight_scale=beta, D=D)


def make_lattice_ising(grid: int, beta: float = 0.4) -> MatchGraph:
    """Nearest-neighbor Ising on a grid (sparse, 2-colorable): the workload
    where chromatic scheduling applies."""
    n = grid * grid
    W = np.zeros((n, n))
    for r in range(grid):
        for c in range(grid):
            i = r * grid + c
            for (dr, dc) in ((0, 1), (1, 0)):
                rr, cc = r + dr, c + dc
                if rr < grid and cc < grid:
                    j = rr * grid + cc
                    W[i, j] = W[j, i] = 2.0 * beta   # ising match weight
    return MatchGraph.from_interactions(W, match_weight_scale=1.0, D=2)


def lattice_colors(grid: int) -> np.ndarray:
    """Checkerboard 2-coloring of the ``grid x grid`` lattice."""
    r, c = np.divmod(np.arange(grid * grid), grid)
    return ((r + c) % 2).astype(np.int32)


def make_pair_ising(n_strong: int, n_weak: int, w_strong: float = 3.5,
                    w_weak: float = 0.25) -> MatchGraph:
    """Heterogeneous pair-Ising: ``n_strong + n_weak`` independent 2-site
    Ising pairs (sites 2p, 2p+1 coupled with match weight ``w_strong`` for
    the first ``n_strong`` pairs, ``w_weak`` after).

    The diagnostics workload: every marginal is exactly uniform (value
    relabeling is an energy-preserving bijection), but strongly coupled
    pairs flip orders of magnitude more slowly than weak ones — a uniform
    random scan wastes most of its updates on already-decorrelated sites,
    which is precisely the asymmetry ``AdaptiveScan`` exploits.  Pairs are
    2-colorable (``pair_colors``)."""
    n = 2 * (n_strong + n_weak)
    W = np.zeros((n, n))
    for p in range(n_strong + n_weak):
        w = w_strong if p < n_strong else w_weak
        W[2 * p, 2 * p + 1] = W[2 * p + 1, 2 * p] = w
    return MatchGraph.from_interactions(W, match_weight_scale=1.0, D=2)


def pair_colors(n_pairs: int) -> np.ndarray:
    """Proper 2-coloring of ``make_pair_ising`` (even/odd site of a pair)."""
    return (np.arange(2 * n_pairs) % 2).astype(np.int32)


# ---------------------------------------------------------------------------
# TabularPairwiseGraph — general factors for exact validation
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TabularPairwiseGraph:
    """General pairwise factor graph with explicit tables.

    Factor f connects variables (a_f, b_f) and has value
    ``phi_f(x) = table[f, x[a_f], x[b_f]] >= 0``.  Used by the exact
    transition-matrix validators (tests/), small n only.  Pure numpy.
    """

    pairs: np.ndarray   # (F, 2) int
    tables: np.ndarray  # (F, D, D) float64, non-negative
    n: int
    D: int

    def __post_init__(self):
        assert self.tables.min() >= 0.0, "factors must be non-negative"

    @property
    def num_factors(self) -> int:
        return self.pairs.shape[0]

    def factor_values(self, x: np.ndarray) -> np.ndarray:
        """phi_f(x) for all f.  x: (n,) -> (F,)."""
        a, b = self.pairs[:, 0], self.pairs[:, 1]
        return self.tables[np.arange(self.num_factors), x[a], x[b]]

    def energy(self, x: np.ndarray) -> float:
        return float(self.factor_values(x).sum())

    # Definition 1 quantities ------------------------------------------------
    @property
    def M(self) -> np.ndarray:
        """Per-factor maximum energies."""
        return self.tables.max(axis=(1, 2))

    @property
    def psi(self) -> float:
        return float(self.M.sum())

    def adjacent(self, i: int) -> np.ndarray:
        """Indices of factors that depend on variable i (A[i])."""
        return np.where((self.pairs == i).any(axis=1))[0]

    @property
    def L(self) -> float:
        return float(max(self.M[self.adjacent(i)].sum()
                         for i in range(self.n)))

    @property
    def delta(self) -> int:
        return int(max(len(self.adjacent(i)) for i in range(self.n)))

    def all_states(self) -> np.ndarray:
        """Enumerate Omega (D^n states).  (|Omega|, n) int array."""
        grids = np.meshgrid(*([np.arange(self.D)] * self.n), indexing="ij")
        return np.stack([g.ravel() for g in grids], axis=-1)

    def pi(self) -> np.ndarray:
        """Exact stationary distribution over all_states()."""
        states = self.all_states()
        e = np.array([self.energy(s) for s in states])
        w = np.exp(e - e.max())
        return w / w.sum()

    @staticmethod
    def random(n: int, D: int, max_energy: float, seed: int,
               connectivity: str = "full") -> "TabularPairwiseGraph":
        rng = np.random.default_rng(seed)
        if connectivity == "full":
            pairs = np.array([(i, j) for i in range(n) for j in range(i + 1, n)])
        elif connectivity == "chain":
            pairs = np.array([(i, i + 1) for i in range(n - 1)])
        else:
            raise ValueError(connectivity)
        tables = rng.uniform(0.0, max_energy, size=(len(pairs), D, D))
        return TabularPairwiseGraph(pairs=pairs, tables=tables, n=n, D=D)

    @staticmethod
    def from_match_graph(g: MatchGraph) -> "TabularPairwiseGraph":
        W = np.asarray(g.W)
        a = np.asarray(g.pair_a)
        b = np.asarray(g.pair_b)
        pairs = np.stack([a, b], -1)
        eye = np.eye(g.D)
        tables = W[a, b][:, None, None] * eye[None, :, :]
        return TabularPairwiseGraph(pairs=pairs, tables=tables,
                                    n=W.shape[0], D=g.D)
