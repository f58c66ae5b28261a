"""Plain reference of the neighbour-list chromatic Gibbs sweep, written from
its draw contract (``core/samplers.py``, ``_build_neighbor_chromatic_sweep``)
over an explicit undirected edge list: numpy float32, one site at a time,
no gather or table shared with the program.  The random numbers are the
contract's own ``jax.random`` draws; everything after them is numpy.

Also the exact single-site and edge marginals of a tiny lattice by
enumerating its states.
"""
import itertools

import jax
import numpy as np

jax.config.update("jax_default_matmul_precision", "highest")


def lattice_edges(grid, periodic):
    """Undirected edges ``(a, b)`` of the ``grid x grid`` nearest-neighbour
    lattice, sites row-major, each edge once."""
    edges = set()
    for r, c in itertools.product(range(grid), range(grid)):
        for dr, dc in ((0, 1), (1, 0)):
            rr, cc = r + dr, c + dc
            if periodic:
                rr, cc = rr % grid, cc % grid
            elif rr >= grid or cc >= grid:
                continue
            i, j = r * grid + c, rr * grid + cc
            edges.add((min(i, j), max(i, j)))
    return sorted(edges)


def incident(n, edges, weights):
    """Per site, its ``(neighbour, weight)`` pairs in ascending neighbour
    order (the order the contract sums in)."""
    out = [[] for _ in range(n)]
    for (a, b), wt in zip(edges, weights):
        out[a].append((b, np.float32(wt)))
        out[b].append((a, np.float32(wt)))
    return [sorted(lst) for lst in out]


def sweep(x, keys, n, D, edges, weights, colors, evidence=None):
    """One call of the contract on every chain: ``x`` (C, n) int, ``keys``
    (C, 2) uint32.  Returns ``(x, next keys)`` as numpy arrays."""
    x = np.array(x, np.int32)
    keys = np.asarray(keys)
    C, K = x.shape[0], int(np.max(colors)) + 1
    nb = incident(n, edges, weights)
    split = np.stack([np.asarray(jax.random.split(k, K + 1)) for k in keys])
    for q in range(K):
        sites = [i for i in range(n) if colors[i] == q]
        noise = np.stack([np.asarray(jax.random.gumbel(
            split[c, q], (len(sites), D))) for c in range(C)])
        old = x.copy()              # same-class sites share no edge
        for r, i in enumerate(sites):
            for c in range(C):
                scores = []
                for u in range(D):
                    e = None
                    for j, wt in nb[i]:
                        term = wt if old[c, j] == u else np.float32(0.0)
                        e = term if e is None else np.float32(e + term)
                    e = np.float32(0.0) if e is None else e
                    scores.append(np.float32(e + noise[c, r, u]))
                x[c, i] = int(np.argmax(scores))
        if evidence is not None:
            mask, vals = (np.asarray(a) for a in evidence)
            x[:, mask > 0] = vals[mask > 0]
    return x, split[:, K]


def exact_marginals(n, D, edges, weights, evidence=()):
    """Exact ``(n, D)`` site marginals and per-edge agreement
    probabilities ``P(x_a == x_b)`` given ``evidence`` ``((site, value),
    ...)``, by enumerating all ``D^n`` states in float64."""
    states = np.array(list(itertools.product(range(D), repeat=n)), np.int8)
    for s, v in evidence:
        states = states[states[:, s] == v]
    a = np.array([e[0] for e in edges])
    b = np.array([e[1] for e in edges])
    energy = (states[:, a] == states[:, b]) @ np.asarray(weights,
                                                          np.float64)
    p = np.exp(energy - energy.max())
    p /= p.sum()
    marg = np.stack([p @ (states == u) for u in range(D)], axis=1)
    agree = p @ (states[:, a] == states[:, b])
    return marg, agree
