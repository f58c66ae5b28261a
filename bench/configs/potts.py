"""Plain reference of the dense Potts construction (De Sa, Chen & Wong,
ICML 2018, Appendix B), written from the paper and independent of the
program: variables on a ``grid x grid`` lattice, one factor per unordered
pair with match weight ``W_ij = beta * exp(-gamma * d_ij^2)``, zero
diagonal.  Everything is numpy; the arrays are handed to the oracles.

The per-row alias tables follow Vose's method with last-in-first-out
worklists (small and large sites in index order, both popped from the
end).  The method is deterministic, so any faithful implementation of it
gives the same table for the same weights; it runs here over all rows at
once.
"""
from __future__ import annotations

import numpy as np


def interactions(grid: int, beta: float, gamma: float) -> np.ndarray:
    """(n, n) float64 match weights ``beta * exp(-gamma d^2)``, zero
    diagonal, sites numbered row-major."""
    r, c = np.divmod(np.arange(grid * grid), grid)
    d2 = (r[:, None] - r[None, :]) ** 2 + (c[:, None] - c[None, :]) ** 2
    a = np.exp(-gamma * d2.astype(np.float64))
    np.fill_diagonal(a, 0.0)
    w = beta * a
    np.fill_diagonal(w, 0.0)
    return w


def alias_tables(p: np.ndarray):
    """Vose alias tables of every row of ``p`` (rows need not be
    normalised): ``(prob float32, alias int32)``, each shaped like ``p``.
    Draw ``j``: index ``k`` uniform, keep ``k`` when a second uniform is
    below ``prob[k]``, else take ``alias[k]``."""
    p = np.asarray(p, np.float64)
    rows, m = p.shape
    total = p.sum(axis=1)
    q = p * (m / np.where(total > 0, total, 1.0))[:, None]
    prob = np.zeros((rows, m), np.float64)
    alias = np.zeros((rows, m), np.int64)
    # per-row stacks: entries [0, top) are live, popped from the top
    small = np.zeros((rows, m), np.int64)
    large = np.zeros((rows, m), np.int64)
    n_small = np.zeros(rows, np.int64)
    n_large = np.zeros(rows, np.int64)
    is_small = q < 1.0
    for r in range(rows):   # initial worklists in index order
        s = np.flatnonzero(is_small[r])
        g = np.flatnonzero(~is_small[r])
        small[r, :s.size], large[r, :g.size] = s, g
        n_small[r], n_large[r] = s.size, g.size
    live = (n_small > 0) & (n_large > 0)
    while live.any():
        rr = np.flatnonzero(live)
        s = small[rr, n_small[rr] - 1]
        g = large[rr, n_large[rr] - 1]
        n_small[rr] -= 1
        n_large[rr] -= 1
        prob[rr, s] = q[rr, s]
        alias[rr, s] = g
        q[rr, g] = (q[rr, g] + q[rr, s]) - 1.0
        to_small = q[rr, g] < 1.0
        a, b = rr[to_small], rr[~to_small]
        small[a, n_small[a]] = g[to_small]
        n_small[a] += 1
        large[b, n_large[b]] = g[~to_small]
        n_large[b] += 1
        live = (n_small > 0) & (n_large > 0)
    for r in range(rows):   # leftovers keep themselves with certainty
        prob[r, large[r, :n_large[r]]] = 1.0
        prob[r, small[r, :n_small[r]]] = 1.0
        dead = total[r] <= 0
        if dead:
            prob[r], alias[r] = 1.0, np.arange(m)
    return prob.astype(np.float32), alias.astype(np.int32)


def build(cfg: dict, *, tables_from: str = "float64") -> dict:
    """The graph of configuration ``cfg`` as the oracles read it.

    ``tables_from`` says which weights the row tables and row sums are
    taken over: ``"float64"`` (the weights as constructed) or
    ``"float32"`` (the weights after rounding to the stored float32)."""
    w64 = interactions(cfg["grid"], cfg["beta"], cfg["gamma"])
    w32 = w64.astype(np.float32)
    src = w64 if tables_from == "float64" else w32.astype(np.float64)
    row_sum = (w64.sum(1) if tables_from == "float64"
               else w32.sum(1, dtype=np.float32))
    prob, alias = alias_tables(src)
    return {"W": w32, "D": int(cfg["D"]), "n": w32.shape[0],
            "L": float(w64.sum(1).max()),
            "psi": float(np.triu(w64, 1).sum()),
            "delta": int((w64 > 0).sum(1).max()),
            "row_sum": np.asarray(row_sum, np.float32),
            "row_prob": prob, "row_alias": alias}
