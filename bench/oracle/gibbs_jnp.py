"""Plain replay of one ``Engine.sweep`` call of chromatic Gibbs on the
neighbour-list path (``engine.make("gibbs", g, schedule="chromatic",
backend="jnp")``): every site of every chain updated once, colour class by
colour class.

The random streams follow the path's documented contract: each chain's key
splits into ``K + 1`` keys (``K`` colour classes); class ``q`` draws
``(n_q, D)`` standard Gumbel noise from key ``q``, row ``r`` for the
class's ``r``-th site in ascending order; key ``K`` is the chain's next
key.  Class ``q`` sets ``x_i = argmax_u (e_u + g_u)`` (the first on ties)
at its sites, with ``e_u`` the float32 sum, left to right over the site's
neighbours in ascending order, of the weights whose neighbour holds ``u``
(exact on any backend).  Here each class's energies are computed for every
site of the lattice and kept at the class's sites, a different data path
from the program's per-class gathers.  ``table`` may round the weights
first (the controls); evidence is re-clamped after every class.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def make(graph: dict, params: dict, S: int, table):
    n, D = graph["n"], graph["D"]
    if S != n:
        raise ValueError(f"a chromatic call updates all {n} sites, not {S}")
    nbr = jnp.asarray(graph["nbr"])
    w = jnp.asarray(table(graph["w"]))
    colors = np.asarray(graph["colors"])
    K = int(colors.max()) + 1
    # the row of each site in its class's noise
    rank = np.zeros(n, np.int32)
    for q in range(K):
        at = colors == q
        rank[at] = np.arange(int(at.sum()))
    rank, sizes = jnp.asarray(rank), [int((colors == q).sum())
                                      for q in range(K)]
    in_class = [jnp.asarray(colors == q) for q in range(K)]

    def chain(x, key, evidence):
        ks = jax.random.split(key, K + 1)
        for q in range(K):
            g = jax.random.gumbel(ks[q], (sizes[q], D))[rank]   # (n, D)
            xn = x[nbr]                                       # (n, 4)
            e = []
            for u in range(D):
                acc = jnp.where(xn[:, 0] == u, w[:, 0], 0.0)
                for k in range(1, nbr.shape[1]):
                    acc = acc + jnp.where(xn[:, k] == u, w[:, k], 0.0)
                e.append(acc + g[:, u])
            v = jnp.argmax(jnp.stack(e, 1), axis=1).astype(x.dtype)
            x = jnp.where(in_class[q], v, x)
            if evidence is not None:
                x = jnp.where(evidence[0] > 0.0,
                              evidence[1].astype(x.dtype), x)
        return x, ks[K]

    def call(st: dict, evidence=None) -> dict:
        x, key = jax.vmap(chain, in_axes=(0, 0, None))(st["x"], st["key"],
                                                       evidence)
        return {"x": x, "key": key, "accepts": st["accepts"]}

    return call
