"""Plain replay of one ``Engine.sweep`` call of vanilla Gibbs (Algorithm 1)
on the fused-kernel path: ``S`` sequential single-site updates per chain.

The random streams follow the path's documented contract: each chain's
key splits three ways into (sites, Gumbel noise, next key); the sites are
``S`` uniform draws over the sites (with evidence: an inverse-CDF draw
uniform over the unobserved sites); the noise is ``(S, D)`` standard
Gumbel.  Each update sets ``x_i = argmax_u (e_u + g_u)`` with the exact
conditional energy ``e_u = sum_j W_ij [x_j = u]`` (Gumbel-max is a draw
from ``exp(e_u)``).  Energies are elementwise float32 sums, exact on any
backend; ``table`` may round W first (the control).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def site_draws(ki, S, n, evidence):
    """(C, S) site ids from per-chain keys ``ki`` (C, 2)."""
    if evidence is None:
        return jax.vmap(lambda k: jax.random.randint(k, (S,), 0, n))(ki)
    c = jnp.cumsum(1.0 - evidence[0])
    cdf = c / jnp.maximum(c[-1], 1e-30)
    u = jax.vmap(lambda k: jax.random.uniform(k, (S,)))(ki)
    i = jnp.searchsorted(cdf, u, side="right").astype(jnp.int32)
    return jnp.minimum(i, n - 1)


def energies(w_rows, x, D):
    """(C, D): ``sum_j w[c, j] [x[c, j] = u]`` for every value u."""
    return jnp.stack([jnp.sum(jnp.where(x == u, w_rows, 0.0), axis=1)
                      for u in range(D)], axis=1)


def make(graph: dict, params: dict, S: int, table):
    W = jnp.asarray(table(graph["W"]))
    n, D = graph["n"], graph["D"]

    def call(st: dict, evidence=None) -> dict:
        x, key = st["x"], st["key"]
        C = x.shape[0]
        rows = jnp.arange(C)
        ks = jax.vmap(lambda k: jax.random.split(k, 3))(key)
        i = site_draws(ks[:, 0], S, n, evidence)
        gumbel = jax.vmap(lambda k: jax.random.gumbel(k, (S, D)))(ks[:, 1])

        def update(x, s):
            e = energies(W[i[:, s]], x, D)
            v = jnp.argmax(e + gumbel[:, s], axis=1).astype(jnp.int32)
            return x.at[rows, i[:, s]].set(v), None

        x, _ = jax.lax.scan(update, x, jnp.arange(S))
        return {"x": x, "key": ks[:, 2], "accepts": st["accepts"]}

    return call
