"""Plain replay of one ``Engine.sweep`` call of MGPMH (Algorithm 4) on the
fused-kernel path: ``S`` sequential single-site updates per chain.

Random streams, per the path's contract: each chain's key splits seven
ways into (sites, batch sizes, draw indices, alias uniforms, Gumbel
noise, accept uniforms, next key).  Sub-step ``s`` of a chain at site
``i``:

  B   ~ Poisson(lam * L_i / L), capped at the capacity K
  j_k = the row-``i`` alias draw from index uniform ``a_k`` and accept
        uniform ``b_k``: ``t = min(floor(a_k n), n - 1)``, ``j_k = t`` if
        ``b_k < prob[i, t]`` else ``alias[i, t]``               (k < B)
  eps_u = (L / lam) * #{k < B : x[j_k] = u}                 (minibatch)
  v   = argmax_u (eps_u + g_u)                              (proposal)
  accept iff log U < e_v - e_{x_i} + eps_{x_i} - eps_v,
        with the exact energies e_u = sum_j W_ij [x_j = u]  (MH)

Sums are elementwise float32, exact on any backend; ``table`` may round
W and the alias tables first (the control).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.spec import plugin

_gibbs = plugin("oracle", "gibbs_pallas")


def mh_update(x, i_s, j, live, g, logu, W, scale, D):
    """One MGPMH sub-step for every chain: draws ``j`` (C, K) with mask
    ``live``; returns (x, accepted (C,) bool)."""
    C = x.shape[0]
    rows = jnp.arange(C)
    vals = jnp.take_along_axis(x, j, axis=1)
    cnt = jnp.stack([jnp.sum((vals == u) & live, axis=1)
                     for u in range(D)], axis=1)
    eps = scale * cnt.astype(jnp.float32)
    v = jnp.argmax(eps + g, axis=1).astype(jnp.int32)
    xi = x[rows, i_s]
    e = _gibbs.energies(W[i_s], x, D)
    log_a = (e[rows, v] - e[rows, xi] + eps[rows, xi] - eps[rows, v])
    accept = logu < log_a
    x = x.at[rows, i_s].set(jnp.where(accept, v, xi))
    return x, accept


def make(graph: dict, params: dict, S: int, table):
    W = jnp.asarray(table(graph["W"]))
    prob = jnp.asarray(table(graph["row_prob"]))
    alias = jnp.asarray(table(graph["row_alias"]).astype(np.int32))
    row_sum = jnp.asarray(graph["row_sum"])
    n, D, L = graph["n"], graph["D"], graph["L"]
    lam, K = float(params["lam"]), int(params["capacity"])
    scale = float(L / lam)

    def call(st: dict, evidence=None) -> dict:
        x, key = st["x"], st["key"]
        ks = jax.vmap(lambda k: jax.random.split(k, 7))(key)
        i = _gibbs.site_draws(ks[:, 0], S, n, evidence)
        lam_i = lam * row_sum[i] / L
        B = jax.vmap(lambda k, l: jax.random.poisson(
            k, l, dtype=jnp.int32))(ks[:, 1], lam_i)
        B = jnp.minimum(B, K)
        a = jax.vmap(lambda k: jax.random.uniform(k, (S, K)))(ks[:, 2])
        b = jax.vmap(lambda k: jax.random.uniform(k, (S, K)))(ks[:, 3])
        g = jax.vmap(lambda k: jax.random.gumbel(k, (S, D)))(ks[:, 4])
        logu = jnp.log(jax.vmap(lambda k: jax.random.uniform(
            k, (S,)))(ks[:, 5]))
        t = jnp.minimum((a * n).astype(jnp.int32), n - 1)
        j = jnp.where(b < prob[i[..., None], t], t, alias[i[..., None], t])
        live = jnp.arange(K)[None, None, :] < B[..., None]

        def update(carry, s):
            x, acc = carry
            x, ok = mh_update(x, i[:, s], j[:, s], live[:, s], g[:, s],
                              logu[:, s], W, scale, D)
            return (x, acc + ok.astype(jnp.int32)), None

        (x, acc), _ = jax.lax.scan(
            update, (x, jnp.zeros(x.shape[:1], jnp.int32)), jnp.arange(S))
        return {"x": x, "key": ks[:, 6], "accepts": st["accepts"] + acc}

    return call
