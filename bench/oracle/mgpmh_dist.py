"""Plain replay of one ``Engine.sweep`` call of MGPMH (Algorithm 4) on the
dist path over a one-device mesh: ``S`` sequential single-site updates
per chain, every chain drawing from one shared key.

Random streams, per the path's contract: the key splits six ways into
(next key, sites, minibatch, -, Gumbel noise, accept uniforms); the
minibatch key is folded with the shard index (0) and split into (batch
sizes, draw indices, alias uniforms).  The sites are ``(C, S)`` uniform
draws; a draw at site ``i`` takes a uniform column ``t`` and keeps it
when its uniform is below ``prob[i, t]``, else ``alias[i, t]``.  The
update itself is the one of ``mgpmh_pallas``: minibatch proposal, exact
MH correction.  ``table`` may round W and the alias tables first
(the control).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.spec import plugin

_mgpmh = plugin("oracle", "mgpmh_pallas")

# the dist path builds its tables over the float32 weights it stores
TABLES_FROM = "float32"


def make(graph: dict, params: dict, S: int, table):
    W = jnp.asarray(table(graph["W"]))
    prob = jnp.asarray(table(graph["row_prob"]))
    alias = jnp.asarray(table(graph["row_alias"]).astype(np.int32))
    row_sum = jnp.asarray(graph["row_sum"])
    n, D, L = graph["n"], graph["D"], graph["L"]
    lam, K = float(params["lam"]), int(params["capacity"])
    scale = float(L / lam)

    def call(st: dict, evidence=None) -> dict:
        if evidence is not None:
            raise ValueError("the dist path takes no evidence")
        x, key = st["x"], st["key"]
        C = x.shape[0]
        nxt, ki, kd, _, kg, ka = jax.random.split(key.reshape(2), 6)
        i = jax.random.randint(ki, (C, S), 0, n)
        kb, kj, ku = jax.random.split(jax.random.fold_in(kd, 0), 3)
        B = jnp.minimum(jax.random.poisson(
            kb, lam * row_sum[i] / L, dtype=jnp.int32), K)
        t = jax.random.randint(kj, (C, S, K), 0, n)
        u = jax.random.uniform(ku, (C, S, K))
        j = jnp.where(u < prob[i[..., None], t], t, alias[i[..., None], t])
        live = jnp.arange(K)[None, None, :] < B[..., None]
        g = jax.random.gumbel(kg, (C, S, D))
        logu = jnp.log(jax.random.uniform(ka, (C, S)))

        def update(carry, s):
            x, acc = carry
            x, ok = _mgpmh.mh_update(x, i[:, s], j[:, s], live[:, s],
                                     g[:, s], logu[:, s], W, scale, D)
            return (x, acc + ok.astype(jnp.int32)), None

        (x, acc), _ = jax.lax.scan(
            update, (x, jnp.zeros((C,), jnp.int32)), jnp.arange(S))
        return {"x": x, "key": nxt.reshape(key.shape),
                "accepts": st["accepts"] + acc}

    return call
