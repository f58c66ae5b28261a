"""Work of one sweep call, counted at the algorithm level from shapes: the
same count whatever backend runs the call.

Operations follow the paper's cost per site update (De Sa, Chen & Wong,
ICML 2018, Table 1):

  gibbs      D * Delta             the exact conditional: D energies, each
                                   over the Delta neighbours
  mgpmh      D * lam + 2 * Delta   minibatch energies over the lam
                                   expected draws for D values, plus the
                                   exact MH energies at the two values
                                   (x_i and the proposal) over a row
  min-gibbs  D * lam + Delta       D global minibatches of lam draws; the
                                   row read that re-anchors the cache
  doublemin  D * lam1 + lam2 + Delta

one operation per (value, term) pair.  The copy in ``obs/costmodel.py``
left out the exact-energy row (the ``+ Delta`` terms), which every
minibatch sampler reads to accept, or to re-anchor, a move.

Bytes are the compulsory HBM traffic of the call, so no implementation
can beat them: each table row the call's updates touch, read once (the
expected number of distinct sites among ``C * S`` uniform draws); for the
alias tables only the entries the draws read when that is fewer; the
chain state in and out at 4 bytes a site; and every random number the
updates consume at 4 bytes.  At these sizes the bytes set the bound:
``least_seconds`` says which.
"""
from __future__ import annotations

from typing import Dict


def distinct_rows(n: int, draws: int) -> float:
    """Expected number of distinct sites among ``draws`` uniform picks."""
    return n * (1.0 - (1.0 - 1.0 / n) ** draws)


def sweep_cost(engine: str, *, n: int, D: int, delta: int, chains: int,
               sweep: int, lam: float = 0.0, lam2: float = 0.0
               ) -> Dict[str, float]:
    """Operations and bytes of one call of ``sweep`` updates on each of
    ``chains`` chains over a dense graph of ``n`` sites."""
    updates = chains * sweep
    rows = distinct_rows(n, updates)
    row_bytes = rows * n * 4.0
    state_bytes = 2.0 * chains * n * 4.0
    # random numbers per update: the site and D Gumbel values, always
    rand = 1.0 + D
    if engine == "gibbs":
        ops = D * delta
        table_bytes = row_bytes
    elif engine == "mgpmh":
        ops = D * lam + 2.0 * delta
        # the W row for the exact energies, and two alias entries a draw
        table_bytes = row_bytes + min(updates * lam * 8.0, 2.0 * row_bytes)
        rand += 1.0 + 2.0 * lam + 1.0      # batch size, draws, accept
    elif engine == "min-gibbs":
        ops = D * lam + delta
        table_bytes = row_bytes + min(updates * D * lam * 16.0,
                                      2.0 * row_bytes)
        rand += D + 4.0 * D * lam
    elif engine == "doublemin":
        ops = D * lam + lam2 + delta
        table_bytes = row_bytes + min(updates * (lam + 2 * lam2) * 8.0,
                                      2.0 * row_bytes)
        rand += 2.0 + 2.0 * lam + 4.0 * lam2 + 1.0
    else:
        raise ValueError(f"no cost model for engine {engine!r}")
    rand_bytes = updates * rand * 4.0
    return {"updates": float(updates), "flops": float(updates * ops),
            "bytes": float(table_bytes + state_bytes + rand_bytes)}


def least_seconds(cost: Dict[str, float], peak: Dict[str, float]):
    """(seconds, bound): the least time the chip could take for ``cost``
    and which of ``"flops"`` or ``"bytes"`` sets it."""
    t_ops = cost["flops"] / peak["flops_per_s"]
    t_bytes = cost["bytes"] / peak["hbm_bytes_per_s"]
    return (t_ops, "flops") if t_ops >= t_bytes else (t_bytes, "bytes")



def peak(device_kind: str) -> Dict[str, float]:
    """The published peaks of ``device_kind`` from ``peaks.json``; a device
    missing from the table is an error, not a default."""
    import json
    import os
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"bench/peaks.json has {sorted(table)}")
    return table[device_kind]


def roofline_share(rec: dict, seconds_per_call: float) -> float:
    """Per cent of the roofline one sweep call of the record's cell reaches
    when it takes ``seconds_per_call`` on the device."""
    p = rec["params"]
    c = sweep_cost(rec["engine"], n=rec["n"], D=rec["D"], delta=rec["delta"],
                   chains=rec["chains"], sweep=rec["sweep"],
                   lam=float(p.get("lam", p.get("lam1", 0.0))),
                   lam2=float(p.get("lam2", 0.0)))
    least, _ = least_seconds(c, peak(rec["device_kind"]))
    return 100.0 * least / seconds_per_call
