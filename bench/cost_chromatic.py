"""Work of one chromatic sweep call over neighbour lists, counted from
shapes, as ``bench/cost.py`` counts the dense sweeps.

Operations: every site of every chain updated once, each update D energies
over its Delta neighbours (the paper's gibbs cost, D * Delta an update).
Bytes, the least any implementation moves: the chain state in and out at
4 bytes a site, the neighbour table and its weights read once (4 bytes an
entry each), and every random number the updates consume (D Gumbel values
an update) at 4 bytes.  The bytes set the bound at the cell's size.
"""
from __future__ import annotations

from typing import Dict

from bench.cost import least_seconds, peak


def sweep_cost(*, n: int, D: int, delta: int, chains: int
               ) -> Dict[str, float]:
    """Operations and bytes of one call on ``chains`` chains of ``n``
    sites."""
    updates = chains * n
    return {"updates": float(updates),
            "flops": float(updates * D * delta),
            "bytes": float(2.0 * chains * n * 4.0 + n * delta * 8.0
                           + updates * D * 4.0)}


def roofline_share(rec: dict, seconds_per_call: float) -> float:
    """Per cent of the roofline one call of the record's cell reaches when
    it takes ``seconds_per_call`` on the device."""
    c = sweep_cost(n=rec["n"], D=rec["D"], delta=rec["delta"],
                   chains=rec["chains"])
    least, _ = least_seconds(c, peak(rec["device_kind"]))
    return 100.0 * least / seconds_per_call
