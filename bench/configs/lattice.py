"""Plain reference of the nearest-neighbour lattice, written from its
definition and independent of the program: ``grid x grid`` sites numbered
row-major, an edge to each of the 4 lattice neighbours (wrapped round when
periodic), match weight ``2 beta`` on every edge, checkerboard colours.
Everything is numpy; the arrays are handed to the oracle.

Each site's neighbours are listed in ascending index order and padded with
the site itself at weight 0 up to 4 (the order the energies are summed in).
"""
from __future__ import annotations

import numpy as np


def build(cfg: dict, *, tables_from: str = "float64") -> dict:
    """The lattice of configuration ``cfg`` as the oracle reads it
    (``tables_from`` has no tables to choose between here)."""
    grid, periodic = int(cfg["grid"]), bool(cfg["periodic"])
    n = grid * grid
    r, c = np.divmod(np.arange(n), grid)
    cand, real = [], []
    for dr, dc in ((-1, 0), (1, 0), (0, -1), (0, 1)):
        rr, cc = r + dr, c + dc
        real.append(periodic | ((rr >= 0) & (rr < grid) & (cc >= 0)
                                & (cc < grid)))
        cand.append((rr % grid) * grid + cc % grid)
    cand, real = np.stack(cand, 1), np.stack(real, 1)
    # padding sorts last: key n for a missing neighbour
    key = np.where(real, cand, n)
    order = np.argsort(key, axis=1, kind="stable")
    nbr = np.take_along_axis(key, order, 1)
    pad = nbr == n
    nbr = np.where(pad, np.arange(n)[:, None], nbr).astype(np.int32)
    w = np.where(pad, 0.0, np.float32(2.0 * cfg["beta"])).astype(np.float32)
    return {"n": n, "D": int(cfg["D"]), "delta": 4, "nbr": nbr, "w": w,
            "colors": ((r + c) % 2).astype(np.int32)}
