"""The lattice cell's harness on the CPU at a tiny size: a sound run of the
neighbour-list chromatic sweep reads ``correct``; each planted fault reads
not correct (one site flipped a call; the weights rounded to bfloat16,
the oracle at that precision in the program's place; a sweep that drops
one neighbour direction).  ``bench/cost_chromatic.py``'s counts and the
roofline reader, pinned at small shapes."""
import dataclasses

import jax
import numpy as np
import pytest

from bench import cost_chromatic, system
from bench import run as bench_run
from bench.spec import plugin

SEED = 2 ** 31 + 77          # larger than 32 signed bits hold
GRID = 8


def tiny_cell() -> dict:
    args = {"grid": GRID, "beta": 0.4406868, "D": 2, "periodic": True}
    cfg = {"name": "lattice-8x8", "reference": dict(args, family="lattice"),
           "program": {"graph": "make_lattice_graph", "args": args}}
    work = {"config": "lattice-8x8", "engine": "gibbs", "backend": "jnp",
            "chains": 8, "sweep": GRID * GRID,
            "params": {"schedule": "chromatic"},
            "traffic": {"name": "t", "kind": "offline", "in_flight": 2},
            "check": {"mismatch_share": 0.0, "calls": "all", "block": 8}}
    return {"entry": {"name": "tiny", "config": "lattice-8x8",
                      "traffic": "t", "chips": 1, "why": "test"},
            "work": work, "config": cfg, "per_layer": [],
            "end_to_end": [{"name": "site_updates_per_s",
                            "unit": "updates/s"},
                           {"name": "setup_s", "unit": "s"}]}


def one_site_flipped(eng):
    sweep = eng.sweep_fn

    def broken(st, **kw):
        new = sweep(st, **kw)
        return new._replace(x=new.x.at[0, 0].set(1 - new.x[0, 0]))
    return dataclasses.replace(eng, sweep_fn=broken)


def left_neighbour_dropped(eng):
    """The program on a graph whose edge to the left neighbour weighs 0."""
    from repro.core import samplers
    g = eng.graph
    nbr, w = np.asarray(g.nbr), np.asarray(g.w).copy()
    site = np.arange(g.n)
    left = site - site % GRID + (site - 1) % GRID
    w[nbr == left[:, None]] = 0.0
    g = dataclasses.replace(g, w=jax.numpy.asarray(w))
    return dataclasses.replace(
        eng, graph=g, sweep_fn=samplers._build_neighbor_chromatic_sweep(g))


def run_with(monkeypatch, fault=None, control=None):
    if fault is not None:
        make = system.engine
        monkeypatch.setattr(system, "engine",
                            lambda work, g: fault(make(work, g)))
    cell = tiny_cell()
    if control is None:
        return bench_run.execute(cell, SEED, 0.15, False, jax.devices())
    res = plugin("traffic", "offline").run(
        bench_run.Context(cell, SEED, 0.0, False), control=control,
        max_calls=160)
    return bench_run.result_line(cell, res, False, jax.devices())


def test_sound_run_is_correct(monkeypatch):
    out = run_with(monkeypatch)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["mismatch_share"]["value"] == 0.0
    assert set(out["metrics"]) == {"site_updates_per_s", "setup_s"}


@pytest.mark.parametrize("fault, control", [
    (one_site_flipped, None), (left_neighbour_dropped, None),
    (None, "bfloat16")], ids=["one-site-flipped", "neighbour-dropped",
                              "bfloat16-weights"])
def test_planted_fault_is_not_correct(monkeypatch, fault, control):
    out = run_with(monkeypatch, fault, control)
    assert not out["correct"], out["checks"]
    assert out["checks"]["mismatch_share"]["value"] > 0
    assert out["checks"]["unchecked_calls"]["value"] == 0


def test_cost_counts_by_hand():
    got = cost_chromatic.sweep_cost(n=64, D=2, delta=4, chains=8)
    assert got["updates"] == 512
    assert got["flops"] == 512 * 2 * 4                  # D * Delta each
    state = 2 * 8 * 64 * 4                              # x in and out
    table = 64 * 4 * (4 + 4)                            # nbr and w once
    noise = 512 * 2 * 4                                 # D Gumbel each
    assert got["bytes"] == state + table + noise
    # the cell: about 1.1 GB a call, 1.35 ms at the v5e's 819 GB/s
    cell = cost_chromatic.sweep_cost(n=2 ** 20, D=2, delta=4, chains=64)
    assert cell["bytes"] == 1_107_296_256


def test_roofline_reader_reads_the_sweep_program_only():
    read = plugin("metrics", "chromatic_sweep_roofline").read
    rec = {"n": 2 ** 20, "D": 2, "delta": 4, "chains": 64, "calls": 10,
           "device_kind": "TPU v5 lite"}
    least = 1_107_296_256 / 819e9
    red = {"modules": {"jit_gibbs_chromatic_sweep": 10 * 4 * least,
                       "jit_keep_copy": 1.0}}
    assert read(rec, red) == pytest.approx(25.0)
    assert read(rec, {"modules": {"jit_gibbs_sweep": 1.0}}) is None
