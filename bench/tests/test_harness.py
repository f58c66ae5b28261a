"""Drive the harness on the CPU at a tiny size, past its look for a chip:
a sound run reads ``correct``; the timed path broken underneath reads
not correct, once for each fault an offline cell can have (a sweep that
returns its state unchanged; half the chains left out; one site of one
chain altered where the sweep writes it).  The pallas cells compare every
call, the dist cell a sample drawn from the seed.  One chip, no exchange
between chips to leave out."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from bench import run as bench_run
from bench import system

SEED = 2 ** 31 + 99          # larger than 32 signed bits hold


def tiny_cell(engine: str, backend: str) -> dict:
    ref = {"family": "potts", "grid": 6, "beta": 4.6, "D": 10, "gamma": 1.5}
    cfg = {"name": "potts-6x6", "reference": ref,
           "program": {"graph": "make_potts_graph",
                       "args": {"grid": 6, "beta": 4.6, "D": 10,
                                "gamma": 1.5}}}
    work = {"config": "potts-6x6", "engine": engine, "backend": backend,
            "chains": 8, "sweep": 4, "params": {},
            "traffic": {"name": "t", "kind": "offline", "in_flight": 2},
            "check": {"mismatch_share": 0.0,
                      "calls": 16 if backend == "dist" else "all",
                      "block": 8}}
    if backend == "dist":
        work["mesh"] = [1, 1]
    return {"entry": {"name": "tiny", "config": "potts-6x6", "traffic": "t",
                      "chips": 1, "why": "test"},
            "work": work, "config": cfg, "per_layer": [],
            "end_to_end": [{"name": "site_updates_per_s",
                            "unit": "updates/s"},
                           {"name": "setup_s", "unit": "s"}]}


def unchanged(sweep):
    return lambda st, **kw: st


def half_left_out(sweep):
    def broken(st, **kw):
        keep = (jnp.copy(st.x), jnp.copy(st.key), jnp.copy(st.accepts))
        new = sweep(st, **kw)
        half = jnp.arange(keep[0].shape[0]) < keep[0].shape[0] // 2
        pick = lambda a, b: jnp.where(
            half.reshape((-1,) + (1,) * (a.ndim - 1)), a, b) \
            if a.shape[:1] == half.shape else a
        return new._replace(x=pick(new.x, keep[0]),
                            key=pick(new.key, keep[1]),
                            accepts=pick(new.accepts, keep[2]))
    return broken


def one_site_altered(sweep):
    def broken(st, **kw):
        new = sweep(st, **kw)
        x = new.x.at[0, 0].set((new.x[0, 0] + 1) % 10)
        return new._replace(x=x)
    return broken


def run_with(monkeypatch, cell, fault=None):
    if fault is not None:
        make = system.engine

        def broken_engine(work, g):
            eng = make(work, g)
            return dataclasses.replace(eng, sweep_fn=fault(eng.sweep_fn))
        monkeypatch.setattr(system, "engine", broken_engine)
    return bench_run.execute(cell, SEED, 0.15, False, jax.devices())


@pytest.mark.parametrize("engine, backend", [
    ("mgpmh", "pallas"), ("gibbs", "pallas"), ("mgpmh", "dist")])
def test_sound_run_is_correct(monkeypatch, engine, backend):
    out = run_with(monkeypatch, tiny_cell(engine, backend))
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == jax.devices()[0].platform
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"site_updates_per_s", "setup_s"}


@pytest.mark.parametrize("engine, backend, fault", [
    ("mgpmh", "pallas", unchanged), ("mgpmh", "pallas", half_left_out),
    ("mgpmh", "pallas", one_site_altered),
    ("gibbs", "pallas", half_left_out), ("gibbs", "pallas", unchanged),
    ("gibbs", "pallas", one_site_altered), ("mgpmh", "dist", unchanged),
    ("mgpmh", "dist", half_left_out), ("mgpmh", "dist", one_site_altered)])
def test_broken_sweep_is_not_correct(monkeypatch, engine, backend, fault):
    out = run_with(monkeypatch, tiny_cell(engine, backend), fault)
    assert not out["correct"], out["checks"]
    assert out["checks"]["mismatch_share"]["value"] > 0
    assert out["checks"]["unchecked_calls"]["value"] == 0


def test_dist_copies_only_kept_calls_and_handles(monkeypatch):
    """A donating path copies a state only where the check keeps it or
    where the host waits on it, and still checks every kept call."""
    from bench.spec import plugin
    offline = plugin("traffic", "offline")
    copies = []
    init = offline.Sampler.__init__

    def counting(self, work, cfg, control=None):
        init(self, work, cfg, control)
        keep = self.keep
        self.keep = lambda d: copies.append(1) or keep(d)
    monkeypatch.setattr(offline.Sampler, "__init__", counting)
    out = run_with(monkeypatch, tiny_cell("mgpmh", "dist"))
    assert out["correct"], out["checks"]
    calls = out["attempted"]
    assert calls > 40, calls
    assert 2 <= len(copies) < calls, (len(copies), calls)



def test_group_metric_reads_its_quantity():
    """An end-to-end metric named ``<quantity>.<group>`` reports the
    traffic kind's quantity under its own name."""
    cell = {"end_to_end": [{"name": "site_updates_per_s.dist",
                            "unit": "updates/s"},
                           {"name": "setup_s", "unit": "s"}],
            "per_layer": []}
    res = {"metrics": {"site_updates_per_s": 5.0, "setup_s": 1.0},
           "correct": True, "attempted": 1, "failed": 0,
           "memory_peak_bytes": 0, "checks": {}}
    out = bench_run.result_line(cell, res, False, jax.devices())
    assert out["metrics"] == {
        "site_updates_per_s.dist": {"value": 5.0, "unit": "updates/s"},
        "setup_s": {"value": 1.0, "unit": "s"}}
