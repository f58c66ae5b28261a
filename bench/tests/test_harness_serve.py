"""The serving cell's harness on the CPU at a tiny size, past its look
for a chip: a sound run reads ``correct``; a sweep that leaves the lanes'
chains unchanged, and an answer altered where the pool produces it, read
not correct."""
import dataclasses

import jax
import pytest

from bench import run as bench_run
from bench.tests.test_harness import SEED, tiny_cell


def tiny_serve_cell() -> dict:
    c = tiny_cell("gibbs", "pallas")
    c["work"].update({
        "sweeps_per_chunk": 2, "max_conditioned": 2,
        "freshness": {"min_samples": 4, "min_ess_per_site": 4.0,
                      "max_rhat": 2.0},
        "traffic": {"name": "t", "kind": "open_loop", "rate_per_s": 20.0,
                    "sites_per_query": 3, "conditioned": 0.5,
                    "evidence_sets": 4, "min_observed": 1,
                    "max_observed": 2, "zipf_s": 1.1},
        "check": {"mismatch_share": 0.0, "calls": 32, "block": 16}})
    c["end_to_end"] = [{"name": "query_p95_ms", "unit": "ms"},
                       {"name": "setup_s", "unit": "s"}]
    return c


def run_serve(monkeypatch, fault=None):
    from repro.core import engine as engine_lib
    from repro.serving import ChainPool
    if fault == "unchanged":
        make = engine_lib.make

        def broken_make(*a, **kw):
            eng = make(*a, **kw)
            stats = eng.sweep_stats_fn

            def still(st, **kw2):
                return st, stats(st, **kw2)[1]
            return dataclasses.replace(eng, sweep_stats_fn=still)
        monkeypatch.setattr(engine_lib, "make", broken_make)
    if fault == "answer":
        submit = ChainPool.submit

        def altered(self, queries, **kw):
            out = submit(self, queries, **kw)
            for a in out:
                if a.marginals is not None:
                    a.marginals = a.marginals[:, ::-1].copy()
            return out
        monkeypatch.setattr(ChainPool, "submit", altered)
    return bench_run.execute(tiny_serve_cell(), SEED, 0.5, False,
                             jax.devices())


def test_sound_serve_run_is_correct(monkeypatch):
    out = run_serve(monkeypatch)
    assert out["correct"], out["checks"]
    assert out["attempted"] == 10 and out["failed"] == 0
    assert set(out["metrics"]) == {"query_p95_ms", "setup_s"}


@pytest.mark.parametrize("fault, check", [
    ("unchanged", "chunk_mismatch_share"), ("answer", "answers_unmatched")])
def test_broken_serve_is_not_correct(monkeypatch, fault, check):
    out = run_serve(monkeypatch, fault)
    assert not out["correct"], out["checks"]
    assert out["checks"][check]["value"] > 0
