"""Multi-device tests (shard_map Gibbs engine, compressed collectives).

These spawn subprocesses because the 8-device host platform flag must be
set before jax initializes — the main test process keeps 1 device (per the
dry-run-only rule for device-count overrides).

XLA:CPU's in-process collectives can hang when consecutive sharded programs
overlap on a forced multi-device host: a jitted ``shard_map`` psum over a
(2, 4) CPU mesh called 2000 times in a loop hangs in the all-reduce
rendezvous in about half the runs, with async dispatch on or off, and
never when each call is waited for.  ``_run`` therefore makes every
``Engine.sweep`` wait for its result in these subprocesses (``_SERIAL``);
the engine itself dispatches ahead on every platform.
"""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


_SERIAL = """
import jax
from repro.core import engine as _engine
_dispatch = _engine.Engine.sweep
_engine.Engine.sweep = (
    lambda self, *a, **k: jax.block_until_ready(_dispatch(self, *a, **k)))
"""


def _run(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c",
                          _SERIAL + textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=1200)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_dist_sweep_matches_reference_all_engines():
    """All four dist sweep engines (2 dp x 4 mp, ONE psum per S-update
    sweep through the shared template) match the exact marginals the jnp
    engines are validated against (test_engine.py / test_sweep.py validate
    the jnp sweeps on the same enumerable graph)."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import engine
        from repro.core.factor_graph import make_potts_graph, TabularPairwiseGraph
        from repro.launch.mesh import make_auto_mesh

        g = make_potts_graph(grid=2, beta=0.8, D=3)     # n=4, enumerable
        tg = TabularPairwiseGraph.from_match_graph(g)
        pi = tg.pi(); states = tg.all_states()
        exact = np.zeros((g.n, g.D))
        for p_, s_ in zip(pi, states):
            for i, v in enumerate(s_):
                exact[i, v] += p_

        mesh = make_auto_mesh((2,4), ("data","model"))
        C, S, calls = 64, 4, 800
        for name in ("gibbs", "mgpmh", "min-gibbs", "doublemin"):
            kw = dict(lam=float(2*g.psi**2)) if name == "min-gibbs" else {}
            eng = engine.make(name, g, backend="dist", mesh=mesh, sweep=S,
                              **kw)
            assert eng.updates_per_call == S
            st = eng.init(jax.random.PRNGKey(0), C)
            for _ in range(calls):
                st = eng.sweep(st)
            emp = np.asarray(st.marg).sum(0) / (float(st.count) * C)
            err = np.abs(emp - exact).max()
            print("ERR", name, err)
            assert err < 0.05, (name, err)
    """)
    assert out.count("ERR") == 4


def test_compressed_psum_mean():
    """int8 RS/AG all-reduce with error feedback: close to the exact mean,
    residual bounded by the quantization step."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.runtime.compression import compressed_psum_mean

        from repro.launch.mesh import make_auto_mesh
        mesh = make_auto_mesh((8,), ("data",))
        L = 1024
        x = jnp.asarray(np.random.default_rng(0).normal(size=(8, L)).astype(np.float32))
        err0 = jnp.zeros((8, L), jnp.float32)

        def body(xv, ev):
            mean, err = compressed_psum_mean(xv[0], "data", ev[0])
            return mean, err[None]           # err stays per-shard
        f = jax.shard_map(body, mesh=mesh,
                          in_specs=(P("data", None), P("data", None)),
                          out_specs=(P(None), P("data", None)),
                          check_vma=False)
        with mesh:
            mean, err = f(x, err0)
        got = np.asarray(mean)
        want = np.asarray(x).mean(0)
        rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
        print("REL", rel)
        assert rel < 0.05, rel
        # error feedback captured the residual
        assert np.abs(np.asarray(err)).max() <= np.abs(np.asarray(x)).max() / 127.0 + 1e-6
    """)
    assert "REL" in out


def test_chromatic_gibbs_lattice():
    """Beyond-paper chromatic sweeps match exact marginals on a 2-colorable
    lattice (single process — no sharding needed for correctness)."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.runtime.dist_gibbs import (make_lattice_ising,
                                              lattice_colors,
                                              make_chromatic_gibbs_step)
        from repro.core.factor_graph import TabularPairwiseGraph
        g = make_lattice_ising(3, beta=0.45)   # n=9, enumerable (2^9)
        colors = lattice_colors(3)
        step = make_chromatic_gibbs_step(g, colors)
        C = 128
        x = jnp.zeros((C, g.n), jnp.int32)
        key = jax.random.PRNGKey(0)
        marg = jnp.zeros((C, g.n, 2), jnp.float32)
        sweeps = 3000
        @jax.jit
        def run(x, key, marg):
            def body(carry, _):
                x, key, marg = carry
                for color in (0, 1):
                    key, sub = jax.random.split(key)
                    x = step(x, sub, color)
                marg = marg + jax.nn.one_hot(x, 2, dtype=jnp.float32)
                return (x, key, marg), None
            (x, key, marg), _ = jax.lax.scan(body, (x, key, marg), None, length=sweeps)
            return marg
        marg = run(x, key, marg)
        emp = np.asarray(marg).sum(0) / (sweeps * C)
        tg = TabularPairwiseGraph.from_match_graph(g)
        pi = tg.pi(); states = tg.all_states()
        exact = np.zeros((g.n, 2))
        for p_, s_ in zip(pi, states):
            for i, v in enumerate(s_):
                exact[i, v] += p_
        err = np.abs(emp - exact).max()
        print("ERR", err)
        assert err < 0.05, err
    """)
    assert "ERR" in out


def test_sharded_moe_matches_gspmd():
    """moe_ffn_sharded (shard_map local dispatch) must match the GSPMD
    reference loss for both TP (mixtral) and EP (deepseek) parallelism."""
    out = _run("""
        import dataclasses, jax, jax.numpy as jnp
        from repro.configs.registry import SMOKES
        from repro.models import transformer as T, meshctx
        from repro.launch.mesh import make_auto_mesh
        mesh = make_auto_mesh((2,4), ("data","model"))
        for name, par in [("mixtral-8x7b","tp"), ("deepseek-v2-lite-16b","ep")]:
            cfg0 = dataclasses.replace(SMOKES[name], moe_parallelism=par)
            params = T.init_params(cfg0, jax.random.PRNGKey(0))
            toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 1,
                                      cfg0.vocab_size, dtype=jnp.int32)
            batch = {"tokens": toks, "labels": toks}
            meshctx.clear()
            l0 = float(T.loss_fn(cfg0, params, batch, loss_chunk=32))
            cfg1 = dataclasses.replace(cfg0, moe_impl="shard_map")
            meshctx.set_mesh(mesh, ("data",), "model")
            with mesh:
                l1 = float(jax.jit(lambda p, b: T.loss_fn(cfg1, p, b,
                                                          loss_chunk=32))(params, batch))
            meshctx.clear()
            # per-shard local capacity changes which tokens drop (both
            # parallelisms dispatch shard-locally) + bf16 noise
            assert abs(l0 - l1) < 2e-2, (name, l0, l1)
            print("OK", name, abs(l0 - l1))
    """)
    assert out.count("OK") == 2


def test_dist_chromatic_bitexact_lattice64():
    """The ChromaticBlocks dist schedule (graph column-sharded over 8 model
    shards, one psum per color class) is BIT-exact vs the single-host dense
    chromatic reference on lattice-ising-64x64: the lattice energies are
    small-integer multiples of beta, exactly representable under any
    summation order, and the key/draw protocol mirrors the dense path."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import engine, make_lattice_ising, lattice_colors
        from repro.launch.mesh import make_auto_mesh
        from repro.runtime.dist_gibbs import make_chromatic_gibbs_step

        grid = 64
        g = make_lattice_ising(grid, beta=0.4)
        colors = lattice_colors(grid)
        mesh = make_auto_mesh((1, 8), ("data", "model"))
        eng = engine.make("gibbs", g, backend="dist", mesh=mesh,
                          schedule=engine.ChromaticBlocks(colors))
        assert eng.updates_per_call == g.n == 64 * 64
        C = 2
        key0 = jax.random.PRNGKey(3)
        st = eng.init(key0, C)
        dense = make_chromatic_gibbs_step(g, colors)

        # replicate the dist key protocol host-side on the dense reference
        x_ref = jnp.zeros((C, g.n), jnp.int32)
        k = jax.random.split(key0, 1)[0]    # the single dp-shard key
        for sweep in range(2):
            k, master = jax.random.split(k)
            keys = jax.random.split(master, 2)
            for c in range(2):
                x_ref = dense(x_ref, keys[c], c)
            st = eng.sweep(st)
            np.testing.assert_array_equal(np.asarray(st.x),
                                          np.asarray(x_ref))
            print("BITEXACT", sweep)
    """)
    assert out.count("BITEXACT") == 2


def test_dist_adaptive_scan():
    """AdaptiveScan on the dist backend: the flip-rate table is reduced
    across every data shard inside the sweep's one psum (no extra
    collective), adapts toward the sticky strong-pair sites, and the chain
    stays correct (exact uniform marginals on hetero-pairs-24)."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import engine
        from repro.launch.mesh import make_auto_mesh

        g = engine.make_workload("hetero-pairs-24").graph   # n=24
        mesh = make_auto_mesh((2, 4), ("data", "model"))
        C, S, calls = 32, 16, 500
        eng = engine.make("gibbs", g, backend="dist", mesh=mesh,
                          schedule=engine.AdaptiveScan(sweep_len=S,
                                                       refresh_every=4))
        st = eng.init(jax.random.PRNGKey(0), C)
        cdf0 = np.asarray(st.cdf).copy()
        for _ in range(calls):
            st = eng.sweep(st)
        cdf = np.asarray(st.cdf)
        emp = np.asarray(st.marg).sum(0) / (float(st.count) * C)
        err = np.abs(emp - 0.5).max()       # exact marginals are uniform
        assert err < 0.06, err
        assert abs(cdf[-1] - 1.0) < 1e-4
        assert not np.allclose(cdf, cdf0)   # the table adapted
        p = np.diff(np.concatenate([[0.0], cdf]))
        # sticky strong-pair sites (the first 4) upweighted vs weak sites
        assert p[:4].mean() > 1.5 * p[4:].mean(), p
        # both dp shards fed the table: per-shard counters accumulated
        hits = np.asarray(st.hits)
        assert hits.shape[0] == 2 and (hits.sum(1) > 0).all()
        print("ADAPTIVE_OK", err)
    """)
    assert "ADAPTIVE_OK" in out


def test_dist_telemetry_matches_jnp():
    """``Engine.sweep(state, telemetry=...)`` on the dist backend (the
    donated-buffer copy path) agrees with the jnp backend on hetero-pairs-24
    for every dist engine: acceptance counters statistically match and the
    per-site split-R-hat profile is comparable (mean over sites, plus a
    factor-2 bound on the heavy-tailed worst site)."""
    out = _run("""
        import jax, numpy as np
        from repro.core import engine
        from repro import diagnostics as diag
        from repro.launch.mesh import make_auto_mesh

        g = engine.make_workload("hetero-pairs-24").graph
        mesh = make_auto_mesh((2, 4), ("data", "model"))
        C, S, calls = 32, 8, 120
        for name in ("gibbs", "mgpmh", "min-gibbs", "doublemin"):
            kw = dict(lam=256.0) if name == "min-gibbs" else {}
            acc, rhat = {}, {}
            for backend in ("jnp", "dist"):
                bkw = dict(mesh=mesh) if backend == "dist" else {}
                eng = engine.make(name, g, backend=backend, sweep=S,
                                  **kw, **bkw)
                st = eng.init(jax.random.PRNGKey(2), C)
                tel = eng.init_telemetry(st, half_at=calls // 2)
                for _ in range(calls):
                    st, tel = eng.sweep(st, tel)
                acc[backend] = diag.acceptance_rate(tel, eng.exact_accept)
                rhat[backend] = diag.split_rhat(tel)
            assert abs(acc["jnp"] - acc["dist"]) < 0.05, (name, acc)
            r_j, r_d = rhat["jnp"], rhat["dist"]
            assert np.isfinite(r_d).all(), name
            # the site-mean R-hat profile is stable; the max over sites is
            # a heavy-tailed point estimate, bounded to a factor of 2
            assert abs(r_j.mean() - r_d.mean()) < 0.2, (name, r_j.mean(),
                                                        r_d.mean())
            assert max(r_j.max(), r_d.max()) < 2 * min(r_j.max(), r_d.max())
            print("TEL_OK", name, round(acc["dist"], 3),
                  round(float(r_d.mean()), 3))
    """)
    assert out.count("TEL_OK") == 4


def test_supervised_dist_crash_resume_bit_exact():
    """A supervised dist run (2 dp x 4 mp) preempted AND checkpoint-corrupted
    mid-run ends with marginals bit-identical to the fault-free supervised
    run — the whole fault path (verify -> quarantine -> restore -> replay)
    is deterministic."""
    out = _run("""
        import tempfile, numpy as np
        from repro.launch.gibbs import run_supervised

        kw = dict(steps=24, chains=16, mp_shards=4, backend="dist", chunk=4)
        with tempfile.TemporaryDirectory() as da, \\
                tempfile.TemporaryDirectory() as db:
            clean = run_supervised("hetero-pairs-24", "mgpmh",
                                   ckpt_dir=da, **kw)
            plan = ('{"faults": ['
                    '{"step": 2, "kind": "corrupt", "target": "arrays"},'
                    '{"step": 2, "kind": "preempt"},'
                    '{"step": 4, "kind": "nan", "target": "x"}]}')
            fault = run_supervised("hetero-pairs-24", "mgpmh",
                                   ckpt_dir=db, fault_plan=plan, **kw)
            assert fault.restarts >= 1 and fault.rollbacks >= 1
            assert np.array_equal(clean.marginals, fault.marginals), (
                np.abs(clean.marginals - fault.marginals).max())
            print("SUP_DIST_OK", fault.restarts, fault.rollbacks)
    """)
    assert "SUP_DIST_OK" in out


def test_supervised_dist_elastic_8_to_4_devices():
    """Simulated device loss mid-run: a checkpoint written on the 8-device
    (2 dp x 4 mp) mesh restores onto the surviving 4 devices (1 dp x 4 mp)
    — per-dp-shard leaves are re-binned — and the run completes with sane
    marginals."""
    out = _run("""
        import tempfile, numpy as np
        from repro.launch.gibbs import run_supervised

        plan = '{"faults": [{"step": 3, "kind": "device-loss", "keep": 4}]}'
        with tempfile.TemporaryDirectory() as d:
            res = run_supervised("hetero-pairs-24", "mgpmh", steps=80,
                                 chains=16, ckpt_dir=d, mp_shards=4,
                                 backend="dist", fault_plan=plan, chunk=8,
                                 sweep=24)
        assert res.restarts >= 1
        assert any(i["kind"] == "elastic" and i["devices"] == 4
                   for i in res.incidents)
        assert res.outer_steps == 10
        m = res.marginals
        np.testing.assert_allclose(m.sum(-1), 1.0, atol=1e-4)
        # hetero-pairs marginals are exactly uniform; loose mixing check
        assert np.abs(m - 1.0 / m.shape[-1]).max() < 0.25
        print("ELASTIC_OK", res.restarts)
    """)
    assert "ELASTIC_OK" in out
