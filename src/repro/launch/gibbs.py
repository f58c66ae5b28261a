"""Gibbs-engine launcher: the paper's workload end to end on whatever mesh
is present (devices x model shards), with checkpointed sampler state,
marginal-error reporting, and streaming convergence telemetry.

  PYTHONPATH=src python -m repro.launch.gibbs --config potts-20x20 \
      --engine mgpmh --steps 20000 --chains 64 [--ckpt-dir /tmp/gc]
  PYTHONPATH=src python -m repro.launch.gibbs --config hetero-pairs-1024 \
      --engine gibbs --backend jnp --adaptive --telemetry --sweep 64

Engines and workloads come straight from the registries in
``repro.core.engine`` — this launcher holds NO construction logic: it calls
``engine.make(...)`` and drives the returned Engine.  ``--backend dist``
(the default) shards the graph over the mesh (one psum per sweep for all
four dist algorithms, see runtime/dist_gibbs.py); ``--backend
jnp|pallas|auto`` runs the fused single-host schedules.  ``--adaptive``
switches to the telemetry-driven ``AdaptiveScan`` site-selection schedule
on any backend (under dist the cross-shard table reduction rides the
sweep's one psum).  ``--telemetry``
threads the streaming diagnostics carry through the run and logs mean
acceptance / max split-R-hat / ESS alongside throughput.  Sampler state
(chains, caches, rng, running marginals) is a pytree checkpointed/restored
exactly like model params — restart resumes the chain bit-exactly.
"""
from __future__ import annotations

import argparse
import os
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..core import engine as engine_lib
from ..checkpoint import checkpoint as ckpt
from .compile_cache import use_compile_cache
from .mesh import make_device_mesh


def _engine_factory(config: str, sweep: int, mp_shards: int, backend: str,
                    adaptive: bool):
    """``(make_engine, graph)`` where ``make_engine(name, devices,
    **params)`` builds the engine over an explicit device list — the ONE
    construction hook both the plain loop and the supervisor (which swaps
    engines on degrade/retune and shrinks the device list on elastic
    restart) call."""
    wl = engine_lib.make_workload(config)
    g = wl.graph
    schedule = (engine_lib.AdaptiveScan(sweep_len=max(sweep, 1)) if adaptive
                else engine_lib.UniformSites(max(sweep, 1)))

    def make_engine(name, devices, **params):
        if backend == "dist":
            mp = mp_shards or 1
            dp = max(len(devices) // mp, 1)
            mesh = make_device_mesh((dp, mp), ("data", "model"), devices)
            return engine_lib.make(name, g, schedule=schedule,
                                   backend="dist", mesh=mesh, **params)
        return engine_lib.make(name, g, schedule=schedule, backend=backend,
                               **params)
    return make_engine, g


def _build_engine(config: str, engine: str, sweep: int, mp_shards: int,
                  backend: str, adaptive: bool, params: dict):
    make_engine, g = _engine_factory(config, sweep, mp_shards, backend,
                                     adaptive)
    return make_engine(engine, list(jax.devices()), **params), g


def run_supervised(config: str, engine: str, steps: int, chains: int,
                   ckpt_dir: str = "", mp_shards: int = 0, seed: int = 0,
                   sweep: int = 0, backend: str = "dist",
                   adaptive: bool = False, fault_plan: str = "",
                   chunk: int = 16, max_restarts: int = 5):
    """The supervised counterpart of :func:`run`: same engine/workload
    flags, but the loop is driven by ``runtime.supervisor.SupervisedRun``
    — retrying restarts, verified-checkpoint rollback, health guards with
    λ-retune / degrade-to-gibbs escalation, elastic restart — optionally
    under a deterministic ``--fault-plan`` (inline JSON or a path)."""
    from ..runtime import supervisor as sup
    from ..runtime.faultinject import FaultPlan

    make_engine, g = _engine_factory(config, sweep, mp_shards, backend,
                                     adaptive)
    cfg = sup.SupervisorConfig(
        outer_steps=-(-steps // chunk), sweeps_per_outer=chunk,
        chains=chains, seed=seed, ckpt_dir=ckpt_dir,
        max_restarts=max_restarts, workload=config,
        heartbeat=os.path.join(ckpt_dir, "heartbeat.json")
        if ckpt_dir else "")
    plan = FaultPlan.from_json(fault_plan) if fault_plan else None
    res = sup.SupervisedRun(engine, make_engine, cfg, plan).run()
    m = res.marginals
    err = float(np.sqrt(((m - 1 / g.D) ** 2).sum(-1)).mean())
    print(f"[gibbs] supervised done: outer_steps={res.outer_steps} "
          f"restarts={res.restarts} rollbacks={res.rollbacks} "
          f"engine={res.engine.name} marg_err={err:.4f}", flush=True)
    return res


def run(config: str, engine: str, steps: int, chains: int,
        ckpt_dir: str = "", log_every: int = 2000, mp_shards: int = 0,
        seed: int = 0, sweep: int = 0, backend: str = "dist",
        adaptive: bool = False, telemetry: bool = False,
        params: Optional[dict] = None) -> dict:
    """Run ``steps`` sweeps of ``engine`` on workload ``config``; logs
    every ``log_every`` steps.  ``params`` go to ``engine.make`` (lam,
    capacity, ...).  Returns the final ``state``, the engine's
    ``describe()`` and the last logged ``marg_err`` / ``acceptance`` /
    ``updates_per_sec``."""
    from .. import diagnostics as diag

    eng, g = _build_engine(config, engine, sweep, mp_shards, backend,
                           adaptive, params or {})
    upd_per_step = eng.updates_per_call
    dist = eng.backend == "dist"
    rec = obs.get_recorder()
    labels = rec.register_engine(eng, workload=config, chains=chains)

    st = eng.init(jax.random.PRNGKey(seed), chains)
    tel = eng.init_telemetry(st) if telemetry else None
    # non-dist engines carry no running marginals — accumulate here and
    # checkpoint (st, marg) together so resume keeps the full-run estimate
    # (dist keeps marg/count inside its own state)
    marg = None if dist else jnp.zeros((chains, g.n, g.D), jnp.float32)
    start = 0
    if ckpt_dir and (last := ckpt.latest_step(ckpt_dir)) is not None:
        if dist:
            st = ckpt.restore(ckpt_dir, last, st)
        else:
            st, marg = ckpt.restore(ckpt_dir, last, (st, marg))
        start = last
        print(f"[gibbs] resumed at step {start}")

    t0 = time.time()
    last_logged = 0
    out = {"engine": eng.describe()}
    for s in range(start, steps):
        # span brackets one compiled sweep launch (dispatch only — the
        # host read below at the log boundary is the loop's only sync)
        with rec.span("sweep_chunk", **labels):
            if tel is None:
                st = eng.sweep(st)
            else:
                st, tel = eng.sweep(st, tel)
            if not dist:
                marg = marg + jax.nn.one_hot(st.x, g.D, dtype=jnp.float32)
        if (s + 1) % log_every == 0 or s == steps - 1:
            # samples accumulated since step 0 (marg and accepts are both
            # cumulative across restarts on every backend)
            cnt = float(st.count) if dist else float(s + 1)
            m = np.asarray(st.marg if dist else marg).sum(0) / (cnt * chains)
            err = float(np.sqrt(((m - 1 / g.D) ** 2).sum(-1)).mean())
            # count counts accumulated samples (sweeps accumulate once
            # per S site updates); acc is per site update either way
            # (identically 1 for Gibbs-type engines, which keep no counter)
            acc = 1.0 if eng.exact_accept else (
                float(np.asarray(st.accepts).mean()) / (cnt * upd_per_step))
            rate = ((s + 1 - start) * chains * upd_per_step
                    / (time.time() - t0))
            out.update(marg_err=err, acceptance=acc, updates_per_sec=rate)
            line = (f"[gibbs] step {s+1:7d} marg_err={err:.4f} "
                    f"acc={acc:.3f} {rate/1e3:.1f}k updates/s")
            if tel is not None:
                ts = diag.summarize(tel, eng.exact_accept,
                                    elapsed_sec=time.time() - t0)
                line += (f" rhat={ts['max_split_rhat']:.3f} "
                         f"ess/s={ts.get('ess_per_sec', 0.0):.1f}")
            print(line, flush=True)
            # piggyback the log boundary's host read for metric export
            rec.count("sweeps_total", s + 1 - start - last_logged, **labels)
            rec.count("updates_total",
                      (s + 1 - start - last_logged) * chains * upd_per_step,
                      **labels)
            last_logged = s + 1 - start
            rec.gauge("acceptance", acc, **labels)
            rec.gauge("marginal_err", err, **labels)
            rec.snapshot()
            if ckpt_dir:
                ckpt.save(ckpt_dir, s + 1, st if dist else (st, marg))
    out["state"] = st
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="potts-20x20",
                    choices=list(engine_lib.workload_names()))
    ap.add_argument("--engine", default="mgpmh",
                    choices=list(engine_lib.names()))
    ap.add_argument("--backend", default="dist",
                    choices=["dist", "jnp", "pallas", "auto"])
    ap.add_argument("--steps", type=int, default=20_000)
    ap.add_argument("--chains", type=int, default=64)
    ap.add_argument("--mp-shards", type=int, default=0)
    ap.add_argument("--sweep", type=int, default=0,
                    help="site updates per launch: fused sweep (one psum "
                         "per sweep on the dist backend)")
    ap.add_argument("--adaptive", action="store_true",
                    help="AdaptiveScan schedule (any backend incl. dist, "
                         "where the table reduction rides the sweep psum): "
                         "telemetry-driven non-uniform site selection")
    ap.add_argument("--telemetry", action="store_true",
                    help="thread streaming convergence telemetry and log "
                         "acceptance / split-R-hat / ESS per second")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--supervise", action="store_true",
                    help="run under the supervised runtime: verified-"
                         "checkpoint restarts, in-graph health guards "
                         "with rollback + lambda-retune / degrade-to-gibbs, "
                         "elastic restart (runtime/supervisor.py)")
    ap.add_argument("--fault-plan", default="",
                    help="deterministic FaultPlan as inline JSON or a file "
                         "path (requires --supervise); see "
                         "runtime/faultinject.py")
    ap.add_argument("--supervise-chunk", type=int, default=16,
                    help="sweep calls per supervised outer step (health "
                         "check + checkpoint cadence)")
    ap.add_argument("--max-restarts", type=int, default=5)
    ap.add_argument("--metrics-dir", default="",
                    help="write metrics.jsonl / metrics.prom / "
                         "events.jsonl here")
    ap.add_argument("--trace", default="",
                    help="write a Chrome/Perfetto trace-event JSON here")
    ap.add_argument("--profile", default="",
                    help="capture a jax.profiler trace into this dir")
    args = ap.parse_args()
    # reject impossible combinations with a usage message, not a traceback
    supported = engine_lib.backends(args.engine)
    if args.backend != "auto" and args.backend not in supported:
        ap.error(f"engine {args.engine!r} supports backends {supported}, "
                 f"not {args.backend!r} (jnp-only engines need "
                 f"--backend jnp)")
    if args.adaptive and args.engine not in ("gibbs", "mgpmh", "min-gibbs",
                                             "doublemin"):
        ap.error(f"--adaptive supports the gibbs/mgpmh/min-gibbs/doublemin "
                 f"engines, not {args.engine!r}")
    if args.fault_plan and not args.supervise:
        ap.error("--fault-plan requires --supervise")
    use_compile_cache()
    rec = obs.configure(metrics_dir=args.metrics_dir or None,
                        trace_path=args.trace or None,
                        profile_dir=args.profile or None,
                        process_name="repro.gibbs")
    with rec.profile():
        if args.supervise:
            run_supervised(args.config, args.engine, args.steps,
                           args.chains, ckpt_dir=args.ckpt_dir,
                           mp_shards=args.mp_shards, sweep=args.sweep,
                           backend=args.backend, adaptive=args.adaptive,
                           fault_plan=args.fault_plan,
                           chunk=args.supervise_chunk,
                           max_restarts=args.max_restarts)
        else:
            run(args.config, args.engine, args.steps, args.chains,
                ckpt_dir=args.ckpt_dir, mp_shards=args.mp_shards,
                sweep=args.sweep, backend=args.backend,
                adaptive=args.adaptive, telemetry=args.telemetry)
    rec.close()


if __name__ == "__main__":
    main()
