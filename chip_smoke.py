"""Bring-up check: the sampler and the chain pool on a TPU.

  python chip_smoke.py              # one chip: every phase below
  python chip_smoke.py --four-chip  # four chips: the sharded dist path only

One process; nothing here starts a child.  Every phase prints one line and
raises on any failure; the last line of a passing run is the JSON verdict
``{"ok": true, "device": {...}}``.  Without a TPU the script exits non-zero
before any work and prints no verdict.

One-chip phases:
  agree    each fused Pallas kernel vs its jnp oracle on potts-20x20, on the
           same host-drawn uniforms (the oracle run on the host CPU; the
           oracle on the TPU is reported beside it);
  offline  ``repro.launch.gibbs.run`` on potts-20x20 (beta=4.6, D=10) with
           --backend pallas for gibbs, mgpmh, doublemin and min-gibbs;
  dist     the launcher's default dist backend on a 1x1 mesh (potts-64x64),
           and chromatic dist gibbs on lattice-ising-64x64 against its
           single-host dense reference and against the same engine on the
           host CPU, bit for bit;
  serve    ``repro.launch.serve.serve_batch`` on potts-20x20, gibbs engine
           on pallas, 8 demo queries (half evidence-clamped).
Four-chip phases (``--four-chip``): the four dist engines on a (1, 4) mesh
on potts-64x64, marginals matched against the same engine on one device,
with the graph tables checked to sit one shard per device; chromatic dist
gibbs on lattice-ising-64x64, bit-exact against one device and the host.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax

SEED, SWEEP = 0, 16
CHAINS, STEPS = 256, 200              # offline sampling on potts-20x20
AGREE_CHAINS = 64                     # kernel-vs-oracle inputs
DIST_CHAINS, DIST_STEPS = 64, 200     # the dist launcher on potts-64x64
# each dist engine on 1x1 and 1x4.  Sized from one v5e host of four
# (100 calls of 64 chains, compile included, 1x1 / 1x4): gibbs 9.5 / 7.7 s,
# mgpmh 25.0 / 7.9 s, min-gibbs at lam 4096 411.9 / 121.1 s; min-gibbs's
# time grows with lam, so at 512 its 30 calls take ~15 s on one device
FOUR_CHAINS, FOUR_CALLS, FOUR_LAM = 64, 30, 512.0
# Draw rates of the two global-minibatch samplers.  The paper recipe
# (2 Psi^2 ~ 1.8e6 on potts-20x20, capped at 16384 by the engine) is far
# past what a chain block holds in VMEM; kernels.ops.sweep_vmem_bytes admits
# up to lam 361 (min-gibbs, 512 lanes) and lam2 252 (doublemin, 384 lanes).
# These rates (256 lanes each) compile for v5e in 38 s and 54 s of host
# time against 156 s and 99 s at those limits, and the limits buy no
# stronger check: on the CPU, doublemin accepts 0.28% of proposals at both
# rates and min-gibbs falls less from START_ERR at 361 than at 147.
MIN_GIBBS_LAM = 147.0
DOUBLEMIN_LAM2 = 147.0
# marg_err (mean over sites of the L2 distance of the marginal to uniform)
# starts at START_ERR with every chain at x = 0 and falls as the chains mix.
# CPU_REF: (marg_err, acceptance) of the same launcher call on the CPU (jnp
# backend; dist: the dist backend on a 1x1 CPU mesh), same seed, chains and
# steps (dist: DIST_CHAINS chains, DIST_STEPS steps on potts-64x64).  A chip
# run passes when its marg_err lies within TOL of the CPU run's fall from
# the CPU value, and its acceptance within TOL of the CPU acceptance: a
# sampler that mixes too slowly or too fast fails, not only a stuck one.
START_ERR = 0.9486833
CPU_REF = {"gibbs": (0.7461512, 1.0), "mgpmh": (0.7705225, 0.9580505),
           "doublemin": (0.9464973, 0.0027637),
           "min-gibbs": (0.9311761, 1.0), "dist": (0.9296890, 0.9804639)}
TOL = 0.25


def say(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def check_against_cpu(name: str, out: dict) -> None:
    """The launcher's ``out`` agrees with the CPU run of ``CPU_REF``."""
    import math
    err, acc = out["marg_err"], out["acceptance"]
    ref_err, ref_acc = CPU_REF[name]
    check(math.isfinite(err)
          and abs(err - ref_err) < TOL * (START_ERR - ref_err),
          f"{name}: marg_err {err} too far from the CPU's {ref_err}")
    check(abs(acc - ref_acc) <= TOL * ref_acc,
          f"{name}: acceptance {acc} too far from the CPU's {ref_acc}")


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def phase_agree(g) -> None:
    """Kernel vs oracle on identical host-drawn uniforms."""
    import numpy as np
    from repro.core.factor_graph import build_alias_table
    from repro.kernels import ops

    cpu = jax.devices("cpu")[0]
    tpu = jax.devices()[0]
    rng = np.random.default_rng(SEED)
    C, S, n, D = AGREE_CHAINS, SWEEP, g.n, g.D
    f32 = np.float32
    node_prob, node_alias = build_alias_table(np.asarray(g.row_sum))
    tabs = dict(W=np.asarray(g.W), rp=np.asarray(g.row_prob),
                ra=np.asarray(g.row_alias), np_=node_prob.astype(f32),
                na=node_alias.astype(np.int32))
    x = rng.integers(0, D, (C, n)).astype(np.int32)
    i_sites = rng.integers(0, n, (C, S)).astype(np.int32)
    gum = rng.gumbel(size=(C, S, D)).astype(f32)
    logu = np.log(rng.uniform(size=(C, S))).astype(f32)
    cache = rng.uniform(0, 3, (C,)).astype(f32)
    U = lambda *shape: rng.uniform(size=shape).astype(f32)
    K, K2 = 201, 128
    cases = {
        "gibbs": (ops.gibbs_sweep, (x, tabs["W"], i_sites, gum),
                  dict(D=D)),
        "mgpmh": (ops.mgpmh_sweep,
                  (x, tabs["W"], tabs["rp"], tabs["ra"], i_sites,
                   rng.integers(0, K + 1, (C, S)).astype(np.int32),
                   U(C, S, K), U(C, S, K), gum, logu),
                  dict(D=D, scale=0.05)),
        "min-gibbs": (ops.min_gibbs_sweep,
                      (x, tabs["np_"], tabs["na"], tabs["rp"], tabs["ra"],
                       i_sites,
                       rng.integers(0, K2 + 1, (C, S, D)).astype(np.int32),
                       U(C, S, D, K2), U(C, S, D, K2), U(C, S, D, K2),
                       U(C, S, D, K2), gum, cache),
                      dict(D=D, lscale=0.02)),
        "doublemin": (ops.double_min_sweep,
                      (x, tabs["rp"], tabs["ra"], tabs["np_"], tabs["na"],
                       i_sites,
                       rng.integers(0, K + 1, (C, S)).astype(np.int32),
                       U(C, S, K), U(C, S, K), gum,
                       rng.integers(0, K2 + 1, (C, S)).astype(np.int32),
                       U(C, S, K2), U(C, S, K2), U(C, S, K2), U(C, S, K2),
                       logu, cache),
                      dict(D=D, scale1=0.05, lscale2=0.02)),
    }
    for name, (fn, args, kw) in cases.items():
        ref = fn(*jax.device_put(args, cpu), impl="jnp", **kw)
        got = fn(*jax.device_put(args, tpu), impl="pallas", **kw)
        xla = fn(*jax.device_put(args, tpu), impl="jnp", **kw)
        as_np = lambda out: [np.asarray(a) for a in
                             (out if isinstance(out, tuple) else (out,))]
        ref, got, xla = as_np(ref), as_np(got), as_np(xla)
        rate = lambda o: float(np.mean(np.all(o[0] == ref[0], axis=1)))
        extras = all(np.array_equal(a, b) for a, b in zip(got[1:], ref[1:]))
        say("agree", kernel=name, pallas_chains_equal=rate(got),
            pallas_sites_equal=float(np.mean(got[0] == ref[0])),
            pallas_aux_equal=extras, xla_chains_equal=rate(xla),
            xla_sites_equal=float(np.mean(xla[0] == ref[0])))
        check(rate(got) == 1.0 and extras,
              f"{name}: pallas kernel disagrees with the oracle")
        check(rate(xla) == 1.0,
              f"{name}: the jnp oracle on the TPU disagrees with the CPU")


def phase_offline() -> None:
    import numpy as np
    from repro.launch.gibbs import run

    params = {"min-gibbs": dict(lam=MIN_GIBBS_LAM),
              "doublemin": dict(lam2=DOUBLEMIN_LAM2)}
    for name in ("gibbs", "mgpmh", "doublemin", "min-gibbs"):
        t0 = time.time()
        out = run("potts-20x20", name, steps=STEPS, chains=CHAINS,
                  log_every=STEPS, seed=SEED, sweep=SWEEP, backend="pallas",
                  params=params.get(name))
        desc = out["engine"]
        say("offline", engine=name, backend=desc["backend"],
            interpret=desc["interpret"], lam=params.get(name, {}),
            marg_err=out["marg_err"], cpu_marg_err=CPU_REF[name][0],
            acc=out["acceptance"], cpu_acc=CPU_REF[name][1],
            updates_per_sec=out["updates_per_sec"],
            seconds=time.time() - t0)
        check(desc["backend"] == "pallas" and desc["interpret"] is False,
              f"{name}: not the compiled pallas kernel: {desc}")
        check(bool(np.all(np.asarray(out["state"].x) >= 0)), "state")
        check_against_cpu(name, out)
        if name == "mgpmh":
            check(out["acceptance"] > 0.9,
                  f"mgpmh acceptance {out['acceptance']} <= 0.9")


def _chromatic_bitexact(mesh, C: int = 4, sweeps: int = 2) -> None:
    """Chromatic dist gibbs on lattice-ising-64x64 vs the single-host
    dense chromatic step under the same key protocol, and vs the same
    engine on the host CPU, bit for bit: the energy contractions (real
    matmuls here) must be exact on the chip."""
    import numpy as np
    import jax.numpy as jnp
    from repro.core import engine
    from repro.launch.mesh import make_device_mesh
    from repro.runtime.dist_gibbs import make_chromatic_gibbs_step

    t0 = time.time()
    wl = engine.make_workload("lattice-ising-64x64")
    chromatic = engine.ChromaticBlocks(wl.colors)
    eng = engine.make("gibbs", wl.graph, backend="dist", mesh=mesh,
                      schedule=chromatic)
    host = engine.make("gibbs", wl.graph, backend="dist",
                       schedule=chromatic,
                       mesh=make_device_mesh((1, 1), ("data", "model"),
                                             jax.devices("cpu")[:1]))
    key0 = jax.random.PRNGKey(3)
    st, st_host = eng.init(key0, C), host.init(key0, C)
    dense = jax.jit(make_chromatic_gibbs_step(wl.graph, wl.colors),
                    static_argnums=2)
    x_ref = jnp.zeros((C, wl.graph.n), jnp.int32)
    k = jax.random.split(key0, 1)[0]           # the single dp-shard key
    for _ in range(sweeps):
        k, master = jax.random.split(k)
        keys = jax.random.split(master, 2)
        for c in range(2):
            x_ref = dense(x_ref, keys[c], c)
        st, st_host = eng.sweep(st), host.sweep(st_host)
    x, x_host = np.asarray(st.x), np.asarray(st_host.x)
    say("dist", what="chromatic-lattice-ising-64x64",
        model_shards=mesh.shape["model"], sweeps=sweeps,
        bitexact_vs_dense=bool(np.array_equal(x, np.asarray(x_ref))),
        chains_equal_vs_host=float(np.mean(np.all(x == x_host, axis=1))),
        mean_spin=float(x.mean()), seconds=time.time() - t0)
    check(np.array_equal(x, np.asarray(x_ref)),
          "chromatic dist gibbs differs from the dense reference")
    check(np.array_equal(x, x_host),
          "chromatic dist gibbs on the chip differs from the host CPU")


def phase_dist() -> None:
    import numpy as np
    from repro.launch.gibbs import run
    from repro.launch.mesh import make_device_mesh

    t0 = time.time()
    out = run("potts-64x64", "mgpmh", steps=DIST_STEPS, chains=DIST_CHAINS,
              log_every=DIST_STEPS, seed=SEED, sweep=SWEEP, backend="dist",
              mp_shards=1)
    say("dist", what="launcher-mgpmh-potts-64x64", mesh="1x1",
        backend=out["engine"]["backend"], marg_err=out["marg_err"],
        cpu_marg_err=CPU_REF["dist"][0], acc=out["acceptance"],
        cpu_acc=CPU_REF["dist"][1], updates_per_sec=out["updates_per_sec"],
        seconds=time.time() - t0)
    check(out["engine"]["backend"] == "dist", "not the dist backend")
    check_against_cpu("dist", out)
    check(out["acceptance"] > 0.9, f"dist acceptance {out['acceptance']}")
    _chromatic_bitexact(make_device_mesh((1, 1), ("data", "model"),
                                         jax.devices()[:1]))


def phase_serve() -> None:
    from repro.core import engine
    from repro.launch.serve import _demo_queries, serve_batch

    g = engine.make_workload("potts-20x20").graph
    queries = _demo_queries("potts-20x20", g, 8, SEED)
    t0 = time.time()
    res = serve_batch("potts-20x20", queries, engine="gibbs",
                      backend="pallas", chains=32, sweep=SWEEP, chunk=16,
                      seed=SEED)
    statuses = [a["status"] for a in res["answers"]]
    say("serve", engine=res["engine"]["engine"],
        backend=res["engine"]["backend"],
        interpret=res["engine"]["interpret"], n_queries=res["n_queries"],
        clamped=sum(1 for q in queries if q.evidence),
        statuses=res["status_counts"], sources=res["source_counts"],
        fresh_fraction=res["fresh_fraction"],
        compiled_traces=res["compiled_traces"], seconds=time.time() - t0)
    check(res["engine"]["interpret"] is False, "serve kernel interpreted")
    check(all(s == "ok" for s in statuses), f"answers {statuses}")
    check(res["compiled_traces"] == 1,
          f"compiled_traces {res['compiled_traces']}")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def phase_four_chip() -> None:
    import numpy as np
    from repro.core import engine
    from repro.launch.mesh import make_device_mesh

    devs = jax.devices()
    check(len(devs) == 4, f"--four-chip needs 4 devices, found {len(devs)}")
    g = engine.make_workload("potts-64x64").graph
    mesh4 = make_device_mesh((1, 4), ("data", "model"), devs)
    mesh1 = make_device_mesh((1, 1), ("data", "model"), devs[:1])
    C, calls = FOUR_CHAINS, FOUR_CALLS
    # the global-minibatch rates are cut from the default 16384: on one
    # device min-gibbs's draw buffers would take ~19 GB at these chains
    params = {"min-gibbs": dict(lam=FOUR_LAM),
              "doublemin": dict(lam2=FOUR_LAM)}
    for name in ("gibbs", "mgpmh", "min-gibbs", "doublemin"):
        marg, t_engine = {}, time.time()
        for mesh in (mesh1, mesh4):
            eng = engine.make(name, g, sweep=SWEEP, backend="dist",
                              mesh=mesh, **params.get(name, {}))
            if mesh is mesh4:
                W = eng.device_tables["W_cols"]
                held = sorted(d.id for s in W.addressable_shards
                              for d in [s.device])
                check(held == sorted(d.id for d in devs)
                      and all(s.data.shape[0] == 1
                              for s in W.addressable_shards),
                      f"W_cols not one shard per device: {held}")
            st = eng.init(jax.random.PRNGKey(SEED), C)
            t0 = time.time()
            for _ in range(calls):
                st = eng.sweep(st)
            m = np.asarray(st.marg).sum(0) / (float(st.count) * C)
            marg[mesh.shape["model"]] = (m, time.time() - t0)
        (m1, t1), (m4, t4) = marg[1], marg[4]
        err = [float(np.sqrt(((m - 1 / g.D) ** 2).sum(-1)).mean())
               for m in (m1, m4)]
        # the value frequencies averaged over sites: two runs of one chain
        # with other bits differ here only by Monte Carlo noise
        site_mean = float(np.abs(m4.mean(0) - m1.mean(0)).max())
        say("four-chip", engine=name, mesh="1x4", vs="1x1",
            bitexact=bool(np.array_equal(m4, m1)),
            mean_abs_marg_diff=float(np.abs(m4 - m1).mean()),
            max_site_mean_diff=site_mean, marg_err_1x1=err[0],
            marg_err_1x4=err[1], seconds_1x1=t1, seconds_1x4=t4,
            seconds_total=time.time() - t_engine)
        check(bool(np.isfinite(m4).all()) and site_mean < 0.02
              and abs(err[1] - err[0]) < 0.05,
              f"{name}: 1x4 marginals differ from 1x1")
    _chromatic_bitexact(mesh4)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the sharded dist path on four chips")
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX backend is "
                 f"{jax.default_backend()!r})")
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.core import engine
    from repro.launch.compile_cache import use_compile_cache

    dev = jax.devices()[0]
    say("device", platform=dev.platform, kind=dev.device_kind,
        count=len(jax.devices()), compile_cache=use_compile_cache())
    if args.four_chip:
        phase_four_chip()
    else:
        phase_agree(engine.make_workload("potts-20x20").graph)
        phase_offline()
        phase_dist()
        phase_serve()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
