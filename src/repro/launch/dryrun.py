import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import: jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell on
the production mesh with 512 placeholder host devices, record
memory_analysis / cost_analysis / per-collective wire bytes to JSON.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --all                # 16x16
  PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod    # 2x16x16
  PYTHONPATH=src python -m repro.launch.dryrun --arch mixtral-8x7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --gibbs              # paper cells

Results land in results/dryrun/<mesh>/<arch>__<shape>.json and are skipped
when present (resumable); EXPERIMENTS.md §Dry-run / §Roofline read them.
"""
import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import SHAPES
from ..configs.registry import ARCHS, GIBBS_CONFIGS
from ..models import transformer as T
from . import steps as steps_lib
from .mesh import make_production_mesh, dp_axes, MP_AXIS
from .shardings import (param_pspecs, batch_pspecs, cache_pspecs, tree_named,
                        named)
from jax.sharding import PartitionSpec as P

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1,
                "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
                "c64": 8, "c128": 16}

_COLL_RE = re.compile(
    r"=\s*(?P<shapes>\(?[a-z0-9\[\],{}\s/]+?\)?)\s*"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start|-done)?\(")
_SHAPE_RE = re.compile(r"([a-z]\d*[a-z0-9]*)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_RE2 = re.compile(r"replica_groups=\{\{([\d,]+)\}")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_stats(hlo: str) -> dict:
    """Per-device wire-byte estimate per collective family.

    Convention (documented in EXPERIMENTS.md): for result bytes R and group
    size g —  all-reduce: 2*R*(g-1)/g (RS+AG phases);  all-gather /
    all-to-all: R*(g-1)/g;  reduce-scatter: R*(g-1) (R is the scattered
    output);  collective-permute: R.
    """
    out = {"bytes_by_op": {}, "count_by_op": {}, "wire_bytes": 0.0}
    for line in hlo.splitlines():
        m = _COLL_RE.search(line)
        if not m or "-done(" in line:
            continue
        op = m.group("op")
        R = _shape_bytes(m.group("shapes"))
        g = None
        mg = _GROUPS_RE.search(line)
        if mg:
            g = int(mg.group(2))
        else:
            mg2 = _GROUPS_RE2.search(line)
            if mg2:
                g = len(mg2.group(1).split(","))
        g = g or 1
        if g <= 1:
            wire = 0.0
        elif op == "all-reduce":
            wire = 2.0 * R * (g - 1) / g
        elif op in ("all-gather", "all-to-all"):
            wire = R * (g - 1) / g
        elif op == "reduce-scatter":
            wire = R * (g - 1)
        else:                               # collective-permute
            wire = float(R)
        out["bytes_by_op"][op] = out["bytes_by_op"].get(op, 0.0) + wire
        out["count_by_op"][op] = out["count_by_op"].get(op, 0) + 1
        out["wire_bytes"] += wire
    return out


# TPU v5e per-chip constants (roofline denominators)
PEAK_FLOPS = 197e12      # bf16
HBM_BW = 819e9           # bytes/s
ICI_BW = 50e9            # bytes/s/link


def roofline_terms(cost: dict, coll: dict) -> dict:
    flops = float(cost.get("flops", 0.0))            # per device
    bytes_acc = float(cost.get("bytes accessed", 0.0))
    t_compute = flops / PEAK_FLOPS
    t_memory = bytes_acc / HBM_BW
    t_coll = coll["wire_bytes"] / ICI_BW
    terms = {"t_compute_s": t_compute, "t_memory_s": t_memory,
             "t_collective_s": t_coll}
    dom = max(terms, key=terms.get)
    terms["bottleneck"] = dom.replace("t_", "").replace("_s", "")
    return terms


def _cost_dict(compiled) -> dict:
    """``compiled.cost_analysis()`` normalized to a dict (older jax returns
    ``[dict]``, newer returns the dict directly, either may be empty)."""
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, list):
        cost = cost[0] if cost else {}
    return cost


def _depth_variant(cfg, g: int):
    """A g-group-deep copy of cfg (uniform stacks => costs affine in g)."""
    import dataclasses as _dc
    return _dc.replace(
        cfg, num_layers=cfg.first_dense_layers + g * cfg.period,
        encoder_layers=(g if cfg.encoder_layers else 0))


def analysis_costs(cfg, shape, mesh) -> dict:
    """Loop-corrected per-device costs.

    XLA's cost_analysis counts while-loop bodies ONCE (verified: a length-10
    scan reports the same flops as a single body).  We therefore lower fully
    UNROLLED depth variants with g=1 and g=2 layer groups — cheap compiles —
    and use exact affine extrapolation cost(g) = A + g*B to the full depth:
    A = 2*c1 - c2 (depth-independent part: embed, loss, optimizer),
    B = c2 - c1 (one group).  Collect flops / bytes / per-op wire bytes.
    """
    c = {}
    for g in (1, 2):
        vcfg = _depth_variant(cfg, g)
        comp = _lower_cell(vcfg, shape, mesh, unroll=True).compile()
        cost = _cost_dict(comp)
        coll = collective_stats(comp.as_text())
        c[g] = {"flops": float(cost.get("flops", 0.0)),
                "bytes": float(cost.get("bytes accessed", 0.0)),
                "wire_bytes": coll["wire_bytes"],
                **{f"wire_{k}": v for k, v in coll["bytes_by_op"].items()}}
    G = cfg.num_groups
    keys = set(c[1]) | set(c[2])
    out = {}
    for k in keys:
        c1, c2 = c[1].get(k, 0.0), c[2].get(k, 0.0)
        out[k] = max((2 * c1 - c2) + G * (c2 - c1), 0.0)
    out["per_group"] = {k: c[2].get(k, 0.0) - c[1].get(k, 0.0) for k in keys}
    return out


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             out_dir: str, force: bool = False,
             variant: str = "", analysis: bool = True,
             cfg_override: dict | None = None) -> dict:
    mesh_tag = "pod2x16x16" if multi_pod else "pod16x16"
    os.makedirs(os.path.join(out_dir, mesh_tag), exist_ok=True)
    suffix = f"__{variant}" if variant else ""
    path = os.path.join(out_dir, mesh_tag, f"{arch}__{shape_name}{suffix}.json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    cfg = ARCHS[arch]
    if cfg_override:
        import dataclasses as _dc
        cfg = _dc.replace(cfg, **cfg_override)
    shape = SHAPES[shape_name]
    t0 = time.time()
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
           "kind": shape.kind, "variant": variant}
    try:
        if shape_name in cfg.skip_shapes:
            rec["status"] = "skipped"
            rec["reason"] = ("pure full-attention arch; sub-quadratic "
                            "required for long_500k (DESIGN.md)")
            _write(path, rec)
            return rec
        mesh = make_production_mesh(multi_pod=multi_pod)
        with mesh:
            lowered = _lower_cell(cfg, shape, mesh)
            rec["lower_s"] = time.time() - t0
            t1 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = time.time() - t1
            mem = compiled.memory_analysis()
            rec["memory"] = {
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
            }
            cost = _cost_dict(compiled)
            rec["cost"] = {k: float(v) for k, v in cost.items()
                           if k in ("flops", "bytes accessed",
                                    "transcendentals")}
            coll = collective_stats(compiled.as_text())
            rec["collectives"] = coll
            rec["roofline_raw"] = roofline_terms(rec["cost"], coll)
            if analysis:
                ac = analysis_costs(cfg, shape, mesh)
                rec["analysis"] = ac
                rec["roofline"] = roofline_terms(
                    {"flops": ac["flops"], "bytes accessed": ac["bytes"]},
                    {"wire_bytes": ac["wire_bytes"]})
            else:
                rec["roofline"] = rec["roofline_raw"]
            # MODEL_FLOPS (useful-work reference)
            tokens = shape.global_batch * (1 if shape.kind == "decode"
                                           else shape.seq_len)
            mf = T.model_flops_per_token(
                cfg, shape.seq_len,
                "train" if shape.kind == "train" else "fwd") * tokens
            n_dev = 512 if multi_pod else 256
            rec["model_flops_per_device"] = mf / n_dev
            fl = (rec.get("analysis", rec["cost"]).get("flops")
                  or rec["cost"].get("flops", 0.0))
            rec["model_flops_ratio"] = (mf / n_dev) / fl if fl else None
            rec["status"] = "ok"
    except Exception as e:   # noqa: BLE001 — record the failure, keep going
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["total_s"] = time.time() - t0
    _write(path, rec)
    return rec


def _lower_cell(cfg, shape, mesh, unroll: bool = False):
    from ..models import meshctx
    meshctx.set_mesh(mesh, dp_axes(mesh), MP_AXIS)
    specs = steps_lib.input_specs(cfg, shape)
    bspecs = batch_pspecs(cfg, shape, mesh)
    batch_sh = {k: named(mesh, bspecs[k]) for k in specs}
    params = T.abstract_params(cfg)
    pspecs = param_pspecs(cfg, params)
    psh = tree_named(mesh, pspecs)
    if shape.kind == "train":
        params_a, opt_a = steps_lib.abstract_train_state(cfg)
        # moments mirror params; step is replicated
        osh = type(opt_a)(step=named(mesh, P()), m=psh, v=psh)
        fn = steps_lib.make_train_step(cfg, unroll=unroll)
        return jax.jit(fn, in_shardings=(psh, osh, batch_sh),
                       donate_argnums=(0, 1)).lower(params_a, opt_a, specs)
    if shape.kind == "prefill":
        fn = steps_lib.make_prefill_step(cfg, unroll=unroll)
        return jax.jit(fn, in_shardings=(psh, batch_sh)).lower(params, specs)
    # decode
    cache_a = T.init_cache(cfg, shape.global_batch, shape.seq_len,
                           abstract=True)
    cspecs = cache_pspecs(cfg, shape, mesh, cache_a)
    csh = tree_named(mesh, cspecs)
    fn = steps_lib.make_serve_step(cfg, unroll=unroll)
    tok_sh = {k: batch_sh[k] for k in specs}
    return jax.jit(fn, in_shardings=(psh, tok_sh["tokens"], csh),
                   donate_argnums=(2,)).lower(
        params, specs["tokens"], cache_a)


def _write(path, rec):
    with open(path + ".tmp", "w") as f:
        json.dump(rec, f, indent=1)
    os.replace(path + ".tmp", path)


# ---------------------------------------------------------------------------
# Gibbs-engine dry-run cells (the paper's workload on the production mesh)
# ---------------------------------------------------------------------------

def run_gibbs_cell(name: str, *, multi_pod: bool, out_dir: str,
                   force: bool = False, engine: str = "mgpmh",
                   n: int = 16384, chains: int = 4096, D: int = 10,
                   lam: float = 26.0, capacity: int = 8,
                   lam2: float = 4096.0, capacity2: int = 512,
                   table_dtype=None, variant: str = "") -> dict:
    """Lower + compile one distributed Gibbs-engine step (the paper's
    workload) for a dense weighted-match graph of n variables.

    engine: "mgpmh" (Alg 4: minibatch proposal + exact O(Delta) pass) or
    "doublemin" (Alg 5: second minibatch replaces the exact pass — the
    paper's own optimization, visible as a structural drop of the memory
    roofline term).
    """
    from ..runtime import dist_gibbs as DG

    mesh_tag = "pod2x16x16" if multi_pod else "pod16x16"
    os.makedirs(os.path.join(out_dir, mesh_tag), exist_ok=True)
    suffix = "" if engine == "mgpmh" else f"__{engine}"
    if variant:
        suffix += f"__{variant}"
    path = os.path.join(out_dir, mesh_tag, f"gibbs-{name}{suffix}.json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    t0 = time.time()
    rec = {"arch": f"gibbs-{name}{suffix}", "shape": f"n{n}_c{chains}_D{D}",
           "mesh": mesh_tag, "kind": "gibbs", "engine": engine}
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        mp = mesh.shape[MP_AXIS]
        dp = dp_axes(mesh)
        n_loc = n // mp
        F_max = (n * (n - 1) // 2) // mp + n
        sds = jax.ShapeDtypeStruct
        tdt = table_dtype or jnp.float32
        gs = DG.ShardedMatchGraph(
            W_cols=sds((mp, n, n_loc), tdt),
            row_prob=sds((mp, n, n_loc), tdt),
            row_alias=sds((mp, n, n_loc), jnp.int32),
            row_sum=sds((mp, n), jnp.float32),
            pair_a=sds((mp, F_max), jnp.int32),
            pair_b=sds((mp, F_max), jnp.int32),
            pair_prob=sds((mp, F_max), tdt),
            pair_alias=sds((mp, F_max), jnp.int32),
            psi_loc=sds((mp,), jnp.float32),
            D=D, psi=float(n), L=float(np.sqrt(n)), n=n, n_shards=mp)
        if engine == "doublemin":
            step = DG.make_dist_sweep(gs, "doublemin", 1, lam=lam,
                                      capacity=capacity, lam2=lam2,
                                      capacity2=capacity2)
        else:
            step = DG.make_dist_sweep(gs, "mgpmh", 1, lam=lam,
                                      capacity=capacity)

        shard_specs = {"W_cols": P(MP_AXIS, None, None),
                       "row_prob": P(MP_AXIS, None, None),
                       "row_alias": P(MP_AXIS, None, None),
                       "row_sum": P(MP_AXIS, None),
                       "pair_a": P(MP_AXIS, None),
                       "pair_b": P(MP_AXIS, None),
                       "pair_prob": P(MP_AXIS, None),
                       "pair_alias": P(MP_AXIS, None),
                       "psi_loc": P(MP_AXIS)}
        state_specs = DG.DistState(
            x=P(dp, None), cache=P(dp), key=P(dp),
            accepts=P(dp), marg=P(dp, MP_AXIS, None), count=P())

        smapped = jax.shard_map(
            lambda st, sh: step(st, sh), mesh=mesh,
            in_specs=(state_specs, shard_specs),
            out_specs=state_specs,
            check_vma=False)

        dp_total = 1
        for a in dp:
            dp_total *= mesh.shape[a]
        state_a = DG.DistState(
            x=sds((chains, n), jnp.int32),
            cache=sds((chains,), jnp.float32),
            key=sds((dp_total, 2), jnp.uint32),
            accepts=sds((chains,), jnp.int32),
            marg=sds((chains, n, D), jnp.float32),
            count=sds((), jnp.int32))
        sh_a = {k: getattr(gs, k) for k in shard_specs}
        in_sh = (tree_named(mesh, state_specs), tree_named(mesh, shard_specs))
        lowered = jax.jit(smapped, in_shardings=in_sh,
                          donate_argnums=(0,)).lower(state_a, sh_a)
        rec["lower_s"] = time.time() - t0
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = time.time() - t1
        mem = compiled.memory_analysis()
        rec["memory"] = {"argument_bytes": mem.argument_size_in_bytes,
                         "temp_bytes": mem.temp_size_in_bytes}
        cost = _cost_dict(compiled)
        rec["cost"] = {k: float(v) for k, v in cost.items()
                       if k in ("flops", "bytes accessed")}
        coll = collective_stats(compiled.as_text())
        rec["collectives"] = coll
        rec["roofline"] = roofline_terms(rec["cost"], coll)
        rec["status"] = "ok"
    except Exception as e:   # noqa: BLE001
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["total_s"] = time.time() - t0
    _write(path, rec)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--gibbs", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    cells = []
    if args.all:
        cells = [(a, s) for a in ARCHS for s in SHAPES]
    elif args.arch and args.shape:
        cells = [(args.arch, args.shape)]
    elif args.arch:
        cells = [(args.arch, s) for s in SHAPES]

    for arch, shape in cells:
        rec = run_cell(arch, shape, multi_pod=args.multi_pod,
                       out_dir=args.out, force=args.force)
        r = rec.get("roofline", {})
        print(f"[dryrun] {rec['mesh']} {arch:22s} {shape:12s} "
              f"{rec['status']:8s} "
              f"compile={rec.get('compile_s', 0):6.1f}s "
              f"bottleneck={r.get('bottleneck', '-'):10s} "
              f"{rec.get('error', '')}", flush=True)

    if args.gibbs:
        for name, size in [("potts-16k", 16384), ("potts-64k", 65536)]:
            for engine in ("mgpmh", "doublemin"):
                rec = run_gibbs_cell(name, n=size, engine=engine,
                                     multi_pod=args.multi_pod,
                                     out_dir=args.out, force=args.force)
                print(f"[dryrun] {rec['mesh']} gibbs-{name}-{engine:10s} "
                      f"{rec['status']:8s} {rec.get('error', '')}",
                      flush=True)


if __name__ == "__main__":
    main()
