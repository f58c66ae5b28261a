"""Benchmark harness entry point — one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV.  ``--paper-scale`` switches the
Gibbs benchmarks to the paper's exact 20x20 / 10^6-iteration setting.
``--json PATH`` additionally writes every row as a BENCH_kernel.json-style
record (name, us_per_call, derived, engine identity fields
engine/backend/schedule/updates_per_call, plus metric fields like
sites_per_sec and — on telemetry'd rows — mean_acceptance / ess_per_sec /
max_split_rhat) wrapped as ``{"schema_version": N, "records": [...]}`` so
the perf trajectory is machine-readable and attributable across PRs.
``--smoke`` runs the diagnostics module plus the newly-swept kernel rows
and the serving smoke at CI scale (CPU minutes): the convergence-telemetry
+ peak-bytes + queries/sec records CI uploads as artifacts."""
import argparse
import inspect
import json


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--paper-scale", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma list: table1,fig1,fig2,kernel,roofline,"
                         "sweep,diag,dist,serve")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write all rows as JSON records to PATH")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: diagnostics + newly-swept kernel rows, "
                         "tiny scales")
    ap.add_argument("--metrics-dir", default="",
                    help="export bench metrics (metrics.jsonl/.prom) here")
    ap.add_argument("--trace", default="",
                    help="write a Chrome/Perfetto trace-event JSON here")
    args = ap.parse_args()
    import types

    from repro import obs
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    rec = obs.configure(metrics_dir=args.metrics_dir or None,
                        trace_path=args.trace or None,
                        process_name="repro.bench")

    from . import (table1_cost, fig1_min_gibbs, fig2_variants, kernel_bench,
                   roofline, sweep_bench, diagnostics_bench, serve_bench,
                   common)
    mods = {"table1": table1_cost, "fig1": fig1_min_gibbs,
            "fig2": fig2_variants, "kernel": kernel_bench,
            "roofline": roofline, "sweep": sweep_bench,
            "diag": diagnostics_bench,
            # dist-backend rows (one-psum sweep template; BENCH_dist.json
            # comes from ``--json BENCH_dist.json --only dist``)
            "dist": types.SimpleNamespace(run=sweep_bench.run_dist),
            # serving-layer rows (queries/sec + staleness percentiles;
            # BENCH_serve.json comes from ``--json ... --only serve``)
            "serve": serve_bench}
    if args.smoke:
        only = ["diag", "sweep", "dist", "serve", "roofline"]
    else:
        only = args.only.split(",") if args.only else list(mods)
    print("name,us_per_call,derived")
    try:
        for key in only:
            fn = mods[key].run
            kwargs = dict(paper_scale=args.paper_scale)
            if "smoke" in inspect.signature(fn).parameters:
                kwargs["smoke"] = args.smoke
            fn(**kwargs)
    finally:
        # dump whatever was collected even if a later module failed
        if args.json:
            with open(args.json, "w") as f:
                json.dump({"schema_version": common.SCHEMA_VERSION,
                           "records": common.RECORDS}, f, indent=1)
            print(f"# wrote {len(common.RECORDS)} records to {args.json}",
                  flush=True)
        rec.close()


if __name__ == '__main__':
    main()
