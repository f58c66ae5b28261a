"""Read the numbers ``correct`` compares, for the program and for its
control, on many seeds in one process: the readings a cell's limits are
set from (PERF.md gives them).  Not part of a benchmark run.

  python -m bench.readings --workload <cell> --seeds 1,2,3 --seconds 30 \\
      [--control high --control-seeds 1,2,3]

For each seed it runs the cell's window and check as ``bench.run`` does
and prints the program's reading.  For each control seed it then puts the
oracle computed at the control's precision in the program's place, runs
as many calls from that seed's start through the same window and check,
and prints the control's reading.  One JSON line per run.  Offline cells
only.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _reading(s, cfg, seed, seconds, max_calls, limit):
    st = s.start(seed)
    got = s.window(st, seconds, False, seed, max_calls)
    del st
    t = time.perf_counter()
    res = s.compare(cfg, got)
    share = res["mismatched"] / max(res["chain_calls"], 1)
    return {"seed": seed, "calls": got["calls"],
            "updates_per_s": s.chains * int(s.work["sweep"]) * got["calls"]
            / (got["t1"] - got["t0"]),
            "mismatched": res["mismatched"],
            "chain_calls": res["chain_calls"], "share": share,
            "bad_calls": res["bad_calls"], "correct": share <= limit,
            "check_s": time.perf_counter() - t}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m bench.readings")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", default="high")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)

    from bench import run as bench_run
    from bench import spec as spec_mod
    top = spec_mod.root()
    sys.path.insert(0, os.path.join(top, "src"))
    spec = spec_mod.load(top)
    cell = spec_mod.cell(spec, args.workload, top)
    if bench_run._devices(int(cell["entry"]["chips"])) is None:
        return 2
    bench_run._compile_cache(top)
    work, cfg = cell["work"], cell["config"]
    if work["traffic"]["kind"] != "offline":
        print("bench.readings reads offline cells only", file=sys.stderr)
        return 2
    Sampler = spec_mod.plugin("traffic", "offline").Sampler
    limit = float(work["check"]["mismatch_share"])
    calls = {}
    s = Sampler(work, cfg)
    for seed in [int(x) for x in args.seeds.split(",")]:
        out = _reading(s, cfg, seed, args.seconds, None, limit)
        calls[seed] = out["calls"]
        print(json.dumps(dict(out, workload=args.workload, side="program")),
              flush=True)
    del s
    ctrl = [int(x) for x in args.control_seeds.split(",") if x]
    if ctrl:
        s = Sampler(work, cfg, control=args.control)
        for seed in ctrl:
            out = _reading(s, cfg, seed, args.seconds, calls.get(seed),
                           limit)
            print(json.dumps(dict(out, workload=args.workload,
                                  side=args.control)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
