"""Run one benchmark cell on the chip and print its result.

  python -m bench.run --workload <cell> --seed <n> --seconds <s> --trace 0|1

From the root of a checkout.  One process holds the chip and starts no
child.  The cell's files (``bench/workloads/<cell>.json``, its
configuration, its traffic kind in ``bench/traffic/``) say what runs; the
traffic kind builds the system, warms every shape the window uses, runs
the measured window and then the comparison that decides ``correct``.
With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window by ``bench/metrics/<metric>.py``.  The numbers compared are
printed beside their limits as the last lines on standard error and under
``checks``, the last key of the result, the last line on standard output.

Without a TPU, or with fewer chips than the cell asks for, the command
prints why on standard error and exits 2 with no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _process_age() -> float:
    """Seconds since this process started, from /proc (0 if unknown)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(uptime - start_ticks / os.sysconf("SC_CLK_TCK"), 0.0)
    except (OSError, ValueError, IndexError):
        return 0.0


T_START = time.perf_counter() - _process_age()


class Context:
    """What a traffic kind runs from."""

    def __init__(self, cell: dict, seed: int, seconds: float, trace: bool):
        self.entry, self.work = cell["entry"], cell["work"]
        self.config = cell["config"]
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.t_start = T_START


def _devices(chips: int):
    """The chips, or None with the reason on stderr."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        print(f"bench: no accelerator: {e}", file=sys.stderr)
        return None
    if devs[0].platform != "tpu":
        print(f"bench: no TPU (JAX found {devs[0].platform}); the benchmark "
              f"runs on the chip only", file=sys.stderr)
        return None
    if len(devs) < chips:
        print(f"bench: the cell needs {chips} chips, JAX found "
              f"{len(devs)}", file=sys.stderr)
        return None
    return devs[:chips]


def _compile_cache(top: str) -> None:
    """JAX's persistent cache at a fixed path in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), every program cached."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(top, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def result_line(cell: dict, res: dict, trace: bool, devs) -> dict:
    """The result object from what the traffic kind returned."""
    from bench.spec import plugin
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs),
              "memory_peak_bytes": int(res["memory_peak_bytes"])}
    metrics = {}
    if trace:
        red = res["trace"]
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        for m in cell["per_layer"]:
            value = plugin("metrics", m["name"]).read(res["record"], red)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell["end_to_end"]:
            # `<quantity>.<group>`: the traffic kind's quantity, held to a
            # bound of its own in the cells that list it
            value = res["metrics"][m["name"].split(".", 1)[0]]
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out = {"correct": bool(res["correct"]), "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = res["trace"]["breakdown"]
    out["checks"] = res["checks"]
    return out


def execute(cell: dict, seed: int, seconds: float, trace: bool,
            devs) -> dict:
    """Run ``cell`` on ``devs`` (the chips, already checked) and return its
    result object."""
    from bench.spec import plugin
    kind = plugin("traffic", cell["work"]["traffic"]["kind"])
    res = kind.run(Context(cell, seed, seconds, trace))
    return result_line(cell, res, trace, devs)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m bench.run",
                                 description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import spec as spec_mod
    top = spec_mod.root()
    sys.path.insert(0, os.path.join(top, "src"))
    spec = spec_mod.load(top)
    cell = spec_mod.cell(spec, args.workload, top)
    devs = _devices(int(cell["entry"]["chips"]))
    if devs is None:
        return 2
    _compile_cache(top)
    out = execute(cell, args.seed, args.seconds, bool(args.trace), devs)
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
