"""Offline traffic: back-to-back sweep calls on one batch of chains.

Parameters (the workload file's ``traffic``): ``in_flight``, the calls
dispatched ahead of the device (the host waits for the call that many
back before it dispatches the next).  The window runs from the first
dispatch after warm-up to the end of the last call.

The calls whose input and output states the oracle replays once the
window has closed (``bench/check.py``) are the workload's
``check.calls``: ``"all"``, or that many drawn from the seed by reservoir
sampling as the calls are dispatched.  A path that donates its input
(dist) gets a copy of a state only where the oracle needs it (a drawn
call's input, and its output, which is the next call's input) and, every
``in_flight`` calls, one that the host waits on, since a donated state
cannot be waited on.

End-to-end metrics: ``site_updates_per_s`` (chains x updates per call x
calls, over the window) and ``setup_s`` (process start to the first timed
dispatch).  ``attempted`` counts the window's calls, ``failed`` the calls
whose output breaks the health guard (a site value outside the domain).
"""
from __future__ import annotations

import collections
import sys
import time

from bench import check, system
from bench import trace as tracing


def _view(st) -> dict:
    return {"x": st.x, "key": st.key, "accepts": st.accepts}


def control_engine(eng, work: dict, cfg: dict, precision: str):
    """``eng`` with its sweep replaced by the oracle computed at
    ``precision``: the reference put in the program's place."""
    import dataclasses
    import jax
    call = check.oracle(work, cfg, dict(eng.params), precision)

    @jax.jit
    def sweep(st):
        return st._replace(**call(_view(st), None))
    return dataclasses.replace(eng, sweep_fn=sweep)


class Sampler:
    """The cell's engine with its chains, warmed: ``window`` runs the
    measured calls and keeps the states the oracle replays."""

    def __init__(self, work: dict, cfg: dict, control: str = None):
        import jax
        import jax.numpy as jnp
        self.work = work
        self.stages = {}
        t = time.perf_counter()
        g = system.graph(cfg)
        self.stages["graph_s"] = time.perf_counter() - t
        t = time.perf_counter()
        self.eng = system.engine(work, g)
        if control:
            self.eng = control_engine(self.eng, work, cfg, control)
        self.stages["engine_s"] = time.perf_counter() - t
        self.chains = int(work["chains"])
        self.donates = self.eng.backend == "dist"
        def keep_copy(d):
            return jax.tree.map(jnp.copy, d)
        self.keep = jax.jit(keep_copy)

    def start(self, seed: int):
        """Fresh chains from ``seed``, every program of the window
        compiled and run once."""
        import jax
        t = time.perf_counter()
        st = self.eng.init(system.key(seed), self.chains)
        for _ in range(2):
            if self.donates:
                jax.block_until_ready(self.keep(_view(st)))
            st = self.eng.sweep(st)
        st = jax.block_until_ready(st)
        self.stages["warm_s"] = time.perf_counter() - t
        return st

    def window(self, st, seconds: float, trace: bool, seed: int,
               max_calls: int = None) -> dict:
        """Dispatch calls for ``seconds`` (or, given ``max_calls``, that
        many calls) and keep the input and output of every call the check
        replays."""
        import jax
        depth = int(self.work["traffic"]["in_flight"])
        size = self.work["check"]["calls"]
        every = size == "all"
        pick = system.rng(seed, 3)
        slots, pending = [], None
        handles = collections.deque()
        # a donated state cannot be waited on: one copy every `depth`
        # calls is the handle, and the host waits for the one before it
        ahead = 1 if self.donates else depth
        tracer = tracing.Tracer() if trace else None
        if tracer:
            tracer.start()
        t0 = time.perf_counter()
        calls = 0
        with tracing.span("window"):
            while (time.perf_counter() - t0 < seconds) if max_calls is None \
                    else calls < max_calls:
                with tracing.span("dispatch"):
                    if every or calls < size:
                        slot = calls
                    else:
                        j = int(pick.integers(0, calls + 1))
                        slot = j if j < size else None
                    mark = not self.donates or calls % depth == 0
                    view = _view(st)
                    if self.donates and (mark or slot is not None
                                         or pending is not None):
                        view = self.keep(view)
                    if pending is not None:    # the last kept call's out
                        slots[pending]["out"] = view
                        pending = None
                    if slot is not None:
                        if slot == len(slots):
                            slots.append({"in": view})
                        else:
                            slots[slot] = {"in": view}
                        pending = slot
                    if mark:
                        handles.append(view["x"])
                    st = self.eng.sweep(st)
                    calls += 1
                if len(handles) > ahead:
                    with tracing.span("wait"):
                        handles.popleft().block_until_ready()
            with tracing.span("wait"):
                jax.block_until_ready(st)
        t1 = time.perf_counter()
        if pending is not None:
            slots[pending]["out"] = _view(st)
        return {"t0": t0, "t1": t1, "calls": calls,
                "inputs": [sl["in"] for sl in slots],
                "outputs": [sl["out"] for sl in slots],
                "trace": tracer.stop() if tracer else None}

    def compare(self, cfg: dict, got: dict) -> dict:
        """Replay the kept calls with the float32 oracle and count the
        chain-calls whose output differs from the one kept."""
        call = check.oracle(self.work, cfg, dict(self.eng.params),
                            "highest")
        return check.compare(call, got["inputs"], got["outputs"],
                             D=self.eng.graph.D,
                             block=int(self.work["check"]["block"]))


def run(ctx, control: str = None, max_calls: int = None) -> dict:
    """One run of the cell: set-up, the window, then the check.  Given
    ``control``, the oracle at that precision runs in the program's place
    (the readings of ``bench.readings``; a benchmark run never does)."""
    import jax
    work, cfg = ctx.work, ctx.config
    t = time.perf_counter()
    s = Sampler(work, cfg, control)
    st = s.start(ctx.seed)
    setup_s = time.perf_counter() - ctx.t_start
    s.stages["start_s"] = t - ctx.t_start
    got = s.window(st, ctx.seconds, ctx.trace, ctx.seed, max_calls)
    del st
    peak = system.peak_bytes()
    eng = s.eng
    rec = {"engine": work["engine"], "chains": s.chains,
           "sweep": int(work["sweep"]), "params": dict(eng.params),
           "n": eng.graph.n, "D": eng.graph.D,
           "delta": int(eng.graph.delta), "calls": got["calls"],
           "device_kind": jax.devices()[0].device_kind}
    t = time.perf_counter()
    res = s.compare(cfg, got)
    check_s = time.perf_counter() - t
    print("setup " + " ".join(f"{k}={v:.3f}" for k, v in s.stages.items())
          + f" check_s={check_s:.3f}", file=sys.stderr)
    calls, chains = rec["calls"], s.chains
    share = res["mismatched"] / max(res["chain_calls"], 1)
    limit = float(work["check"]["mismatch_share"])
    size = work["check"]["calls"]
    due = calls if size == "all" else min(calls, int(size))
    unchecked = due * chains - res["chain_calls"]
    checks = {"mismatch_share": {"value": share, "limit": limit},
              "unchecked_calls": {"value": unchecked, "limit": 0}}
    window_s = got["t1"] - got["t0"]
    return {
        "metrics": {"site_updates_per_s":
                    chains * rec["sweep"] * calls / window_s,
                    "setup_s": setup_s},
        "attempted": calls, "failed": res["bad_calls"],
        "correct": calls > 0 and share <= limit and unchecked == 0,
        "checks": checks, "memory_peak_bytes": peak,
        "trace": got["trace"], "record": rec,
        "mismatched": res["mismatched"], "chain_calls": res["chain_calls"],
    }
