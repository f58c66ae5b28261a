"""The control is caught: the oracle put in the program's place and
computed at ``Precision.HIGH`` (three bfloat16 passes, the step below the
float32 products the configurations state) differs from the float32
oracle wherever its rounding changes a draw, and the comparison flags
that chain-call.  At a test's size such a call is rare (about one
chain-call in 10^5, PERF.md), so the inputs are planted: chain states and
keys of the cells' own graphs and engines, drawn from a trajectory from
the cells' start, at which the two oracles were seen to differ on a CPU
(``data/high_ties.json``).  The dist cell's oracle runs on the
potts-20x20 graph, which a test run holds.

One bfloat16 pass, coarser still, fails each cell's limit on the cell's
own chains over a few calls of a plain trajectory."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, spec, system

CELLS = ("potts20-mgpmh-offline", "potts20-gibbs-offline",
         "potts64-mgpmh-dist")
TIES = os.path.join(os.path.dirname(__file__), "data", "high_ties.json")


def cell(name):
    """The cell, run on the potts-20x20 configuration."""
    c = spec.cell(spec.load(), name)
    return dict(c, config=spec.cell(spec.load(),
                                    "potts20-mgpmh-offline")["config"])


def params_of(c):
    """The engine parameters the program resolves for the cell: mgpmh's
    rate 4 L^2 and its draw capacity."""
    ref = spec.plugin("configs", c["config"]["reference"]["family"])
    if c["work"]["engine"] != "mgpmh":
        return {}
    from repro.core.estimators import recommended_capacity
    r = c["config"]["reference"]
    L = float(ref.interactions(r["grid"], r["beta"], r["gamma"])
              .sum(1).max())
    lam = 4.0 * L * L
    cap = recommended_capacity(lam)
    if c["work"]["backend"] == "dist":
        cap += 8           # the dist path's worst-shard slack on one shard
    return {"lam": lam, "capacity": cap}


def oracles(c):
    p = params_of(c)
    return (check.oracle(c["work"], c["config"], p, "highest"),
            lambda prec: check.oracle(c["work"], c["config"], p, prec))


def planted(name):
    """The planted calls of ``name``: one chain each on the pallas path,
    the whole batch under its one key on the dist path."""
    with open(TIES) as f:
        cases = json.load(f)[name]
    out = []
    for case in cases:
        x = np.array([[int(ch) for ch in row] for row in case["x"]],
                     np.int32)
        out.append({"x": jnp.asarray(x),
                    "key": jnp.asarray(np.array([case["key"]], np.uint32)),
                    "accepts": jnp.zeros((x.shape[0],), jnp.int32)})
    return out


@pytest.mark.parametrize("name", CELLS)
def test_high_control_is_caught(name):
    c = cell(name)
    ref, at = oracles(c)
    inputs = planted(name)
    assert inputs, name
    ctrl = check.replay(at("high"), inputs, block=1)
    got = check.compare(ref, inputs, ctrl, D=10, block=1)
    assert got["mismatched"] >= len(inputs), (name, got)
    # the float32 oracle in the program's place reads sound on them
    same = check.replay(at("highest"), inputs, block=1)
    assert check.compare(ref, inputs, same, D=10, block=1)[
        "mismatched"] == 0


def trajectory(call, n, chains, calls, seed, dist):
    """``calls`` input states of the oracle's own chain from the cells'
    start (every site 0); the dist path keeps one key for all chains."""
    key = system.key(seed)
    st = {"x": jnp.zeros((chains, n), jnp.int32),
          "accepts": jnp.zeros((chains,), jnp.int32),
          "key": key.reshape(1, 2) if dist
          else jax.random.split(key, chains)}
    out = []
    step = jax.jit(lambda s: call(s, None))
    for _ in range(calls):
        out.append(st)
        st = step(st)
    return out


@pytest.mark.parametrize("name, calls", [
    ("potts20-mgpmh-offline", 2), ("potts20-gibbs-offline", 12),
    ("potts64-mgpmh-dist", 4)])
def test_one_bfloat16_pass_fails_offline_limits(name, calls):
    c = cell(name)
    work = c["work"]
    ref, at = oracles(c)
    n = c["config"]["reference"]["grid"] ** 2
    inputs = trajectory(ref, n, int(work["chains"]), calls, 7,
                        work["backend"] == "dist")
    block = 4
    ctrl = check.replay(at("bfloat16"), inputs, block=block)
    got = check.compare(ref, inputs, ctrl, D=10, block=block)
    share = got["mismatched"] / got["chain_calls"]
    assert share > float(work["check"]["mismatch_share"]), (name, got)
