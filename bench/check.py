"""The comparison that decides ``correct`` for sweep calls: each kept
call of the window is replayed by the plain oracle of its engine and
path, from the call's own input state, and each chain's output (sites,
accept count, key; for a pool chunk also the running marginal sums) must
equal the program's.

``precision`` picks what the oracle's table operands keep: ``"highest"``
is float32, what the configurations state (every product of a weight
with a one-hot exact, as the program's ``Precision.HIGHEST`` products
are); ``"high"`` rounds W, the alias probabilities and the alias indices
as a three-pass bfloat16 product (``Precision.HIGH``) does, the control
put in the program's place (``bench.readings``); ``"bfloat16"`` rounds
them as one bfloat16 pass does, a reading of how a coarser product shows.
"""
from __future__ import annotations

from typing import Dict, List

import ml_dtypes
import numpy as np

from bench.spec import plugin


def high(a) -> np.ndarray:
    """``a`` as a bfloat16 high part plus a bfloat16 low part, the operand
    a three-pass bf16 matrix product sees (about 16 mantissa bits)."""
    a = np.asarray(a, np.float32)
    hi = a.astype(ml_dtypes.bfloat16).astype(np.float32)
    lo = (a - hi).astype(ml_dtypes.bfloat16).astype(np.float32)
    return hi + lo


def bfloat16(a) -> np.ndarray:
    """``a`` rounded to bfloat16, the operand of one bf16 pass."""
    return np.asarray(a, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


TABLES = {"highest": lambda a: np.asarray(a), "high": high,
          "bfloat16": bfloat16}


def oracle(work: dict, config: dict, params: dict, precision: str):
    """The replay function of one call of the workload's engine on its
    path, over the configuration's plain reference graph."""
    orc = plugin("oracle", f"{work['engine']}_{work['backend']}")
    ref = plugin("configs", config["reference"]["family"])
    g = ref.build(config["reference"],
                  tables_from=getattr(orc, "TABLES_FROM", "float64"))
    return orc.make(g, params, int(work["sweep"]), TABLES[precision])


def compare(call, inputs: List[dict], outputs: List[dict], *, D: int,
            block: int) -> Dict[str, int]:
    """Replay ``call`` on every input state, ``block`` calls at a time on
    the device, against the matching program outputs.

    Returns counts: ``chain_calls`` compared, ``mismatched`` chain-calls
    (any site, the accept count or the key differs) and ``bad_calls``
    whose program output holds a site value outside [0, D)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def judge(inp, out):
        ref = jax.vmap(lambda s: call(s, None))(inp)
        same = jnp.all(ref["x"] == out["x"], axis=-1)
        same &= ref["accepts"] == out["accepts"]
        key_same = jnp.all(ref["key"] == out["key"], axis=-1)
        same &= key_same if key_same.shape == same.shape \
            else jnp.all(key_same, axis=-1, keepdims=True)
        bad = jnp.any((out["x"] < 0) | (out["x"] >= D), axis=(1, 2))
        return jnp.sum(~same, axis=1), bad

    n = len(inputs)
    mism = np.zeros(n, np.int64)
    bad = np.zeros(n, bool)
    for b in range(0, n, block):
        idx = [min(k, n - 1) for k in range(b, b + block)]  # pad the tail
        stack = lambda seq: {f: jnp.stack([seq[k][f] for k in idx])
                             for f in ("x", "key", "accepts")}
        m, bd = judge(stack(inputs), stack(outputs))
        take = min(block, n - b)
        mism[b:b + take] = np.asarray(m)[:take]
        bad[b:b + take] = np.asarray(bd)[:take]
    chains = int(inputs[0]["x"].shape[0]) if n else 0
    return {"chain_calls": n * chains, "mismatched": int(mism.sum()),
            "bad_calls": int(bad.sum())}


def _chunk(call, sweeps: int, D: int):
    """One pool chunk replayed: ``sweeps`` oracle sweeps under evidence,
    each followed by the running one-hot sums and their count."""
    import jax
    import jax.numpy as jnp

    def chunk(i, em, ev):
        def body(carry, _):
            s, marg, count = carry
            s = call(s, (em, ev))
            marg = marg + jax.nn.one_hot(s["x"], D, dtype=jnp.float32)
            return (s, marg, count + 1.0), None
        s0 = {"x": i["x"], "key": i["key"], "accepts": i["accepts"]}
        (s, marg, count), _ = jax.lax.scan(
            body, (s0, i["marg"], i["count"]), None, length=sweeps)
        return dict(s, marg=marg, count=count)
    return chunk


_CHUNK_FIELDS = ("x", "key", "accepts", "marg", "count")


def _chunk_blocks(calls, block, side):
    import jax.numpy as jnp
    n = len(calls)
    for b in range(0, n, block):
        idx = [min(k, n - 1) for k in range(b, b + block)]
        yield b, min(block, n - b), (
            {f: jnp.stack([calls[k][side][f] for k in idx])
             for f in _CHUNK_FIELDS},
            jnp.stack([calls[k]["ev"][0] for k in idx]),
            jnp.stack([calls[k]["ev"][1] for k in idx]))


def compare_chunks(call, calls: List[dict], *, sweeps: int, D: int,
                   block: int) -> Dict[str, int]:
    """Replay a serving pool's chunk calls: ``sweeps`` oracle sweeps under
    the call's evidence, each followed by adding the chains' one-hot
    sites to the running marginal sums and one to their count.  A
    chain-call matches when its sites, accept count, key and marginal sums
    equal the program's, and the count does."""
    import jax
    import jax.numpy as jnp
    chunk = _chunk(call, sweeps, D)

    def one(i, o, em, ev):
        r = chunk(i, em, ev)
        same = jnp.all(r["x"] == o["x"], axis=-1)
        same &= r["accepts"] == o["accepts"]
        same &= jnp.all(r["key"] == o["key"], axis=-1)
        same &= jnp.all(r["marg"] == o["marg"], axis=(1, 2))
        same &= r["count"] == o["count"]
        bad = jnp.any((o["x"] < 0) | (o["x"] >= D))
        return jnp.sum(~same), bad

    judge = jax.jit(jax.vmap(one))
    n = len(calls)
    mism = np.zeros(n, np.int64)
    bad = np.zeros(n, bool)
    for (b, take, (inp, em, ev)), (_, _, (out, _, _)) in zip(
            _chunk_blocks(calls, block, "in"),
            _chunk_blocks(calls, block, "out")):
        m, bd = judge(inp, out, em, ev)
        mism[b:b + take] = np.asarray(m)[:take]
        bad[b:b + take] = np.asarray(bd)[:take]
    chains = int(calls[0]["in"]["x"].shape[0]) if n else 0
    return {"chain_calls": n * chains, "mismatched": int(mism.sum()),
            "bad_calls": int(bad.sum())}


def replay(call, inputs: List[dict], *, block: int) -> List[dict]:
    """The oracle's own outputs on ``inputs`` (a control in the program's
    place), ``block`` calls at a time."""
    import jax
    import jax.numpy as jnp
    run = jax.jit(jax.vmap(lambda s: call(s, None)))
    n, outs = len(inputs), []
    for b in range(0, n, block):
        idx = [min(k, n - 1) for k in range(b, b + block)]
        r = run({f: jnp.stack([inputs[k][f] for k in idx])
                 for f in ("x", "key", "accepts")})
        for j in range(min(block, n - b)):
            outs.append({f: r[f][j] for f in ("x", "key", "accepts")})
    return outs
