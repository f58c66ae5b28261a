"""Open-loop query traffic against a ``ChainPool``.

One client sends marginal queries on a schedule fixed before the window
and never waits for an answer before the next one is due: when it falls
behind, every query due by then goes to ``ChainPool.submit`` in one
batch.  Each query asks the marginals of ``sites_per_query`` sites drawn
uniformly.  A share ``conditioned`` of the queries carries one of
``evidence_sets`` evidence sets (each ``min_observed`` to
``max_observed`` observed sites with uniform values) chosen by a Zipf law
of exponent ``zipf_s``, so the sets in use outgrow the pool's lane LRU;
the rest carry none.

Every seed offers the same work in another order: the gaps between
arrivals (exponential, mean ``1 / rate_per_s``), the split into
conditioned and plain queries and the Zipf ranks are one fixed multiset,
shuffled by the seed; the seed draws the sites asked and the evidence
sets' contents.

Latency is measured from when a query was due to when its answer came
back; a query answered other than ``ok`` counts with the latency of the
whole drain (the longest the run can see).  The run drains every query
due in the window.  The pool runs its background driver throughout.

Checks: a sample of the chunks the pool ran (its one compiled sweep
chunk, tapped where the pool calls it; ``check.calls`` of them drawn from
the seed) is replayed by the oracle and must agree chain by chain; every
``ok`` answer must equal the marginals of a snapshot the pool served.
"""
from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List

import numpy as np

from bench import check, system
from bench import trace as tracing

# the multiset of gaps, splits and ranks every seed shares
_BASE_SEED = 20180710
# submits that warm the resident lane past the freshness gate (each may
# run 64 chunks); a lane still stale after them serves stale answers
WARM_TRIES = 16


def evidence_sets(rng, p: dict, n: int, D: int) -> List[tuple]:
    """The ``evidence_sets`` evidence sets: sorted ((site, value), ...)."""
    out = []
    for _ in range(int(p["evidence_sets"])):
        k = int(rng.integers(p["min_observed"], p["max_observed"] + 1))
        sites = rng.choice(n, size=k, replace=False)
        vals = rng.integers(0, D, size=k)
        out.append(tuple(sorted((int(s), int(v))
                                for s, v in zip(sites, vals))))
    return out


def schedule(p: dict, seconds: float, seed: int, n: int, D: int
             ) -> List[Dict]:
    """The queries due in a window of ``seconds``: dicts with ``due``
    (seconds from the window's start), ``sites`` and ``evidence``."""
    count = int(round(float(p["rate_per_s"]) * seconds))
    base = np.random.default_rng(_BASE_SEED)
    gaps = base.exponential(1.0 / float(p["rate_per_s"]), size=count)
    gaps *= seconds / max(gaps.sum(), 1e-12) * count / (count + 1.0)
    n_cond = int(round(count * float(p["conditioned"])))
    sets = int(p["evidence_sets"])
    w = 1.0 / np.arange(1, sets + 1) ** float(p["zipf_s"])
    ranks = base.choice(sets, size=n_cond, p=w / w.sum())
    kinds = np.concatenate([ranks, np.full(count - n_cond, -1)])
    rng = system.rng(seed, 1)
    gaps = rng.permutation(gaps)
    kinds = rng.permutation(kinds)
    ev = evidence_sets(rng, p, n, D)
    due = np.cumsum(gaps)
    out = []
    for t, kind in zip(due, kinds):
        sites = np.sort(rng.choice(n, size=int(p["sites_per_query"]),
                                   replace=False))
        out.append({"due": float(t),
                    "sites": tuple(int(s) for s in sites),
                    "evidence": () if kind < 0 else ev[int(kind)]})
    return out


def drive(queries: List[Dict], submit: Callable, clock: Callable,
          sleep: Callable) -> List[Dict]:
    """The open-loop client: send each query when it is due, or at once
    with every other overdue query when behind.  ``submit(batch)`` returns
    one status per query.  Returns per query ``due``, ``sent`` and
    ``answered`` (seconds from the start) and ``status``."""
    t0 = clock()
    out, i = [], 0
    while i < len(queries):
        now = clock() - t0
        if queries[i]["due"] > now:
            with tracing.span("generate"):
                sleep(queries[i]["due"] - now)
            continue
        j = i
        while j < len(queries) and queries[j]["due"] <= now:
            j += 1
        with tracing.span("submit"):
            statuses = submit(queries[i:j])
        done = clock() - t0
        for q, st in zip(queries[i:j], statuses):
            out.append({"due": q["due"], "sent": now, "answered": done,
                        "status": st})
        i = j
    return out


def percentile(values, q: float) -> float:
    """The ``q``-th percentile by the nearest rank (no interpolation)."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        return float("nan")
    k = int(np.ceil(q / 100.0 * v.size)) - 1
    return float(v[min(max(k, 0), v.size - 1)])


def latencies(recs: List[Dict]) -> np.ndarray:
    """Seconds from due to answer; a query not answered ``ok`` gets the
    longest latency the run saw (the end of the drain)."""
    end = max((r["answered"] for r in recs), default=0.0)
    return np.asarray([r["answered"] - r["due"] if r["status"] == "ok"
                       else end - r["due"] for r in recs])


class ChunkTap:
    """Record, for the checks, a sample of a pool workload's chunk calls
    (``size`` of them, reservoir-sampled with a generator from the seed):
    their inputs and outputs, and the evidence that names the lane; and
    every snapshot the pool turns into answers (its ``_snap_marginals``).
    The wrapped functions are the pool's own; nothing is recompiled.
    ``close`` puts them back."""

    def __init__(self, w, size: int, seed: int):
        from repro.serving import pool as pool_mod
        self.calls, self.snaps = [], []
        self.size, self.seen = size, 0
        self.pick = system.rng(seed, 4)
        self.lock = threading.Lock()
        self.w, self.inner = w, w.chunk
        self.pool_mod = pool_mod
        self.snap_inner = pool_mod._snap_marginals
        w.chunk = self
        pool_mod._snap_marginals = self.served

    def __call__(self, st, tel, marg, count, ev_mask, ev_vals):
        out = self.inner(st, tel, marg, count, ev_mask, ev_vals)
        rec = {"in": {"x": st.x, "key": st.key, "accepts": st.accepts,
                      "marg": marg, "count": count},
               "out": {"x": out[0].x, "key": out[0].key,
                       "accepts": out[0].accepts, "marg": out[2],
                       "count": out[3]},
               "ev": (ev_mask, ev_vals)}
        with self.lock:
            if self.seen < self.size:
                self.calls.append(rec)
            else:
                j = int(self.pick.integers(0, self.seen + 1))
                if j < self.size:
                    self.calls[j] = rec
            self.seen += 1
        return out

    def served(self, snap):
        m = self.snap_inner(snap)
        with self.lock:
            self.snaps.append((snap.marg, snap.count))
        return m

    def reset(self) -> None:
        with self.lock:
            self.calls, self.snaps, self.seen = [], [], 0

    def close(self) -> None:
        self.w.chunk = self.inner
        self.pool_mod._snap_marginals = self.snap_inner


def check_answers(answers, tap: ChunkTap) -> Dict[str, int]:
    """Every ``ok`` answer must equal, at its sites, the marginals of a
    snapshot the pool served: the mean over chains and snapshots of the
    sites' one-hot values, from the snapshot's running sums."""
    refs = []
    for marg, count in tap.snaps:
        m = np.asarray(marg, np.float64)
        cnt = max(float(np.asarray(count)), 1.0)
        refs.append(m.sum(0) / (cnt * m.shape[0]))
    ok = unmatched = 0
    for a in answers:
        if a.status != "ok":
            continue
        ok += 1
        sites = np.asarray(a.query.sites, np.int64)
        unmatched += a.marginals is None or not any(
            np.array_equal(r[sites], a.marginals) for r in refs)
    return {"ok_answers": ok, "unmatched": unmatched}


class Server:
    """The cell's pool, registered and warmed, its chunk tapped and its
    background driver running: ``window`` runs one open-loop window."""

    def __init__(self, work: dict, cfg: dict, name: str, seed: int,
                 trace: bool):
        import jax
        from repro import obs
        from repro.diagnostics.freshness import FreshnessPolicy
        from repro.serving import ChainPool, Query
        self.work, self.name, self.Query = work, name, Query
        g = system.graph(cfg)
        self.n, self.D = g.n, g.D
        # the program's own spans, read by the per-layer metrics
        self.rec = obs.Recorder() if trace else None
        if self.rec is not None:
            obs.set_recorder(self.rec)
        self.pool = ChainPool(
            seed=int(system.rng(seed, 2).integers(2 ** 31)),
            policy=FreshnessPolicy(**work.get("freshness", {})))
        self.w = self.pool.register(
            name, graph=g, engine=work["engine"], backend=work["backend"],
            chains=int(work["chains"]), sweep=int(work["sweep"]),
            sweeps_per_chunk=int(work["sweeps_per_chunk"]),
            max_conditioned=int(work["max_conditioned"]),
            **work.get("params", {}))
        self.tap = ChunkTap(self.w, int(work["check"]["calls"]), seed)
        # set-up the traffic needs: the resident lane past the freshness
        # gate, one lane forked (its programs compiled), the driver on
        warm = Query(workload=name, sites=(0,))
        for _ in range(WARM_TRIES):
            if self.pool.submit([warm])[0].fresh:
                break
        self.pool.submit([Query(workload=name, sites=(0,),
                                evidence=((0, 0),))])
        self.pool.start()
        jax.block_until_ready(self.w.resident.snap.st.x)
        self.tap.reset()

    def window(self, queries: List[Dict], trace: bool) -> dict:
        answers: List = []

        def submit(batch):
            ans = self.pool.submit([self.Query(
                workload=self.name, sites=q["sites"],
                evidence=q["evidence"]) for q in batch])
            answers.extend(ans)
            return [a.status for a in ans]

        tracer = tracing.Tracer() if trace else None
        if tracer:
            tracer.start()
        span0 = self.rec.now_us() if self.rec is not None else 0.0
        t0 = time.perf_counter()
        with tracing.span("window"):
            recs = drive(queries, submit, time.perf_counter, time.sleep)
        t1 = time.perf_counter()
        span1 = self.rec.now_us() if self.rec is not None else 0.0
        return {"t0": t0, "t1": t1, "recs": recs, "answers": answers,
                "span_window_us": [span0, span1],
                "trace": tracer.stop() if tracer else None}

    def stop(self) -> None:
        import jax
        from repro import obs
        self.pool.stop()
        jax.block_until_ready(self.w.resident.snap.st.x)
        self.tap.close()
        if self.rec is not None:
            obs.set_recorder(obs.NullRecorder())

    def compare(self, cfg: dict, precision: str) -> dict:
        work = self.work
        call = check.oracle(work, cfg, dict(self.w.engine.params),
                            precision)
        return check.compare_chunks(
            call, self.tap.calls, sweeps=int(work["sweeps_per_chunk"]),
            D=self.D, block=int(work["check"]["block"]))


def run(ctx) -> dict:
    work, cfg = ctx.work, ctx.config
    srv = Server(work, cfg, ctx.entry["config"], ctx.seed, ctx.trace)
    queries = schedule(work["traffic"], ctx.seconds, ctx.seed, srv.n, srv.D)
    setup_s = time.perf_counter() - ctx.t_start
    got = srv.window(queries, ctx.trace)
    srv.stop()
    peak = system.peak_bytes()
    recs = got["recs"]
    lat = latencies(recs)
    failed = sum(r["status"] != "ok" for r in recs)
    ans = check_answers(got["answers"], srv.tap)
    res = srv.compare(cfg, "highest")
    share = res["mismatched"] / max(res["chain_calls"], 1)
    limit = float(work["check"]["mismatch_share"])
    checks = {"chunk_mismatch_share": {"value": share, "limit": limit},
              "answers_unmatched": {"value": ans["unmatched"], "limit": 0}}
    correct = (res["chain_calls"] > 0 and share <= limit
               and ans["unmatched"] == 0 and ans["ok_answers"] > 0)
    spans = srv.rec.trace.events() if srv.rec is not None else []
    return {
        "metrics": {"query_p95_ms": 1e3 * percentile(lat, 95.0),
                    "setup_s": setup_s},
        "attempted": len(recs), "failed": int(failed),
        "correct": bool(correct), "checks": checks,
        "memory_peak_bytes": peak, "trace": got["trace"],
        "record": {"lags_s": [r["sent"] - r["due"] for r in recs],
                   "answered_ok": len(recs) - int(failed), "spans": spans,
                   "span_window_us": got["span_window_us"],
                   "client_tid": threading.main_thread().ident % 2 ** 31},
    }
