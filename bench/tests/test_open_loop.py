"""The open-loop generator and client on a fake clock: due times, the
Zipf choice of evidence sets, lag and latency accounting."""
import numpy as np
import pytest

from bench.spec import plugin

ol = plugin("traffic", "open_loop")

P = {"rate_per_s": 50.0, "sites_per_query": 8, "conditioned": 0.5,
     "evidence_sets": 32, "min_observed": 1, "max_observed": 4,
     "zipf_s": 1.1}


def test_schedule_fixes_the_work_and_shuffles_it_by_seed():
    a = ol.schedule(P, 20.0, 1, 400, 10)
    b = ol.schedule(P, 20.0, 2 ** 31 + 7, 400, 10)
    assert len(a) == len(b) == 1000                 # rate x seconds
    dues = np.asarray([q["due"] for q in a])
    assert np.all(np.diff(dues) > 0) and dues[-1] < 20.0
    gaps = lambda s: np.sort(np.diff([0.0] + [q["due"] for q in s]))
    np.testing.assert_allclose(gaps(a), gaps(b))    # same gaps, reordered
    assert [q["due"] for q in a] != [q["due"] for q in b]
    plain = lambda s: sum(q["evidence"] == () for q in s)
    assert plain(a) == plain(b) == 500
    assert a == ol.schedule(P, 20.0, 1, 400, 10)    # the seed repeats
    for q in a:
        assert len(set(q["sites"])) == 8
        assert all(0 <= s < 400 for s in q["sites"])
        assert 0 <= len(q["evidence"]) <= 4
        assert all(0 <= v < 10 for _, v in q["evidence"])


def test_evidence_sets_follow_zipf():
    a = ol.schedule(dict(P, rate_per_s=500.0), 20.0, 5, 400, 10)
    counts = {}
    for q in a:
        if q["evidence"]:
            counts[q["evidence"]] = counts.get(q["evidence"], 0) + 1
    ranked = sorted(counts.values(), reverse=True)
    w = 1.0 / np.arange(1, 33) ** 1.1
    expect = 5000 * w / w.sum()
    assert len(counts) >= 30
    assert ranked[0] == pytest.approx(expect[0], rel=0.1)
    assert ranked[9] == pytest.approx(expect[9], rel=0.35)


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += max(s, 0.0)


def test_drive_batches_overdue_queries_and_counts_lag():
    clock = FakeClock()
    queries = [{"due": d, "sites": (0,), "evidence": ()}
               for d in (0.0, 0.1, 0.15, 0.2, 1.0)]
    sizes = []

    def submit(batch):
        sizes.append(len(batch))
        clock.t += 0.3            # each submit takes 0.3 s
        return ["ok"] * (len(batch) - 1) + ["shed"]

    recs = ol.drive(queries, submit, clock, clock.sleep)
    assert sizes == [1, 3, 1]     # 0.1, 0.15, 0.2 were due by t=0.3
    lags = [r["sent"] - r["due"] for r in recs]
    np.testing.assert_allclose(lags, [0.0, 0.2, 0.15, 0.1, 0.0])
    np.testing.assert_allclose([r["answered"] for r in recs],
                               [0.3, 0.6, 0.6, 0.6, 1.3])
    lat = ol.latencies(recs)
    # the shed ones count with the whole drain's latency (end 1.3 s)
    np.testing.assert_allclose(lat, [1.3, 0.5, 0.45, 1.1, 0.3])
    assert ol.percentile(lat, 95) == pytest.approx(1.3)
    assert ol.percentile([1, 2, 3, 4], 50) == 2


def test_lag_reader_takes_the_95th_percentile():
    reader = plugin("metrics", "generator_lag_ms.serve")
    lags = list(np.arange(100) / 1000.0)
    assert reader.read({"lags_s": lags}, None) == pytest.approx(94.0)
    assert reader.read({"lags_s": []}, None) is None
