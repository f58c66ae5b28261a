"""bench/cost.py against counts made by hand at potts-20x20 shapes."""
import pytest

from bench import cost

N, D, DELTA, C, S = 400, 10, 399, 256, 16
L = 5.087789212030686             # max row sum of the 20x20 Potts weights
LAM = 4 * L * L                   # the engine's mgpmh default rate


def test_distinct_rows():
    # 4096 uniform picks over 400 sites leave (399/400)^4096 of them unseen
    assert cost.distinct_rows(N, C * S) == pytest.approx(
        400 * (1 - 0.9975 ** 4096))
    assert cost.distinct_rows(N, 1) == pytest.approx(1.0)


def test_gibbs_counts_by_hand():
    got = cost.sweep_cost("gibbs", n=N, D=D, delta=DELTA, chains=C, sweep=S)
    updates = 4096
    rows = 400 * (1 - 0.9975 ** 4096)
    assert got["updates"] == updates
    assert got["flops"] == updates * 10 * 399            # D * Delta each
    w_rows = rows * 400 * 4                              # each W row once
    state = 2 * 256 * 400 * 4                            # x in and out
    noise = 4096 * (1 + 10) * 4                          # site + D Gumbel
    assert got["bytes"] == pytest.approx(w_rows + state + noise)


def test_mgpmh_counts_include_the_exact_energy_row():
    got = cost.sweep_cost("mgpmh", n=N, D=D, delta=DELTA, chains=C, sweep=S,
                          lam=LAM)
    updates = 4096
    assert got["flops"] == pytest.approx(updates * (10 * LAM + 2 * 399))
    rows = 400 * (1 - 0.9975 ** 4096)
    w_rows = rows * 400 * 4
    alias = min(4096 * LAM * 8, 2 * w_rows)    # prob + alias entry a draw
    state = 2 * 256 * 400 * 4
    noise = 4096 * (1 + 10 + 1 + 2 * LAM + 1) * 4
    assert got["bytes"] == pytest.approx(w_rows + alias + state + noise)
    plain = cost.sweep_cost("mgpmh", n=N, D=D, delta=0, chains=C, sweep=S,
                            lam=LAM)
    assert got["flops"] - plain["flops"] == pytest.approx(updates * 2 * 399)


def test_least_time_names_its_bound():
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = cost.least_seconds({"flops": 1e6, "bytes": 1e9}, peak)
    assert bound == "bytes" and t == pytest.approx(1e9 / 819e9)
    t, bound = cost.least_seconds({"flops": 1e15, "bytes": 1.0}, peak)
    assert bound == "flops" and t == pytest.approx(1e15 / 197e12)


def test_unknown_engine_is_an_error():
    with pytest.raises(ValueError):
        cost.sweep_cost("local-gibbs", n=N, D=D, delta=DELTA, chains=C,
                        sweep=S)
