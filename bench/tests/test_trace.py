"""The trace reducer on a small recorded chip trace and on a hand-made
one: idle share, device time per program, gap attribution, and the
per-layer readers on top."""
import json
import os

import pytest

from bench import trace as T
from bench.spec import plugin

HERE = os.path.dirname(os.path.abspath(__file__))


def recorded():
    with open(os.path.join(HERE, "data", "trace_mgpmh_pallas.json")) as f:
        return json.load(f)


def test_hand_made_trace():
    ev = {"host": [["window", 0, 100], ["dispatch", 0, 30],
                   ["dispatch", 50, 60], ["wait", 60, 100]],
          "devices": [{"modules": [["jit_a(1)", 10, 40], ["jit_b(2)", 70, 80]],
                       "ops": [["%x.1 = f32[] add()", 10, 20],
                               ["%y.2 = f32[] mul()", 15, 40],
                               ["%z.3 = f32[] fusion()", 70, 80]],
                       "async": [["%c = copy-start()", 35, 45]]}]}
    r = T.reduce(ev)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(45e-9)     # [10, 45] and [70, 80]
    # idle: [0,10] in a dispatch; [45,50] under no span; [50,60] in a
    # dispatch and [60,70] in the wait; [80,100] in the wait
    assert r["idle_by_span"] == pytest.approx(
        {"dispatch": 20e-9, T.NO_SPAN: 5e-9, "wait": 30e-9})
    assert r["modules"] == pytest.approx({"jit_a": 30e-9, "jit_b": 10e-9})
    assert r["ops"][0][:2] == ["jit_a", "y.2"]
    assert {tuple(o[:2]) for o in r["ops"]} == {
        ("jit_a", "x.1"), ("jit_a", "y.2"), ("jit_b", "z.3")}
    assert r["breakdown"]["idle_gaps"][0][0] == "wait"


def union_length(iv):
    total, end = 0.0, float("-inf")
    for a, b in sorted(iv):
        if b > end:
            total += b - max(a, end)
            end = b
    return total


def test_recorded_chip_trace():
    ev = recorded()
    r = T.reduce(ev)
    d = ev["devices"][0]
    w1 = ev["host"][0][2]
    iv = [(max(a, 0.0), min(b, w1)) for _, a, b in d["ops"] + d["async"]
          if b > 0 and a < w1]
    assert r["window_s"] == pytest.approx(60e-3)
    assert r["busy_s"] == pytest.approx(union_length(iv) * 1e-9)
    # host-bound: the device waits on the eager draws' dispatch
    idle = 1 - r["busy_s"] / r["window_s"]
    assert 0.5 < idle < 0.9
    assert set(r["idle_by_span"]) <= {"dispatch", "wait", T.NO_SPAN}
    assert max(r["idle_by_span"], key=r["idle_by_span"].get) == "dispatch"
    assert max(r["modules"], key=r["modules"].get) == "jit_mgpmh_sweep"
    kernel = [o for o in r["ops"] if o[1].startswith("mgpmh_sweep_pallas")]
    assert kernel and kernel[0][0] == "jit_mgpmh_sweep"
    assert len(r["breakdown"]["device_ops"]) <= 10
    assert json.loads(json.dumps(r["breakdown"])) == r["breakdown"]


def test_readers_on_the_recorded_trace():
    r = T.reduce(recorded())
    rec = {"engine": "mgpmh", "chains": 256, "sweep": 16, "n": 400, "D": 10,
           "delta": 399, "calls": r["module_runs"]["jit_mgpmh_sweep"],
           "params": {"lam": 103.54239626422333, "capacity": 201},
           "device_kind": "TPU v5 lite"}
    share = plugin("metrics", "mgpmh_kernel_roofline").read(rec, r)
    assert 0 < share < 1          # latency-bound: far below the roofline
    assert plugin("metrics", "gibbs_kernel_roofline").read(rec, r) \
        is None                   # no gibbs kernel ran: nothing to read
    draws = plugin("metrics", "draws_device_ms.pallas").read(rec, r)
    kernel_ms = 1e3 * r["modules"]["jit_mgpmh_sweep"] / rec["calls"]
    assert 0 < draws < kernel_ms
    idle = plugin("metrics", "device_idle_share.offline").read(rec, r)
    assert idle == pytest.approx(100 * (1 - r["busy_s"] / r["window_s"]))
    with pytest.raises(KeyError):
        plugin("metrics", "mgpmh_kernel_roofline").read(
            dict(rec, device_kind="TPU v9"), r)


def test_one_window_span_is_required():
    ev = recorded()
    ev["host"] = [h for h in ev["host"] if h[0] != "window"]
    with pytest.raises(ValueError):
        T.reduce(ev)
