"""The loader of BENCHMARK.json refuses what the benchmark's rules
refuse, and accepts the committed file."""
import copy
import json
import os

import pytest

from bench import spec

TOP = spec.root()


def committed():
    with open(os.path.join(TOP, "BENCHMARK.json")) as f:
        return json.load(f)


def test_committed_benchmark_loads():
    s = spec.load(TOP)
    for w in s["workloads"]:
        c = spec.cell(s, w["name"], TOP)
        assert c["work"]["config"] == w["config"]
        assert c["per_layer"], w["name"]


@pytest.mark.parametrize("where, value", [
    (("workloads", 0, "name"), "has space"),
    (("workloads", 0, "name"), "a/b"),
    (("configs", 0, "name"), "x" * 65),
    (("end_to_end", 0, "name"), "rate,p95"),
    (("end_to_end", 0, "unit"), "updates per s"),
    (("end_to_end", 0, "unit"), "µs"),
    (("per_layer", 0, "unit"), ""),
    (("per_layer", 0, "better"), "up"),
    (("end_to_end", 0, "bound"), 0.3),
])
def test_refuses_bad_names_and_units(where, value):
    s = committed()
    node = s
    for k in where[:-1]:
        node = node[k]
    node[where[-1]] = value
    with pytest.raises(spec.SpecError):
        spec.validate(s)


def test_refuses_layer_metric_whose_cells_do_not_report_its_moves():
    s = committed()
    e2e = {m["name"]: m for m in s["end_to_end"]}
    m = next(m for m in s["per_layer"] if "workloads" in e2e[m["moves"]])
    outside = [w["name"] for w in s["workloads"]
               if w["name"] not in e2e[m["moves"]]["workloads"]]
    bad = copy.deepcopy(s)
    if outside:
        target = next(x for x in bad["per_layer"] if x["name"] == m["name"])
        target["workloads"] = target["workloads"] + outside[:1]
    else:   # every cell reports it: narrow the end-to-end metric instead
        mv = next(x for x in bad["end_to_end"] if x["name"] == m["moves"])
        mv["workloads"] = mv["workloads"][1:]
    with pytest.raises(spec.SpecError, match="does not report"):
        spec.validate(bad)
    unlisted = copy.deepcopy(s)
    target = next(x for x in unlisted["per_layer"] if x["name"] == m["name"])
    target.pop("workloads", None)
    if outside:   # without the key it is read in every cell
        with pytest.raises(spec.SpecError, match="does not report"):
            spec.validate(unlisted)


def test_refuses_unknown_references():
    s = committed()
    s["workloads"][0]["config"] = "no-such-config"
    with pytest.raises(spec.SpecError):
        spec.validate(s)
    s = committed()
    s["per_layer"][0]["moves"] = "no_such_metric"
    with pytest.raises(spec.SpecError):
        spec.validate(s)
    s = committed()
    s["per_layer"][0]["workloads"] = ["no-such-cell"]
    with pytest.raises(spec.SpecError):
        spec.validate(s)


def test_refuses_repeated_config_and_traffic():
    s = committed()
    s["workloads"].append(dict(s["workloads"][0], name="copy-cell"))
    with pytest.raises(spec.SpecError, match="appear twice"):
        spec.validate(s)


def test_every_cell_and_metric_has_its_file():
    s = committed()
    for w in s["workloads"]:
        assert os.path.isfile(os.path.join(TOP, "bench", "workloads",
                                           w["name"] + ".json"))
    for m in s["per_layer"]:
        mod = spec.plugin("metrics", m["name"])
        assert callable(mod.read)
    for c in s["configs"]:
        with open(os.path.join(TOP, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        spec.plugin("configs", cfg["reference"]["family"])
