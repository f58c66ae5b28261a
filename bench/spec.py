"""Load ``BENCHMARK.json`` and the files it names, and refuse a benchmark
that breaks its own rules: a bad name or unit, a cell or metric that
names something missing, or a per-layer metric listed in a cell that does
not report the end-to-end metric it moves."""
from __future__ import annotations

import importlib.util
import json
import os
import re
import sys
from typing import Any, Dict

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES_E2E = ("host_clock", "device_trace")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


class SpecError(ValueError):
    """``BENCHMARK.json`` or a file it names breaks the benchmark's
    rules."""


def _line(text: Any, what: str) -> None:
    if not isinstance(text, str) or not 1 <= len(text) <= 200 \
            or "\n" in text or "\t" in text:
        raise SpecError(f"{what}: 1 to 200 characters on one line")


def _name(text: Any, what: str) -> None:
    if not isinstance(text, str) or not NAME.match(text):
        raise SpecError(f"{what} {text!r} is not a name (letters, digits, "
                        f"'_', '.', '-'; at most 64)")


def _keys(entry: dict, required: set, optional: set, what: str) -> None:
    have = set(entry)
    if not required <= have or not have <= required | optional:
        raise SpecError(f"{what} has keys {sorted(have)}; expected "
                        f"{sorted(required)} (+ {sorted(optional)})")


def reports(metric: dict, cell: str) -> bool:
    """Whether ``cell`` reports the end-to-end ``metric``."""
    return "workloads" not in metric or cell in metric["workloads"]


def validate(spec: dict) -> None:
    """Raise :class:`SpecError` where ``spec`` breaks a rule."""
    if set(spec) != TOP_KEYS:
        raise SpecError(f"top-level keys {sorted(spec)} != "
                        f"{sorted(TOP_KEYS)}")
    cmd = spec["command"]
    if not (isinstance(cmd, list) and 1 <= len(cmd) <= 32):
        raise SpecError("command is a list of 1 to 32 strings")
    for word in cmd:
        _line(word, "command word")
        if word.startswith("/") or ".." in word.split("/"):
            raise SpecError(f"command word {word!r} leaves the checkout")
    paths = spec["paths"]
    if not (isinstance(paths, list) and 1 <= len(paths) <= 16):
        raise SpecError("paths holds 1 to 16 directories")
    for p in paths:
        if not isinstance(p, str) or not PATH.match(p) or p.startswith("/") \
                or ".." in p.split("/"):
            raise SpecError(f"path {p!r} is not a relative directory")
    rs = spec["run_seconds"]
    if not (isinstance(rs, int) and 1 <= rs <= 51):
        raise SpecError("run_seconds is a whole number from 1 to 51")

    configs = spec["configs"]
    if not 1 <= len(configs) <= 24:
        raise SpecError("1 to 24 configs")
    for c in configs:
        _keys(c, {"name", "source", "file", "reduced", "why"}, set(),
              "config")
        _name(c["name"], "config name")
        _line(c["source"], f"config {c['name']} source")
        _line(c["why"], f"config {c['name']} why")
        if len(c["reduced"]) > 16:
            raise SpecError(f"config {c['name']}: at most 16 reduced keys")
        for k in c["reduced"]:
            _name(k, "reduced key")
        if not any(c["file"].startswith(p.rstrip("/") + "/")
                   for p in paths):
            raise SpecError(f"config file {c['file']} is not under paths")

    cells = spec["workloads"]
    if not 1 <= len(cells) <= 24:
        raise SpecError("1 to 24 workloads")
    cfg_names = [c["name"] for c in configs]
    pairs = set()
    for w in cells:
        _keys(w, {"name", "config", "traffic", "chips", "why"}, set(),
              "workload")
        _name(w["name"], "workload name")
        _name(w["traffic"], "traffic")
        _line(w["why"], f"workload {w['name']} why")
        if w["config"] not in cfg_names:
            raise SpecError(f"workload {w['name']} names an unknown config "
                            f"{w['config']!r}")
        if w["chips"] not in (1, 4):
            raise SpecError(f"workload {w['name']}: chips is 1 or 4")
        pair = (w["config"], w["traffic"])
        if pair in pairs:
            raise SpecError(f"config and traffic {pair} appear twice")
        pairs.add(pair)
    cell_names = [w["name"] for w in cells]
    for c in cfg_names:
        if not any(w["config"] == c for w in cells):
            raise SpecError(f"config {c} is used by no workload")

    e2e, layer = spec["end_to_end"], spec["per_layer"]
    if not 1 <= len(e2e) <= 16 or not 1 <= len(layer) <= 128:
        raise SpecError("1 to 16 end-to-end and 1 to 128 per-layer metrics")
    for m in e2e:
        _keys(m, {"name", "unit", "better", "bound", "source"},
              {"workloads"}, "end-to-end metric")
        if m["source"] not in SOURCES_E2E:
            raise SpecError(f"{m['name']}: end-to-end source is one of "
                            f"{SOURCES_E2E}")
        if not (isinstance(m["bound"], (int, float))
                and 0.01 <= m["bound"] <= 0.25):
            raise SpecError(f"{m['name']}: bound lies in [0.01, 0.25]")
    for m in layer:
        _keys(m, {"name", "unit", "better", "source", "layer", "moves"},
              {"workloads"}, "per-layer metric")
        if m["source"] not in SOURCES:
            raise SpecError(f"{m['name']}: source is one of {SOURCES}")
        _line(m["layer"], f"{m['name']} layer")
    by_name = {}
    for m in e2e + layer:
        _name(m["name"], "metric name")
        if not isinstance(m["unit"], str) or not UNIT.match(m["unit"]):
            raise SpecError(f"{m['name']}: unit {m['unit']!r} is not 1 to "
                            f"16 of letters, digits, _ / % . -")
        if m["better"] not in ("lower", "higher"):
            raise SpecError(f"{m['name']}: better is lower or higher")
        for c in m.get("workloads", []):
            if c not in cell_names:
                raise SpecError(f"{m['name']} lists unknown workload {c!r}")
        if m["name"] in by_name:
            raise SpecError(f"metric {m['name']} appears twice")
        by_name[m["name"]] = m
    for dup in (cfg_names, cell_names):
        if len(set(dup)) != len(dup):
            raise SpecError("two configs or two workloads share a name")
    e2e_names = {m["name"]: m for m in e2e}
    for m in layer:
        moves = e2e_names.get(m["moves"])
        if moves is None:
            raise SpecError(f"{m['name']} moves {m['moves']!r}, which is "
                            f"no end-to-end metric")
        for c in m.get("workloads", cell_names):
            if not reports(moves, c):
                raise SpecError(f"{m['name']} is read in {c}, which does "
                                f"not report {m['moves']}")
    for c in cell_names:
        mine = [m["name"] for m in e2e if reports(m, c)]
        if len(mine) < 2:
            raise SpecError(f"workload {c} reports fewer than two "
                            f"end-to-end metrics")
        if not any(reports(m, c) for m in layer):
            raise SpecError(f"workload {c} reports no per-layer metric")
    if len(json.dumps(spec)) > 64 * 1024:
        raise SpecError("BENCHMARK.json is over 64 KiB")


def root() -> str:
    """The checkout: the directory that holds this package."""
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read(path: str) -> Any:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise SpecError(f"cannot read {path}: {e}") from e


def load(top: str = None) -> dict:
    """``BENCHMARK.json`` of the checkout ``top``, validated, with every
    file it names present."""
    top = top or root()
    spec = _read(os.path.join(top, "BENCHMARK.json"))
    validate(spec)
    here = os.path.dirname(os.path.abspath(__file__))
    for c in spec["configs"]:
        if not os.path.isfile(os.path.join(top, c["file"])):
            raise SpecError(f"config file {c['file']} is missing")
    for w in spec["workloads"]:
        if not os.path.isfile(os.path.join(here, "workloads",
                                           w["name"] + ".json")):
            raise SpecError(f"workload {w['name']} has no file")
    for m in spec["per_layer"]:
        if not os.path.isfile(os.path.join(here, "metrics",
                                           m["name"] + ".py")):
            raise SpecError(f"per-layer metric {m['name']} has no reader")
    return spec


def cell(spec: dict, name: str, top: str = None) -> Dict[str, Any]:
    """Everything one cell runs from: its entry in ``BENCHMARK.json``, its
    workload file and its configuration file."""
    top = top or root()
    entry = next((w for w in spec["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SpecError(f"unknown workload {name!r}; have "
                        f"{[w['name'] for w in spec['workloads']]}")
    cfg_entry = next(c for c in spec["configs"]
                     if c["name"] == entry["config"])
    here = os.path.dirname(os.path.abspath(__file__))
    work = _read(os.path.join(here, "workloads", name + ".json"))
    cfg = _read(os.path.join(top, cfg_entry["file"]))
    if work.get("config") != entry["config"] \
            or work.get("traffic", {}).get("name") != entry["traffic"]:
        raise SpecError(f"workload file of {name} disagrees with "
                        f"BENCHMARK.json on its config or traffic")
    return {"entry": entry, "work": work, "config": cfg,
            "end_to_end": [m for m in spec["end_to_end"]
                           if reports(m, name)],
            "per_layer": [m for m in spec["per_layer"]
                          if name in m.get("workloads", [name])
                          and reports(next(e for e in spec["end_to_end"]
                                           if e["name"] == m["moves"]),
                                      name)]}



def plugin(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``, loaded by its path (names may
    hold dots and dashes): a traffic kind, a per-layer metric reader, an
    oracle, or a configuration's plain reference."""
    here = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(here, kind, name + ".py")
    if not os.path.isfile(path):
        raise SpecError(f"no {kind} module {name!r} ({path})")
    mod_name = f"bench_{kind}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}"
    mod = sys.modules.get(mod_name)
    if mod is None:
        loader = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(loader)
        sys.modules[mod_name] = mod
        loader.loader.exec_module(mod)
    return mod
