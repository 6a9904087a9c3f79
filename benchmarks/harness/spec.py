"""Find a cell's files by the names ``BENCHMARK.json`` uses.

A cell (one entry of ``workloads``) names a configuration and a traffic
mix. Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found here by name:

- configuration  -> the ``file`` its ``configs`` entry gives (JSON); that
  file names its ``adapter`` (``adapters/<name>.py``, the only code that
  touches the program) and its ``reference`` (``configs/<name>.py``: the
  plain reference sits beside the configuration, ``<config>.reference.py``)
- traffic mix    -> ``traffic/<traffic>.json``; it names its ``generator``
  (``generators/<name>.py``) and the generator's parameters
- per-layer metric -> ``metrics/<name>.py`` with ``read(ctx)``

so a PR adds a cell by adding files and ``BENCHMARK.json`` entries.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def load_benchmark() -> Dict[str, Any]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """Import ``<kind>/<name>.py`` under a name that cannot collide."""
    if not NAME_RE.match(name):
        raise ValueError(f"bad {kind} name {name!r}")
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{kind} {name!r}: no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"_bench_{kind}_{name.replace('-', '_').replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _merged(base: Dict[str, Any], over: Dict[str, Any]) -> Dict[str, Any]:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merged(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


class Cell:
    """One workload of ``BENCHMARK.json`` with its files resolved."""

    def __init__(self, bench: Dict[str, Any], name: str,
                 rehearse: bool = False) -> None:
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
        w = cells[name]
        self.name = name
        self.chips = int(w["chips"])
        self.traffic_name = w["traffic"]
        entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
        self.config_name = entry["name"]
        with open(os.path.join(ROOT, entry["file"])) as f:
            self.config = json.load(f)
        self.traffic = load_json("traffic", self.traffic_name + ".json")
        if rehearse:
            # the tiny sizes a CPU can run: each file carries its own
            self.config = _merged(self.config, self.config["rehearsal"])
            self.traffic = _merged(self.traffic,
                                   self.traffic.get("rehearsal", {}))
        self.sizes = dict(self.config["sizes"])
        self.sizes.update(self.traffic.get("sizes", {}))
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]

    def adapter(self):
        return load_module("adapters", self.config["adapter"])

    def reference(self):
        return load_module("configs", self.config["reference"])

    def generator(self):
        return load_module("generators", self.traffic["generator"])


def check_contract(bench: Dict[str, Any]) -> List[str]:
    """The names, units and lengths of ``BENCHMARK.json`` against the
    allowed characters, and what this harness itself relies on: every cell
    names a configuration that is there, and every per-layer metric moves a
    metric its cells report. The driver's own check holds the rest of the
    contract; this does not repeat it. Returns the faults (empty = fine)."""
    bad: List[str] = []

    def name_ok(x, what):
        if not isinstance(x, str) or not NAME_RE.match(x):
            bad.append(f"{what}: bad name {x!r}")

    def line_ok(x, what):
        if (not isinstance(x, str) or not 1 <= len(x) <= 200
                or "\n" in x or "\t" in x):
            bad.append(f"{what}: not 1..200 characters on one line")

    for wd in bench["command"]:
        line_ok(wd, "command word")
    cfg_names = {c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        name_ok(c["name"], "config")
        line_ok(c["source"], f"config {c['name']} source")
        line_ok(c["why"], f"config {c['name']} why")
        for k in c["reduced"]:
            name_ok(k, f"config {c['name']} reduced")
    for w in bench["workloads"]:
        for k in ("name", "config", "traffic"):
            name_ok(w[k], f"workload {k}")
        line_ok(w["why"], f"workload {w['name']} why")
        if w["config"] not in cfg_names:
            bad.append(f"workload {w['name']}: unknown config {w['config']}")
    cells = [w["name"] for w in bench["workloads"]]
    metrics = bench["end_to_end"] + bench["per_layer"]
    for m in metrics:
        name_ok(m["name"], "metric")
        if not UNIT_RE.match(m["unit"]):
            bad.append(f"{m['name']}: bad unit {m['unit']!r}")
        if m["source"] not in SOURCES:
            bad.append(f"{m['name']}: source {m['source']!r}")
        if "layer" in m:
            line_ok(m["layer"], f"{m['name']} layer")
    for group in (cells, [m["name"] for m in metrics], sorted(cfg_names)):
        if len(set(group)) != len(group):
            bad.append(f"a name appears twice in {sorted(group)}")
    for cell in cells:
        reports = {m["name"] for m in bench["end_to_end"]
                   if cell in m.get("workloads", [cell])}
        for m in bench["per_layer"]:
            if cell in m.get("workloads", [cell]) and \
                    m["moves"] not in reports:
                bad.append(f"cell {cell}: {m['name']} moves {m['moves']}, "
                           "which the cell does not report")
    return bad
